"""The yardstick's own checks: ``python -m pytest benchmark/tests -q`` (by hand;
not part of the repo's ``tests/``).  No device is needed: the trace reduction
runs on a hand-built event list, the rest reads files."""

import importlib.util
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")  # what PR 22 was refused for
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location("bench_" + "_".join(parts)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = load_json(ROOT, "BENCHMARK.json")
xplane = load("xplane.py")


# ---- the trace reduction on a hand-built trace --------------------------------


def hand_trace():
    """Two steps of 1.0 s on the profiler's clock, window [10, 12].  Device 0
    runs 0.2 s of ``grad`` and 0.1 s of ``apply`` a step; ``grad`` is a while
    loop whose body covers 0.15 s of it.  Device 1 is busy 0.5 s in all."""
    ops0, mods0, host = [], [], []
    for t in (10.0, 11.0):
        host += [("bench.step.call", t, t + 0.6), ("bench.step.block", t + 0.6, t + 1.0),
                 ("bench.other", t, t + 2.0)]
        ops0 += [("while.1", t + 0.1, t + 0.3), ("fusion.7", t + 0.1, t + 0.25),
                 ("all-reduce.3", t + 0.7, t + 0.8)]
        mods0 += [("jit_grad(123)", t + 0.1, t + 0.3), ("jit_apply(77)", t + 0.7, t + 0.8)]
    ops0.append(("fusion.7", 9.0, 9.5))  # before the window: not counted
    return {"host": host,
            "devices": {0: {"ops": ops0, "modules": mods0},
                        1: {"ops": [("fusion.7", 10.5, 11.0)], "modules": []}}}


def test_busy_union_and_idle_share():
    r = xplane.reduce(hand_trace())
    assert r["steps"] == 2
    assert r["window_s"] == pytest.approx(2.0)
    assert r["busy0_s"] == pytest.approx(0.6)  # nested fusion.7 not counted twice
    assert r["busy_s"] == pytest.approx((0.6 + 0.5) / 2)  # mean over the devices
    reader = load("readers", "trace.py")
    run = {"trace": r}
    assert reader.read(run, quantity="busy_ms") == pytest.approx(300.0)
    assert reader.read(run, quantity="idle_share") == pytest.approx(70.0)
    assert reader.read(run, quantity="op_ms", match="all-reduce") == pytest.approx(100.0)
    assert reader.read(run, quantity="op_ms", match="no-such-op") is None
    assert reader.read({"trace": None}, quantity="busy_ms") is None


def test_self_time_charges_a_parent_only_for_what_its_children_leave():
    r = xplane.reduce(hand_trace())
    ops = dict(map(tuple, r["device_ops"]))
    assert ops["fusion.7"] == pytest.approx(0.30)
    assert ops["while.1"] == pytest.approx(0.10)
    assert ops["all-reduce.3"] == pytest.approx(0.20)
    assert len(r["device_ops"]) <= 10


def test_gaps_are_named_by_annotation_and_neighbouring_programs():
    gaps = dict(map(tuple, xplane.reduce(hand_trace())["idle_gaps"]))
    assert gaps["bench.step.call:jit_grad_-_jit_apply"] == pytest.approx(0.8)
    assert gaps["bench.step.call:window_start_-_jit_grad"] == pytest.approx(0.1)
    assert gaps["bench.step.block:jit_apply_-_jit_grad"] == pytest.approx(0.3)
    assert gaps["bench.step.block:jit_apply_-_window_end"] == pytest.approx(0.2)
    assert sum(gaps.values()) == pytest.approx(2.0 - 0.6)


def test_a_gap_is_named_by_the_innermost_program_phase_that_covers_it():
    """The hand trace with the program's phases beside the harness's: the
    caller waits in ``bps.hybrid.hop_wait`` from t+0.3 to t+0.7 inside
    ``bps.hybrid.step``, a stage thread serves ``bps.stage.PUSH`` from t+0.4
    to t+0.6.  The long gap (t+0.3 .. t+0.7, middle t+0.5) is PUSH's, the
    innermost; a gap that no phase covers keeps the harness's name."""
    trace = hand_trace()
    for t in (10.0, 11.0):
        trace["host"] += [("bps.hybrid.step", t, t + 0.9), ("bps.hybrid.hop_wait", t + 0.3, t + 0.7),
                          ("bps.stage.PUSH", t + 0.4, t + 0.6)]
    r = xplane.reduce(trace)
    assert r["steps"] == 2 and r["window_s"] == pytest.approx(2.0)  # bps.* bound no window
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["bps.stage.PUSH:jit_grad_-_jit_apply"] == pytest.approx(0.8)
    assert gaps["bps.hybrid.step:window_start_-_jit_grad"] == pytest.approx(0.1)
    # t+0.8 .. t+1.1, middle t+0.95: the program's step is over, the harness blocks
    assert gaps["bench.step.block:jit_apply_-_jit_grad"] == pytest.approx(0.3)
    assert sum(gaps.values()) == pytest.approx(2.0 - 0.6)
    only_wait = {**trace, "host": [h for h in trace["host"] if h[0] != "bps.stage.PUSH"]}
    gaps = dict(map(tuple, xplane.reduce(only_wait)["idle_gaps"]))
    assert gaps["bps.hybrid.hop_wait:jit_grad_-_jit_apply"] == pytest.approx(0.8)


def test_an_operation_is_named_by_its_name_and_opcode():
    line = ("%psum_invariant.259 = f32[25088,4096]{1,0:T(8,128)S(1)} all-reduce(f32[25088,4096]{1,0:T(8,128)} "
            "%fusion.3), channel_id=5, replica_groups={{0,1,2,3}}")
    assert xplane._op_name(line) == "psum_invariant.259:all-reduce"
    start = ("%copy-start.9 = (bf16[64,128]{1,0:T(8,128)(2,1)}, bf16[64,128]{1,0:T(8,128)(2,1)S(1)}, "
             "u32[]{:S(2)}) copy-start(bf16[64,128]{1,0:T(8,128)(2,1)S(1)} %remat2.144)")
    assert xplane._op_name(start) == "copy-start.9"
    fusion = "%add_add_fusion.2 = bf16[64,128,1024]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[4096]{0} %p), kind=kOutput"
    assert xplane._op_name(fusion) == "add_add_fusion.2"
    assert xplane._op_name("jit_step(421660827885372520)") == "jit_step(421660827885372520)"
    assert xplane._label("jit_step(421660827885372520)") == "jit_step"


def test_a_trace_without_annotations_or_devices_is_an_error():
    with pytest.raises(ValueError, match="nothing to reduce"):
        xplane.reduce({"host": [], "devices": {}})


# ---- names, units and files ----------------------------------------------------


def metric_files():
    return sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "metrics")))


def test_every_name_and_unit_is_in_the_alphabet():
    names = [c["name"] for c in BENCH["configs"]]
    for w in BENCH["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        names += [m["name"]] + ([m["layer"]] if "layer" in m else [])
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES, m
    for name in names:
        assert NAME.fullmatch(name), name
    assert len({m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}) == len(
        BENCH["end_to_end"] + BENCH["per_layer"])
    assert {m["name"] for m in BENCH["end_to_end"]} == {"samples_per_s", "setup_s"}


def test_each_per_layer_entry_has_its_metric_file_and_reader():
    """BENCHMARK.json alone says what a metric is (name, layer, unit, better,
    source, moves); its file says only how it is read."""
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert metric_files() == sorted(m["name"] for m in BENCH["per_layer"])
    for m in BENCH["per_layer"]:
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        assert set(spec) == {"reader", "args", "what"}, m["name"]
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", cells)) <= cells
        reader = load("readers", f"{spec['reader']}.py")
        assert callable(reader.read)


def test_the_listing_holds_together():
    """What ISSUE 70 set up, by data alone: ``per_layer`` under its cap, one entry
    a metric file - no two files with the same reader and arguments, so a quantity
    that several cells report is ONE entry listing them -, nothing waiting outside
    the list, and every family reader known to ``step_rest``'s ``unscoped_ms``."""
    assert len(BENCH["per_layer"]) <= 128
    read_by = {}
    for name in metric_files():
        spec = load_json(HERE, "metrics", f"{name}.json")
        read_by.setdefault((spec["reader"], json.dumps(spec["args"], sort_keys=True)), []).append(name)
    assert [names for names in read_by.values() if len(names) > 1] == []
    assert not os.path.exists(os.path.join(HERE, "unlisted"))
    for name in load("readers", "step_rest.py").FAMILY_READERS:
        assert load("readers", f"{name}.py").SCOPES, name


def test_every_cell_finds_its_files_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    used = set()
    for w in BENCH["workloads"]:
        entry = configs[w["config"]]
        used.add(w["config"])
        assert entry["file"].startswith(BENCH["paths"][0] + "/")
        config = load_json(ROOT, entry["file"])
        assert config["reduced"] == entry["reduced"]
        builder = load("builders", f"{config['builder']}.py")
        for fn in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build"):
            assert callable(getattr(builder, fn)), (config["builder"], fn)
        traffic = load_json(HERE, "traffic", f"{w['traffic']}.json")
        assert traffic["step_path"] in ("local", "ps")
        assert traffic["mesh"]["dp"] == w["chips"]
        for tol in {"reference_rtol", "reference_update_rtol"} & set(config):  # each with its reason
            assert config[tol]["value"] > 0 and len(config[tol]["why"]) > 40, (w["config"], tol)
        if traffic["step_path"] == "ps":  # a wrong gradient must show where gradients travel
            assert 0 < config["reference_update_rtol"]["value"] < 0.5
            assert 0 < config["reference_update_rtol"]["leaf_value"] < 0.5
    assert used == set(configs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_nothing_under_benchmark_reads_a_byteps_variable():
    read = re.compile(r"(environ\.get\(|environ\[|getenv\()\s*[\"']BYTEPS_")
    for top, _, files in os.walk(HERE):
        for fn in files:
            if fn.endswith(".py") and fn != os.path.basename(__file__):
                with open(os.path.join(top, fn)) as f:
                    assert not read.search(f.read()), fn


# ---- FLOP per sample against hand-computed values ------------------------------


def test_vgg16_flops_per_sample():
    cfg = load_json(HERE, "configs", "vgg16.json")
    # multiply-accumulates by hand: 13 convolutions at 224x224 input, 3 dense
    conv = 9 * (224**2 * (3 * 64 + 64 * 64) + 112**2 * (64 * 128 + 128 * 128)
                + 56**2 * (128 * 256 + 2 * 256 * 256)
                + 28**2 * (256 * 512 + 2 * 512 * 512) + 14**2 * 3 * 512 * 512)
    dense = 7 * 7 * 512 * 4096 + 4096 * 4096 + 4096 * 1000
    assert conv + dense == 15_470_264_320
    got = load("builders", "vgg16.py").flops_per_sample(cfg)
    assert got == 6.0 * (conv + dense)
    assert got == pytest.approx(93e9, rel=0.01)  # ~93 GFLOP forward + backward


def test_vgg16_config_is_the_programs_configuration_d():
    from byteps_tpu.models import vgg

    cfg = load_json(HERE, "configs", "vgg16.json")
    assert cfg["conv_channels"] == vgg._CFG16
    weights = 0
    cin, hw = cfg["in_channels"], cfg["image_size"]
    for v in cfg["conv_channels"]:
        if v == "M":
            hw //= 2
        else:
            weights += 9 * cin * v + v
            cin = v
    h, c = cfg["hidden"], cfg["num_classes"]
    weights += cin * hw * hw * h + h + h * h + h + h * c + c
    assert 4 * weights == cfg["grad_bytes_per_step"] == 553_430_176


def test_bert_large_flops_per_sample():
    cfg = load_json(HERE, "configs", "bert_large.json")
    s, layers, d, v = 128, 24, 1024, 30528
    assert (cfg["max_seq"], cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]) == (s, layers, d, v)
    want = 6 * s * (12 * layers * d * d + d * v) + 12 * layers * s * s * d
    assert want == 260_768_268_288
    assert load("builders", "bert_large.py").flops_per_sample(cfg) == float(want)


def test_mfu_of_the_harness_reader():
    run = {"steps": 10, "global_batch": 64, "window_s": 2.0, "flops_per_sample": 1e11,
           "chips": 1, "peak_flops_per_s": 197e12, "peak_hbm_bytes": 3 * 2**30,
           "peak_in_use_bytes": 2**30}
    reader = load("readers", "harness.py")
    assert reader.read(run, quantity="mfu") == pytest.approx(320 * 1e11 / 197e12 * 100)
    assert reader.read(run, quantity="peak_hbm_gib") == pytest.approx(3.0)
    assert reader.read(run, quantity="peak_in_use_gib") == pytest.approx(1.0)
    assert reader.read({**run, "peak_hbm_bytes": 0}, quantity="peak_hbm_gib") is None


def test_histogram_mean_and_counter_delta():
    key_a, key_b = 'stage_dwell_seconds{stage="PUSH"}', 'stage_dwell_seconds{stage="PULL"}'
    run = {
        "steps": 4,
        "histograms": {"before": {key_a: {"sum": 1.0, "count": 10}},
                       "after": {key_a: {"sum": 3.0, "count": 20}, key_b: {"sum": 6.0, "count": 30}}},
        "counters": {"before": {"wire_tx_bytes": 100}, "after": {"wire_tx_bytes": 500, "wire_rx_bytes": 400}},
    }
    hist = load("readers", "histogram_mean.py")
    assert hist.read(run, keys=[key_a], scale=1000) == pytest.approx(200.0)
    assert hist.read(run, keys=[key_a, key_b], scale=1000) == pytest.approx(200.0)
    assert hist.read(run, keys=["absent"]) is None
    delta = load("readers", "counter_delta.py")
    assert delta.read(run, counters=["wire_tx_bytes", "wire_rx_bytes"], scale=0.5) == pytest.approx(100.0)
    assert delta.read(run, counters=["absent"]) is None
    # a counter of what should not happen is absent until raised: 0 beside its sibling
    assert delta.read(run, counters=["absent"], beside=["wire_tx_bytes"]) == 0.0
    assert delta.read(run, counters=["absent"], beside=["absent_too"]) is None


# ---- the step record -------------------------------------------------------------

EVEN = [0.5] * 20
STALLED = [0.5] * 19 + [2.0]


@pytest.mark.parametrize("step_s, window_s, want", [
    # an even run: every step at the median, nothing slow
    (EVEN, 10.0, {"steps": 20, "p50_ms": 500.0, "p10_ms": 500.0, "p90_ms": 500.0,
                  "slowest_ms": 500.0, "slowest_index": 0, "slow_share": 0.0, "in_steps_share": 1.0}),
    # one stalled step: the median does not move, the stall is 2.0 of 11.5 s + 0.5 s of collecting
    (STALLED, 12.0, {"steps": 20, "p50_ms": 500.0, "p10_ms": 500.0, "p90_ms": 500.0,
                     "slowest_ms": 2000.0, "slowest_index": 19, "slow_share": 2.0 / 12.0,
                     "in_steps_share": 11.5 / 12.0}),
    # every step slower from the start: the median carries it, no step is slow against it
    ([0.55] * 20, 11.0, {"steps": 20, "p50_ms": 550.0, "slow_share": 0.0}),
    ([0.4], 0.4, {"steps": 1, "p50_ms": 400.0, "p10_ms": 400.0, "p90_ms": 400.0, "slow_share": 0.0}),
])
def test_step_record(step_s, window_s, want):
    got = load("readers", "harness.py").step_record(step_s, window_s)
    for key, value in want.items():
        assert got[key] == pytest.approx(value), key


def test_step_quantities_of_the_harness_reader():
    reader = load("readers", "harness.py")
    run = {"step_s": STALLED, "window_s": 12.0}
    assert reader.read(run, quantity="slow_step_share") == pytest.approx(100 * 2.0 / 12.0)
    assert reader.read({"step_s": EVEN, "window_s": 10.0}, quantity="slow_step_share") == 0.0
    # an empty window has no step to read, and a run from before the record none either
    assert reader.step_record([], 0.0) is None
    assert reader.read({"step_s": [], "window_s": 0.0}, quantity="slow_step_share") is None
    assert reader.read({"window_s": 40.0}, quantity="slow_step_share") is None


def test_the_windows_steps_and_collections_add_up_to_it():
    """``run_window`` keeps every step's and every collection's seconds: with
    the memory readings between them they are the window."""
    import time

    import jax
    import numpy as np

    run = load("run.py")

    def step():
        time.sleep(0.02)
        return np.float32(1.0), None

    w = run.run_window(jax, step, 0.5, None, 4)
    assert len(w["step_s"]) == len(w["losses"]) == w["attempted"] and w["failed"] == 0
    assert len(w["collect_s"]) == (w["attempted"] - 1) // 4
    assert all(t >= 0.02 for t in w["step_s"])
    inside = sum(w["step_s"]) + sum(w["collect_s"])
    assert 0.9 * w["window_s"] <= inside <= w["window_s"]
    assert w["window_s"] >= 0.5


def test_per_layer_metrics_list_cells_that_report_what_they_move():
    """A ``workloads`` list names cells that exist, once each, and each of them
    reports the end-to-end metric that the metric moves.  Nothing here names a
    cell or a layer: whether a listed cell gives the reader something to read
    is what a traced run of that cell shows (a missing value is refused
    there), so a later cell, or a metric of one cell alone, is new entries
    and new files, and no edit of this test."""
    cells_of = {w["name"] for w in BENCH["workloads"]}
    end_to_end = {m["name"]: set(m.get("workloads", cells_of)) for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            continue
        assert cells and len(set(cells)) == len(cells) and set(cells) <= cells_of, m["name"]
        assert set(cells) <= end_to_end[m["moves"]], m["name"]
