"""The yardstick's checks of what the ``kimi_linear_48b_ep32`` configuration
brought: ``python -m pytest benchmark/tests/test_kimi_linear.py -q`` (by hand; no
device needed; the last test is the cell's rehearsal, about two minutes)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "kimi_linear_ep32_train16k", "kimi_linear_48b_ep32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NAMES = {"train_step.kda_scan_ms", "train_step.kda_proj_ms",
         "train_step.nope_latent_attention_ms", "kernels.kda_scan_roofline_share",
         "kernels.nope_mla_flash_roofline_share", "moe.held_slots_per_step",
         "moe.dropped_slots_per_step", "moe.fullest_expert_share"}
#: the three scopes that no general entry reads by this family's rule
NAMES |= {"train_step.channel_delta_moe_route_ms", "train_step.channel_delta_moe_experts_ms",
          "train_step.channel_delta_moe_shared_ms"}
#: the routing counters, which every held-expert family reads through ``latent_moe``
SHARED = {name for name in NAMES if name.startswith("moe.")}
#: the general entries that list the cell since PR 70, in place of the stand-ins it waited with
APPENDED = ("train_step.dense_mlp_ms", "train_step.head_loss_ms", "train_step.embed_ms",
            "train_step.unscoped_ms", "train_step.no_phase_ms", "train_step.dispatch_ms",
            "train_step.idle_in_dispatch_ms", "train_step.fold_ms",
            "train_step.grouped_products_ms", "moe.rows_walked_per_step")


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
CFG = load_json(HERE, "configs", f"{CONFIG}.json")
MINE = [m for m in BENCH["per_layer"] if m["name"] in NAMES]
reader = load("readers", "channel_delta_moe.py")
counters = load("readers", "latent_moe.py")
READERS = {"channel_delta_moe": reader, "latent_moe": counters,
           "step_rest": load("readers", "step_rest.py")}
builder = load("builders", "kimi_linear.py")


def test_the_cell_finds_its_files_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "local_closed"
    assert len(cell["why"]) <= 200 and "1 x 16384 tokens" in cell["why"]
    assert len(entry["why"]) <= 200 and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["reduced"] == REDUCED
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    assert CFG["builder"] == "kimi_linear" and CFG["reduced"] == REDUCED
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build",
                 "_model_config", "_mesh4"):  # the last two: tools/latent_moe_precision.py's
        assert callable(getattr(builder, name))
    assert {m["name"] for m in MINE} == NAMES
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    assert all(CELL in listed[name]["workloads"] for name in APPENDED)
    assert not [name for name in listed if "channel_delta" in name and name not in NAMES]


@pytest.mark.parametrize("name", sorted(NAMES))
def test_every_metric_file_loads_and_names_the_cell(name):
    m = next(m for m in MINE if m["name"] == name)
    spec = load_json(HERE, "metrics", f"{name}.json")
    # the counters are read by latent_moe's reader, by data alone
    want = "latent_moe" if name in SHARED else "channel_delta_moe"
    assert spec["reader"] == want and spec["what"]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (CELL in m["workloads"] if name in SHARED else m["workloads"] == [CELL])
    assert m["moves"] == "samples_per_s"
    if name.endswith("roofline_share"):
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", "higher", "device_trace", "kernels")
    # a run without a trace and without the counters reads None and does not raise
    empty = {"trace": None, "steps": 3, "counters": {"before": {}, "after": {}}}
    assert READERS[want].read(empty, **spec["args"]) is None


def test_the_counters_are_read_from_a_runs_snapshots():
    run = {"trace": None, "steps": 4, "counters": {
        "before": {"moe_slots_routed": 10, "moe_slots_held": 100, "moe_fullest_expert_slots": 20},
        "after": {"moe_slots_routed": 50, "moe_slots_held": 500, "moe_fullest_expert_slots": 80}}}
    value = lambda name: counters.read(  # noqa: E731
        run, **load_json(HERE, "metrics", f"moe.{name}.json")["args"])
    assert value("held_slots_per_step") == 100.0
    assert value("dropped_slots_per_step") == 0.0  # a counter that never grew is not there
    assert value("fullest_expert_share") == 15.0


def test_every_published_key_stands_as_published():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert row["source_url"] == SOURCE and SOURCE in CFG["source"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CFG[key] != value and CFG["published"][key] == value
        else:  # the nested group whole: both lists as published, the widths too
            assert CFG[key] == value and type(CFG[key]) is type(value), key
    assert (CFG["num_hidden_layers"], CFG["num_experts"], CFG["vocab_size"]) == (5, 8, 20480)
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    assert CFG["router_width"] == CFG["published"]["num_experts"] == 256
    # layers 1-5 by published index: the dense layer, then one whole period of the 3 : 1
    assert builder.layer_types(CFG) == ("channel_delta",) * 3 + ("latent_attention",
                                                                 "channel_delta")
    whole = builder.layer_types(dict(CFG, num_hidden_layers=27))
    assert whole.count("channel_delta") == 20 and whole.count("latent_attention") == 7
    for key in ("gate_rank", "a_log", "dt_bias", "biases", "q_scale", "initialiser", "state",
                "e_score_correction_bias", "aux_loss", "tokens", "optimizer", "compute_dtype",
                "remat"):
        assert CFG["assumed"][key], key
    for key in ("num_hidden_layers", "num_experts", "vocab_size", "expert_load", "parameters"):
        assert CFG["held"][key], key
    assert "32 chips share each layer" in CFG["deployment"]


def test_the_parameter_count():
    import numpy as np

    from byteps_tpu.models import channel_delta_moe

    layout = channel_delta_moe.layouts(builder._model_config(CFG))
    count = sum(int(np.prod(s)) for s, _, _ in layout.values())
    parts = builder.parameter_count(CFG)
    assert count == parts["whole"] == 602_434_432
    assert "602 434 432" in CFG["held"]["parameters"]
    # ISSUE 68's count by hand, part by part
    assert round(parts["delta_mixer"] / 1e6, 1) == 39.5
    assert round(parts["latent_mixer"] / 1e6, 1) == 29.1
    assert round(parts["expert_mlp"] / 1e6, 1) == 64.3
    assert round(parts["dense_mlp"] / 1e6, 1) == 63.7
    # the whole model, uncut: 48B-A3B
    whole = builder.parameter_count(dict(CFG, **CFG["published"]))["whole"]
    assert 48e9 < whole < 50e9


def test_flops_per_sample_against_a_count_by_hand():
    s, d, v = 16384, 2304, 20480
    kda = d * 3 * 4096 + d * (2 * 128 + 32) + 128 * 2 * 4096 + 4096 * d
    latent = d * 32 * 192 + d * (512 + 64) + 512 * 32 * 256 + 32 * 128 * d
    moe = d * 256 + 3 * d * 1024 + 0.25 * 3 * d * 1024
    per_token = 2 * (4 * kda + latent + 3 * d * 9216 + 4 * moe + d * v)
    per_token += 4 * 32 * (6 * 128 * 128 + 128)  # the rule: a token a head
    attention = s * (s + 1) // 2 * 32 * 2 * (192 + 128)
    want = 3 * (s * per_token + attention)
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 40e12 < want < 44e12


def test_the_rules_cost_against_a_count_by_hand():
    ops, nbytes = reader.channel_delta_rule_cost(16384, 32, 128, 128, 2)
    assert ops == 3 * 16384 * 32 * (6 * 128 * 128 + 128)
    # q, k, v, o in bf16, g in f32 a channel, beta in f32: once forward, twice more backward
    assert nbytes == 3 * 16384 * 32 * (2 * 4 * 128 + 4 * 128 + 4)
    # bytes bound it on a v5e: 2.96 ms a layer against 0.79 of operations
    assert nbytes / 819e9 == pytest.approx(2.957e-3, rel=1e-3)
    assert ops / 197e12 == pytest.approx(0.786e-3, rel=1e-3)


def test_the_reader_on_a_made_up_trace():
    """Three operations under the family's scopes, a grouped product with no
    scope path, two flash calls, one operation each under the head's scope, the
    optimizer's and none, and one step in the window."""
    flash = ("%flash_{}.1 = bf16[32,16384,128] custom-call(bf16[32,16384,192] %q, "
             "bf16[32,16384,192] %k, bf16[32,16384,128] %v)")
    names = {"scan": "%fusion.1", "proj": "%fusion.2", "latent": "%fusion.3",
             "ragged": "%ragged-dot.4", "fwd": flash.format("fwd"), "bwd": flash.format("bwd"),
             "head": "%fusion.5", "adamw": "%fusion.6", "copy": "%copy.7"}
    phases = reader._reader("phases")
    xp = phases._xplane()
    trace = {
        "bench": [(xp.CALL, 0.0, 0.005), (xp.BLOCK, 0.005, 1.0)],  # one step
        "ops": [(names["scan"], 0.01, 0.31), (names["proj"], 0.31, 0.41),
                (names["latent"], 0.41, 0.43), (names["ragged"], 0.43, 0.53),
                (names["fwd"], 0.53, 0.57), (names["bwd"], 0.57, 0.67),
                (names["head"], 0.67, 0.70), (names["adamw"], 0.70, 0.72),
                (names["copy"], 0.72, 0.73)],
        "paths": {names["scan"]: "jit(train_step)/checkpoint/kda_scan/exp",
                  names["proj"]: "jit(train_step)/kda_proj/dot_general",
                  names["latent"]: "jit(train_step)/nope_latent_attention/mul",
                  names["fwd"]: "jit(train_step)/nope_latent_attention/flash_fwd",
                  names["bwd"]: "jit(train_step)/nope_latent_attention/flash_bwd",
                  names["head"]: "jit(train_step)/lm_head/dot_general",
                  names["adamw"]: "jit(train_step)/optimizer/mul",
                  names["copy"]: "jit(train_step)/while/body/copy"},
    }
    assert phases.window(trace["bench"])[2] == 1
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert reader.measure(trace, "scope_ms", "kda_scan") == pytest.approx(300.0)
    assert reader.measure(trace, "scope_ms", "kda_proj") == pytest.approx(100.0)
    assert reader.measure(trace, "scope_ms", "moe_experts") == pytest.approx(100.0)
    assert reader.measure(trace, "scope_ms", "nope_latent_attention") == pytest.approx(160.0)
    assert reader.measure(trace, "scope_ms", "dense_mlp") is None
    # under no name: the copy alone - not the head's, the optimizer's or the grouped product
    assert READERS["step_rest"].measure(trace, "unscoped_ms") == pytest.approx(10.0)
    assert READERS["step_rest"].measure(trace, "scope_ms", "lm_head") == pytest.approx(30.0)
    share = reader.measure(trace, "kda_scan_roofline_share", least_s=0.012)
    assert share == pytest.approx(4.0)
    entries = 32 * 16384 * 16385 // 2
    least = (entries * 2 * (192 + 128) + entries * 2 * (3 * 192 + 2 * 128)) / 197e12
    assert reader.measure(trace, "nope_mla_flash_roofline_share", peaks=peaks) == pytest.approx(
        least / 0.14 * 100.0)
    assert reader.scope_of("", "%ragged-dot.7 = ...") == "moe_experts"
    assert reader.scope_of("jit(f)/lm_head/dot") is None


def test_the_rehearsal_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL, "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {} and line["failed"] == 0
    compared = line["compared"]
    # 128 tokens over 256 rows learn fast: the rehearsal's loss stays within 1 %
    # of the reference's, not within the cell's limit
    assert compared["loss_off_reference"]["value"] < 1e-2 and compared["compiles_in_window"]["ok"]
    assert compared["update_off_worst_leaf"]["value"] < 1.0


#: run.py with a fault planted in what the builder hands it: the program's steps
#: count the first half of the sequence's targets alone, or leave the state as it
#: was (the optimizer's update x 0); the reference sees the batch and the optimizer
#: as they are
PLANTED = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("run", sys.argv.pop(1))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
fault, builder = sys.argv.pop(1), run.load_module("builders", "kimi_linear")
sound, optimizer = builder.build, builder.make_optimizer

def build(cfg, traffic, params, batch, mesh):
    tokens, targets = batch
    if fault == "half_the_targets":
        import jax.numpy as jnp
        targets = jnp.where(jnp.arange(targets.shape[1]) < targets.shape[1] // 2, targets, -1)
    if fault == "state_unchanged":
        import optax
        builder.make_optimizer = lambda cfg: optax.chain(optimizer(cfg), optax.scale(0.0))
    try:
        return sound(cfg, traffic, params, (tokens, targets), mesh)
    finally:
        builder.make_optimizer = optimizer

builder.build = build  # load_module caches: run.py's own call gets this module
raise SystemExit(run.main())
"""


@pytest.mark.parametrize("fault, caught_by", [
    ("half_the_targets", {"update_off_all_leaves", "update_off_worst_leaf"}),
    ("state_unchanged", {"update_off_all_leaves", "update_off_worst_leaf",
                         "loss_end_over_first"})])
def test_a_planted_fault_fails_the_harness_comparison(fault, caught_by):
    """Through run.py's own comparison at the rehearsal's size, under the
    configuration's limits (the same script read both faults on the chip at
    the cell's size: PERF.md section 6, PR 68).  Half of the sequence left out
    of the loss is another gradient: the update limits refuse it.  A state left
    unchanged reads exactly 1 of the reference's own update, over all leaves and
    in every one, and its loss never falls."""
    out = subprocess.run(
        [sys.executable, "-c", PLANTED, os.path.join(HERE, "run.py"), fault, "--workload", CELL,
         "--seed", str(2**31 + 6), "--seconds", "1", "--trace", "0", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    compared = json.loads(out.stdout.strip().splitlines()[-1])["compared"]
    assert {name for name, c in compared.items() if not c["ok"]} >= caught_by, compared
    if fault == "state_unchanged":
        assert compared["update_off_all_leaves"]["value"] == pytest.approx(1.0, abs=1e-6)
        assert compared["update_off_worst_leaf"]["value"] == pytest.approx(1.0, abs=1e-6)
