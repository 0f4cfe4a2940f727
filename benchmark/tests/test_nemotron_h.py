"""The yardstick's checks of what the ``nemotron_twotower_30b_ep16``
configuration brought: ``python -m pytest benchmark/tests -q`` (by hand; no
device needed)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "nemotron_twotower_ep16_train8k", "nemotron_twotower_30b_ep16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = ("https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16/blob/main/"
          "config.json")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


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
reader = load("readers", "ssm_moe.py")
builder = load("builders", "nemotron_h.py")
NAMES = {"train_step.ssd_scan_ms", "train_step.ssm_proj_ms", "train_step.nope16_attention_ms",
         "train_step.window_family_route_ms", "train_step.window_family_experts_ms", "train_step.shared_expert_ms",
         "kernels.global_flash_roofline_share", "kernels.ssd_scan_roofline_share",
         "moe.held_slots_per_step", "moe.dropped_slots_per_step",
         "moe.fullest_expert_share"}
MINE = [m for m in BENCH["per_layer"] if m["name"] in NAMES]


def test_the_cell_finds_its_files_by_name():
    """By name alone: where in ``BENCHMARK.json``'s lists the entries stand is
    nobody's to assert — a later PR appends after them."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "local_closed"
    assert len(cell["why"]) <= 200 and "16x their share" in cell["why"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["source"] == SOURCE
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    assert CFG["builder"] == "nemotron_h"
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build",
                 "_model_config", "_mesh4"):  # the last two: tools/latent_moe_precision.py's
        assert callable(getattr(builder, name))
    assert {m["name"] for m in MINE} == NAMES
    assert [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]] == [
        "train_step.ssd_scan_ms", "train_step.ssm_proj_ms", "train_step.nope16_attention_ms",
        "kernels.ssd_scan_roofline_share"]
    # and every metric without a list of cells finds something to read here:
    # the ten that every training cell has
    everywhere = [m["name"] for m in BENCH["per_layer"]
                  if "workloads" not in m and m["moves"] in ("samples_per_s", "setup_s")]
    assert len(everywhere) == 10


@pytest.mark.parametrize("name", sorted(NAMES))
def test_every_metric_file_loads_and_names_the_cell(name):
    m = next(m for m in MINE if m["name"] == name)
    spec = load_json(HERE, "metrics", f"{name}.json")
    assert spec["reader"] in ("ssm_moe", "window_moe", "latent_moe")
    assert m["moves"] == "samples_per_s"
    assert callable(load("readers", f"{spec['reader']}.py").read) and spec["what"]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert CELL in m["workloads"] and m["layer"] in ("train_step", "kernels", "moe")
    if name.endswith("roofline_share"):
        assert m["unit"] == "%" and m["better"] == "higher" and m["source"] == "device_trace"


def test_the_builders_reference_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "builders", f"{CFG['builder']}.py")) as f:
        text = f.read()
    top = text[:text.index("# the program")]
    assert "import byteps_tpu" not in top and "from byteps_tpu" not in top
    assert "from byteps_tpu" in text[len(top):]  # the program's part does


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_every_key_of_the_catalog_row_is_held_and_only_the_cut_differs():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16")
    assert row["source_url"] == SOURCE and SOURCE in CFG["source"] and len(CFG["source"]) <= 200
    assert set(row["config"]) <= set(CFG)
    differs = sorted(k for k, v in row["config"].items() if CFG[k] != v)
    assert differs == sorted(REDUCED)  # the pattern stands whole, and every width
    assert CFG["published"] == {k: row["config"][k] for k in REDUCED}
    assert CFG["hybrid_override_pattern"] == PATTERN == row["config"]["hybrid_override_pattern"]


def test_reduced_is_the_same_in_both_places_and_the_cut_keeps_its_floors():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == REDUCED
    # from the model's start through its first attention layer and the expert
    # layer after it: every kind of layer, 3 : 3 : 1
    kinds = builder._kinds(CFG)
    assert kinds == "MEMEM*E" == PATTERN[:7] and CFG["first_layer"] == 0
    assert (kinds.count("M"), kinds.count("E"), kinds.count("*")) == (3, 3, 1)
    assert CFG["n_routed_experts"] >= 8 and CFG["router_width"] == 128
    assert CFG["vocab_size"] * 8 == 131072
    for key in ("deployment", "assumed", "held", "rehearsal"):
        assert CFG[key]
    assert "16 chips share each layer" in CFG["deployment"] and "nine" in CFG["deployment"]
    assert "1/16" in CFG["held"]["expert_load"]
    for key in ("second_tower", "positions", "state", "router", "experts", "initialiser", "expand",
                "aux_loss", "tokens", "optimizer", "compute_dtype", "remat"):
        assert CFG["assumed"][key], key
    assert "absent" in CFG["assumed"]["second_tower"]
    for tol in ("reference_rtol", "reference_update_rtol"):
        assert CFG[tol]["value"] > 0 and "below" in CFG[tol]["why"]
    # the rehearsal runs a layer of every kind too
    cut = CFG["rehearsal"]
    assert PATTERN[cut["first_layer"]:][:cut["num_hidden_layers"]] == "M*E"


def test_flops_per_sample_against_a_hand_count():
    # a token's forward matrix products, in multiply-adds
    mamba = 2688 * 10304 + 4096 * 2688 + 4 * 6144  # in_proj, out_proj, the taps
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256  # q, out; k, v
    routed = 2688 * 128 + 2 * 2688 * 3712 + 6 * 8 / 128 * 2 * 2688 * 1856  # 0.375 slots a token
    assert (mamba, attention) == (38_731_776, 23_396_352)
    assert routed == pytest.approx(24.04e6, rel=1e-3)
    products = 2 * (3 * mamba + attention + 3 * routed + 2688 * 16384)
    scan = 3 * builder.scan_operations(64, 64, 128)
    assert scan == 3 * 5 * 64 * 64 * 128
    causal = 8192 * 8193 // 2
    want = 3 * (8192 * (products + scan) + causal * 32 * 2 * (128 + 128))
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-9)
    assert want == pytest.approx(14.6e12, rel=2e-2)  # a step is two of these
    # nine layers would be a Mamba-2 and an expert layer more
    nine = builder.flops_per_sample({**CFG, "num_hidden_layers": 9})
    assert nine - want == pytest.approx(
        3 * 8192 * (2 * (mamba + routed) + scan // 3), rel=1e-9)


def test_parameter_count_of_the_share():
    mamba = 2688 * 10304 + 4096 * 2688 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 2688
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    experts = 8 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128 + 128 + 2688
    assert (mamba, attention, experts) == (38_744_896, 23_399_040, 100_125_440)
    top = 2 * 16384 * 2688 + 2688
    seven, nine = (n * (mamba + experts) + attention + top for n in (3, 4))
    assert (seven, nine) == (528_093_120, 666_963_456)  # 528.1 M | 667.0 M
    assert "528 093 120" in CFG["held"]["parameters"] and "7.87 GiB" in CFG["held"]["parameters"]
    assert seven * 16 / 2**30 == pytest.approx(7.87, abs=0.005)
    assert nine * 16 / 2**30 == pytest.approx(9.94, abs=0.005)
    assert "666 963 456" in CFG["deployment"]


# ---- the reader ---------------------------------------------------------------------


def test_the_scans_cost_is_the_recurrences():
    args = load_json(HERE, "metrics", "kernels.ssd_scan_roofline_share.json")["args"]
    assert args == {"quantity": "ssd_scan_roofline_share", "layers": 3, "tokens_per_sample": 8192,
                    "heads": 64, "head_dim": 64, "state": 128, "groups": 8, "item": 2}
    assert (args["heads"], args["head_dim"], args["state"], args["groups"]) == (
        CFG["mamba_num_heads"], CFG["mamba_head_dim"], CFG["ssm_state_size"], CFG["n_groups"])
    assert args["layers"] == builder._kinds(CFG).count("M")
    tokens = 2 * 8192
    ops, nbytes = reader.ssd_cost(tokens, 64, 64, 128, 8, 2)
    # decay, update (a multiply-add) and read (a multiply-add) an entry of the
    # state, forward; twice that backward
    assert ops == 3 * tokens * 64 * 5 * 64 * 128
    assert ops == 3 * tokens * builder.scan_operations(64, 64, 128)
    # x, z, y a head and B, C a group in bf16, dt in f32; forward once, and
    # they and their cotangents once backward
    forward = tokens * (2 * (3 * 4096 + 2 * 1024) + 4 * 64)
    assert nbytes == 3 * forward == 3 * tokens * 28_928
    assert ops / 197e12 == pytest.approx(0.654e-3, rel=1e-2)
    assert nbytes / 819e9 == pytest.approx(1.736e-3, rel=1e-2)  # bytes-bound: 1.74 ms a layer


def test_the_scopes_the_metrics_read_are_the_programs():
    assert reader.SCOPES == ("ssd_scan", "ssm_proj", "nope16_attention", "moe_route",
                             "shared_expert", "moe_experts")
    window = load("readers", "window_moe.py")
    for name, scope, own in (("ssd_scan_ms", "ssd_scan", True), ("ssm_proj_ms", "ssm_proj", True),
                             ("nope16_attention_ms", "nope16_attention", True),
                             ("window_family_route_ms", "moe_route", False),
                             ("window_family_experts_ms", "moe_experts", False),
                             ("shared_expert_ms", "shared_expert", False)):
        spec = load_json(HERE, "metrics", f"train_step.{name}.json")
        assert spec["args"] == {"quantity": "scope_ms", "match": scope}
        assert spec["reader"] == ("ssm_moe" if own else "window_moe")
        assert scope in (reader if own else window).SCOPES
    # no scope of this family is one of window_moe's mixers': first match is right there too
    assert not {"ssd_scan", "ssm_proj", "nope16_attention"} & set(window.SCOPES)
    with open(os.path.join(ROOT, "byteps_tpu", "models", "ssm_moe.py")) as f:
        text = f.read()
    for scope in ("ssd_scan", "ssm_proj", "nope16_attention", "shared_expert", "moe_experts"):
        assert f'"{scope}"' in text
    assert reader.scope_of("jit(step)/ssm_proj/ssd_scan/mul") == "ssd_scan"  # the first of SCOPES
    assert reader.scope_of("", "%ragged-dot.3 = custom-call(") == "moe_experts"
    assert reader.scope_of("jit(step)/optimizer/add") is None
    assert load_json(HERE, "metrics", "kernels.global_flash_roofline_share.json")["args"] == {
        "quantity": "flash_roofline_share", "kind": "global"}


def test_the_roofline_share_is_least_time_over_scope_time():
    """``measure`` on a hand-made trace: two steps, 30 ms under ``ssd_scan``
    in all, 3 ms the least a step: 20 %."""
    phases = reader._phases()
    xp = phases._xplane()
    trace = {"bench": [(xp.CALL, 0.0, 0.005), (xp.BLOCK, 0.005, 0.1), (xp.CALL, 0.1, 0.105),
                       (xp.BLOCK, 0.105, 0.2)],
             "ops": [("%a", 0.01, 0.02), ("%b", 0.11, 0.13), ("%c", 0.15, 0.16)],
             "paths": {"%a": "jit(s)/ssd_scan/exp", "%b": "jit(s)/ssd_scan/dot",
                       "%c": "jit(s)/ssm_proj/dot"}}
    assert phases.window(trace["bench"]) == (0.0, 0.2, 2)
    assert reader.measure(trace, "scope_ms", "ssd_scan") == pytest.approx(15.0)
    assert reader.measure(trace, "scope_ms", "ssm_proj") == pytest.approx(5.0)
    assert reader.measure(trace, "ssd_scan_roofline_share", least_s=3e-3) == pytest.approx(20.0)
    assert reader.measure(trace, "scope_ms", "nope16_attention") is None
    with pytest.raises(ValueError, match="no quantity"):
        reader.measure(trace, "flash_roofline_share")


def test_a_program_without_the_family_reads_nothing():
    parent = {"steps": 4, "counters": {"before": {}, "after": {"d2h_bytes": 7}}, "trace": None,
              "global_batch": 1, "peak_flops_per_s": 197e12}
    for spec in MINE:
        m = load_json(HERE, "metrics", f"{spec['name']}.json")
        assert load("readers", f"{m['reader']}.py").read(parent, **m["args"]) is None


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL, "--seed", "2951000077",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {} and line["failed"] == 0
    assert {"samples_per_s", "setup_s"} <= set(line["rehearsal"])
    compared = line["compared"]
    assert compared["steps_failed"]["ok"] and compared["compiles_in_window"]["ok"]
    assert {"loss_off_reference", "update_off_all_leaves", "update_off_worst_leaf"} <= set(compared)
