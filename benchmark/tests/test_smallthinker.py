"""The yardstick's checks of what the ``smallthinker_21b_ep8`` configuration
brought: ``python -m pytest benchmark/tests -q`` (by hand; no device needed)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "smallthinker_ep8_train16k", "smallthinker_21b_ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
SLIDING, FULL = "sliding_attention", "full_attention"


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
reader = load("readers", "window_moe.py")

#: the source's config.json, as the catalog has it
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_layout": [int(i % 4 != 0) for i in range(52)],
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": [int(i % 4 != 0) for i in range(52)], "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
}
#: the family's own metrics; but for the one banded share they list the other
#: cells of the window family's reader too (one entry a metric file)
NAMES = {"train_step.window_attention_ms", "train_step.global_attention_ms",
         "train_step.window_family_route_ms", "train_step.window_family_experts_ms",
         "kernels.window4096_flash_roofline_share", "kernels.global_flash_roofline_share",
         "moe.held_slots_per_step", "moe.dropped_slots_per_step",
         "moe.fullest_expert_share"}
MINE = [m for m in BENCH["per_layer"] if m["name"] in NAMES]


def test_the_cell_finds_its_files_by_name():
    """By name alone: where in ``BENCHMARK.json``'s lists the entries stand is
    nobody's to assert — a later PR appends after them."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "local_closed"
    assert len(cell["why"]) <= 200 and "8x their share" in cell["why"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
                               "blob/main/config.json")
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    builder = load("builders", f"{CFG['builder']}.py")
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build"):
        assert callable(getattr(builder, name))
    assert {m["name"] for m in MINE} == NAMES
    assert [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]] == [
        "kernels.window4096_flash_roofline_share"]
    # and every metric without a list of cells finds something to read here:
    # the ten that every training cell has
    everywhere = [m["name"] for m in BENCH["per_layer"]
                  if "workloads" not in m and m["moves"] in ("samples_per_s", "setup_s")]
    assert len(everywhere) == 10


@pytest.mark.parametrize("name", sorted(NAMES))
def test_every_metric_file_loads_and_names_the_cell(name):
    m = next(m for m in MINE if m["name"] == name)
    spec = load_json(HERE, "metrics", f"{name}.json")
    assert spec["reader"] in ("window_moe", "latent_moe") and m["moves"] == "samples_per_s"
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


def test_reduced_is_the_same_in_both_places_and_nothing_else_left_the_source():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == REDUCED
    assert entry["source"] in CFG["source"]
    differs = sorted(k for k, v in PUBLISHED.items() if CFG.get(k, "absent") != v)
    assert differs == sorted(REDUCED)  # both layouts stand whole, and every width
    assert CFG["published"] == {k: PUBLISHED[k] for k in REDUCED}
    # the floors of a cut: a whole period and four layers, 8 experts, an eighth of the rows
    builder = load("builders", "smallthinker.py")
    kinds = builder._kinds(CFG)
    assert kinds == [FULL, SLIDING, SLIDING, SLIDING]
    assert CFG["moe_num_primary_experts"] >= 8
    assert CFG["router_width"] == PUBLISHED["moe_num_primary_experts"]
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in ("deployment", "assumed", "held", "rehearsal"):
        assert CFG[key]
    assert "8 chips share each layer" in CFG["deployment"]
    assert "1/8" in CFG["held"]["expert_load"]
    for key in ("router_input", "router", "gate", "attention", "mask", "positions", "norms",
                "secondary_experts", "aux_loss", "weights", "tokens", "optimizer",
                "compute_dtype", "remat"):
        assert CFG["assumed"][key]
    assert "norm_in" in CFG["assumed"]["router_input"] and "relu" in CFG["assumed"]["gate"]
    for tol in ("reference_rtol", "reference_update_rtol"):
        assert CFG[tol]["value"] > 0 and "below" in CFG[tol]["why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_every_key_of_the_catalog_row_is_held():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert row["config"] == PUBLISHED
    assert row["source_url"] in CFG["source"]
    assert set(row["config"]) <= set(CFG)
    for key in set(row["config"]) - set(REDUCED):
        assert CFG[key] == row["config"][key], key


def test_flops_per_sample_against_a_hand_count():
    builder = load("builders", "smallthinker.py")
    # a token's forward matrix products a layer, in multiply-adds
    mixer = 2 * 2560 * 3584 + 2 * 2560 * 512  # q, out; k, v
    routed = 2560 * 64 + 6 * 8 / 64 * 3 * 2560 * 768  # the router; 0.75 held slots a token
    assert (mixer, routed) == (20_971_520, 163_840 + 4_423_680)
    products = 2 * (4 * (mixer + routed) + 2560 * 18992)
    assert products == pytest.approx(301.7e6, rel=1e-3)  # 151 M active parameters a token
    band = 4096 * 16384 - 4096 * 4095 // 2  # entries a head under the window
    causal = 16384 * 16385 // 2
    assert (builder.band_entries(16384, 4096), builder.band_entries(16384, None)) == (band, causal)
    assert builder.band_entries(128, 4096) == 128 * 129 // 2  # a window over the sequence
    assert band == pytest.approx(58.72e6, rel=1e-3) and causal == pytest.approx(134.2e6, rel=1e-3)
    scores = (3 * band + causal) * 28 * 2 * (128 + 128)
    want = 3 * (16384 * products + scores)
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-9)
    assert want == pytest.approx(28.18e12, rel=2e-3)  # a step is two of these: 56.4 TFLOP
    # the band is charged, not the causal triangle: three full layers would add 9.7 TFLOP
    assert 3 * 3 * (causal - band) * 28 * 512 == pytest.approx(9.74e12, rel=1e-2)


def test_parameter_count_of_the_share():
    mixer = 2 * 2560 * 3584 + 2 * 2560 * 512
    layer = mixer + 2 * 2560 + 2560 * 64 + 8 * 3 * 2560 * 768
    assert (mixer, layer) == (20_971_520, 68_326_400)
    total = 4 * layer + 2 * 18992 * 2560 + 2560
    assert total == 370_547_200  # what early_route_moe.init_params makes at these sizes
    assert "370 547 200" in CFG["held"]["parameters"] and "5.52 GiB" in CFG["held"]["parameters"]
    assert total * 16 / 2**30 == pytest.approx(5.52, abs=0.005)


# ---- the reader ---------------------------------------------------------------------


def test_flash_cost_at_window_4096_and_group_7():
    bh, s, d, item, w = 56, 16384, 128, 2, 4096
    band, causal = w * s - w * (w - 1) // 2, s * (s + 1) // 2
    # forward: two products an entry; the one backward kernel: five
    assert reader.flash_cost("flash_fwd_win", bh, s, d, d, item, w)[0] == bh * band * 2 * 256
    assert reader.flash_cost("flash_bwd_win", bh, s, d, d, item, w)[0] == bh * band * 2 * 640
    assert reader.flash_cost("flash_fwd", bh, s, d, d, item)[0] == bh * causal * 2 * 256
    assert reader.flash_cost("flash_bwd", bh, s, d, d, item)[0] == bh * causal * 2 * 640
    # the three banded layers need 1.31 of the one global layer's operations: 17.7 | 13.5 TFLOP
    # a step of two sequences, forward and backward kernels
    banded = sum(reader.flash_cost(k, bh, s, d, d, item, w)[0]
                 for k in ("flash_fwd_win", "flash_bwd_win"))
    full = sum(reader.flash_cost(k, bh, s, d, d, item)[0] for k in ("flash_fwd", "flash_bwd"))
    assert 3 * banded == pytest.approx(17.68e12, rel=1e-2)
    assert full == pytest.approx(13.47e12, rel=1e-2)
    # compute-bound both: the bytes (K and V charged once a QUERY head, which the
    # kernels no longer move) would take far less than the operations
    for kernel, window in (("flash_fwd_win", w), ("flash_fwd", None)):
        ops, nbytes = reader.flash_cost(kernel, bh, s, d, d, item, window)
        assert ops / 197e12 > 5 * nbytes / 819e9
    args = load_json(HERE, "metrics", "kernels.window4096_flash_roofline_share.json")["args"]
    assert args["window"] == CFG["sliding_window_size"] == 4096 and args["kind"] == "window"
    assert load_json(HERE, "metrics", "kernels.global_flash_roofline_share.json")["args"] == {
        "quantity": "flash_roofline_share", "kind": "global"}


def test_the_scopes_the_metrics_read_are_the_programs():
    for name, scope in (("window_attention_ms", "window_attention"),
                        ("global_attention_ms", "global_attention"),
                        ("window_family_route_ms", "moe_route"), ("window_family_experts_ms", "moe_experts")):
        args = load_json(HERE, "metrics", f"train_step.{name}.json")["args"]
        assert args == {"quantity": "scope_ms", "match": scope} and scope in reader.SCOPES
    with open(os.path.join(ROOT, "byteps_tpu", "models", "early_route_moe.py")) as f:
        text = f.read()
    assert '"window_attention"' in text and '"global_attention"' in text


def test_a_program_without_the_family_reads_nothing():
    parent = {"steps": 4, "counters": {"before": {}, "after": {"d2h_bytes": 7}}, "trace": None,
              "global_batch": 1, "peak_flops_per_s": 197e12}
    for spec in MINE:
        m = load_json(HERE, "metrics", f"{spec['name']}.json")
        assert load("readers", f"{m['reader']}.py").read(parent, **m["args"]) is None


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL, "--seed", "2948000077",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {} and line["failed"] == 0
    assert {"samples_per_s", "setup_s"} <= set(line["rehearsal"])
    compared = line["compared"]
    assert compared["steps_failed"]["ok"] and compared["compiles_in_window"]["ok"]
    assert {"loss_off_reference", "update_off_all_leaves", "update_off_worst_leaf"} <= set(compared)
