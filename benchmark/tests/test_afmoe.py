"""The yardstick's checks of what the ``trinity_mini_ep16`` configuration
brought: ``python -m pytest benchmark/tests -q`` (by hand; no device needed)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "trinity_mini_ep16_train16k", "trinity_mini_ep16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
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
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144,
    "layer_types": [FULL if i % 4 == 3 else SLIDING for i in range(32)],
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}
NAMES = ["train_step.window_attention_ms", "train_step.global_attention_ms",
         "train_step.dense_mlp_ms", "train_step.window_family_route_ms", "train_step.shared_expert_ms",
         "train_step.window_family_experts_ms", "kernels.window_flash_roofline_share",
         "kernels.global_flash_roofline_share", "moe.held_slots_per_step",
         "moe.dropped_slots_per_step", "moe.fullest_expert_share"]
#: in the list's order; but for the one banded share they list other cells too
MINE = [m for m in BENCH["per_layer"] if m["name"] in NAMES]


def test_the_cell_finds_its_files_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "local_closed"
    assert len(cell["why"]) <= 200 and "16x their share" in cell["why"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    builder = load("builders", f"{CFG['builder']}.py")
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build"):
        assert callable(getattr(builder, name))
    assert sorted(m["name"] for m in MINE) == sorted(NAMES)
    assert [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]] == [
        "kernels.window_flash_roofline_share"]


@pytest.mark.parametrize("name", NAMES)
def test_every_metric_file_loads_and_names_the_cell(name):
    m = next(m for m in MINE if m["name"] == name)
    spec = load_json(HERE, "metrics", f"{name}.json")
    assert spec["reader"] in ("window_moe", "latent_moe", "step_rest")
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


def test_reduced_is_the_same_in_both_places_and_nothing_else_left_the_source():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == REDUCED
    assert entry["source"] in CFG["source"]
    differs = sorted(k for k, v in PUBLISHED.items() if CFG.get(k, "absent") != v)
    assert differs == sorted(REDUCED)  # layer_types stands whole, and every width
    assert CFG["published"] == {k: PUBLISHED[k] for k in REDUCED}
    # the floors of a cut: a whole period and four layers after the dense one, 8 experts,
    # an eighth of the rows
    builder = load("builders", "afmoe.py")
    kinds = builder._kinds(CFG)
    assert kinds == [(SLIDING, "dense"), (SLIDING, "moe"), (FULL, "moe"), (SLIDING, "moe"),
                     (SLIDING, "moe")]
    assert [t for t, _ in kinds] == PUBLISHED["layer_types"][1:6]
    assert sorted(t for t, _ in kinds[1:]) == sorted(PUBLISHED["layer_types"][0:4])  # a period
    assert CFG["num_experts"] >= 8 and CFG["router_width"] == PUBLISHED["num_experts"]
    assert CFG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    for key in ("deployment", "assumed", "held", "rehearsal"):
        assert CFG[key]
    assert "16 chips share each layer" in CFG["deployment"]
    assert "1/16" in CFG["held"]["expert_load"]
    for key in ("head_dim", "mask", "positions", "gate", "norms", "mup_enabled", "route_eps",
                "expert_bias", "aux_loss", "weights", "tokens", "optimizer", "compute_dtype",
                "remat"):
        assert CFG["assumed"][key]
    for tol in ("reference_rtol", "reference_update_rtol"):
        assert CFG[tol]["value"] > 0 and "below" in CFG[tol]["why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_every_key_of_the_catalog_row_is_held():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    assert row["config"] == PUBLISHED
    assert row["source_url"] in CFG["source"]
    assert set(row["config"]) <= set(CFG)
    for key in set(row["config"]) - set(REDUCED):
        assert CFG[key] == row["config"][key], key


def test_flops_per_sample_against_a_hand_count():
    builder = load("builders", "afmoe.py")
    # a token's forward matrix products, in FLOP (2 a multiply-add)
    mixer = 2 * (3 * 2048 * 4096 + 2 * 2048 * 512)  # q, gate, out; k, v
    dense = 2 * 3 * 2048 * 6144
    expert = 2 * (2048 * 128 + 3 * 2048 * 1024 + 8 * 8 / 128 * 3 * 2048 * 1024)
    products = 5 * mixer + dense + 4 * expert + 2 * 2048 * 25024
    assert products == pytest.approx(528.5e6, rel=1e-3)  # 264 M active parameters a token
    band = 2048 * 16384 - 2048 * 2047 // 2  # entries a head under the window
    causal = 16384 * 16385 // 2
    assert (builder.band_entries(16384, 2048), builder.band_entries(16384, None)) == (band, causal)
    assert builder.band_entries(128, 2048) == 128 * 129 // 2  # a window over the sequence
    assert band == pytest.approx(31.46e6, rel=1e-3) and causal == pytest.approx(134.2e6, rel=1e-3)
    scores = (4 * band + causal) * 32 * 2 * (128 + 128)
    want = 3 * (16384 * products + scores)
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-9)
    assert want == pytest.approx(38.8e12, rel=2e-3)  # a step of one sequence
    # the band is charged, not the causal triangle: four full layers would add 20 TFLOP
    assert 3 * 4 * (causal - band) * 32 * 512 == pytest.approx(20.2e12, rel=1e-2)


def test_parameter_count_of_the_share():
    mixer = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    assert mixer == 27_263_232
    dense = mixer + 4 * 2048 + 3 * 2048 * 6144
    expert = mixer + 4 * 2048 + 2048 * 128 + 128 + 3 * 2048 * 1024 + 8 * 3 * 2048 * 1024
    assert (dense, expert) == (65_020_160, 84_156_800)
    total = dense + 4 * expert + 2 * 25024 * 2048 + 2048
    assert total == 504_147_712  # what window_moe.init_params makes at these sizes
    assert "504 147 712" in CFG["held"]["parameters"] and "7.51 GiB" in CFG["held"]["parameters"]
    assert total * 16 / 2**30 == pytest.approx(7.51, abs=0.005)


# ---- the reader ---------------------------------------------------------------------


@pytest.mark.parametrize("path, want", [
    ("jit(train_step)/jvp(forward)/checkpoint/window_attention/flash_fwd_win/pallas_call",
     "window_attention"),
    ("jit(train_step)/transpose(jvp(forward))/checkpoint/global_attention/flash_bwd/"
     "pallas_call", "global_attention"),
    ("jit(train_step)/jvp(forward)/checkpoint/dense_mlp/dot_general", "dense_mlp"),
    ("jit(train_step)/jvp(forward)/checkpoint/moe_route/top_k", "moe_route"),
    ("jit(train_step)/jvp(forward)/checkpoint/shared_expert/dot_general", "shared_expert"),
    ("jit(train_step)/jvp(forward)/checkpoint/moe_experts/ragged_dot", "moe_experts"),
    ("jit(train_step)/optimizer/mul", None),
    ("jit(train_step)/jvp(forward)/not_window_attention_at_all/add", None),
])
def test_an_operation_is_filed_under_the_first_scope_it_has(path, want):
    assert reader.scope_of(path) == want


def test_a_grouped_product_without_a_scope_path_is_the_experts():
    name = "%ragged-dot-none.53 = bf16[8,1024,2048]{2,1,0} custom-call(s32[1]{0} %x)"
    assert reader.scope_of("ragged-dot-none:", name) == "moe_experts"
    assert reader.scope_of("jit(train_step)/optimizer/mul", "%fusion.3 = f32[8] fusion()") is None
    assert reader.scope_of("jit(train_step)/jvp(forward)/shared_expert/x", name) == "shared_expert"


def test_flash_cost_at_a_window_and_without():
    bh, s, d, item, w = 32, 16384, 128, 2, 2048
    band, causal = w * s - w * (w - 1) // 2, s * (s + 1) // 2
    # forward: two products an entry; the one backward kernel: five
    assert reader.flash_cost("flash_fwd_win", bh, s, d, d, item, w)[0] == bh * band * 2 * 256
    assert reader.flash_cost("flash_bwd_win", bh, s, d, d, item, w)[0] == bh * band * 2 * 640
    assert reader.flash_cost("flash_fwd", bh, s, d, d, item)[0] == bh * causal * 2 * 256
    assert reader.flash_cost("flash_bwd", bh, s, d, d, item)[0] == bh * causal * 2 * 640
    # a window over the sequence is the causal triangle
    assert reader.flash_cost("flash_fwd_win", bh, 1024, d, d, item, w) == \
        reader.flash_cost("flash_fwd", bh, 1024, d, d, item)
    # bytes: operands and results once, one f32 statistic a row forward and two backward
    assert reader.flash_cost("flash_fwd", bh, s, d, d, item)[1] == bh * s * (2 * 4 * d + 4)
    assert reader.flash_cost("flash_bwd", bh, s, 192, d, item)[1] == \
        bh * s * (2 * (4 * 192 + 3 * d) + 8)
    # the four banded layers need 0.94 of the one global layer's operations: 7.2 | 7.7 TFLOP
    banded = sum(reader.flash_cost(k, bh, s, d, d, item, w)[0]
                 for k in ("flash_fwd_win", "flash_bwd_win"))
    full = sum(reader.flash_cost(k, bh, s, d, d, item)[0] for k in ("flash_fwd", "flash_bwd"))
    assert 4 * banded == pytest.approx(7.22e12, rel=1e-2) and full == pytest.approx(7.70e12, rel=1e-2)
    with pytest.raises(ValueError, match="no flash kernel"):
        reader.flash_cost("flash_sideways", bh, s, d, d, item)
    args = load_json(HERE, "metrics", "kernels.window_flash_roofline_share.json")["args"]
    assert args["window"] == CFG["sliding_window"] and args["kind"] == "window"
    assert load_json(HERE, "metrics", "kernels.global_flash_roofline_share.json")["args"] == {
        "quantity": "flash_roofline_share", "kind": "global"}


def _call(kernel, n, bh=32, s=16384, d=128):
    shape = f"bf16[{bh},{s},{d}]{{2,1,0}}"
    return (f"%{kernel}.{n} = ({shape}) custom-call({shape} %q, {shape} %k, {shape} %v), "
            f'custom_call_target="tpu_custom_call"')


def test_roofline_shares_and_scope_time_on_a_hand_trace():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    fwd_win, bwd_win, fwd, bwd = (_call(k, i) for i, k in enumerate(reader.KERNELS))
    assert reader._flash_call(fwd_win)[:2] == ("flash_fwd_win", 32)  # the longest name first
    assert reader._flash_call(bwd)[0] == "flash_bwd" and reader._flash_call("%fusion.1") is None
    grouped = "%ragged-dot-none.5 = bf16[8,8] custom-call()"
    trace = {
        "bench": [("bench.step.call", 10.0, 10.5), ("bench.step.block", 10.5, 11.0),
                  ("bench.step.call", 11.0, 11.5), ("bench.step.block", 11.5, 12.0)],
        "ops": [(fwd_win, 10.10, 10.11), (bwd_win, 10.20, 10.22), (fwd, 10.30, 10.32),
                (bwd, 10.40, 10.45), (fwd_win, 9.0, 9.5), (grouped, 11.6, 11.62)],
        "paths": {fwd_win: "jit(train_step)/jvp(forward)/checkpoint/window_attention/x",
                  bwd_win: "jit(train_step)/transpose(jvp(forward))/window_attention/x",
                  fwd: "jit(train_step)/jvp(forward)/checkpoint/global_attention/x",
                  bwd: "jit(train_step)/transpose(jvp(forward))/global_attention/x",
                  grouped: "ragged-dot-none:"},
        "spans": [],
    }
    assert reader.measure(trace, "scope_ms", "window_attention") == pytest.approx(15.0)
    assert reader.measure(trace, "scope_ms", "global_attention") == pytest.approx(35.0)
    assert reader.measure(trace, "scope_ms", "moe_experts") == pytest.approx(10.0)
    assert reader.measure(trace, "scope_ms", "moe_route") is None  # the parent has no such scope
    least = {k: reader.flash_cost(k, 32, 16384, 128, 128, 2, 2048 if k.endswith("win") else None)[0]
             / 197e12 for k in reader.KERNELS}
    share = reader.measure(trace, "flash_roofline_share", peaks=peaks, kind="window", window=2048)
    assert share == pytest.approx((least["flash_fwd_win"] + least["flash_bwd_win"]) / 0.03 * 100)
    share = reader.measure(trace, "flash_roofline_share", peaks=peaks, kind="global")
    assert share == pytest.approx((least["flash_fwd"] + least["flash_bwd"]) / 0.07 * 100)
    assert 0 < share < 100
    trace["ops"] = trace["ops"][2:4]  # a program without the band
    assert reader.measure(trace, "flash_roofline_share", peaks=peaks, kind="window",
                          window=2048) is None
    with pytest.raises(ValueError, match="no quantity"):
        reader.measure(trace, "gdn_scan_roofline_share")


def test_a_program_without_the_family_reads_nothing():
    parent = {"steps": 4, "counters": {"before": {}, "after": {"d2h_bytes": 7}}, "trace": None,
              "global_batch": 1, "peak_flops_per_s": 197e12}
    for spec in MINE:
        m = load_json(HERE, "metrics", f"{spec['name']}.json")
        assert load("readers", f"{m['reader']}.py").read(parent, **m["args"]) is None


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL, "--seed", "2940000077",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {} and line["failed"] == 0
    assert {"samples_per_s", "setup_s"} <= set(line["rehearsal"])
    compared = line["compared"]
    assert compared["steps_failed"]["ok"] and compared["compiles_in_window"]["ok"]
    assert {"loss_off_reference", "update_off_all_leaves", "update_off_worst_leaf"} <= set(compared)
