"""The yardstick's checks of what the ``lfm2_24b_a2b_ep8`` configuration
brought: ``python -m pytest benchmark/tests -q`` (by hand; no device needed)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "lfm2_ep8_train8k", "lfm2_24b_a2b_ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]


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
reader = load("readers", "conv_moe.py")
#: the family's own metrics, in BENCHMARK.json's order; the last five list other cells too
NAMES = [
    "train_step.short_conv_ms", "train_step.conv_proj_ms", "train_step.gqa_attention_ms",
    "train_step.dense_mlp_ms", "train_step.sigmoid_route_ms", "train_step.small_experts_ms",
    "kernels.short_conv_roofline_share", "kernels.flash_roofline_share",
    "moe.held_slots_per_step", "moe.dropped_slots_per_step", "moe.fullest_expert_share"]
MINE = [m for m in BENCH["per_layer"] if m["name"] in NAMES]

#: the source's config.json, as the catalog has it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": ["full_attention" if i % 4 == 2 else "conv" for i in range(40)],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


def test_the_cell_finds_its_files_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "local_closed"
    assert len(cell["why"]) <= 200 and "8x their share" in cell["why"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    builder = load("builders", f"{CFG['builder']}.py")
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build"):
        assert callable(getattr(builder, name))
    assert sorted(m["name"] for m in MINE) == sorted(NAMES)
    for m in MINE:
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        assert spec["reader"] in ("conv_moe", "latent_moe", "step_rest")
        assert m["moves"] == "samples_per_s" and CELL in m["workloads"]
        assert os.path.exists(os.path.join(HERE, "readers", f"{spec['reader']}.py"))
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}


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
    assert differs == sorted(REDUCED)  # layer_types and rope_parameters stand whole
    assert CFG["published"] == {k: PUBLISHED[k] for k in REDUCED}
    # the floors of a cut: a whole period and four layers after the dense one, 8 experts,
    # an eighth of the rows
    builder = load("builders", "lfm2_moe.py")
    kinds = builder._kinds(CFG)
    assert kinds == [("conv", "dense"), ("full_attention", "moe"), ("conv", "moe"),
                     ("conv", "moe"), ("conv", "moe")]
    assert [t for t, _ in kinds] == PUBLISHED["layer_types"][1:6]
    assert sorted(t for t, _ in kinds[1:]) == sorted(PUBLISHED["layer_types"][2:6])  # a period
    assert CFG["num_experts"] >= 8 and CFG["router_width"] == PUBLISHED["num_experts"]
    assert CFG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    for key in ("deployment", "assumed", "held", "rehearsal"):
        assert CFG[key]
    assert "8 chips share each layer" in CFG["deployment"] and "1/8" in CFG["held"]["expert_load"]
    for key in ("head_dim", "tie_word_embeddings", "route_eps", "expert_bias", "aux_loss"):
        assert CFG["assumed"][key]
    for tol in ("reference_rtol", "reference_update_rtol"):
        assert CFG[tol]["value"] > 0 and "below" in CFG[tol]["why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_every_key_of_the_catalog_row_is_held():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    assert row["config"] == PUBLISHED
    assert row["source_url"] in CFG["source"]
    assert set(row["config"]) <= set(CFG)


def test_flops_per_sample_against_a_hand_count():
    builder = load("builders", "lfm2_moe.py")
    # a token's forward matrix products, in FLOP (2 a multiply-add)
    conv = 2 * (2048 * 6144 + 2048 * 2048)
    full = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    dense = 2 * 3 * 2048 * 11776
    expert = 2 * (2048 * 64 + 4 * 8 / 64 * 3 * 2048 * 1536)
    products = 4 * conv + full + dense + 4 * expert + 2 * 2048 * 8192
    assert products == pytest.approx(372.3e6, rel=1e-3)
    scores = (8192 + 1) / 2 * 32 * 2 * (64 + 64)  # causal: (S + 1) / 2 keys a query
    gates_and_taps = 4 * 2048 * (2 + 2 * 3)
    assert scores == pytest.approx(33.56e6, rel=1e-3) and gates_and_taps == 65536
    want = 3 * 8192 * (products + scores + gates_and_taps)
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-9)
    assert want == pytest.approx(9.976e12, rel=1e-3)  # 19.95 TFLOP a step of two sequences


def test_parameter_count_of_the_share():
    conv = 2048 + 2048 * 6144 + 3 * 2048 + 2048 * 2048
    full = 2048 + 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense = 2048 + 3 * 2048 * 11776
    expert = 2048 + 2048 * 64 + 64 + 8 * 3 * 2048 * 1536
    total = 4 * conv + full + dense + 4 * expert + 8192 * 2048 + 2048
    assert total == 469_285_248  # what conv_moe.init_params makes at these sizes
    assert "469.3 M" in CFG["held"]["parameters"] and "6.99 GiB" in CFG["held"]["parameters"]
    assert total * 16 / 2**30 == pytest.approx(6.99, abs=0.005)


# ---- the reader ---------------------------------------------------------------------


@pytest.mark.parametrize("path, want", [
    ("jit(train_step)/jvp(forward)/checkpoint/short_conv/mul", "short_conv"),
    ("jit(train_step)/transpose(jvp(forward))/checkpoint/rematted_computation/conv_proj/"
     "dot_general", "conv_proj"),
    ("jit(train_step)/transpose(jvp(forward))/checkpoint/gqa_attention/flash_bwd_dkv/"
     "pallas_call", "gqa_attention"),
    ("jit(train_step)/jvp(forward)/checkpoint/dense_mlp/dot_general", "dense_mlp"),
    ("jit(train_step)/jvp(forward)/checkpoint/moe_route/top_k", "moe_route"),
    ("jit(train_step)/jvp(forward)/checkpoint/moe_experts/ragged_dot", "moe_experts"),
    ("jit(train_step)/optimizer/mul", None),
    ("jit(train_step)/jvp(forward)/not_short_conv_at_all/add", None),
])
def test_an_operation_is_filed_under_the_first_scope_it_has(path, want):
    assert reader.scope_of(path) == want


def test_a_grouped_product_without_a_scope_path_is_the_experts():
    name = "%ragged-dot-none.53 = bf16[8,1536,2048]{2,1,0} custom-call(s32[1]{0} %x)"
    assert reader.scope_of("ragged-dot-none:", name) == "moe_experts"
    assert reader.scope_of("", name) == "moe_experts"
    assert reader.scope_of("jit(train_step)/optimizer/mul", "%fusion.3 = f32[8] fusion()") is None
    # a scope path, where there is one, decides
    assert reader.scope_of("jit(train_step)/jvp(forward)/dense_mlp/x", name) == "dense_mlp"


def test_the_convolutions_cost_is_of_the_mathematics_and_bound_by_bytes():
    args = load_json(HERE, "metrics", "kernels.short_conv_roofline_share.json")["args"]
    shape = {k: args[k] for k in ("channels", "taps", "item")}
    conv_layers = sum(t == "conv" for t, _ in load("builders", "lfm2_moe.py")._kinds(CFG))
    assert (args["layers"], args["tokens_per_sample"]) == (conv_layers, CFG["max_seq"])
    assert (shape["channels"], shape["taps"]) == (CFG["hidden_size"], CFG["conv_L_cache"])
    ops, nbytes = reader.short_conv_cost(16384, **shape)
    assert ops == 3 * 16384 * 2048 * 8  # as flops_per_sample counts a layer
    assert nbytes == 11 * 16384 * 2048 * 2 == 738_197_504  # the issue's 737 MB
    assert nbytes / 819e9 == pytest.approx(0.90e-3, rel=5e-3) and nbytes / 819e9 > ops / 197e12


def test_roofline_share_and_scope_time_on_a_hand_trace():
    conv, proj = "%fusion.1 = f32[8] fusion()", "%fusion.2 = f32[8] fusion()"
    grouped = "%ragged-dot-none.5 = bf16[8,8] custom-call()"
    trace = {
        "bench": [("bench.step.call", 10.0, 10.5), ("bench.step.block", 10.5, 11.0),
                  ("bench.step.call", 11.0, 11.5), ("bench.step.block", 11.5, 12.0)],
        "ops": [(conv, 10.1, 10.3), (conv, 11.1, 11.3), (proj, 10.6, 10.7), (conv, 9.0, 9.5),
                (grouped, 11.6, 11.62)],
        "paths": {conv: "jit(train_step)/jvp(forward)/checkpoint/short_conv/mul",
                  proj: "jit(train_step)/jvp(forward)/checkpoint/conv_proj/dot_general",
                  grouped: "ragged-dot-none:"},
        "spans": [],
    }
    assert reader.measure(trace, "scope_ms", "short_conv") == pytest.approx(200.0)
    assert reader.measure(trace, "scope_ms", "conv_proj") == pytest.approx(50.0)
    assert reader.measure(trace, "scope_ms", "moe_experts") == pytest.approx(10.0)
    assert reader.measure(trace, "scope_ms", "moe_route") is None  # the parent has no such scope
    # 6 ms of least time a step against 200 ms under the scope
    assert reader.measure(trace, "short_conv_roofline_share", "", 6e-3) == pytest.approx(3.0)
    trace["ops"] = trace["ops"][2:3]
    assert reader.measure(trace, "short_conv_roofline_share", "", 6e-3) is None
    with pytest.raises(ValueError, match="no quantity"):
        reader.measure(trace, "gdn_scan_roofline_share")


def test_a_program_without_the_family_reads_nothing():
    parent = {"steps": 4, "counters": {"before": {}, "after": {"d2h_bytes": 7}}, "trace": None,
              "global_batch": 2, "peak_flops_per_s": 197e12}
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
