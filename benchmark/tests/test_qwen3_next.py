"""The yardstick's checks of what the ``qwen3_next_80b_ep32`` configuration
brought: ``python -m pytest benchmark/tests -q`` (by hand; no device needed)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "qwen3_next_ep32_train16k", "qwen3_next_80b_ep32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
reader = load("readers", "delta_moe.py")
#: the family's own metrics: the entries that list the cell and read through its readers
MINE = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ()) and load_json(
    HERE, "metrics", f"{m['name']}.json")["reader"] in ("delta_moe", "latent_moe")]

#: the source's config.json, as the catalog has it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}


def test_the_cell_finds_its_files_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "local_closed"
    assert len(cell["why"]) <= 200 and "32x their share" in cell["why"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert "Qwen3-Next-80B-A3B-Instruct/blob/main/config.json" in entry["source"]
    assert "qwen3_next" in entry["source"]
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    builder = load("builders", f"{CFG['builder']}.py")
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build"):
        assert callable(getattr(builder, name))
    # six through delta_moe; the flash share, the three routing counters and the rows walked
    assert len(MINE) == 11
    for m in MINE:
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        assert spec["reader"] in ("delta_moe", "latent_moe") and m["moves"] == "samples_per_s"
        assert os.path.exists(os.path.join(HERE, "readers", f"{spec['reader']}.py"))


def test_the_builders_reference_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "builders", f"{CFG['builder']}.py")) as f:
        text = f.read()
    top = text[:text.index("# the program")]
    assert "import byteps_tpu" not in top and "from byteps_tpu" not in top
    assert "from byteps_tpu" in text[len(top):]  # the program's part does


def test_reduced_is_the_same_in_both_places_and_nothing_else_left_the_source():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"].split(" ")[0] in CFG["source"]
    differs = sorted(k for k, v in PUBLISHED.items() if CFG.get(k, "absent") != v)
    assert differs == sorted(CFG["reduced"])
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    # the floors of a cut: a whole period and four layers, 8 experts, an eighth of the rows
    assert CFG["num_hidden_layers"] >= 4
    assert CFG["num_hidden_layers"] % CFG["full_attention_interval"] == 0
    assert CFG["num_experts"] >= 8 and CFG["router_width"] == PUBLISHED["num_experts"]
    assert CFG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    for key in ("deployment", "assumed", "held", "rehearsal"):
        assert CFG[key]
    assert "32 chips share each layer" in CFG["deployment"] and "1/32" in CFG["held"]["expert_load"]
    for tol in ("reference_rtol", "reference_update_rtol"):
        assert CFG[tol]["value"] > 0 and "below" in CFG[tol]["why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_every_key_of_the_catalog_row_is_held():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert row["config"] == PUBLISHED
    assert row["source_url"] in CFG["source"]


def test_flops_per_sample_against_a_hand_count():
    builder = load("builders", "qwen3_next.py")
    # a token's forward matrix products, in FLOP (2 a multiply-add)
    linear = 2 * (2048 * (2048 + 2048 + 4096 + 4096 + 64) + 4096 * 2048)
    full = 2 * (2048 * (16 * 512 + 2 * 2 * 256) + 4096 * 2048)
    mlp = 2 * (2048 * 512 + 2048 + 3 * 2048 * 512 * (1 + 10 * 16 / 512))
    products = 3 * linear + full + 4 * mlp + 2 * 2048 * 18992
    assert products == pytest.approx(375.9e6, rel=1e-3)
    scores = (16384 + 1) / 2 * 16 * 2 * (256 + 256)  # causal: (S + 1) / 2 keys a query
    rule = 3 * 32 * 6 * 128 * 128
    assert scores == pytest.approx(134.2e6, rel=1e-3) and rule == pytest.approx(9.44e6, rel=1e-3)
    want = 3 * 16384 * (products + scores + rule)
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-9)
    assert want == pytest.approx(25.54e12, rel=1e-3)


def test_parameter_count_of_the_share():
    mlp = 2 * 2048 + 2048 * 512 + 16 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048
    linear = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048 + mlp
    full = 2048 * 16 * 512 + 2 * 2048 * 2 * 256 + 2 * 256 + 16 * 256 * 2048 + mlp
    total = 3 * linear + full + 2 * 18992 * 2048 + 2048
    assert total == 424_340_544  # what delta_moe.init_params makes at these sizes


# ---- the reader ---------------------------------------------------------------------


@pytest.mark.parametrize("path, want", [
    ("jit(train_step)/jvp(forward)/while/body/closed_call/while/body/closed_call/gdn_scan/"
     "while/body/dot_general", "gdn_scan"),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/while/body/closed_call/"
     "checkpoint/rematted_computation/gdn_proj/dot_general", "gdn_proj"),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/checkpoint/"
     "gated_attention/flash_bwd_dkv/pallas_call", "gated_attention"),
    ("jit(train_step)/jvp(forward)/while/body/closed_call/moe_route/top_k", "moe_route"),
    ("jit(train_step)/jvp(forward)/while/body/closed_call/moe_experts/ragged_dot", "moe_experts"),
    ("jit(train_step)/jvp(forward)/while/body/closed_call/moe_shared/dot_general", "moe_shared"),
    ("jit(train_step)/optimizer/mul", None),
    ("jit(train_step)/jvp(forward)/not_gdn_scan_at_all/add", None),
])
def test_an_operation_is_filed_under_the_first_scope_it_has(path, want):
    assert reader.scope_of(path) == want


def test_the_rules_cost_is_of_the_recurrence_and_bound_by_bytes():
    args = load_json(HERE, "metrics", "kernels.gdn_scan_roofline_share.json")["args"]
    shape = {k: args[k] for k in ("key_heads", "value_heads", "d_k", "d_v", "item")}
    assert (args["layers"], args["tokens_per_sample"]) == (3, CFG["max_seq"])
    assert (shape["key_heads"], shape["value_heads"], shape["d_k"], shape["d_v"]) == (
        CFG["linear_num_key_heads"], CFG["linear_num_value_heads"],
        CFG["linear_key_head_dim"], CFG["linear_value_head_dim"])
    ops, nbytes = reader.delta_rule_cost(16384, **shape)
    assert ops == 3 * 16384 * 32 * 6 * 128 * 128  # as flops_per_sample counts a layer
    assert nbytes == 3 * 16384 * (2 * (2 * 2048 + 3 * 4096) + 4 * 64)
    assert nbytes / 819e9 == pytest.approx(1.98e-3, rel=5e-3) and nbytes / 819e9 > ops / 197e12


def test_roofline_share_and_scope_time_on_a_hand_trace():
    scan, proj = "%fusion.1 = f32[8] fusion()", "%fusion.2 = f32[8] fusion()"
    trace = {
        "bench": [("bench.step.call", 10.0, 10.5), ("bench.step.block", 10.5, 11.0),
                  ("bench.step.call", 11.0, 11.5), ("bench.step.block", 11.5, 12.0)],
        "ops": [(scan, 10.1, 10.3), (scan, 11.1, 11.3), (proj, 10.6, 10.7), (scan, 9.0, 9.5)],
        "paths": {scan: "jit(train_step)/jvp(forward)/while/body/gdn_scan/while/body/dot_general",
                  proj: "jit(train_step)/jvp(forward)/while/body/gdn_proj/dot_general"},
        "spans": [],
    }
    assert reader.measure(trace, "scope_ms", "gdn_scan") == pytest.approx(200.0)
    assert reader.measure(trace, "scope_ms", "gdn_proj") == pytest.approx(50.0)
    assert reader.measure(trace, "scope_ms", "moe_route") is None  # the parent has no such scope
    # 6 ms of least time a step against 200 ms under the scope
    assert reader.measure(trace, "gdn_scan_roofline_share", "gdn_scan", 6e-3) == pytest.approx(3.0)
    trace["ops"] = trace["ops"][2:3]
    assert reader.measure(trace, "gdn_scan_roofline_share", "gdn_scan", 6e-3) is None


def test_a_program_without_the_family_reads_nothing():
    parent = {"steps": 4, "counters": {"before": {}, "after": {"d2h_bytes": 7}}, "trace": None,
              "global_batch": 1, "peak_flops_per_s": 197e12}
    for spec in MINE:
        m = load_json(HERE, "metrics", f"{spec['name']}.json")
        assert load("readers", f"{m['reader']}.py").read(parent, **m["args"]) is None


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL, "--seed", "2936000099",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {} and line["failed"] == 0
    assert {"samples_per_s", "setup_s"} <= set(line["rehearsal"])
    compared = line["compared"]
    assert compared["steps_failed"]["ok"] and compared["compiles_in_window"]["ok"]
    assert {"loss_off_reference", "update_off_all_leaves", "update_off_worst_leaf"} <= set(compared)
