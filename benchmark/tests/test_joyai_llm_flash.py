"""The yardstick's checks of what the ``joyai_llm_flash_ep32`` configuration
brought: ``python -m pytest benchmark/tests -q`` (by hand; no device needed)."""

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "joyai_flash_ep32_train8k", "joyai_llm_flash_ep32"


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
reader = load("readers", "latent_moe.py")


def test_the_cell_finds_its_files_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and "32x its share" in cell["why"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert os.path.exists(os.path.join(HERE, "builders", f"{CFG['builder']}.py"))
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    builder = load("builders", f"{CFG['builder']}.py")
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build"):
        assert callable(getattr(builder, name))
    mine = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "train_step.attention_ms", "train_step.moe_route_ms", "train_step.moe_experts_ms",
        "train_step.mtp_ms"]
    for m in mine:
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        assert spec["reader"] == "latent_moe" and m["moves"] == "samples_per_s"


def test_reduced_is_the_same_in_both_places_and_nothing_else_left_the_source():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] in CFG["source"]
    published = {  # the source's config.json, as the catalog has it
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 7168,
        "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1,
        "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 129280,
    }
    differs = sorted(k for k, v in published.items() if CFG.get(k, "absent") != v)
    assert differs == sorted(CFG["reduced"])
    assert CFG["published"] == {k: published[k] for k in CFG["reduced"]}
    # the floors of a cut: 4 expert layers after the dense one, 8 experts, an eighth of the rows
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4
    assert CFG["n_routed_experts"] >= 8 and CFG["router_width"] == published["n_routed_experts"]
    assert CFG["vocab_size"] * 8 >= published["vocab_size"]
    for key in ("deployment", "assumed", "held"):
        assert CFG[key]
    assert "32 chips share each layer" in CFG["deployment"] and "1/32" in CFG["held"]["expert_load"]


def test_flops_per_sample_against_a_hand_count():
    builder = load("builders", "joyai_llm_flash.py")
    # a token's forward matrix products, in MFLOP (2 a multiply-add)
    attention = 2 * (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048)
    dense = attention + 2 * 3 * 2048 * 7168
    expert = attention + 2 * (2048 * 256 + 3 * 2048 * 768 * (1 + 8 * 8 / 256))
    heads = 2 * 2 * 2048 * 16160
    products = dense + 5 * expert + 2 * 4096 * 2048 + heads
    assert products == pytest.approx(617.7e6, rel=1e-3)
    scores = 6 * (8192 + 1) / 2 * 32 * 2 * (192 + 128)  # causal: (S + 1) / 2 keys a query
    assert scores == pytest.approx(503.4e6, rel=1e-3)
    want = 3 * 8192 * (products + scores)
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-9)
    assert want == pytest.approx(27.55e12, rel=1e-3)


def test_parameter_count_of_the_share():
    # attention with its three norms, then the MLP's norm
    a = (2048 + 2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256
         + 4096 * 2048)
    expert_layer = a + 2048 + 2048 * 256 + 256 + 9 * 3 * 2048 * 768
    total = (a + 2048 + 3 * 2048 * 7168) + 5 * expert_layer + 2 * 16160 * 2048 \
        + 2048 + 2 * 2048 * 2048 + 3 * 2048
    assert total == 491_697_408  # what latent_moe.init_params makes at these sizes


# ---- the reader ---------------------------------------------------------------------


@pytest.mark.parametrize("path, want", [
    ("jit(train_step)/jvp(forward)/while/body/checkpoint/mla_attention/dot_general", "mla_attention"),
    ("jit(train_step)/transpose(jvp(forward))/while/body/rematted_computation/moe_route/top_k",
     "moe_route"),
    ("jit(train_step)/jvp(forward)/mtp/while/body/checkpoint/mla_attention/flash_fwd", "mtp"),
    ("jit(train_step)/jvp(forward)/while/body/checkpoint/moe_experts/ragged_dot", "moe_experts"),
    ("jit(train_step)/jvp(forward)/while/body/checkpoint/moe_shared/dot_general", "moe_shared"),
    ("jit(train_step)/optimizer/mul", None),
    ("jit(train_step)/jvp(forward)/not_mtp_at_all/add", None),
])
def test_an_operation_is_filed_under_the_first_scope_it_has(path, want):
    assert reader.scope_of(path) == want


FWD = ("%flash_fwd.31 = (bf16[64,8192,128]{2,1,0}, f32[64,8192,128]{2,1,0}) custom-call("
       "bf16[64,8192,192]{2,1,0} %a, bf16[64,8192,192]{2,1,0} %b, bf16[64,8192,128]{2,1,0} %c), "
       "custom_call_target=\"tpu_custom_call\"")


def test_a_kernel_call_is_read_from_its_own_line():
    assert reader._flash_call(FWD) == ("flash_fwd", 64, 8192, 192, 128, 2)
    assert reader._flash_call(FWD.replace("flash_fwd", "flash_bwd_dkv"))[0] == "flash_bwd_dkv"
    assert reader._flash_call("%fusion.3 = bf16[8] fusion(bf16[8] %x)") is None


def test_kernel_costs_are_of_the_mathematics():
    entries = 64 * 8192 * 8193 // 2
    ops = {k: reader.flash_call_cost(k, 64, 8192, 192, 128, 2)[0]
           for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    assert ops["flash_fwd"] == entries * 2 * (192 + 128)
    # the backward pass's five products, shared by its two kernels: 2.5 x ... of the forward
    assert ops["flash_bwd_dq"] + ops["flash_bwd_dkv"] == entries * 2 * (3 * 192 + 2 * 128)
    # one layer's forward over the two sequences, as flops_per_sample counts it
    assert ops["flash_fwd"] == pytest.approx(2 * 8192 * 503.4e6 / 6, rel=1e-3)


def test_roofline_share_and_scope_time_on_a_hand_trace():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, _ = reader.flash_call_cost("flash_fwd", 64, 8192, 192, 128, 2)
    least = ops / peaks["bf16_flops_per_s"]
    trace = {
        "bench": [("bench.step.call", 10.0, 10.5), ("bench.step.block", 10.5, 11.0)],
        "ops": [(FWD, 10.1, 10.1 + 2 * least), ("%fusion.9 = f32[8] fusion()", 10.6, 10.7),
                (FWD, 9.0, 9.5)],  # before the window: not counted
        "paths": {FWD: "jit(train_step)/jvp(forward)/while/body/checkpoint/mla_attention/flash_fwd",
                  "%fusion.9 = f32[8] fusion()": "jit(train_step)/jvp(forward)/mtp/add"},
        "spans": [],
    }
    assert reader.measure(trace, "flash_roofline_share", peaks=peaks) == pytest.approx(50.0)
    assert reader.measure(trace, "scope_ms", "mla_attention") == pytest.approx(2e3 * least)
    assert reader.measure(trace, "scope_ms", "mtp") == pytest.approx(100.0)
    assert reader.measure(trace, "scope_ms", "moe_route") is None  # the parent has no such scope
    trace["ops"] = trace["ops"][1:2]
    assert reader.measure(trace, "flash_roofline_share", peaks=peaks) is None


def test_counters_per_step_and_what_a_program_without_them_reads():
    run = {"steps": 4, "counters": {
        "before": {"moe_slots_routed": 100, "moe_slots_held": 10, "moe_fullest_expert_slots": 4},
        "after": {"moe_slots_routed": 900, "moe_slots_held": 90, "moe_fullest_expert_slots": 24}}}
    read = reader.read
    assert read(run, quantity="counter_per_step", counter="moe_slots_held") == 20.0
    # a counter that never grew is absent from a snapshot: 0, not nothing
    assert read(run, quantity="counter_per_step", counter="moe_slots_dropped") == 0.0
    assert read(run, quantity="counter_share", counter="moe_fullest_expert_slots",
                of="moe_slots_held") == 25.0
    parent = {"steps": 4, "counters": {"before": {}, "after": {"d2h_bytes": 7}}, "trace": None}
    for spec in BENCH["per_layer"]:  # every metric through this reader, whatever cells it lists
        m = load_json(HERE, "metrics", f"{spec['name']}.json")
        if m["reader"] == "latent_moe":
            assert read(parent, **m["args"]) is None
