"""The yardstick's checks of what the ``sdar_30b_a3b_ep8`` configuration brought:
``python -m pytest benchmark/tests -q`` (by hand; no device needed)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "sdar_ep8_train8k", "sdar_30b_a3b_ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


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
reader = load("readers", "block_diffusion_moe.py")
builder = load("builders", "sdar_moe.py")
METRICS = os.path.join(HERE, "metrics")
SCOPED = {"train_step.block_diffusion_attention_ms": "block_diffusion_attention",
          "train_step.copies_assembly_ms": "copies_assembly"}
COUNTED = {"block_diffusion.masked_tokens_per_step": "block_diffusion_masked_tokens",
           "block_diffusion.mean_weight_milli": "block_diffusion_weight_milli",
           "moe.held_slots_per_step": "moe_slots_held",
           "moe.dropped_slots_per_step": "moe_slots_dropped"}
SHARE = "moe.fullest_expert_share"
ROOFLINE = "kernels.block_diffusion_flash_roofline_share"
NAMES = set(SCOPED) | set(COUNTED) | {SHARE, ROOFLINE}
#: the family's eight per-layer metrics, listed since PR 70: five of its own, and the
#: three routing counters that every held-expert family reads through ``latent_moe``
MINE = [m for m in BENCH["per_layer"] if m["name"] in NAMES]
SHARED = {name for name in NAMES if name.startswith("moe.")}
#: accepted metrics whose ``workloads`` the cell was appended to
APPENDED = ("train_step.head_loss_ms", "train_step.embed_ms", "train_step.grouped_products_ms",
            "train_step.no_phase_ms", "train_step.dispatch_ms", "train_step.fold_ms",
            "train_step.idle_in_dispatch_ms", "moe.rows_walked_per_step",
            "train_step.softmax_route_ms", "train_step.held_experts_ms",
            "train_step.unscoped_ms")


def test_the_cell_finds_its_files_by_name():
    """By name alone: where in ``BENCHMARK.json``'s lists the entries stand is
    nobody's to assert — a later PR appends after them."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "local_closed"
    assert len(cell["why"]) <= 200 and "16384 rows" in cell["why"] and "block length 4" in cell["why"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["source"] == SOURCE
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    assert CFG["builder"] == "sdar_moe"
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build",
                 "_model_config", "_mesh4"):  # the last two: tools/latent_moe_precision.py's
        assert callable(getattr(builder, name))
    assert {m["name"] for m in MINE} == NAMES
    assert [w["name"] for w in BENCH["workloads"] if w["config"] == CONFIG] == [CELL]
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name in APPENDED:
        assert listed[name]["workloads"][-1] == CELL or CELL in listed[name]["workloads"]


@pytest.mark.parametrize("name", sorted(NAMES))
def test_every_metric_file_loads_and_names_the_cell(name):
    m = next(m for m in MINE if m["name"] == name)
    spec = load_json(METRICS, f"{name}.json")
    assert spec["reader"] == ("latent_moe" if name in SHARED else "block_diffusion_moe")
    assert spec["what"]
    assert m["moves"] == "samples_per_s"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (CELL in m["workloads"] if name in SHARED else m["workloads"] == [CELL])
    assert m["layer"] in ("train_step", "kernels", "moe")
    if name == ROOFLINE:
        assert m["unit"] == "%" and m["better"] == "higher" and m["source"] == "device_trace"
        # the block length is the configuration's own, read where the cell reads it
        assert spec["args"] == {"quantity": "flash_roofline_share", "config": CONFIG}
    elif name in SCOPED:
        assert spec["args"] == {"quantity": "scope_ms", "match": SCOPED[name]}
        assert m["unit"] == "ms" and m["source"] == "device_trace"
    elif name in COUNTED:
        assert spec["args"] == {"quantity": "counter_per_step", "counter": COUNTED[name]}
        assert m["unit"] == "count" and m["source"] == "program_counter"
    else:
        assert spec["args"] == {"quantity": "counter_share", "counter": "moe_fullest_expert_slots",
                                "of": "moe_slots_held"}


def test_the_builders_reference_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "builders", f"{CFG['builder']}.py")) as f:
        text = f.read()
    top = text[:text.index("# the program")]
    assert "import byteps_tpu" not in top and "from byteps_tpu" not in top
    assert "from byteps_tpu" in text[len(top):]  # the program's part does
    assert 'default_matmul_precision("highest")' in top


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_every_key_of_the_catalog_row_is_held_and_only_the_cut_differs():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
    assert row["source_url"] == SOURCE and SOURCE in CFG["source"]
    assert set(row["config"]) <= set(CFG)
    differs = sorted(k for k, v in row["config"].items() if CFG[k] != v)
    assert differs == sorted(REDUCED)  # every width stands
    assert CFG["published"] == {k: row["config"][k] for k in REDUCED}
    assert sorted(row["not_given"]) == ["block length", "noise schedule"]  # hence ``assumed``


def test_reduced_is_the_same_in_both_places_and_the_file_says_what_it_must():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == REDUCED
    assert (CFG["num_hidden_layers"], CFG["num_experts"], CFG["router_width"], CFG["vocab_size"],
            CFG["max_seq"], CFG["batch_per_chip"]) == (6, 16, 128, 18992, 8192, 1)
    assert (CFG["hidden_size"], CFG["num_attention_heads"], CFG["num_key_value_heads"],
            CFG["head_dim"], CFG["moe_intermediate_size"], CFG["num_experts_per_tok"]) == (
                2048, 32, 4, 128, 768, 8)
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    for key in ("source", "deployment", "assumed", "held", "rehearsal"):
        assert CFG[key]
    assert "8 chips share each layer" in CFG["deployment"]
    assert "645 623 296" in CFG["held"]["parameters"]
    for key in ("block_length", "noise_law", "mask_token_id", "qk_norm", "no_shift", "loss", "mask",
                "positions", "weights", "tokens", "optimizer", "compute_dtype", "remat"):
        assert CFG["assumed"][key], key
    assert (CFG["block_length"], CFG["noise_lo"], CFG["noise_hi"]) == (4, 0.45, 0.95)
    assert CFG["mask_token_id"] == CFG["vocab_size"] - 1
    assert CFG["rehearsal"]["mask_token_id"] == CFG["rehearsal"]["vocab_size"] - 1
    for tol in ("reference_rtol", "reference_update_rtol"):
        assert CFG[tol]["value"] > 0 and "below" in CFG[tol]["why"]
    assert CFG["reference_update_rtol"]["leaf_value"] > CFG["reference_update_rtol"]["value"]
    assert builder._model_config({**CFG, **CFG["rehearsal"]}).block_length == 4


def test_parameter_count_of_the_share():
    d, hd, fe = 2048, 128, 768
    attention = 2 * d * 32 * hd + 2 * d * 4 * hd
    layer = attention + 2 * hd + 2 * d + d * 128 + 16 * 3 * d * fe
    assert (attention, layer) == (18_874_368, 94_638_336)
    assert builder.parameters(CFG) == 6 * layer + 2 * 18992 * d + d == 645_623_296
    sys.path.insert(0, ROOT)
    from byteps_tpu.models import block_diffusion_moe

    shapes = block_diffusion_moe.layouts(builder._model_config(CFG))
    count = 0
    for shape, _, _ in shapes.values():
        n = 1
        for s in shape:
            n *= s
        count += n
    assert count == builder.parameters(CFG)


def test_flops_per_sample_against_a_hand_count():
    """What the loss depends on, and no more: five layers over both copies,
    the last over the noised copy's queries (its clean half gives k, v alone),
    the head over L · E[t] rows."""
    s, d, blk = 8192, 2048, 4
    q_o, k_v = 2 * d * 4096, 2 * d * 512
    mlp = d * 128 + 1 * 3 * d * 768  # top-8 x 16 held / 128 = one slot a row
    macs = 5 * 2 * s * (q_o + k_v + mlp) + s * (q_o + mlp) + 2 * s * k_v + s * 0.7 * d * 18992
    both = s * s + s * blk
    entries = 5 * both + both // 2
    want = 3 * (2 * macs + entries * 32 * 2 * 256)
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert builder.mean_noise(CFG) == pytest.approx(0.7)
    assert builder.visible_entries(s, blk) == both and both < (2 * s) * (2 * s + 1) // 2 * 0.51
    # the masked attention is the larger part of the count
    attention = 3 * entries * 32 * 2 * 256
    assert 0.5 < attention / want < 0.7


def test_the_flash_cost_is_the_mathematics_of_a_masked_call():
    bh, bh_kv, length, d, blk = 32, 4, 8192, 128, 4
    both = length * length + length * blk
    fwd = reader.flash_cost("flash_fwd_bd", bh, bh_kv, 2 * length, 2 * length, d, d, 2, blk)
    bwd = reader.flash_cost("flash_bwd_bd", bh, bh_kv, 2 * length, 2 * length, d, d, 2, blk)
    last = reader.flash_cost("flash_fwd_bd", bh, bh_kv, length, 2 * length, d, d, 2, blk)
    assert fwd[0] == bh * both * 2 * 256 and bwd[0] == bh * both * 2 * 5 * 128
    assert last[0] * 2 == fwd[0]
    # q and out once a query head, the logsumexp; k, v once a key/value head,
    # the clean copy's a second time for the second half's queries
    assert fwd[1] == bh * 2 * length * (2 * 256 + 4) + bh_kv * 3 * length * 2 * 256
    assert last[1] == bh * length * (2 * 256 + 4) + bh_kv * 2 * length * 2 * 256
    assert bwd[1] == (bh * 2 * length * (2 * 384 + 8) + bh_kv * 5 * length * 2 * 256)
    assert fwd[0] / 197e12 > fwd[1] / 819e9  # compute-bound
    with pytest.raises(ValueError, match="no block-diffusion flash kernel"):
        reader.flash_cost("flash_fwd", bh, bh_kv, length, 2 * length, d, d, 2, blk)


FWD = ("%flash_fwd_bd.3 = (bf16[32,16384,128], f32[32,16384,128]) custom-call(s32[144] %t, "
       "s32[16] %n, s32[144] %w, bf16[32,16384,128] %q, bf16[4,16384,128] %k, "
       "bf16[4,16384,128] %v)")


def test_a_call_is_told_by_its_name_and_sized_by_its_line():
    assert reader._flash_call(FWD) == ("flash_fwd_bd", 32, 4, 16384, 16384, 128, 128, 2)
    last = FWD.replace("bf16[32,16384,128] %q", "bf16[32,8192,128] %q")
    assert reader._flash_call(last)[3:5] == (8192, 16384)
    assert reader._flash_call(FWD.replace("flash_fwd_bd", "flash_bwd_bd"))[0] == "flash_bwd_bd"
    for other in ("flash_fwd.1", "flash_fwd_win.2", "fusion.7"):
        assert reader._flash_call(FWD.replace("flash_fwd_bd.3", other)) is None


def test_the_scopes_the_metrics_read_are_the_programs():
    assert reader.SCOPES == ("block_diffusion_attention", "copies_assembly")
    with open(os.path.join(ROOT, "byteps_tpu", "models", "block_diffusion_moe.py")) as f:
        text = f.read()
    assert 'ATTENTION, ASSEMBLY = "block_diffusion_attention", "copies_assembly"' in text
    for scope in ("ATTENTION", "ASSEMBLY", '"embed"', '"moe_experts"'):
        assert f"jax.named_scope({scope})" in text, scope
    of = reader.scope_of
    assert of("jit(s)/forward/checkpoint/block_diffusion_attention/dot") == \
        "block_diffusion_attention"
    assert of("jit(s)/transpose(jvp(forward))/copies_assembly/pad") == "copies_assembly"
    assert of("jit(s)/forward/moe_route/top_k") is None  # readers/delta_moe.py's, listed
    assert of("jit(s)/forward/lm_head/while/body/dot") is None and of("jit(s)/optimizer/add") is None


def test_the_times_are_disjoint_and_the_share_is_least_over_taken():
    """``measure`` on a hand-made trace: two steps; every operation is read
    under one name; the masked calls' least time over the time they took."""
    phases = reader._phases()
    xp = phases._xplane()
    bwd = FWD.replace("flash_fwd_bd.3", "flash_bwd_bd.4")
    trace = {"bench": [(xp.CALL, 0.0, 0.005), (xp.BLOCK, 0.005, 0.1), (xp.CALL, 0.1, 0.105),
                       (xp.BLOCK, 0.105, 0.2)],
             "ops": [(FWD, 0.010, 0.020), (bwd, 0.020, 0.050), ("%p", 0.05, 0.06),
                     ("%c", 0.06, 0.061), ("%r", 0.07, 0.072), ("%ragged-dot.1", 0.11, 0.13),
                     ("%o", 0.15, 0.16)],
             "paths": {FWD: "jit(s)/forward/block_diffusion_attention/flash",
                       bwd: "jit(s)/transpose(jvp(forward))/block_diffusion_attention/flash",
                       "%p": "jit(s)/forward/block_diffusion_attention/dot",
                       "%c": "jit(s)/forward/copies_assembly/concatenate",
                       "%r": "jit(s)/forward/moe_route/top_k", "%o": "jit(s)/optimizer/mul"}}
    got = {name: reader.measure(trace, "scope_ms", name) for name in reader.SCOPES}
    assert got == pytest.approx({"block_diffusion_attention": 25.0, "copies_assembly": 0.5})
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = sum(reader.flash_cost(k, 32, 4, 16384, 16384, 128, 128, 2, 4)[0]
                for k in reader.KERNELS) / 197e12
    assert reader.measure(trace, "flash_roofline_share", peaks=peaks, block=4) == pytest.approx(
        least / 0.040 * 100.0)
    with pytest.raises(ValueError, match="no quantity"):
        reader.measure(trace, "ssd_scan_roofline_share")


def test_the_counters_read_their_growth_a_step():
    run = {"steps": 4, "trace": None,
           "counters": {"before": {"block_diffusion_masked_tokens": 100, "moe_slots_held": 10,
                                   "moe_fullest_expert_slots": 5,
                                   "block_diffusion_weight_milli": 1000},
                        "after": {"block_diffusion_masked_tokens": 23036, "moe_slots_held": 410,
                                  "moe_fullest_expert_slots": 55,
                                  "block_diffusion_weight_milli": 5008}}}
    assert reader.read(run, "counter_per_step", counter="block_diffusion_masked_tokens") == 5734
    assert reader.read(run, "counter_per_step", counter="block_diffusion_weight_milli") == 1002
    # the routing counters go through latent_moe's reader, as every held-expert family's
    shared = load("readers", "latent_moe.py")
    run["counters"]["after"]["moe_slots_routed"] = 3200
    assert shared.read(run, "counter_share", counter="moe_fullest_expert_slots",
                       of="moe_slots_held") == 12.5
    assert shared.read(run, "counter_per_step", counter="moe_slots_dropped") == 0.0


def test_a_program_without_the_family_reads_nothing():
    parent = {"steps": 4, "counters": {"before": {}, "after": {"d2h_bytes": 7}},
              "trace": None, "global_batch": 1, "peak_flops_per_s": 197e12}
    for spec in MINE:
        m = load_json(METRICS, f"{spec['name']}.json")
        assert load("readers", f"{m['reader']}.py").read(parent, **m["args"]) is None


def test_the_blocked_reference_is_the_plain_one():
    """The builder's blocked copy against
    byteps_tpu/models/block_diffusion_moe_reference.py at a small size with
    blocks that cut: loss and every gradient, f32."""
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.data import block_diffusion_noise
    from byteps_tpu.models import block_diffusion_moe, block_diffusion_moe_reference

    small = {**CFG, "num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 8, "moe_intermediate_size": 16,
             "router_width": 8, "num_experts": 4, "held_expert_lo": 2, "num_experts_per_tok": 2,
             "vocab_size": 96, "max_seq": 32, "compute_dtype": "float32"}
    mcfg = builder._model_config(small)
    params = block_diffusion_moe.init_params(mcfg, jax.random.PRNGKey(3))
    clean = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, 95)
    noisy, weights = block_diffusion_noise(jax.random.PRNGKey(6), clean, 4, 95, 0.3, 0.9)
    old = builder.Q_BLOCK, builder.ROW_BLOCK
    builder.Q_BLOCK, builder.ROW_BLOCK = 4, 8
    try:
        got = jax.value_and_grad(builder.plain_loss(small))(params, (noisy, clean, weights))
    finally:
        builder.Q_BLOCK, builder.ROW_BLOCK = old
    want = jax.value_and_grad(lambda p: block_diffusion_moe_reference.loss(
        mcfg, p, noisy, clean, weights))(params)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name in params:
        scale = float(jnp.abs(want[1][name]).max())
        np.testing.assert_allclose(got[1][name], want[1][name], rtol=0, atol=2e-4 * scale,
                                   err_msg=name)


def test_make_state_never_holds_the_mask_token_as_data():
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    from jax.sharding import Mesh

    cfg = {**CFG, **CFG["rehearsal"], "num_hidden_layers": 1, "hidden_size": 32,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
           "moe_intermediate_size": 16, "compute_dtype": "float32"}
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    _, (noisy, clean, weights), batch = builder.make_state(cfg, jax.random.PRNGKey(1), mesh)
    noisy, clean, weights = (np.asarray(x) for x in (noisy, clean, weights))
    mask_id = cfg["mask_token_id"]
    assert batch == 1 and noisy.shape == clean.shape == weights.shape == (1, cfg["max_seq"])
    assert clean.max() < mask_id and ((noisy == mask_id) == (weights > 0)).all()
    assert 1 / cfg["noise_hi"] - 1e-6 <= weights[weights > 0].min()
    assert weights.max() <= 1 / cfg["noise_lo"] + 1e-6


def _wrong_noise(fault: str):
    """``data.block_diffusion_noise`` with the law moved, on the same draws."""
    import jax
    import jax.numpy as jnp

    def noise(key, tokens, block_length, mask_id, lo, hi):
        k_level, k_coin = jax.random.split(key)
        per = 1 if fault == "a_level_a_token" else block_length
        t = jnp.repeat(jax.random.uniform(k_level, tokens.shape[:-1] + (tokens.shape[-1] // per,),
                                          jnp.float32, lo, hi), per, axis=-1)
        masked = jax.random.uniform(k_coin, tokens.shape, jnp.float32) < t
        weight = 1.0 / (1.0 - t) if fault == "one_over_one_minus_t" else 1.0 / t
        return (jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens),
                jnp.where(masked | (fault == "every_token_weighs"), weight, 0.0))

    return noise


@pytest.mark.parametrize("fault", ["one_over_one_minus_t", "a_level_a_token", "every_token_weighs"])
def test_make_state_refuses_a_batch_under_another_law(fault, monkeypatch):
    """The program and the reference are fed ONE noised batch, the input
    pipeline's: ``make_state`` holds it to the builder's own ``plain_noise``."""
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import byteps_tpu.data

    cfg = {**CFG, **CFG["rehearsal"], "num_hidden_layers": 1, "hidden_size": 32,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
           "moe_intermediate_size": 16, "compute_dtype": "float32"}
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    monkeypatch.setattr(byteps_tpu.data, "block_diffusion_noise", _wrong_noise(fault))
    with pytest.raises(ValueError, match="the noising's law moved"):
        builder.make_state(cfg, jax.random.PRNGKey(1), mesh)


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL, "--seed", "2959000059",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {} and line["failed"] == 0
    assert {"samples_per_s", "setup_s"} <= set(line["rehearsal"])
    compared = line["compared"]
    assert compared["steps_failed"]["ok"] and compared["compiles_in_window"]["ok"]
    assert {"loss_off_reference", "update_off_all_leaves", "update_off_worst_leaf"} <= set(compared)


#: run.py with a fault planted in what the builder hands it: the program's
#: steps see every masked token at weight 1, or the noised copy under the
#: causal mask among its own rows (tools/latent_moe_precision.py's --fault, the
#: same two that were read on the chip)
PLANTED = """
import importlib.util, os, sys
spec = importlib.util.spec_from_file_location("run", sys.argv.pop(1))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
fault, builder = sys.argv.pop(1), run.load_module("builders", "sdar_moe")
sound = builder.build

def build(cfg, traffic, params, batch, mesh):
    noisy, clean, weights = batch
    if fault == "unit_weights":
        weights = (weights > 0).astype(weights.dtype)
    if fault == "causal_noisy":
        sys.path.insert(0, os.path.join(os.path.dirname(run.HERE), "tools"))
        import latent_moe_precision
        import byteps_tpu.models.block_diffusion_moe as family
        family.block_diffusion_attention = latent_moe_precision.causal_noisy_attention
    return sound(cfg, traffic, params, (noisy, clean, weights), mesh)

builder.build = build  # load_module caches: run.py's own call gets this module
raise SystemExit(run.main())
"""


@pytest.mark.parametrize("fault, caught_by", [
    ("unit_weights", {"loss_off_reference"}),
    ("causal_noisy", {"update_off_all_leaves", "update_off_worst_leaf"})])
def test_a_planted_fault_fails_the_harness_comparison(fault, caught_by):
    """Through run.py's own comparison at the rehearsal's size: the reference
    sees the batch's weights and the block mask; the limits are the
    configuration's.  Each fault is refused by the limit that carries it on
    the chip too: ignored weights by the loss (a third off), the wrong mask by
    the update (another gradient), whose limits stand at what this cell's
    start lets bf16 reach and so over what ignored weights alone move."""
    out = subprocess.run(
        [sys.executable, "-c", PLANTED, os.path.join(HERE, "run.py"), fault, "--workload", CELL,
         "--seed", "2959000060", "--seconds", "2", "--trace", "0", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    compared = json.loads(out.stdout.strip().splitlines()[-1])["compared"]
    assert {name for name, c in compared.items() if not c["ok"]} >= caught_by, compared
