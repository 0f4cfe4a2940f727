"""The yardstick's checks of what the ``ouro_2_6b_pp8`` configuration brought:
``python -m pytest benchmark/tests -q`` (by hand; no device needed)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "ouro_pp8_train8k", "ouro_2_6b_pp8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
REDUCED = ["num_hidden_layers"]


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
reader = load("readers", "looped_dense.py")
builder = load("builders", "ouro.py")
METRICS = os.path.join(HERE, "metrics")
SCOPED = {"train_step.loop_attention_ms": "loop_attention", "train_step.loop_mlp_ms": "loop_mlp",
          "train_step.loop_heads_ms": "loop_heads", "train_step.exit_gate_ms": "exit_gate",
          "train_step.loop_carry_ms": "loop_carry"}
COUNTED = {"looped.layer_passes_per_step": "looped_layer_passes",
           "looped.mean_exit_step_milli": "looped_exit_step_milli"}
NAMES = set(SCOPED) | set(COUNTED) | {"kernels.mha128_flash_roofline_share"}
#: the family's eight per-layer metrics, listed since PR 70
MINE = [m for m in BENCH["per_layer"] if m["name"] in NAMES]
#: the general entries that list the cell too (``lm_head`` stands inside ``loop_heads`` here)
APPENDED = ("train_step.head_loss_ms", "train_step.embed_ms", "train_step.unscoped_ms",
            "train_step.no_phase_ms", "train_step.dispatch_ms", "train_step.idle_in_dispatch_ms")


def test_the_cell_finds_its_files_by_name():
    """By name alone: where in ``BENCHMARK.json``'s lists the entries stand is
    nobody's to assert — a later PR appends after them."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "local_closed"
    assert len(cell["why"]) <= 200 and "20 %" in cell["why"] and "3 % in 48" in cell["why"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["source"] == SOURCE
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    assert CFG["builder"] == "ouro"
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build",
                 "_model_config", "_mesh4"):  # the last two: tools/latent_moe_precision.py's
        assert callable(getattr(builder, name))
    assert {m["name"] for m in MINE} == NAMES
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    assert all(CELL in listed[name]["workloads"] for name in APPENDED)
    assert [w["name"] for w in BENCH["workloads"] if w["config"] == CONFIG] == [CELL]


@pytest.mark.parametrize("name", sorted(NAMES))
def test_every_metric_file_loads_and_names_the_cell(name):
    m = next(m for m in MINE if m["name"] == name)
    spec = load_json(METRICS, f"{name}.json")
    assert spec["reader"] == "looped_dense" and spec["what"]
    assert m["moves"] == "samples_per_s"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["workloads"] == [CELL] and m["layer"] in ("train_step", "kernels")
    if name.endswith("roofline_share"):
        assert m["unit"] == "%" and m["better"] == "higher" and m["source"] == "device_trace"
        assert spec["args"] == {"quantity": "flash_roofline_share"}
    elif name in SCOPED:
        assert spec["args"] == {"quantity": "scope_ms", "match": SCOPED[name]}
        assert m["unit"] == "ms" and m["source"] == "device_trace"
    else:
        assert spec["args"] == {"quantity": "counter_per_step", "counter": COUNTED[name]}
        assert m["unit"] == "count" and m["source"] == "program_counter"


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
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert row["source_url"] == SOURCE and SOURCE in CFG["source"] and len(CFG["source"]) <= 200
    assert set(row["config"]) <= set(CFG)
    differs = sorted(k for k, v in row["config"].items() if CFG[k] != v)
    assert differs == REDUCED  # every width, the loop count and the vocabulary stand
    assert CFG["published"] == {"num_hidden_layers": 48}
    assert CFG["layer_types"] == ["full_attention"] * 48


def test_reduced_is_the_same_in_both_places_and_the_file_says_what_it_must():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == REDUCED
    assert (CFG["num_hidden_layers"], CFG["total_ut_steps"], CFG["max_seq"],
            CFG["batch_per_chip"]) == (6, 4, 8192, 1)
    assert (CFG["hidden_size"], CFG["num_attention_heads"], CFG["num_key_value_heads"],
            CFG["head_dim"], CFG["intermediate_size"], CFG["vocab_size"]) == (
                2048, 16, 16, 128, 5632, 49152)
    for key in ("deployment", "assumed", "held", "rehearsal"):
        assert CFG[key]
    assert "8 stages of 6 layers" in CFG["deployment"] and "RING" in CFG["deployment"]
    assert "crosses it total_ut_steps = 4 times" in CFG["deployment"]
    assert "first stage" in CFG["deployment"] and "last" in CFG["deployment"]
    for key in ("exit_beta", "norm_in_loop", "gate", "early_exit_threshold", "positions", "norms",
                "weights", "tokens", "optimizer", "compute_dtype", "remat"):
        assert CFG["assumed"][key], key
    assert CFG["exit_beta"] == 0.1
    for tol in ("reference_rtol", "reference_update_rtol"):
        assert CFG[tol]["value"] > 0 and "below" in CFG[tol]["why"]
    assert CFG["reference_update_rtol"]["leaf_value"] > CFG["reference_update_rtol"]["value"]
    assert builder._model_config({**CFG, **CFG["rehearsal"]}).n_loops == 4


def test_parameter_count_of_the_stage():
    attention, mlp = 4 * 2048 * 16 * 128, 3 * 2048 * 5632
    assert (attention, mlp) == (4 * 4_194_304, 3 * 11_534_336)
    layer = attention + mlp + 4 * 2048
    assert layer == 51_388_416
    total = 6 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert total == 509_661_185 == builder.parameters(CFG)
    assert "509 661 185" in CFG["held"]["parameters"] and "7.59 GiB" in CFG["held"]["parameters"]
    assert total * 16 / 2**30 == pytest.approx(7.59, abs=0.005)
    assert total * 20 / 2**30 == pytest.approx(9.49, abs=0.005)
    # the program's layout is the same count
    sys.path.insert(0, ROOT)
    import math

    from byteps_tpu.models import looped_dense

    shapes = looped_dense.layouts(builder._model_config(CFG))
    assert sum(math.prod(s) for s, _, _ in shapes.values()) == total


def test_flops_per_sample_against_a_hand_count():
    """All four passes: 6 x parameters a pass + causal attention + four heads."""
    tokens, loops = 8192, 4
    layer_macs = 4 * 2048 * 2048 + 3 * 2048 * 5632  # q, k, v, o; gate, up, down
    stack = 3 * loops * tokens * 2 * 6 * layer_macs
    assert stack == pytest.approx(60.6e12, rel=2e-3)
    causal = 8192 * 8193 // 2
    attention = 3 * loops * 6 * causal * 16 * 2 * (128 + 128)
    assert attention == pytest.approx(19.8e12, rel=2e-3)  # 23.1 with the scores made again
    heads = 3 * loops * tokens * 2 * 2048 * 49152
    assert heads == pytest.approx(19.8e12, rel=2e-3)
    gate = 3 * loops * tokens * 2 * 2048
    want = stack + attention + heads + gate
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(100.2e12, rel=1e-3)
    assert heads / want == pytest.approx(0.20, abs=0.005)  # the cell's why
    whole = builder.flops_per_sample({**CFG, "num_hidden_layers": 48})
    assert heads / whole == pytest.approx(0.03, abs=0.005)
    # one pass is a quarter: mfu counts all four
    assert builder.flops_per_sample({**CFG, "total_ut_steps": 1}) == pytest.approx(want / 4)


# ---- the reader ---------------------------------------------------------------------


def test_the_flash_cost_is_the_mathematics_of_a_causal_call():
    entries = 16 * (8192 * 8193 // 2)
    ops, nbytes = reader.flash_cost("flash_fwd", 16, 8192, 128, 128, 2)
    assert ops == entries * 2 * (128 + 128)  # QK^T and PV
    assert nbytes == 16 * 8192 * (2 * 4 * 128 + 4)  # q, k, v, out in bf16; the logsumexp
    bops, bbytes = reader.flash_cost("flash_bwd", 16, 8192, 128, 128, 2)
    assert bops == entries * 2 * 5 * 128  # the scores again, dV, dP, dQ, dK
    assert bbytes == 16 * 8192 * (2 * 7 * 128 + 8)
    # compute-bound both ways: 1.40 ms and 3.49 ms a call at the peak
    assert ops / 197e12 == pytest.approx(1.395e-3, rel=1e-2) and ops / 197e12 > nbytes / 819e9
    assert bops / 197e12 == pytest.approx(3.488e-3, rel=1e-2) and bops / 197e12 > bbytes / 819e9
    # the issue's 23.1 TFLOP a step charges the kernels' seven products a pass
    assert 24 * (ops + bops) == pytest.approx(23.1e12, rel=2e-3)
    with pytest.raises(ValueError, match="no full causal flash kernel"):
        reader.flash_cost("flash_fwd_win", 16, 8192, 128, 128, 2)


def test_a_call_is_told_by_its_name_and_sized_by_its_line():
    line = ("%flash_fwd.7 = (bf16[16,8192,128]{2,1,0}, f32[16,8192]{1,0}) custom-call("
            "bf16[16,8192,128]{2,1,0} %q, bf16[16,8192,128]{2,1,0} %k, "
            "bf16[16,8192,128]{2,1,0} %v), custom_call_target=\"tpu_custom_call\"")
    assert reader._flash_call(line) == ("flash_fwd", 16, 8192, 128, 128, 2)
    assert reader._flash_call(line.replace("flash_fwd", "flash_bwd"))[0] == "flash_bwd"
    assert reader._flash_call(line.replace("flash_fwd", "flash_fwd_win")) is None  # no band here
    assert reader._flash_call("%fusion.3 = bf16[16,8192,128] fusion(") is None


def test_the_scopes_the_metrics_read_are_the_programs():
    assert reader.SCOPES == ("exit_gate", "loop_heads", "loop_attention", "loop_mlp")
    with open(os.path.join(ROOT, "byteps_tpu", "models", "looped_dense.py")) as f:
        text = f.read()
    for scope in reader.SCOPES + (reader.LOOP, "embed"):
        assert f'jax.named_scope("{scope}")' in text, scope
    of = reader.scope_of
    assert of("jit(train_step)/forward/loop_steps/while/body/loop_attention/dot") == "loop_attention"
    assert of("jit(s)/transpose(jvp(forward))/loop_steps/while/body/loop_mlp/mul") == "loop_mlp"
    assert of("jit(s)/forward/loop_steps/while/body/loop_heads/rsqrt") == "loop_heads"
    assert of("jit(s)/forward/loop_heads/lm_head/while/body/dot_general") == "loop_heads"
    assert of("jit(s)/forward/exit_gate/log") == "exit_gate"
    # under the loop and under nothing inside it: what looping costs
    assert of("jit(s)/transpose(jvp(forward))/loop_steps/while/body/add_any") == "loop_carry"
    assert of("jit(s)/forward/loop_steps/while/body/while/body/dynamic_slice") == "loop_carry"
    assert of("jit(s)/optimizer/add") is None and of("jit(s)/forward/embed/gather") is None
    assert of("") is None


def test_the_five_times_are_disjoint_and_the_share_is_least_over_taken():
    """``measure`` on a hand-made trace: two steps; every operation is read
    under one name; the flash calls' least time over the time they took."""
    phases = reader._phases()
    xp = phases._xplane()
    fwd = ("%flash_fwd.1 = bf16[16,8192,128] custom-call(bf16[16,8192,128] %q, "
           "bf16[16,8192,128] %k, bf16[16,8192,128] %v)")
    bwd = fwd.replace("flash_fwd.1", "flash_bwd.2")
    trace = {"bench": [(xp.CALL, 0.0, 0.005), (xp.BLOCK, 0.005, 0.1), (xp.CALL, 0.1, 0.105),
                       (xp.BLOCK, 0.105, 0.2)],
             "ops": [(fwd, 0.010, 0.012), (bwd, 0.020, 0.025), ("%m", 0.03, 0.05),
                     ("%h", 0.05, 0.06), ("%g", 0.06, 0.062), ("%c", 0.11, 0.13),
                     ("%o", 0.15, 0.16)],
             "paths": {fwd: "jit(s)/forward/loop_steps/loop_attention/flash",
                       bwd: "jit(s)/transpose(jvp(forward))/loop_steps/loop_attention/flash",
                       "%m": "jit(s)/forward/loop_steps/loop_mlp/dot",
                       "%h": "jit(s)/forward/loop_heads/lm_head/dot",
                       "%g": "jit(s)/forward/exit_gate/exp",
                       "%c": "jit(s)/transpose(jvp(forward))/loop_steps/add_any",
                       "%o": "jit(s)/optimizer/mul"}}
    assert phases.window(trace["bench"]) == (0.0, 0.2, 2)
    got = {name: reader.measure(trace, "scope_ms", name)
           for name in reader.SCOPES + (reader.CARRY,)}
    assert got == pytest.approx({"loop_attention": 3.5, "loop_mlp": 10.0, "loop_heads": 5.0,
                                 "exit_gate": 1.0, "loop_carry": 10.0})
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = sum(reader.flash_cost(k, 16, 8192, 128, 128, 2)[0] for k in reader.KERNELS) / 197e12
    assert reader.measure(trace, "flash_roofline_share", peaks=peaks) == pytest.approx(
        least / 0.007 * 100.0)
    with pytest.raises(ValueError, match="no quantity"):
        reader.measure(trace, "ssd_scan_roofline_share")


def test_the_counters_read_their_growth_a_step():
    run = {"steps": 4, "trace": None,
           "counters": {"before": {"looped_layer_passes": 72, "looped_exit_step_milli": 5625},
                        "after": {"looped_layer_passes": 168, "looped_exit_step_milli": 13125}}}
    assert reader.read(run, "counter_per_step", counter="looped_layer_passes") == 24
    assert reader.read(run, "counter_per_step", counter="looped_exit_step_milli") == 1875


def test_a_program_without_the_family_reads_nothing():
    parent = {"steps": 4, "counters": {"before": {}, "after": {"d2h_bytes": 7, "moe_slots_routed": 0}},
              "trace": None, "global_batch": 1, "peak_flops_per_s": 197e12}
    for spec in MINE:
        m = load_json(METRICS, f"{spec['name']}.json")
        assert load("readers", f"{m['reader']}.py").read(parent, **m["args"]) is None


def test_the_blocked_reference_is_the_plain_one():
    """The builder's blocked copy against byteps_tpu/models/looped_dense_reference.py
    at a small size with blocks that cut: loss and every gradient, f32."""
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.models import looped_dense, looped_dense_reference

    small = {**CFG, "num_hidden_layers": 2, "total_ut_steps": 3, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
             "intermediate_size": 96, "vocab_size": 96, "max_seq": 32, "compute_dtype": "float32"}
    mcfg = builder._model_config(small)
    params = looped_dense.init_params(mcfg, jax.random.PRNGKey(3))
    params["gate_w"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (64,))
    params["gate_b"] = jnp.asarray(-0.2, jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, 96)
    targets = jnp.roll(tokens, -1, axis=1).at[1, 7].set(-1)
    old = builder.Q_BLOCK, builder.ROW_BLOCK
    builder.Q_BLOCK, builder.ROW_BLOCK = 4, 8
    try:
        got = jax.value_and_grad(builder.plain_loss(small))(params, (tokens, targets))
    finally:
        builder.Q_BLOCK, builder.ROW_BLOCK = old
    want = jax.value_and_grad(
        lambda p: looped_dense_reference.loss(mcfg, p, tokens, targets))(params)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name in params:
        scale = float(jnp.abs(want[1][name]).max())
        np.testing.assert_allclose(got[1][name], want[1][name], rtol=0, atol=2e-4 * scale,
                                   err_msg=name)


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL, "--seed", "2957000057",
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
#: steps see half the sequence's targets, or one leaf comes back unmoved
PLANTED = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("run", sys.argv.pop(1))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
fault, builder = sys.argv.pop(1), run.load_module("builders", "ouro")
sound = builder.build

def build(cfg, traffic, params, batch, mesh):
    tokens, targets = batch
    if fault == "half_tokens":
        targets = targets.at[:, targets.shape[1] // 2:].set(-1)
    held = params["layer.wq"] + 0  # a copy: the step donates its parameters
    step = sound(cfg, traffic, params, (tokens, targets), mesh)
    if fault != "lost_leaf":
        return step

    def lost():
        loss, after = step()
        return loss, {**after, "layer.wq": held}
    return lost

builder.build = build  # load_module caches: run.py's own call gets this module
raise SystemExit(run.main())
"""


@pytest.mark.parametrize("fault, caught_by", [
    ("half_tokens", {"loss_off_reference", "update_off_all_leaves", "update_off_worst_leaf"}),
    ("lost_leaf", {"update_off_all_leaves", "update_off_worst_leaf"})])
def test_a_planted_fault_fails_the_harness_comparison(fault, caught_by):
    """Through run.py's own comparison at the rehearsal's size: the reference
    sees the whole batch and every leaf; the limits are the configuration's."""
    out = subprocess.run(
        [sys.executable, "-c", PLANTED, os.path.join(HERE, "run.py"), fault, "--workload", CELL,
         "--seed", "2957000058", "--seconds", "2", "--trace", "0", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    compared = json.loads(out.stdout.strip().splitlines()[-1])["compared"]
    assert {name for name, c in compared.items() if not c["ok"]} == caught_by, compared
    assert compared["update_off_worst_leaf"]["value"] > 0.9
