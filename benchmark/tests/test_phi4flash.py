"""The yardstick's checks of what the ``phi4_mini_flash_vp8`` configuration
brought: ``python -m pytest benchmark/tests/test_phi4flash.py -q`` (by hand; no
device needed; the last test is the cell's rehearsal, about two minutes)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, CONFIG = "phi4_flash_vp8_train16k", "phi4_mini_flash_vp8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
REDUCED = ["num_hidden_layers", "vocab_size"]
NAMES = {"train_step.selective_scan_ms", "train_step.mamba_proj_ms",
         "train_step.diff_window_attention_ms", "train_step.diff_full_attention_ms",
         "train_step.diff_cross_attention_ms", "train_step.gated_memory_ms",
         "kernels.selective_scan_roofline_share", "kernels.diff_flash_roofline_share"}


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
#: the family's eight per-layer metrics, listed since PR 70
MINE = [m for m in BENCH["per_layer"] if m["name"] in NAMES]
#: the general entries that list the cell too: its five SwiGLUs, head, embedding and the rest
APPENDED = ("train_step.dense_mlp_ms", "train_step.head_loss_ms", "train_step.embed_ms",
            "train_step.unscoped_ms", "train_step.no_phase_ms", "train_step.dispatch_ms",
            "train_step.idle_in_dispatch_ms")
reader = load("readers", "cross_decoder.py")
builder = load("builders", "phi4flash.py")


def test_the_cell_finds_its_files_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "local_closed"
    assert len(cell["why"]) <= 200 and "1 x 16384 tokens" in cell["why"]
    assert len(entry["why"]) <= 200 and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["reduced"] == REDUCED
    assert os.path.exists(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    assert CFG["builder"] == "phi4flash" and CFG["reduced"] == REDUCED
    for name in ("flops_per_sample", "make_optimizer", "plain_loss", "make_state", "build",
                 "compared_params", "program_params",
                 "_model_config", "_mesh4"):  # the last four: tools/latent_moe_precision.py's
        assert callable(getattr(builder, name))
    assert {m["name"] for m in MINE} == NAMES
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    assert all(CELL in listed[name]["workloads"] for name in APPENDED)


@pytest.mark.parametrize("name", sorted(NAMES))
def test_every_metric_file_loads_and_names_the_cell(name):
    m = next(m for m in MINE if m["name"] == name)
    spec = load_json(HERE, "metrics", f"{name}.json")
    assert spec["reader"] == "cross_decoder" and spec["what"]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["workloads"] == [CELL] and m["moves"] == "samples_per_s"
    assert m["layer"] == ("kernels" if name.startswith("kernels.") else "train_step")
    if name.endswith("roofline_share"):
        assert m["unit"] == "%" and m["better"] == "higher" and m["source"] == "device_trace"
    # a run without a trace reads None and does not raise
    assert reader.read({"trace": None}, **spec["args"]) is None


def test_every_published_key_stands_as_published():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
    assert row["source_url"] == SOURCE
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CFG[key] != value and CFG["published"][key] == value
        else:
            assert CFG[key] == value and type(CFG[key]) is type(value), key
    assert (CFG["first_layer"], CFG["num_hidden_layers"], CFG["vocab_size"]) == (15, 5, 25008)
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    for key in ("differential_attention", "biases", "mamba", "window", "initialiser",
                "compute_dtype", "remat"):
        assert CFG["assumed"][key], key


def test_the_parameter_count():
    import numpy as np

    from byteps_tpu.models import cross_decoder

    layout = cross_decoder.layouts(builder._model_config(CFG))
    assert sum(int(np.prod(s)) for s, _, _ in layout.values()) == 577_199_232
    assert "577 199 232" in CFG["held"]["parameters"]


def test_flops_per_sample_against_a_count_by_hand():
    s, d, f, v = 16384, 2560, 10240, 25008
    di, n, r = 5120, 16, 160
    mamba = d * 2 * di + di * (r + 2 * n) + r * di + di * d + 4 * di
    own, cross, gmu, mlp = 2 * d * 2560 + 2 * d * 1280, 2 * d * 2560, 2 * d * di, 3 * d * f
    per_token = 2 * (mamba + 2 * own + cross + gmu + 5 * mlp + d * v) + 5 * di * n
    full, band = s * (s + 1) // 2, 512 * s - 512 * 511 // 2
    # 40 query heads, each a softmax over 64-wide q.k with a 128-wide value
    attention = (2 * full + band) * 40 * 2 * (64 + 128)
    want = 3 * (s * per_token + attention)
    assert builder.flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 69e12 < want < 72e12  # ISSUE 63's "about 70 TFLOP"
    assert builder.scan_operations(di, n) == 5 * 5120 * 16
    ops, nbytes = reader.scan_cost(s, di, n, 2)
    assert ops == 3 * s * 5 * di * n
    assert nbytes == 3 * s * (2 * (2 * di + 2 * n) + 4 * di)


def test_the_blocked_reference_is_the_plain_reference():
    """At a tiny size with blocks small enough to cut everything: the windowed
    layer's band, the full layers' runs of keys, the channel groups, the rows."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.models import cross_decoder, cross_decoder_reference

    cfg = dict(CFG, hidden_size=32, intermediate_size=48, num_attention_heads=8,
               num_key_value_heads=4, sliding_window=5, d_state=3, dt_rank=4, first_layer=3,
               num_hidden_layers=5, published_layers=8, max_seq=16, vocab_size=96,
               scan_chunk=8, batch_per_chip=2, compute_dtype="float32")
    blocks = builder.Q_BLOCK, builder.ROW_BLOCK
    builder.Q_BLOCK, builder.ROW_BLOCK = 4, 8
    try:
        model = builder._model_config(cfg)
        assert model.layer_types == ("window", "mamba", "full", "gmu", "cross")
        params = cross_decoder.init_params(model, jax.random.PRNGKey(0))
        keys = jax.random.split(jax.random.PRNGKey(1), len(params))
        params = {k: p + 0.1 * jax.random.normal(key, p.shape)
                  for (k, p), key in zip(params.items(), keys)}
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 96)
        batch = (tokens, jnp.roll(tokens, -1, axis=1))
        got, got_grads = jax.value_and_grad(builder.plain_loss(cfg))(params, batch)
    finally:
        builder.Q_BLOCK, builder.ROW_BLOCK = blocks
    want, want_grads = jax.value_and_grad(
        lambda p: cross_decoder_reference.loss(model, p, *batch))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for name in params:
        if not name.endswith(".bk"):  # softmax does not see a key's bias
            # a lambda vector's gradient is a near-cancelling sum: 1e-4 here, where a
            # matrix's is 1e-1
            scale = max(float(jnp.abs(want_grads[name]).max()), 1e-3)
            assert float(jnp.abs(got_grads[name] - want_grads[name]).max()) < 1e-4 * scale, name


def test_the_compared_tree_is_the_programs_with_the_lambda_vectors_under_wq():
    import jax
    import numpy as np

    from byteps_tpu.models import cross_decoder

    cfg = dict(CFG, **CFG["rehearsal"])
    params = cross_decoder.init_params(builder._model_config(cfg), jax.random.PRNGKey(0))
    seen = builder.compared_params(params)
    stacks = ("win", "full", "cross")
    gone = {f"{s}.{leaf}" for s in stacks for leaf in ("wq", *builder._LAMBDAS)}
    assert set(params) - set(seen) == gone
    assert set(seen) - set(params) == {f"{s}.wq+lambdas" for s in stacks}
    assert seen["full.wq+lambdas"].shape == (1, 2560 * 40 // 4 + 1, 4 * 64)
    assert all(seen[k] is params[k] for k in set(params) - gone)  # no other leaf is touched
    back = builder.program_params(cfg, seen)
    assert set(back) == set(params)
    for name in params:
        np.testing.assert_array_equal(back[name], params[name], err_msg=name)
    assert builder.program_params(cfg, params).keys() == params.keys()  # the program's: as it is


#: run.py with one leaf of what the program's steps hand back left where it
#: started, as a lost gradient leaves it (the rows of ``wq`` or the row of λ
#: vectors alone where the leaf is ``<stack>.wq+lambdas``)
PLANTED = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("run", sys.argv.pop(1))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
leaf, _, rows = sys.argv.pop(1).partition(":")
builder = run.load_module("builders", "phi4flash")
sound = builder.build

def build(cfg, traffic, params, batch, mesh):
    held = params[leaf] + 0  # a copy: the step donates its parameters
    step = sound(cfg, traffic, params, batch, mesh)

    def lost():
        loss, after = step()
        cut = {"wq": slice(0, -1), "lambdas": slice(-1, None)}.get(rows)
        back = held if cut is None else after[leaf].at[:, cut].set(held[:, cut])
        return loss, {**after, leaf: back}
    return lost

builder.build = build  # load_module caches: run.py's own call gets this module
raise SystemExit(run.main())
"""


def planted(lost, *arguments):
    out = subprocess.run(
        [sys.executable, "-c", PLANTED, os.path.join(HERE, "run.py"), lost, "--workload", CELL,
         *arguments], env=os.environ, capture_output=True, text=True, timeout=3000)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])["compared"]


@pytest.mark.parametrize("lost", ["win.norm", "win.norm_bias", "full.bq", "full.bo", "cross.subln",
                                  "cross.wq+lambdas:wq"])
def test_a_lost_gradient_fails_the_harness_comparison(lost):
    """Through run.py's own comparison at the rehearsal's size, the limits the
    configuration's: each of an attention layer's small vectors is a leaf of
    its own and reads 1.0 when it does not move, and so does ``wq`` beside
    the λ vectors."""
    compared = planted(lost, "--seed", "2951006377", "--seconds", "1", "--trace", "0",
                       "--rehearse")
    worst = compared["update_off_worst_leaf"]
    assert not worst["ok"] and 0.97 < worst["value"] < 1.03, compared


def test_the_lambda_vectors_update_is_not_held_by_the_comparison():
    """What the compared tree costs (``compared_params``), written down as a
    test so that it is not forgotten: λ vectors that do not move pass."""
    compared = planted("full.wq+lambdas:lambdas", "--seed", "2951006377", "--seconds", "1",
                       "--trace", "0", "--rehearse")
    assert compared["update_off_worst_leaf"]["ok"], compared


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
    # 128 tokens over 256 rows learn fast (the loss falls by 8 % a step): the
    # rehearsal's loss stays within 1 % of the reference's, not within the cell's limit
    assert compared["loss_off_reference"]["value"] < 1e-2 and compared["compiles_in_window"]["ok"]
    assert compared["update_off_worst_leaf"]["value"] < 1.0
