"""The decoder-hybrid-decoder family (models/cross_decoder.py) against its
plain reference (models/cross_decoder_reference.py) at a tiny size on seeded
weights, through ``build_train_step`` itself: logits, loss, every leaf's
gradient, with and without remat; differential attention against its dense
formula in its three forms; the slice test — a whole model equals its slices
chained with x, the memory and the shared keys and values handed over."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import cross_decoder as cd
from byteps_tpu.models import cross_decoder_reference as ref
from byteps_tpu.models import moe_family as mf
from byteps_tpu.parallel.mesh_utils import make_training_mesh

import family_cases as fc


def _state(cfg, seed=0, batch=2):
    """Every leaf that starts at a constant (biases, norms, D) is moved off
    it with the rest, so that each takes part."""
    return fc._state(cd, cfg, seed, batch, moved=lambda name: True)


FAMILY = fc.Family(
    name="cross_decoder", model=cd, ref=ref, tiny=cd.tiny_cross_decoder, state=_state, batch=2,
    variants={
        "whole_model_of_8": dict(),
        "the_seam_3_to_7": dict(first_layer=3, held_layers=5),
        "one_query_pair_a_key_pair": dict(n_heads=4, n_kv_heads=4),
        "window_covers_the_sequence": dict(window=16),
        "one_chunk_a_sequence": dict(chunk=16),
        "chunk_does_not_divide": dict(chunk=5),
        "no_remat": dict(remat=False),
    },
    ref_logits=ref.forward,
    # entry by entry, as the slice test below reads its leaves
    grad_off=fc._worst_entry, grad_tol=1e-4,
    # a key's bias moves every score of a query alike, so softmax does not see
    # it: its gradient is 0 in the mathematics, rounding in the reference, and
    # 0 in the program, which takes it as that
    learns=lambda cfg, name: False if name.endswith(".bk") else None,
    rounding={".bk": ".wk"},
    dp2_to_reference=("whole_model_of_8", "the_seam_3_to_7"),
)
globals().update(fc.family_cases(FAMILY))


def test_the_program_in_bf16_is_near_the_reference():
    """At bf16 operands the loss stays within a percent and no leaf's gradient
    points away from the reference's."""
    cfg = cd.tiny_cross_decoder(first_layer=3, held_layers=5, compute_dtype=jnp.bfloat16)
    params, tokens, targets = _state(cfg, batch=1)
    loss, grads = fc._system_loss_and_grads(cfg, params, tokens, targets)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(cfg, p, tokens, targets)))(params)
    assert loss == pytest.approx(float(want_loss), rel=2e-2)
    for name, g in grads.items():
        w = np.asarray(want[name]).ravel()
        if not name.endswith(".bk"):
            assert np.dot(g.ravel(), w) > 0.8 * np.linalg.norm(g) * np.linalg.norm(w), name


# ---------------------------------------------------------------------------
# the pattern
# ---------------------------------------------------------------------------


def test_the_published_pattern():
    """32 layers: Mamba-1 at 0, 2, …, 16; windows at 1, 3, …, 15; full at 17;
    GMUs at 18, …, 30; cross at 19, …, 31 — 9 : 8 : 1 : 7 : 7."""
    kinds = [cd.kind_of(layer, 32) for layer in range(32)]
    assert [i for i, k in enumerate(kinds) if k == "mamba"] == list(range(0, 17, 2))
    assert [i for i, k in enumerate(kinds) if k == "window"] == list(range(1, 16, 2))
    assert [i for i, k in enumerate(kinds) if k == "full"] == [17]
    assert [i for i, k in enumerate(kinds) if k == "gmu"] == list(range(18, 32, 2))
    assert [i for i, k in enumerate(kinds) if k == "cross"] == list(range(19, 32, 2))
    assert kinds == [ref.kind_of(layer, 32) for layer in range(32)]
    cell = cd.CrossDecoderConfig()
    assert cell.layers == (15, 16, 17, 18, 19)
    assert cell.layer_types == ("window", "mamba", "full", "gmu", "cross")


@pytest.mark.parametrize("lo,hi", [(0, 3), (3, 8), (0, 6), (6, 8), (2, 5)])
def test_a_slices_kinds_are_the_whole_models(lo, hi):
    whole = cd.tiny_cross_decoder()
    part = cd.tiny_cross_decoder(first_layer=lo, held_layers=hi - lo)
    assert part.layer_types == whole.layer_types[lo:hi]
    assert part.kinds() == whole.kinds()[lo:hi]
    assert all(mlp == "dense" for _, mlp in part.kinds())


def test_a_family_with_a_pattern_and_no_experts():
    cfg = cd.tiny_cross_decoder()
    assert isinstance(cfg, mf.Patterned) and not isinstance(cfg, mf.ExpertFamily)
    assert issubclass(mf.PatternedFamily, mf.Patterned)
    assert issubclass(mf.PatternedFamily, mf.ExpertFamily)
    with pytest.raises(ValueError, match="data-parallel only"):
        cfg.validate_mesh(make_training_mesh(2, {"dp": 1, "pp": 2, "sp": 1, "tp": 1}))
    with pytest.raises(ValueError, match="not both even"):
        cd.tiny_cross_decoder(n_heads=3, n_kv_heads=3)
    with pytest.raises(ValueError, match="of a model of"):
        cd.tiny_cross_decoder(first_layer=6, held_layers=3)


def test_the_cells_parameter_count():
    layout = cd.layouts(cd.CrossDecoderConfig())
    count = lambda prefix: sum(int(np.prod(s)) for k, (s, _, _) in layout.items()  # noqa: E731
                               if k.startswith(prefix))
    norms = 2 * 2560
    assert count("mamba.") == 41_241_600 + norms
    assert count("win.") == count("full.") == 19_668_864 + norms
    assert count("gmu.") == 26_214_400 + norms
    assert count("cross.") == 13_112_704 + norms
    assert count("dense.") == 5 * (78_643_200 + norms)
    assert count("") == 577_199_232


def test_the_start():
    cfg = cd.tiny_cross_decoder()
    p = cd.init_params(cfg, jax.random.PRNGKey(3))
    np.testing.assert_allclose(jnp.exp(p["mamba.a_log"][1, 5]), [1.0, 2.0, 3.0], rtol=1e-6)
    win, cross = (mf.stack_of(p, stack) for stack in ("win", "cross"))
    assert win["lambda_k2"].shape == (2, 8) and cross["subln"].shape == (1, 16)
    assert bool(jnp.all(p["mamba.d_skip"] == 1)) and bool(jnp.all(win["norm"] == 1))
    assert bool(jnp.all(cross["subln"] == 1)) and cross["bq"].shape == (1, 8, 8)
    for name, leaf in (("win.norm_bias", win["norm_bias"]), ("norm_f_bias", p["norm_f_bias"]),
                       ("win.bq", win["bq"]), ("cross.bo", cross["bo"]), ("full.bv", p["full.bv"]),
                       ("dense.norm_bias", p["dense.norm_bias"])):
        assert bool(jnp.all(leaf == 0)), name
    step = jax.nn.softplus(p["mamba.dt_bias"])
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 0.1 * 1.001
    assert float(jnp.abs(p["mamba.w_dt"]).max()) <= cfg.dt_rank ** -0.5
    lambdas = jnp.stack([win[f"lambda_{x}"] for x in ("q1", "k1", "q2", "k2")])
    assert 0.05 < float(jnp.std(lambdas)) < 0.2
    assert float(jnp.abs(lambdas[0] - lambdas[1]).max()) > 0  # each from its own key


# ---------------------------------------------------------------------------
# differential attention against its dense formula
# ---------------------------------------------------------------------------


def _layer(cfg, params, stack, layer):
    """The first layer of an attention stack as its part sees it: λ_init of
    published layer ``layer`` beside its leaves."""
    lp = {k: v[0] for k, v in mf.stack_of(params, stack).items()}
    return {**lp, "lambda_init": jnp.float32(cd.lambda_init(layer))}


def _dense_differential(cfg, u, lp, k1, k2, v, window):
    """The formula with nothing shared: every query pair's two softmaxes over
    the whole masked score matrix.  k1, k2 (B, kv/2, S, d), v (B, kv/2, S, 2d)."""
    hd, group = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    s = u.shape[1]
    q = jnp.einsum("bsd,dhk->bhsk", u, lp["wq"]) + lp["bq"][:, None, :]
    rows, cols = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (cols <= rows) & (rows - cols < (window or s))
    lam_init = lp["lambda_init"]
    lam = (jnp.exp(lp["lambda_q1"] @ lp["lambda_k1"]) - jnp.exp(lp["lambda_q2"] @ lp["lambda_k2"])
           + lam_init)
    out = []
    for p in range(cfg.n_heads // 2):
        j = p // group
        a = [jax.nn.softmax(jnp.where(seen, q[:, 2 * p + i] @ jnp.swapaxes(k[:, j], 1, 2)
                                      / hd ** 0.5, -jnp.inf), axis=-1) @ v[:, j]
             for i, k in ((0, k1), (1, k2))]
        diff = a[0] - lam * a[1]
        diff = diff / jnp.sqrt(jnp.mean(diff ** 2, axis=-1, keepdims=True) + cfg.norm_eps)
        out.append((1 - lam_init) * diff * lp["subln"])
    o = jnp.concatenate(out, axis=-1)  # (B, S, h · d)
    return o @ lp["wo"].reshape(-1, cfg.d_model) + lp["bo"]


@pytest.mark.parametrize("form", ["window", "full", "cross"])
def test_differential_attention_is_its_dense_formula(form):
    cfg = cd.tiny_cross_decoder()
    params, _, _ = _state(cfg, seed=5)
    stack = {"window": "win", "full": "full", "cross": "cross"}[form]
    layer = {"window": 1, "full": 5, "cross": 7}[form]
    lp = _layer(cfg, params, stack, layer)
    source = lp if form != "cross" else _layer(cfg, params, "full", 5)
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model))
    window = cfg.window if form == "window" else None

    def program(u, lp, source):
        return cd.differential_attention(cfg, u, lp, cd._keys_values(cfg, u, source), window)

    def dense(u, lp, source):
        return _dense_differential(cfg, u, lp, *cd._keys_values(cfg, u, source), window)

    weights = jax.random.normal(jax.random.PRNGKey(7), u.shape)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(weights * program(*a)), argnums=(0, 1, 2)))(u, lp, source)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(weights * dense(*a)), argnums=(0, 1, 2)))(u, lp, source)
    assert got == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=1e-4 * max(float(jnp.abs(w).max()), 0.1))


def test_shared_keys_and_values_gradients_sum_over_both_readers():
    """The ``full`` layer's (k¹, k², V) are read by its own queries and by the
    cross layer's: through ``run_layers`` their cotangent is the sum of each
    reader's alone."""
    cfg = cd.tiny_cross_decoder(first_layer=5, held_layers=3)  # full, gmu, cross
    params, _, _ = _state(cfg, seed=8)
    full, cross = _layer(cfg, params, "full", 5), _layer(cfg, params, "cross", 7)
    u = jax.random.normal(jax.random.PRNGKey(9), (1, 16, cfg.d_model))
    kv = cd._keys_values(cfg, u, full)
    own = lambda kv: jnp.sum(cd.differential_attention(cfg, u, full, kv, None) ** 2)  # noqa: E731
    other = lambda kv: jnp.sum(cd.differential_attention(cfg, 2 * u, cross, kv, None) ** 2)  # noqa: E731
    both, alone = jax.jit(lambda kv: (jax.grad(lambda kv: own(kv) + other(kv))(kv),
                                      (jax.grad(own)(kv), jax.grad(other)(kv))))(kv)
    for g, a, b in zip(both, *alone):
        np.testing.assert_allclose(g, a + b, atol=1e-5 * float(jnp.abs(g).max()))
        assert float(jnp.abs(a).max()) > 0 and float(jnp.abs(b).max()) > 0


# ---------------------------------------------------------------------------
# the slice test
# ---------------------------------------------------------------------------


def _slice_params(whole_cfg, params, lo, hi):
    """The leaves of layers [lo, hi) of a whole model's stacks."""
    before = [t for pair in whole_cfg.kinds()[:lo] for t in pair]
    held = [t for pair in whole_cfg.kinds()[lo:hi] for t in pair]
    out = {}
    for name, leaf in params.items():
        if "." in name:
            stack = name.split(".", 1)[0]
            if stack in held:
                out[name] = leaf[before.count(stack):before.count(stack) + held.count(stack)]
    return out


@pytest.mark.parametrize("cut,remat", [(3, True), (5, True), (6, True), (6, False)])
def test_a_whole_model_is_its_slices_chained(cut, remat):
    """8 layers whole = slices [0, cut) and [cut, 8) chained, with x, the
    memory and (k¹, k², V) handed over — at 3 nothing but x has been made
    yet, at 5 the memory crosses, at 6 the memory and the keys and values do
    — in values and in the gradient of every leaf and of the input."""
    whole = cd.tiny_cross_decoder(remat=remat)
    params, _, _ = _state(whole, seed=11)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 16, whole.d_model))
    first = dataclasses.replace(whole, first_layer=0, held_layers=cut)
    second = dataclasses.replace(whole, first_layer=cut, held_layers=8 - cut)
    weights = jax.random.normal(jax.random.PRNGKey(13), x.shape)

    def run_whole(params, x):
        return jnp.sum(weights * cd.run_layers(whole, params, x)[0])

    def run_chained(params, x):
        x, carried = cd.run_layers(first, _slice_params(whole, params, 0, cut), x)
        assert set(carried) == ({"memory", "kv"} if cut == 6 else {"memory"})
        x, _ = cd.run_layers(second, _slice_params(whole, params, cut, 8), x, carried)
        return jnp.sum(weights * x)

    want, want_grads = jax.jit(jax.value_and_grad(run_whole, argnums=(0, 1)))(params, x)
    got, got_grads = jax.jit(jax.value_and_grad(run_chained, argnums=(0, 1)))(params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    layer_leaves = {k for k in params if "." in k}
    for name in layer_leaves:
        np.testing.assert_allclose(
            got_grads[0][name], want_grads[0][name], err_msg=name,
            atol=1e-5 * max(float(jnp.abs(want_grads[0][name]).max()), 1e-6))
    np.testing.assert_allclose(got_grads[1], want_grads[1],
                               atol=1e-5 * float(jnp.abs(want_grads[1]).max()))


def test_the_reference_chains_its_slices_too():
    whole = cd.tiny_cross_decoder()
    params, _, _ = _state(whole, seed=14)
    x = jax.random.normal(jax.random.PRNGKey(15), (1, 16, whole.d_model))
    first = dataclasses.replace(whole, first_layer=0, held_layers=6)
    second = dataclasses.replace(whole, first_layer=6, held_layers=2)

    @jax.jit
    def runs(params, x):
        """(the reference whole, its slices chained, the program whole)"""
        mid, memory, k, v = ref.run_layers(first, _slice_params(whole, params, 0, 6), x)
        return (ref.run_layers(whole, params, x)[0],
                ref.run_layers(second, _slice_params(whole, params, 6, 8), mid, memory, k, v)[0],
                cd.run_layers(whole, params, x)[0])

    want, got, program = runs(params, x)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(program, want, atol=1e-4)
