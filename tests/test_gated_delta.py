"""``ops/gated_delta.py`` on its own, on the CPU: the chunked gated delta rule
— XLA's form at toy widths, the Pallas kernels in the interpreter at shapes
that tile, with a decay a head (``ops/gated_delta_kernels.py``) and a decay a
key channel (``ops/kda_kernels.py``) — against the token-by-token recurrence, which form a call takes
(``_kernel_path`` over ``_dispatch.kernels_run``) and how it is counted, the
chunks a grid step, what the rule refuses, and the triangular inverse.  (The
family's other pieces: tests/test_delta_moe_pieces.py, which held these cases
up to PR 61.  Two files so that ``--dist loadfile`` spreads them.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import delta_moe as dm
from byteps_tpu.ops import _dispatch
from byteps_tpu.ops import gated_delta as gd
from byteps_tpu.ops import kda_kernels as kk


# ---------------------------------------------------------------------------
# the chunked rule against the token-by-token recurrence, on its own
# ---------------------------------------------------------------------------


def _rule_inputs(decay, b=2, hk=2, r=2, s=32, dk=8, dv=6, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, hk, dk)))
    v = jax.random.normal(ks[2], (b, s, hk * r, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, hk * r)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hk * r)))
    return q, k, v, g, beta  # token-major: the one layout every caller has


def _by_token(q, k, v, g, beta):
    r = v.shape[2] // q.shape[2]
    return gd.gated_delta_recurrence(jnp.repeat(q, r, 2), jnp.repeat(k, r, 2), v, g, beta)


#: implementation → (chunk, the inputs' shape): XLA's chunked form at toy widths,
#: the Pallas kernels (in the interpreter, two chunks a grid step so that the
#: state crosses grid steps) at a shape that tiles.  Every input is random by
#: position, so an index map that took one batch, key head or value head for
#: another would read another's numbers: the cases with B = 2 and two key
#: heads of two (or three) value heads each in one call are there for that,
#: one of them with d_v ≠ d_k so that a block's lanes are counted in the
#: right head size
_IMPLEMENTATIONS = {
    "xla-4": ("xla", 4, {}), "xla-16": ("xla", 16, {}), "xla-32": ("xla", 32, {}),
    "kernels-1x1": ("kernels", 64, dict(b=1, hk=1, s=256, dk=128, dv=128)),
    "kernels-2x2": ("kernels", 64, dict(b=2, hk=2, s=256, dk=128, dv=128)),
    "kernels-128": ("kernels", 128, dict(b=1, hk=1, s=512, dk=128, dv=128)),
    "kernels-2x2x2-wide-v": ("kernels", 64, dict(b=2, hk=2, r=2, s=128, dk=128, dv=256)),
    "kernels-2x2x3": ("kernels", 64, dict(b=2, hk=2, r=3, s=128, dk=128, dv=128)),
    "kernels-2x2x2-128": ("kernels", 128, dict(b=2, hk=2, r=2, s=256, dk=128, dv=128)),
}


def _rule(implementation, chunk):
    if implementation == "xla":
        return lambda *a: gd.chunked_gated_delta_rule(*a, chunk=chunk)
    return lambda *a: gd.chunked_gated_delta_rule(*a, chunk=chunk, interpret=True,
                                                  blocks=(2, 2, 2))


@pytest.mark.parametrize("decay", [1e-4, 1.0, 40.0], ids=["near_one", "middling", "near_zero"])
@pytest.mark.parametrize("implementation", list(_IMPLEMENTATIONS))
def test_chunked_rule_is_the_recurrence(decay, implementation):
    """Values and all five gradients; exp(g) from 0.9999 a token (the state
    hardly fades) to e^-40 (nothing survives a token).  The kernels are held
    to the recurrence and to XLA's chunked form, their other oracle."""
    implementation, chunk, shape = _IMPLEMENTATIONS[implementation]
    args = _rule_inputs(decay, **shape)
    rule = _rule(implementation, chunk)
    oracles = [_by_token] + ([_rule("xla", chunk)] if implementation == "kernels" else [])
    # 128-wide heads sum 16 times the toy widths' terms in f32: the oracles
    # themselves stand 4e-6 apart there
    value_atol = 2e-6 if implementation == "xla" else 1e-5
    got = jax.jit(rule)(*args)
    weigh = jnp.cos(jnp.arange(got.size, dtype=jnp.float32)).reshape(got.shape)

    def gradients(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * weigh), argnums=(0, 1, 2, 3, 4)))(*args)

    grads = gradients(rule)
    for oracle in oracles:
        want = jax.jit(oracle)(*args)
        np.testing.assert_allclose(got, want, atol=value_atol * float(jnp.abs(want).max()) + 1e-7)
        for name, got_g, want_g in zip("q k v g beta".split(), grads, gradients(oracle)):
            assert np.all(np.isfinite(got_g)), name
            scale = float(jnp.abs(want_g).max())
            np.testing.assert_allclose(got_g, want_g, atol=1e-4 * scale + 1e-9, err_msg=name)


# ---------------------------------------------------------------------------
# a decay a key channel (g (B, S, H, d_k)): the chunked form in sub-blocks
# ---------------------------------------------------------------------------


def _channel_inputs(decay, b=2, h=2, s=128, dk=8, dv=6, seed=1, constant=False):
    """As ``_rule_inputs`` with H_k = H_v and a log-decay a channel; ``decay``
    a number scales a softplus, ``constant`` makes every token's every
    channel's log-decay exactly ``−decay``."""
    q, k, v, _, beta = _rule_inputs(1.0, b=b, hk=h, r=1, s=s, dk=dk, dv=dv, seed=seed)
    g = -decay * (jnp.ones((b, s, h, dk)) if constant else jax.nn.softplus(
        jax.random.normal(jax.random.PRNGKey(seed + 7), (b, s, h, dk))))
    return q, k, v, g, beta


_CHANNEL_CASES = {
    # chunk 64 in sub-blocks of SUB_CHUNK = 16: both kinds of sub-block, two chunks, d_k ≠ d_v
    "64x16": dict(chunk=64),
    "32x16": dict(chunk=32),
    "8x4": dict(chunk=8),  # a short chunk: two sub-blocks of half of it
    # 16 heads: two blocks of HEAD_BLOCK heads, one after another
    "64x16-16_heads": dict(chunk=64, shape=dict(b=1, h=16, s=64)),
    # the Pallas kernels of ops/kda_kernels.py in the interpreter (``blocks``:
    # chunks a grid step), at shapes that tile; two batches of two heads in one
    # call (an index map that took one for another would read another's
    # numbers), the state crossing grid steps, d_v ≠ d_k, a chunk a grid step
    "kernels-64": dict(chunk=64, blocks=(2, 2, 2), shape=dict(b=2, h=2, s=256, dk=128, dv=128)),
    "kernels-128": dict(chunk=128, blocks=(2, 2, 2), shape=dict(b=1, h=2, s=512, dk=128, dv=128)),
    "kernels-64-wide-v": dict(chunk=64, blocks=(2, 1, 1),
                              shape=dict(b=1, h=2, s=128, dk=128, dv=256)),
    "kernels-64-one-step": dict(chunk=64, blocks=(2, 2, 2),
                                shape=dict(b=1, h=3, s=128, dk=128, dv=128)),
}


def _channel_rule(chunk, blocks=None):
    """The chunked rule a case names: XLA's form, or with ``blocks`` the
    kernels in the interpreter."""
    how = dict(interpret=True, blocks=blocks) if blocks else {}
    return lambda *a: gd.chunked_gated_delta_rule(*a, chunk=chunk, **how)


def _channel_xla(chunk):
    """XLA's channel form whatever the shapes: the kernels' other oracle."""
    return lambda *a: gd._by_head_blocks(*a, chunk, min(gd.SUB_CHUNK, chunk // 2), jnp.float32)


@pytest.mark.parametrize("decay", [1e-4, 1.0, 40.0], ids=["near_one", "middling", "near_zero"])
@pytest.mark.parametrize("case", list(_CHANNEL_CASES))
def test_channel_rule_is_the_recurrence(decay, case):
    """Values and all five gradients of the chunked form with a decay a
    channel against the token-by-token recurrence; the kernels also against
    XLA's channel form (two chunked forms, each within 1e-4 of the recurrence,
    stand within 2e-4 of each other)."""
    case = dict(_CHANNEL_CASES[case])
    args = _channel_inputs(decay, **case.pop("shape", {}))
    kernels = "blocks" in case
    rule = _channel_rule(**case)
    oracles = [(gd.gated_delta_recurrence, 1e-4)] + (
        [(_channel_xla(case["chunk"]), 2e-4)] if kernels else [])
    got = jax.jit(rule)(*args)
    weigh = jnp.cos(jnp.arange(got.size, dtype=jnp.float32)).reshape(got.shape)

    def gradients(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * weigh), argnums=(0, 1, 2, 3, 4)))(*args)

    grads = gradients(rule)
    for oracle, atol in oracles:
        want = jax.jit(oracle)(*args)
        np.testing.assert_allclose(
            got, want, atol=(1e-5 if kernels else 2e-6) * float(jnp.abs(want).max()) + 1e-7)
        for name, got_g, want_g in zip("q k v g beta".split(), grads, gradients(oracle)):
            assert np.all(np.isfinite(got_g)), name
            scale = float(jnp.abs(want_g).max())
            np.testing.assert_allclose(got_g, want_g, atol=atol * scale + 1e-9, err_msg=name)


@pytest.mark.parametrize("implementation", ["xla", "kernels"])
def test_channel_rule_takes_no_positive_exponent(implementation):
    """Decays of −12 a token a channel: over a sub-block of 16 rows a
    reference row at its START would need e^{+180}, which f32 does not hold,
    and over a chunk e^{+756}.  Every exponential the chunked form takes is of
    a non-positive number: every intermediate is finite (``jax_debug_nans``
    and ``jax_debug_infs`` stop the first that is not — in the interpreter the
    kernels' too), values and gradients agree with the recurrence to f32
    rounding."""
    shape, blocks = (dict(dk=128, dv=128), (2, 1, 1)) if implementation == "kernels" else ({}, None)
    args = _channel_inputs(12.0, constant=True, b=1, h=2, s=128, **shape)
    rule = _channel_rule(64, blocks)
    weigh = jnp.cos(jnp.arange(args[2].size, dtype=jnp.float32)).reshape(args[2].shape)
    loss = lambda fn: (lambda *a: jnp.sum(fn(*a) * weigh))  # noqa: E731
    with jax.debug_nans(True), jax.debug_infs(True):
        got = rule(*args)
        grads = jax.grad(loss(rule), argnums=(0, 1, 2, 3, 4))(*args)
    want = gd.gated_delta_recurrence(*args)
    # the state hardly outlives a token (e^-12): o_t is β_t (k_t·q_t) v_t to six places
    q, k, v, _, beta = args
    np.testing.assert_allclose(want, (beta * jnp.sum(q * k, -1))[..., None] * v, atol=2e-5)
    np.testing.assert_allclose(got, want, atol=2e-6 * float(jnp.abs(want).max()))
    for name, got_g, want_g in zip("q k v g beta".split(), grads, jax.grad(
            loss(gd.gated_delta_recurrence), argnums=(0, 1, 2, 3, 4))(*args)):
        assert np.all(np.isfinite(got_g)), name
        # g's own gradient is e^-12 of the others' (1e-7 and less): the chunked
        # form sums it from a chunk's dΓ, whose terms of the others' size cancel,
        # so it is held to THEIR rounding (2e-7), every other to its own size
        np.testing.assert_allclose(got_g, want_g, err_msg=name, atol=2e-7 if name == "g" else (
            1e-4 * float(jnp.abs(want_g).max()) + 1e-9))


@pytest.mark.parametrize("implementation", ["xla", "kernels"])
@pytest.mark.parametrize("decay", [0.05, 3.0])
def test_a_decay_constant_over_channels_is_the_scalar_rule(decay, implementation):
    """g broadcast over the key's channels: the channel form, the scalar
    chunked form and both recurrences give one answer to rounding — XLA's two
    forms, and the two sets of kernels in the interpreter."""
    shape, how, atol = ((dict(dk=128, dv=128), dict(interpret=True, blocks=(2, 2, 2)), 1e-5)
                        if implementation == "kernels" else ({}, {}, 2e-6))
    q, k, v, g, beta = _rule_inputs(decay, b=1, hk=2, r=1, s=128, **shape)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    scalar = gd.chunked_gated_delta_rule(q, k, v, g, beta, chunk=64, **how)
    channel = gd.chunked_gated_delta_rule(q, k, v, wide, beta, chunk=64, **how)
    scale = float(jnp.abs(scalar).max())
    np.testing.assert_allclose(channel, scalar, atol=atol * scale)
    np.testing.assert_allclose(gd.gated_delta_recurrence(q, k, v, wide, beta),
                               gd.gated_delta_recurrence(q, k, v, g, beta), atol=1e-7 * scale)


def test_the_recurrence_with_a_decay_a_head_gives_what_it_gave():
    """The one definition serves both: with g (B, S, H) it is, bit for bit,
    the scan it was before g could have a channel's dim (written out here)."""
    q, k, v, g, beta = _rule_inputs(1.0, r=1)

    def as_it_was(q, k, v, g, beta):
        def token(state, xs):
            q_t, k_t, v_t, g_t, beta_t = xs
            state = jnp.exp(g_t)[..., None, None] * state
            u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
            state = state + k_t[..., :, None] * u[..., None, :]
            return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

        xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
        state = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[-1]), g.dtype)
        return jnp.moveaxis(jax.lax.scan(token, state, xs)[1], 0, 1)

    np.testing.assert_array_equal(gd.gated_delta_recurrence(q, k, v, g, beta),
                                  as_it_was(q, k, v, g, beta))


def test_channel_form_is_counted_and_refuses_what_it_cannot_cut():
    from byteps_tpu.core.telemetry import counters

    args = _channel_inputs(1.0, b=1, s=32)
    before = counters().snapshot()
    jax.make_jaxpr(lambda *a: gd.chunked_gated_delta_rule(*a, chunk=16))(*args)
    after = counters().snapshot()
    grown = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    assert grown == {"gdn_channel_xla_traces": 1}  # neither of the scalar form's two
    q, k, v, g, beta = args
    with pytest.raises(ValueError, match="sub-blocks of 16 in a chunk of 40"):
        gd.chunked_gated_delta_rule(*_channel_inputs(1.0, b=1, s=80), chunk=40)
    with pytest.raises(ValueError, match="sub-blocks of 0 in a chunk of 1"):
        gd.chunked_gated_delta_rule(q, k, v, g, beta, chunk=1)
    with pytest.raises(ValueError, match="a decay a channel"):
        gd.chunked_gated_delta_rule(q[:, :, :1], k[:, :, :1], v, g, beta, chunk=16)
    with pytest.raises(ValueError, match="does not divide"):
        gd.chunked_gated_delta_rule(*(x[:, :24] for x in args), chunk=16)


def test_the_channel_path_is_chosen_from_platform_and_shapes(monkeypatch):
    """The scalar form's one function decides for a decay a channel too: the
    kernels of ops/kda_kernels.py in the interpreter and on a (stand-in) TPU at
    whole tiles, XLA's form off a TPU and at shapes that do not tile; each
    traced call counted under its own name, never under the scalar form's."""
    from byteps_tpu.core.telemetry import counters

    def grown(fn, *args):
        before = counters().snapshot()
        text = str(jax.make_jaxpr(fn)(*args))
        after = counters().snapshot()
        return text, {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}

    tiling = _channel_inputs(1.0, b=1, h=2, s=256, dk=128, dv=128)
    rule = lambda *a: gd.chunked_gated_delta_rule(*a, chunk=64)  # noqa: E731
    text, counted = grown(rule, *tiling)
    assert "pallas_call" not in text and counted == {"gdn_channel_xla_traces": 1}  # this is a CPU
    text, counted = grown(_channel_rule(64, (2, 2, 2)), *tiling)
    assert text.count("pallas_call") == 2 and counted == {"gdn_channel_kernel_traces": 1}
    text, counted = grown(jax.grad(lambda *a: jnp.sum(_channel_rule(64, (2, 2, 2))(*a))), *tiling)
    assert all(name in text for name in kk.SAVED)
    assert [text.count(name) for name in (kk.INVERSE_KERNEL, kk.FWD_KERNEL, kk.BWD_KERNEL)] == [
        1, 1, 1] and counted == {"gdn_channel_kernel_traces": 1}
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    # (a new function: jax keeps a trace by the function traced; nothing is
    # lowered on this CPU)
    text, counted = grown(lambda *a: rule(*a), *tiling)
    assert text.count("pallas_call") == 2 and counted == {"gdn_channel_kernel_traces": 1}
    for chunk, shape in [(32, dict(dk=128, dv=128)), (64, dict(dk=64, dv=128)),
                         (64, dict(dk=128, dv=96)), (8, {})]:
        toy = _channel_inputs(1.0, b=1, h=2, s=256, **shape)
        text, counted = grown(lambda *a: gd.chunked_gated_delta_rule(*a, chunk=chunk), *toy)
        assert "pallas_call" not in text and counted == {"gdn_channel_xla_traces": 1}, (chunk, shape)
    assert gd.tuned_blocks(256, 64, channel=True) == gd.tuned_blocks(
        256, 64, gd._tuned_table()[True, 16384])  # the channel form's own entry of the table


def _traces():
    from byteps_tpu.core.telemetry import counters

    snapshot = counters().snapshot()
    return snapshot.get("gdn_kernel_traces", 0), snapshot.get("gdn_xla_traces", 0)


def test_the_path_is_chosen_from_platform_and_shapes(monkeypatch):
    """One function decides: off a TPU and at shapes the kernels do not tile,
    XLA's form; on a TPU at whole tiles, the kernels; each traced call counted."""
    tiling = dict(chunk=64, dk=128, dv=128)
    assert not gd._kernel_path(**tiling, interpret=False)  # this is a CPU
    assert gd._kernel_path(**tiling, interpret=True)
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    assert gd._kernel_path(**tiling, interpret=False)
    assert gd._kernel_path(chunk=128, dk=256, dv=128, interpret=False)
    for chunk, dk, dv in [(8, 8, 6), (32, 128, 128), (64, 64, 128), (64, 128, 96), (256, 128, 128)]:
        assert not gd._kernel_path(chunk, dk, dv, interpret=True), (chunk, dk, dv)

    # a stand-in TPU traces the kernels (nothing is lowered on this CPU) ...
    args = _rule_inputs(1.0, b=1, hk=1, s=256, dk=128, dv=128)
    kernels, xla = _traces()
    text = str(jax.make_jaxpr(lambda *a: gd.chunked_gated_delta_rule(*a, chunk=64))(*args))
    assert text.count("pallas_call") == 2 and _traces() == (kernels + 1, xla)
    # ... and XLA's form where the shapes do not tile, as tiny_delta_moe's do not
    cfg = dm.tiny_delta_moe()
    toy = _rule_inputs(1.0, dk=cfg.lin_k_dim, dv=cfg.lin_v_dim)
    text = str(jax.make_jaxpr(lambda *a: gd.chunked_gated_delta_rule(*a, chunk=cfg.chunk))(*toy))
    assert "pallas_call" not in text and _traces() == (kernels + 1, xla + 1)
    with pytest.raises(ValueError, match="does not divide"):
        gd.chunked_gated_delta_rule(*_rule_inputs(1.0, b=1, hk=1, s=288, dk=128, dv=128), chunk=64)


def test_a_cpu_step_of_the_tiny_model_counts_xla_traces_alone():
    cfg = dm.tiny_delta_moe()
    params = dm.init_params(cfg, jax.random.PRNGKey(0))
    kernels, xla = _traces()
    jax.make_jaxpr(lambda p, t: dm.local_logits(cfg, p, t))(
        params, jnp.zeros((1, cfg.max_seq), jnp.int32))
    assert _traces()[0] == kernels and _traces()[1] > xla


def test_blocks_fit_the_sequence():
    """The tuned or asked-for chunks a grid step come down to what divides
    the sequence's chunks; the inverse keeps whole stacks of 128 rows."""
    assert gd.tuned_blocks(4, 64, (16, 8, 2)) == (4, 4, 2)
    assert gd.tuned_blocks(6, 64, (8, 8, 8)) == (2, 2, 2)
    assert gd.tuned_blocks(3, 128, (8, 8, 8)) == (1, 1, 1)
    assert all(256 % nb == 0 for nb in gd.tuned_blocks(256, 64))
    with pytest.raises(ValueError, match="whole number of stacks"):
        gd.tuned_blocks(3, 64)


def test_rule_refuses_what_it_would_have_to_pad_or_guess():
    q, k, v, g, beta = _rule_inputs(1.0, s=12)
    with pytest.raises(ValueError, match="does not divide"):
        gd.chunked_gated_delta_rule(q, k, v, g, beta, chunk=8)
    with pytest.raises(ValueError, match="no multiple"):
        gd.chunked_gated_delta_rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3], chunk=4)


@pytest.mark.parametrize("size", [2, 8, 64])
def test_unit_lower_inverse_and_its_backward_pass(size):
    a = 0.3 * jax.random.normal(jax.random.PRNGKey(size), (3, 2, size, size))
    want = jnp.linalg.inv(jnp.eye(size) + jnp.tril(a, -1))
    np.testing.assert_allclose(jax.jit(gd.unit_lower_inverse)(a), want,
                               atol=1e-5 * float(jnp.abs(want).max()))
    weigh = jnp.sin(jnp.arange(a.size, dtype=jnp.float32)).reshape(a.shape)
    got = jax.jit(jax.grad(lambda a: jnp.sum(gd.unit_lower_inverse(a) * weigh)))(a)
    by_blocks = jax.jit(jax.grad(lambda a: jnp.sum(gd._inverse_by_blocks(a) * weigh)))(a)
    np.testing.assert_allclose(got, by_blocks, atol=1e-4 * float(jnp.abs(by_blocks).max()))
    assert not np.any(np.triu(np.asarray(got)))  # what is not read takes no gradient
    with pytest.raises(ValueError, match="power of two"):
        gd.unit_lower_inverse(jnp.zeros((6, 6)))
