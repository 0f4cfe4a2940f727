"""ops/selective_scan.py: Mamba-1's scan, its chunk-wise backward pass held to
the token-by-token recurrence (the definition) — values and all six gradients,
chunks that do and do not divide the sequence, a decay strong enough to
underflow, f32 and bf16 operands, what a rebuilt caller keeps, the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu as bps
from byteps_tpu.ops import selective_scan as ss

NAMES = ("x", "dt", "a", "b", "c", "d")


def _operands(seed=0, batch=2, seq=20, channels=6, state=3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (batch, seq, channels)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, seq, channels)))
    a = -jnp.exp(jax.random.normal(ks[2], (channels, state)))
    b = jax.random.normal(ks[3], (batch, seq, state)).astype(dtype)
    c = jax.random.normal(ks[4], (batch, seq, state)).astype(dtype)
    d = jax.random.normal(ks[5], (channels,))
    return x, dt, a, b, c, d


def _weighted(fn, weights):
    return lambda *ops: jnp.sum(weights * fn(*ops).astype(jnp.float32))


@pytest.mark.parametrize("chunk", [1, 4, 5, 7, 10, 20, 128])
def test_values_are_the_recurrence(chunk):
    """Chunks of 4, 5, 10 and 20 divide 20 tokens, 7 does not (padded with
    steps of size 0), 128 is one chunk."""
    ops = _operands()
    np.testing.assert_allclose(ss.selective_scan(*ops, chunk=chunk),
                               ss.selective_scan_recurrence(*ops), atol=2e-5)


@pytest.fixture(scope="module")
def gradients():
    """``gradients(chunk)`` → the scan's six gradients at that chunk, and
    ``gradients(None)`` the recurrence's: each differentiated once and read by
    its leaves' cases."""
    ops = _operands(seed=1)
    weights = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)
    made = {}

    def of(chunk):
        if chunk not in made:
            fn = ss.selective_scan_recurrence if chunk is None else (
                lambda *o: ss.selective_scan(*o, chunk=chunk))
            made[chunk] = jax.grad(_weighted(fn, weights), range(6))(*ops)
        return ops, made[chunk]

    return of


@pytest.mark.parametrize("chunk", [4, 7, 20])
@pytest.mark.parametrize("leaf", range(6), ids=NAMES)
def test_every_gradient_is_the_recurrences(gradients, chunk, leaf):
    (ops, got), (_, want) = gradients(chunk), gradients(None)
    got, want = got[leaf], want[leaf]
    assert got.shape == ops[leaf].shape and got.dtype == ops[leaf].dtype
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("chunk", [8, 6])
def test_a_strong_decay_forgets_without_inf_or_nan(chunk):
    """A = −16 at a step size of 10: exp(−160) underflows to 0 in f32; every
    exponent is of a non-positive number, so nothing overflows, in the values
    or in any gradient."""
    x, _, a, b, c, d = _operands(seed=2, seq=16)
    dt, a = jnp.full(x.shape, 10.0), jnp.full(a.shape, -16.0)
    y = ss.selective_scan(x, dt, a, b, c, d, chunk=chunk)
    # nothing is remembered: y_t = Δ_t x_t (B_t · C_t) + D x_t
    want = 10.0 * x * jnp.sum(b * c, axis=-1, keepdims=True) + d * x
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    grads = jax.grad(lambda *o: jnp.sum(ss.selective_scan(*o, chunk=chunk) ** 2),
                     argnums=range(6))(x, dt, a, b, c, d)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_operands_dtype_is_the_outputs_and_the_sums_are_f32(dtype):
    """bf16 x, B, C: y comes back in bf16, but the state and the sums are f32
    — it is the f32 scan of the same (rounded) operands, rounded once."""
    ops = _operands(seed=3, dtype=dtype)
    y = ss.selective_scan(*ops, chunk=8)
    assert y.dtype == dtype
    want = ss.selective_scan_recurrence(*(t.astype(jnp.float32) for t in ops))
    np.testing.assert_allclose(y.astype(jnp.float32), want.astype(dtype).astype(jnp.float32),
                               atol=2e-2 if dtype == jnp.bfloat16 else 2e-5)
    weights = jax.random.normal(jax.random.PRNGKey(4), y.shape)
    grads = jax.grad(_weighted(lambda *o: ss.selective_scan(*o, chunk=8), weights),
                     argnums=range(6))(*ops)
    assert [g.dtype for g in grads] == [t.dtype for t in ops]
    want = jax.grad(_weighted(ss.selective_scan_recurrence, weights), argnums=range(6))(
        *(t.astype(jnp.float32) for t in ops))
    for name, g, w in zip(NAMES, grads, want):
        np.testing.assert_allclose(g.astype(jnp.float32), w, err_msg=name,
                                   atol=(2e-2 if dtype == jnp.bfloat16 else 2e-5)
                                   * float(jnp.abs(w).max()))


def test_the_backward_pass_keeps_a_state_a_chunk():
    """The residuals of the custom_vjp: the operands and ONE state a chunk,
    (chunks, B, N, C) f32 — no array with a state a token."""
    ops = _operands(seq=24)
    _, residuals = ss._scan_fwd(*ops, 8)
    assert residuals[-1].shape == (3, 2, 3, 6) and residuals[-1].dtype == jnp.float32
    assert all(r.size <= ops[1].size for r in residuals)


def test_a_rebuilt_caller_does_not_scan_twice():
    """Under a ``jax.checkpoint`` that saves ``SAVED``, the backward program
    holds the backward scans alone: two nested ``while`` pairs fewer than with
    nothing saved."""
    ops = _operands(seq=16)

    def loss(policy):
        f = jax.checkpoint(lambda *o: ss.selective_scan(*o, chunk=8) * 2.0, policy=policy)
        return lambda *o: jnp.sum(f(*o))

    def scans(policy):
        text = str(jax.make_jaxpr(jax.grad(loss(policy), argnums=(0, 1)))(*ops))
        return text.count(" scan[")

    kept = scans(jax.checkpoint_policies.save_only_these_names(*ss.SAVED))
    rebuilt = scans(None)
    assert kept < rebuilt


def test_a_traced_call_is_counted():
    before = bps.get_robustness_counters().get("selective_scan_xla_traces", 0)
    jax.make_jaxpr(lambda *o: ss.selective_scan(*o))(*_operands())
    after = bps.get_robustness_counters()
    assert after["selective_scan_xla_traces"] == before + 1
