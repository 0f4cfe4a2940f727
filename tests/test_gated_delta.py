"""``ops/gated_delta.py`` on its own, on the CPU: the chunked gated delta rule
— XLA's form at toy widths, the Pallas kernels in the interpreter at shapes
that tile — against the token-by-token recurrence, which form a call takes
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
