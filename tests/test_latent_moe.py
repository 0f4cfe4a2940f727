"""The latent-attention MoE family (models/latent_moe.py) against its plain
reference (models/latent_moe_reference.py): tiny widths, seeded random
weights, f32, on the CPU mesh.  (The pieces the family brought — the
held-expert layer, the flash kernel at d_qk != d_v, the routing counters:
tests/test_latent_moe_pieces.py.  The cell's blocked reference, its
precision controls and leg E's readings: tests/test_latent_moe_readings.py.
Three files so that `--dist loadfile` spreads what was one worker's 319 s.)
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import latent_moe as lm
from byteps_tpu.models import latent_moe_reference as ref
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel.mesh_utils import make_training_mesh


def _mesh(dp=1):
    return make_training_mesh(dp, {"dp": dp, "pp": 1, "sp": 1, "tp": 1},
                              devices=jax.devices()[:dp])


def _state(cfg, seed=0, batch=4, bias=0.3):
    """Parameters with a NON-ZERO selection bias, tokens, next-token targets."""
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    for name in params:
        if name.endswith("router_bias"):
            params[name] = bias * jax.random.normal(
                jax.random.PRNGKey(seed + 7), params[name].shape)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, cfg.max_seq), 0, cfg.vocab_size)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def _system_loss_and_grads(cfg, params, tokens, targets, dp=1):
    """Through build_train_step itself: sgd at rate 1 turns the update into
    the gradient."""
    tx = optax.sgd(1.0)
    step = tfm.build_train_step(cfg, _mesh(dp), tx, donate=False)
    new, _, loss = step(params, tx.init(params), tokens, targets)
    new = jax.device_get(new)  # off the mesh: dp 2 leaves them on two devices
    return float(loss), {k: np.asarray(params[k]) - new[k] for k in params}


def _worst(got, want):
    """(relative L2 distance, leaf) of the leaf that is furthest off."""
    def rel(k):
        scale = float(jnp.linalg.norm(want[k]))
        diff = float(jnp.linalg.norm(got[k] - want[k]))
        return diff / scale if scale else diff
    return max((rel(k), k) for k in got)


VARIANTS = {
    "dense_layer": dict(n_expert_layers=0, mtp_modules=0),
    "expert_layer_biased_choice": dict(n_dense_layers=0, n_expert_layers=1, mtp_modules=0),
    "two_kinds_no_mtp": dict(mtp_modules=0),
    "mtp_and_both_losses": dict(),
    "held_share_of_experts": dict(experts_held=2, expert_lo=4),
}


@pytest.fixture(scope="module")
def tiny():
    """``tiny(variant)`` → that variant's config and state, with the system's
    and the reference's loss and gradients made once and shared by the cases:
    ``.system(dp)`` and ``.reference()`` each return (loss, gradients)."""
    made = {}

    def of(variant):
        if variant not in made:
            cfg = lm.tiny_latent_moe(**VARIANTS[variant])
            params, tokens, targets = _state(cfg)
            runs = {}

            def system(dp=1):
                if dp not in runs:
                    runs[dp] = _system_loss_and_grads(cfg, params, tokens, targets, dp)
                return runs[dp]

            def reference():
                if "ref" not in runs:
                    runs["ref"] = jax.value_and_grad(
                        lambda p: ref.loss(cfg, p, tokens, targets))(params)
                return runs["ref"]

            made[variant] = types.SimpleNamespace(
                cfg=cfg, params=params, tokens=tokens, targets=targets,
                system=system, reference=reference)
        return made[variant]

    return of


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_logits_match_reference(tiny, variant):
    t = tiny(variant)
    got = tfm.build_forward(t.cfg, _mesh())(t.params, t.tokens)[0]
    want, _ = ref.forward(t.cfg, t.params, t.tokens)
    assert got.shape == (4, t.cfg.max_seq, t.cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_loss_and_every_leaf_gradient_match_reference(tiny, variant):
    t = tiny(variant)
    loss, grads = t.system()
    want_loss, want = t.reference()
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert set(grads) == set(want) == set(lm.layouts(t.cfg))
    off, leaf = _worst(grads, want)
    assert off < 1e-4, f"{leaf} is {off:.2e} of its gradient off the reference's"
    for name in want:  # the selection bias picks and takes no gradient
        if name.endswith("router_bias"):
            assert not np.any(np.asarray(grads[name]))


def test_mtp_loss_is_a_second_term_with_its_weight(tiny):
    t = tiny("mtp_and_both_losses")
    with_mtp = t.reference()[0]
    main = ref.loss(lm.tiny_latent_moe(mtp_modules=0),
                    {k: v for k, v in t.params.items() if "mtp" not in k}, t.tokens, t.targets)
    double = ref.loss(lm.tiny_latent_moe(mtp_lambda=0.6), t.params, t.tokens, t.targets)
    assert float(with_mtp) > float(main)
    assert float(double - main) == pytest.approx(2 * float(with_mtp - main), rel=1e-5)


def test_same_loss_and_gradients_at_dp2_as_at_dp1(tiny):
    t = tiny("mtp_and_both_losses")
    loss1, grads1 = t.system(dp=1)
    loss2, grads2 = t.system(dp=2)
    assert loss2 == pytest.approx(loss1, rel=1e-6)
    off, leaf = _worst(grads2, grads1)
    assert off < 1e-5, f"{leaf} differs by {off:.2e} between dp 1 and dp 2"


def test_mesh_axes_that_are_not_built_are_refused():
    cfg = lm.tiny_latent_moe()
    mesh = make_training_mesh(2, {"dp": 1, "pp": 1, "sp": 1, "tp": 2},
                              devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="data-parallel only"):
        tfm.build_train_step(cfg, mesh, optax.sgd(1.0))


# ---------------------------------------------------------------------------
# what shares build_train_step with this family stays as it was
# ---------------------------------------------------------------------------


def test_bert_tiny_preset_loss_is_bit_equal_to_before_this_family():
    """Recorded at the parent commit (f12c015) with this very script: two
    adamw steps of ``tiny_test()`` on one device."""
    cfg = tfm.tiny_test()
    mesh = _mesh()
    params = tfm.shard_params(tfm.init_params(cfg, seed=0), cfg, mesh)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, cfg.max_seq)), jnp.int32)
    tx = optax.adamw(1e-3)
    step = tfm.build_train_step(cfg, mesh, tx, donate=False)
    state, losses = tx.init(params), []
    for _ in range(2):
        params, state, loss = step(params, state, tokens, jnp.roll(tokens, -1, 1))
        losses.append(np.float32(loss).tobytes().hex())
    assert losses == ["b3eb9140", "c0a78b40"]
