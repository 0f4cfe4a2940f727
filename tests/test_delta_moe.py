"""The gated-delta MoE family (models/delta_moe.py, ops/gated_delta.py,
parallel/moe.softmax_topk_route) against its plain reference
(models/delta_moe_reference.py) and its pieces against hand-written cases:
tiny widths, seeded random weights, f32, on the CPU mesh.  (The rule, the
router, the share and the hand-written cases: tests/test_delta_moe_pieces.py.)
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import delta_moe as dm
from byteps_tpu.models import delta_moe_reference as ref
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.mesh_utils import make_training_mesh

from test_latent_moe import _mesh, _system_loss_and_grads, _worst  # noqa: F401 (re-exported)


def _state(cfg, seed=0, batch=4):
    """Parameters with norm scales off their starting values, tokens,
    next-token targets."""
    params = dm.init_params(cfg, jax.random.PRNGKey(seed))
    for i, name in enumerate(params):
        if "norm" in name:
            params[name] = params[name] + 0.1 * jax.random.normal(
                jax.random.PRNGKey(seed + 100 + i), params[name].shape)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, cfg.max_seq), 0, cfg.vocab_size)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


VARIANTS = {
    "two_periods_of_two": dict(),
    "one_period_of_four": dict(full_attention_interval=4),
    "every_layer_full": dict(full_attention_interval=1, n_layers=2),
    "held_share_of_experts": dict(experts_held=2, expert_lo=4),
    "two_chunks_a_sequence": dict(chunk=8, max_seq=16, lin_k_heads=2, lin_v_heads=2),
}


@pytest.fixture(scope="module")
def tiny():
    """``tiny(variant)`` → that variant's config and state, with the system's
    and the reference's loss and gradients made once and shared by the cases."""
    made = {}

    def of(variant):
        if variant not in made:
            cfg = dm.tiny_delta_moe(**VARIANTS[variant])
            params, tokens, targets = _state(cfg)
            runs = {}

            def system(dp=1):
                if dp not in runs:
                    runs[dp] = _system_loss_and_grads(cfg, params, tokens, targets, dp)
                return runs[dp]

            def reference():
                if "ref" not in runs:
                    runs["ref"] = jax.jit(jax.value_and_grad(
                        lambda p: ref.loss(cfg, p, tokens, targets)))(params)
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
    want = jax.jit(lambda p, x: ref.forward(t.cfg, p, x))(t.params, t.tokens)
    assert got.shape == (4, t.cfg.max_seq, t.cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_loss_and_every_leaf_gradient_match_reference(tiny, variant):
    t = tiny(variant)
    loss, grads = t.system()
    want_loss, want = t.reference()
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert set(grads) == set(want) == set(dm.layouts(t.cfg))
    off, leaf = _worst(grads, want)
    assert off < 2e-4, f"{leaf} is {off:.2e} of its gradient off the reference's"


def test_same_loss_and_gradients_at_dp2_as_at_dp1(tiny):
    t = tiny("two_periods_of_two")
    loss1, grads1 = t.system(dp=1)
    loss2, grads2 = t.system(dp=2)
    assert loss2 == pytest.approx(loss1, rel=1e-6)
    off, leaf = _worst(grads2, grads1)
    assert off < 1e-4, f"{leaf} differs by {off:.2e} between dp 1 and dp 2"


def test_mesh_axes_that_are_not_built_are_refused():
    mesh = make_training_mesh(2, {"dp": 1, "pp": 1, "sp": 2, "tp": 1},
                              devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="data-parallel only"):
        tfm.build_train_step(dm.tiny_delta_moe(), mesh, optax.sgd(1.0))


def test_a_sequence_the_chunk_does_not_divide_raises():
    cfg = dm.tiny_delta_moe(chunk=8, max_seq=12)
    params, tokens, _ = _state(cfg)
    with pytest.raises(ValueError, match="does not divide"):
        tfm.build_forward(cfg, _mesh())(params, tokens)


def test_layers_that_are_no_whole_periods_are_refused():
    with pytest.raises(ValueError, match="whole number of periods"):
        dm.tiny_delta_moe(n_layers=5)


def test_routing_counts_reach_the_programs_counters(tiny):
    import byteps_tpu as bps

    t = tiny("held_share_of_experts")
    before = bps.get_robustness_counters()
    tx = optax.sgd(0.0)
    step = tfm.build_train_step(t.cfg, _mesh(), tx, donate=False)
    step(t.params, tx.init(t.params), t.tokens, t.targets)
    after = bps.get_robustness_counters()
    grown = {k: after.get(k, 0) - before.get(k, 0) for k in moe.ROUTING_STATS}
    slots = t.tokens.size * t.cfg.top_k * t.cfg.n_layers
    assert grown["moe_slots_routed"] == slots
    assert 0 < grown["moe_slots_held"] < slots and grown["moe_slots_dropped"] == 0
    assert grown["moe_slots_held"] <= grown["moe_rows_walked"] <= slots  # the chunks that ran
