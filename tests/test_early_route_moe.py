"""The early-routed MoE family (models/early_route_moe.py) against its plain
reference (models/early_route_moe_reference.py): tiny widths, seeded random
weights, on the CPU mesh.  (The router's place, the one sort, the shares, the
seam in ``held_expert_mlp`` and the cell's blocked reference by hand:
tests/test_early_route_moe_pieces.py.  Two files so that ``--dist loadfile``
spreads them.)
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import early_route_moe as em
from byteps_tpu.models import early_route_moe_reference as ref
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.mesh_utils import make_training_mesh

from test_latent_moe import _mesh, _system_loss_and_grads, _worst  # noqa: F401 (re-exported)

#: SmallThinker's ``sliding_window_layout`` = ``rope_layout``: a global layer
#: without positions where i % 4 == 0, 52 layers
PUBLISHED_PATTERN = tuple("sliding_attention" if i % 4 else "full_attention" for i in range(52))


def _state(cfg, seed=0, batch=4):
    """Parameters with norm scales off their starting values, tokens,
    next-token targets."""
    params = em.init_params(cfg, jax.random.PRNGKey(seed))
    for i, name in enumerate(params):
        if "norm" in name:
            params[name] = params[name] + 0.1 * jax.random.normal(
                jax.random.PRNGKey(seed + 100 + i), params[name].shape)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, cfg.max_seq), 0, cfg.vocab_size)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


#: name → config overrides
VARIANTS = {
    "one_period_group_of_seven": dict(),
    "window_first": dict(layer_types=("sliding_attention", "full_attention",
                                      "sliding_attention")),
    "group_of_three_two_kv_heads": dict(n_heads=6, n_kv_heads=2),
    "published_pattern": dict(layer_types=PUBLISHED_PATTERN[:12], max_seq=8, sliding_window=3),
    "held_share_of_experts": dict(experts_held=2, expert_lo=4),
    "window_of_one_top_3": dict(sliding_window=1, top_k=3),
    "window_over_the_sequence": dict(sliding_window=64),
}


@pytest.fixture(scope="module")
def tiny():
    """``tiny(variant)`` → that variant's config and state, with the system's
    and the reference's loss and gradients made once and shared by the cases."""
    made = {}

    def of(variant):
        if variant not in made:
            cfg = em.tiny_early_route_moe(**VARIANTS[variant])
            params, tokens, targets = _state(cfg, batch=2 if cfg.n_layers > 8 else 4)
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
    assert got.shape == t.tokens.shape + (t.cfg.vocab_size,)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_loss_and_every_leaf_gradient_match_reference(tiny, variant):
    """f32: what is left is the order of sums (a grouped product against a
    loop, the softmax over six against the full one's six renormalised), a
    few 1e-5 of a leaf's gradient."""
    t = tiny(variant)
    loss, grads = t.system()
    want_loss, want = t.reference()
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert set(grads) == set(want) == set(em.layouts(t.cfg))
    # the routers learn through the weights they give, across the attention
    assert all(np.any(g) for name, g in grads.items() if name.endswith("router"))
    off, leaf = _worst(grads, want)
    assert off < 2e-4, f"{leaf} is {off:.2e} of its gradient off the reference's"


def test_bf16_loss_and_gradients_stay_near_the_f32_reference():
    """bf16 operands, f32 statistics, at batch 1 (the CPU backend multiplies
    no bf16 with several batch dims at toy widths): 8 bits of mantissa give a
    few 1e-3 a product, and a near-tie among 8 logits or a gate near relu's
    kink that rounds to the other side moves one slot's whole contribution —
    so the limits are loose by design: a wrong equation reads 1."""
    cfg = em.tiny_early_route_moe(compute_dtype=jnp.bfloat16)
    params, tokens, targets = _state(cfg, batch=1)
    loss, grads = _system_loss_and_grads(cfg, params, tokens, targets)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(cfg, p, tokens, targets)))(params)
    assert loss == pytest.approx(float(want_loss), rel=2e-2)
    off, leaf = _worst(grads, want)
    assert off < 0.25, f"{leaf} is {off:.2e} of its gradient off the reference's"


def test_the_published_pattern_builds_its_stacks():
    cfg = em.tiny_early_route_moe(layer_types=PUBLISHED_PATTERN)
    assert cfg.n_layers == 52 and cfg.n_dense_layers == 0
    assert cfg.kinds()[:5] == (("glob", "moe"),) + (("win", "moe"),) * 3 + (("glob", "moe"),)
    assert {k: n for k, (n, _) in em.stacks(cfg).items()} == {"win": 39, "glob": 13, "moe": 52}
    shapes = {k: s for k, (s, _, _) in em.layouts(cfg).items()}
    assert shapes["head"] == shapes["embed"] == (96, 32)  # untied, laid out alike
    assert shapes["win.wq"] == (39, 32, 7, 8) and shapes["win.wk"] == (39, 32, 1, 8)
    # a layer's router stands with its mixer, whose input it reads; the MLP's
    # stack holds a norm and the experts, and no shared expert
    assert shapes["glob.router"] == (13, 32, 8) and set(em.stacks(cfg)["moe"][1]) == {
        "norm", "e_gate", "e_up", "e_down"}


def test_same_loss_and_gradients_at_dp2_as_at_dp1(tiny):
    t = tiny("one_period_group_of_seven")
    loss1, grads1 = t.system(dp=1)
    loss2, grads2 = t.system(dp=2)
    assert loss2 == pytest.approx(loss1, rel=1e-6)
    off, leaf = _worst(grads2, grads1)
    assert off < 1e-4, f"{leaf} differs by {off:.2e} between dp 1 and dp 2"


@pytest.mark.parametrize("axis", ["pp", "sp", "tp"])
def test_mesh_axes_that_are_not_built_are_refused(axis):
    sizes = {"dp": 1, "pp": 1, "sp": 1, "tp": 1, axis: 2}
    mesh = make_training_mesh(2, sizes, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="early-routed MoE family runs data-parallel only"):
        tfm.build_train_step(em.tiny_early_route_moe(), mesh, optax.sgd(1.0))


@pytest.mark.parametrize("overrides, match", [
    (dict(layer_types=("sliding_attention", "conv")), "conv"),
    (dict(layer_types=()), "nothing"),
    (dict(experts_held=4, expert_lo=6), "outside the router"),
    (dict(n_heads=6, n_kv_heads=4), "multiple of key/value heads"),
    (dict(head_dim=7), "even head_dim"),
    (dict(sliding_window=0), "the query itself"),
])
def test_patterns_and_shares_that_cannot_be_are_refused(overrides, match):
    with pytest.raises(ValueError, match=match):
        em.tiny_early_route_moe(**overrides)


def test_routing_counts_reach_the_programs_counters(tiny):
    import byteps_tpu as bps

    t = tiny("held_share_of_experts")
    before = bps.get_robustness_counters()
    tx = optax.sgd(0.0)
    step = tfm.build_train_step(t.cfg, _mesh(), tx, donate=False)
    step(t.params, tx.init(t.params), t.tokens, t.targets)
    after = bps.get_robustness_counters()
    grown = {k: after.get(k, 0) - before.get(k, 0) for k in moe.ROUTING_STATS}
    slots = t.tokens.size * t.cfg.top_k * t.cfg.n_layers  # every layer routes
    assert grown["moe_slots_routed"] == slots
    assert 0 < grown["moe_slots_held"] < slots and grown["moe_slots_dropped"] == 0
    assert grown["moe_slots_held"] <= grown["moe_rows_walked"] <= slots  # the chunks that ran
    assert 0 < grown["moe_fullest_expert_slots"] <= grown["moe_slots_held"]
