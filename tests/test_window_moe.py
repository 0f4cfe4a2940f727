"""The sliding-window / global-attention MoE family (models/window_moe.py)
against its plain reference (models/window_moe_reference.py): tiny widths,
seeded random weights, f32, on the CPU mesh.  (The two masks, rope on one
kind alone, the gate, the four norms, the router, the shares and the cell's
blocked reference by hand: tests/test_window_moe_pieces.py.  Two files so
that ``--dist loadfile`` spreads them.)
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import transformer as tfm
from byteps_tpu.models import window_moe as wm
from byteps_tpu.models import window_moe_reference as ref
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.mesh_utils import make_training_mesh

from test_latent_moe import _mesh, _system_loss_and_grads, _worst  # noqa: F401 (re-exported)

#: Trinity-Mini's ``layer_types``: a global layer where i % 4 == 3, 32 layers
PUBLISHED_PATTERN = tuple("full_attention" if i % 4 == 3 else "sliding_attention"
                          for i in range(32))


def _state(cfg, seed=0, batch=4, bias=0.01):
    """Parameters with norm scales off their starting values and a selection
    bias of standard deviation ``bias``, tokens, next-token targets."""
    params = wm.init_params(cfg, jax.random.PRNGKey(seed))
    for i, name in enumerate(params):
        k = jax.random.PRNGKey(seed + 100 + i)
        if "norm" in name:
            params[name] = params[name] + 0.1 * jax.random.normal(k, params[name].shape)
        elif name.endswith("router_bias"):
            params[name] = bias * jax.random.normal(k, params[name].shape)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, cfg.max_seq), 0, cfg.vocab_size)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


#: name → (config overrides, the selection bias's standard deviation)
VARIANTS = {
    "window_first_one_dense": (dict(), 0.01),
    "global_first_no_dense": (dict(layer_types=("full_attention", "sliding_attention",
                                                "sliding_attention"), n_dense_layers=0), 0.01),
    "two_dense_layers": (dict(n_dense_layers=2), 0.01),
    "published_pattern": (dict(layer_types=PUBLISHED_PATTERN, n_dense_layers=2, max_seq=8,
                               sliding_window=3), 0.01),
    "mup_off": (dict(mup=False), 0.01),
    "skewed_expert_bias": (dict(), 0.5),
    "held_share_of_experts": (dict(experts_held=2, expert_lo=4), 0.01),
    "window_of_one_and_wider_shared_expert": (dict(sliding_window=1, d_shared=32), 0.01),
}


@pytest.fixture(scope="module")
def tiny():
    """``tiny(variant)`` → that variant's config and state, with the system's
    and the reference's loss and gradients made once and shared by the cases."""
    made = {}

    def of(variant):
        if variant not in made:
            overrides, bias = VARIANTS[variant]
            cfg = wm.tiny_window_moe(**overrides)
            params, tokens, targets = _state(cfg, bias=bias,
                                             batch=2 if cfg.n_layers > 8 else 4)
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
    t = tiny(variant)
    loss, grads = t.system()
    want_loss, want = t.reference()
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert set(grads) == set(want) == set(wm.layouts(t.cfg))
    assert not np.any(grads["moe.router_bias"])  # the bias picks: it takes no gradient
    off, leaf = _worst(grads, want)
    assert off < 2e-4, f"{leaf} is {off:.2e} of its gradient off the reference's"


def test_the_published_pattern_builds_its_stacks():
    cfg = wm.tiny_window_moe(layer_types=PUBLISHED_PATTERN, n_dense_layers=2)
    assert cfg.n_layers == 32 and cfg.kinds()[:4] == (
        ("win", "dense"), ("win", "dense"), ("win", "moe"), ("glob", "moe"))
    assert {k: n for k, (n, _) in wm.stacks(cfg).items()} == {
        "win": 24, "glob": 8, "dense": 2, "moe": 30}
    # the cut's five layers are entries 1-5 of the list, the dense layers counted once
    cut = wm.tiny_window_moe(layer_types=PUBLISHED_PATTERN[1:6], n_dense_layers=1)
    assert cut.kinds() == (("win", "dense"), ("win", "moe"), ("glob", "moe"),
                           ("win", "moe"), ("win", "moe"))
    shapes = {k: s for k, (s, _, _) in wm.layouts(cfg).items()}
    assert shapes["head"] == shapes["embed"] == (96, 32)  # untied, laid out alike
    assert shapes["win.wg"] == shapes["win.wq"] == (24, 32, 4, 8)
    assert {"win.post_norm", "glob.post_norm", "dense.post_norm", "moe.post_norm"} <= set(shapes)


def test_same_loss_and_gradients_at_dp2_as_at_dp1(tiny):
    t = tiny("window_first_one_dense")
    loss1, grads1 = t.system(dp=1)
    loss2, grads2 = t.system(dp=2)
    assert loss2 == pytest.approx(loss1, rel=1e-6)
    off, leaf = _worst(grads2, grads1)
    assert off < 1e-4, f"{leaf} differs by {off:.2e} between dp 1 and dp 2"


@pytest.mark.parametrize("axis", ["pp", "sp", "tp"])
def test_mesh_axes_that_are_not_built_are_refused(axis):
    sizes = {"dp": 1, "pp": 1, "sp": 1, "tp": 1, axis: 2}
    mesh = make_training_mesh(2, sizes, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="data-parallel only"):
        tfm.build_train_step(wm.tiny_window_moe(), mesh, optax.sgd(1.0))


@pytest.mark.parametrize("overrides, match", [
    (dict(layer_types=("sliding_attention", "conv")), "conv"),
    (dict(layer_types=()), "nothing"),
    (dict(n_dense_layers=5), "leading dense layers"),
    (dict(experts_held=4, expert_lo=6), "outside the router"),
    (dict(n_heads=3), "multiple of key/value heads"),
    (dict(head_dim=7), "even head_dim"),
    (dict(sliding_window=0), "the query itself"),
])
def test_patterns_and_shares_that_cannot_be_are_refused(overrides, match):
    with pytest.raises(ValueError, match=match):
        wm.tiny_window_moe(**overrides)


def test_routing_counts_reach_the_programs_counters(tiny):
    import byteps_tpu as bps

    t = tiny("held_share_of_experts")
    before = bps.get_robustness_counters()
    tx = optax.sgd(0.0)
    step = tfm.build_train_step(t.cfg, _mesh(), tx, donate=False)
    step(t.params, tx.init(t.params), t.tokens, t.targets)
    after = bps.get_robustness_counters()
    grown = {k: after.get(k, 0) - before.get(k, 0) for k in moe.ROUTING_STATS}
    expert_layers = t.cfg.n_layers - t.cfg.n_dense_layers  # the dense layer routes nothing
    slots = t.tokens.size * t.cfg.top_k * expert_layers
    assert grown["moe_slots_routed"] == slots
    assert 0 < grown["moe_slots_held"] < slots and grown["moe_slots_dropped"] == 0
    assert grown["moe_slots_held"] <= grown["moe_rows_walked"] <= slots  # the chunks that ran
    assert 0 < grown["moe_fullest_expert_slots"] <= grown["moe_slots_held"]
