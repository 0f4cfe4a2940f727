"""The state-space MoE family (models/ssm_moe.py) against its plain reference
(models/ssm_moe_reference.py): tiny widths, seeded random weights, on the CPU
mesh.  (The chunked scan against the recurrence, the convolution's bias, the
grouped gated norm, the ungated experts, the router's order, the shares and
the cell's blocked reference by hand: tests/test_ssm_moe_pieces.py.  Two files
so that ``--dist loadfile`` spreads them.)
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import ssm_moe as sm
from byteps_tpu.models import ssm_moe_reference as ref
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.mesh_utils import make_training_mesh

from test_latent_moe import _mesh, _worst  # noqa: F401 (re-exported)

#: Nemotron-H's ``hybrid_override_pattern`` as TwoTower-30B-A3B publishes it
PUBLISHED_PATTERN = tuple("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")


def _state(cfg, seed=0, batch=4):
    """Parameters with norm scales, the selection bias and ``D`` off their
    starting values, tokens, next-token targets."""
    params = sm.init_params(cfg, jax.random.PRNGKey(seed))
    for i, name in enumerate(params):
        if "norm" in name or name.endswith(("router_bias", "d_skip")):
            params[name] = params[name] + 0.1 * jax.random.normal(
                jax.random.PRNGKey(seed + 100 + i), params[name].shape)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, cfg.max_seq), 0, cfg.vocab_size)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def _system_loss_and_grads(cfg, params, tokens, targets, dp=1):
    """Through build_train_step itself, the gradient kept as the "optimizer's"
    state.  (sgd at rate 1 and ``params − new``, the other families' way,
    loses ``dt_bias``'s and ``A_log``'s gradients in the subtraction's
    rounding: they are 1e-4 of their leaves, which start at −7 to 3.)"""
    keep = optax.GradientTransformation(
        lambda p: jax.tree.map(jnp.zeros_like, p),
        lambda g, state, p=None: (jax.tree.map(jnp.zeros_like, g), g))
    step = tfm.build_train_step(cfg, _mesh(dp), keep, donate=False)
    _, grads, loss = step(params, keep.init(params), tokens, targets)
    return float(loss), {k: np.asarray(v) for k, v in jax.device_get(grads).items()}


#: name → config overrides
VARIANTS = {
    "five_layers_three_kinds": dict(),
    "attention_first": dict(layer_types=tuple("*ME")),
    "mixers_alone": dict(layer_types=tuple("MM*")),
    "one_chunk_a_sequence": dict(chunk=16),
    "one_head_a_group_16_query_heads": dict(ssm_groups=4, n_heads=16, n_kv_heads=1, head_dim=4),
    "published_start": dict(layer_types=PUBLISHED_PATTERN[:9]),
    "held_share_of_experts": dict(experts_held=2, expert_lo=4),
    "top_3_unscaled": dict(top_k=3, routed_scale=1.0),
}


@pytest.fixture(scope="module")
def tiny():
    """``tiny(variant)`` → that variant's config and state, with the system's
    and the reference's loss and gradients made once and shared by the cases."""
    made = {}

    def of(variant):
        if variant not in made:
            cfg = sm.tiny_ssm_moe(**VARIANTS[variant])
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
    """f32: what is left is the order of sums (chunks against tokens, a
    grouped product against a loop), a few 1e-5 of a leaf's gradient."""
    t = tiny(variant)
    loss, grads = t.system()
    want_loss, want = t.reference()
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert set(grads) == set(want) == set(sm.layouts(t.cfg))
    # every leaf learns, but the bias that only picks
    assert all(np.any(g) == (not name.endswith("router_bias")) for name, g in grads.items())
    off, leaf = _worst(grads, want)
    assert off < 2e-4, f"{leaf} is {off:.2e} of its gradient off the reference's"


def test_three_adamw_steps_match_the_references(tiny):
    """The same three steps by the program and by ``jax.value_and_grad`` of
    the reference with the same optax transformation: losses and parameters."""
    t = tiny("five_layers_three_kinds")
    tx = optax.adamw(1e-3)
    step = tfm.build_train_step(t.cfg, _mesh(), tx, donate=False)

    @jax.jit
    def ref_step(p, s):
        loss, grads = jax.value_and_grad(lambda p: ref.loss(t.cfg, p, t.tokens, t.targets))(p)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    p, s, q, r = t.params, tx.init(t.params), t.params, tx.init(t.params)
    for _ in range(3):
        p, s, loss = step(p, s, t.tokens, t.targets)
        q, r, want = ref_step(q, r)
        assert float(loss) == pytest.approx(float(want), rel=1e-5)
    moved = {k: np.asarray(q[k]) - np.asarray(t.params[k]) for k in q}
    off = {k: np.asarray(p[k]) - np.asarray(q[k]) for k in q}
    # adam's first steps are lr · sign(g): an element whose tiny gradient
    # rounds to the other sign moves the other way, so the share is loose
    whole = np.sqrt(sum(np.sum(v ** 2) for v in off.values())
                    / sum(np.sum(v ** 2) for v in moved.values()))
    assert whole < 0.05, f"the parameters are {whole:.2e} of the reference's update apart"


def test_bf16_loss_and_gradients_stay_near_the_f32_reference():
    """bf16 operands, f32 statistics, decay sums and states, at batch 1 (the
    CPU backend multiplies no bf16 with several batch dims at toy widths): 8
    bits of mantissa give a few 1e-3 a product, and a near-tie among 8 scores
    that rounds to the other side moves one slot's whole contribution — so the
    limits are loose by design: a wrong equation reads 1."""
    cfg = sm.tiny_ssm_moe(compute_dtype=jnp.bfloat16)
    params, tokens, targets = _state(cfg, batch=1)
    loss, grads = _system_loss_and_grads(cfg, params, tokens, targets)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(cfg, p, tokens, targets)))(params)
    assert loss == pytest.approx(float(want_loss), rel=2e-2)
    grads.pop("moe.router_bias"), want.pop("moe.router_bias")  # picks, never learns: 0 = 0
    off, leaf = _worst(grads, want)
    assert off < 0.25, f"{leaf} is {off:.2e} of its gradient off the reference's"


def test_the_published_pattern_builds_its_stacks():
    cfg = sm.tiny_ssm_moe(layer_types=PUBLISHED_PATTERN)
    assert cfg.n_layers == 52
    # one stack a layer: a mixer or an MLP alone
    assert cfg.kinds()[:7] == (("ssm",), ("moe",), ("ssm",), ("moe",), ("ssm",), ("attn",),
                               ("moe",))
    assert {k: n for k, (n, _) in sm.stacks(cfg).items()} == {"ssm": 23, "attn": 6, "moe": 23}
    shapes = {k: s for k, (s, _, _) in sm.layouts(cfg).items()}
    assert shapes["head"] == shapes["embed"] == (96, 32)  # untied, laid out alike
    # in_proj's columns: z | x | B | C | dt
    assert shapes["ssm.w_in"] == (23, 32, 24 + (24 + 2 * 2 * 5) + 4)
    assert shapes["ssm.conv"] == (23, 4, 44) and shapes["ssm.conv_bias"] == (23, 44)
    assert shapes["attn.wq"] == (6, 32, 6, 8) and shapes["attn.wk"] == (6, 32, 2, 8)
    # ungated experts: two matrices each, the shared one twice as wide
    assert set(sm.stacks(cfg)["moe"][1]) == {
        "norm", "router", "router_bias", "e_up", "e_down", "s_up", "s_down"}
    assert shapes["moe.e_up"] == (23, 8, 32, 16) and shapes["moe.s_up"] == (23, 32, 32)


def test_the_published_sizes_count_the_issues_parameters():
    """One chip's share at the published widths, by shapes alone: 667.0 M at
    nine layers, 528.1 M at seven."""
    def held(n_layers):
        cfg = sm.SsmMoEConfig(vocab_size=16384, layer_types=PUBLISHED_PATTERN[:n_layers],
                              experts_held=8)
        by_stack = {}
        for name, (shape, _, _) in sm.layouts(cfg).items():
            stack = name.split(".")[0] if "." in name else "top"
            by_stack[stack] = by_stack.get(stack, 0) + int(np.prod(shape))
        return cfg, by_stack

    cfg, nine = held(9)
    assert nine["ssm"] == 4 * 38_744_896 and nine["attn"] == 23_399_040
    assert nine["moe"] == 4 * 100_125_440 and nine["top"] == 2 * 16384 * 2688 + 2688
    assert sum(nine.values()) == 666_963_456
    assert sum(held(7)[1].values()) == 528_093_120


def test_same_loss_and_gradients_at_dp2_as_at_dp1(tiny):
    t = tiny("five_layers_three_kinds")
    loss1, grads1 = t.system(dp=1)
    loss2, grads2 = t.system(dp=2)
    assert loss2 == pytest.approx(loss1, rel=1e-6)
    off, leaf = _worst(grads2, grads1)
    assert off < 1e-4, f"{leaf} differs by {off:.2e} between dp 1 and dp 2"


@pytest.mark.parametrize("axis", ["pp", "sp", "tp"])
def test_mesh_axes_that_are_not_built_are_refused(axis):
    sizes = {"dp": 1, "pp": 1, "sp": 1, "tp": 1, axis: 2}
    mesh = make_training_mesh(2, sizes, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="state-space MoE family runs data-parallel only"):
        tfm.build_train_step(sm.tiny_ssm_moe(), mesh, optax.sgd(1.0))


@pytest.mark.parametrize("overrides, match", [
    (dict(layer_types=tuple("ME-")), "-"),
    (dict(layer_types=()), "nothing"),
    (dict(experts_held=4, expert_lo=6), "outside the router"),
    (dict(n_heads=6, n_kv_heads=4), "multiple of key/value heads"),
    (dict(ssm_heads=4, ssm_groups=3), "no multiple of 3 groups"),
])
def test_patterns_and_shares_that_cannot_be_are_refused(overrides, match):
    with pytest.raises(ValueError, match=match):
        sm.tiny_ssm_moe(**overrides)


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    cfg = sm.tiny_ssm_moe(chunk=6)
    params, tokens, targets = _state(cfg, batch=1)
    with pytest.raises(ValueError, match="chunk 6 does not divide sequence 16"):
        sm.local_logits(cfg, params, tokens)


def test_routing_and_scan_counts_reach_the_programs_counters(tiny):
    import byteps_tpu as bps

    t = tiny("held_share_of_experts")
    before = bps.get_robustness_counters()
    tx = optax.sgd(0.0)
    step = tfm.build_train_step(t.cfg, _mesh(), tx, donate=False)
    step(t.params, tx.init(t.params), t.tokens, t.targets)
    after = bps.get_robustness_counters()
    grown = {k: after.get(k, 0) - before.get(k, 0) for k in moe.ROUTING_STATS}
    slots = t.tokens.size * t.cfg.top_k * t.cfg.layer_types.count("E")
    assert grown["moe_slots_routed"] == slots
    assert 0 < grown["moe_slots_held"] < slots and grown["moe_slots_dropped"] == 0
    assert grown["moe_slots_held"] <= grown["moe_rows_walked"] <= slots  # the chunks that ran
    assert 0 < grown["moe_fullest_expert_slots"] <= grown["moe_slots_held"]
    # a traced call of the scan's one form counts itself, as the gated delta rule's do
    assert after.get("ssd_xla_traces", 0) > before.get("ssd_xla_traces", 0)
