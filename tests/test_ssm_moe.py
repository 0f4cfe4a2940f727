"""The state-space MoE family (models/ssm_moe.py) against its plain reference
(models/ssm_moe_reference.py): tiny widths, seeded random weights, on the CPU
mesh.  (The chunked scan against the recurrence, the convolution's bias, the
grouped gated norm, the ungated experts, the router's order, the shares and
the cell's blocked reference by hand: tests/test_ssm_moe_pieces.py.  Two files
so that ``--dist loadfile`` spreads them.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import ssm_moe as sm
from byteps_tpu.models import ssm_moe_reference as ref
from byteps_tpu.models import transformer as tfm

import family_cases as fc

#: Nemotron-H's ``hybrid_override_pattern`` as TwoTower-30B-A3B publishes it
PUBLISHED_PATTERN = tuple("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")

#: the norms' scales, the selection bias and ``D`` off their starting values
_state = functools.partial(
    fc._state, sm, moved=lambda name: "norm" in name or name.endswith(("router_bias", "d_skip")))


def _published_also(cfg, shapes):
    assert shapes["head"] == shapes["embed"] == (96, 32)  # untied, laid out alike
    # in_proj's columns: z | x | B | C | dt
    assert shapes["ssm.w_in"] == (23, 32, 24 + (24 + 2 * 2 * 5) + 4)
    assert shapes["ssm.conv"] == (23, 4, 44) and shapes["ssm.conv_bias"] == (23, 44)
    assert shapes["attn.wq"] == (6, 32, 6, 8) and shapes["attn.wk"] == (6, 32, 2, 8)
    # ungated experts: two matrices each, the shared one twice as wide
    assert set(sm.stacks(cfg)["moe"][1]) == {
        "norm", "router", "router_bias", "e_up", "e_down", "s_up", "s_down"}
    assert shapes["moe.e_up"] == (23, 8, 32, 16) and shapes["moe.s_up"] == (23, 32, 32)


FAMILY = fc.Family(
    name="ssm_moe", model=sm, ref=ref, tiny=sm.tiny_ssm_moe, state=_state,
    variants={
        "five_layers_three_kinds": dict(),
        "attention_first": dict(layer_types=tuple("*ME")),
        "mixers_alone": dict(layer_types=tuple("MM*")),
        "one_chunk_a_sequence": dict(chunk=16),
        "one_head_a_group_16_query_heads": dict(ssm_groups=4, n_heads=16, n_kv_heads=1,
                                                head_dim=4),
        "published_start": dict(layer_types=PUBLISHED_PATTERN[:9]),
        "held_share_of_experts": dict(experts_held=2, expert_lo=4),
        "top_3_unscaled": dict(top_k=3, routed_scale=1.0),
    },
    ref_logits=ref.forward,
    # every leaf learns, but the bias that only picks
    learns=lambda cfg, name: not name.endswith("router_bias"),
    dp2=("five_layers_three_kinds", 1e-4),
    refused_axes=dict.fromkeys(("pp", "sp", "tp"),
                               "state-space MoE family runs data-parallel only"),
    refused=(
        (dict(layer_types=tuple("ME-")), "-"),
        (dict(layer_types=()), "nothing"),
        (dict(experts_held=4, expert_lo=6), "outside the router"),
        (dict(n_heads=6, n_kv_heads=4), "multiple of key/value heads"),
        (dict(ssm_heads=4, ssm_groups=3), "no multiple of 3 groups"),
    ),
    # one stack a layer: a mixer or an MLP alone
    published=(dict(layer_types=PUBLISHED_PATTERN), 52,
               (("ssm",), ("moe",), ("ssm",), ("moe",), ("ssm",), ("attn",), ("moe",)),
               {"ssm": 23, "attn": 6, "moe": 23}, _published_also),
    routing_layers=lambda cfg: cfg.layer_types.count("E"),
    also_counts=("ssd_xla_traces",),  # as the gated delta rule's do
)
globals().update(fc.family_cases(FAMILY))


def test_three_adamw_steps_match_the_references(tiny):
    """The same three steps by the program and by ``jax.value_and_grad`` of
    the reference with the same optax transformation: losses and parameters."""
    t = tiny("five_layers_three_kinds")
    tx = optax.adamw(1e-3)
    step = tfm.build_train_step(t.cfg, fc._mesh(), tx, donate=False)

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
    loss, grads = fc._system_loss_and_grads(cfg, params, tokens, targets)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(cfg, p, tokens, targets)))(params)
    assert loss == pytest.approx(float(want_loss), rel=2e-2)
    grads.pop("moe.router_bias"), want.pop("moe.router_bias")  # picks, never learns: 0 = 0
    off, leaf = fc._worst(grads, want)
    assert off < 0.25, f"{leaf} is {off:.2e} of its gradient off the reference's"


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


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    cfg = sm.tiny_ssm_moe(chunk=6)
    params, tokens, targets = _state(cfg, batch=1)
    with pytest.raises(ValueError, match="chunk 6 does not divide sequence 16"):
        sm.local_logits(cfg, params, tokens)
