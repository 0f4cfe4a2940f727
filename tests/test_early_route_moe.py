"""The early-routed MoE family (models/early_route_moe.py) against its plain
reference (models/early_route_moe_reference.py): tiny widths, seeded random
weights, on the CPU mesh.  (The router's place, the one sort, the shares, the
seam in ``held_expert_mlp`` and the cell's blocked reference by hand:
tests/test_early_route_moe_pieces.py.  Two files so that ``--dist loadfile``
spreads them.)
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from byteps_tpu.models import early_route_moe as em
from byteps_tpu.models import early_route_moe_reference as ref

import family_cases as fc

#: SmallThinker's ``sliding_window_layout`` = ``rope_layout``: a global layer
#: without positions where i % 4 == 0, 52 layers
PUBLISHED_PATTERN = tuple("sliding_attention" if i % 4 else "full_attention" for i in range(52))

_state = functools.partial(fc._state, em)


def _published_also(cfg, shapes):
    assert cfg.n_dense_layers == 0
    assert shapes["head"] == shapes["embed"] == (96, 32)  # untied, laid out alike
    assert shapes["win.wq"] == (39, 32, 7, 8) and shapes["win.wk"] == (39, 32, 1, 8)
    # a layer's router stands with its mixer, whose input it reads; the MLP's
    # stack holds a norm and the experts, and no shared expert
    assert shapes["glob.router"] == (13, 32, 8) and set(em.stacks(cfg)["moe"][1]) == {
        "norm", "e_gate", "e_up", "e_down"}


FAMILY = fc.Family(
    name="early_route_moe", model=em, ref=ref, tiny=em.tiny_early_route_moe, state=_state,
    variants={
        "one_period_group_of_seven": dict(),
        "window_first": dict(layer_types=("sliding_attention", "full_attention",
                                          "sliding_attention")),
        "group_of_three_two_kv_heads": dict(n_heads=6, n_kv_heads=2),
        "published_pattern": dict(layer_types=PUBLISHED_PATTERN[:12], max_seq=8, sliding_window=3),
        "held_share_of_experts": dict(experts_held=2, expert_lo=4),
        "window_of_one_top_3": dict(sliding_window=1, top_k=3),
        "window_over_the_sequence": dict(sliding_window=64),
    },
    ref_logits=ref.forward,
    # the routers learn through the weights they give, across the attention
    learns=lambda cfg, name: True if name.endswith("router") else None,
    dp2=("one_period_group_of_seven", 1e-4),
    refused_axes=dict.fromkeys(("pp", "sp", "tp"),
                               "early-routed MoE family runs data-parallel only"),
    refused=(
        (dict(layer_types=("sliding_attention", "conv")), "conv"),
        (dict(layer_types=()), "nothing"),
        (dict(experts_held=4, expert_lo=6), "outside the router"),
        (dict(n_heads=6, n_kv_heads=4), "multiple of key/value heads"),
        (dict(head_dim=7), "even head_dim"),
        (dict(sliding_window=0), "the query itself"),
    ),
    published=(dict(layer_types=PUBLISHED_PATTERN), 52,
               (("glob", "moe"),) + (("win", "moe"),) * 3 + (("glob", "moe"),),
               {"win": 39, "glob": 13, "moe": 52}, _published_also),
    routing_layers=lambda cfg: cfg.n_layers,  # every layer routes
)
globals().update(fc.family_cases(FAMILY))


def test_bf16_loss_and_gradients_stay_near_the_f32_reference():
    """bf16 operands, f32 statistics, at batch 1 (the CPU backend multiplies
    no bf16 with several batch dims at toy widths): 8 bits of mantissa give a
    few 1e-3 a product, and a near-tie among 8 logits or a gate near relu's
    kink that rounds to the other side moves one slot's whole contribution —
    so the limits are loose by design: a wrong equation reads 1."""
    cfg = em.tiny_early_route_moe(compute_dtype=jnp.bfloat16)
    params, tokens, targets = _state(cfg, batch=1)
    loss, grads = fc._system_loss_and_grads(cfg, params, tokens, targets)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(cfg, p, tokens, targets)))(params)
    assert loss == pytest.approx(float(want_loss), rel=2e-2)
    off, leaf = fc._worst(grads, want)
    assert off < 0.25, f"{leaf} is {off:.2e} of its gradient off the reference's"
