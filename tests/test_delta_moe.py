"""The gated-delta MoE family (models/delta_moe.py, ops/gated_delta.py,
parallel/moe.softmax_topk_route) against its plain reference
(models/delta_moe_reference.py) and its pieces against hand-written cases:
tiny widths, seeded random weights, f32, on the CPU mesh.  (The rule, the
router, the share and the hand-written cases: tests/test_delta_moe_pieces.py.)
"""

import functools

import pytest

from byteps_tpu.models import delta_moe as dm
from byteps_tpu.models import delta_moe_reference as ref
from byteps_tpu.models import transformer as tfm

import family_cases as fc

_state = functools.partial(fc._state, dm)

FAMILY = fc.Family(
    name="delta_moe", model=dm, ref=ref, tiny=dm.tiny_delta_moe, state=_state,
    variants={
        "two_periods_of_two": dict(),
        "one_period_of_four": dict(full_attention_interval=4),
        "every_layer_full": dict(full_attention_interval=1, n_layers=2),
        "held_share_of_experts": dict(experts_held=2, expert_lo=4),
        "two_chunks_a_sequence": dict(chunk=8, max_seq=16, lin_k_heads=2, lin_v_heads=2),
    },
    ref_logits=ref.forward,
    dp2=("two_periods_of_two", 1e-4),
    refused_axes=dict.fromkeys(("pp", "sp", "tp"), "data-parallel only"),
    refused=((dict(n_layers=5), "whole number of periods"),),
    routing_layers=lambda cfg: cfg.n_layers,
)
globals().update(fc.family_cases(FAMILY))


def test_a_sequence_the_chunk_does_not_divide_raises():
    cfg = dm.tiny_delta_moe(chunk=8, max_seq=12)
    params, tokens, _ = _state(cfg)
    with pytest.raises(ValueError, match="does not divide"):
        tfm.build_forward(cfg, fc._mesh())(params, tokens)
