"""The short-convolution MoE family (models/conv_moe.py) against its plain
reference (models/conv_moe_reference.py): tiny widths, seeded random weights,
f32, on the CPU mesh.  (The convolution, the gates, the norms and rope, the
router, the share and the cell's blocked reference by hand:
tests/test_conv_moe_pieces.py.  Two files so that ``--dist loadfile`` spreads
them.)
"""

import functools

from byteps_tpu.models import conv_moe as cm
from byteps_tpu.models import conv_moe_reference as ref

import family_cases as fc

#: LFM2-24B-A2B's ``layer_types``: attention where i % 4 == 2, 40 layers
PUBLISHED_PATTERN = tuple("full_attention" if i % 4 == 2 else "conv" for i in range(40))

_state = functools.partial(fc._state, cm, bias=0.01)


def _published_also(cfg, shapes):
    # the cut's five layers are entries 1-5 of the list, the dense layers counted once
    cut = cm.tiny_conv_moe(layer_types=PUBLISHED_PATTERN[1:6], n_dense_layers=1)
    assert cut.kinds() == (("conv", "dense"), ("attn", "moe"), ("conv", "moe"),
                           ("conv", "moe"), ("conv", "moe"))
    assert "head" not in shapes  # tied: the logits are taken with embed


FAMILY = fc.Family(
    name="conv_moe", model=cm, ref=ref, tiny=cm.tiny_conv_moe, state=_state,
    # name → (config overrides, the selection bias's standard deviation)
    variants={
        "conv_first_one_dense": (dict(), 0.01),
        "attention_first_no_dense": (dict(layer_types=("full_attention", "conv", "conv"),
                                          n_dense_layers=0), 0.01),
        "two_dense_layers": (dict(n_dense_layers=2), 0.01),
        "every_layer_dense": (dict(layer_types=("conv", "full_attention"), n_dense_layers=2), 0.01),
        # the 40 layers compiled whole, and their twin: the list's distinct
        # prefix (both dense layers, the first attention, the first expert
        # layers) and one whole period after it
        "published_pattern": (dict(layer_types=PUBLISHED_PATTERN, n_dense_layers=2, max_seq=8),
                              0.01),
        "published_prefix_and_period": (dict(layer_types=PUBLISHED_PATTERN[:8],
                                             n_dense_layers=2, max_seq=8), 0.01),
        "skewed_expert_bias": (dict(), 0.5),
        "held_share_of_experts": (dict(experts_held=2, expert_lo=4), 0.01),
    },
    slow=("published_pattern",),
    ref_logits=ref.forward,
    learns=lambda cfg, name: False if name.endswith("router_bias") else None,  # it picks
    dp2=("conv_first_one_dense", 1e-4),
    refused_axes=dict.fromkeys(("pp", "sp", "tp"), "data-parallel only"),
    refused=(
        (dict(layer_types=("conv", "sliding_attention")), "sliding_attention"),
        (dict(layer_types=()), "nothing"),
        (dict(n_dense_layers=5), "leading dense layers"),
        (dict(experts_held=4, expert_lo=6), "outside the router"),
        (dict(n_heads=3), "multiple of key/value heads"),
        (dict(head_dim=7), "even head_dim"),
    ),
    published=(dict(layer_types=PUBLISHED_PATTERN, n_dense_layers=2), 40,
               (("conv", "dense"), ("conv", "dense"), ("attn", "moe")),
               {"conv": 30, "attn": 10, "dense": 2, "moe": 38}, _published_also),
    routing_layers=lambda cfg: cfg.n_layers - cfg.n_dense_layers,  # a dense layer routes nothing
)
globals().update(fc.family_cases(FAMILY))
