"""The sliding-window / global-attention MoE family (models/window_moe.py)
against its plain reference (models/window_moe_reference.py): tiny widths,
seeded random weights, f32, on the CPU mesh.  (The two masks, rope on one
kind alone, the gate, the four norms, the router, the shares and the cell's
blocked reference by hand: tests/test_window_moe_pieces.py.  Two files so
that ``--dist loadfile`` spreads them.)
"""

import functools

from byteps_tpu.models import window_moe as wm
from byteps_tpu.models import window_moe_reference as ref

import family_cases as fc

#: Trinity-Mini's ``layer_types``: a global layer where i % 4 == 3, 32 layers
PUBLISHED_PATTERN = tuple("full_attention" if i % 4 == 3 else "sliding_attention"
                          for i in range(32))

_state = functools.partial(fc._state, wm, bias=0.01)


def _published_also(cfg, shapes):
    # the cut's five layers are entries 1-5 of the list, the dense layers counted once
    cut = wm.tiny_window_moe(layer_types=PUBLISHED_PATTERN[1:6], n_dense_layers=1)
    assert cut.kinds() == (("win", "dense"), ("win", "moe"), ("glob", "moe"),
                           ("win", "moe"), ("win", "moe"))
    assert shapes["head"] == shapes["embed"] == (96, 32)  # untied, laid out alike
    assert shapes["win.wg"] == shapes["win.wq"] == (24, 32, 4, 8)
    assert {"win.post_norm", "glob.post_norm", "dense.post_norm", "moe.post_norm"} <= set(shapes)


FAMILY = fc.Family(
    name="window_moe", model=wm, ref=ref, tiny=wm.tiny_window_moe, state=_state,
    # name → (config overrides, the selection bias's standard deviation)
    variants={
        "window_first_one_dense": (dict(), 0.01),
        "global_first_no_dense": (dict(layer_types=("full_attention", "sliding_attention",
                                                    "sliding_attention"), n_dense_layers=0), 0.01),
        "two_dense_layers": (dict(n_dense_layers=2), 0.01),
        # the 32 layers compiled whole, and their twin: both dense layers, the
        # first expert layer and first global layer, and one whole period
        "published_pattern": (dict(layer_types=PUBLISHED_PATTERN, n_dense_layers=2, max_seq=8,
                                   sliding_window=3), 0.01),
        "published_prefix_and_period": (dict(layer_types=PUBLISHED_PATTERN[:8], n_dense_layers=2,
                                             max_seq=8, sliding_window=3), 0.01),
        "mup_off": (dict(mup=False), 0.01),
        "skewed_expert_bias": (dict(), 0.5),
        "held_share_of_experts": (dict(experts_held=2, expert_lo=4), 0.01),
        "window_of_one_and_wider_shared_expert": (dict(sliding_window=1, d_shared=32), 0.01),
    },
    slow=("published_pattern",),
    ref_logits=ref.forward,
    learns=lambda cfg, name: False if name.endswith("router_bias") else None,  # it picks
    dp2=("window_first_one_dense", 1e-4),
    refused_axes=dict.fromkeys(("pp", "sp", "tp"), "data-parallel only"),
    refused=(
        (dict(layer_types=("sliding_attention", "conv")), "conv"),
        (dict(layer_types=()), "nothing"),
        (dict(n_dense_layers=5), "leading dense layers"),
        (dict(experts_held=4, expert_lo=6), "outside the router"),
        (dict(n_heads=3), "multiple of key/value heads"),
        (dict(head_dim=7), "even head_dim"),
        (dict(sliding_window=0), "the query itself"),
    ),
    published=(dict(layer_types=PUBLISHED_PATTERN, n_dense_layers=2), 32,
               (("win", "dense"), ("win", "dense"), ("win", "moe"), ("glob", "moe")),
               {"win": 24, "glob": 8, "dense": 2, "moe": 30}, _published_also),
    routing_layers=lambda cfg: cfg.n_layers - cfg.n_dense_layers,  # a dense layer routes nothing
)
globals().update(fc.family_cases(FAMILY))
