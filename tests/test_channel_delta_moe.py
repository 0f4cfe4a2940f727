"""The channel-delta MoE family (models/channel_delta_moe.py) against its plain
reference (models/channel_delta_moe_reference.py): tiny widths, seeded random
weights, f32, on the CPU mesh — loss, logits and every leaf's gradient with
every kind of layer once (dense delta, expert delta, expert latent), the
published pattern walked whole, the 32 shares of an expert-parallel layer
adding up, the counters.  (The chunked rule with a decay a channel against
the recurrence: tests/test_gated_delta.py; the turn by no angle:
tests/test_latent_moe_pieces.py.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import channel_delta_moe as cd
from byteps_tpu.models import channel_delta_moe_reference as ref
from byteps_tpu.models import moe_family as mf

import family_cases as fc

#: Kimi-Linear-48B-A3B's 27 layers by published index: ``full_attn_layers`` are
#: 4, 8, …, 24 and 27, every other one of ``kda_layers``
FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)
PUBLISHED_PATTERN = tuple("latent_attention" if i in FULL_ATTN_LAYERS else "channel_delta"
                          for i in range(1, 28))

#: the norms' scales and the selection bias off their starting values
_state = functools.partial(
    fc._state, cd, moved=lambda name: "norm" in name or name.endswith("router_bias"))


def _published_also(cfg, shapes):
    # the cell's five layers are the list's first five: the dense layer, one whole period
    cut = cd.tiny_channel_delta_moe(layer_types=PUBLISHED_PATTERN[:5])
    assert cut.kinds() == (("delta", "dense"), ("delta", "moe"), ("delta", "moe"),
                           ("latent", "moe"), ("delta", "moe"))
    assert shapes["head"] == shapes["embed"] == (96, 32)  # untied, laid out alike
    # [q | k | v] of 2 heads of 8 | 8 | 6 and [f↓ | g↓ | β] through 4
    assert shapes["delta.w_qkv"] == (20, 32, 44) and shapes["delta.w_fgb"] == (20, 32, 10)
    assert shapes["delta.w_f"] == (20, 4, 16) and shapes["delta.w_g"] == (20, 4, 12)
    # a rate a head, a bias a channel
    assert shapes["delta.a_log"] == (20, 2) and shapes["delta.dt_bias"] == (20, 16)
    # no query bottleneck, no rope
    assert shapes["latent.wq"] == (7, 32, 4, 12) and "latent.wq_a" not in shapes
    assert cfg.rope_theta is None


FAMILY = fc.Family(
    name="channel_delta_moe", model=cd, ref=ref, tiny=cd.tiny_channel_delta_moe, state=_state,
    variants={
        "every_kind_of_layer_once": dict(),
        "latent_first_no_dense": dict(
            layer_types=("latent_attention", "channel_delta"), n_dense_layers=0),
        "held_share_of_experts": dict(experts_held=2, expert_lo=4),
    },
    ref_logits=ref.forward,
    # every leaf learns, but the bias that only picks
    learns=lambda cfg, name: not name.endswith("router_bias"),
    dp2=("every_kind_of_layer_once", 1e-4),
    refused_axes=dict.fromkeys(("pp", "sp", "tp"),
                               "channel-delta MoE family runs data-parallel only"),
    refused=(
        (dict(layer_types=("channel_delta", "full_attention")), "full_attention"),
        (dict(layer_types=()), "nothing"),
        (dict(n_dense_layers=4), "leading dense layers"),
        (dict(experts_held=4, expert_lo=6), "outside the router"),
        (dict(qk_rope_dim=3), "even qk_rope_dim"),
    ),
    published=(dict(layer_types=PUBLISHED_PATTERN, n_dense_layers=1), 27,
               (("delta", "dense"), ("delta", "moe"), ("delta", "moe"), ("latent", "moe")),
               {"delta": 20, "latent": 7, "dense": 1, "moe": 26}, _published_also),
    routing_layers=lambda cfg: cfg.n_layers - cfg.n_dense_layers,
    also_counts=("gdn_channel_xla_traces",),
)
globals().update(fc.family_cases(FAMILY))


def test_thirty_two_shares_add_up_to_the_uncut_expert_layer():
    """The 32 shares of a 32-way expert-parallel layer — at tiny size 2 of 64
    experts each, the router 64 wide on every one — give routed parts that add
    up, with the shared expert counted ONCE, to the uncut reference's layer."""
    whole = cd.tiny_channel_delta_moe(n_experts=64, experts_held=64, top_k=8, remat=False)
    params = cd.init_params(whole, jax.random.PRNGKey(3))
    lp = {k: v[0] for k, v in mf.stack_of(params, "moe").items()}
    lp["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (64,))
    g = jax.random.normal(jax.random.PRNGKey(11), (40, whole.d_model))
    route = lambda cfg: (lambda g32, lp: cd.sigmoid_topk_route(  # noqa: E731
        g32, lp["router"], lp["router_bias"], cfg.top_k, cfg.routed_scale))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_mlp(whole, g, lp)
        shared = ref._swiglu(g, lp["s_gate"], lp["s_up"], lp["s_down"])
        total, held = jnp.zeros_like(g), 0
        for rank in range(32):
            share = cd.tiny_channel_delta_moe(n_experts=64, experts_held=2, expert_lo=2 * rank,
                                              top_k=8, remat=False)
            mine = {**lp, **{w: lp[w][2 * rank:2 * rank + 2]
                             for w in ("e_gate", "e_up", "e_down")}}
            y, stats = mf.routed_mlp(share, g, g, mine, route(share), None)
            total, held = total + y, held + int(stats[1])
            if rank in (0, 17):  # the reference given a share leaves out the others' too
                np.testing.assert_allclose(y, ref.expert_mlp(share, g, mine) - shared,
                                           rtol=2e-4, atol=2e-4)
    assert held == 40 * 8  # every slot is held by exactly one share
    np.testing.assert_allclose(total + shared, want, rtol=2e-4, atol=2e-4)


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    cfg = cd.tiny_channel_delta_moe(chunk=8)
    params = cd.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="chunk 8 does not divide sequence 12"):
        cd.local_logits(cfg, params, jnp.zeros((1, 12), jnp.int32))
