"""The pieces the short-convolution MoE family brought, each against a
hand-written case on the CPU: the short convolution (tap order, the past
only, zeros before the start), the two gates, the per-head norms and rope
over the whole head, the sigmoid router with its selection bias
(parallel/moe.sigmoid_topk_route at LFM2's epsilon), the held share, the
cell's blocked reference (benchmark/builders/lfm2_moe.py) against
models/conv_moe_reference.py, and the three other families' steps, which the
PR that brought this one must not have moved.  (The model against its
reference: tests/test_conv_moe.py.  Two files so that ``--dist loadfile``
spreads them.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import conv_moe as cm
from byteps_tpu.models import conv_moe_reference as ref
from byteps_tpu.models import moe_family as mf
from byteps_tpu.parallel import moe

import family_cases as fc

_state = functools.partial(fc._state, cm, bias=0.01)


def _layer(cfg, stack, seed=3):
    """The first layer of ``stack`` of a seeded state."""
    params, _, _ = _state(cfg, seed=seed)
    return {k.split(".", 1)[1]: v[0] for k, v in params.items() if k.startswith(stack + ".")}


# ---------------------------------------------------------------------------
# the short convolution and its gates, by hand
# ---------------------------------------------------------------------------


def test_three_taps_read_the_present_and_the_two_tokens_before_it():
    """Tap order as ``Conv1d``'s: the LAST tap weighs the present token.
    Channel 0 has taps (1, 10, 100), channel 1 passes the present through."""
    x = jnp.arange(1.0, 11.0).reshape(1, 5, 2)
    taps = jnp.array([[1.0, 0.0], [10.0, 0.0], [100.0, 1.0]])
    want = [100, 310, 531, 753, 975]  # x_t·100 + x_{t-1}·10 + x_{t-2}·1, zeros before the start
    for conv in (mf.causal_conv, ref.short_conv):
        got = np.asarray(conv(x, taps))
        np.testing.assert_allclose(got[0, :, 1], x[0, :, 1])
        np.testing.assert_allclose(got[0, :, 0], want)


def test_the_convolution_takes_nothing_from_a_later_token():
    cfg = cm.tiny_conv_moe()
    lp = _layer(cfg, "conv")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, cfg.max_seq, cfg.d_model))
    later = x.at[:, 9:].add(1.0)
    for mixer in (cm._conv_mixer, ref.conv_mixer):
        a, b = mixer(cfg, x, lp), mixer(cfg, later, lp)
        np.testing.assert_array_equal(a[:, :9], b[:, :9])
        assert np.all(np.any(np.asarray(a[:, 9:] != b[:, 9:]), axis=-1))


def test_the_mixer_is_c_times_conv_of_b_times_x():
    """One sequence, every step written out: the projection's thirds are B,
    C and x in that order; closing either gate closes the mixer."""
    cfg = cm.tiny_conv_moe()
    lp = _layer(cfg, "conv")
    d = cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, d))
    g = np.asarray(lp["norm"] * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps))[0]
    bcx = g @ np.asarray(lp["w_in"])
    u = bcx[:, :d] * bcx[:, 2 * d:]
    taps = np.asarray(lp["taps"])
    conv = np.stack([sum(taps[2 - back] * u[t - back] for back in range(3) if t >= back)
                     for t in range(6)])
    want = (bcx[:, d:2 * d] * conv) @ np.asarray(lp["w_out"])
    np.testing.assert_allclose(cm._conv_mixer(cfg, x, lp)[0], want, atol=2e-5)
    np.testing.assert_allclose(ref.conv_mixer(cfg, x, lp)[0], want, atol=2e-5)
    for third in (0, 1):  # B's columns, then C's
        shut = {**lp, "w_in": lp["w_in"].at[:, third * d:(third + 1) * d].set(0.0)}
        assert not np.any(cm._conv_mixer(cfg, x, shut))


# ---------------------------------------------------------------------------
# grouped-query attention's norms and rope, by hand
# ---------------------------------------------------------------------------


def test_rope_turns_the_whole_head_in_half_rotation_pairs():
    """Head of 4: dims (0, 2) and (1, 3) are the pairs; position 0 is left
    alone; nothing passes unrotated."""
    theta = 100.0
    x = jnp.arange(1.0, 13.0).reshape(3, 4)
    got = np.asarray(mf.rope_partial(x, 4, theta))
    np.testing.assert_allclose(got[0], x[0])
    for pos in (1, 2):
        for i, freq in ((0, 1.0), (1, theta ** -0.5)):
            a, b = float(x[pos, i]), float(x[pos, i + 2])
            c, s = np.cos(pos * freq), np.sin(pos * freq)
            np.testing.assert_allclose(got[pos, i], a * c - b * s, rtol=1e-5)
            np.testing.assert_allclose(got[pos, i + 2], b * c + a * s, rtol=1e-5)
    np.testing.assert_allclose(got, ref.rope(x, theta), rtol=1e-5)


def test_one_token_attends_to_itself_and_each_kv_head_serves_its_group():
    """A sequence of one: attention's output is the token's value, so the
    mixer gives W_o of v with each key/value head repeated for its two query
    heads — no gate, and the q/k norms cannot matter."""
    cfg = cm.tiny_conv_moe(max_seq=1)
    lp = _layer(cfg, "attn")
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 1, cfg.d_model))
    g = ref._rms(x, lp["norm"], cfg.norm_eps)[0, 0]
    v = jnp.repeat(jnp.einsum("d,dhk->hk", g, lp["wv"]), cfg.n_heads // cfg.n_kv_heads, axis=0)
    want = jnp.einsum("hk,hkd->d", v, lp["wo"])
    other = {**lp, "q_norm": 3.0 * lp["q_norm"], "k_norm": -lp["k_norm"]}
    for params in (lp, other):
        np.testing.assert_allclose(cm._attention_mixer(cfg, x, params)[0, 0], want, atol=1e-5)
        np.testing.assert_allclose(ref.attention_mixer(cfg, x, params)[0, 0], want, atol=1e-5)


def test_q_and_k_are_normed_over_each_head_before_rope():
    """Two tokens: the scores are those of the hand-normed, hand-rotated
    heads, so the second token's output is their softmax over two values."""
    cfg = cm.tiny_conv_moe(max_seq=2, n_heads=2, n_kv_heads=1)
    lp = _layer(cfg, "attn")
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 2, cfg.d_model))
    g = np.asarray(ref._rms(x, lp["norm"], cfg.norm_eps)[0])

    def head_norm(t, w):
        return np.asarray(w) * t / np.sqrt(np.mean(t * t, -1, keepdims=True) + cfg.norm_eps)

    q = head_norm(np.einsum("sd,dhk->hsk", g, lp["wq"]), lp["q_norm"])
    k = head_norm(np.einsum("sd,dhk->hsk", g, lp["wk"]), lp["k_norm"])
    v = np.einsum("sd,dhk->hsk", g, lp["wv"])
    q, k = (np.asarray(ref.rope(jnp.asarray(t), cfg.rope_theta)) for t in (q, k))
    out = []
    for head in range(2):  # both query heads read the one key/value head
        scores = q[head, 1] @ k[0].T / np.sqrt(cfg.head_dim)
        p = np.exp(scores - scores.max())
        out.append(p / p.sum() @ v[0])
    want = np.einsum("hk,hkd->d", np.stack(out), lp["wo"])
    np.testing.assert_allclose(cm._attention_mixer(cfg, x, lp)[0, 1], want, atol=2e-5)
    np.testing.assert_allclose(ref.attention_mixer(cfg, x, lp)[0, 1], want, atol=2e-5)


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------


def test_the_bias_picks_and_does_not_weigh_with_planted_ties():
    """Experts 3 and 7 score alike for every token.  Unbiased, a tie at the
    edge of the choice goes to the lower id; a bias on 7 turns it; and the
    weights are the chosen SCORES over their sum + 1e-6, the bias nowhere."""
    t, d, e, k = 256, 8, 16, 3
    g = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, e))
    w = w.at[:, 7].set(w[:, 3])
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(g, w, precision="highest")))

    def route(bias):
        ids, weights = moe.sigmoid_topk_route(g, w, bias, k, 1.0, eps=1e-6)
        assert ids.dtype == jnp.int32 and weights.dtype == jnp.float32
        chosen = np.take_along_axis(scores, np.asarray(ids), axis=1)
        np.testing.assert_allclose(weights, chosen / (chosen.sum(1, keepdims=True) + 1e-6),
                                   rtol=1e-6)
        has = lambda i: np.any(np.asarray(ids) == i, axis=1)  # noqa: E731
        return has(3), has(7)

    has3, has7 = route(jnp.zeros((e,)))
    assert (has3 & ~has7).any() and not (has7 & ~has3).any()
    has3, has7 = route(jnp.zeros((e,)).at[7].set(1e-3))
    assert (has7 & ~has3).any() and not (has3 & ~has7).any()
    _, has7 = route(jnp.zeros((e,)).at[7].set(10.0))
    assert has7.all()  # a large bias picks 7 for every token, and its weight stays its score


def test_the_published_epsilon_is_not_the_other_familys():
    """With tiny scores the 1e-6 shows: the weights no longer add up to 1."""
    g = jnp.ones((4, 8))
    w = jnp.full((8, 6), -2.5)  # every score is sigmoid(-20) = 2.1e-9
    bias = jnp.zeros((6,))
    _, lfm2 = moe.sigmoid_topk_route(g, w, bias, 2, 1.0, eps=1e-6)
    _, v3 = moe.sigmoid_topk_route(g, w, bias, 2, 1.0)
    np.testing.assert_allclose(np.asarray(v3).sum(1), 1.0, rtol=1e-5)
    assert np.all(np.asarray(lfm2).sum(1) < 0.01)


# ---------------------------------------------------------------------------
# the held share
# ---------------------------------------------------------------------------


def test_8_shares_of_8_add_up_to_the_uncut_layer():
    """The cell's cut at toy widths: a 64-wide router, top-4, in 8 shares of
    8 experts.  The shares' routed parts (there is nothing else: no shared
    expert) give what the reference gives with all 64."""
    whole = cm.tiny_conv_moe(n_experts=64, experts_held=64, top_k=4)
    lp = _layer(whole, "moe")
    g = jax.random.normal(jax.random.PRNGKey(9), (40, whole.d_model))
    want = ref.expert_mlp(whole, g, lp)
    total, held = 0.0, 0
    for lo in range(0, 64, 8):
        share = cm.tiny_conv_moe(n_experts=64, experts_held=8, expert_lo=lo, top_k=4)
        lp_share = {**lp, **{w: lp[w][lo:lo + 8] for w in ("e_gate", "e_up", "e_down")}}
        y, stats = cm.expert_mlp(share, g, lp_share)
        total = total + y
        held += int(stats[1])
        assert int(stats[2]) == 0
        if lo in (0, 56):  # and a share is what the reference gives for that share
            np.testing.assert_allclose(y, ref.expert_mlp(share, g, lp_share), atol=1e-5)
    assert held == 40 * 4  # every slot is held by exactly one share
    np.testing.assert_allclose(total, want, atol=2e-5)


test_no_slot_is_dropped_under_a_skewed_router = fc.skewed_router_case(
    cm.tiny_conv_moe, lambda cfg: _layer(cfg, "moe"), cm.expert_mlp, ref.expert_mlp)


# ---------------------------------------------------------------------------
# the cell's blocked reference, and the programs this PR must not move
# ---------------------------------------------------------------------------


# toy widths: all five layers of the cut, so that both mixers meet both MLPs
globals().update(fc.builder_cases(
    "conv_moe", ref, _state, builder="lfm2_moe", config="lfm2_24b_a2b_ep8",
    toy=dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=48, moe_intermediate_size=16, num_experts=4, router_width=16,
             num_experts_per_tok=3, vocab_size=96, max_seq=64, num_hidden_layers=5)))


def test_the_builder_runs_entries_1_to_5_of_the_published_list(rehearsal):
    builder, cfg, mcfg, _, _ = rehearsal
    assert len(cfg["layer_types"]) == 40 and cfg["first_layer"] == 1
    assert mcfg.layer_types == ("conv", "full_attention", "conv", "conv", "conv")
    assert mcfg.n_dense_layers == 1 and mcfg.head_dim == 8 and mcfg.route_eps == 1e-6
    assert (mcfg.n_experts, mcfg.experts_held, mcfg.expert_lo) == (16, 4, 0)
    with pytest.raises(ValueError, match="conv_bias"):
        builder._model_config({**cfg, "conv_bias": True})
