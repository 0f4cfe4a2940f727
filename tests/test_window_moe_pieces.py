"""The pieces the sliding-window / global-attention MoE family brought, each
against a hand-written case on the CPU: a global layer that knows no
positions beside a windowed layer that does, nothing from a key
``sliding_window`` or more back, the gate and the four norms by hand, the
router at Trinity's scale (parallel/moe.sigmoid_topk_route), the held share
beside a shared expert that is counted once, the cell's blocked reference
(benchmark/builders/afmoe.py) against models/window_moe_reference.py, and the
four other families' steps, which the PR that brought this one must not have
moved.  (The model against its reference: tests/test_window_moe.py.  Two
files so that ``--dist loadfile`` spreads them.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import transformer as tfm
from byteps_tpu.models import window_moe as wm
from byteps_tpu.models import window_moe_reference as ref
from byteps_tpu.parallel import moe

import family_cases as fc
from family_cases import _mesh

_state = functools.partial(fc._state, wm, bias=0.01)

SLIDING, FULL = "sliding_attention", "full_attention"


def _layer(cfg, stack, seed=3):
    """The first layer of ``stack`` of a seeded state."""
    params, _, _ = _state(cfg, seed=seed)
    return {k.split(".", 1)[1]: v[0] for k, v in params.items() if k.startswith(stack + ".")}


def _mixers(cfg, kind):
    """(the program's, the reference's) mixer of one kind as functions of (x, lp)."""
    stack = wm.MIXERS[kind]
    return (lambda x, lp: wm._attention_mixer(cfg, x, lp, stack),
            lambda x, lp: ref.attention_mixer(cfg, x, lp, kind))


# ---------------------------------------------------------------------------
# positions and masks
# ---------------------------------------------------------------------------


def test_a_global_layer_knows_no_positions_and_a_windowed_layer_does(monkeypatch):
    """Rope's positions shifted by 5 (every token turned as if it stood five
    places later): a full_attention layer's output is unchanged to the bit —
    it never calls rope —, a sliding layer's is not: rope is relative, so the
    shift is planted as an absolute one, on q alone."""
    cfg = wm.tiny_window_moe()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, cfg.max_seq, cfg.d_model))
    calls = []

    def shifted(rope, positions=lambda *args, **kw: True):
        """``rope`` with every token turned as if it stood five places
        later: five rows put before the sequence and cut off again (a row of
        zeros is normed to zeros, so the program's norm-and-rope takes it
        too).  ``positions``: whether this call turns anything at all."""
        def turned(t, *args, **kw):
            if not positions(*args, **kw):
                return rope(t, *args, **kw)
            calls.append(t.shape)
            s = t.shape[-2]
            padded = jnp.concatenate([jnp.zeros_like(t[..., :5, :]), t], axis=-2)
            out = rope(padded, *args, **kw)[..., 5:, :]  # positions 5 .. s + 4
            assert out.shape[-2] == s
            # q alone is shifted: odd calls (k) keep their positions
            return out if len(calls) % 2 else rope(t, *args, **kw)
        return turned

    # the program's one pass norms a head and turns it: head_norm_rope(x, w, eps, theta),
    # theta None where the mixer knows no positions
    has_theta = lambda w, eps, theta=None, **kw: theta is not None  # noqa: E731

    for kind in (FULL, SLIDING):
        system, reference = _mixers(cfg, kind)
        lp = _layer(cfg, wm.MIXERS[kind])
        plain = system(x, lp), reference(x, lp)
        calls.clear()
        with monkeypatch.context() as m:
            m.setattr(wm, "head_norm_rope", shifted(wm.head_norm_rope, has_theta))
            m.setattr(ref, "rope", shifted(ref.rope))
            moved = system(x, lp), reference(x, lp)
        if kind == FULL:
            assert not calls
            for a, b in zip(plain, moved):
                np.testing.assert_array_equal(a, b)
        else:
            assert len(calls) == 4  # q and k, in the program and in the reference
            for a, b in zip(plain, moved):
                assert float(jnp.abs(a - b).max()) > 1e-3
        np.testing.assert_allclose(plain[0], plain[1], atol=2e-5)


def test_a_global_layer_is_permutation_blind_but_for_its_mask():
    """No positional encoding at all: the LAST token of a global layer sees
    every token, so shuffling the tokens before it leaves its output alone;
    a sliding layer's last token notices."""
    cfg = wm.tiny_window_moe(sliding_window=16)  # the window holds the whole sequence
    x = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.max_seq, cfg.d_model))
    perm = jnp.concatenate([jax.random.permutation(jax.random.PRNGKey(2), cfg.max_seq - 1),
                            jnp.array([cfg.max_seq - 1])])
    for kind, same in ((FULL, True), (SLIDING, False)):
        system, _ = _mixers(cfg, kind)
        lp = _layer(cfg, wm.MIXERS[kind])
        a, b = system(x, lp)[0, -1], system(x[:, perm], lp)[0, -1]
        assert bool(jnp.allclose(a, b, atol=2e-5)) is same


@pytest.mark.parametrize("window", [1, 3, 5])
def test_nothing_reaches_a_query_from_a_key_a_window_or_more_back(window):
    """Token 4 changed: a sliding layer's outputs move at positions 4 ..
    4 + window - 1 and nowhere else (the published mask: 0 <= i - j <
    window, itself included); a global layer's from 4 to the end."""
    cfg = wm.tiny_window_moe(sliding_window=window)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, cfg.max_seq, cfg.d_model))
    other = x.at[:, 4].add(1.0)
    for kind, last in ((SLIDING, 4 + window - 1), (FULL, cfg.max_seq - 1)):
        lp = _layer(cfg, wm.MIXERS[kind])
        for mixer in _mixers(cfg, kind):
            moved = np.any(np.asarray(mixer(x, lp) != mixer(other, lp)), axis=-1)[0]
            assert list(np.flatnonzero(moved)) == list(range(4, last + 1)), (kind, moved)


def test_the_reference_writes_both_masks_as_comparisons_of_positions():
    got = np.asarray(ref.visible(5, 2)).astype(int)
    assert got.tolist() == [[1, 0, 0, 0, 0], [1, 1, 0, 0, 0], [0, 1, 1, 0, 0],
                            [0, 0, 1, 1, 0], [0, 0, 0, 1, 1]]
    assert np.asarray(ref.visible(4)).astype(int).tolist() == np.tril(np.ones((4, 4), int)).tolist()


# ---------------------------------------------------------------------------
# the gate and the four norms, by hand
# ---------------------------------------------------------------------------


def _rms_np(t, w, eps):
    return np.asarray(w) * t / np.sqrt(np.mean(t * t, -1, keepdims=True) + eps)


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_the_mixer_is_norm_of_gated_attention_of_norm(kind):
    """Two tokens, every step written out in numpy: the input norm, the
    per-head q/k norms (before rope, which turns position 1 alone and on the
    sliding kind alone), each key/value head serving two query heads, the
    sigmoid gate from the normed input on the attention's output, W_o, the
    post-norm inside the branch."""
    cfg = wm.tiny_window_moe(max_seq=2)
    lp = _layer(cfg, wm.MIXERS[kind])
    eps, hd, group = cfg.norm_eps, cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (1, 2, cfg.d_model)))
    g = _rms_np(x[0], lp["norm"], eps)
    q = _rms_np(np.einsum("sd,dhk->hsk", g, lp["wq"]), lp["q_norm"], eps)
    k = _rms_np(np.einsum("sd,dhk->hsk", g, lp["wk"]), lp["k_norm"], eps)
    v = np.einsum("sd,dhk->hsk", g, lp["wv"])
    z = np.einsum("sd,dhk->hsk", g, lp["wg"])
    if kind == SLIDING:
        q, k = (np.asarray(ref.rope(jnp.asarray(t), cfg.rope_theta)) for t in (q, k))
    out = np.zeros((cfg.n_heads, 2, hd))
    for head in range(cfg.n_heads):
        kv = head // group
        out[head, 0] = v[kv, 0]  # the first token sees itself alone
        scores = q[head, 1] @ k[kv].T / np.sqrt(hd)
        p = np.exp(scores - scores.max())
        out[head, 1] = p / p.sum() @ v[kv]
    gated = out / (1.0 + np.exp(-z))
    want = _rms_np(np.einsum("hsk,hkd->sd", gated, lp["wo"]), lp["post_norm"], eps)
    for mixer in _mixers(cfg, kind):
        np.testing.assert_allclose(mixer(jnp.asarray(x), lp)[0], want, atol=3e-5)
    # the gate is no uniform factor (the post-norm would cancel one: a gate
    # of 0.5 everywhere gives the ungated attention); the post-norm's scale scales
    system, _ = _mixers(cfg, kind)
    assert float(jnp.abs(system(jnp.asarray(x), {**lp, "wg": lp["wg"] * 0})[0] - want).max()) > 1e-2
    np.testing.assert_allclose(system(jnp.asarray(x), {**lp, "post_norm": 2 * lp["post_norm"]})[0],
                               2 * want, atol=6e-5)


def test_a_layer_adds_the_normed_branches_to_the_stream_and_scales_the_embedding():
    """One dense layer, by the reference's own pieces: h0 = E[tokens] sqrt(d);
    h1 = h0 + mixer(h0); h2 = h1 + post_norm(mlp(norm(h1))); logits =
    norm_f(h2) head^T — the post-norms INSIDE the branches, the head untied."""
    cfg = wm.tiny_window_moe(layer_types=(SLIDING,), n_dense_layers=1)
    params, tokens, _ = _state(cfg, batch=2)
    h0 = params["embed"][tokens] * cfg.d_model ** 0.5
    win = {k.split(".", 1)[1]: v[0] for k, v in params.items() if k.startswith("win.")}
    mlp = {k.split(".", 1)[1]: v[0] for k, v in params.items() if k.startswith("dense.")}
    h1 = h0 + ref.attention_mixer(cfg, h0, win, SLIDING)
    inner = _rms_np(np.asarray(h1), mlp["norm"], cfg.norm_eps)
    hidden = np.asarray(jax.nn.silu(inner @ mlp["w_gate"])) * (inner @ np.asarray(mlp["w_up"]))
    h2 = np.asarray(h1) + _rms_np(hidden @ np.asarray(mlp["w_down"]), mlp["post_norm"], cfg.norm_eps)
    want = _rms_np(h2, params["norm_f"], cfg.norm_eps) @ np.asarray(params["head"]).T
    got = tfm.build_forward(cfg, _mesh())(params, tokens)[0]
    np.testing.assert_allclose(got, want, atol=2e-4 * float(np.abs(want).max()))
    off = tfm.build_forward(wm.tiny_window_moe(layer_types=(SLIDING,), mup=False), _mesh())(
        params, tokens)[0]
    assert float(jnp.abs(off - got).max()) > 1e-2  # the scale is not a no-op under the norms


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------


def test_route_scale_weighs_and_planted_ties_go_to_the_lower_id():
    """Experts 3 and 7 score alike for every token.  Unbiased, a tie at the
    edge of the choice goes to the lower id; a bias on 7 turns it; the
    weights are 2.826 x the chosen SCORES over their sum, the bias nowhere,
    so they add up to route_scale."""
    t, d, e, k, scale = 256, 8, 16, 3, 2.826
    g = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, e))
    w = w.at[:, 7].set(w[:, 3])
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(g, w, precision="highest")))

    def route(bias):
        ids, weights = moe.sigmoid_topk_route(g, w, bias, k, scale)
        chosen = np.take_along_axis(scores, np.asarray(ids), axis=1)
        np.testing.assert_allclose(weights, scale * chosen / chosen.sum(1, keepdims=True),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(weights).sum(1), scale, rtol=1e-6)
        has = lambda i: np.any(np.asarray(ids) == i, axis=1)  # noqa: E731
        return has(3), has(7)

    has3, has7 = route(jnp.zeros((e,)))
    assert (has3 & ~has7).any() and not (has7 & ~has3).any()
    has3, has7 = route(jnp.zeros((e,)).at[7].set(1e-3))
    assert (has7 & ~has3).any() and not (has3 & ~has7).any()


def test_the_layer_routes_with_the_configs_scale_and_epsilon():
    cfg = wm.tiny_window_moe()
    assert (cfg.routed_scale, cfg.route_eps) == (2.826, 1e-20)
    lp = _layer(cfg, "moe")
    g = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.d_model))
    unit = wm.tiny_window_moe(routed_scale=1.0)
    shared = ref._swiglu(g, lp["s_gate"], lp["s_up"], lp["s_down"])
    scaled, one = wm.moe_mlp(cfg, g, lp)[0] - shared, wm.moe_mlp(unit, g, lp)[0] - shared
    np.testing.assert_allclose(scaled, 2.826 * one, atol=1e-5)  # the routed part alone is scaled


# ---------------------------------------------------------------------------
# the held share
# ---------------------------------------------------------------------------


def test_16_shares_of_8_with_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The cell's cut at toy widths: a 128-wide router, top-8, in 16 shares of
    8 experts.  Every share computes the shared expert in full, so the uncut
    layer is the shares' sum less 15 shared experts — the routed parts add
    up, the shared expert is counted once."""
    whole = wm.tiny_window_moe(n_experts=128, experts_held=128, top_k=8)
    lp = _layer(whole, "moe")
    g = jax.random.normal(jax.random.PRNGKey(9), (40, whole.d_model))
    want = ref.moe_mlp(whole, g, lp)
    shared = ref._swiglu(g, lp["s_gate"], lp["s_up"], lp["s_down"])
    total, held = 0.0, 0
    for lo in range(0, 128, 8):
        share = wm.tiny_window_moe(n_experts=128, experts_held=8, expert_lo=lo, top_k=8)
        lp_share = {**lp, **{w: lp[w][lo:lo + 8] for w in ("e_gate", "e_up", "e_down")}}
        y, stats = wm.moe_mlp(share, g, lp_share)
        total = total + y
        held += int(stats[1])
        assert int(stats[2]) == 0
        if lo in (0, 120):  # and a share is what the reference gives for that share
            np.testing.assert_allclose(y, ref.moe_mlp(share, g, lp_share), atol=2e-5)
    assert held == 40 * 8  # every slot is held by exactly one share
    np.testing.assert_allclose(total - 15 * shared, want, atol=5e-5)


test_no_slot_is_dropped_under_a_skewed_router = fc.skewed_router_case(
    wm.tiny_window_moe, lambda cfg: _layer(cfg, "moe"), wm.moe_mlp, ref.moe_mlp)


# ---------------------------------------------------------------------------
# the cell's blocked reference, and the programs this PR must not move
# ---------------------------------------------------------------------------


# toy widths: all five layers of the cut, so that both mixers meet both MLPs,
# and a window that is no multiple of the query block
globals().update(fc.builder_cases(
    "window_moe", ref, _state, builder="afmoe", config="trinity_mini_ep16",
    toy=dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
             intermediate_size=48, moe_intermediate_size=16, num_experts=4, router_width=16,
             num_experts_per_tok=3, vocab_size=96, max_seq=64, sliding_window=11,
             num_hidden_layers=5),
    windows=("sliding_window", {"odd": 11, "two_blocks": 16, "over_the_sequence": 200})))


def test_the_builder_runs_entries_1_to_5_of_the_published_list(rehearsal):
    builder, cfg, mcfg, _, _ = rehearsal
    assert len(cfg["layer_types"]) == 32 and cfg["first_layer"] == 1
    assert mcfg.layer_types == (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
    assert mcfg.n_dense_layers == 1 and mcfg.head_dim == 8 and mcfg.route_eps == 1e-20
    assert (mcfg.n_experts, mcfg.experts_held, mcfg.expert_lo) == (16, 4, 0)
    assert (mcfg.routed_scale, mcfg.mup, mcfg.sliding_window, mcfg.d_shared) == (2.826, True, 11, 16)
    for key, other in (("score_func", "softmax"), ("tie_word_embeddings", True), ("n_group", 2),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            builder._model_config({**cfg, key: other})
