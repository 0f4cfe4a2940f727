"""Plain reference of the sliding-window / global-attention MoE family
(models/window_moe.py): the published equations (transformers'
``modeling_afmoe.py``) in straightforward float32 ``jax.numpy`` — no kernel,
no grouping, no remat, no blocks, nothing of the program's.  Dense attention
over the whole score matrix with both masks written out as comparisons of
positions and the key/value heads repeated, a loop over the held experts with
a mask, matrix products at ``highest`` precision.  The tests hold the system
to it; the benchmark keeps its own blocked copy
(benchmark/builders/afmoe.py).

Like the system it is given a share: the experts ``[expert_lo, expert_lo +
experts_held)`` and the first ``vocab_size`` rows of embedding and head, and
it leaves out what the absent experts would add; the shared expert is whole.
It reads sizes from the same config and the same flat parameter dict.

Departures from the published code: the head is stored (vocabulary, model) as
the embedding is; the selection bias (``expert_bias``) holds seeded values
and not the zeros training starts from, and no rule updates it
(``load_balance_coeff`` is that rule's rate: there is no auxiliary loss to
leave out); ``n_group = topk_group = 1``, so no group limits the choice; the
sliding mask is the window alone, with no attention-sink or cache logic; the
grouped products (``use_grouped_mm``) are an implementation's, not an
equation's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def rope(x, theta):
    """x (..., S, d): ``x · cos + rotate_half(x) · sin`` over the whole head,
    where rotate_half([a | b]) = [−b | a]."""
    s, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], axis=-1) for f in (jnp.cos, jnp.sin))
    half_turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half_turned * sin


def visible(s, window=None):
    """(S, S) bool, queries down and keys across: key ``j`` is seen by query
    ``i`` iff ``j <= i`` and, at a window, ``j > i - window``."""
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    return (j <= i) if window is None else (j <= i) & (j > i - window)


def attention_mixer(cfg, x, lp, kind):
    """``kind``: the layer's ``layer_types`` entry."""
    sliding = kind == "sliding_attention"
    hd, group = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    g = _rms(x, lp["norm"], cfg.norm_eps)
    q, k, v, z = (jnp.einsum("bsd,dhk->bhsk", g, lp[w]) for w in ("wq", "wk", "wv", "wg"))
    q, k = _rms(q, lp["q_norm"], cfg.norm_eps), _rms(k, lp["k_norm"], cfg.norm_eps)
    if sliding:  # the full layers take no positional encoding at all
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / hd ** 0.5
    seen = visible(scores.shape[-1], cfg.sliding_window if sliding else None)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v)
    y = jnp.einsum("bhsk,hkd->bsd", o * jax.nn.sigmoid(z), lp["wo"])
    return _rms(y, lp["post_norm"], cfg.norm_eps)


def moe_mlp(cfg, g, lp):
    """g (T, D) → the held experts' routed part plus the shared expert."""
    scores = jax.nn.sigmoid(g @ lp["router"])
    _, ids = jax.lax.top_k(scores + lp["router_bias"], cfg.top_k)
    chosen = jnp.zeros_like(scores).at[jnp.arange(g.shape[0])[:, None], ids].set(1.0)
    weights = cfg.routed_scale * scores * chosen / (
        jnp.sum(scores * chosen, axis=-1, keepdims=True) + cfg.route_eps)
    y = _swiglu(g, lp["s_gate"], lp["s_up"], lp["s_down"])
    for e in range(cfg.experts_held):
        y = y + weights[:, cfg.expert_lo + e, None] * _swiglu(
            g, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e])
    return y


def dense_mlp(cfg, g, lp):
    return _swiglu(g, lp["w_gate"], lp["w_up"], lp["w_down"])


def _layer_params(params, stack, i):
    return {k.split(".", 1)[1]: v[i] for k, v in params.items() if k.startswith(stack + ".")}


def forward(cfg, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens] * (cfg.d_model ** 0.5 if cfg.mup else 1.0)
        nth = {"win": 0, "glob": 0, "dense": 0, "moe": 0}
        for i, kind in enumerate(cfg.layer_types):
            stack = "win" if kind == "sliding_attention" else "glob"
            mlp, mlp_stack = (dense_mlp, "dense") if i < cfg.n_dense_layers else (moe_mlp, "moe")
            x = x + attention_mixer(cfg, x, _layer_params(params, stack, nth[stack]), kind)
            lp = _layer_params(params, mlp_stack, nth[mlp_stack])
            b, s, d = x.shape
            g = _rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
            x = x + _rms(mlp(cfg, g, lp).reshape(b, s, d), lp["post_norm"], cfg.norm_eps)
            nth[stack] += 1
            nth[mlp_stack] += 1
        return _rms(x, params["norm_f"], cfg.norm_eps) @ params["head"].T


def loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy over targets >= 0."""
    logits = forward(cfg, params, tokens)
    valid = targets >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * valid) / jnp.sum(valid)
