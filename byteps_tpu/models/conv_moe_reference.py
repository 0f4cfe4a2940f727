"""Plain reference of the short-convolution MoE family (models/conv_moe.py):
the published equations (LFM2-MoE's ``modeling_lfm2_moe.py``) in
straightforward float32 ``jax.numpy`` — no kernel, no grouping, no remat, no
blocks.  The convolution as shifted products written out, dense causal
attention over the whole score matrix with the key/value heads repeated, a
loop over the held experts with a mask, matrix products at ``highest``
precision.  The tests hold the system to it; the benchmark keeps its own
blocked copy (benchmark/builders/lfm2_moe.py).

Like the system it is given a share: the experts ``[expert_lo, expert_lo +
experts_held)`` and the first ``vocab_size`` rows, and it leaves out what the
absent experts would add.  It reads sizes from the same config and the same
flat parameter dict.

Departures from the published description: the convolution starts from zeros
in every sequence (no cache is carried in); the input projection's columns
are laid out ``[B | C | x]`` as the published ``chunk(3)`` takes them; the
head is the embedding (the family's convention; the catalog's row has no
``tie_word_embeddings`` key); the selection bias (``expert_bias``) holds
seeded values and not the zeros training starts from; no auxiliary
load-balancing loss (none is among the published config's keys).
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


def short_conv(u, taps):
    """u (B, S, C), taps (K, C), as ``Conv1d(groups=C, padding=K-1)`` cut to
    the sequence: the last tap weighs the present token, the one before it
    the previous token, and so on; before the start there are zeros."""
    kernel, s = taps.shape[0], u.shape[1]
    out = jnp.zeros_like(u)
    for back in range(kernel):  # how many tokens back this tap reads
        earlier = jnp.concatenate(
            [jnp.zeros_like(u[:, :back]), u[:, :s - back]], axis=1) if back else u
        out = out + taps[kernel - 1 - back] * earlier
    return out


def conv_mixer(cfg, x, lp):
    g = _rms(x, lp["norm"], cfg.norm_eps)
    b_gate, c_gate, inner = jnp.split(g @ lp["w_in"], 3, axis=-1)
    return (c_gate * short_conv(b_gate * inner, lp["taps"])) @ lp["w_out"]


def attention_mixer(cfg, x, lp):
    hd, group = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    g = _rms(x, lp["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bhsk", g, lp["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", g, lp["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", g, lp["wv"])
    q = rope(_rms(q, lp["q_norm"], cfg.norm_eps), cfg.rope_theta)
    k = rope(_rms(k, lp["k_norm"], cfg.norm_eps), cfg.rope_theta)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / hd ** 0.5
    s = scores.shape[-1]
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"])


def expert_mlp(cfg, g, lp):
    """g (T, D) → the held experts' routed part."""
    scores = jax.nn.sigmoid(g @ lp["router"])
    _, ids = jax.lax.top_k(scores + lp["router_bias"], cfg.top_k)
    chosen = jnp.zeros_like(scores).at[jnp.arange(g.shape[0])[:, None], ids].set(1.0)
    weights = cfg.routed_scale * scores * chosen / (
        jnp.sum(scores * chosen, axis=-1, keepdims=True) + cfg.route_eps)
    y = jnp.zeros_like(g)
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
        x = params["embed"][tokens]
        nth = {"conv": 0, "attn": 0, "dense": 0, "moe": 0}
        for i, kind in enumerate(cfg.layer_types):
            mixer, stack = ((conv_mixer, "conv") if kind == "conv" else
                            (attention_mixer, "attn"))
            mlp, mlp_stack = (dense_mlp, "dense") if i < cfg.n_dense_layers else (
                expert_mlp, "moe")
            x = x + mixer(cfg, x, _layer_params(params, stack, nth[stack]))
            lp = _layer_params(params, mlp_stack, nth[mlp_stack])
            b, s, d = x.shape
            g = _rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
            x = x + mlp(cfg, g, lp).reshape(b, s, d)
            nth[stack] += 1
            nth[mlp_stack] += 1
        return _rms(x, params["norm_f"], cfg.norm_eps) @ params["embed"].T


def loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy over targets >= 0."""
    logits = forward(cfg, params, tokens)
    valid = targets >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * valid) / jnp.sum(valid)
