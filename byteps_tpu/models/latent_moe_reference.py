"""Plain reference of the latent-attention MoE family (models/latent_moe.py):
the published equations in straightforward float32 ``jax.numpy`` — no kernel,
no grouping, no remat, no scan.  Dense causal attention over the whole score
matrix, a loop over the held experts with a mask, matrix products at
``highest`` precision.  The tests hold the system to it; the benchmark keeps
its own blocked copy (benchmark/builders/joyai_llm_flash.py).

Like the system it is given a share: the experts ``[expert_lo, expert_lo +
experts_held)`` and the first ``vocab_size`` rows, and it leaves out what the
absent experts would add.  It reads sizes from the same config and the same
flat parameter dict.

Departures from the published description: none known.  Assumed, because the
config does not say: the MTP module's concatenation order (next token's
embedding first) and its loss weight (``cfg.mtp_lambda``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (..., S, d): adjacent pairs (2i, 2i+1) rotated by pos · theta^(-2i/d)."""
    s, d = x.shape[-2], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def attention(cfg, x, lp):
    nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    h = _rms(x, lp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bhsk", _rms(h @ lp["wq_a"], lp["q_norm"], cfg.norm_eps), lp["wq_b"])
    kv_a = h @ lp["wkv_a"]
    kv = jnp.einsum("bsr,rhk->bhsk", _rms(kv_a[..., :r], lp["kv_norm"], cfg.norm_eps), lp["wkv_b"])
    k_rope = _rope(kv_a[:, None, :, r:], cfg.rope_theta)  # (B, 1, S, rope): all heads share it
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bhqd,bzkd->bhqk", _rope(q[..., nope:], cfg.rope_theta), k_rope))
    s = scores.shape[-1]
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores / cfg.qk_dim ** 0.5, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return x + jnp.einsum("bhsk,hkd->bsd", o, lp["wo"])


def expert_mlp(cfg, g, lp):
    """g (T, D) → routed part of the held experts + the shared expert."""
    scores = jax.nn.sigmoid(g @ lp["router"])
    _, ids = jax.lax.top_k(scores + lp["router_bias"], cfg.top_k)
    chosen = jnp.zeros_like(scores).at[jnp.arange(g.shape[0])[:, None], ids].set(1.0)
    weights = cfg.routed_scale * scores * chosen / (
        jnp.sum(scores * chosen, axis=-1, keepdims=True) + 1e-20)
    y = _swiglu(g, lp["s_gate"], lp["s_up"], lp["s_down"])
    for e in range(cfg.experts_held):
        y = y + weights[:, cfg.expert_lo + e, None] * _swiglu(
            g, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e])
    return y


def _layers(params, stack):
    lps = {k.split(".", 1)[1]: v for k, v in params.items() if k.startswith(stack + ".")}
    n = next(iter(lps.values())).shape[0] if lps else 0
    return [{k: v[i] for k, v in lps.items()} for i in range(n)]


def expert_layer(cfg, x, lp):
    x = attention(cfg, x, lp)
    b, s, d = x.shape
    g = _rms(x, lp["mlp_norm"], cfg.norm_eps).reshape(b * s, d)
    return x + expert_mlp(cfg, g, lp).reshape(b, s, d)


def _xent(logits, targets):
    valid = targets >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * valid) / jnp.sum(valid)


def forward(cfg, params, tokens, targets=None):
    """(main logits, MTP logits or None), f32, over the held rows."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for lp in _layers(params, "dense"):
            x = attention(cfg, x, lp)
            g = _rms(x, lp["mlp_norm"], cfg.norm_eps)
            x = x + _swiglu(g, lp["w_gate"], lp["w_up"], lp["w_down"])
        for lp in _layers(params, "moe"):
            x = expert_layer(cfg, x, lp)
        logits = _rms(x, params["norm_f"], cfg.norm_eps) @ params["head"]
        mtp_logits = None
        if cfg.mtp_modules and targets is not None:
            nxt = _rms(params["embed"][jnp.maximum(targets, 0)], params["mtp_norm_e"], cfg.norm_eps)
            both = jnp.concatenate([nxt, _rms(x, params["mtp_norm_h"], cfg.norm_eps)], axis=-1)
            y = both @ params["mtp_proj"]
            for lp in _layers(params, "mtp"):
                y = expert_layer(cfg, y, lp)
            mtp_logits = _rms(y, params["mtp_norm_f"], cfg.norm_eps) @ params["head"]
        return logits, mtp_logits


def loss(cfg, params, tokens, targets):
    """``L_main + mtp_lambda · L_mtp``; the MTP module predicts t_{i+2}."""
    logits, mtp_logits = forward(cfg, params, tokens, targets)
    total = _xent(logits, targets)
    if mtp_logits is not None:
        after = jnp.concatenate([targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1)
        total = total + cfg.mtp_lambda * _xent(mtp_logits, after)
    return total
