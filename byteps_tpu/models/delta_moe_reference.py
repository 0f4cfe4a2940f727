"""Plain reference of the gated-delta MoE family (models/delta_moe.py): the
published equations (Qwen3-Next's ``modeling_qwen3_next.py``) in
straightforward float32 ``jax.numpy`` — no kernel, no chunks, no solve, no
grouping, no remat.  The delta rule token by token, dense causal attention
over the whole score matrix with the key/value heads repeated, a loop over
the held experts with a mask, matrix products at ``highest`` precision.  The
tests hold the system to it; the benchmark keeps its own blocked copy
(benchmark/builders/qwen3_next.py).

Like the system it is given a share: the experts ``[expert_lo, expert_lo +
experts_held)`` and the first ``vocab_size`` rows, and it leaves out what the
absent experts would add.  It reads sizes from the same config and the same
flat parameter dict.

Departures from the published description: the rule's state and the
convolution start at zero in every sequence (no cache is carried in); the
columns of the two input projections are laid out ``[q | k | v | z]`` and
``[b | a]``, not interleaved by key head (a permutation of a seeded matrix's
columns); no auxiliary load-balancing loss (its coefficient is no key of the
published config).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def rope(x, rotary_dim, theta):
    """x (..., S, d): ``x · cos + rotate_half(x) · sin`` on the first
    ``rotary_dim`` dims, where rotate_half([a | b]) = [−b | a]."""
    s, half = x.shape[-2], rotary_dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], axis=-1) for f in (jnp.cos, jnp.sin))
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half_turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([rot * cos + half_turned * sin, rest], axis=-1)


def delta_rule(q, k, v, g, beta):
    """Token by token; q, k (B, S, H, d_k), v (B, S, H, d_v), g, beta (B, S, H)."""
    b, s, h, dk = q.shape
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    out = []
    for t in range(s):
        state = jnp.exp(g[:, t])[..., None, None] * state
        u = beta[:, t][..., None] * (v[:, t] - jnp.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., :, None] * u[..., None, :]
        out.append(jnp.einsum("bhkv,bhk->bhv", state, q[:, t]))
    return jnp.stack(out, axis=1)


def delta_mixer(cfg, x, lp):
    hk, hv, dk, dv = cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim
    b, s, _ = x.shape
    h = _rms(x, lp["mixer_norm"], cfg.norm_eps)
    qkvz, ba = h @ lp["w_qkvz"], h @ lp["w_ba"]
    mixed, z = qkvz[..., :cfg.lin_channels], qkvz[..., cfg.lin_channels:]
    kernel = lp["conv"].shape[0]
    padded = jnp.pad(mixed, ((0, 0), (kernel - 1, 0), (0, 0)))
    conv = jnp.zeros_like(mixed)
    for j in range(kernel):
        conv = conv + padded[:, j:j + s] * lp["conv"][j]
    conv = jax.nn.silu(conv)
    q = conv[..., :hk * dk].reshape(b, s, hk, dk)
    k = conv[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
    v = conv[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    q = jnp.repeat(_l2(q) * dk ** -0.5, hv // hk, axis=2)
    k = jnp.repeat(_l2(k), hv // hk, axis=2)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(ba[..., hv:] + lp["dt_bias"])
    o = delta_rule(q, k, v, g, beta)  # (B, S, hv, dv)
    o = lp["gdn_norm"] * o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * jax.nn.silu(z.reshape(b, s, hv, dv))
    return o.reshape(b, s, hv * dv) @ lp["w_out"]


def attention_mixer(cfg, x, lp):
    hd, group = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    h = _rms(x, lp["mixer_norm"], cfg.norm_eps)
    q_gate = jnp.einsum("bsd,dhk->bhsk", h, lp["wq"])
    q, gate = q_gate[..., :hd], q_gate[..., hd:]
    k = jnp.einsum("bsd,dhk->bhsk", h, lp["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", h, lp["wv"])
    q = rope(_rms(q, lp["q_norm"], cfg.norm_eps), cfg.rotary_dim, cfg.rope_theta)
    k = rope(_rms(k, lp["k_norm"], cfg.norm_eps), cfg.rotary_dim, cfg.rope_theta)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / hd ** 0.5
    s = scores.shape[-1]
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bhsk,hkd->bsd", o * jax.nn.sigmoid(gate), lp["wo"])


def expert_mlp(cfg, g, lp):
    """g (T, D) → routed part of the held experts + the gated shared expert."""
    probs = jax.nn.softmax(g @ lp["router"], axis=-1)
    _, ids = jax.lax.top_k(probs, cfg.top_k)
    chosen = jnp.zeros_like(probs).at[jnp.arange(g.shape[0])[:, None], ids].set(1.0)
    weights = probs * chosen / jnp.sum(probs * chosen, axis=-1, keepdims=True)
    y = jax.nn.sigmoid(g @ lp["shared_gate"])[:, None] * _swiglu(
        g, lp["s_gate"], lp["s_up"], lp["s_down"])
    for e in range(cfg.experts_held):
        y = y + weights[:, cfg.expert_lo + e, None] * _swiglu(
            g, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e])
    return y


def layer(cfg, mixer, x, lp):
    x = x + mixer(cfg, x, lp)
    b, s, d = x.shape
    g = _rms(x, lp["mlp_norm"], cfg.norm_eps).reshape(b * s, d)
    return x + expert_mlp(cfg, g, lp).reshape(b, s, d)


def _kind(params, prefix):
    return {k.split(".", 1)[1]: v for k, v in params.items() if k.startswith(prefix + ".")}


def forward(cfg, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    with jax.default_matmul_precision("highest"):
        lin, full = _kind(params, "lin"), _kind(params, "full")
        x = params["embed"][tokens]
        for i in range(cfg.n_layers):
            period, within = divmod(i, cfg.full_attention_interval)
            if within == cfg.full_attention_interval - 1:
                x = layer(cfg, attention_mixer, x, {k: v[period] for k, v in full.items()})
            else:
                x = layer(cfg, delta_mixer, x, {k: v[period, within] for k, v in lin.items()})
        return _rms(x, params["norm_f"], cfg.norm_eps) @ params["head"]


def loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy over targets >= 0."""
    logits = forward(cfg, params, tokens)
    valid = targets >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * valid) / jnp.sum(valid)
