"""Plain reference of the channel-delta MoE family
(models/channel_delta_moe.py): the published equations (Kimi Linear,
arXiv:2510.26692 §3 and §5, and the modeling code beside the published
``config.json``) in straightforward float32 ``jax.numpy`` — no kernel, no
chunks, no solve, no grouping, no remat, nothing of ``byteps_tpu.ops``.  The
delta rule token by token exactly as its five lines read, dense causal
attention over the whole score matrix, a loop over the held experts with a
mask, matrix products at ``highest`` precision.  The tests hold the system to
it; the benchmark keeps its own blocked copy
(benchmark/builders/kimi_linear.py).

Like the system it is given a share: the experts ``[expert_lo, expert_lo +
experts_held)`` and the first ``vocab_size`` rows, and it leaves out what the
absent experts would add.  It reads sizes from the same config and the same
flat parameter dict.

Departures from the published code: the rule's state and the convolution
start at zero in every sequence (no cache is carried in); q, k and v are the
columns ``[q | k | v]`` of one matrix and the three narrow projections
``[f↓ | g↓ | β]`` of another (``q_proj`` … side by side: a seeded matrix's
columns); the latent layer's shared key is not turned, so its columns keep the
order they have (no ``even_first``); no auxiliary load-balancing loss (its
coefficient is no key of the published config).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def _conv_silu(x, taps):
    """silu of the depthwise causal convolution: x (B, S, C), taps (K, C)."""
    kernel, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + s] * taps[j] for j in range(kernel)))


def delta_rule(q, k, v, g, beta):
    """Token by token; q, k, g (B, S, H, d_k), v (B, S, H, d_v), beta (B, S, H):
    ``S ← (I − β k kᵀ) Diag(e^g) S + β k vᵀ``, ``o = Sᵀ q``."""
    b, s, h, dk = q.shape
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    out = []
    for t in range(s):
        state = jnp.exp(g[:, t])[..., None] * state  # a decay a row of S
        u = beta[:, t][..., None] * (v[:, t] - jnp.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., :, None] * u[..., None, :]
        out.append(jnp.einsum("bhkv,bhk->bhv", state, q[:, t]))
    return jnp.stack(out, axis=1)


def delta_mixer(cfg, x, lp):
    h, dk, dv, r = cfg.lin_heads, cfg.lin_k_dim, cfg.lin_v_dim, cfg.gate_rank
    b, s, _ = x.shape
    a = _rms(x, lp["norm"], cfg.norm_eps)
    conv = _conv_silu(a @ lp["w_qkv"], lp["conv"])
    q = _l2(conv[..., :h * dk].reshape(b, s, h, dk)) * dk ** -0.5
    k = _l2(conv[..., h * dk:2 * h * dk].reshape(b, s, h, dk))
    v = conv[..., 2 * h * dk:].reshape(b, s, h, dv)
    fgb = a @ lp["w_fgb"]
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
        fgb[..., :r] @ lp["w_f"] + lp["dt_bias"]).reshape(b, s, h, dk)
    beta = jax.nn.sigmoid(fgb[..., 2 * r:])
    o = delta_rule(q, k, v, g, beta)  # (B, S, H, d_v)
    o = lp["o_norm"] * o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * jax.nn.sigmoid(fgb[..., r:2 * r] @ lp["w_g"]).reshape(b, s, h, dv)
    return o.reshape(b, s, h * dv) @ lp["w_out"]


def latent_mixer(cfg, x, lp):
    """Latent attention without positions and without a query bottleneck."""
    nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    a = _rms(x, lp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bhsk", a, lp["wq"])
    kv_a = a @ lp["wkv_a"]
    kv = jnp.einsum("bsr,rhk->bhsk", _rms(kv_a[..., :r], lp["kv_norm"], cfg.norm_eps), lp["wkv_b"])
    k_pe = kv_a[:, None, :, r:]  # (B, 1, S, 64): all heads share it, as it comes
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bhqd,bzkd->bhqk", q[..., nope:], k_pe))
    s = scores.shape[-1]
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores / cfg.qk_dim ** 0.5, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"])


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


MIXER = {"delta": delta_mixer, "latent": latent_mixer}


def _stack(params, stack):
    return {k.split(".", 1)[1]: v for k, v in params.items() if k.startswith(stack + ".")}


def forward(cfg, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    with jax.default_matmul_precision("highest"):
        stacks = {s: _stack(params, s) for s in ("delta", "latent", "dense", "moe")}
        seen = dict.fromkeys(stacks, 0)

        def next_of(stack):
            lp = {k: v[seen[stack]] for k, v in stacks[stack].items()}
            seen[stack] += 1
            return lp

        x = params["embed"][tokens]
        for mixer, mlp in cfg.kinds():
            x = x + MIXER[mixer](cfg, x, next_of(mixer))
            lp = next_of(mlp)
            g = _rms(x, lp["norm"], cfg.norm_eps)
            if mlp == "dense":
                x = x + _swiglu(g, lp["w_gate"], lp["w_up"], lp["w_down"])
            else:
                b, s, d = x.shape
                x = x + expert_mlp(cfg, g.reshape(b * s, d), lp).reshape(b, s, d)
        return _rms(x, params["norm_f"], cfg.norm_eps) @ params["head"].T


def loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy over targets >= 0."""
    logits = forward(cfg, params, tokens)
    valid = targets >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * valid) / jnp.sum(valid)
