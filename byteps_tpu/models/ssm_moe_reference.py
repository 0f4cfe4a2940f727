"""Plain reference of the state-space MoE family (models/ssm_moe.py): the
published equations of Nemotron-H's block (``modeling_nemotron_h.py``) in
straightforward float32 ``jax.numpy`` — no kernel, no chunks, no grouping, no
sort, no remat, no blocks, nothing of the program's.  The state-space
recurrence token by token (one ``lax.scan`` over ``h_t``), dense causal
attention over the whole score matrix with the key/value heads repeated, a
loop over the held experts with a mask, the router in the published order
(sigmoid scores, the ``top_k`` largest of score + bias, the unbiased scores of
the chosen renormalised, then scaled), matrix products at ``highest``
precision.  The tests hold the system to it; the benchmark keeps its own
blocked copy (benchmark/builders/nemotron_h.py).

Like the system it is given a share: the experts ``[expert_lo, expert_lo +
experts_held)`` and the first ``vocab_size`` rows of embedding and head, and
it leaves out what the absent experts would add (the shared expert is whole).
It reads sizes from the same config and the same flat parameter dict.

Departures from ``modeling_nemotron_h.py``: the head is stored (vocabulary,
model) as the embedding is; ``in_proj`` is stored (model, columns) with the
published column order [z | x | B | C | dt]; the convolution is written as a
sum over its taps (``conv.weight[c, 0, j]`` is ``conv[j, c]``), with zeros
before a sequence's start and no cache; the state starts at zero and no state
is returned; ``time_step_limit`` (0, inf) clamps nothing and is left out; the
recurrence is the definition, where the published ``torch_forward`` computes
the same in chunks; ``n_group`` and ``topk_group`` of 1 mean no groups, and
none are written; no second tower (no key of the config belongs to one).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def relu2(h):
    return jnp.where(h > 0, h, 0.0) ** 2


def conv(x, taps, bias):
    """x (B, S, C), taps (K, C): ``y_t = bias + Σ_j taps[j] x_{t−K+1+j}``."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(taps[j] * padded[:, j:j + s] for j in range(k))


def selective_scan(x, dt, a, b, c):
    """x (B, S, H, P), dt (B, S, H), a (H,), b and c (B, S, H, N) (a group's
    already repeated for its heads) → y (B, S, H, P): ``h_t = exp(dt_t a)
    h_{t−1} + dt_t x_t ⊗ B_t``, ``y_t = h_t C_t``, h a (P, N) matrix a head."""
    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + dt_t[..., None, None] * x_t[..., :, None] * b_t[..., None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], x.dtype)
    _, y = jax.lax.scan(token, h0, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def mamba2(cfg, u, lp):
    """u (B, S, D) normed → the mixer's output (B, S, D)."""
    bsz, s, _ = u.shape
    di, h, p = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    zxbcdt = u @ lp["w_in"]
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:di + di + 2 * g * n], zxbcdt[..., -h:]
    xbc = jax.nn.silu(conv(xbc, lp["conv"], lp["conv_bias"]))
    x = xbc[..., :di].reshape(bsz, s, h, p)
    b, c = (jnp.repeat(m.reshape(bsz, s, g, n), h // g, axis=2)
            for m in (xbc[..., di:di + g * n], xbc[..., di + g * n:]))
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    y = selective_scan(x, dt, -jnp.exp(lp["a_log"]), b, c) + lp["d_skip"][:, None] * x
    gated = (y.reshape(bsz, s, di) * jax.nn.silu(z)).reshape(bsz, s, g, di // g)
    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg.norm_eps)
    return (normed.reshape(bsz, s, di) * lp["gate_norm"]) @ lp["w_out"]


def attention(cfg, a, lp):
    """a (B, S, D) normed: causal, no positional encoding."""
    hd, group = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    q, k, v = (jnp.einsum("bsd,dhk->bhsk", a, lp[w]) for w in ("wq", "wk", "wv"))
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / hd ** 0.5
    s = scores.shape[-1]
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v)
    return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"])


def route(cfg, g, lp):
    """g (T, D) → (T, n_experts) weights, zero off the ``top_k`` chosen."""
    scores = jax.nn.sigmoid(g @ lp["router"])
    _, ids = jax.lax.top_k(scores + lp["router_bias"], cfg.top_k)
    chosen = jnp.zeros_like(scores).at[jnp.arange(g.shape[0])[:, None], ids].set(1.0)
    picked = scores * chosen
    return cfg.routed_scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def experts(cfg, g, lp):
    """g (T, D) normed → the held experts' part plus the shared expert."""
    weights = route(cfg, g, lp)
    y = relu2(g @ lp["s_up"]) @ lp["s_down"]
    for e in range(cfg.experts_held):
        y = y + weights[:, cfg.expert_lo + e, None] * (
            relu2(g @ lp["e_up"][e]) @ lp["e_down"][e])
    return y


def _layer_params(params, stack, i):
    return {k.split(".", 1)[1]: v[i] for k, v in params.items() if k.startswith(stack + ".")}


def forward(cfg, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    stack_of = {"M": "ssm", "*": "attn", "E": "moe"}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        nth = dict.fromkeys(stack_of.values(), 0)
        for kind in cfg.layer_types:
            stack = stack_of[kind]
            lp = _layer_params(params, stack, nth[stack])
            nth[stack] += 1
            u = _rms(x, lp["norm"], cfg.norm_eps)
            if kind == "M":
                x = x + mamba2(cfg, u, lp)
            elif kind == "*":
                x = x + attention(cfg, u, lp)
            else:
                b, s, d = x.shape
                x = x + experts(cfg, u.reshape(b * s, d), lp).reshape(b, s, d)
        return _rms(x, params["norm_f"], cfg.norm_eps) @ params["head"].T


def loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy over targets >= 0."""
    logits = forward(cfg, params, tokens)
    valid = targets >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * valid) / jnp.sum(valid)
