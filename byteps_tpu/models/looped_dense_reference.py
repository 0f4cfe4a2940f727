"""Plain reference of the looped dense family (models/looped_dense.py): the
published equations (arXiv 2510.25741 and the model's published modeling
code) in straightforward float32 ``jax.numpy`` — no kernel, no scan, no remat,
no blocks, nothing of the program's.  The loop steps are a Python loop over
the same parameter dict, attention is dense over the whole score matrix with
the causal mask written out and the key/value heads repeated, the logits of
every loop step stand whole, matrix products run at ``highest`` precision.
The tests hold the system to it; the benchmark keeps its own blocked copy
(benchmark/builders/ouro.py).

It reads sizes from the same config and the same flat parameter dict.

Departures from the published code, and what the config does not give: the
head is stored (vocabulary, model) as the embedding is; the final norm's
output is what the next loop step starts from as well as what head and gate
read (the modeling code's order; the paper's ``F = lmhead ∘ M ∘ … ∘ M ∘ emb``
leaves the norm's place open); the exit gate is a linear map with a bias;
the loss is the paper's first stage, ``Σₜ pᵗ CEᵗ − β H(p)`` with β a field of
the config (``exit_beta``); ``early_exit_threshold`` acts at inference alone
and is not here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (..., S, d): ``x · cos + rotate_half(x) · sin`` over the whole head,
    where rotate_half([a | b]) = [−b | a]."""
    s, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], axis=-1) for f in (jnp.cos, jnp.sin))
    half_turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half_turned * sin


def attention(cfg, x, lp):
    group = cfg.n_heads // cfg.n_kv_heads
    q, k, v = (jnp.einsum("bsd,dhk->bhsk", x, lp[w]) for w in ("wq", "wk", "wv"))
    q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / cfg.head_dim ** 0.5
    s = scores.shape[-1]
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]  # queries down, keys across
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v)
    return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"])


def mlp(x, lp):
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def stack(cfg, params, h):
    """The layers once, each sandwich-normed."""
    eps = cfg.norm_eps
    for i in range(cfg.n_layers):
        lp = {k.split(".", 1)[1]: v[i] for k, v in params.items() if k.startswith("layer.")}
        h = h + _rms(attention(cfg, _rms(h, lp["norm"], eps), lp), lp["post_norm"], eps)
        h = h + _rms(mlp(_rms(h, lp["mlp_norm"], eps), lp), lp["mlp_post_norm"], eps)
    return h


def forward(cfg, params, tokens):
    """(B, S) → every loop step's logits (n_loops, B, S, V) and gate
    ``λᵗ`` (n_loops, B, S)."""
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens]
        logits, gates = [], []
        for _ in range(cfg.n_loops):
            h = _rms(stack(cfg, params, h), params["norm_f"], cfg.norm_eps)
            logits.append(h @ params["head"].T)
            gates.append(jax.nn.sigmoid(h @ params["gate_w"] + params["gate_b"]))
        return jnp.stack(logits), jnp.stack(gates)


def exit_distribution(gates):
    """λ (L, ...) → p (L, ...): ``pᵗ = λᵗ ∏_{j<t}(1 − λʲ)``, the last step
    taking what is left."""
    p, left = [], jnp.ones_like(gates[0])
    for lam in gates[:-1]:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])


def loss(cfg, params, tokens, targets):
    """Mean over targets >= 0 of ``Σₜ pᵗ CEᵗ − β H(p)``."""
    logits, gates = forward(cfg, params, tokens)
    p = exit_distribution(gates)
    valid = targets >= 0
    gold = jnp.take_along_axis(logits, jnp.broadcast_to(
        jnp.maximum(targets, 0), logits.shape[:-1])[..., None], axis=-1)[..., 0]
    each = jax.nn.logsumexp(logits, axis=-1) - gold  # (L, B, S)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.sum((jnp.sum(p * each, axis=0) - cfg.exit_beta * entropy) * valid) / jnp.sum(valid)
