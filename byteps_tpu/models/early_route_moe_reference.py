"""Plain reference of the early-routed MoE family (models/early_route_moe.py):
the published equations of SmallThinker's block in straightforward float32
``jax.numpy`` — no kernel, no grouping, no sort, no remat, no blocks, nothing
of the program's.  Dense attention over the whole score matrix with both
masks written out as comparisons of positions and the key/value heads
repeated, a loop over the held experts with a mask, the router in the
published order (the ``top_k`` largest logits, then a softmax over those),
matrix products at ``highest`` precision.  The tests hold the system to it;
the benchmark keeps its own blocked copy (benchmark/builders/smallthinker.py).

Like the system it is given a share: the experts ``[expert_lo, expert_lo +
experts_held)`` and the first ``vocab_size`` rows of embedding and head, and
it leaves out what the absent experts would add.  It reads sizes from the
same config and the same flat parameter dict.

Departures from the published code: the head is stored (vocabulary, model) as
the embedding is; a layer's router is stored with its mixer (``win.router``,
``glob.router``), whose normed input it reads; the sliding mask is the window
alone, with no cache logic; no secondary experts and no load-balancing term
(the published config has keys for neither).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


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


def route(cfg, a, router):
    """a (T, D) → (T, n_experts) weights, zero off the ``top_k`` chosen: the
    largest logits, then a softmax over those alone."""
    logits = a @ router
    chosen, ids = jax.lax.top_k(logits, cfg.top_k)
    return jnp.zeros_like(logits).at[jnp.arange(a.shape[0])[:, None], ids].set(
        jax.nn.softmax(chosen, axis=-1))


def attention(cfg, a, lp, kind):
    """``kind``: the layer's ``layer_types`` entry; a (B, S, D) normed."""
    sliding = kind == "sliding_attention"
    hd, group = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    q, k, v = (jnp.einsum("bsd,dhk->bhsk", a, lp[w]) for w in ("wq", "wk", "wv"))
    if sliding:  # the full layers take no positional encoding at all
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / hd ** 0.5
    seen = visible(scores.shape[-1], cfg.sliding_window if sliding else None)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v)
    return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"])


def experts(cfg, b, weights, lp):
    """b (T, D) → the held experts' part, each a ReLU-gated MLP."""
    y = jnp.zeros_like(b)
    for e in range(cfg.experts_held):
        gate = b @ lp["e_gate"][e]
        hidden = jnp.where(gate > 0, gate, 0.0) * (b @ lp["e_up"][e])
        y = y + weights[:, cfg.expert_lo + e, None] * (hidden @ lp["e_down"][e])
    return y


def _layer_params(params, stack, i):
    return {k.split(".", 1)[1]: v[i] for k, v in params.items() if k.startswith(stack + ".")}


def forward(cfg, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        nth = {"win": 0, "glob": 0}
        for i, kind in enumerate(cfg.layer_types):
            stack = "win" if kind == "sliding_attention" else "glob"
            mixer, mlp = _layer_params(params, stack, nth[stack]), _layer_params(params, "moe", i)
            nth[stack] += 1
            b, s, d = x.shape
            a = _rms(x, mixer["norm"], cfg.norm_eps)
            weights = route(cfg, a.reshape(b * s, d), mixer["router"])  # before the attention
            x = x + attention(cfg, a, mixer, kind)
            g = _rms(x, mlp["norm"], cfg.norm_eps).reshape(b * s, d)
            x = x + experts(cfg, g, weights, mlp).reshape(b, s, d)
        return _rms(x, params["norm_f"], cfg.norm_eps) @ params["head"].T


def loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy over targets >= 0."""
    logits = forward(cfg, params, tokens)
    valid = targets >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * valid) / jnp.sum(valid)
