"""Plain reference of the block-diffusion MoE family
(models/block_diffusion_moe.py): SDAR's block (``model_type: sdar_moe``, the
Qwen3-MoE layer) under block diffusion's training objective (arXiv 2503.09573,
which arXiv 2510.06303 trains by) in straightforward float32 ``jax.numpy`` —
no kernel, no grouping, no remat, no blocks, nothing of the program's.

The equations.  *Block*: ``h ← h + Attn(RMSNorm(h))``, ``h ← h +
MoE(RMSNorm(h))``, one RMSNorm after the last layer, an untied head.  ``Attn``:
q, k, v without bias at ``n_heads`` | ``n_kv_heads`` heads of ``head_dim``,
RMSNorm over each head of q and of k, rope (half-rotation pairs over the whole
head) at the row's position, softmax at scale ``head_dim^-1/2``, ``W_o``.
``MoE``: router scores in f32, softmax over all ``n_experts``, ``top_k``
chosen, their weights renormalised to sum to one, SwiGLU experts, no shared
expert, no bias, no auxiliary loss.  *One pass*: rows ``[x_t ‖ x_0]``, 2L of
them, embedded by the same table, positions ``(0 … L−1, 0 … L−1)``; key ``j``
is visible to query ``i`` iff (``n`` = "is in the noisy half", ``blk`` over
the position) ``n(i) ∧ n(j) ∧ blk(i) = blk(j)``, or ``n(i) ∧ ¬n(j) ∧ blk(i) >
blk(j)``, or ``¬n(i) ∧ ¬n(j) ∧ blk(i) ≥ blk(j)`` (:func:`visible`).  *Loss*:
the noisy half's logits, no shift, ``(1 / (batch · L)) Σ_i w_i ·
CE(logits_i, x_0[i])``.

:func:`one_pass_logits` is that pass (it computes the last layer's clean half
too, which nothing reads: plain before frugal).  :func:`block_by_block_logits`
is the DEFINITION it vectorises: for every block ``b`` the model run on
``[x_0^{<b}, x_t^b]`` — the clean tokens before the block, then the block
noised — under a block-causal mask, the block's logits kept; the tests hold
the one pass to it.

Like the system it is given a share: the experts ``[expert_lo, expert_lo +
experts_held)`` and the first ``vocab_size`` rows of embedding and head, and
it leaves out what the absent experts would add.  It reads sizes from the same
config and the same flat parameter dict.

Departures from the published code: the head is stored (vocabulary, model) as
the embedding is; the q/k norms are the Qwen3 block's, which ``sdar_moe``
keeps (the config has no key for them); no shift (the published training code
is not in the config: a masked position predicts its own token, as in masked
diffusion); dense attention over a mask, where the published code may use a
flex-attention block mask — an implementation's, not an equation's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def rope(x, theta, positions):
    """x (..., R, d), row r at ``positions[r]``: ``x · cos + rotate_half(x) ·
    sin`` over the whole head, where rotate_half([a | b]) = [−b | a]."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], axis=-1) for f in (jnp.cos, jnp.sin))
    half_turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half_turned * sin


def visible(length, block):
    """(2L, 2L) bool, queries down and keys across, rows ``[0, L)`` the noisy
    half: the three clauses, as comparisons of positions."""
    row = jnp.arange(2 * length)
    noisy, blk = row < length, (row % length) // block
    ni, nj = noisy[:, None], noisy[None, :]
    bi, bj = blk[:, None], blk[None, :]
    return (ni & nj & (bi == bj)) | (ni & ~nj & (bi > bj)) | (~ni & ~nj & (bi >= bj))


def block_causal(rows, block):
    """(R, R) bool over one sequence: key ``j`` iff ``blk(j) <= blk(i)``."""
    blk = jnp.arange(rows) // block
    return blk[None, :] <= blk[:, None]


def attention(cfg, x, lp, seen, positions):
    """x (B, R, D) → (B, R, D): ``seen`` (R, R) says which keys a query sees."""
    hd, group = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    g = _rms(x, lp["norm"], cfg.norm_eps)
    q, k, v = (jnp.einsum("bsd,dhk->bhsk", g, lp[w]) for w in ("wq", "wk", "wv"))
    q = rope(_rms(q, lp["q_norm"], cfg.norm_eps), cfg.rope_theta, positions)
    k = rope(_rms(k, lp["k_norm"], cfg.norm_eps), cfg.rope_theta, positions)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / hd ** 0.5
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v)
    return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"])


def moe_mlp(cfg, g, lp):
    """g (T, D) → the held experts' routed part."""
    probs = jax.nn.softmax(g @ lp["router"], axis=-1)
    _, ids = jax.lax.top_k(probs, cfg.top_k)
    chosen = jnp.zeros_like(probs).at[jnp.arange(g.shape[0])[:, None], ids].set(1.0)
    weights = probs * chosen / jnp.sum(probs * chosen, axis=-1, keepdims=True)
    y = jnp.zeros_like(g)
    for e in range(cfg.experts_held):
        y = y + weights[:, cfg.expert_lo + e, None] * _swiglu(
            g, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e])
    return y


def _layer_params(params, stack, i):
    return {k.split(".", 1)[1]: v[i] for k, v in params.items() if k.startswith(stack + ".")}


def forward(cfg, params, rows, seen, positions):
    """rows (B, R) token ids → (B, R, V) f32 logits over the held rows."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][rows]
        for i in range(cfg.n_layers):
            x = x + attention(cfg, x, _layer_params(params, "attn", i), seen, positions)
            lp = _layer_params(params, "moe", i)
            b, r, d = x.shape
            g = _rms(x, lp["norm"], cfg.norm_eps).reshape(b * r, d)
            x = x + moe_mlp(cfg, g, lp).reshape(b, r, d)
        return _rms(x, params["norm_f"], cfg.norm_eps) @ params["head"].T


def one_pass_logits(cfg, params, noisy, clean):
    """noisy, clean (B, L) → the noisy half's logits (B, L, V), from one pass
    over the 2L rows ``[x_t ‖ x_0]``."""
    length = noisy.shape[1]
    positions = jnp.concatenate([jnp.arange(length)] * 2)
    logits = forward(cfg, params, jnp.concatenate([noisy, clean], axis=1),
                     visible(length, cfg.block_length), positions)
    return logits[:, :length]


def block_by_block_logits(cfg, params, noisy, clean):
    """The same logits by the definition: block ``b``'s from the model run on
    ``[x_0^{<b}, x_t^b]`` under the block-causal mask."""
    length, blk = noisy.shape[1], cfg.block_length
    out = []
    for lo in range(0, length, blk):
        rows = jnp.concatenate([clean[:, :lo], noisy[:, lo:lo + blk]], axis=1)
        logits = forward(cfg, params, rows, block_causal(lo + blk, blk), jnp.arange(lo + blk))
        out.append(logits[:, lo:])
    return jnp.concatenate(out, axis=1)


def loss(cfg, params, noisy, clean, weights, logits=one_pass_logits):
    """``(1 / (batch · L)) Σ w · CE(logits, x_0)``, f32."""
    rows = logits(cfg, params, noisy, clean)
    gold = jnp.take_along_axis(rows, clean[..., None], axis=-1)[..., 0]
    each = jax.nn.logsumexp(rows, axis=-1) - gold
    return jnp.sum(weights.astype(jnp.float32) * each) / weights.size
