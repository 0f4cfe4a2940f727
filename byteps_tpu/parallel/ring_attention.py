"""Ring attention: sequence/context parallelism over a mesh axis.

New scope beyond reference parity (the reference scales batch only, SURVEY
§5.7) but first-class here: long sequences are sharded into contiguous
blocks along the ``sp`` mesh axis; queries stay local while key/value
blocks rotate around the ring via ``lax.ppermute``, with a numerically
stable online-softmax accumulation (flash-attention style m/l/acc state).
Compute on block t overlaps the ICI transfer of block t+1 — XLA schedules
the ppermute concurrently with the einsums.

Causal masking across blocks: a KV block that started ``s`` hops upstream
of this query block is fully visible if it is strictly older, diagonal-
masked if it is the same block, and fully masked if younger.

Works for any axis size (size 1 = plain flash-style attention, zero
collectives), any per-head layout; differentiable (ppermute has a
transpose rule), so jax.grad gives the reverse ring for free.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attend(q, k, v, bias):
    """One block-pair attention: returns (scores_max, exp_sums, weighted_v).

    q: (B, H, Sq, dh), k/v: (B, H, Sk, dh), bias: (Sq, Sk) additive mask.
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) + bias  # (B,H,Sq,Sk)
    m = jnp.max(scores, axis=-1)  # (B,H,Sq)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)  # (B,H,Sq)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m, l, pv


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: Optional[str] = "sp",
    axis_size: int = 1,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Sequence-parallel attention over blocks rotating on ``axis_name``.

    q/k/v: (B, H, S_local, dh) — the local sequence block.
    Returns (B, H, S_local, dh).
    """
    dh = q.shape[-1]
    s_local = q.shape[2]
    scale = scale if scale is not None else dh ** -0.5
    q = q * scale

    # local causal bias template for same-block attention
    idx = jnp.arange(s_local)
    diag_bias = jnp.where(idx[:, None] >= idx[None, :], 0.0, NEG_INF)

    if axis_size == 1 or axis_name is None:
        bias = diag_bias if causal else jnp.zeros_like(diag_bias)
        m, l, pv = _block_attend(q, k, v, bias)
        return pv / l[..., None]

    my_block = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, t):
        k_t, v_t, m_acc, l_acc, o_acc = carry
        # the block currently held started t hops upstream
        src_block = (my_block - t) % axis_size

        def attend(operand):
            k_t, v_t, m_acc, l_acc, o_acc = operand
            if causal:
                # src older → full attend; same block → diagonal mask
                bias = jnp.where(src_block < my_block, 0.0, diag_bias)
            else:
                bias = jnp.zeros((s_local, s_local))
            m_t, l_t, pv_t = _block_attend(q, k_t, v_t, bias)
            # online-softmax merge of (m_acc, l_acc, o_acc) with block t
            m_new = jnp.maximum(m_acc, m_t)
            a = jnp.exp(m_acc - m_new)
            b = jnp.exp(m_t - m_new)
            l_new = l_acc * a + l_t * b
            o_new = o_acc * a[..., None] + pv_t * b[..., None]
            return m_new, l_new, o_new

        def skip(operand):
            # fully-masked future block: contributes nothing — skip both
            # einsums (the block still rotates; downstream devices need it)
            _, _, m_acc, l_acc, o_acc = operand
            return m_acc, l_acc, o_acc

        operand = (k_t, v_t, m_acc, l_acc, o_acc)
        if causal:
            m_new, l_new, o_new = lax.cond(
                src_block <= my_block, attend, skip, operand
            )
        else:
            m_new, l_new, o_new = attend(operand)
        # rotate kv to the next ring position
        k_n = lax.ppermute(k_t, axis_name, perm)
        v_n = lax.ppermute(v_t, axis_name, perm)
        return (k_n, v_n, m_new, l_new, o_new), None

    # derive carries from q so they inherit its varying-axes type (VMA mode)
    zero = (q[..., 0] * 0).astype(jnp.float32)
    m0 = zero + NEG_INF
    l0 = zero
    o0 = (q * 0).astype(jnp.float32)
    (k_f, v_f, m_f, l_f, o_f), _ = lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(axis_size)
    )
    # guard fully-masked rows (l==0 can't happen causally: diagonal always
    # contributes, but keep the guard for non-causal degenerate shapes)
    l_f = jnp.where(l_f == 0, 1.0, l_f)
    return o_f / l_f[..., None]


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: Optional[str] = "sp",
    axis_size: int = 1,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Ring attention whose per-hop compute is the Pallas flash kernel —
    O(block) memory per hop instead of the (B, H, Sq, Sk) score matrix
    :func:`ring_attention` materializes: the two long-context pieces
    composed.

    Each hop runs :func:`flash_attention_lse` on (local q, rotating kv)
    and merges (o_t, lse_t) into the running result with the exact
    logsumexp rule — mathematically identical to the dense ring.  The hop
    mask is structural (full / diagonal-causal / skip), selected by
    ``lax.switch`` on the rotating block's ring distance, so each branch
    traces its own statically-shaped kernel.

    Differentiable end to end: the flash VJP folds the lse cotangent into
    its delta term, and ppermute transposes to the reverse ring.
    """
    from byteps_tpu.ops.flash_attention import flash_attention_lse

    dh = q.shape[-1]
    scale = scale if scale is not None else dh ** -0.5

    def hop(k_t, v_t, causal_flag):
        return flash_attention_lse(
            q, k_t, v_t, causal=causal_flag, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )

    if axis_size == 1 or axis_name is None:
        o, _ = hop(k, v, causal)
        return o

    my_block = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def merge(L_acc, o_acc, lse_t, o_t):
        L_new = jnp.logaddexp(L_acc, lse_t)
        w_acc = jnp.exp(L_acc - L_new)[..., None]
        w_t = jnp.exp(lse_t - L_new)[..., None]
        return L_new, o_acc * w_acc + o_t.astype(jnp.float32) * w_t

    def step(carry, t):
        k_t, v_t, L_acc, o_acc = carry
        src_block = (my_block - t) % axis_size

        def b_skip(op):
            _, _, L_acc, o_acc = op
            return L_acc, o_acc

        def b_diag(op):
            k_t, v_t, L_acc, o_acc = op
            o_t, lse_t = hop(k_t, v_t, True)
            return merge(L_acc, o_acc, lse_t, o_t)

        def b_full(op):
            k_t, v_t, L_acc, o_acc = op
            o_t, lse_t = hop(k_t, v_t, False)
            return merge(L_acc, o_acc, lse_t, o_t)

        operand = (k_t, v_t, L_acc, o_acc)
        if causal:
            # 0 = younger block (skip), 1 = same (diagonal), 2 = older (full)
            idx = jnp.where(
                src_block < my_block, 2, jnp.where(src_block == my_block, 1, 0)
            )
            L_new, o_new = lax.switch(idx, [b_skip, b_diag, b_full], operand)
        else:
            L_new, o_new = b_full(operand)
        k_n = lax.ppermute(k_t, axis_name, perm)
        v_n = lax.ppermute(v_t, axis_name, perm)
        return (k_n, v_n, L_new, o_new), None

    L0 = (q[..., 0] * 0).astype(jnp.float32) + NEG_INF
    o0 = (q * 0).astype(jnp.float32)
    (_, _, L_f, o_f), _ = lax.scan(step, (k, v, L0, o0), jnp.arange(axis_size))
    return o_f.astype(q.dtype)
