"""What the kernels of both chunked linear recurrences (the gated delta rule,
``ops/gated_delta_kernels.py``; the selective state-space scan,
``ops/ssd_kernels.py``) do to a chunk in VMEM, once: the f32 product, a
per-token scalar turned between row and column by a masked sum (no
transposes), the decay matrix from a row of log-decays, and the block rounds
of a unit lower-triangular inverse (both forms of the delta rule:
``ops/gated_delta_kernels.py``, ``ops/kda_kernels.py``).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
EXACT = lax.Precision.HIGHEST


def dot(x, y, contract, precision=None):
    """x · y over the given pair of dims, f32 out."""
    return lax.dot_general(x, y, ((contract[:1], contract[1:]), ((), ())),
                           precision=precision, preferred_element_type=F32)


NN, NT, TN = (1, 0), (1, 1), (0, 0)


def iotas(n):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0), lax.broadcasted_iota(jnp.int32, (n, n), 1))


def column(across, eye):
    """(1, n) → (n, 1): a masked sum, no transpose."""
    return jnp.sum(jnp.where(eye, across, 0.0), axis=1, keepdims=True)


def row(down, eye):
    return jnp.sum(jnp.where(eye, down, 0.0), axis=0, keepdims=True)


def decays(g_row, seen, eye):
    """From a chunk's g as a row: γ as a column, and the decay matrix D (0
    above the diagonal; the mask goes on the exponent too)."""
    gam_col = jnp.sum(jnp.where(seen, g_row, 0.0), axis=1, keepdims=True)
    exponent = jnp.where(seen, gam_col - row(gam_col, eye), 0.0)
    return gam_col, jnp.where(seen, jnp.exp(exponent), 0.0)


def total(x):
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)  # (1, 1)


def by_head(x):
    """A per-token scalar of every head, (B, S, H) → (B·H, S): the rows the
    kernels read such scalars from (a few MB: XLA's)."""
    b, s, h = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, s)


def round_levels(rows, cols, strict, chunk):
    """The round of :func:`inverse_rounds` that fills (i, j): the highest bit
    in which i and j differ — the block of that size below the diagonal of
    the square twice it; −1 where ``strict`` is not set."""
    differ = rows ^ cols
    return jnp.where(strict, sum((differ >= 2 ** s).astype(jnp.int32)
                                 for s in range(1, chunk.bit_length() - 1)), -1)


def inverse_rounds(a, level, eye, chunk):
    """``(I + a)⁻¹`` of a stack of chunks, block-diagonal and strictly lower
    inside a chunk, by the block rounds of ``gated_delta._inverse_by_blocks``
    at f32 accuracy: with T = diag(P⁻¹, Q⁻¹) so far and L the block below,
    T − T L T."""
    inv = jnp.where(eye, 1.0, 0.0) - jnp.where(level == 0, a, 0.0)
    for s in range(1, chunk.bit_length() - 1):
        inv = inv - dot(dot(inv, jnp.where(level == s, a, 0.0), NN, EXACT), inv, NN, EXACT)
    return inv
