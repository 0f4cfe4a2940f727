"""The selective state-space scan (Mamba-2's SSD) — a linear recurrence with
an input-dependent step size, a scalar decay a head and B, C shared by a group
of heads — in its chunked form.

A head keeps a state ``h`` (N × P) along the sequence, zero at its start.  At
token t, with ``dt_t > 0`` the step size, ``a < 0`` the head's rate, ``x_t``
(P,) the head's input and ``B_t``, ``C_t`` (N,) its group's:

    h ← exp(dt_t a) h + dt_t B_t ⊗ x_t;   y_t = hᵀ C_t

(:func:`ssd_recurrence`: one ``lax.scan`` over the positions, the definition;
latency-bound, and its backward pass keeps a state a token.)  There is no
correction term (``ops/gated_delta.py``'s rule has one, and with it an inverse
a chunk); ``D x`` and the gate are the caller's.

:func:`ssd_scan` computes the same in chunks of C tokens.  With γ_i the
running sum of ``dt a`` inside a chunk, ``Λ_ij = exp(γ_i − γ_j)`` for i ≥ j
(the decay matrix), ``G = C Bᵀ`` (one a group) and h₀ the state entering the
chunk:

    y_i = Σ_{j≤i} G_ij Λ_ij dt_j x_j  +  exp(γ_i) h₀ᵀ C_i
    h_C = exp(γ_C) h₀ + Σ_j exp(γ_C − γ_j) dt_j B_j ⊗ x_j

so a chunk is matrix products (the diagonal block ``(G ⊙ Λ)(dt x)``, the
chunk's own state, the entering state read by C) and one state a chunk is
carried along the chunks.  Every exponent is of a non-positive number, so
nothing overflows however strong the decay: a head whose decay over a chunk
underflows just forgets.  γ, Λ and the states are f32; the products take
their operands in ``compute_dtype`` and accumulate in f32.

**Token-major**: x ``(B, S, H·P)``, B and C ``(B, S, G·N)``, dt ``(B, S, H)``
— tokens down, heads side by side along the lanes, as the projection before
the scan writes them and the one after it reads y ``(B, S, H·P)``.

Two implementations of that one algorithm, chosen by :func:`_kernel_path`
from what the code can observe (the shapes, and through
``_dispatch.kernels_run`` the default device's platform): on a TPU, at shapes
the kernels tile, the Pallas kernels of ``ops/ssd_kernels.py``
(``ssd_scan_fwd`` and, behind a ``custom_vjp``, ``ssd_scan_bwd``: a chunk
stays in VMEM from its first product to its last — no decay matrix ever
reaches HBM —, a group's states in VMEM scratch along the sequence; the
backward pass keeps a chunk's entering states in f32, and they and y carry
the names in :data:`SAVED` for a caller's ``jax.checkpoint`` policy to keep,
so that a rebuilt layer does not run the forward kernel again); everywhere
else XLA's form (:func:`_chunked_xla`): a ``lax.scan`` over blocks of
:data:`BLOCK_CHUNKS` chunks, every chunk of a block and every head at once in
batched products, the state carried from block to block; a block is rebuilt
in the backward pass (autodiff through the scan keeps a block's operands and
its entering state, (B, H, N, P) f32: the decay matrices of one block stand at
a time, 1/16 of a layer's at 8192 tokens).  XLA's form is every CPU test's
path and, beside the recurrence, the kernels' oracle.  A traced call bumps
``ssd_kernel_traces`` or ``ssd_xla_traces``
(``bps.get_robustness_counters()``), as ``gdn_kernel_traces`` |
``gdn_xla_traces`` count the gated delta rule's.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.core.telemetry import counters
from byteps_tpu.ops._dispatch import LANES, kernels_run
from byteps_tpu.ops.ssd_kernels import SAVED  # noqa: F401 — for a caller's policy, by this name
from byteps_tpu.ops.ssd_kernels import heads_a_tile, ssd_kernels

CHUNK = 128
#: XLA's form: chunks a step of its scan over the sequence takes at once (fewer
#: where that does not divide the sequence's chunks).  On the chip at (2, 8192)
#: tokens, 64 heads of 64 x 128 in 8 groups, forward + backward of the scan
#: alone: 22.9 ms at 4, 24.9 at 2, 25.7 at 8, 33.8 at 16, 38.9 at 32 (PERF.md
#: §6, PR 51)
BLOCK_CHUNKS = 4
#: the kernels: chunks a grid step of ``ssd_scan_fwd`` | ``ssd_scan_bwd``
#: (fewer where that does not divide the sequence's chunks)
KERNEL_BLOCKS = (4, 4)


def ssd_recurrence(x, dt, a, b, c):
    """The scan token by token.  x (B, S, H, P), dt (B, S, H), a (H,), b and
    c (B, S, G, N), each group serving H / G heads in a row; the state is
    carried in dt's dtype.  Returns y (B, S, H, P)."""
    st = dt.dtype
    bsz, _, h, p = x.shape
    g, n = b.shape[2:]

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs  # (B, H, P), (B, H), (B, G, N) x 2
        b_t, c_t = (jnp.repeat(m, h // g, axis=1) for m in (b_t, c_t))  # (B, H, N)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :])
        return state, jnp.einsum("bhnp,bhn->bhp", state, c_t)

    xs = tuple(jnp.moveaxis(t.astype(st), 1, 0) for t in (x, dt, b, c))
    _, y = lax.scan(token, jnp.zeros((bsz, h, n, p), st), xs)
    return jnp.moveaxis(y, 0, 1)


def _kernel_path(chunk: int, head_dim: int, state: int, heads_a_group: int,
                 interpret: bool) -> bool:
    """The Pallas kernels (True) or XLA's chunked form (False), from the
    platform and the shapes alone.  The kernels tile a chunk of 128 tokens
    (the decay matrix is one f32 128 x 128 block), a state of whole lane
    tiles, and heads that fill whole lane tiles — alone, or a whole number of
    them side by side in one tile with the group's heads a whole number of
    tiles; where they fit, ``_dispatch.kernels_run`` decides.  Everything
    else (the CPU tests' heads of 6 and state of 5) is XLA's."""
    whole = head_dim % LANES == 0 or (
        LANES % head_dim == 0 and heads_a_group % heads_a_tile(head_dim) == 0)
    return kernels_run(chunk == LANES and state % LANES == 0 and whole, interpret)


def ssd_scan(x, dt, a, b, c, heads: int, groups: int, chunk: int = CHUNK, compute_dtype=None,
             interpret: bool = False, blocks: Optional[Sequence[int]] = None):
    """x (B, S, H·P), dt (B, S, H) > 0 — the softplus taken by the caller —,
    a (H,) < 0, b and c (B, S, G·N); each group serves H / G heads in a row
    (it is never repeated in memory).  Returns y (B, S, H·P) f32, without
    ``D x``.  A sequence that ``chunk`` does not divide raises: padding would
    have to be the caller's choice (a padded token writes to the state unless
    its dt is 0).  Which implementation runs is :func:`_kernel_path`'s call;
    ``interpret`` asks for the Pallas interpreter off a TPU (the CPU tests),
    ``blocks`` overrides :data:`KERNEL_BLOCKS`."""
    s = x.shape[1]
    if s % chunk:
        raise ValueError(f"state-space scan: chunk {chunk} does not divide sequence {s}")
    if heads % groups or x.shape[-1] % heads or b.shape[-1] % groups:
        raise ValueError(f"{heads} heads in {groups} groups do not divide the operands' "
                         f"{x.shape[-1]} | {b.shape[-1]} channels")
    cdt = compute_dtype or x.dtype
    # decided once a traced call; bps.get_robustness_counters() shows which
    if not _kernel_path(chunk, x.shape[-1] // heads, b.shape[-1] // groups, heads // groups,
                        interpret):
        counters().bump("ssd_xla_traces")
        return _chunked_xla(x, dt, a, b, c, heads, groups, chunk, cdt)
    counters().bump("ssd_kernel_traces")
    f32 = jnp.float32
    fit = tuple(math.gcd(s // chunk, nb) for nb in blocks or KERNEL_BLOCKS)
    return ssd_kernels(x.astype(cdt), dt.astype(f32), a.astype(f32), b.astype(cdt), c.astype(cdt),
                       groups, chunk, fit, interpret)


def _chunked_xla(x, dt, a, b, c, heads, groups, chunk, cdt):
    """XLA's form: a scan over blocks of chunks, the backward pass autodiff
    through it with a block rebuilt at a time."""
    bsz, s, _ = x.shape
    f32 = jnp.float32
    r, p, n = heads // groups, x.shape[-1] // heads, b.shape[-1] // groups
    n_chunks = s // chunk
    per = math.gcd(n_chunks, BLOCK_CHUNKS)  # chunks a block
    rows = jnp.arange(chunk)
    upto = rows[:, None] >= rows[None, :]  # (i, j): j is no later than i
    seen = upto[:, :, None, None]
    rate = a.astype(f32).reshape(groups, r)

    def product(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(cdt), rhs.astype(cdt), preferred_element_type=f32)

    # index letters: b batch, c chunk of the block, i/j rows of a chunk, g
    # group, r head of it, n and p the state's two sizes

    @jax.checkpoint
    def block(state, xs):
        """``per`` chunks: state (B, G, r, N, P) f32 entering → leaving, and
        the block's y (B, per·chunk, H·P) f32."""
        x_, dt_, b_, c_ = xs
        x_ = x_.reshape(bsz, per, chunk, groups, r, p)
        dt_ = dt_.astype(f32).reshape(bsz, per, chunk, groups, r)
        b_, c_ = (m.reshape(bsz, per, chunk, groups, n) for m in (b_, c_))
        # the running sum as a product with the triangle of ones, f32 at full
        # precision: XLA:TPU's cumsum is a reduce-window that took 46 ms a step
        # of three layers for 4 MB of sums (PERF.md §6, PR 51)
        gamma = jnp.einsum("ij,bcjgr->bcigr", upto.astype(f32), dt_ * rate,
                           precision=lax.Precision.HIGHEST)  # (b, c, i, g, r), <= 0
        # the mask goes on the exponent too: above the diagonal it is positive
        # and may overflow, and an inf there would poison the gradient
        apart = gamma[:, :, :, None] - gamma[:, :, None, :]  # (b, c, i, j, g, r)
        decay = jnp.where(seen, jnp.exp(jnp.where(seen, apart, 0.0)), 0.0)
        e_gamma = jnp.exp(gamma)
        to_end = jnp.exp(gamma[:, :, -1:] - gamma)  # exp(γ_C − γ_j)
        xdt = x_.astype(f32) * dt_[..., None]  # dt_j x_j

        cb = product("bcign,bcjgn->bcijg", c_, b_)
        y = product("bcijgr,bcjgrp->bcigrp", cb[..., None] * decay, xdt)
        own = product("bcjgn,bcjgrp->bcgrnp", b_, xdt * to_end[..., None])  # the chunk's state
        entering = []
        for k in range(per):  # one state a chunk, carried along the chunks
            entering.append(state)
            state = e_gamma[:, k, -1][..., None, None] * state + own[:, k]
        y = y + e_gamma[..., None] * product(
            "bcign,bcgrnp->bcigrp", c_, jnp.stack(entering, axis=1))
        return state, y.reshape(bsz, per * chunk, heads * p)

    state0 = jnp.zeros((bsz, groups, r, n, p), f32)
    varying = tuple(jax.typeof(x).vma)  # the carry's type under shard_map: as x varies
    if varying:
        state0 = lax.pcast(state0, varying, to="varying")

    def blocks(t):  # (B, S, C) → (blocks, B, per·chunk, C)
        return jnp.moveaxis(t.reshape(bsz, n_chunks // per, per * chunk, t.shape[-1]), 1, 0)

    _, y = lax.scan(block, state0, (blocks(x), blocks(dt), blocks(b), blocks(c)))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, s, heads * p)
