"""The gated delta rule — linear attention with a data-dependent decay and a
rank-one correction — in its chunked form.

A head keeps a state ``S`` (d_k × d_v) along the sequence, zero at its start.
At token t, with ``g_t ≤ 0`` the log of the decay and ``β_t`` the writing
strength:

    S ← exp(g_t) S;   u_t = β_t (v_t − Sᵀ k_t);   S ← S + k_t u_tᵀ;   o_t = Sᵀ q_t

(:func:`gated_delta_recurrence`: one ``lax.scan`` over the positions, the
definition; latency-bound, and its backward pass keeps a state a token.)

:func:`chunked_gated_delta_rule` computes the same in chunks of C tokens.
With γ_i the running sum of g inside a chunk, D_ij = exp(γ_i − γ_j) for
i ≥ j and S₀ the state entering the chunk, the u of a chunk solve the
unit-lower-triangular system

    u_i + β_i Σ_{j<i} D_ij (k_i·k_j) u_j = β_i (v_i − exp(γ_i) S₀ᵀ k_i)

so ``U = T (β V) − T (β e^γ K) S₀`` with ``T = (I + A)⁻¹``, which no state
enters: every chunk's T is found at once (:func:`unit_lower_inverse`, matrix
products only).  Then ``o_i = exp(γ_i) S₀ᵀ q_i + Σ_{j≤i} D_ij (k_j·q_i) u_j``
and ``S_C = exp(γ_C) S₀ + Σ_j exp(γ_C − γ_j) k_j u_jᵀ``: one ``lax.scan``
over the chunks carries S with two small products a step and leaves every
chunk's entering state and u; the outputs are batched products after it.
Every exponent is of a non-positive number, so nothing overflows however
strong the decay.  γ, D, the inverse and S are f32; the products take their
operands in ``compute_dtype`` and accumulate in f32.

One layout for every caller, **token-major**: q, k ``(B, S, H_k, d_k)``, v and
o ``(B, S, H_v, d_v)``, g and β ``(B, S, H_v)`` — tokens down, heads side by
side along the lanes, which is how the projections around the rule write and
read them.  The kernels take the ``(B, S, H·d)`` array itself and find a head
by their index maps (a caller's reshape to ``(B, S, H, d)`` and the one back
cancel; an XLA operation ON the 4-D shape would be a copy on a TPU, whose
tiles lie over the last two dims); XLA's form turns to head-major inside
itself.

Two implementations of that one algorithm, chosen by :func:`_kernel_path`
from what the code can observe (the shapes, and through
``_dispatch.kernels_run`` the default device's platform): on a TPU, at
shapes the kernels tile, the Pallas kernels of
``ops/gated_delta_kernels.py`` (``gdn_chunk_inverse``, ``gdn_scan_fwd`` and,
behind a ``custom_vjp``, ``gdn_scan_bwd``: a chunk stays in VMEM from its
first product to its last, the state in VMEM scratch along the sequence; the
backward pass keeps T and a chunk's entering state in the compute dtype, and
T, the entering states and o carry the names in ``gated_delta_kernels.SAVED``
for a caller's ``jax.checkpoint`` policy to keep, so that a rebuilt layer runs
neither forward kernel again);
everywhere else XLA's form below (:func:`_chunked_xla`: the scan over chunks
and autodiff through it, which keeps a chunk's entering state and u), which
is also the kernels' oracle beside the recurrence.  Nothing a token is kept
by either.

**A decay a key channel.**  ``g`` may be ``(B, S, H_v, d_k)``: ``exp(g_t)`` is
then a vector that multiplies S's ROWS, ``S ← Diag(exp(g_t)) S`` (Kimi Delta
Attention; the scalar case is its broadcast, and the recurrence is written
once for both).  With Γ_i the running sum of g inside a chunk, now a vector,
the chunk's system keeps its shape —

    u_i + β_i Σ_{j<i} A_ij u_j = β_i (v_i − S₀ᵀ(e^{Γ_i} ⊙ k_i)),
    o_i = S₀ᵀ(e^{Γ_i} ⊙ q_i) + Σ_{j≤i} B_ij u_j,
    S_C = Diag(e^{Γ_C}) S₀ + Σ_j (e^{Γ_C − Γ_j} ⊙ k_j) u_jᵀ

— but ``A_ij = Σ_c k_ic k_jc e^{Γ_ic − Γ_jc}`` (B the same with q_i) does not
factor into ``D_ij (k_i·k_j)``: the decay sits inside the contraction.  As a
matrix product it is ``(k_i ⊙ e^{Γ_i − Γ_r}) · (k_j ⊙ e^{Γ_r − Γ_j})`` for a
reference row r, and both exponents are ≤ 0 only where j ≤ r ≤ i.  So a
chunk is cut into sub-blocks of ``SUB_CHUNK`` rows (half a chunk where that is
fewer): a sub-block BELOW the diagonal takes r at its rows' first and is a
matrix product; a sub-block ON the diagonal is summed pair by pair,
``Σ_c x_ic k_jc e^{Γ_ic − Γ_jc}`` over i ≥ j alone.  The promise above holds:
every exponent is of a non-positive number.

This form too has two implementations, chosen inside the ``g.ndim == q.ndim``
branch by the same :func:`_kernel_path`: on a TPU at shapes that tile, the
Pallas kernels of ``ops/kda_kernels.py`` (``kda_chunk_inverse``,
``kda_scan_fwd``, ``kda_scan_bwd``: the sub-blocks are built in VMEM, nothing
of a chunk's sub × sub × d_k terms is ever written; T, the entering states
and o carry the names in ``CHANNEL_SAVED``); everywhere else XLA's form
(:func:`_chunked_channel_xla` under :func:`_by_head_blocks`: ``HEAD_BLOCK``
heads at a time, the diagonal sub-blocks of ``PAIRWISE_CHUNKS`` chunks at a
time in a ``lax.map`` rebuilt in the backward pass — the terms of a few
chunks stand, never a sequence's), the kernels' oracle beside the recurrence.
"""

from __future__ import annotations

import math
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.core.telemetry import counters
from byteps_tpu.ops._dispatch import LANES, kernels_run, tuned
from byteps_tpu.ops.gated_delta_kernels import STACK, gated_delta_kernels
from byteps_tpu.ops.kda_kernels import SAVED as CHANNEL_SAVED  # noqa: F401 (callers' policies)
from byteps_tpu.ops.kda_kernels import kda_kernels

CHUNK = 64
#: rows of a sub-block of the channel form: below the diagonal a sub-block is a
#: matrix product, on it a sum pair by pair
SUB_CHUNK = 16
#: heads XLA's channel form takes at a time (it keeps a dozen f32 arrays
#: of q's size for its backward pass: at 32 heads of 128 and 16 384 tokens 4.6
#: GiB all heads at once, PERF.md §6 PR 68)
HEAD_BLOCK = 8
#: chunks whose diagonal sub-blocks XLA's form sums pair by pair at a time: their
#: sub × sub × d_k terms stand together (64 MiB in f32 for 8 heads of 128), and
#: a sequence's 256 chunks are 16 turns of a loop, not 256
PAIRWISE_CHUNKS = 16
# ``CHANNEL_SAVED`` (``kda_kernels.SAVED``): the ``checkpoint_name``s of what the
# channel form leaves for a caller's ``jax.checkpoint`` policy to keep — the
# kernels' T, entering states and o (f32); XLA's form names its o by the last
# alone (its blocks of heads rebuild themselves in their own backward pass).
# Kept, a rebuilt layer runs no forward of the rule again.

#: chunks a grid step of the three kernels (inverse, forward, backward) by
#: sequence length, of the scalar form (``blocks``) and of the channel form
#: (``channel_blocks``): tools/gdn_tune.py's sweeps on the chip, as
#: ops/flash_blocks.json is tools/flash_tune.py's
_TUNED_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "gdn_blocks.json")
_DEFAULT_BLOCKS = (8, 8, 8)


def gated_delta_recurrence(q, k, v, g, beta):
    """The rule token by token.  q, k (B, S, H, d_k), v (B, S, H, d_v), beta
    (B, S, H) and g (B, S, H) — one decay a head — or (B, S, H, d_k) — one a
    key channel, a row of the state —; the state is carried in g's dtype.
    Returns o (B, S, H, d_v)."""
    st = g.dtype
    b, _, h, dk = q.shape
    if g.ndim == beta.ndim:  # a head's one decay is every channel's
        g = g[..., None]

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs  # (B, H, d), (B, H, d_k | 1), (B, H)
        state = jnp.exp(g_t)[..., None] * state
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(x.astype(st), 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1]), st), xs)
    return jnp.moveaxis(o, 0, 1)


def _inverse_by_blocks(a):
    """Every round works on whole C × C matrices and picks its blocks by a
    mask: slicing them out would leave trailing dims of 1, 2, 4 …, which a
    TPU pads to whole tiles.  The rounds are a ``lax.scan`` over the block
    size, so one round's masked copy of ``a`` is alive at a time (unrolled,
    the compiler made all of them first: 1.2 GiB more at 16k tokens)."""
    c = a.shape[-1]
    rows = jnp.arange(c)

    def below_diagonal(size):
        """``a`` where it lies in the block below the diagonal of a 2·size
        square on the diagonal, 0 elsewhere."""
        square, lower_half = rows // (2 * size), (rows // size) % 2 == 1
        return jnp.where((square[:, None] == square[None, :])
                         & lower_half[:, None] & ~lower_half[None, :], a, 0.0)

    def round_(inv, size):
        # with T = diag(P⁻¹, Q⁻¹) so far and L the block below: T − T L T
        return inv - jnp.einsum("...ij,...jk,...kl->...il", inv, below_diagonal(size), inv,
                                precision=lax.Precision.HIGHEST), None

    # the first round's T is the identity
    inv = jnp.eye(c, dtype=a.dtype) - below_diagonal(1)
    if c > 2:
        inv, _ = lax.scan(round_, inv, 2 ** jnp.arange(1, c.bit_length() - 1))
    return inv


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)⁻¹`` for ``a`` (..., C, C) of which only the strictly lower
    triangle is read, C a power of two.  Block by block from the diagonal
    outwards — ``[[P, 0], [L, Q]]⁻¹ = [[P⁻¹, 0], [−Q⁻¹ L P⁻¹, Q⁻¹]]`` — so
    log₂ C − 1 rounds of two batched products and no row-by-row substitution.  Its
    backward pass keeps the inverse alone: ``dA = −Tᵀ dT Tᵀ``, strictly lower."""
    if a.shape[-1] & (a.shape[-1] - 1):
        raise ValueError(f"unit_lower_inverse needs a power of two, got {a.shape[-1]}")
    return _inverse_by_blocks(a)


def _inverse_fwd(a):
    t = _inverse_by_blocks(a)
    return t, t


def _inverse_bwd(t, dt):
    da = -jnp.einsum("...ji,...jk,...lk->...il", t, dt, t, precision=lax.Precision.HIGHEST)
    rows = jnp.arange(t.shape[-1])
    return (jnp.where(rows[:, None] > rows[None, :], da, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _kernel_path(chunk: int, dk: int, dv: int, interpret: bool) -> bool:
    """The Pallas kernels (True) or XLA's chunked form (False), from the
    platform and the shapes alone.  The kernels tile a chunk of 64 or 128
    tokens (whole sublane tiles of any compute dtype, and a whole number of
    them stacks to the MXU's 128 rows) and head sizes of whole lane tiles;
    where they fit, ``_dispatch.kernels_run`` decides.  Everything else is
    XLA's."""
    tiles = chunk in (STACK // 2, STACK) and dk % LANES == 0 and dv % LANES == 0
    return kernels_run(tiles, interpret)


def _sections(doc: dict) -> dict:
    """(a decay a channel?, tokens) → the three kernels' chunks a grid step."""
    return {(channel, int(s)): tuple(b)
            for channel, section in ((False, "blocks"), (True, "channel_blocks"))
            for s, b in doc.get(section, {}).items()}


def _tuned_table() -> dict:
    return tuned(_TUNED_PATH, _sections)


def tuned_blocks(n_chunks: int, chunk: int, blocks: Optional[Sequence[int]] = None,
                 channel: bool = False) -> tuple:
    """Chunks a grid step for (inverse, forward, backward) of the scalar form's
    kernels or the ``channel`` form's: the caller's, or the table's entry for
    this sequence, or the default — each brought down to a power of two that
    divides the sequence's chunks, the first kept a whole number of stacks."""
    wanted = tuple(blocks or _tuned_table().get((channel, n_chunks * chunk), _DEFAULT_BLOCKS))
    per_stack = max(STACK // chunk, 1)
    if n_chunks % per_stack:
        raise ValueError(f"gated delta kernels: {n_chunks} chunks of {chunk} are no whole "
                         f"number of stacks of {STACK} rows")

    def fit(nb, least):
        while nb > least and n_chunks % nb:
            nb //= 2
        return max(nb, least)

    return (fit(wanted[0], per_stack), fit(wanted[1], 1), fit(wanted[2], 1))


def chunked_gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK, compute_dtype=None,
                             interpret: bool = False, blocks: Optional[Sequence[int]] = None):
    """q, k (B, S, H_k, d_k) — normalised and scaled by the caller —, v
    (B, S, H_v, d_v), g ≤ 0 and beta (B, S, H_v); each key head serves
    H_v / H_k value heads in a row (it is never repeated in memory).  With g
    (B, S, H_v, d_k), a decay a key channel, H_k = H_v and the chunk is cut
    into sub-blocks of ``SUB_CHUNK`` rows, two at least (the module's docstring).  Returns
    o (B, S, H_v, d_v) f32.  A sequence that ``chunk`` does not divide raises:
    padding would have to be the caller's choice (a padded token writes to
    the state unless its beta is 0).  Which implementation runs is
    :func:`_kernel_path`'s call; ``interpret`` asks for the Pallas interpreter
    off a TPU (the CPU tests), ``blocks`` overrides the tuned chunks a grid
    step of the kernels."""
    s, hk, hv = q.shape[1], q.shape[2], v.shape[2]
    if s % chunk:
        raise ValueError(f"gated delta rule: chunk {chunk} does not divide sequence {s}")
    if hv % hk:
        raise ValueError(f"{hv} value heads are no multiple of {hk} key heads")
    cdt = compute_dtype or q.dtype
    # decided once a traced call; bps.get_robustness_counters() shows which
    if g.ndim == q.ndim:
        sub = min(SUB_CHUNK, chunk // 2)  # a short chunk still has a sub-block below the diagonal
        if hv != hk or g.shape != q.shape or not sub or chunk % sub:
            raise ValueError(f"gated delta rule, a decay a channel: g {g.shape} beside q "
                             f"{q.shape}, v {v.shape}; sub-blocks of {sub} in a chunk of {chunk}")
        if not _kernel_path(chunk, q.shape[-1], v.shape[-1], interpret):
            counters().bump("gdn_channel_xla_traces")
            return checkpoint_name(_by_head_blocks(q, k, v, g, beta, chunk, sub, cdt),
                                   CHANNEL_SAVED[-1])
        counters().bump("gdn_channel_kernel_traces")
        return kda_kernels(
            q.astype(cdt), k.astype(cdt), v.astype(cdt), g.astype(jnp.float32),
            beta.astype(jnp.float32), chunk,
            tuned_blocks(s // chunk, chunk, blocks, channel=True), interpret)
    if not _kernel_path(chunk, q.shape[-1], v.shape[-1], interpret):
        counters().bump("gdn_xla_traces")
        return _chunked_xla(q, k, v, g, beta, chunk, cdt)
    counters().bump("gdn_kernel_traces")
    f32 = jnp.float32
    return gated_delta_kernels(
        q.astype(cdt), k.astype(cdt), v.astype(cdt), g.astype(f32), beta.astype(f32), chunk,
        tuned_blocks(s // chunk, chunk, blocks), interpret)


def _chunked_xla(q, k, v, g, beta, chunk, cdt):
    """XLA's form: every chunk's T at once, one ``lax.scan`` over the chunks,
    the outputs batched products after it; the backward pass is autodiff.
    Head-major inside itself: the operands are turned on the way in, o on the
    way out."""
    q, k, v, g, beta = (jnp.moveaxis(x, 1, 2) for x in (q, k, v, g, beta))
    b, hk, s, dk = q.shape
    hv, dv = v.shape[1], v.shape[-1]
    f32 = jnp.float32
    n, r = s // chunk, hv // hk

    def product(spec, x, y):
        return jnp.einsum(spec, x.astype(cdt), y.astype(cdt), preferred_element_type=f32)

    # index letters: b batch, h key head, r value head of it, n chunk, i/j
    # rows of a chunk, k/v the two head sizes
    q, k = (x.reshape(b, hk, n, chunk, dk).astype(cdt) for x in (q, k))
    v = v.reshape(b, hk, r, n, chunk, dv).astype(cdt)
    beta = beta.astype(f32).reshape(b, hk, r, n, chunk)
    gamma = jnp.cumsum(g.astype(f32).reshape(b, hk, r, n, chunk), axis=-1)
    rows = jnp.arange(chunk)
    seen = rows[:, None] >= rows[None, :]
    # the mask goes on the exponent too: above the diagonal it is positive
    # and may overflow, and an inf there would poison the gradient
    decay = jnp.where(
        seen, jnp.exp(jnp.where(seen, gamma[..., :, None] - gamma[..., None, :], 0.0)), 0.0)
    e_gamma = jnp.exp(gamma)
    to_end = jnp.exp(gamma[..., -1:] - gamma)  # exp(γ_C − γ_j)

    kk = product("bhnik,bhnjk->bhnij", k, k)[:, :, None]
    a = jnp.where(rows[:, None] > rows[None, :], beta[..., None] * decay * kk, 0.0)
    t = unit_lower_inverse(a)
    # T (β e^γ K) and T (β V): the row scales go on T's columns
    w = product("bhrnij,bhnjk->bhrnik", t * (beta * e_gamma)[..., None, :], k).astype(cdt)
    u0 = product("bhrnij,bhrnjv->bhrniv", t * beta[..., None, :], v)

    def step(state, xs):
        w_n, u0_n, k_n, to_end_n, decay_n = xs
        held = state.astype(cdt)
        u = u0_n - jnp.einsum("bhrik,bhrkv->bhriv", w_n, held, preferred_element_type=f32)
        state = decay_n * state + jnp.einsum(
            "bhik,bhriv->bhrkv", k_n, (to_end_n * u).astype(cdt), preferred_element_type=f32)
        return state, (held, u.astype(cdt))

    state0 = jnp.zeros((b, hk, r, dk, dv), f32)
    varying = tuple(jax.typeof(q).vma)  # the carry's type under shard_map: as q varies
    if varying:
        state0 = lax.pcast(state0, varying, to="varying")
    per_chunk = (jnp.moveaxis(w, 3, 0), jnp.moveaxis(u0, 3, 0), jnp.moveaxis(k, 2, 0),
                 jnp.moveaxis(to_end, 3, 0)[..., None],
                 jnp.moveaxis(e_gamma[..., -1], 3, 0)[..., None, None])
    _, (entering, u) = lax.scan(step, state0, per_chunk)
    entering, u = jnp.moveaxis(entering, 0, 3), jnp.moveaxis(u, 0, 3)

    o = e_gamma[..., None] * product("bhnik,bhrnkv->bhrniv", q, entering)
    qk = product("bhnik,bhnjk->bhnij", q, k)[:, :, None]
    o = o + product("bhrnij,bhrnjv->bhrniv", decay * qk, u)
    return jnp.moveaxis(o.reshape(b, hv, s, dv), 1, 2)


def _pairwise(x, k, gamma, strict: bool):
    """A chunk's diagonal sub-blocks pair by pair: x, k, gamma (..., sub, d_k)
    f32 → ``Σ_c x_ic k_jc exp(γ_ic − γ_jc)`` (..., sub, sub) over i > j
    (``strict``) or i ≥ j, 0 elsewhere.  γ falls along a sub-block's rows, so
    the exponent that is taken is never positive; the mask goes on it too."""
    rows = jnp.arange(x.shape[-2])
    seen = rows[:, None] > rows[None, :] if strict else rows[:, None] >= rows[None, :]
    fall = jnp.where(seen[..., None], gamma[..., :, None, :] - gamma[..., None, :, :], 0.0)
    return jnp.where(seen, jnp.sum(
        x[..., :, None, :] * k[..., None, :, :] * jnp.exp(fall), axis=-1), 0.0)


def _by_head_blocks(q, k, v, g, beta, chunk, sub, cdt):
    """:func:`_chunked_channel_xla` on ``HEAD_BLOCK`` heads at a time, one
    block after another (``lax.map``), each rebuilt in the backward pass:
    heads do not meet in the rule, and what autodiff keeps is one block's.
    Fewer heads than two blocks, or no whole number of them, go at once."""
    h = q.shape[2]
    if h < 2 * HEAD_BLOCK or h % HEAD_BLOCK:
        return _chunked_channel_xla(q, k, v, g, beta, chunk, sub, cdt)

    def blocks(x):  # (B, S, H, ...) → (H / block, B, S, block, ...)
        return jnp.moveaxis(
            x.reshape(x.shape[:2] + (h // HEAD_BLOCK, HEAD_BLOCK) + x.shape[3:]), 2, 0)

    one = jax.checkpoint(lambda xs: _chunked_channel_xla(*xs, chunk, sub, cdt))
    o = jnp.moveaxis(lax.map(one, tuple(blocks(x) for x in (q, k, v, g, beta))), 0, 2)
    return o.reshape(o.shape[:2] + (h,) + o.shape[4:])


def _chunked_channel_xla(q, k, v, g, beta, chunk, sub, cdt):
    """The rule with a decay a key channel (g (B, S, H, d_k)), XLA's form: as
    :func:`_chunked_xla` — every chunk's T at once, one ``lax.scan`` over the
    chunks, batched products after it, autodiff backwards — but A and B are
    built sub-block by sub-block (the module's docstring) and the decays
    that no longer factor out ride on the operands: ``k ⊙ e^Γ``, ``q ⊙ e^Γ``,
    ``k ⊙ e^{Γ_C − Γ}``."""
    q, k, v, g, beta = (jnp.moveaxis(x, 1, 2) for x in (q, k, v, g, beta))
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    n, m = s // chunk, chunk // sub

    def product(spec, x, y):
        return jnp.einsum(spec, x.astype(cdt), y.astype(cdt), preferred_element_type=f32)

    # index letters: b batch, h head, n chunk, i/j rows of a chunk, I/J
    # sub-blocks of it, a/c rows of a sub-block, k/v the two head sizes
    q, k = (x.reshape(b, h, n, chunk, dk).astype(f32) for x in (q, k))
    v = v.reshape(b, h, n, chunk, dv).astype(cdt)
    beta = beta.astype(f32).reshape(b, h, n, chunk)
    gamma = jnp.cumsum(g.astype(f32).reshape(b, h, n, chunk, dk), axis=-2)
    e_gamma = jnp.exp(gamma)
    k_end = k * jnp.exp(gamma[..., -1:, :] - gamma)  # e^{Γ_C − Γ_j} ⊙ k_j

    # -- A (i > j, with k) and B (i ≥ j, with q), sub-block by sub-block
    blocks = (b, h, n, m, sub, dk)
    qs, ks, gs = (x.reshape(blocks) for x in (q, k, gamma))

    @jax.checkpoint
    def diagonal(xs):  # a chunk's sub-blocks: the sub × sub × d_k terms stand here alone
        q_n, k_n, g_n = xs
        return _pairwise(k_n, k_n, g_n, True), _pairwise(q_n, k_n, g_n, False)

    a_diag, b_diag = (jnp.moveaxis(x, 0, 2) for x in lax.map(
        diagonal, tuple(jnp.moveaxis(x, 2, 0) for x in (qs, ks, gs)),
        batch_size=math.gcd(n, PAIRWISE_CHUNKS)))
    same = jnp.eye(m, dtype=bool)[:, None, :, None]

    def whole(diag, below):
        """(…, m, sub, sub) on the diagonal and (…, m−1, sub, m−1, sub) below
        it → a chunk's (chunk, chunk)."""
        full = jnp.where(same, diag[..., :, :, None, :], 0.0)
        full = full + jnp.pad(below, [(0, 0)] * 3 + [(1, 0), (0, 0), (0, 1), (0, 0)])
        return full.reshape(b, h, n, chunk, chunk)

    # sub-block I ≥ 1 below sub-block J < I: r is I's first row, so rows
    # i ≥ r fall from it and Γ_r lies below every Γ_j
    ref = gs[..., 1:, 0, :]  # (b, h, n, m − 1, d_k)
    rows = jnp.exp(gs[..., 1:, :, :] - ref[..., None, :])
    before = (jnp.arange(m - 1)[None, :] <= jnp.arange(m - 1)[:, None])[..., None, None]
    rise = ref[..., :, None, None, :] - gs[..., None, :-1, :, :]
    cols = (ks[..., None, :-1, :, :] * jnp.where(
        before, jnp.exp(jnp.where(before, rise, 0.0)), 0.0)).astype(cdt)
    a_below = product("bhnIak,bhnIJck->bhnIaJc", ks[..., 1:, :, :] * rows, cols)
    b_below = product("bhnIak,bhnIJck->bhnIaJc", qs[..., 1:, :, :] * rows, cols)

    t = unit_lower_inverse(beta[..., None] * whole(a_diag, a_below))
    seen_u = whole(b_diag, b_below).astype(cdt)
    # T (β (e^Γ ⊙ K)) and T (β V): the row scales go on T's columns
    t_beta = t * beta[..., None, :]
    w = product("bhnij,bhnjk->bhnik", t_beta, k * e_gamma).astype(cdt)
    u0 = product("bhnij,bhnjv->bhniv", t_beta, v)

    def step(state, xs):
        w_n, u0_n, k_end_n, end_n = xs
        held = state.astype(cdt)
        u = u0_n - jnp.einsum("bhik,bhkv->bhiv", w_n, held, preferred_element_type=f32)
        state = end_n[..., None] * state + jnp.einsum(
            "bhik,bhiv->bhkv", k_end_n, u.astype(cdt), preferred_element_type=f32)
        return state, (held, u.astype(cdt))

    state0 = jnp.zeros((b, h, dk, dv), f32)
    varying = tuple(jax.typeof(q).vma)  # the carry's type under shard_map: as q varies
    if varying:
        state0 = lax.pcast(state0, varying, to="varying")
    per_chunk = tuple(jnp.moveaxis(x, 2, 0) for x in (
        w, u0, k_end.astype(cdt), e_gamma[..., -1, :]))
    _, (entering, u) = lax.scan(step, state0, per_chunk)
    entering, u = jnp.moveaxis(entering, 0, 2), jnp.moveaxis(u, 0, 2)

    o = product("bhnik,bhnkv->bhniv", q * e_gamma, entering)
    o = o + product("bhnij,bhnjv->bhniv", seen_u, u)
    return jnp.moveaxis(o.reshape(b, h, s, dv), 1, 2)
