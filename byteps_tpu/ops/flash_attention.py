"""Flash attention — Pallas TPU kernels with online softmax, forward and
backward.

Forward: one grid cell per (batch·head, query-block); the KV dimension is
the innermost sequential grid axis so Pallas auto-pipelines one (bk, dh)
K/V block at a time through VMEM (O(block) footprint, never the S×S score
matrix).  Online-softmax state (m, l, acc) lives in VMEM scratch persisted
across KV grid steps; the per-row logsumexp is emitted for the backward.

Backward: ONE kernel, grid (bh, nk, nq) with the query blocks innermost.
A block pair's scores, P = exp(QKᵀ·scale − lse) (no saved probabilities:
the forward's logsumexp and Δ = rowsum(dO ∘ O) rebuild them), dP and dS are
computed once and feed all three gradients — five products a pair.  dK and
dV accumulate in f32 scratch for their key block along the query blocks, as
the forward's state does; dQ accumulates in an f32 scratch over the WHOLE
sequence of one (batch·head), each pair adding into its query block's rows,
key blocks ascending, and is cast and written once, while the last key
block's steps pass.  The kernel states the VMEM this takes from its shapes
(:func:`_bwd_vmem_bytes`).  A pair is computed transposed — keys down,
queries across — so lse and Δ are read as one row and only dQ's product
takes a transposed operand.

Grouped queries: K and V may hold fewer heads than Q, a count that divides
Q's.  They go to the kernels as they are, (batch·h_kv, s, d), and a grid row
``i`` (a query head) finds its key/value head at row ``i // group`` by the
index maps (:func:`_kv_row`): per (head, query block) the same blocks are
fetched, from an array ``group`` times smaller than a repeated one.  The
backward kernel still writes dK and dV a query head; the wrapper sums a
group's.  Equal head counts lower as they did before any of this.

The query/key head size and the value head size may differ (latent
attention: 192 for q·k, 128 for v); the matrix products take their operands
in the dtype they arrive in (bf16 in, bf16 on the MXU) and accumulate in
f32, the softmax statistics are f32 throughout.

Fully-masked causal blocks skip all matmuls via pl.when, and their K/V (in
the backward kernel Q/dO/lse/Δ) blocks are not fetched: the block index is
clamped to the last one needed, and Pallas does not copy a block whose index
did not change.  On a TPU the kernels are the only path (a sequence the
blocks do not divide raises);
off a TPU the dense reference stands in — see :func:`_kernel_path`, the one
place that asks (``_dispatch.kernels_run`` answers).  Differentiable end to end.

The band (``window=W`` with ``causal=True``): query ``i`` sees key ``j`` iff
``0 <= i - j < W`` — itself and the ``W - 1`` keys before it.  A banded call
does not walk the whole (query block, key block) square and skip: its
innermost grid axis is only as long as the band is wide in blocks
(:func:`_band_steps`), and step ``t`` of it is the ``t``-th block of the
band — key block ``first + t`` of query block ``qi`` forward, query block
``first + t`` of key block ``j`` backward — so a block pair outside the band
is neither computed, nor fetched, nor stepped over.  What is left to skip by
``pl.when`` are the steps past the band's end near the sequence's edges
(their block index is clamped, so nothing is copied); the mask inside the
band's two edge blocks is the causal comparison and ``i - j < W``.  dQ's
accumulator is cleared where a query block meets its first key block and the
running sum is written at every pair, the last of which is whole (at a window
the last key block meets only the last query blocks, so "while the last key
block's steps pass" would leave the others unwritten).  Banded calls carry
their own kernel names (``flash_fwd_win``, ``flash_bwd_win``) and their own
table of blocks, keyed by (sequence, window).  ``window=None`` is the code
above, unchanged.

The block-diffusion mask (:func:`block_diffusion_attention`): the keys are two
copies of one sequence of ``L`` tokens, a noised copy in rows ``[0, L)`` and
the clean copy in rows ``[L, 2L)``, and with ``blk(i) = (i mod L) // B`` a
NOISY query sees the noisy keys of its own block (both directions) and the
clean keys of every earlier block; a CLEAN query sees the clean keys of its own
and every earlier block; nothing else.  ``L² + L·B`` entries over ``2L`` rows
in a shape that is neither causal nor a band, so the pair of kernels
(``flash_fwd_bd``, ``flash_bwd_bd``) walks a TABLE of tiles: which key tiles a
query tile meets, in ascending order, how many, and which of them are visible
whole (no mask is computed there) — made from the shapes alone
(:func:`_bd_tiles`), handed to the kernels as scalars before the grid runs, and
read by the index maps, so a tile that holds no visible entry (the whole
clean-query × noisy-key quadrant; all of noisy × noisy but its diagonal) is
neither computed, nor fetched, nor stepped over.  The innermost grid axis is
as long as the longest row of the table; the steps past a row's end repeat its
last tile and run nothing.  The queries may be the noisy half alone
(``L`` rows against ``2L`` keys: what a last layer's loss reads).  dQ is
written as the banded kernel writes it: the running sum at every pair.

One body a direction for all three masks: every forward kernel's pair is
:func:`_softmax_pair` (between :func:`_softmax_init` and :func:`_softmax_emit`),
every backward kernel's :func:`_grad_pair`, and each direction has one
``pallas_call`` site (:func:`_forward_call`, :func:`_backward_call`: a plain
grid, or with tables before it a ``PrefetchScalarGridSpec``).  A kernel
factory holds what its mask family really differs in: how a step finds its
tile, the mask, and when dQ's rows are cleared and written.

A partial tile is walked by sub-blocks.  A tile the mask crosses — the causal
diagonal, a band's near and far edge, a block-diffusion diagonal — is not
computed whole and masked: the pair's body takes a static LIST of rectangles
(query rows × key columns, in whole sub-blocks of ``SUB_BLOCK`` a side) that
hold at least one visible entry, and makes the scores, the mask, the
exponentials and the products for those alone; a sub-block the mask hides
whole costs nothing.  The tile, its grid step and its DMA stay what they were.
A KIND of tile is a distinct map of such sub-blocks, made in numpy at trace
time from the mask's own predicate (:func:`_band_kinds` from
:func:`_band_visible` — with ``bq == bk`` a tile's pattern depends on its
offset ``qi − j`` and the window alone; :func:`_bd_kinds` from
:func:`block_diffusion_visible`, tile by tile, so two quadrants whose tiles
list the same sub-blocks share a kind), and a kernel has one instance of the
body a kind, chosen by ``pl.when``: the causal kernels on the diagonal, the
banded ones on the offset, the table-driven ones from a ``kind`` table beside
``kv_whole`` | ``q_whole``.  Adjacent sub-blocks merge into one rectangle
along a strip (:func:`_rectangles`): the forward kernels cut a tile into strips
of QUERY ROWS, so a row's maximum, sum and P V lose only terms that were exact
zeros; the backward kernel into strips of KEYS, so dK's and dV's sums do —
dQ's sum over a tile's keys is then taken a strip at a time into its f32
accumulator, equal to rounding and not bit for bit (``STRIPS``).  The mask is
still applied inside a listed rectangle.  The path is taken only where the
geometry lines up (tiles of whole sub-blocks, at least two a side; for the
causal and banded kernels ``bq == bk``) AND a kind spares at least
``MIN_SPARED`` of its tile's sub-blocks; everything else — every whole tile,
every small-block test, ``bq != bk`` — lowers as it did.  ``SUB_BLOCK`` and
``MIN_SPARED`` come from one on-chip sweep (``tools/flash_tune.py
--sub-blocks``; its numbers are in ``flash_blocks.json``'s source texts), and
:func:`computed_entries` says from the shapes what a call computes and what
its mask keeps.

This is the per-device compute of the transformer's attention; sequence
parallelism composes on top (ring attention rotates KV blocks *between*
devices, these kernels handle the blocks *within* one device).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.ops._dispatch import LANES, kernels_run, tuned, vma_union

NEG_INF = -1e30

#: what the forward leaves for the backward, by name: a ``jax.checkpoint``
#: whose policy saves these (``save_only_these_names(*SAVED)``) does not run
#: the forward kernel a second time to rebuild them — the output and one f32
#: logsumexp a row, against the kernel's S^2 work
SAVED = ("flash_out", "flash_lse")

#: the kernels' names: a trace files their time under these
FWD_KERNEL, BWD_KERNEL = "flash_fwd", "flash_bwd"
#: and of a banded call's (``window=``): filed apart whatever scope path is kept
FWD_WIN_KERNEL, BWD_WIN_KERNEL = "flash_fwd_win", "flash_bwd_win"
#: and of a block-diffusion call's (:func:`block_diffusion_attention`)
FWD_BD_KERNEL, BWD_BD_KERNEL = "flash_fwd_bd", "flash_bwd_bd"


def _dense_reference(q, k, v, causal, scale, window=None):
    return _dense_reference_lse(q, k, v, causal, scale, window)[0]


def _dense_reference_lse(q, k, v, causal, scale, window=None):
    """Dense (out, lse) from ONE (s, s) score matrix — the lse fallback
    must not materialize scores twice (round-3 advisor finding).  Fewer
    key/value heads than query heads are repeated here, each for its group."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool))
        if window is not None:  # 0 <= i - j < window
            mask &= ~jnp.tril(jnp.ones((qlen, klen), bool), -window)
        s = jnp.where(mask, s, NEG_INF)
    s32 = s.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(s32, axis=-1)
    p = jnp.exp(s32 - lse[..., None]).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v), lse


def _block_needed(causal: bool, qi, j, bq: int, bk: int, window=None):
    """Whether KV block j contributes anything to query block qi: its first
    key is not after the block's last query and, at a window, its last key
    is within the window of the block's first query."""
    if not causal:
        return True
    needed = j * bk < (qi + 1) * bq
    if window is not None:
        needed &= (j + 1) * bk - 1 > qi * bq - window
    return needed


def _band_visible(rows, cols, window=None):
    """The causal rule, and the band's: query ``rows`` sees key ``cols`` iff
    ``0 <= rows - cols`` (``< window``).  Ints that broadcast, numpy's (the
    lists of sub-blocks, :func:`_band_kinds`) or jax's (the kernels' mask)."""
    if window is not None:
        return (rows >= cols) & (rows - cols < window)
    return rows >= cols


def _causal_keep(qi, j, bq: int, bk: int, keys_down: bool = False, window=None, rect=None):
    """Bool mask of a block pair's causally-visible positions: (bq, bk), or
    (bk, bq) with the keys down and the queries across; at a window, of those
    the ones fewer than ``window`` back.  ``rect``: of that rectangle of the
    pair alone (:func:`_rectangles`)."""
    r0, r1, c0, c1 = rect or (0, bq, 0, bk)
    shape, q_dim = ((c1 - c0, r1 - r0), 1) if keys_down else ((r1 - r0, c1 - c0), 0)
    rows = _from(qi * bq, r0) + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    cols = _from(j * bk, c0) + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    return _band_visible(rows, cols, window)


def _from(base, lo: int):
    """``base + lo``; ``base`` itself at 0, so that a whole tile's text is the
    one it was before a tile could be cut."""
    return base + lo if lo else base


# ---------------------------------------------------------------------------
# a partial tile's sub-blocks: which of them hold a visible entry
# ---------------------------------------------------------------------------

#: side of a sub-block: a tile the mask crosses is walked in squares of this
#: many rows and columns, and a square with no visible entry is not computed
SUB_BLOCK = 256
#: the least share of its sub-blocks a kind of tile must spare to be walked so
MIN_SPARED = 0.3
#: the axis of the strips a direction cuts a tile into: the forward kernels by
#: query rows (a row's maximum, sum and P V keep their order), the backward
#: kernel by keys (dK's and dV's sums keep theirs; dQ's is taken in pieces)
STRIPS = {"fwd": "q", "bwd": "k"}
#: a table-driven call with more distinct kinds than this lowers whole tiles
MAX_KINDS = 4


def _lines_up(bq: int, bk: int, sub: int) -> bool:
    """Whether a (bq, bk) tile is whole sub-blocks, at least two a side."""
    return bq % sub == 0 and bk % sub == 0 and min(bq, bk) >= 2 * sub


def _sub_map(visible, sub: int):
    """(bq, bk) bools → (bq / sub, bk / sub): a sub-block holds a visible entry."""
    bq, bk = visible.shape
    return visible.reshape(bq // sub, sub, bk // sub, sub).any(axis=(1, 3))


def _spares_enough(sub_map, least: float) -> bool:
    return 1 - sub_map.mean() >= least


def _rectangles(sub_map, sub: int, by: str) -> tuple:
    """The listed sub-blocks of a map as rectangles ``(r0, r1, c0, c1)`` of
    entries (query rows × key columns), strips ascending: ``by`` "q" cuts the
    tile into strips of query rows one sub-block high, "k" of key columns; a
    strip's adjacent sub-blocks are one rectangle, and adjacent strips that
    list the same run merge."""
    import numpy as np

    found = []  # [strip from, to, run from, to), in sub-blocks
    for at, line in enumerate(sub_map if by == "q" else sub_map.T):
        edges = np.flatnonzero(np.diff(np.r_[0, line.astype(int), 0])).tolist()
        for run in zip(edges[::2], edges[1::2]):  # a run's first sub-block, and one past its last
            same = [f for f in found if f[1] == at and f[2:] == list(run)]
            if same:
                same[0][1] = at + 1
            else:
                found.append([at, at + 1, *run])
    return tuple(tuple(sub * x for x in (f if by == "q" else f[2:] + f[:2])) for f in found)


@functools.lru_cache(maxsize=None)
def _band_kinds(bq: int, bk: int, window, sub: int, least: float) -> dict:
    """offset ``qi - j`` → sub-block map (numpy bools), for the kinds of tile
    of a causal (``window`` None) or banded call that are walked by sub-blocks.
    With ``bq == bk`` a tile's pattern is its offset's, whatever the tile; a
    geometry that does not line up has no kinds and lowers whole tiles."""
    import numpy as np

    if bq != bk or not _lines_up(bq, bk, sub):
        return {}
    offsets = range(1 if window is None else (window + bq - 2) // bq + 1)
    rows, cols = np.ogrid[:bq, :bk]
    maps = {o: _sub_map(_band_visible(o * bq + rows, cols, window), sub) for o in offsets}
    return {o: m for o, m in maps.items() if _spares_enough(m, least)}


def _band_rects(bq, bk, window, direction: str) -> dict:
    """offset → rectangles, at the module's constants, for ``direction``'s kernel."""
    kinds = _band_kinds(bq, bk, window, SUB_BLOCK, MIN_SPARED)
    return {o: _rectangles(m, SUB_BLOCK, STRIPS[direction]) for o, m in kinds.items()}


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _last_kv_block(qi, bq: int, bk: int):
    """Index of the last KV block a causal query block qi reads."""
    return ((qi + 1) * bq - 1) // bk


def _first_q_block(j, bq: int, bk: int):
    """Index of the first query block that sees causal KV block j."""
    return (j * bk) // bq


def _first_kv_block(qi, bq: int, bk: int, window: int):
    """Index of the first KV block a banded query block qi reads: the one
    that holds the key ``window - 1`` before the block's first query.  (A
    Python ``qi`` gives a Python index: the grid's length is static.)"""
    largest = max if isinstance(qi, int) else jnp.maximum
    return largest(qi * bq - window + 1, 0) // bk


def _last_q_block(j, bq: int, bk: int, window: int, nq: int):
    """Index of the last query block that sees banded KV block j: the one
    that holds the query ``window - 1`` after the block's last key."""
    least = min if isinstance(j, int) else jnp.minimum
    return least(((j + 1) * bk + window - 2) // bq, nq - 1)


def _band_steps(s: int, bq: int, bk: int, window: int) -> tuple:
    """How many KV blocks the widest query block's band spans, and how many
    query blocks the widest KV block's: the lengths of the banded kernels'
    innermost grid axes (forward, backward)."""
    nq = s // bq
    fwd = max(_last_kv_block(qi, bq, bk) - _first_kv_block(qi, bq, bk, window) + 1
              for qi in range(nq))
    bwd = max(_last_q_block(j, bq, bk, window, nq) - _first_q_block(j, bq, bk) + 1
              for j in range(s // bk))
    return fwd, bwd


# The forward kernels carry their per-row softmax state (m, l, and the lse they
# emit) broadcast across a trailing LANES dim, so every block-mapped ref keeps
# its last two dims (8, 128)-tileable — a (bh, s) output with (1, bq) blocks
# fails Mosaic's block-mapping check (the same layout jax's bundled TPU flash
# kernel uses for its l/m residuals).  The residual is one value a row, and the
# backward kernels read it so: lse and Δ as (bh, 1, s), a (1, bq) block along
# the lanes.


def _softmax_init(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _cut(ref, lo: int, hi: int, axis: int = 0):
    """Rows (``axis`` 0) or lanes (1) ``[lo, hi)`` of a block ref's leading
    item — static, on sub-block boundaries; the whole item where the range is."""
    if (lo, hi) == (0, ref.shape[1 + axis]):
        return ref[0]
    return ref[0, lo:hi, :] if axis == 0 else ref[0, :, lo:hi]


def _softmax_pair(q_ref, k_ref, v_ref, keep, scale, m_scr, l_scr, acc_scr, rects=None):
    """One (query block, key block) pair of the online softmax, for every
    forward kernel.  ``keep``: ``None`` where the whole tile is visible, else
    a function that gives the bool tile of visible positions — a function, so
    that the mask is made where it is used, after the scores.  A row with no
    visible key in a tile adds it at weight 1 under m = NEG_INF, which its
    first real maximum's exp(NEG_INF - m) wipes: every row sees at least one
    key, itself or the sequence's first.

    ``rects``: a partial tile's listed rectangles (:func:`_rectangles`) — the
    scores, the mask (``keep(rect)``), the exponentials and P V are made for
    these alone, each against its own rows of the state; a row outside every
    rectangle has no visible key here and its state stands.  By strips of
    query rows a row's maximum, sum and P V lose only terms that were exact
    zeros."""
    for rect in rects or (None,):
        r0, r1, c0, c1 = rect or (0, q_ref.shape[1], 0, k_ref.shape[1])
        rows = slice(None) if rect is None else slice(r0, r1)
        v = _cut(v_ref, c0, c1)
        s = jax.lax.dot_general(
            _cut(q_ref, r0, r1), _cut(k_ref, c0, c1), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if keep is not None:
            s = jnp.where(keep(rect=rect), s, NEG_INF)
        m = m_scr[rows]  # (rows, LANES), value broadcast across lanes
        l = l_scr[rows]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, 0:1])
        m_scr[rows] = m_new
        l_scr[rows] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[rows] = acc_scr[rows] * alpha[:, 0:1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _softmax_emit(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    l = l_scr[:]
    l = jnp.where(l == 0, 1.0, l)
    o_ref[0] = (acc_scr[:] / l[:, 0:1]).astype(o_ref.dtype)
    lse_ref[0] = m_scr[:] + jnp.log(l)


def _fwd_kernel_factory(bq, bk, nk, causal, scale, window=None):
    """``nk``: the innermost grid axis's length — the KV blocks, or at a
    window the band's (:func:`_band_steps`), step ``t`` being KV block
    ``first + t`` of its query block."""
    from jax.experimental import pallas as pl

    kinds = _band_rects(bq, bk, window, "fwd") if causal else {}

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch):
        qi = pl.program_id(1)
        step = j = pl.program_id(2)
        if window is not None:
            j = _first_kv_block(qi, bq, bk, window) + step
        keep = functools.partial(_causal_keep, qi, j, bq, bk, window=window) if causal else None
        pl.when(step == 0)(functools.partial(_softmax_init, *scratch))
        for needed, rects in _by_kind(_block_needed(causal, qi, j, bq, bk, window), qi, j, kinds):
            pl.when(needed)(functools.partial(
                _softmax_pair, q_ref, k_ref, v_ref, keep, scale, *scratch, rects=rects))
        pl.when(step == nk - 1)(functools.partial(_softmax_emit, o_ref, lse_ref, *scratch))

    return kernel


def _by_kind(needed, qi, j, kinds: dict):
    """(when, rectangles) of a causal or banded kernel's pair: the whole-tile
    body where the tile's offset ``qi - j`` is of no kind — all there is where
    the geometry does not line up —, and one body a kind (:func:`_band_kinds`)."""
    if not kinds:
        return [(needed, None)]
    offset = qi - j
    rest = functools.reduce(jnp.logical_and, [offset != o for o in kinds])
    return [(needed & rest, None)] + [(needed & (offset == o), rects)
                                      for o, rects in kinds.items()]


def _kv_index(causal, bq, bk, window=None, group=1):
    """Index map of a K/V block in a (bh, q block, kv block) grid; at a
    window the last axis counts from the band's first KV block, and the index
    is held at the band's last.  ``group`` query heads read one key/value
    head (:func:`_kv_row`)."""
    if not causal:
        index = lambda i, qi, j: (i, j, 0)  # noqa: E731
    elif window is not None:
        index = lambda i, qi, t: (i, jnp.minimum(_first_kv_block(qi, bq, bk, window) + t,  # noqa: E731
                                                 _last_kv_block(qi, bq, bk)), 0)
    else:
        index = lambda i, qi, j: (i, jnp.minimum(j, _last_kv_block(qi, bq, bk)), 0)  # noqa: E731
    return _kv_row(index, group)


def _kv_row(index, group: int):
    """``index`` with its first block index turned from the grid's row — a
    query head, ``i = batch · h + head`` — into the key/value head that serves
    it: with ``h = h_kv · group`` that is row ``i // group`` of K and V laid
    out (batch · h_kv, s, d).  At equal head counts the map itself."""
    if group == 1:
        return index
    return lambda i, *at: (i // group,) + index(i, *at)[1:]


def _q_index(causal, bq, bk, window=None, nq=None):
    """Index map of a Q/dO block in a (bh, kv block, q block) grid; at a
    window the last axis counts from the band's first query block, and the
    index is held at the band's last."""
    if not causal:
        return lambda i, j, qi: (i, qi, 0)
    if window is not None:
        return lambda i, j, t: (i, jnp.minimum(_first_q_block(j, bq, bk) + t,
                                               _last_q_block(j, bq, bk, window, nq)), 0)
    return lambda i, j, qi: (i, jnp.maximum(qi, _first_q_block(j, bq, bk)), 0)


def _forward_call(kernel, name, steps, kv_index, q, k, v, bq, bk, interpret, scalars=()):
    """Every forward kernel's ``pallas_call``: a (batch·head, query block,
    ``steps``) grid, the query blocks' index map the plain one, K's and V's
    ``kv_index``.  ``scalars``: tables handed to the kernel and the index maps
    before the grid runs (the table-driven kernel's); none is a plain grid."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    vma = vma_union(q, k, v)
    b, h, sq, dqk = q.shape
    h_kv, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    q_index = lambda i, qi, t, *_: (i, qi, 0)  # noqa: E731
    grid = dict(
        grid=(b * h, sq // bq, steps),
        in_specs=[
            pl.BlockSpec((1, bq, dqk), q_index),
            pl.BlockSpec((1, bk, dqk), kv_index),
            pl.BlockSpec((1, bk, dv), kv_index),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, dv), q_index),
            pl.BlockSpec((1, bq, LANES), q_index),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
    )
    if scalars:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), **grid))
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b * h, sq, dv), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((b * h, sq, LANES), jnp.float32, vma=vma),
        ),
        compiler_params=pltpu.CompilerParams(
            # bh and q-block cells are independent; only the k scan (which
            # accumulates into scratch) is order-dependent — telling Mosaic
            # lets it pipeline/parallelize the outer grid dims
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
        **grid,
    )(*scalars, q.reshape(b * h, sq, dqk), k.reshape(b * h_kv, sk, dqk),
      v.reshape(b * h_kv, sk, dv))
    return out.reshape(b, h, sq, dv), lse


def _flash_forward(q, k, v, causal, scale, bq, bk, interpret, window=None):
    s, group = q.shape[2], q.shape[1] // k.shape[1]
    nk = s // bk if window is None else _band_steps(s, bq, bk, window)[0]
    return _forward_call(
        _fwd_kernel_factory(bq, bk, nk, causal, scale, window),
        FWD_KERNEL if window is None else FWD_WIN_KERNEL, nk,
        _kv_index(causal, bq, bk, window, group), q, k, v, bq, bk, interpret)


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------


def _grad_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, keep, scale, rows,
               dq_scr, dk_scr, dv_scr, rects=None):
    """One (key block, query block) pair of every backward kernel: the five
    products and the three accumulations, dQ's into ``rows`` of its accumulator.
    Everything is (bk, bq), keys down and queries across: lse and Δ are then
    one row, and of the five products only dQ's takes its left operand
    transposed.  ``keep`` as :func:`_softmax_pair`'s, of a keys-down tile.

    ``rects`` as :func:`_softmax_pair`'s: the five products for the listed
    rectangles alone, dQ into the rectangle's rows of ``rows``, dK | dV into
    its columns' rows of their accumulators.  By strips of keys dK's and dV's
    sums lose only exact zeros; dQ's sum over a tile's keys is then taken a
    strip at a time, each added to the f32 accumulator: equal to rounding,
    not bit for bit (by strips of query rows it is the other way round)."""
    from jax.experimental import pallas as pl

    for rect in rects or (None,):
        r0, r1, c0, c1 = rect or (0, q_ref.shape[1], 0, k_ref.shape[1])
        cols = slice(None) if rect is None else slice(c0, c1)
        into = rows if rect is None else pl.ds(
            pl.multiple_of(rows.start + r0, math.gcd(rows.size, r0)), r1 - r0)
        q, k, do = _cut(q_ref, r0, r1), _cut(k_ref, c0, c1), _cut(do_ref, r0, r1)
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        pt = jnp.exp(st - _cut(lse_ref, r0, r1, axis=1))
        if keep is not None:
            pt = jnp.where(keep(rect=rect), pt, 0.0)
        dv_scr[cols] = dv_scr[cols] + jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            _cut(v_ref, c0, c1), do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dst = (pt * (dpt - _cut(delta_ref, r0, r1, axis=1))).astype(q.dtype)
        dk_scr[cols] = dk_scr[cols] + scale * jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dq_scr[into, :] = dq_scr[into, :] + scale * jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )


def _dkv_init(dk_scr, dv_scr):
    dk_scr[:] = jnp.zeros_like(dk_scr)
    dv_scr[:] = jnp.zeros_like(dv_scr)


def _dkv_emit(dk_ref, dv_ref, dk_scr, dv_scr):
    dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_clear(dq_scr, rows, bq):
    dq_scr[rows, :] = jnp.zeros((bq, dq_scr.shape[1]), dq_scr.dtype)


def _dq_write(dq_ref, dq_scr, rows):
    dq_ref[0] = dq_scr[rows, :].astype(dq_ref.dtype)


def _bwd_kernel_factory(bq, bk, nq, nk, causal, scale, window=None, steps=None):
    """``nq``, ``nk``: the sequence's query and key blocks.  ``steps``: the
    innermost grid axis's length — ``nq``, or at a window the band's
    (:func:`_band_steps`), step ``t`` being query block ``first + t`` of its
    key block."""
    from jax.experimental import pallas as pl

    steps = nq if steps is None else steps
    kinds = _band_rects(bq, bk, window, "bwd") if causal else {}

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr):
        j = pl.program_id(1)   # key block (sequential: dQ accumulates over it)
        step = qi = pl.program_id(2)  # query block (sequential, innermost)
        if window is not None:
            qi = _first_q_block(j, bq, bk) + step
        rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)  # this query block's of dq_scr
        keep = (functools.partial(_causal_keep, qi, j, bq, bk, keys_down=True, window=window)
                if causal else None)
        clear_dq = functools.partial(_dq_clear, dq_scr, rows, bq)
        write_dq = functools.partial(_dq_write, dq_ref, dq_scr, rows)

        if window is None:
            pl.when(j == 0)(clear_dq)
        pl.when(step == 0)(functools.partial(_dkv_init, dk_scr, dv_scr))

        needed = _block_needed(causal, qi, j, bq, bk, window)
        if window is not None:
            needed &= qi < nq  # the band's steps past the sequence's end

        def _block(rects):
            if window is not None:  # the first key block this query block meets
                pl.when(j == _first_kv_block(qi, bq, bk, window))(clear_dq)
            _grad_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, keep, scale, rows,
                       dq_scr, dk_scr, dv_scr, rects=rects)
            if window is not None:  # the running sum; a block's last pair writes it whole
                write_dq()

        for when, rects in _by_kind(needed, qi, j, kinds):
            pl.when(when)(functools.partial(_block, rects))

        pl.when(step == steps - 1)(functools.partial(_dkv_emit, dk_ref, dv_ref, dk_scr, dv_scr))
        if window is None:
            pl.when(j == nk - 1)(write_dq)

    return kernel


def _bwd_vmem_bytes(s, bq, bk, dqk, dv, itemsize) -> int:
    """What the backward kernel asks of VMEM, from its shapes: dQ's
    accumulator and dK's and dV's in f32, every block-mapped operand twice
    (Pallas double-buffers them), the (bk, bq) intermediates — scores, P, dP
    and dS in f32, P's and dS's casts —, a quarter over for what Mosaic
    spills, and never under Mosaic's own default.  A head size takes whole
    lane tiles.  (A partial tile's rectangles hold smaller intermediates, one
    rectangle at a time; the whole tiles beside them set the peak, so the
    figure stands.)"""
    dqk, dv = (-(-d // LANES) * LANES for d in (dqk, dv))
    acc = 4 * (s * dqk + bk * (dqk + dv))
    blocks = 2 * itemsize * (2 * (bq + bk) * dqk + (bq + 2 * bk) * dv) + 2 * 2 * 8 * bq * 4
    inter = (4 * 4 + 2 * itemsize) * bq * bk
    return max((acc + blocks + inter) * 5 // 4, 16 * 2**20)


def _heads_flat(q, k, v) -> tuple:
    """q, k, v with batch and heads as one leading dim, each at its own head count."""
    return tuple(x.reshape(-1, *x.shape[2:]) for x in (q, k, v))


def _rows_flat(do, o, lse, dlse) -> tuple:
    """(dO with batch and heads as one dim, lse and Δ = rowsum(dO ∘ O) one
    value a row, laid along the lanes: a (1, bq) block of (bh, 1, s)).  An lse
    cotangent (ring attention's online-softmax merge, a block-diffusion loss:
    both consume lse) folds EXACTLY into Δ: with ∂lse/∂s_ij = p_ij,
    ds_ij = p_ij·(dp_ij − Δ_i + dlse_i), so the kernels run unchanged on
    Δ' = Δ − dlse."""
    bh, s, dv = do.shape[0] * do.shape[1], do.shape[2], do.shape[3]
    dof = do.reshape(bh, s, dv)
    delta = jnp.sum(
        dof.astype(jnp.float32) * o.reshape(bh, s, dv).astype(jnp.float32), axis=-1
    )
    if dlse is not None:
        delta = delta - dlse.reshape(bh, s).astype(jnp.float32)
    delta, lse = delta.reshape(bh, 1, s), lse.reshape(bh, 1, s)
    return dof, lse, delta


def _backward_call(kernel, name, steps, q_index, dq_index, like, flat, bq, bk, interpret,
                   scalars=()):
    """Every backward kernel's ``pallas_call``: a (batch·head, key block,
    ``steps``) grid.  ``q_index``: the index map of a step's Q | dO block (and
    of its lse | Δ row), ``dq_index`` of the dQ block it writes; ``like``: q, k,
    v as the caller has them, ``flat``: :func:`_heads_flat`'s and
    :func:`_rows_flat`'s six operands; ``scalars`` as :func:`_forward_call`'s."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v = like
    vma = vma_union(*flat)
    b, h, sq, dqk = q.shape
    h_kv, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    bh, group = b * h, h // h_kv
    row_index = lambda *at: (at[0], 0, q_index(*at)[1])  # noqa: E731
    kv_index = lambda i, j, t, *_: (i, j, 0)  # noqa: E731
    # K and V come in a key/value head, dK and dV go out a query head
    kv_read = _kv_row(kv_index, group)
    grid = dict(
        grid=(bh, sk // bk, steps),
        in_specs=[
            pl.BlockSpec((1, bq, dqk), q_index),
            pl.BlockSpec((1, bk, dqk), kv_read),
            pl.BlockSpec((1, bk, dv), kv_read),
            pl.BlockSpec((1, bq, dv), q_index),
            pl.BlockSpec((1, 1, bq), row_index),
            pl.BlockSpec((1, 1, bq), row_index),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, dqk), dq_index),
            pl.BlockSpec((1, bk, dqk), kv_index),
            pl.BlockSpec((1, bk, dv), kv_index),
        ),
        scratch_shapes=[
            pltpu.VMEM((sq, dqk), jnp.float32),
            pltpu.VMEM((bk, dqk), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
    )
    if scalars:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), **grid))
    dq, dk, dv_ = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bh, sq, dqk), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, sk, dqk), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, sk, dv), v.dtype, vma=vma),
        ),
        compiler_params=pltpu.CompilerParams(
            # dK/dV accumulate along the query blocks and dQ along the key
            # blocks of one (batch·head): only that axis is free
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_bwd_vmem_bytes(sq, bq, bk, dqk, dv, q.dtype.itemsize),
        ),
        interpret=interpret,
        name=name,
        **grid,
    )(*scalars, *flat)
    if group > 1:
        # dK and dV leave the kernel one per QUERY head; a key/value head's is
        # the sum over its group, in the operands' dtype: what the transpose of
        # a caller's ``jnp.repeat`` was
        dk, dv_ = (jnp.sum(x.reshape(b, h_kv, group, sk, x.shape[-1]), axis=2) for x in (dk, dv_))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv_.reshape(v.shape)


def _flash_backward(q, k, v, o, lse, do, causal, scale, bq, bk, interpret,
                    dlse=None, window=None):
    s = q.shape[2]
    nq, nk = s // bq, s // bk
    steps = nq if window is None else _band_steps(s, bq, bk, window)[1]
    q_index = dq_index = _q_index(causal, bq, bk, window, nq)
    if window is None:
        # dQ's block leaves VMEM once: its index stands still until the last
        # key block, whose steps write one query block each.  (At a window it
        # follows the query blocks, each pair writing the running sum.)
        dq_index = lambda i, j, qi: (i, jnp.where(j == nk - 1, qi, 0), 0)  # noqa: E731
    return _backward_call(
        _bwd_kernel_factory(bq, bk, nq, nk, causal, scale, window, steps),
        BWD_KERNEL if window is None else BWD_WIN_KERNEL, steps, q_index, dq_index,
        (q, k, v), _heads_flat(q, k, v) + _rows_flat(do, o, lse, dlse), bq, bk, interpret)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, bq, bk, interpret, window=None):
    out, _ = _flash_forward(q, k, v, causal, scale, bq, bk, interpret, window)
    return out


def _residuals(q, k, v, out, lse):
    """(out, lse a row) under their names, and the residual tuple."""
    out = checkpoint_name(out, SAVED[0])
    lse = checkpoint_name(lse[..., 0], SAVED[1])  # (bh, s): the lanes repeat one value
    return out, lse, (q, k, v, out, lse)


def _flash_fwd(q, k, v, causal, scale, bq, bk, interpret, window=None):
    out, _, res = _residuals(
        q, k, v, *_flash_forward(q, k, v, causal, scale, bq, bk, interpret, window))
    return out, res


def _flash_bwd(causal, scale, bq, bk, interpret, window, res, g):
    q, k, v, o, lse = res
    return _flash_backward(q, k, v, o, lse, g, causal, scale, bq, bk, interpret, window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, causal, scale, bq, bk, interpret, window=None):
    out, lse = _flash_forward(q, k, v, causal, scale, bq, bk, interpret, window)
    return out, lse[..., 0].reshape(q.shape[:3])  # (b, h, s)


def _flash_lse_fwd(q, k, v, causal, scale, bq, bk, interpret, window=None):
    out, lse, res = _residuals(
        q, k, v, *_flash_forward(q, k, v, causal, scale, bq, bk, interpret, window))
    return (out, lse.reshape(q.shape[:3])), res


def _flash_lse_bwd(causal, scale, bq, bk, interpret, window, res, g):
    q, k, v, o, lse = res
    do, dlse = g
    return _flash_backward(
        q, k, v, o, lse, do, causal, scale, bq, bk, interpret, dlse=dlse, window=window
    )


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


#: on-chip sweep artifact written by tools/flash_tune.py; absent until a
#: tune has run on real hardware.  Deliberately committable: every TPU in
#: this deployment is the same generation, so the tuned table ships like
#: any framework's pre-tuned kernel configs (tuned_blocks' divisibility
#: guard keeps foreign sequence lengths on safe defaults).
_TUNED_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "flash_blocks.json")


def _sections(doc: dict) -> dict:
    def pairs(table):
        return {tuple(int(x) for x in k.split(",")): tuple(v)
                for k, v in doc.get(table, {}).items()}

    return {"blocks": {int(k): tuple(v) for k, v in doc["blocks"].items()},
            "banded": pairs("banded"), "block_diffusion": pairs("block_diffusion")}


def _tuned_table() -> dict:
    """The artifact's three tables: ``blocks`` by sequence, ``banded`` by
    (sequence, window), ``block_diffusion`` by (key rows, block length); none
    where the file does not read."""
    return tuned(_TUNED_PATH, _sections)


def tuned_blocks(seq: int, window: Optional[int] = None) -> tuple:
    """Best (block_q, block_k) for this sequence length, from the on-chip
    sweep artifact (tools/flash_tune.py → ops/flash_blocks.json).  Falls
    back to the nearest tuned seq below whose blocks DIVIDE this seq
    (block choice varies slowly with S, and a non-dividing block is an
    error on a TPU), then to (128, 128) — the MXU-aligned safe default.
    Callers passing explicit block sizes bypass this table.

    A banded call (``window``) has a table of its own, keyed by (sequence,
    window): a band is a few blocks wide, so smaller blocks compute less
    outside it.  Where that has no entry that divides, the sequence's."""
    tables = _tuned_table()
    banded = tables.get("banded", {}).get((seq, window))
    if banded and seq % banded[0] == 0 and seq % banded[1] == 0:
        return banded
    table = tables.get("blocks", {})

    def fits(entry) -> bool:
        bq, bk = entry
        return seq % bq == 0 and seq % bk == 0

    if seq in table and fits(table[seq]):
        return table[seq]
    below = [s for s in table if s < seq and fits(table[s])]
    if below:
        return table[max(below)]
    return (128, 128)


def _kernel_path(s: int, bq: int, bk: int, interpret: bool) -> bool:
    """The Pallas kernels (True) or the dense reference (False): the blocks
    must divide the sequence, and the rest is ``_dispatch.kernels_run``'s
    call — the only place either wrapper asks.

    On a TPU the kernels always run: neither ``interpret`` nor anything
    else selects the reference there, and a sequence the blocks do not
    divide raises instead of quietly costing an S×S score matrix.

    Off a TPU (the CPU test harness; Mosaic cannot compile there) the
    dense reference stands in, unless the caller asked for the Pallas
    interpreter and the blocks divide."""
    divides = s % bq == 0 and s % bk == 0
    if not divides and kernels_run(True, interpret=False):  # unasked, kernels run on a TPU alone
        raise ValueError(
            f"flash attention: blocks ({bq}, {bk}) do not divide seq {s}; "
            "pad the sequence or pass block_q/block_k that divide it"
        )
    return kernels_run(divides, interpret)


def _resolve(q, k, v, scale, block_q, block_k, causal=True, window=None):
    """Defaults filled in: (scale, block_q, block_k) for this q."""
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"flash attention: window={window} is a causal band of at least the "
                         "query itself (causal=True, window >= 1)")
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError(f"flash attention: {k.shape[1]} key and {v.shape[1]} value heads must be "
                         f"one count that divides the {q.shape[1]} query heads")
    s, dh = q.shape[2], q.shape[3]
    tq, tk = tuned_blocks(s, window)
    bq = min(block_q if block_q is not None else tq, s)
    bk = min(block_k if block_k is not None else tk, s)
    return (scale if scale is not None else dh**-0.5), bq, bk


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> tuple:
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    ``(b, h, s)`` — the hook ring attention needs to merge per-hop partial
    attention online (o, lse merging is exact: L = logaddexp(L_a, L_b),
    o = o_a·e^{L_a−L} + o_b·e^{L_b−L}).  Differentiable in (q, k, v)
    including the lse output (its cotangent folds into the backward's
    delta term).  ``window``: as :func:`flash_attention`'s."""
    scale, bq, bk = _resolve(q, k, v, scale, block_q, block_k, causal, window)
    if not _kernel_path(q.shape[2], bq, bk, interpret):
        return _dense_reference_lse(q, k, v, causal, scale, window)
    return _flash_lse(q, k, v, causal, scale, bq, bk, interpret, window)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """q: (B, H, S, d_qk), k: (B, H_kv, S, d_qk), v: (B, H_kv, S, d_v) →
    (B, H, S, d_v); the default scale is d_qk**-0.5.  ``H_kv`` divides ``H``:
    query head ``h`` reads key/value head ``h // (H / H_kv)``, found by the
    kernels' index maps (no repeated copy of K or V exists), and dK, dV are
    summed over a head's group of queries.

    Pallas kernels (fwd + blocked bwd) on a TPU, where S must divide by
    the block sizes; what runs elsewhere is :func:`_kernel_path`'s call.
    ``window=W`` (with ``causal=True``; anything else raises): query ``i``
    sees key ``j`` iff ``0 <= i - j < W``, by the banded kernels.
    """
    scale, bq, bk = _resolve(q, k, v, scale, block_q, block_k, causal, window)
    if not _kernel_path(q.shape[2], bq, bk, interpret):
        return _dense_reference(q, k, v, causal, scale, window)
    return _flash(q, k, v, causal, scale, bq, bk, interpret, window)


# ---------------------------------------------------------------------------
# the block-diffusion mask: a noised copy and the clean copy of one sequence
# ---------------------------------------------------------------------------


def _block_of(x, block: int):
    """``x // block`` of non-negative ints, a shift where ``block`` is a power
    of two (what a kernel's vector unit has)."""
    return x >> (block.bit_length() - 1) if block & (block - 1) == 0 else x // block


def block_diffusion_visible(rows, cols, half: int, block: int):
    """Whether query row ``rows`` sees key row ``cols`` (ints that broadcast,
    numpy's or jax's): rows ``[0, half)`` are the noised copy and ``[half,
    2 half)`` the clean one, ``blk(i) = (i mod half) // block``, and a noisy
    query sees the noisy keys of its own block and the clean keys of every
    earlier block; a clean query the clean keys of its own and every earlier
    block.  Written so that what is two-dimensional is three operations: the
    two cases of the key's half are folded into what is compared."""
    nr, nc = rows < half, cols < half
    br = _block_of(rows - half * (1 - nr), block)
    bc = _block_of(cols - half * (1 - nc), block)
    # noisy key: the same block, of a noisy query (−1 | −2 never meet);
    # clean key: a block the query's is past, or has reached if it is clean
    same = (br * nr - (1 - nr)) == (bc * nc - 2 * (1 - nc))
    reached = (br - nr) >= (bc + nc * (1 << 30))
    return same | reached


def _dense_block_diffusion_lse(q, k, v, block, scale):
    """Dense (out, lse) under the block-diffusion mask, from one (s_q, 2L)
    score matrix a head; key/value heads repeated for their groups."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    sq, sk = q.shape[2], k.shape[2]
    seen = block_diffusion_visible(jnp.arange(sq)[:, None], jnp.arange(sk)[None, :],
                                   sk // 2, block)
    s32 = jnp.where(seen, jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale,
                    NEG_INF).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(s32, axis=-1)
    p = jnp.exp(s32 - lse[..., None]).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v), lse


@functools.lru_cache(maxsize=None)
def _bd_tiles(sq: int, half: int, block: int, bq: int, bk: int) -> dict:
    """The table of tiles of a block-diffusion call, from the shapes alone
    (numpy, int32).  ``kv_of`` (nq · steps_f,): query tile ``qi``'s key tiles
    in ascending order at ``[qi · steps_f, …)``, ``n_kv`` (nq,) of them, the
    rest of the row its last one again; ``kv_whole`` beside it: 1 where every
    entry of the pair is visible.  ``q_of``, ``n_q``, ``q_whole`` the same by
    key tile, for the backward kernel; ``first_kv`` (nq,) the first key tile a
    query tile meets.  A pair is listed iff it holds a visible entry —
    decided from the tiles' ranges of blocks in each half, not entry by
    entry — and every tile of either side is listed somewhere (a key tile no
    query sees would leave the backward kernel a block it never writes)."""
    import numpy as np

    def halves(lo, n):  # rows [lo, lo + n) → ranges of blocks (noisy, clean), or None
        hi = lo + n - 1
        noisy = (lo // block, min(hi, half - 1) // block) if lo < half else None
        clean = ((max(lo, half) - half) // block, (hi - half) // block) if hi >= half else None
        return noisy, clean

    def any_all(r, c, rel):  # over a rectangle of block ranges r × c
        if r is None or c is None:
            return False, True  # empty: nothing visible, nothing hidden
        if rel == "same":
            return r[0] <= c[1] and c[0] <= r[1], r[0] == r[1] == c[0] == c[1]
        if rel == "past":
            return r[1] > c[0], r[0] > c[1]
        if rel == "reached":
            return r[1] >= c[0], r[0] >= c[1]
        return False, False  # a clean query and a noisy key

    nq, nk = sq // bq, 2 * half // bk
    needed, whole = np.zeros((nq, nk), bool), np.zeros((nq, nk), bool)
    for qi in range(nq):
        rn, rc = halves(qi * bq, bq)
        for j in range(nk):
            cn, cc = halves(j * bk, bk)
            parts = [any_all(rn, cn, "same"), any_all(rn, cc, "past"),
                     any_all(rc, cn, "never"), any_all(rc, cc, "reached")]
            needed[qi, j] = any(a for a, _ in parts)
            whole[qi, j] = all(w for _, w in parts)
    if not (needed.any(axis=1).all() and needed.any(axis=0).all()):
        raise ValueError(f"flash attention: at tiles ({bq}, {bk}) of a block-diffusion call "
                         f"({sq} queries, 2 x {half} keys, blocks of {block}) some tile meets "
                         "no other; take larger tiles")

    def rows_of(needed, whole):
        steps = int(needed.sum(axis=1).max())
        of = np.zeros((needed.shape[0], steps), np.int32)
        full = np.zeros_like(of)
        for i, row in enumerate(needed):
            at = np.flatnonzero(row)
            of[i, :len(at)], of[i, len(at):] = at, at[-1]
            full[i, :len(at)] = whole[i, at]
        return of.reshape(-1), needed.sum(axis=1).astype(np.int32), full.reshape(-1), steps

    kv_of, n_kv, kv_whole, steps_f = rows_of(needed, whole)
    q_of, n_q, q_whole, steps_b = rows_of(needed.T, whole.T)
    return {"kv_of": kv_of, "n_kv": n_kv, "kv_whole": kv_whole, "steps_f": steps_f,
            "q_of": q_of, "n_q": n_q, "q_whole": q_whole, "steps_b": steps_b,
            "first_kv": kv_of.reshape(nq, steps_f)[:, 0].copy(), "pairs": int(needed.sum())}


@functools.lru_cache(maxsize=None)
def _bd_kinds(sq: int, half: int, block: int, bq: int, bk: int, sub: int, least: float) -> tuple:
    """(``kind`` (nq, nk) int32, the kinds' sub-block maps, whether a partial
    tile is left on the whole-tile path): for every tile
    pair of a block-diffusion call 0 where it takes the whole-tile path, else
    ``i + 1`` for the ``i``-th distinct map of sub-blocks that hold a visible
    entry — made tile by tile from :func:`block_diffusion_visible`, for the
    partial tiles of :func:`_bd_tiles` whose map spares enough.  Two quadrants
    whose tiles list the same sub-blocks are one kind.  No kinds where the tiles
    are not whole sub-blocks or the call has more than ``MAX_KINDS`` of them."""
    import numpy as np

    nq, nk = sq // bq, 2 * half // bk
    kind, maps, rest = np.zeros((nq, nk), np.int32), [], False
    if not _lines_up(bq, bk, sub):
        return kind, (), True
    tiles = _bd_tiles(sq, half, block, bq, bk)
    steps = tiles["steps_f"]
    for qi in range(nq):
        for t in range(int(tiles["n_kv"][qi])):
            j = int(tiles["kv_of"][qi * steps + t])
            if tiles["kv_whole"][qi * steps + t]:
                continue
            rows, cols = np.ogrid[qi * bq:(qi + 1) * bq, j * bk:(j + 1) * bk]
            sub_map = _sub_map(block_diffusion_visible(rows, cols, half, block), sub)
            if not _spares_enough(sub_map, least):
                rest = True
                continue
            same = [i for i, m in enumerate(maps) if np.array_equal(m, sub_map)]
            if not same:
                maps.append(sub_map)
            kind[qi, j] = same[0] + 1 if same else len(maps)
    if len(maps) > MAX_KINDS:
        return np.zeros_like(kind), (), True
    return kind, tuple(maps), rest


def _bd_kind_tables(tiles: dict, sq, half, block, bq, bk) -> dict:
    """``kv_kind`` laid out as ``kv_of`` and ``q_kind`` as ``q_of``, the kinds'
    ``maps`` and whether the masked whole tile is still some pair's body
    (``rest``), at the module's constants; no tables where the call has no kinds."""
    import numpy as np

    kind, maps, rest = _bd_kinds(sq, half, block, bq, bk, SUB_BLOCK, MIN_SPARED)
    if not maps:
        return {"maps": (), "rest": True}
    nq, nk = kind.shape
    return {"kv_kind": kind[np.repeat(np.arange(nq), tiles["steps_f"]), tiles["kv_of"]],
            "q_kind": kind.T[np.repeat(np.arange(nk), tiles["steps_b"]), tiles["q_of"]],
            "maps": maps, "rest": rest}


def _bd_by_kind(needed, whole, kind, kinds: dict, direction: str):
    """(when, masked, rectangles) of a table-driven kernel's pair: the tile
    visible whole, the masked whole tile (where some pair still takes it), and
    one body a kind; ``kind``: a function that reads this pair's from its table."""
    yield needed & whole, False, None  # (a generator: each condition is traced at its branch)
    partial = needed & jnp.logical_not(whole)
    if not kinds["maps"]:
        yield partial, True, None
        return
    kind = kind()
    if kinds["rest"]:
        yield partial & (kind == 0), True, None
    for i, m in enumerate(kinds["maps"]):
        yield needed & (kind == i + 1), True, _rectangles(m, SUB_BLOCK, STRIPS[direction])


def computed_entries(sq: int, sk: int, bq: int, bk: int, window=None, block_length=None,
                     whole_tiles: bool = False) -> tuple:
    """(entries of the score matrix one head's call computes, entries its mask
    keeps), from the shapes alone: a causal call (``sq == sk``), a banded one
    (``window``) or a block-diffusion one (``block_length``; ``sk`` = 2L key
    rows) at tiles of (bq, bk) and the module's constants.  A tile that is
    walked by sub-blocks counts its listed rectangles, any other tile that holds
    a visible entry counts whole; ``whole_tiles`` counts every tile whole (what
    was computed before a tile could be cut).  The forward kernel's list — the
    backward kernel's covers the same sub-blocks."""
    import numpy as np

    def listed(sub_map):
        return int(sub_map.sum()) * SUB_BLOCK ** 2

    if block_length is not None:
        half = sk // 2
        tiles = _bd_tiles(sq, half, block_length, bq, bk)
        kind, maps, _ = _bd_kinds(sq, half, block_length, bq, bk, SUB_BLOCK, MIN_SPARED)
        computed = tiles["pairs"] * bq * bk
        if maps and not whole_tiles:
            computed += sum(int(np.sum(kind == i + 1)) * (listed(m) - bq * bk)
                            for i, m in enumerate(maps))
        blocks = half // block_length  # a noisy query: its block, the clean ones before it
        kept = half * block_length + block_length ** 2 * blocks * (blocks - 1) // 2
        if sq == sk:  # a clean query: its block and the ones before it
            kept += block_length ** 2 * blocks * (blocks + 1) // 2
        return computed, kept
    kinds = {} if whole_tiles else _band_kinds(bq, bk, window, SUB_BLOCK, MIN_SPARED)
    computed = 0
    for qi in range(sq // bq):
        first = 0 if window is None else _first_kv_block(qi, bq, bk, window)
        for j in range(first, _last_kv_block(qi, bq, bk) + 1):
            computed += listed(kinds[qi - j]) if qi - j in kinds else bq * bk
    span = sq if window is None else min(window, sq)  # row i keeps min(i + 1, span) keys
    return computed, span * (span + 1) // 2 + (sq - span) * span


def _bd_keep(qi, j, bq: int, bk: int, half: int, block: int, keys_down: bool = False, rect=None):
    """Bool mask of a tile pair's visible positions: (bq, bk), or (bk, bq)
    with the keys down; rows and columns are one vector each until compared.
    ``rect`` as :func:`_causal_keep`'s."""
    r0, r1, c0, c1 = rect or (0, bq, 0, bk)
    nr, nc = r1 - r0, c1 - c0
    q_shape, k_shape, q_dim = ((1, nr), (nc, 1), 1) if keys_down else ((nr, 1), (1, nc), 0)
    rows = _from(qi * bq, r0) + jax.lax.broadcasted_iota(jnp.int32, q_shape, q_dim)
    cols = _from(j * bk, c0) + jax.lax.broadcasted_iota(jnp.int32, k_shape, 1 - q_dim)
    return block_diffusion_visible(rows, cols, half, block)


def _bd_fwd_kernel_factory(bq, bk, steps, scale, half, block, kinds):
    from jax.experimental import pallas as pl

    def kernel(kv_of, n_kv, kv_whole, *refs):
        (kv_kind,), refs = (refs[:1], refs[1:]) if kinds["maps"] else ((None,), refs)
        q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch = refs
        qi, step = pl.program_id(1), pl.program_id(2)
        at = qi * steps + step
        j = kv_of[at]
        pl.when(step == 0)(functools.partial(_softmax_init, *scratch))
        keep = functools.partial(_bd_keep, qi, j, bq, bk, half, block)
        for when, masked, rects in _bd_by_kind(step < n_kv[qi], kv_whole[at] == 1,
                                               lambda: kv_kind[at], kinds, "fwd"):
            pl.when(when)(functools.partial(
                _softmax_pair, q_ref, k_ref, v_ref, keep if masked else None, scale, *scratch,
                rects=rects))
        pl.when(step == steps - 1)(functools.partial(_softmax_emit, o_ref, lse_ref, *scratch))

    return kernel


def _bd_scalars(tiles: dict, names: tuple, *tensors) -> tuple:
    """The named tables as the kernels' scalar operands, typed as varying as
    the tensors are (under ``shard_map``)."""
    vma = vma_union(*tensors)
    made = (jnp.asarray(tiles[n]) for n in names)
    return tuple(jax.lax.pcast(x, tuple(vma), to="varying") if vma else x for x in made)


def _bd_forward(q, k, v, block, scale, bq, bk, interpret):
    sq, half, group = q.shape[2], k.shape[2] // 2, q.shape[1] // k.shape[1]
    tiles = _bd_tiles(sq, half, block, bq, bk)
    kinds = _bd_kind_tables(tiles, sq, half, block, bq, bk)
    steps = tiles["steps_f"]
    kv_index = lambda i, qi, t, kv_of, *_: (i, kv_of[qi * steps + t], 0)  # noqa: E731
    names = ("kv_of", "n_kv", "kv_whole") + ("kv_kind",) * bool(kinds["maps"])
    return _forward_call(
        _bd_fwd_kernel_factory(bq, bk, steps, scale, half, block, kinds), FWD_BD_KERNEL, steps,
        _kv_row(kv_index, group), q, k, v, bq, bk, interpret,
        _bd_scalars({**tiles, **kinds}, names, q, k, v))


def _bd_bwd_kernel_factory(bq, bk, steps, scale, half, block, kinds):
    from jax.experimental import pallas as pl

    def kernel(q_of, n_q, q_whole, first_kv, *refs):
        (q_kind,), refs = (refs[:1], refs[1:]) if kinds["maps"] else ((None,), refs)
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = refs
        j, step = pl.program_id(1), pl.program_id(2)  # key tile; its step-th query tile
        at = j * steps + step
        qi = q_of[at]
        rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)  # this query tile's of dq_scr
        pl.when(step == 0)(functools.partial(_dkv_init, dk_scr, dv_scr))

        def pair(keep, rects):
            # key tiles ascend: the first this query tile meets
            pl.when(j == first_kv[qi])(functools.partial(_dq_clear, dq_scr, rows, bq))
            _grad_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, keep, scale, rows,
                       dq_scr, dk_scr, dv_scr, rects=rects)
            _dq_write(dq_ref, dq_scr, rows)  # the running sum; a tile's last pair writes it whole

        keep = functools.partial(_bd_keep, qi, j, bq, bk, half, block, keys_down=True)
        for when, masked, rects in _bd_by_kind(step < n_q[j], q_whole[at] == 1,
                                               lambda: q_kind[at], kinds, "bwd"):
            pl.when(when)(functools.partial(pair, keep if masked else None, rects))
        pl.when(step == steps - 1)(functools.partial(_dkv_emit, dk_ref, dv_ref, dk_scr, dv_scr))

    return kernel


def _bd_backward(q, k, v, o, lse, do, block, scale, bq, bk, interpret, dlse=None):
    sq, half = q.shape[2], k.shape[2] // 2
    tiles = _bd_tiles(sq, half, block, bq, bk)
    kinds = _bd_kind_tables(tiles, sq, half, block, bq, bk)
    steps = tiles["steps_b"]
    q_index = lambda i, j, t, q_of, *_: (i, q_of[j * steps + t], 0)  # noqa: E731
    rows = _rows_flat(do, o, lse, dlse)
    names = ("q_of", "n_q", "q_whole", "first_kv") + ("q_kind",) * bool(kinds["maps"])
    scalars = _bd_scalars({**tiles, **kinds}, names, q, k, v, o, lse, do)
    return _backward_call(
        _bd_bwd_kernel_factory(bq, bk, steps, scale, half, block, kinds), BWD_BD_KERNEL, steps,
        q_index, q_index, (q, k, v), _heads_flat(q, k, v) + rows, bq, bk, interpret, scalars)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _bd_flash(q, k, v, block, scale, bq, bk, interpret):
    out, lse = _bd_forward(q, k, v, block, scale, bq, bk, interpret)
    return out, lse[..., 0].reshape(q.shape[:3])


def _bd_flash_fwd(q, k, v, block, scale, bq, bk, interpret):
    out, lse, res = _residuals(q, k, v, *_bd_forward(q, k, v, block, scale, bq, bk, interpret))
    return (out, lse.reshape(q.shape[:3])), res


def _bd_flash_bwd(block, scale, bq, bk, interpret, res, g):
    q, k, v, o, lse = res
    return _bd_backward(q, k, v, o, lse, g[0], block, scale, bq, bk, interpret, dlse=g[1])


_bd_flash.defvjp(_bd_flash_fwd, _bd_flash_bwd)


def tuned_block_diffusion_blocks(keys: int, block_length: int, queries: int) -> tuple:
    """(block_q, block_k) of a block-diffusion call over ``keys`` = 2L key rows:
    the artifact's ``block_diffusion`` entry for (keys, block length) where it
    has one whose tiles divide the queries and the keys, else the half's plain
    entry (:func:`tuned_blocks`, which divides L and so both)."""
    entry = _tuned_table().get("block_diffusion", {}).get((keys, block_length))
    if entry and queries % entry[0] == 0 and keys % entry[1] == 0:
        return entry
    return tuned_blocks(keys // 2)


def block_diffusion_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_length: int,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> tuple:
    """Attention over a noised and a clean copy of one sequence under the
    block-diffusion mask (:func:`block_diffusion_visible`; the module's
    docstring): k (B, H_kv, 2L, d_qk), v (B, H_kv, 2L, d_v), rows ``[0, L)``
    the noised copy's and ``[L, 2L)`` the clean copy's, each copy's row ``i``
    at position ``i`` (the caller's rope); q (B, H, 2L, d_qk), both copies'
    queries, or (B, H, L, d_qk), the noised copy's alone.  ``block_length``
    divides L.  Returns (out (B, H, rows of q, d_v), the per-row logsumexp
    (B, H, rows of q)), differentiable in q, k, v through both.  Heads group
    as :func:`flash_attention`'s.  Kernels ``flash_fwd_bd`` | ``flash_bwd_bd``
    on a TPU, the dense mask elsewhere (:func:`_kernel_path`)."""
    sq, sk = q.shape[2], k.shape[2]
    if sk % 2 or sq not in (sk, sk // 2) or block_length < 1 or (sk // 2) % block_length:
        raise ValueError(f"block diffusion: {sk} key rows are two copies of L tokens, the "
                         f"{sq} queries both copies' or the first's, and the block length "
                         f"{block_length} divides L")
    scale = _resolve(q, k, v, scale, None, None)[0]  # the head counts' check, the default scale
    tq, tk = tuned_block_diffusion_blocks(sk, block_length, sq)
    bq = min(block_q if block_q is not None else tq, sq)
    bk = min(block_k if block_k is not None else tk, sk)
    if not (_kernel_path(sq, bq, bq, interpret) and _kernel_path(sk, bk, bk, interpret)):
        return _dense_block_diffusion_lse(q, k, v, block_length, scale)
    return _bd_flash(q, k, v, block_length, scale, bq, bk, interpret)


def block_diffusion_attention(q, k, v, block_length: int, **kw) -> jax.Array:
    """:func:`block_diffusion_attention_lse`'s output alone."""
    return block_diffusion_attention_lse(q, k, v, block_length, **kw)[0]
