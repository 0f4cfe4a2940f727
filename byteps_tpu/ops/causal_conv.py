"""The depthwise causal convolution before a linear mixer, with what always
follows it — a bias, a silu and, for the gated delta rule's q and k, a head's
l2 norm — in one pass each way over the channels it is taken from.

``conv_silu(wide, taps, bias, lo=, hi=, l2_head=, scale=)``: ``wide``
(B, S, W) is a projection's whole output (``qkvz``, ``zxbcdt``, ``xz``) and
the result (B, S, hi − lo), in ``wide``'s dtype, is

    y_t = silu(Σ_j taps[j] · wide[t − K + 1 + j, lo:hi] + bias),

zeros before the sequence's start (``moe_family.causal_conv``: the last tap
weighs the present token); with ``l2_head = d`` every run of d channels is
then divided by its l2 norm (:func:`inv_l2`) and multiplied by ``scale``.
Every product, the silu, the statistic and the gradients' sums over the tokens
are f32, and the result is rounded ONCE.

Two implementations of that one algorithm, chosen by :func:`_kernel_path` from
what the code can observe (the shapes here, the platform in
``_dispatch.kernels_run``).  On a TPU, at column ranges and heads of whole lane
tiles and a sequence of whole row blocks, two Pallas kernels behind a
``custom_vjp`` that keeps ``wide``, the taps and the bias and nothing else:

``conv_silu_fwd`` — grid (batch, lane blocks of ``[lo, hi)``, row blocks).  A
block's rows and, as a second block of the same operand at the rows before
them, its halo (a sublane tile, zeros at the sequence's start) stand one under
the other in VMEM scratch; the body takes them a chunk of rows and a strip of
lanes (a head, or a lane tile) at a time, the chunks written out one after
another: f32 copy, the K − 1 shifts as sublane rolls of it, products, silu,
the statistic, one store.  The column range is an offset in the blocks' index
map: no slice of ``wide`` is ever written.

``conv_silu_bwd`` — the same grid, the row blocks its sequential axis, the
halo on both sides (of ``wide``, and after the block of dy: zeros past the
sequence's end).  A chunk rebuilds the pre-activation over its rows and the
sublane tile after them, takes the cotangent through the norm and the silu,
writes dx (the anti-causal convolution: K − 1 rows of look-ahead) and adds its
part of the taps' and the bias' gradients into f32 output blocks that stand
still along the sequence — eight partial rows each, so that no sum crosses
sublanes in the kernel; XLA adds the eight (and the batch).  dx comes back
through ``lax.pad`` to ``wide``'s width, the transpose of the slice never
taken, which XLA fuses into the transposed projection's operand: no padded
cotangent is written either.

Everywhere else — every CPU test, the toy widths, and as the kernels' oracle
— XLA's form: ``causal_conv`` of the sliced columns, silu, the statistic on the
tiles' view (:func:`per_head`), differentiated by autodiff.  A traced call
bumps ``conv_kernel_traces`` or ``conv_xla_traces``
(``bps.get_robustness_counters()``), as the scans' modules count theirs.

The families differ in parameters alone — a bias or none, a norm or none,
which columns — and no family is named here.  ``causal_conv`` itself stays in
``models/moe_family.py``, where the short-convolution family's mixer (three
taps between two gates, no silu) takes it as it is.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.core.telemetry import counters
from byteps_tpu.models.moe_family import causal_conv
from byteps_tpu.ops._dispatch import LANES, kernels_run, vma_union

#: rows and lanes of a block (fewer where they do not divide the operand):
#: tools/gdn_tune.py --conv sweeps them on the chip
ROW_BLOCK, LANE_BLOCK = 512, 512
#: rows of a block that a kernel's body takes at a time — the chunks written
#: out one after another, so that a chunk's f32 values are short-lived and the
#: compiler has several chunks' chains to interleave
CHUNK_ROWS = 256
#: f32 sublanes: the taps' and the bias' gradients leave the backward kernel
#: as (8, lanes) partial sums
SUBLANES = 8

CONV_FWD_KERNEL, CONV_BWD_KERNEL = "conv_silu_fwd", "conv_silu_bwd"


# ---------------------------------------------------------------------------
# XLA's form
# ---------------------------------------------------------------------------


def inv_l2(x, eps: float = 1e-6):
    """1 / ‖x‖ over the last dim, kept."""
    return lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def head_tiles(x, n: int):
    """x (B, S, n·d) → (B, S/8, n, 8, d): a head's lanes last, eight tokens
    above them (fewer where 8 does not divide S).  That is the (8, 128) tile a
    token-major array already lies in on a TPU, so the view moves nothing and
    a reduction over d stays inside a tile; (B, S, n, d) is another layout
    there and costs a copy each way (PERF.md §6, PR 45)."""
    b, s, c = x.shape
    rows = math.gcd(s, SUBLANES)
    return x.reshape(b, s // rows, rows, n, c // n).transpose(0, 1, 3, 2, 4)


def tokens(t):
    """:func:`head_tiles` back: (B, S/r, n, r, d) → (B, S, n·d)."""
    b, m, n, rows, d = t.shape
    return t.transpose(0, 1, 3, 2, 4).reshape(b, m * rows, n * d)


def per_head(x, n: int, stat):
    """``stat`` (over the last dim, kept) of each of the n heads of x
    (B, S, n·d), on every lane of its head: (B, S, n·d)."""
    tiles = head_tiles(x, n)
    return tokens(jnp.broadcast_to(stat(tiles), tiles.shape))


def _xla_form(wide, taps, bias, lo, hi, l2_head, scale):
    u = causal_conv(wide[..., lo:hi], taps)
    a = jax.nn.silu(u if bias is None else u + bias)
    if l2_head:
        a = a * per_head(a, (hi - lo) // l2_head, inv_l2) * scale
    return a.astype(wide.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


class _Blocks(NamedTuple):
    """What the two kernels are built from, all static."""
    lo: int
    hi: int
    l2_head: Optional[int]
    scale: float
    rows: int  # of a block
    lanes: int  # of a block
    chunk: int  # rows the body takes at a time
    halo: int  # rows of the block before | after: a sublane tile of the dtype
    interpret: bool


def _halo_rows(dtype) -> int:
    """A sublane tile of ``dtype``: 8 rows of f32, 16 of bf16."""
    return SUBLANES * max(4 // jnp.dtype(dtype).itemsize, 1)


def _fit(size: int, block: int, unit: int) -> int:
    """The largest multiple of ``unit`` that divides ``size`` and is no more
    than ``block`` (``unit`` itself where ``block`` is under it)."""
    return max((m for m in range(unit, max(block, unit) + 1, unit) if size % m == 0),
               default=0)


def _blocks(wide, taps, lo, hi, l2_head, scale, interpret, blocks=None) -> Optional[_Blocks]:
    """The kernels' blocks at these shapes, or None where the kernels do not
    tile them: the column range and the head whole lane tiles, the sequence
    whole row blocks of whole sublane tiles, the taps' reach inside one."""
    rows, lanes, chunk = blocks or (ROW_BLOCK, LANE_BLOCK, CHUNK_ROWS)
    s, halo = wide.shape[1], _halo_rows(wide.dtype)
    unit = l2_head or LANES
    if lo % LANES or (hi - lo) % unit or unit % LANES or taps.shape[0] - 1 > SUBLANES:
        return None
    rows = _fit(s, rows, halo)
    lanes = _fit(math.gcd(lo, hi - lo), lanes, unit)
    if not rows or not lanes:
        return None
    return _Blocks(lo, hi, l2_head, float(scale), rows, lanes, _fit(rows, chunk, halo), halo,
                   interpret)


def _kernel_path(blocks: Optional[_Blocks], interpret: bool) -> bool:
    """The Pallas kernels (True) or XLA's form (False): where the kernels
    tile the shapes (:func:`_blocks`), ``_dispatch.kernels_run`` decides."""
    return kernels_run(blocks is not None, interpret)


def _shifted(ext, back: int, first: int, n: int):
    """Rows ``[first − back, first − back + n)`` of the f32 ``ext``: a roll
    along the sublanes, then a slice at whole tiles (``first`` is one)."""
    from jax.experimental.pallas import tpu as pltpu

    shift = back % ext.shape[0]
    if shift:
        ext = pltpu.roll(ext, shift=shift, axis=0)
    return ext[first:first + n]


def _activation(shifts, taps, bias):
    """(u, σ(u)) of the pre-activation ``u = Σ_j taps[j] · shifts[j] + bias``."""
    u = sum(taps[j:j + 1] * x for j, x in enumerate(shifts))
    if bias is not None:
        u = u + bias
    return u, jax.nn.sigmoid(u)


def _strips(blocks: _Blocks):
    """The lane ranges a kernel's body takes one at a time: a head where
    heads are normed, a lane tile else."""
    width = blocks.l2_head or LANES
    return [slice(c, c + width) for c in range(0, blocks.lanes, width)]


def _specs(blocks: _Blocks, b: int, s: int, k: int, with_bias: bool):
    """(grid, the block of ``wide``, its halo before, its halo after, the
    block of a (B, S, hi − lo) array, its halo after, the taps' and the bias'
    blocks).  Grid (batch, lane block, row block): the rows innermost, so the
    taps and the backward kernel's sums stand still along the sequence."""
    from jax.experimental import pallas as pl

    rows, lanes, halo = blocks.rows, blocks.lanes, blocks.halo
    first, per, last = blocks.lo // lanes, rows // halo, s // halo - 1

    def spec(height, row, offset=0):
        return pl.BlockSpec((1, height, lanes), lambda i, c, r: (i, row(r), offset + c))

    before = lambda r: jnp.maximum(r * per - 1, 0)  # noqa: E731
    after = lambda r: jnp.minimum((r + 1) * per, last)  # noqa: E731
    small = [pl.BlockSpec((height, lanes), lambda i, c, r: (0, c))
             for height in (k, 1)[:2 if with_bias else 1]]
    return ((b, (blocks.hi - blocks.lo) // lanes, s // rows),
            spec(rows, lambda r: r, first), spec(halo, before, first), spec(halo, after, first),
            spec(rows, lambda r: r), spec(halo, after), small)


def _stage(scratch, *pieces):
    """The ``(ref, absent)`` pieces — a block and the halos beside it — one
    under another along the rows of the VMEM ``scratch``, so that every chunk
    of the block reads its neighbours' rows the same way.  A halo stands as
    zeros where ``absent`` (a traced bool; None: never) says that nothing
    lies beyond the sequence's edge there."""
    at = 0
    for ref, absent in pieces:
        rows, piece = slice(at, at + ref.shape[1]), ref[0]
        if absent is not None:
            piece = jnp.where(absent, 0.0, piece.astype(jnp.float32)).astype(piece.dtype)
        scratch[rows] = piece
        at = rows.stop


@functools.partial(jax.jit, static_argnames="blocks")
def _forward_kernels(wide, taps, bias, blocks: _Blocks):
    # (a jit of its own: a step's layers call the same kernel at the same
    # shapes, and a body written out in pieces is traced and lowered ONCE a
    # program so — every run pays that before the compile cache is asked)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, s, _ = wide.shape
    k, rows, lanes = taps.shape[0], blocks.rows, blocks.lanes
    chunk, halo = blocks.chunk, blocks.halo
    grid, block, before, _, own, _, small = _specs(blocks, b, s, k, bias is not None)

    def kernel(before_ref, x_ref, taps_ref, *refs):
        *bias_ref, y_ref, x_rows = refs
        # zeros before the sequence's start
        _stage(x_rows, (before_ref, pl.program_id(2) == 0), (x_ref, None))
        for strip in _strips(blocks):
            w = taps_ref[:, strip]
            bias_row = bias_ref[0][:, strip] if bias_ref else None
            for at in range(0, rows, chunk):
                # the chunk and the sublane tile before it, f32
                ext = x_rows[at:at + chunk + halo, strip].astype(f32)[halo - SUBLANES:]
                u, sig = _activation(
                    [_shifted(ext, k - 1 - j, SUBLANES, chunk) for j in range(k)], w, bias_row)
                a = u * sig
                if blocks.l2_head:
                    a = a * inv_l2(a) * blocks.scale
                y_ref[0, at:at + chunk, strip] = a.astype(y_ref.dtype)

    operands = [wide, wide, taps] + ([bias.reshape(1, -1)] if bias is not None else [])
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, s, blocks.hi - blocks.lo), wide.dtype,
                                       vma=vma_union(*operands)),
        grid=grid,
        in_specs=[before, block, *small],
        out_specs=own,
        scratch_shapes=[pltpu.VMEM((halo + rows, lanes), wide.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=blocks.interpret,
        name=CONV_FWD_KERNEL,
    )(*operands)


@functools.partial(jax.jit, static_argnames="blocks")
def _backward_kernels(dy, wide, taps, bias, blocks: _Blocks):
    """(dx (B, S, hi − lo) in ``wide``'s dtype, dtaps (K, hi − lo) f32,
    dbias (hi − lo,) f32 or None)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, s, _ = wide.shape
    k, rows, lanes = taps.shape[0], blocks.rows, blocks.lanes
    chunk, halo = blocks.chunk, blocks.halo
    n = chunk + SUBLANES  # a chunk and the sublane tile after it
    grid, block, before, after, own, own_after, small = _specs(blocks, b, s, k, bias is not None)

    def kernel(before_ref, x_ref, after_ref, dy_ref, dy_after_ref, taps_ref, *refs):
        bias_ref, (dx_ref, dtaps_ref, *dbias_ref) = refs[:len(small) - 1], refs[len(small) - 1:-2]
        x_rows, dy_rows = refs[-2:]
        r = pl.program_id(2)
        _stage(x_rows, (before_ref, r == 0), (x_ref, None), (after_ref, None))
        # past the sequence's end no cotangent comes back
        _stage(dy_rows, (dy_ref, None), (dy_after_ref, r == grid[2] - 1))

        @pl.when(r == 0)
        def _clear():
            for sums in (dtaps_ref, *dbias_ref):
                sums[...] = jnp.zeros_like(sums)

        def tile_sums(x):
            # a chunk's rows added sublane tile on sublane tile: no sum across
            # sublanes in the kernel, the eight that are left are XLA's
            return jnp.sum(x.reshape(chunk // SUBLANES, SUBLANES, -1), axis=0)

        for strip in _strips(blocks):
            w = taps_ref[:, strip]
            bias_row = bias_ref[0][:, strip] if bias_ref else None
            sums = [0.0] * (k + len(dbias_ref))  # of d taps[j], then of d bias
            for at in range(0, rows, chunk):
                # the chunk between the sublane tiles before and after it, f32
                ext = x_rows[at:at + chunk + 2 * halo, strip].astype(f32)[
                    halo - SUBLANES:halo + n]
                shifts = [_shifted(ext, k - 1 - j, SUBLANES, n) for j in range(k)]
                u, sig = _activation(shifts, w, bias_row)
                g = dy_rows[at:at + chunk + halo, strip].astype(f32)[:n]
                if blocks.l2_head:
                    # y = scale · a / ‖a‖: da = scale · (g − a Σ g a / ‖a‖²) / ‖a‖
                    a = u * sig
                    inv = inv_l2(a)
                    g = blocks.scale * inv * (
                        g - a * jnp.square(inv) * jnp.sum(g * a, axis=-1, keepdims=True))
                du = g * sig * (1.0 + u * (1.0 - sig))
                # dx_t = Σ_j taps[j] · du[t + K − 1 − j]
                dx = sum(w[j:j + 1] * _shifted(du, j + 1 - k, 0, chunk) for j in range(k))
                dx_ref[0, at:at + chunk, strip] = dx.astype(dx_ref.dtype)
                here = du[:chunk]
                parts = [here * x[:chunk] for x in shifts] + [here] * len(dbias_ref)
                sums = [acc + tile_sums(part) for acc, part in zip(sums, parts)]
            for j in range(k):
                dtaps_ref[0, j, :, strip] += sums[j]
            if dbias_ref:
                dbias_ref[0][0, :, strip] += sums[k]

    operands = [wide, wide, wide, dy, dy, taps] + (
        [bias.reshape(1, -1)] if bias is not None else [])
    vma = vma_union(*operands)
    c = blocks.hi - blocks.lo
    sums = [(jax.ShapeDtypeStruct((b, k, SUBLANES, c), f32, vma=vma),
             pl.BlockSpec((1, k, SUBLANES, lanes), lambda i, c, r: (i, 0, 0, c)))]
    if bias is not None:
        sums.append((jax.ShapeDtypeStruct((b, SUBLANES, c), f32, vma=vma),
                     pl.BlockSpec((1, SUBLANES, lanes), lambda i, c, r: (i, 0, c))))
    dx, dtaps, *dbias = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, s, c), wide.dtype, vma=vma),
                   *(shape for shape, _ in sums)),
        grid=grid,
        in_specs=[before, block, after, own, own_after, *small],
        out_specs=(own, *(spec for _, spec in sums)),
        scratch_shapes=[pltpu.VMEM((rows + 2 * halo, lanes), wide.dtype),
                        pltpu.VMEM((rows + halo, lanes), dy.dtype)],
        compiler_params=pltpu.CompilerParams(
            # the taps' and the bias' sums accumulate along the sequence
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=blocks.interpret,
        name=CONV_BWD_KERNEL,
    )(*operands)
    return dx, jnp.sum(dtaps, axis=(0, 2)), jnp.sum(dbias[0], axis=(0, 1)) if dbias else None


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu(wide, taps, bias, blocks: _Blocks):
    return _fwd(wide, taps, bias, blocks)[0]


def _fwd(wide, taps, bias, blocks):
    return _forward_kernels(wide, taps, bias, blocks), (wide, taps, bias)


def _bwd(blocks, res, dy):
    wide, taps, bias = res
    dx, dtaps, dbias = _backward_kernels(dy, wide, taps, bias, blocks)
    # the transpose of the slice that was never taken
    edges = ((0, 0, 0), (0, 0, 0), (blocks.lo, wide.shape[-1] - blocks.hi, 0))
    return (lax.pad(dx, jnp.zeros((), dx.dtype), edges), dtaps.astype(taps.dtype),
            None if bias is None else dbias.astype(bias.dtype))


_conv_silu.defvjp(_fwd, _bwd)


def conv_silu(wide, taps, bias=None, *, lo: int, hi: int, l2_head: Optional[int] = None,
              scale: float = 1.0, interpret: bool = False, blocks=None):
    """``wide`` (B, S, W), taps (K, hi − lo) f32, bias (hi − lo,) or None →
    ``silu(causal_conv(wide[..., lo:hi], taps) + bias)`` (B, S, hi − lo) in
    ``wide``'s dtype; with ``l2_head = d`` each run of d channels divided by
    its l2 norm and multiplied by ``scale``.  Differentiable in ``wide``, the
    taps and the bias.  Which implementation runs is :func:`_kernel_path`'s
    call, made once a traced call and counted; ``interpret`` asks for the
    Pallas interpreter off a TPU (the CPU tests), ``blocks`` overrides
    (:data:`ROW_BLOCK`, :data:`LANE_BLOCK`, :data:`CHUNK_ROWS`)."""
    if not 0 <= lo < hi <= wide.shape[-1] or taps.shape[1] != hi - lo or (
            l2_head and (hi - lo) % l2_head):
        raise ValueError(f"columns [{lo}, {hi}) of {wide.shape[-1]}, taps {taps.shape}, heads "
                         f"of {l2_head}: no such convolution")
    fit = _blocks(wide, taps, lo, hi, l2_head, scale, interpret, blocks)
    if not _kernel_path(fit, interpret):
        counters().bump("conv_xla_traces")
        return _xla_form(wide, taps, bias, lo, hi, l2_head, scale)
    counters().bump("conv_kernel_traces")
    f32 = jnp.float32
    taps, bias = taps.astype(f32), None if bias is None else bias.astype(f32)
    # under shard_map the taps and the bias are replicated and wide varies:
    # their cotangents are then summed over wide's axes by this cast's transpose
    need = tuple(jax.typeof(wide).vma - jax.typeof(taps).vma)
    if need:
        taps, bias = (None if t is None else lax.pcast(t, need, to="varying")
                      for t in (taps, bias))
    return _conv_silu(wide, taps, bias, fit)
