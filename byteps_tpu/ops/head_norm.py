"""Per-head RMSNorm and rotary embedding in one pass each way — what stands
between a q | k projection and the flash kernels in a mixer whose heads are a
lane tile wide.

``head_norm_rope(x, w, eps, theta)``: x (B, H, S, d) as the projection wrote
it → ``w · x / rms(x)`` over a head, turned by its position where ``theta``
is one (``None``: the mixer knows no positions), in x's dtype.  The statistic,
the scale and the rotation are f32 and the result is rounded ONCE.  The
rotation is written over the whole head, ``y · cos + roll(y, d/2) · sin``:
dimension i is paired with i + d/2, both turned by ``pos · theta^(-2i/d)``
(``models/delta_moe.rope_partial`` at the whole head), so both halves hold
the same angles and ``sin`` carries the sign, minus on the first half.

Two implementations behind one ``custom_vjp``, chosen in ONE function
(:func:`_kernel_path`: the shapes here, the platform in
``_dispatch.kernels_run``): on a TPU at head sizes of whole lane tiles two
Pallas kernels, ``head_norm_fwd`` (reads x, writes the flash kernels'
operand) and ``head_norm_bwd`` (reads the cotangent
and x, writes dx and the scale's gradient a query block), each a block's
arithmetic in registers; XLA's form of the same equations everywhere else
(every CPU test) and as the kernels' oracle.  Written as XLA's form alone the
compiled mixer still held an f32 copy of q, its two f32 half heads (the
roll's slices) and a head-dim-major copy (tests/test_tpu_compile.py holds
what it holds now).  The backward pass keeps x and w of the forward and
rebuilds the statistic, one lane reduction a row.

``head_rope(x, d, theta)`` is the pass without the norm, for a mixer whose
heads take positions and no norm (``models/early_route_moe.py``'s sliding
layers): the same tables, the same ``turn``, the same blocks and grid, the
same chooser.  It reads x (B, S, H·d) as the projection's product stands —
token-major, heads side by side, no transposed copy of it — and writes the
flash kernels' head-major operand (``head_rope_fwd``); the backward pass turns
the cotangent by the negative angle, writes it token-major for the
projection's two transposed products and keeps nothing (``head_rope_bwd``):
the change of layout is the blocks' index maps.  Which form a traced call
took is counted, ``head_rope_kernel_traces`` | ``head_rope_xla_traces``
(``bps.get_robustness_counters()``), as ``ops/gated_delta.py`` counts its
rule's: a step that fell back to XLA's form can be told from the registry.

The kernels' grid is (query block, batch·head), the heads innermost: a
block of the (S, d) f32 tables is fetched once a query block and stands
still while the heads pass (Pallas does not copy a block whose index did not
change) — 16 MB of tables a pass, not 16 MB a head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.core.telemetry import counters
from byteps_tpu.ops._dispatch import LANES, kernels_run, vma_union

#: rows of a block: (1024, 128) is 256 kB of bf16 and 512 kB a table
BLOCK_ROWS = 1024
#: f32 sublanes: the scale's gradient leaves the kernel as (8, d) partial sums
SUBLANES = 8

FWD_KERNEL, BWD_KERNEL = "head_norm_fwd", "head_norm_bwd"
#: the norm-less pass: one kernel body, named by the way it turns
ROPE_FWD_KERNEL, ROPE_BWD_KERNEL = "head_rope_fwd", "head_rope_bwd"


def rope_tables(s: int, d: int, theta: float):
    """(cos, sin) (S, d) f32 of the rotation written over the whole head."""
    half = d // 2
    freqs = jnp.asarray(theta, jnp.float32) ** (-jnp.arange(half, dtype=jnp.float32) * 2 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)


def _block_rows(s: int) -> int:
    return min(BLOCK_ROWS, s)


def _kernel_path(s: int, d: int, interpret: bool) -> bool:
    """The Pallas kernels (True) or XLA's form (False).  The kernels take
    heads of whole lane tiles and a sequence of whole blocks; where they fit,
    ``_dispatch.kernels_run`` decides."""
    fits = d % LANES == 0 and s % _block_rows(s) == 0 and _block_rows(s) % SUBLANES == 0
    return kernels_run(fits, interpret)


# ---------------------------------------------------------------------------
# the equations, on f32 values: XLA's form whole, a kernel's on one block
# ---------------------------------------------------------------------------


def turn(y, cos, sin, roll):
    return y * cos + roll(y) * sin


def _forward(x32, w, eps, tables, roll):
    r = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    y = x32 * r * w
    return y if tables is None else turn(y, *tables, roll)


def _backward(g32, x32, w, eps, tables, roll):
    """(dx, g · n before any sum): ``g`` turned back (the rotation's
    transpose is the rotation by the negative angle), then the norm's."""
    if tables is not None:
        g32 = turn(g32, tables[0], -tables[1], roll)
    r = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    n, dn = x32 * r, g32 * w
    return r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True)), g32 * n


def xla_roll(y):
    return jnp.roll(y, y.shape[-1] // 2, axis=-1)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _lane_roll(y):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(y, shift=y.shape[-1] // 2, axis=y.ndim - 1)


def _specs(bh, s, d, rows, turned):
    """(grid, a (1, rows, d) block of a (bh, s, d) array, the scale's block,
    the tables' blocks): query blocks outermost, heads innermost."""
    from jax.experimental import pallas as pl

    block = pl.BlockSpec((1, rows, d), lambda qi, i: (i, qi, 0))
    scale = pl.BlockSpec((1, d), lambda qi, i: (0, 0))
    tables = [pl.BlockSpec((rows, d), lambda qi, i: (qi, 0))] * (2 if turned else 0)
    return (s // rows, bh), block, scale, tables


def _forward_kernels(x, w, eps, tables, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = x.shape
    grid, block, scale, table_specs = _specs(b * h, s, d, _block_rows(s), tables is not None)

    def kernel(x_ref, w_ref, *refs):
        *table_refs, y_ref = refs
        held = tuple(t[...] for t in table_refs) or None
        y_ref[0] = _forward(x_ref[0].astype(jnp.float32), w_ref[...], eps, held,
                            _lane_roll).astype(y_ref.dtype)

    y = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), x.dtype, vma=vma_union(x, w)),
        grid=grid,
        in_specs=[block, scale, *table_specs],
        out_specs=block,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=FWD_KERNEL,
    )(x.reshape(b * h, s, d), w.reshape(1, d).astype(jnp.float32), *(tables or ()))
    return y.reshape(x.shape)


def _backward_kernels(g, x, w, eps, tables, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = x.shape
    rows = _block_rows(s)
    grid, block, scale, table_specs = _specs(b * h, s, d, rows, tables is not None)

    def kernel(g_ref, x_ref, w_ref, *refs):
        *table_refs, dx_ref, dw_ref = refs
        held = tuple(t[...] for t in table_refs) or None
        dx, gn = _backward(g_ref[0].astype(jnp.float32), x_ref[0].astype(jnp.float32),
                           w_ref[...], eps, held, _lane_roll)
        dx_ref[0] = dx.astype(dx_ref.dtype)

        @pl.when(pl.program_id(1) == 0)
        def _clear():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        # a query block's rows added sublane tile on sublane tile: no sum
        # across sublanes in the kernel, the (8, d) that is left is XLA's
        dw_ref[0] = dw_ref[0] + jnp.sum(gn.reshape(rows // SUBLANES, SUBLANES, d), axis=0)

    vma = vma_union(g, x, w)
    dx, dw = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b * h, s, d), x.dtype, vma=vma),
                   jax.ShapeDtypeStruct((s // rows, SUBLANES, d), jnp.float32, vma=vma)),
        grid=grid,
        in_specs=[block, block, scale, *table_specs],
        out_specs=(block, pl.BlockSpec((1, SUBLANES, d), lambda qi, i: (qi, 0, 0))),
        compiler_params=pltpu.CompilerParams(
            # the scale's gradient of a query block accumulates along the heads
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=BWD_KERNEL,
    )(g.reshape(b * h, s, d), x.reshape(b * h, s, d), w.reshape(1, d).astype(jnp.float32),
      *(tables or ()))
    return dx.reshape(x.shape), jnp.sum(dw, axis=(0, 1))


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


def _tables(x, theta):
    return None if theta is None else rope_tables(x.shape[-2], x.shape[-1], theta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _head_norm_rope(x, w, eps, theta, interpret):
    return _fwd(x, w, eps, theta, interpret)[0]


def _fwd(x, w, eps, theta, interpret):
    if _kernel_path(x.shape[-2], x.shape[-1], interpret):
        y = _forward_kernels(x, w, eps, _tables(x, theta), interpret)
    else:
        y = _forward(x.astype(jnp.float32), w, eps, _tables(x, theta), xla_roll).astype(x.dtype)
    return y, (x, w)


def _bwd(eps, theta, interpret, res, g):
    x, w = res
    if _kernel_path(x.shape[-2], x.shape[-1], interpret):
        dx, dw = _backward_kernels(g, x, w, eps, _tables(x, theta), interpret)
    else:
        dx, gn = _backward(g.astype(jnp.float32), x.astype(jnp.float32), w, eps,
                           _tables(x, theta), xla_roll)
        dx, dw = dx.astype(x.dtype), jnp.sum(gn, axis=tuple(range(gn.ndim - 1)))
    return dx, dw.astype(w.dtype)


_head_norm_rope.defvjp(_fwd, _bwd)


def head_norm_rope(x, w, eps: float, theta=None, interpret: bool = False):
    """x (B, H, S, d), w (d,) → ``w · x / rms(x)`` over a head, turned by its
    position where ``theta`` is given, in x's dtype; differentiable in x and
    w.  What runs where is :func:`_kernel_path`'s call."""
    if x.shape[-1] % 2:
        raise ValueError(f"a head of {x.shape[-1]} has no halves to pair")
    # under shard_map the scale is replicated and x varies: the scale's
    # cotangent is then summed over x's axes by this cast's transpose
    need = tuple(jax.typeof(x).vma - jax.typeof(w).vma)
    if need:
        w = lax.pcast(w, need, to="varying")
    return _head_norm_rope(x, w, eps, theta, interpret)


# ---------------------------------------------------------------------------
# the rotation alone: a head that takes positions and no norm
# ---------------------------------------------------------------------------


def _turn_kernel(x, d, tables, back, interpret):
    """``turn`` on the norm's blocks and grid, and the change of layout in
    the blocks' index maps: forth it reads a head's columns of the
    token-major x (B, S, H·d) and writes them head-major (B·H, S, d), back
    the other way."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s = x.shape[:3] if back else (x.shape[0], x.shape[2] // d, x.shape[1])
    grid, heads, _, table_specs = _specs(b * h, s, d, _block_rows(s), True)
    tokens = pl.BlockSpec((1, _block_rows(s), d), lambda qi, i: (i // h, qi, i % h))

    def kernel(x_ref, cos_ref, sin_ref, y_ref):
        y_ref[0] = turn(x_ref[0].astype(jnp.float32), cos_ref[...], sin_ref[...],
                         _lane_roll).astype(y_ref.dtype)

    y = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d) if back else (b * h, s, d), x.dtype,
                                       vma=vma_union(x)),
        grid=grid,
        in_specs=[heads if back else tokens, *table_specs],
        out_specs=tokens if back else heads,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=ROPE_BWD_KERNEL if back else ROPE_FWD_KERNEL,
    )(x.reshape(b * h, s, d) if back else x, *tables)
    return y if back else y.reshape(b, h, s, d)


def _turned(x, d, theta, back, kernels, interpret):
    """Forth: x (B, S, H·d) turned by its positions, head-major (B, H, S,
    d).  Back: a head-major cotangent turned by the negative angles — the
    rotation's transpose — token-major.  f32 inside, rounded once."""
    b, s = x.shape[0], x.shape[2 if back else 1]
    cos, sin = rope_tables(s, d, theta)
    tables = (cos, -sin) if back else (cos, sin)
    if kernels:
        return _turn_kernel(x, d, tables, back, interpret)
    x = x if back else jnp.swapaxes(x.reshape(b, s, -1, d), 1, 2)
    y = turn(x.astype(jnp.float32), *tables, xla_roll).astype(x.dtype)
    return jnp.swapaxes(y, 1, 2).reshape(b, s, -1) if back else y


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _head_rope(x, d, theta, kernels, interpret):
    return _rope_fwd(x, d, theta, kernels, interpret)[0]


def _rope_fwd(x, d, theta, kernels, interpret):
    return _turned(x, d, theta, False, kernels, interpret), None


def _rope_bwd(d, theta, kernels, interpret, _, g):
    return (_turned(g, d, theta, True, kernels, interpret),)


_head_rope.defvjp(_rope_fwd, _rope_bwd)


def head_rope(x, d: int, theta: float, interpret: bool = False):
    """x (B, S, H·d), heads of ``d`` side by side as a projection's product
    stands → each head turned by its position over the whole head (what
    :func:`head_norm_rope` does after its norm), head-major (B, H, S, d) as
    the flash kernels read it, in x's dtype.  The backward pass turns the
    cotangent back, writes it token-major and keeps nothing.  What runs where
    is :func:`_kernel_path`'s call, made once a traced call and counted:
    ``head_rope_kernel_traces`` | ``head_rope_xla_traces``
    (``bps.get_robustness_counters()``)."""
    if d % 2 or x.shape[-1] % d:
        raise ValueError(f"{x.shape[-1]} columns are no heads of {d} with halves to pair")
    kernels = _kernel_path(x.shape[1], d, interpret)
    counters().bump("head_rope_kernel_traces" if kernels else "head_rope_xla_traces")
    return _head_rope(x, d, theta, kernels, interpret)
