"""From a latent-attention mixer's token-major products to the flash kernels'
head-major operands, and back, in one pass each way.

``mla_heads(q_nope, q_rope, k_nope, v, k_rope, n_heads, theta)``: the four
products as they are written, heads side by side along the lanes — q_nope
(B, S, H·n), q_rope (B, S, H·r), k_nope (B, S, H·n), v (B, S, H·d_v) — and the
one rotary key a token, k_rope (B, S, r) → q (B, H, S, n + r) = [a head's part
without positions | its rotary part turned by its position], k (B, H, S,
n + r) = [a head's part | the shared rotary key turned, the same for every
head] and v (B, H, S, d_v): what ``flash_attention`` reshapes to (B·H, S, ·)
for free.  The rotation is f32 and the result is rounded ONCE; the other
columns are moved as they are.

**The rotary columns come even-first.**  The configuration's rope turns the
adjacent pairs (2i, 2i+1) by ``pos · theta^(-2i/r)``
(``models/latent_moe_reference._rope``).  This pass turns a PERMUTED copy:
the r rotary columns of a head ordered [0, 2, 4, … | 1, 3, 5, …]
(:func:`even_first`, applied by the caller to the WEIGHTS' columns that make
q_rope and k_rope), so that pair i is columns (i, i + r/2) and the turn is
``y · cos + roll(y, r/2) · sin`` with the sign in ``sin`` — the half-split
form ``ops/head_norm.py`` turns.  q's and k's rotary columns take the same
permutation and a score is a dot product over them, which does not see a
common permutation: the scores, and so the attention's output, are the
interleaved rope's (tests/test_latent_moe_pieces.py holds both statements).

Two implementations behind one ``custom_vjp``, chosen in ONE function
(:func:`_kernel_path`: the shapes here, the platform in
``_dispatch.kernels_run``): on a TPU at heads of
[one lane tile | half a lane tile] and values of one lane tile, two Pallas
kernels — ``mla_heads_fwd`` reads a block of each product by index map and
writes the three operands, ``mla_heads_bwd`` is its transpose: reads dq | dk |
dv as the backward flash kernel wrote them, turns the rotary parts back, sums
dk's rotary part over the heads in an f32 accumulator and writes the five
token-major cotangents —; XLA's form of the same equations everywhere else
(every CPU test) and as the kernels' oracle.  The pass is linear in its five
inputs, so its backward pass keeps nothing.

The kernels' grid is (query block, batch · head pair), the pairs innermost:
two heads' rotary columns are one lane tile, and the rotary key's block and
the (S, 128) f32 tables are fetched once a query block and stand still while
the heads pass (Pallas does not copy a block whose index did not change).

**A configuration without positions** (``theta`` None) takes the same pass
with tables of cos 1 and sin 0 (:func:`_tables`): the turn by no angle is the
identity, q is ``[q_nope | q_rope]`` and k ``[k_nope | the shared key]`` as
they came, and the tables are data — no second pass, no branch in a kernel.

``merge_heads(o, wo)`` is the way out: the output projection on the kernels'
head-major o, with a transpose that writes dO head-major at once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.ops._dispatch import LANES, kernels_run, vma_union
from byteps_tpu.ops.head_norm import rope_tables, turn, xla_roll

#: rows of a block: a pair of heads is (rows, 2 · 192) of bf16 each of q and k
BLOCK_ROWS = 512
#: bf16 rows of a tile
SUBLANES = 16

FWD_KERNEL, BWD_KERNEL = "mla_heads_fwd", "mla_heads_bwd"


def even_first(w):
    """``w``'s last axis ordered [0, 2, 4, … | 1, 3, 5, …]: the rotary columns
    as this pass turns them."""
    return jnp.concatenate([w[..., 0::2], w[..., 1::2]], axis=-1)


def _block_rows(s: int) -> int:
    return min(BLOCK_ROWS, s)


def _tables(s: int, r: int, theta):
    """(cos, sin) (S, r) f32 of the turn (``head_norm.rope_tables``); with
    ``theta`` None the turn by no angle, cos 1 and sin 0."""
    if theta is None:
        return jnp.ones((s, r), jnp.float32), jnp.zeros((s, r), jnp.float32)
    return rope_tables(s, r, theta)


def _kernel_path(s: int, h: int, n: int, r: int, d_v: int, interpret: bool) -> bool:
    """The Pallas kernels (True) or XLA's form (False).  The kernels take
    heads in pairs, each [a lane tile | half a lane tile], values of a lane
    tile and a sequence of whole blocks; where they fit,
    ``_dispatch.kernels_run`` decides."""
    fits = (n == d_v == LANES and 2 * r == LANES and h % 2 == 0
            and s % _block_rows(s) == 0 and _block_rows(s) % SUBLANES == 0)
    return kernels_run(fits, interpret)


# ---------------------------------------------------------------------------
# the equations: XLA's form whole
# ---------------------------------------------------------------------------


def _forward(q_nope, q_rope, k_nope, v, k_rope, h, tables):
    b, s, _ = q_nope.shape
    cos, sin = tables

    def heads(x):
        return x.reshape(b, s, h, -1).transpose(0, 2, 1, 3)

    def turned(x):
        return turn(x.astype(jnp.float32), cos, sin, xla_roll).astype(x.dtype)

    key = jnp.broadcast_to(turned(k_rope)[:, None], (b, h, s, k_rope.shape[-1]))
    return (jnp.concatenate([heads(q_nope), turned(heads(q_rope))], axis=-1),
            jnp.concatenate([heads(k_nope), key], axis=-1), heads(v))


def _backward(dq, dk, dv, n, tables):
    """The transpose: the rotation's is the rotation by the negative angle;
    the shared key's cotangent is summed over the heads in f32."""
    b, _, s, _ = dq.shape
    cos, sin = tables

    def tokens(x):
        return x.transpose(0, 2, 1, 3).reshape(b, s, -1)

    def back(x32, dtype):
        return turn(x32, cos, -sin, xla_roll).astype(dtype)

    key = jnp.sum(dk[..., n:].astype(jnp.float32), axis=1)
    return (tokens(dq[..., :n]), tokens(back(dq[..., n:].astype(jnp.float32), dq.dtype)),
            tokens(dk[..., :n]), tokens(dv), back(key, dk.dtype))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _pair_tables(s: int, r: int, theta):
    """(cos, sin) (S, 128) f32: a head's tables twice along the lanes, once a
    head of a pair."""
    return tuple(jnp.tile(t, (1, LANES // r)) for t in _tables(s, r, theta))


def _turn_pair(y, cos, sin):
    """``y`` (rows, 128) f32, two heads' r = 64 rotary columns side by side,
    each even-first: column l's partner is l + 32 in a head's first half and
    l − 32 in its second."""
    from jax.experimental.pallas import tpu as pltpu

    half = LANES // 4
    lane = lax.broadcasted_iota(jnp.int32, y.shape, y.ndim - 1)
    partner = jnp.where(lane % (2 * half) < half,
                        pltpu.roll(y, shift=LANES - half, axis=y.ndim - 1),
                        pltpu.roll(y, shift=half, axis=y.ndim - 1))
    return y * cos + partner * sin


def _other_head(y):
    """The two heads of a pair exchanged along the lanes."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(y, shift=LANES // 2, axis=y.ndim - 1)


def _specs(b, h, s, n, r, d_v, rows):
    """(grid, token-major blocks of a pair of heads — nope, rope, value, the
    shared key —, head-major blocks of a pair — q | k, v —, the tables'
    block): query blocks outermost, pairs innermost."""
    from jax.experimental import pallas as pl

    pairs = h // 2
    tok = lambda width: pl.BlockSpec(  # noqa: E731
        (1, rows, 2 * width), lambda qi, i: (i // pairs, qi, i % pairs))
    key = pl.BlockSpec((1, rows, r), lambda qi, i: (i // pairs, qi, 0))
    head = lambda width: pl.BlockSpec((2, rows, width), lambda qi, i: (i, qi, 0))  # noqa: E731
    table = pl.BlockSpec((rows, LANES), lambda qi, i: (qi, 0))
    return (s // rows, b * pairs), tok(n), tok(r), tok(d_v), key, head(n + r), head(d_v), table


def _forward_kernels(q_nope, q_rope, k_nope, v, k_rope, h, theta, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = q_nope.shape
    n, r, d_v = q_nope.shape[-1] // h, k_rope.shape[-1], v.shape[-1] // h
    grid, nope, rope, value, key, qk_head, v_head, table = _specs(
        b, h, s, n, r, d_v, _block_rows(s))

    def kernel(qn_ref, qr_ref, kn_ref, v_ref, kr_ref, cos_ref, sin_ref, q_ref, k_ref, vo_ref):
        cos, sin = cos_ref[...], sin_ref[...]
        turned = _turn_pair(qr_ref[0].astype(jnp.float32), cos, sin)
        # the shared key fills one head's place; the other's lanes are not read
        shared = kr_ref[0].astype(jnp.float32)
        shared = _turn_pair(jnp.concatenate([shared, shared], axis=-1), cos, sin)
        for one, mine in ((0, turned), (1, _other_head(turned))):
            q_ref[one, :, :n] = qn_ref[0, :, one * n:(one + 1) * n]
            q_ref[one, :, n:] = mine[:, :r].astype(q_ref.dtype)
            k_ref[one, :, :n] = kn_ref[0, :, one * n:(one + 1) * n]
            k_ref[one, :, n:] = shared[:, :r].astype(k_ref.dtype)
            vo_ref[one] = v_ref[0, :, one * d_v:(one + 1) * d_v]

    args = (q_nope, q_rope, k_nope, v, k_rope)
    vma = vma_union(*args)
    q, k, v = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b * h, s, n + r), q_nope.dtype, vma=vma),
                   jax.ShapeDtypeStruct((b * h, s, n + r), k_nope.dtype, vma=vma),
                   jax.ShapeDtypeStruct((b * h, s, d_v), v.dtype, vma=vma)),
        grid=grid,
        in_specs=[nope, rope, nope, value, key, table, table],
        out_specs=(qk_head, qk_head, v_head),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=FWD_KERNEL,
    )(*args, *_pair_tables(s, r, theta))
    return tuple(x.reshape(b, h, s, -1) for x in (q, k, v))


def _backward_kernels(dq, dk, dv, n, theta, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, _ = dq.shape
    r, d_v = dq.shape[-1] - n, dv.shape[-1]
    rows = _block_rows(s)
    grid, nope, rope, value, key, qk_head, v_head, table = _specs(b, h, s, n, r, d_v, rows)
    pairs = h // 2

    def kernel(dq_ref, dk_ref, dv_ref, cos_ref, sin_ref,
               qn_ref, qr_ref, kn_ref, v_ref, kr_ref, sum_ref):
        cos, back = cos_ref[...], -sin_ref[...]
        pair = lambda ref: jnp.concatenate(  # noqa: E731
            [ref[0, :, n:].astype(jnp.float32), ref[1, :, n:].astype(jnp.float32)], axis=-1)
        qr_ref[0] = _turn_pair(pair(dq_ref), cos, back).astype(qr_ref.dtype)
        for one in (0, 1):
            qn_ref[0, :, one * n:(one + 1) * n] = dq_ref[one, :, :n]
            kn_ref[0, :, one * n:(one + 1) * n] = dk_ref[one, :, :n]
            v_ref[0, :, one * d_v:(one + 1) * d_v] = dv_ref[one]

        @pl.when(pl.program_id(1) % pairs == 0)
        def _clear():
            sum_ref[...] = jnp.zeros_like(sum_ref)

        sum_ref[...] += pair(dk_ref)

        @pl.when(pl.program_id(1) % pairs == pairs - 1)
        def _write():
            # the two heads of a pair summed: both halves then hold the whole
            whole = sum_ref[...] + _other_head(sum_ref[...])
            kr_ref[0] = _turn_pair(whole, cos, back)[:, :r].astype(kr_ref.dtype)

    vma = vma_union(dq, dk, dv)
    token = lambda width, like: jax.ShapeDtypeStruct((b, s, width), like.dtype, vma=vma)  # noqa: E731
    return pl.pallas_call(
        kernel,
        out_shape=(token(h * n, dq), token(h * r, dq), token(h * n, dk), token(h * d_v, dv),
                   token(r, dk)),
        grid=grid,
        in_specs=[qk_head, qk_head, v_head, table, table],
        out_specs=(nope, rope, nope, value, key),
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # the shared key's cotangent of a query block accumulates along the heads
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=BWD_KERNEL,
    )(dq.reshape(b * h, s, n + r), dk.reshape(b * h, s, n + r), dv.reshape(b * h, s, d_v),
      *_pair_tables(s, r, theta))


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _mla_heads(q_nope, q_rope, k_nope, v, k_rope, h, n, theta, interpret):
    s, r = q_nope.shape[1], k_rope.shape[-1]
    if _kernel_path(s, h, n, r, v.shape[-1] // h, interpret):
        return _forward_kernels(q_nope, q_rope, k_nope, v, k_rope, h, theta, interpret)
    return _forward(q_nope, q_rope, k_nope, v, k_rope, h, _tables(s, r, theta))


def _fwd(q_nope, q_rope, k_nope, v, k_rope, h, n, theta, interpret):
    # linear in its inputs: the backward pass needs none of them
    return _mla_heads(q_nope, q_rope, k_nope, v, k_rope, h, n, theta, interpret), None


def _bwd(h, n, theta, interpret, _, cotangents):
    dq, dk, dv = cotangents
    s, r = dq.shape[2], dq.shape[-1] - n
    if _kernel_path(s, h, n, r, dv.shape[-1], interpret):
        return tuple(_backward_kernels(dq, dk, dv, n, theta, interpret))
    return _backward(dq, dk, dv, n, _tables(s, r, theta))


_mla_heads.defvjp(_fwd, _bwd)


def mla_heads(q_nope, q_rope, k_nope, v, k_rope, n_heads: int, theta: Optional[float],
              interpret: bool = False):
    """The four token-major products and the shared rotary key (rotary columns
    even-first, :func:`even_first`) → the flash kernels' q, k (B, H, S, n + r)
    and v (B, H, S, d_v); differentiable in all five.  ``theta`` None: no
    positions, nothing is turned (:func:`_tables`).  What runs where is
    :func:`_kernel_path`'s call."""
    n, r = q_nope.shape[-1] // n_heads, k_rope.shape[-1]
    if r % 2 or q_rope.shape[-1] != n_heads * r or k_nope.shape[-1] != n_heads * n:
        raise ValueError(f"{n_heads} heads of {n} | {r}: q_rope {q_rope.shape}, "
                         f"k_nope {k_nope.shape}, k_rope {k_rope.shape}")
    return _mla_heads(q_nope, q_rope, k_nope, v, k_rope, n_heads, n, theta, interpret)


@jax.custom_vjp
def _merge_heads(o, wo):
    return jnp.einsum("bhsk,hkd->bsd", o, wo)


def _merge_bwd(res, dy):
    o, wo = res
    # dO (B, H, S, k) as the backward flash kernel reads it, written once:
    # the weight first — as the einsum's own transpose, dy first, XLA:TPU
    # writes the product sequence-minor and copies the whole of it — and kept
    # apart from its readers: fused with the kernel's row sums of dO · o it is
    # sequence-minor again, and o is copied to meet it.
    do = lax.optimization_barrier(jnp.einsum("hkd,bsd->bhsk", wo, dy).astype(o.dtype))
    return do, jnp.einsum("bhsk,bsd->hkd", o, dy).astype(wo.dtype)


_merge_heads.defvjp(lambda o, wo: (_merge_heads(o, wo), (o, wo)), _merge_bwd)


def merge_heads(o, wo):
    """o (B, H, S, k) as the flash kernels write it, wo (H, k, D) → (B, S, D):
    the output projection ``bhsk,hkd->bsd``, with a transpose that writes dO
    head-major."""
    # under shard_map the weight is replicated and o varies: the weight's
    # cotangent is then summed over o's axes by this cast's transpose
    need = tuple(jax.typeof(o).vma - jax.typeof(wo).vma)
    if need:
        wo = lax.pcast(wo, need, to="varying")
    return _merge_heads(o, wo)
