"""On-device onebit compression.

The reference compresses on the CPU after staging the full fp32 gradient
to host (compress loop, core_loops.cc:498-536).  On TPU we can do better
(SURVEY §7 hard parts): pack sign bits on the DEVICE, so only scale +
n/32 words cross the device→host boundary — a 32× smaller transfer on the
path that feeds the DCN PS hop.

Wire format matches the host codec exactly ([f32 scale][u32 words],
bit = negative — native/compressor.cc), so the server's C++ decompressor
consumes device-compressed payloads unchanged.

The packing is a Pallas kernel (lane reduction over a 32-wide bit-weight
expansion).  On a TPU it is the only packer: any length is zero-padded on
the device to the kernel's block.  Off a TPU :func:`_pack_jnp` stands in —
see ``onebit_compress_device``, the one place that asks ``_dispatch.kernels_run``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from byteps_tpu.ops._dispatch import kernels_run


def _scale(flat: jax.Array, scaling: bool) -> jax.Array:
    """The codec's L1 scale sum|x|/n (1.0 without scaling), f32 scalar."""
    return jnp.where(
        scaling, jnp.sum(jnp.abs(flat)) / flat.shape[0], jnp.float32(1.0)
    ).astype(jnp.float32)


def _pack_jnp(flat: jax.Array, scaling: bool) -> tuple:
    pad = (-flat.shape[0]) % 32
    bits = jnp.signbit(jnp.pad(flat, (0, pad))).astype(jnp.uint32).reshape(-1, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, :]
    words = jnp.sum(bits * weights, axis=1).astype(jnp.uint32)
    return _scale(flat, scaling), words


#: words per grid cell → one native (8, 128) u32 output tile
_WPB = 1024
#: elements per grid cell; inputs are zero-padded to a multiple of this
_BLOCK = 32 * _WPB


def _pack_kernel(x_ref, out_ref):
    # x block: (_WPB, 32) fp32; out block: (8, _WPB/8) u32.
    # Mosaic has no unsigned reductions: accumulate in int32 — the
    # weights are distinct powers of two, so the wrapping sum is exactly
    # the bitwise OR pattern — and bitcast at the store.
    bits = jnp.signbit(x_ref[:]).astype(jnp.int32)
    weights = jnp.left_shift(
        jnp.int32(1), jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    )
    acc = jnp.sum(bits * weights, axis=1)  # (_WPB,)
    out_ref[:] = jax.lax.bitcast_convert_type(
        acc.reshape(out_ref.shape), jnp.uint32
    )


@functools.partial(jax.jit, static_argnames=("scaling", "interpret"))
def onebit_compress_device(
    grad: jax.Array, scaling: bool = True, interpret: bool = False
) -> tuple:
    """Compress on device: returns (scale f32 scalar, words uint32[ceil(n/32)]).

    Transfer these (scale, words) to host and frame them as
    [f32 scale][u32 words] — identical to OneBitCompressor's payload.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    flat = grad.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    # The Pallas packer or _pack_jnp: the kernel fits every length (the
    # engine's default partition, 1,024,000 elements, is not a block
    # multiple — hence the padding below), so ``_dispatch.kernels_run``
    # alone decides; off a TPU the jnp packer stands in (what the CPU
    # suite's engine tests run) unless the caller asked for the interpreter.
    if not kernels_run(True, interpret):
        return _pack_jnp(flat, scaling)

    # pad with +0.0: sign bit clear, and the scale is taken over the n real
    # elements — so the trimmed words and the scale are exactly the
    # unpadded codec's, and the wire stays byte-identical
    padded = jnp.pad(flat, (0, (-n) % _BLOCK))
    nwords = padded.shape[0] // 32
    # Output blocks must be native (8, 128) u32 tiles: 1-D or (1, wpb)
    # blocks trip Mosaic's layout/divisibility checks.
    words = pl.pallas_call(
        _pack_kernel,
        out_shape=jax.ShapeDtypeStruct((nwords // 128, 128), jnp.uint32),
        grid=(nwords // _WPB,),
        in_specs=[pl.BlockSpec((_WPB, 32), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(padded.reshape(nwords, 32))
    return _scale(flat, scaling), words.reshape(nwords)[: (n + 31) // 32]


def onebit_payload(scale: jax.Array, words: jax.Array) -> bytes:
    """Frame device-compressed pieces as the host/C++ wire format."""
    return (
        np.float32(jax.device_get(scale)).tobytes()
        + np.asarray(jax.device_get(words), dtype=np.uint32).tobytes()
    )


@functools.partial(jax.jit, static_argnames=("n",))
def onebit_decompress_device(scale: jax.Array, words: jax.Array, n: int) -> jax.Array:
    """Device-side inverse (for pulling compressed payloads straight to
    device): words uint32[ceil(n/32)] → fp32[n]."""
    bits = (words[:, None] >> jnp.arange(32, dtype=jnp.uint32)[None, :]) & jnp.uint32(1)
    neg = bits.reshape(-1)[:n].astype(bool)
    return jnp.where(neg, -scale, scale).astype(jnp.float32)
