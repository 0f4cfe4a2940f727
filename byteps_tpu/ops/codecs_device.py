"""On-device topk and dithering compression.

Like :mod:`byteps_tpu.ops.onebit_device`, these move the compression the
reference runs on the CPU (compress loop, core_loops.cc:498-536) onto the
DEVICE, so the device→host transfer that feeds the DCN PS hop carries the
compressed payload instead of the full fp32 gradient:

- topk: 8k bytes instead of 4n (n/k ≫ 1 ⇒ ~n/(2k)× smaller)
- dithering: 4 + n bytes instead of 4n (~4× smaller)

Wire compatibility:

- ``topk``: byte-identical to the host/C++ codec (``[i32 idx, f32 val]``
  pairs sorted by index, topk.cc:26 / native/compressor.cc:87-104).
  All three selectors break magnitude ties toward the LOWER index
  (``lax.top_k``'s documented order; the host paths mirror it), so the
  bit-match holds even when the k-th magnitude is duplicated.
- ``dithering``: the payload is ``[f32 norm][int8 levels]`` and the server
  decodes WITHOUT re-deriving any randomness (unlike randomk, the RNG
  affects only the worker-side stochastic rounding draw — dithering.h:43-78).
  The device path therefore draws from the TPU-native PRNG instead of
  replaying the host codec's sequential xorshift128+ stream: replaying it
  bit-exactly would serialize n draws through a 128-bit recurrence and
  needs float64 (unsupported on TPU).  Decode parity (host ``decompress``
  of a device payload) is exact; the rounding is unbiased with the same
  level grid, asserted statistically in tests.

jnp implementations (XLA fuses them fine — top_k and elementwise quantize
are not MXU-bound, so a Pallas kernel buys nothing here); the onebit
sibling keeps its Pallas packer because bit-packing needs the sublane
reduction trick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("k",))
def topk_compress_device(grad: jax.Array, k: int) -> tuple:
    """Select the k largest-|.| elements on device.

    Returns (idx int32[k] ascending, vals f32[k]) — frame with
    :func:`topk_payload` for the host/C++ wire format."""
    flat = grad.reshape(-1).astype(jnp.float32)
    k = max(1, min(int(k), flat.shape[0]))
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    idx = jnp.sort(idx)
    return idx.astype(jnp.int32), flat[idx]


def topk_payload(idx: jax.Array, vals: jax.Array) -> bytes:
    """[i32 index, f32 value] pairs — identical to TopKCompressor's wire."""
    idx = np.asarray(jax.device_get(idx), dtype=np.int32)
    vals = np.asarray(jax.device_get(vals), dtype=np.float32)
    rec = np.empty(idx.size, dtype=[("i", "<i4"), ("v", "<f4")])
    rec["i"] = idx
    rec["v"] = vals
    return rec.tobytes()


@functools.partial(jax.jit, static_argnames=("n",))
def topk_sum_device(idx: jax.Array, vals: jax.Array, n: int) -> jax.Array:
    """Device-side decompress/scatter (pull-to-device path)."""
    return jnp.zeros(n, jnp.float32).at[idx].set(vals)


@functools.partial(
    jax.jit, static_argnames=("s", "natural", "l2")
)
def dithering_compress_device(
    grad: jax.Array,
    key: jax.Array,
    s: int = 4,
    natural: bool = False,
    l2: bool = False,
) -> tuple:
    """Stochastic quantization on device: returns (norm f32 scalar,
    levels int8[n]) — frame with :func:`dithering_payload`.

    Same level grid as DitheringCompressor (linear: |x|/norm·s rounded
    stochastically; natural: power-of-two buckets); draws come from
    ``key`` (jax threefry) — see module docstring for why the host
    xorshift stream is not replayed."""
    flat = grad.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    norm = jnp.where(
        l2,
        jnp.sqrt(jnp.sum(flat * flat)),
        jnp.max(jnp.abs(flat), initial=0.0),
    )
    norm = jnp.where(norm == 0.0, 1.0, norm).astype(jnp.float32)
    u = jax.random.uniform(key, (n,), dtype=jnp.float32)
    p = jnp.abs(flat) / norm
    if natural:
        pos = p > 0.0
        j = jnp.where(pos, jnp.floor(jnp.log2(jnp.where(pos, p, 1.0))), 0.0)
        hi = pos & (j >= 0)
        lo = pos & (j < -s)
        mid = pos & ~hi & ~lo
        lo_level = (p / (2.0 ** (-s)) > u).astype(jnp.int32)
        lo_b = jnp.exp2(j)
        frac = (p - lo_b) / (jnp.exp2(j + 1.0) - lo_b)
        mid_level = (s + j).astype(jnp.int32) + (frac > u).astype(jnp.int32)
        level = jnp.where(hi, s, jnp.where(lo, lo_level, jnp.where(mid, mid_level, 0)))
    else:
        scaled = p * s
        fl = jnp.floor(scaled)
        level = (fl + ((scaled - fl) > u)).astype(jnp.int32)
        level = jnp.minimum(level, s)
    levels = jnp.where(jnp.signbit(flat), -level, level).astype(jnp.int8)
    return norm, levels


def dithering_payload(norm: jax.Array, levels: jax.Array) -> bytes:
    """[f32 norm][int8 levels] — identical to DitheringCompressor's wire."""
    return (
        np.float32(jax.device_get(norm)).tobytes()
        + np.asarray(jax.device_get(levels), dtype=np.int8).tobytes()
    )


@functools.partial(jax.jit, static_argnames=("s", "natural"))
def dithering_decompress_device(
    norm: jax.Array, levels: jax.Array, s: int = 4, natural: bool = False
) -> jax.Array:
    """Device-side inverse (pull-to-device / EF residual path)."""
    lv = levels.astype(jnp.int32)
    a = jnp.abs(lv)
    if natural:
        mag = jnp.where(a == 0, 0.0, jnp.exp2(a.astype(jnp.float32) - s))
    else:
        mag = a.astype(jnp.float32) / s
    return jnp.sign(lv).astype(jnp.float32) * mag * norm
