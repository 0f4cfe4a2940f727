"""Device-side codec adapters for the engine pipeline.

The reference compresses on the CPU *after* staging the full fp32
gradient to host (compress loop, core_loops.cc:498-536).  On TPU the
order inverts — SURVEY §7 names this the genuine improvement: the Pallas/
jnp packers (ops/onebit_device.py, ops/codecs_device.py) run BEFORE the
device→host copy, so COPYD2H moves the compressed payload (32× smaller
for onebit, ~n/2k for topk, ~4× for dithering), and the pull side moves
the compressed payload host→device and decodes on device.

Wire compatibility is inherited from the device kernels (byte-identical
framing for onebit/topk; dithering's server decode never re-derives
randomness), so the SAME servers — Python or C++ — aggregate payloads
from device-compressing and host-compressing workers interchangeably.

Eligibility (`device_codec_for`):

- bare codec chains only — error-feedback/momentum are stateful *host*
  transforms of the uncompressed gradient, so chains carrying them keep
  the host path (the residual would force a full-size D2H anyway);
- onebit / topk / dithering.  randomk is host-only: its whole contract
  is replaying the server-shared sequential xorshift128+ stream
  (randomk.cc:25), which is a 128-bit serial recurrence — antithetical
  to the device's SIMD model (and needs u64 ops TPU lacks).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from byteps_tpu.compression.registry import parse_codec_config


class _DeviceOneBit:
    def __init__(self, size: int, scaling: bool) -> None:
        self.size = size
        self.scaling = scaling

    def wire_nbytes(self) -> int:
        """Exact wire payload size (f32 scale + packed sign words) — the
        fusion-threshold gauge, same contract as the host codec's
        ``Compressor.wire_nbytes``."""
        return 4 + 4 * ((self.size + 31) // 32)

    def compress(self, dev_flat) -> bytes:
        from byteps_tpu.ops.onebit_device import (
            onebit_compress_device,
            onebit_payload,
        )

        scale, words = onebit_compress_device(dev_flat, scaling=self.scaling)
        return onebit_payload(scale, words)  # the (tiny) D2H happens here

    def decompress(self, payload: bytes, n: int):
        import jax.numpy as jnp

        from byteps_tpu.ops.onebit_device import onebit_decompress_device

        scale = jnp.asarray(np.frombuffer(payload[:4], dtype=np.float32)[0])
        words = jnp.asarray(np.frombuffer(payload[4:], dtype=np.uint32))
        return onebit_decompress_device(scale, words, n)


class _DeviceTopK:
    def __init__(self, size: int, k: int) -> None:
        self.size = size
        self.k = max(1, min(int(k), size))

    def wire_nbytes(self) -> int:
        """Exact wire payload size (k × (i32 index, f32 value) pairs)."""
        return 8 * self.k

    def compress(self, dev_flat) -> bytes:
        from byteps_tpu.ops.codecs_device import (
            topk_compress_device,
            topk_payload,
        )

        idx, vals = topk_compress_device(dev_flat, self.k)
        return topk_payload(idx, vals)

    def decompress(self, payload: bytes, n: int):
        import jax.numpy as jnp

        from byteps_tpu.ops.codecs_device import topk_sum_device

        rec = np.frombuffer(payload, dtype=[("i", "<i4"), ("v", "<f4")])
        idx = jnp.asarray(np.ascontiguousarray(rec["i"]))
        vals = jnp.asarray(np.ascontiguousarray(rec["v"]))
        return topk_sum_device(idx, vals, n)


class _DeviceDithering:
    def __init__(self, size: int, s: int, natural: bool, l2: bool, seed: int) -> None:
        self.size = size
        self.s = s
        self.natural = natural
        self.l2 = l2
        self._seed = seed or 0x5EED
        self._round = 0

    def wire_nbytes(self) -> int:
        """Exact wire payload size (f32 norm + one i8 level per element)."""
        return 4 + self.size

    def compress(self, dev_flat) -> bytes:
        import jax

        from byteps_tpu.ops.codecs_device import (
            dithering_compress_device,
            dithering_payload,
        )

        # fresh fold per round: stochastic rounding must not reuse draws
        # across steps (the host codec advances its xorshift the same way)
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed), self._round)
        self._round += 1
        norm, levels = dithering_compress_device(
            dev_flat, key, s=self.s, natural=self.natural, l2=self.l2
        )
        return dithering_payload(norm, levels)

    def decompress(self, payload: bytes, n: int):
        import jax.numpy as jnp

        from byteps_tpu.ops.codecs_device import dithering_decompress_device

        norm = jnp.asarray(np.frombuffer(payload[:4], dtype=np.float32)[0])
        levels = jnp.asarray(np.frombuffer(payload[4 : 4 + n], dtype=np.int8))
        return dithering_decompress_device(
            norm, levels, s=self.s, natural=self.natural
        )


def device_codec_for(kwargs: Dict[str, str], size: int) -> Optional[object]:
    """Device adapter for a compressor config, or None → host path.

    Parsing is delegated to the registry's ``parse_codec_config`` — the
    single normalizer of byteps_* keys and aliases — so this factory and
    ``create_compressor`` can never disagree about what is configured."""
    cfg = parse_codec_config(kwargs, size)
    if cfg is None:
        return None
    if cfg["ef"] or cfg["momentum"]:
        return None  # stateful host transforms: see module docstring
    if cfg["ctype"] == "onebit":
        return _DeviceOneBit(size, cfg["scaling"])
    if cfg["ctype"] == "topk":
        return _DeviceTopK(size, cfg["k"])
    if cfg["ctype"] == "dithering":
        return _DeviceDithering(
            size, cfg["k"], cfg["natural"], cfg["l2"], cfg["seed"]
        )
    return None  # randomk (host-only by design) or unknown
