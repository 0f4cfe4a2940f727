"""Pallas TPU kernels for the hot ops.

- :mod:`flash_attention` — blocked online-softmax attention (VMEM-tiled,
  MXU matmuls), used by the transformer's per-device attention.
- :mod:`head_norm` — a head's RMSNorm and rotary embedding in one pass each
  way (bf16 in, f32 in registers, bf16 out), between a q | k projection and
  the flash kernels of the sliding-window family's mixers.
- :mod:`onebit_device` — on-device sign compression, shrinking the
  device→host transfer 32× before the PS hop (the improvement SURVEY §7
  "hard parts" identifies over the reference's CPU-side compression).

Every kernel has a pure-jnp fallback selected automatically off-TPU.
"""

from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.onebit_device import onebit_compress_device, onebit_decompress_device
