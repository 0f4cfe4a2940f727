"""Pallas TPU kernels for the hot ops.

- :mod:`flash_attention` — blocked online-softmax attention (VMEM-tiled,
  MXU matmuls), used by the transformer's per-device attention.
- :mod:`head_norm` — a head's RMSNorm and rotary embedding in one pass each
  way (bf16 in, f32 in registers, bf16 out), between a q | k projection and
  the flash kernels of the sliding-window family's mixers; and the rotation
  alone (``head_rope``), which reads a token-major product and writes the
  kernels' head-major operand, for the early-routed family's.
- :mod:`mla_heads` — from a latent-attention mixer's four token-major
  products to the flash kernels' head-major q | k | v (the rotary columns
  turned in f32, the shared rotary key read once a block) and its transpose,
  one pass each way; and the output projection whose transpose writes dO
  head-major.
- :mod:`onebit_device` — on-device sign compression, shrinking the
  device→host transfer 32× before the PS hop (the improvement SURVEY §7
  "hard parts" identifies over the reference's CPU-side compression).

Every kernel has a pure-jnp fallback selected automatically off-TPU.
"""

from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.onebit_device import onebit_compress_device, onebit_decompress_device
