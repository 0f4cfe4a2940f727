"""The device-side operations of the hot paths: Pallas TPU kernels, each with
XLA's form of the same equations beside it.

- :mod:`_dispatch` — what the kernel modules know in common: the default
  device's platform, ``kernels_run`` (THE decision between a module's Pallas
  kernels and XLA's form, given the module's own shape test), the lane width,
  a ``pallas_call``'s ``vma`` under ``shard_map``, a tuned table read once.
- :mod:`flash_attention` — blocked online-softmax attention, forward and one
  backward kernel, under three masks: causal (or none), a causal band
  (``window=``) and the block-diffusion mask over a noised and a clean copy
  (a table of tiles); grouped key/value heads by index map.  Every attention
  mixer of the model families and the ring attention of ``parallel/`` call it.
- :mod:`gated_delta`, :mod:`gated_delta_kernels` — the gated delta rule in
  chunks (linear attention with a decay and a rank-one correction): XLA's
  form and the chooser, and the three kernels (``gdn_chunk_inverse``,
  ``gdn_scan_fwd``, ``gdn_scan_bwd``), for the gated-delta family's linear
  layers.
- :mod:`ssd` — the state-space scan in chunks, XLA's form alone (no kernel,
  no chooser), for the state-space family's mixers.
- :mod:`causal_conv` — the depthwise causal convolution before a linear
  mixer with its bias, silu and a head's l2 norm, one pass each way
  (``conv_silu_fwd``, ``conv_silu_bwd``) over a column range of the
  projection's output found by index map, and XLA's form, for the
  gated-delta, state-space and cross-decoder families' mixers.
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
- :mod:`codecs_device`, :mod:`quantized_allreduce` — the top-k and dithering
  codecs and the quantized all-reduce on the device, plain jax (no kernel).

A module's kernels run on a TPU at the shapes they tile and under the Pallas
interpreter where a caller asks; everywhere else XLA's form does.
"""

from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.onebit_device import onebit_compress_device, onebit_decompress_device
