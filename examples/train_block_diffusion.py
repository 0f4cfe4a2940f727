"""Block-diffusion training of a small MoE: fresh noise every step.

A sequence enters the layers twice, a noised copy and the clean copy, under a
mask in which a noised block sees itself and the clean tokens before it
(``models/block_diffusion_moe.py``); the loss is over the masked tokens,
weighed by ``1 / t`` of their block's noise level.  The noising is the input
pipeline's (``byteps_tpu.data.block_diffusion_noise``): jitted, drawn anew from
the step's key, on the device — the compiled step takes its result as the
batch's three leaves ``(x_t, x_0, weights)``.

    XLA_FLAGS=--xla_force_host_platform_device_count=2 JAX_PLATFORMS=cpu \
        python examples/train_block_diffusion.py --steps 20
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import argparse
import functools

import jax
import jax.numpy as jnp
import optax

from byteps_tpu.data import block_diffusion_noise
from byteps_tpu.models import block_diffusion_moe as bd
from byteps_tpu.models.transformer import build_train_step
from byteps_tpu.parallel.mesh_utils import make_training_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--block-length", type=int, default=4)
    ap.add_argument("--noise", type=float, nargs=2, default=(0.45, 0.95),
                    help="t ~ U[lo, hi] a block; 1e-3 1 is nearly the unclipped objective")
    args = ap.parse_args()

    dp = len(jax.devices()) if args.batch % len(jax.devices()) == 0 else 1
    cfg = bd.tiny_block_diffusion_moe(max_seq=args.seq, block_length=args.block_length,
                                      n_layers=2, d_model=64, head_dim=16)
    mesh = make_training_mesh(dp, {"dp": dp, "pp": 1, "sp": 1, "tp": 1},
                              devices=jax.devices()[:dp])
    mask_id = cfg.vocab_size - 1  # a row of the vocabulary that data never holds
    tx = optax.adamw(3e-3)
    params = bd.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = jax.jit(tx.init)(params)
    step = build_train_step(cfg, mesh, tx)
    noise = jax.jit(functools.partial(block_diffusion_noise, block_length=cfg.block_length,
                                      mask_id=mask_id, lo=args.noise[0], hi=args.noise[1]))
    # one small corpus, so that the loss can be seen to fall; the noise is new each step
    clean = jax.random.randint(jax.random.PRNGKey(1), (args.batch, args.seq), 0, mask_id,
                               jnp.int32)
    for i in range(args.steps):
        x_t, weights = noise(jax.random.fold_in(jax.random.PRNGKey(2), i), clean)
        params, opt_state, loss = step(params, opt_state, x_t, clean, weights)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  masked {int(jnp.sum(weights > 0)):4d} of {clean.size}  "
                  f"mean weight {float(jnp.mean(weights)):.3f}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
