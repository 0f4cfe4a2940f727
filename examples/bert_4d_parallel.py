"""Flagship example: BERT/GPT training with 4-D parallelism
(dp × pp × sp × tp, MoE expert parallelism on the sp axis).

On a single host this runs on the virtual CPU mesh; on a pod slice the
same code spans real chips (BASELINE configs 3 & 5 class).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/bert_4d_parallel.py --dp 1 --pp 2 --sp 2 --tp 2
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from byteps_tpu.models.transformer import (
    TransformerConfig, build_train_step, init_params, shard_params,
)
from byteps_tpu.parallel.mesh_utils import make_training_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--sp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--moe", action="store_true")
    args = ap.parse_args()

    mesh = make_training_mesh(
        args.dp * args.pp * args.sp * args.tp,
        {"dp": args.dp, "pp": args.pp, "sp": args.sp, "tp": args.tp},
    )
    cfg = TransformerConfig(
        vocab_size=1024, d_model=args.d_model, n_heads=4,
        d_head=args.d_model // 4, d_ff=args.d_model * 4,
        n_layers=args.layers, max_seq=args.seq, causal=True,
        moe=args.moe, n_experts=2 * args.sp,
    )
    print(f"mesh {dict(mesh.shape)}  layers={cfg.n_layers} moe={cfg.moe}")
    params = shard_params(init_params(cfg, pp_size=args.pp), cfg, mesh)
    tx = optax.adamw(3e-4)
    opt_state = jax.jit(tx.init)(params)
    step = build_train_step(cfg, mesh, tx, donate=False)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(args.batch, args.seq)).astype(np.int32)
    )
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, 1))
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        print(f"step {i} loss {float(loss):.4f}")
    jax.block_until_ready(loss)
    print(f"{args.batch * args.steps / (time.perf_counter() - t0):.1f} samples/s")


if __name__ == "__main__":
    main()
