"""Synthetic throughput benchmark (img|samples/sec) — the parity example
for example/pytorch/benchmark_byteps.py and
example/tensorflow/synthetic_benchmark.py.

    python examples/benchmark_ddp.py --model resnet50 --batch 64
    python examples/benchmark_ddp.py --model vgg16
    python examples/benchmark_ddp.py --model bert_large --batch 32
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

import byteps_tpu as bps
from byteps_tpu.comm.mesh import get_global_mesh
from byteps_tpu.optim import build_flax_data_parallel_step


def bench_conv(model_name: str, batch: int, steps: int, hw: int = 224):
    from byteps_tpu.models.resnet import ResNet50
    from byteps_tpu.models.vgg import VGG16

    model = ResNet50(dtype=jnp.bfloat16) if model_name == "resnet50" else VGG16(dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, hw, hw, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 1000, size=(batch,)).astype(np.int32))
    variables = model.init(jax.random.PRNGKey(0), x[:1], train=True)
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = jax.jit(tx.init)(variables["params"])
    step = build_flax_data_parallel_step(
        model.apply,
        lambda lg, lb: optax.softmax_cross_entropy_with_integer_labels(lg, lb).mean(),
        tx, mesh=get_global_mesh(),
    )
    for _ in range(3):
        variables, opt_state, loss = step(variables, opt_state, (x, y))
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        variables, opt_state, loss = step(variables, opt_state, (x, y))
    jax.block_until_ready(loss)
    return batch * steps / (time.perf_counter() - t0)


def bench_bert(batch: int, steps: int):
    from byteps_tpu.models.transformer import (
        bert_large, build_train_step, init_params, shard_params,
    )
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    cfg = bert_large(max_seq=128, compute_dtype=jnp.bfloat16)
    # data-parallel over every visible device, like the conv benchmarks
    n = jax.device_count()
    mesh = make_training_mesh(n, {"dp": n, "pp": 1, "sp": 1, "tp": 1})
    params = shard_params(init_params(cfg), cfg, mesh)
    tx = optax.adamw(1e-4)
    opt_state = jax.jit(tx.init)(params)
    step = build_train_step(cfg, mesh, tx)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(batch, 128)).astype(np.int32))
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, 1))
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    jax.block_until_ready(loss)
    return batch * steps / (time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "vgg16", "bert_large"])
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    bps.init()
    if args.model == "bert_large":
        rate = bench_bert(args.batch, args.steps)
    else:
        rate = bench_conv(args.model, args.batch, args.steps)
    unit = "samples/s" if args.model == "bert_large" else "img/s"
    print(f"{args.model}: {rate:.1f} {unit} "
          f"(batch {args.batch}, rank {bps.rank()}/{bps.size()})")
    bps.shutdown()


if __name__ == "__main__":
    main()
