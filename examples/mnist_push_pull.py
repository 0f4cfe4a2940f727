"""Minimum end-to-end example: MNIST-style training with push_pull
(BASELINE config 1: single-process bps.push_pull, DMLC_NUM_WORKER=1;
mirrors example/pytorch's MNIST entry).

Runs anywhere: single chip, CPU mesh, or a distributed PS topology when
DMLC_* env is set (launch with ``python -m byteps_tpu.launcher.launch``).

    python examples/mnist_push_pull.py [--steps 100]
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

import byteps_tpu as bps
from byteps_tpu.comm.mesh import get_global_mesh
from byteps_tpu.optim import build_data_parallel_step


def synthetic_mnist(n=512, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 784)).astype(np.float32)
    w = rng.normal(size=(784, 10)).astype(np.float32)
    y = np.argmax(x @ w + 0.5 * rng.normal(size=(n, 10)), axis=1).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def loss_fn(params, batch):
    x, y = batch
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=0.1)
    args = ap.parse_args()

    bps.init()
    print(f"rank {bps.rank()}/{bps.size()} devices={jax.device_count()}")

    rng = np.random.default_rng(1)
    params = {
        "w1": jnp.asarray(rng.normal(0, 0.05, (784, 128)).astype(np.float32)),
        "b1": jnp.zeros(128),
        "w2": jnp.asarray(rng.normal(0, 0.05, (128, 10)).astype(np.float32)),
        "b2": jnp.zeros(10),
    }
    # cross-worker sync of the initial params (broadcast_parameters parity)
    params = bps.broadcast_parameters(params, root_rank=0)

    tx = optax.sgd(args.lr)
    opt_state = jax.jit(tx.init)(params)
    step = build_data_parallel_step(loss_fn, tx, mesh=get_global_mesh(), donate=False)
    x, y = synthetic_mnist()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, (x, y))
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(loss):.4f}")
    bps.shutdown()


if __name__ == "__main__":
    main()
