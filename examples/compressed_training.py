"""Gradient compression example: train with onebit/topk/randomk/dithering
+ error feedback through the PS path (the usage pattern of the reference's
compression tests and bps.DistributedTrainer compression_params).

Requires a running scheduler/server (see examples/README.md), or set
BYTEPS_FORCE_DISTRIBUTED=1 with a local fake cluster.

    python examples/compressed_training.py --compressor onebit --ef vanilla
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import argparse

import numpy as np

import byteps_tpu as bps
from byteps_tpu.cross_barrier import CrossBarrierOptimizer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compressor", default="onebit",
                    choices=["onebit", "topk", "randomk", "dithering"])
    ap.add_argument("--k", default="0.1")
    ap.add_argument("--ef", default="", choices=["", "vanilla"])
    ap.add_argument("--momentum", default="", choices=["", "nesterov"])
    ap.add_argument("--steps", type=int, default=50)
    args = ap.parse_args()

    bps.init()
    rng = np.random.default_rng(0)
    # least squares: params w fit y = X w*
    n, d = 512, 64
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d,)).astype(np.float32)
    y = X @ w_true

    kwargs = {"byteps_compressor_type": args.compressor, "byteps_compressor_k": args.k}
    if args.ef:
        kwargs["byteps_ef_type"] = args.ef
    if args.momentum:
        kwargs["byteps_momentum_type"] = args.momentum
    bps.declare_tensor("Gradient.w", **kwargs)

    opt = CrossBarrierOptimizer({"w": np.zeros(d, np.float32)}, "sgd", lr=0.01)
    for step in range(args.steps):
        w = opt.params["w"]
        grad = X.T @ (X @ w - y) / n
        opt.backward({"w": grad})
        opt.step()
        if step % 10 == 0 or step == args.steps - 1:
            loss = float(np.mean((X @ opt.params["w"] - y) ** 2))
            print(f"step {step:3d} loss {loss:.5f}")
    bps.shutdown()


if __name__ == "__main__":
    main()
