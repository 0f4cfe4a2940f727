"""Asynchronous training via the parameter-store mode
(BYTEPS_ENABLE_ASYNC, reference: server.cc:315-319 +
torch/__init__.py:195-218's weight-delta pushes).

In async mode the server holds the parameters: each worker pushes its
weight DELTA after local steps and pulls the current global parameters —
no synchronization barrier between workers (stale-gradient SGD).

Run against an async cluster (set BYTEPS_ENABLE_ASYNC=1 on workers AND
servers; see examples/README.md for the topology commands).
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import argparse

import numpy as np

import byteps_tpu as bps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()

    bps.init()
    if not bps.get_config().enable_async:
        raise SystemExit("set BYTEPS_ENABLE_ASYNC=1 on workers and servers")

    rng = np.random.default_rng(bps.rank())
    n, d = 256, 32
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = np.ones(d, dtype=np.float32)
    y = X @ w_true

    # the PS store accumulates deltas; the initial pull seeds local weights
    bps.declare_tensor("AsyncParam.w")
    w = np.asarray(
        bps.push_pull(np.zeros(d, np.float32), name="AsyncParam.w", average=False)
    )
    for r in range(args.rounds):
        w_before = w.copy()
        for _ in range(args.local_steps):  # local SGD, no communication
            g = X.T @ (X @ w - y) / n
            w = w - args.lr * g
        # push the delta; pull the global parameter state (sum of all
        # workers' deltas so far)
        delta = w - w_before
        w = np.asarray(
            bps.push_pull(delta.astype(np.float32), name="AsyncParam.w", average=False)
        )
        if r % 5 == 0 or r == args.rounds - 1:
            loss = float(np.mean((X @ w - y) ** 2))
            print(f"round {r:3d} loss {loss:.5f}")
    bps.shutdown()


if __name__ == "__main__":
    main()
