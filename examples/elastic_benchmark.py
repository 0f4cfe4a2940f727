"""Elastic suspend/resume example
(example/pytorch/elastic_benchmark_byteps.py parity).

Trains, suspends mid-run, resumes with (potentially) rewritten topology,
and verifies declared-key stability across generations.

    python examples/elastic_benchmark.py
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import numpy as np

import byteps_tpu as bps


def main():
    bps.init()
    print(f"gen 0: rank {bps.rank()}/{bps.size()}")
    names = [f"Gradient.layer{i}" for i in range(8)]
    keys0 = {n: bps.declare_tensor(n) for n in names}
    for step in range(5):
        for n in names:
            g = np.full(64, float(step), dtype=np.float32)
            out = bps.push_pull(g, name=n)
    print("gen 0: 5 steps done")

    bps.suspend()
    print("suspended")

    # a real elastic event would change num_workers/global_rank here
    bps.resume(num_workers=bps.size())
    print(f"gen 1: rank {bps.rank()}/{bps.size()}")
    keys1 = {n: bps.declare_tensor(n) for n in names}
    assert keys0 == keys1, "key assignment must be stable across generations"
    for n in names:
        out = bps.push_pull(np.ones(64, dtype=np.float32), name=n)
    print("gen 1: keys stable, traffic OK")
    bps.shutdown()


if __name__ == "__main__":
    main()
