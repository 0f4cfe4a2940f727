"""Quantify the TF plugin's tf.py_function overhead (VERDICT r3 weak #4).

The TensorFlow plugin routes every reduce through a ``tf.py_function``
host callback (byteps_tpu/tensorflow/ops.py) — functionally correct, but
each call is a serialized TF-runtime→host hop.  This tool measures what
that hop costs against the same traffic through the core API directly,
and how much ``push_pull_group`` (one host hop for N tensors) claws back:

  core        — byteps_tpu.push_pull_async/synchronize straight from numpy
  tf-per-op   — byteps_tpu.tensorflow.push_pull once per tensor
  tf-grouped  — byteps_tpu.tensorflow.push_pull_group (one py_function)

Run on the CPU mesh (local mode: the reduce itself is an ICI psum
identity on 1 worker, so the measured delta IS the wrapping overhead):

    JAX_PLATFORMS=cpu python tools/tf_overhead_bench.py

Prints one JSON line (checked in as TF_OVERHEAD_r{N}.json).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main() -> None:
    import numpy as np

    import byteps_tpu as bps
    from byteps_tpu import tensorflow as bps_tf
    from byteps_tpu.tensorflow.ops import push_pull_group_fused

    bps.init()

    # a small model's gradient list: 30 tensors, mixed sizes
    rng = np.random.default_rng(0)
    shapes = [(256, 256)] * 10 + [(1024,)] * 10 + [(64, 64)] * 10
    grads = [rng.normal(size=s).astype(np.float32) for s in shapes]
    names = [f"tfo.g{i}" for i in range(len(grads))]
    rounds = 30

    def run_core() -> float:
        t0 = time.perf_counter()
        for _ in range(rounds):
            hs = [
                bps.push_pull_async(g, name=n, average=False, priority=-i)
                for i, (g, n) in enumerate(zip(grads, names))
            ]
            for h in hs:
                bps.synchronize(h)
        return (time.perf_counter() - t0) / rounds

    def run_tf_per_op() -> float:
        import tensorflow as tf

        ts = [tf.constant(g) for g in grads]
        t0 = time.perf_counter()
        for _ in range(rounds):
            outs = [
                bps_tf.push_pull(t, name=n, average=False)
                for t, n in zip(ts, names)
            ]
            _ = [np.asarray(o) for o in outs]
        return (time.perf_counter() - t0) / rounds

    def run_tf_grouped() -> float:
        import tensorflow as tf

        ts = [tf.constant(g) for g in grads]
        t0 = time.perf_counter()
        for _ in range(rounds):
            outs = bps_tf.push_pull_group(ts, names, average=False)
            _ = [np.asarray(o) for o in outs]
        return (time.perf_counter() - t0) / rounds

    def run_tf_fused() -> float:
        import tensorflow as tf

        ts = [tf.constant(g) for g in grads]
        t0 = time.perf_counter()
        for _ in range(rounds):
            outs = push_pull_group_fused(ts, names, average=False)
            _ = [np.asarray(o) for o in outs]
        return (time.perf_counter() - t0) / rounds

    def run_in_function(fn) -> float:
        """Keras-real mode: the sync inside ONE tf.function — in-graph
        ops compile away, py_function host hops remain per call."""
        import tensorflow as tf

        ts = [tf.constant(g) for g in grads]

        @tf.function
        def step():
            return fn(ts, names, average=False)

        _ = [np.asarray(o) for o in step()]  # trace once
        t0 = time.perf_counter()
        for _ in range(rounds):
            _ = [np.asarray(o) for o in step()]
        return (time.perf_counter() - t0) / rounds

    # short warmups (tensor declaration, trace caches) — the measured
    # loops amortize any residual cold cost over 30 rounds
    for _ in range(3):
        hs = [bps.push_pull_async(g, name=n, average=False)
              for g, n in zip(grads, names)]
        for h in hs:
            bps.synchronize(h)
    import tensorflow as tf
    warm = [tf.constant(g) for g in grads[:2]]
    for _ in range(3):
        [np.asarray(o) for o in (
            bps_tf.push_pull(warm[0], name=names[0], average=False),
            bps_tf.push_pull(warm[1], name=names[1], average=False),
        )]
        [np.asarray(o) for o in bps_tf.push_pull_group(
            warm, names[:2], average=False)]
        [np.asarray(o) for o in push_pull_group_fused(
            warm, names[:2], average=False)]
    core_s = run_core()
    per_op_s = run_tf_per_op()
    grouped_s = run_tf_grouped()
    fused_s = run_tf_fused()
    grouped_fn_s = run_in_function(bps_tf.push_pull_group)
    fused_fn_s = run_in_function(push_pull_group_fused)
    bps.shutdown()

    print(json.dumps({
        "metric": "tf_plugin_overhead_per_step_ms",
        "tensors_per_step": len(grads),
        "payload_mbytes": round(sum(g.nbytes for g in grads) / 1e6, 2),
        "rounds": rounds,
        "core_ms": round(core_s * 1e3, 2),
        "tf_per_op_ms": round(per_op_s * 1e3, 2),
        "tf_grouped_ms": round(grouped_s * 1e3, 2),
        "tf_fused_ms": round(fused_s * 1e3, 2),
        "tf_grouped_in_function_ms": round(grouped_fn_s * 1e3, 2),
        "tf_fused_in_function_ms": round(fused_fn_s * 1e3, 2),
        "per_op_overhead_x": round(per_op_s / core_s, 2),
        "grouped_overhead_x": round(grouped_s / core_s, 2),
        "fused_overhead_x": round(fused_s / core_s, 2),
        "notes": (
            "local mode on the CPU mesh: the reduce is an identity psum, so "
            "deltas are pure wrapping cost; tf-per-op pays one py_function "
            "host hop per tensor, push_pull_group batches all tensors into "
            "one hop; push_pull_group_fused additionally concats per dtype "
            "IN-GRAPH so the hop marshals/submits one tensor per dtype — "
            "the shipped default for the gradient-sync path "
            "(BYTEPS_TF_FUSION=0 restores per-tensor keys)"
        ),
    }))


if __name__ == "__main__":
    main()
