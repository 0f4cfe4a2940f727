"""TF/Keras plugin synthetic benchmark — the reference's
example/tensorflow/synthetic_benchmark.py translated to Keras 3.

Single worker it runs standalone; with a scheduler + server + DMLC_* env
(see examples/mnist_push_pull.py for the cluster bring-up) the gradients
ride the PS path.

    python examples/tensorflow_synthetic.py [--batch 32] [--iters 20]
"""

import argparse
import os as _os
import sys as _sys
import time

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import numpy as np
import tensorflow as tf

import byteps_tpu.tensorflow as bps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dim", type=int, default=512)
    args = ap.parse_args()

    bps.init()
    init = tf.keras.initializers.GlorotUniform(seed=bps.rank())
    model = tf.keras.Sequential(
        [
            tf.keras.layers.Input((args.dim,)),
            tf.keras.layers.Dense(args.dim, activation="relu", kernel_initializer=init),
            tf.keras.layers.Dense(args.dim, activation="relu", kernel_initializer=init),
            tf.keras.layers.Dense(10, kernel_initializer=init),
        ]
    )
    opt = bps.DistributedOptimizer(tf.keras.optimizers.SGD(0.01))

    rng = np.random.default_rng(0)
    x = tf.constant(rng.standard_normal((args.batch, args.dim)).astype(np.float32))
    y = tf.constant(rng.integers(0, 10, args.batch).astype(np.int64))
    loss_fn = tf.keras.losses.SparseCategoricalCrossentropy(from_logits=True)

    # one-shot broadcast so every worker starts from rank 0's weights
    if bps.size() > 1:
        bps.broadcast_variables(model.weights, root_rank=0)

    def train_step():
        with tf.GradientTape() as tape:
            loss = loss_fn(y, model(x))
        grads = tape.gradient(loss, model.trainable_variables)
        opt.apply_gradients(zip(grads, model.trainable_variables))
        return loss

    train_step()  # warmup
    t0 = time.perf_counter()
    for _ in range(args.iters):
        loss = train_step()
    dt = time.perf_counter() - t0
    print(
        f"rank {bps.rank()}/{bps.size()}: "
        f"{args.batch * args.iters / dt:.1f} samples/s, loss {float(loss):.4f}"
    )
    bps.shutdown()


if __name__ == "__main__":
    main()
