"""The full two-level topology in one training loop: mesh collectives
inside the host + PS push_pull across hosts — the reference's defining
architecture (docs/architecture.md:26-44: intra-machine NCCL reduce,
then inter-machine PS push/pull), TPU-translated: the mesh's psum rides
ICI, the host hop rides DCN through the PS plane.

Single process demo (1 worker — the PS hop is an identity average):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/hybrid_mesh_ps.py

Real cluster: start a scheduler + server(s) and N workers with the
DMLC_* env (``python -m byteps_tpu.launcher.launch``); each worker runs
this script unchanged and the PS hop averages gradients across workers.

    python examples/hybrid_mesh_ps.py --steps 20 --dp 2 --tp 2
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

import byteps_tpu as bps
from byteps_tpu.parallel.hybrid import HybridDataParallel


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()

    bps.init()
    n_dev = args.dp * args.tp
    if len(jax.devices()) < n_dev:
        raise SystemExit(
            f"need {n_dev} devices for dp={args.dp}×tp={args.tp}; "
            f"have {len(jax.devices())} (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU)"
        )
    mesh = Mesh(
        np.array(jax.devices()[:n_dev]).reshape(args.dp, args.tp), ("dp", "tp")
    )

    # Megatron block: column-parallel w1, row-parallel w2
    rng = np.random.default_rng(bps.rank())
    r0 = np.random.default_rng(0)
    params = {
        "w1": r0.normal(0, 0.1, (args.dim, args.hidden)).astype(np.float32),
        "w2": r0.normal(0, 0.1, (args.hidden, args.dim)).astype(np.float32),
    }
    specs = {"w1": P(None, "tp"), "w2": P("tp", None)}

    def loss_fn(p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["w1"])
        o = lax.psum(h @ p["w2"], "tp")
        return jnp.mean((o - y) ** 2)

    hdp = HybridDataParallel(
        loss_fn, params, optax.sgd(0.1), mesh=mesh,
        param_specs=specs, batch_spec=(P("dp"), P("dp")),
    )
    x = rng.normal(size=(args.batch, args.dim)).astype(np.float32)
    y = 0.1 * rng.normal(size=(args.batch, args.dim)).astype(np.float32)
    for step in range(args.steps):
        loss = hdp.step((x, y))
        if step % 5 == 0 or step == args.steps - 1:
            print(f"[rank {bps.rank()}] step {step:3d} loss {loss:.6f}")
    bps.shutdown()
    print(f"[rank {bps.rank()}] done — ICI pmean + PS push_pull in every step")


if __name__ == "__main__":
    main()
