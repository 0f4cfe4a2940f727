"""Long-context demo: ring attention over an sp mesh axis.

Attention over a sequence far larger than any single device's comfortable
attention window: the sequence is sharded into contiguous blocks across
the ``sp`` axis and KV blocks rotate around the ring (lax.ppermute) with
online-softmax accumulation — peak per-device score memory is
O(S_local²), independent of total S.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/long_context_ring.py --seq 4096
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.parallel.ring_attention import ring_attention


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dh", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--impl", choices=["ring", "ulysses"], default="ring",
                    help="sequence-parallel strategy (ulysses needs "
                    "heads divisible by the device count)")
    args = ap.parse_args()

    devices = jax.devices()
    sp = len(devices)
    if args.seq % sp:
        raise SystemExit(f"--seq must divide the {sp}-device ring")
    mesh = Mesh(np.array(devices), ("sp",))
    s_local = args.seq // sp
    print(f"{args.impl} over {sp} devices, {args.seq} total tokens, {s_local}/device")

    rng = np.random.default_rng(0)
    shape = (args.batch, args.heads, args.seq, args.dh)
    q = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    k = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    v = jnp.asarray(rng.normal(size=shape).astype(np.float32))

    if args.impl == "ulysses":
        from byteps_tpu.parallel.ulysses import ulysses_attention

        attend = lambda q, k, v: ulysses_attention(q, k, v, "sp", sp, causal=True)  # noqa: E731
    else:
        attend = lambda q, k, v: ring_attention(q, k, v, "sp", sp, causal=True)  # noqa: E731
    fn = jax.jit(
        jax.shard_map(
            attend,
            mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )
    )
    out = fn(q, k, v)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(q, k, v)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"{args.impl} attention: {dt * 1e3:.1f} ms/step, output {out.shape}")

    # spot-check against dense attention on the gathered sequence
    scores = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) / np.sqrt(args.dh)
    mask = np.tril(np.ones((args.seq, args.seq), bool))
    scores = np.where(mask, scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))
    err = np.abs(np.asarray(out) - ref).max()
    print(f"max abs err vs dense: {err:.2e}")
    assert err < 1e-3


if __name__ == "__main__":
    main()
