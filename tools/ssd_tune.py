"""On-chip sizing of the selective state-space scan: XLA's chunked form
against the Pallas kernels (ops/ssd.py, ops/ssd_kernels.py), the chunks a
grid step of each kernel swept, and the Mamba-2 mixer's whole ``ssd_scan``
part around them.

Times forward + all five gradients of ``sum(y**2)`` at the shape one layer of
``nemotron_twotower_30b_ep16`` runs — two sequences of 8192 tokens, 64 heads
of 64 in 8 groups with a state of 128, token-major, chunk 128, bf16 — for
XLA's form and for the kernels, and each kernel alone at every block size;
then ``mixer_ms``: forward + every gradient of ``models/ssm_moe._ssd_part``
(``in_proj``'s output in, ``out_proj``'s operand out: convolution, bias, silu,
softplus, the scan, ``D x``, the gated norm) at the same shape, no
recomputation, so ``mixer_ms − kernels_ms`` is what XLA does around the
kernels.  Prints one JSON line; the winners are ``ops/ssd.KERNEL_BLOCKS``
(a constant: one sequence length is run).  Refuses to run off a TPU: a CPU
timing says nothing of Mosaic.

    python tools/ssd_tune.py [--shape 2,8192,64,64,8,128] [--blocks 2,4,8,16]

(``--shape``: batch, sequence, heads, head size, groups, state.)

``--rehearse`` is the CPU pre-flight of the same control flow (the Pallas
interpreter at a toy length): counts and control flow only.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from tools.timing import timed  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="2,8192,64,64,8,128",
                    help="batch, sequence, heads, head size, groups, state")
    ap.add_argument("--blocks", default="2,4,8,16", help="chunks a grid step to try")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU pre-flight: the interpreter, one step")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from byteps_tpu.models import ssm_moe as sm
    from byteps_tpu.ops import ssd
    from byteps_tpu.ops import ssd_kernels as sk
    from byteps_tpu.ops._chunk import by_head

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print("not on a TPU — refusing (kernel timings need real Mosaic); "
              "--rehearse runs the control flow on the CPU", file=sys.stderr)
        return 2
    interpret = device.platform != "tpu"
    b, s, h, p, g, n = (int(v) for v in args.shape.split(","))
    chunk, steps = ssd.CHUNK, 1 if args.rehearse else args.steps
    cdt = jnp.float32 if interpret else jnp.bfloat16  # the CPU has no bf16 batched products
    sizes = [nb for nb in (int(v) for v in args.blocks.split(",")) if (s // chunk) % nb == 0]

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (b, s, h * p)).astype(cdt)
    bb, cc = ((jax.random.normal(k, (b, s, g * n)) * n ** -0.5).astype(cdt) for k in ks[1:3])
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[4], (h,), minval=0.0, maxval=2.77))

    def ms(fn, *xs):
        return round(timed(jax.jit(fn), *xs, steps=steps), 3)

    def whole(scan):
        # all five gradients: with fewer XLA drops what only the others need
        return jax.value_and_grad(lambda *xs: jnp.sum(scan(*xs) ** 2), argnums=(0, 1, 2, 3, 4))

    # each kernel alone, on what the kernels take: dt and the log-decay a row a head
    rows = by_head(dt)
    flat = (x, bb, cc, rows, rows * jnp.tile(a, b)[:, None])
    _, entering = jax.jit(lambda *xs: sk._scan_forward(*xs, g, chunk, sizes[0], True, interpret))(
        *flat)
    dy = jax.random.normal(ks[5], (b, s, h * p))
    by_kernel = {sk.FWD_KERNEL: {}, sk.BWD_KERNEL: {}}
    for nb in sizes:
        by_kernel[sk.FWD_KERNEL][nb] = ms(
            lambda *xs: sk._scan_forward(*xs, g, chunk, nb, True, interpret), *flat)
        by_kernel[sk.BWD_KERNEL][nb] = ms(
            lambda *xs: sk._scan_backward(*xs, g, chunk, nb, interpret), *flat, entering, dy)
    best = tuple(min(times, key=times.get) for times in by_kernel.values())

    xla_ms = ms(whole(lambda x, dt, a, bb, cc: ssd._chunked_xla(x, dt, a, bb, cc, h, g, chunk,
                                                                cdt)), x, dt, a, bb, cc)
    kernels_ms = ms(whole(lambda x, dt, a, bb, cc: ssd.ssd_scan(
        x, dt, a, bb, cc, h, g, chunk, cdt, interpret=interpret, blocks=best)), x, dt, a, bb, cc)

    # the mixer's whole ssd_scan part around the scan, the kernels at the
    # committed blocks (what a train step takes; a rehearsal's takes XLA's
    # form: _kernel_path's call)
    cfg = sm.SsmMoEConfig(ssm_heads=h, ssm_head_dim=p, ssm_groups=g, ssm_state=n, chunk=chunk,
                          compute_dtype=cdt, max_seq=s)
    lp = {"conv": jax.random.normal(ks[6], (cfg.conv_kernel, cfg.conv_channels)) * 0.5,
          "conv_bias": jnp.zeros((cfg.conv_channels,)), "dt_bias": jnp.zeros((h,)),
          "a_log": jnp.log(-a), "d_skip": jnp.ones((h,)), "gate_norm": jnp.ones((h * p,))}
    zxbcdt = jax.random.normal(ks[7], (b, s, h * p + cfg.conv_channels + h)).astype(cdt)
    mixer_ms = ms(jax.value_and_grad(
        lambda zxbcdt, lp: jnp.sum(sm._ssd_part(cfg, zxbcdt, lp).astype(jnp.float32) ** 2),
        argnums=(0, 1)), zxbcdt, lp)

    print(json.dumps({
        "device": f"{device.platform}:{device.device_kind}", "rehearsal": args.rehearse,
        "shape": [b, s, h, p, g, n], "chunk": chunk, "dtype": jnp.dtype(cdt).name,
        "what": "forward + every gradient of sum(y**2), ms a call; by_kernel: one kernel "
                "alone; mixer_ms: models/ssm_moe._ssd_part, the scan inside it",
        "xla_ms": xla_ms, "kernels_ms": kernels_ms, "mixer_ms": mixer_ms,
        "around_kernels_ms": round(mixer_ms - kernels_ms, 3), "blocks": list(best),
        "committed_blocks": list(ssd.KERNEL_BLOCKS),
        "by_kernel": {name: {str(nb): t for nb, t in times.items()}
                      for name, times in by_kernel.items()},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
