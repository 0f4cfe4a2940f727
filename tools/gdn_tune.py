"""On-chip sizing of the gated delta rule: XLA's chunked form against the
Pallas kernels (ops/gated_delta.py, ops/gated_delta_kernels.py), the chunks a
grid step of each kernel swept, and the linear mixer's whole ``gdn_scan``
part around them.

Times forward + all five gradients of ``sum(o**2)`` at the shape one layer of
``qwen3_next_80b_ep32`` runs — one sequence of 16 384 tokens, 16 | 32 heads of
128 | 128 token-major, chunk 64, bf16 — for XLA's form and for the kernels,
and each kernel alone at every block size; then ``mixer_ms``: forward + every
gradient of ``models/delta_moe._delta_scan`` (the projection's output in,
``w_out``'s operand out: convolution, silu, l2 norms, the rule, the gated
norm) at the same shape, so ``mixer_ms − kernels_ms`` is what XLA does around
the kernels.  Prints one JSON line; the best blocks go to ops/gdn_blocks.json
(with the sweep's shape and milliseconds in ``meta``), where
``gated_delta.tuned_blocks`` finds them.  Refuses to run off a TPU: a CPU
timing says nothing of Mosaic.

    python tools/gdn_tune.py [--shape 1,16,32,16384,128,128] [--blocks 4,8,16]

(``--shape``: batch, key heads, value heads, sequence, d_k, d_v.)

``--channel`` sizes the rule with a decay a KEY CHANNEL instead
(ops/kda_kernels.py; g (B, S, H, d_k), as many key heads as value heads) at
the shape one layer of ``kimi_linear_48b_ep32`` runs: XLA's channel form (a
block of heads at a time), the three ``kda_*`` kernels, and
``models/channel_delta_moe._delta_scan`` around them; the winners go to the
table's ``channel_blocks`` | ``channel_meta``:

    python tools/gdn_tune.py --channel --shape 1,32,32,16384,128,128

``--conv`` times the pass BEFORE the rule instead (ops/causal_conv.py:
depthwise causal convolution + bias + silu + a head's l2 norm, taken from a
column range of a projection's output): forward + every gradient of
``sum(y**2)`` for XLA's form and for the two kernels at every block of
``--conv-blocks`` (rows x lanes x a chunk's rows), and the forward alone, one JSON line a
range; nothing is written.  A range is ``batch,sequence,width,lo,hi,l2_head,
bias`` — the projection's width, the columns, a head's size or 0, 1 for a
bias — and any family's fits: qwen3-next's q is the default, its v
``1,16384,12288,4096,8192,0,0``, nemotron's x ``2,8192,10304,4096,8192,0,1``:

    python tools/gdn_tune.py --conv 1,16384,12288,0,2048,128,0 [more ranges]

``--rehearse`` is the CPU pre-flight of the same control flow (the Pallas
interpreter at a toy length, nothing written): counts and control flow only.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from tools.timing import timed  # noqa: E402


def conv_lines(args, device, interpret) -> int:
    """``--conv``: a JSON line a range, XLA's form beside the kernels."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.ops import causal_conv as cc

    steps = 1 if args.rehearse else args.steps
    cdt = jnp.float32 if interpret else jnp.bfloat16
    sweep = [tuple(int(v) for v in pair.split("x")) for pair in args.conv_blocks.split(",")]

    def ms(fn, *xs):
        return round(timed(jax.jit(fn), *xs, steps=steps), 3)

    for spec in args.conv:
        b, s, width, lo, hi, l2_head, with_bias = (int(v) for v in spec.split(","))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        wide = jax.random.normal(ks[0], (b, s, width)).astype(cdt)
        small = (jax.random.normal(ks[1], (4, hi - lo)) * 0.5,
                 *((jax.random.normal(ks[2], (hi - lo,)),) if with_bias else ()))

        def run(**how):
            return lambda wide, *small: cc.conv_silu(
                wide, *small, lo=lo, hi=hi, l2_head=l2_head or None, scale=0.5, **how)

        def xla(wide, taps, bias=None):  # XLA's form, on any platform
            return cc._xla_form(wide, taps, bias, lo, hi, l2_head or None, 0.5)

        def whole(fn):
            return jax.value_and_grad(lambda *xs: jnp.sum(fn(*xs).astype(jnp.float32) ** 2),
                                      argnums=tuple(range(1 + len(small))))

        xla_ms, xla_forward_ms = ms(whole(xla), wide, *small), ms(xla, wide, *small)
        by_blocks = {}
        for blocks in sweep:
            how = dict(interpret=interpret, blocks=blocks)
            fit = cc._blocks(wide, small[0], lo, hi, l2_head or None, 0.5, interpret, blocks)
            if fit is None or f"{fit.rows}x{fit.lanes}x{fit.chunk}" in by_blocks:
                continue
            by_blocks[f"{fit.rows}x{fit.lanes}x{fit.chunk}"] = {
                "ms": ms(whole(run(**how)), wide, *small),
                "forward_ms": ms(run(**how), wide, *small)}
        print(json.dumps({
            "device": f"{device.platform}:{device.device_kind}", "rehearsal": args.rehearse,
            "conv": [b, s, width, lo, hi, l2_head, with_bias], "dtype": jnp.dtype(cdt).name,
            "what": "forward + every gradient of sum(y**2), ms a call; forward_ms: the "
                    "forward alone; by_blocks: the kernels at rows x lanes a block x rows a chunk",
            "xla_ms": xla_ms, "xla_forward_ms": xla_forward_ms, "by_blocks": by_blocks}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--conv", nargs="*", default=None, metavar="RANGE",
                    help="time ops/causal_conv.py instead: batch,sequence,width,lo,hi,"
                    "l2_head,bias a range (default: qwen3-next's q)")
    ap.add_argument("--conv-blocks", default="512x512x64,512x512x128,512x512x256,512x512x512,"
                    "256x512x128,1024x512x256",
                    help="rows x lanes of a block x rows of a chunk to try")
    ap.add_argument("--shape", default="1,16,32,16384,128,128",
                    help="batch, key heads, value heads, sequence, d_k, d_v")
    ap.add_argument("--channel", action="store_true",
                    help="the rule with a decay a key channel (ops/kda_kernels.py)")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--blocks", default="4,8,16", help="chunks a grid step to try")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default="", help="where to write the winners "
                    "(default: ops/gdn_blocks.json)")
    ap.add_argument("--no-write", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU pre-flight: the interpreter, one step, nothing written")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from byteps_tpu.models import channel_delta_moe as cdm
    from byteps_tpu.models import delta_moe as dm
    from byteps_tpu.ops import gated_delta as gd
    from byteps_tpu.ops import gated_delta_kernels, kda_kernels

    gk = kda_kernels if args.channel else gated_delta_kernels

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print("not on a TPU — refusing (kernel timings need real Mosaic); "
              "--rehearse runs the control flow on the CPU", file=sys.stderr)
        return 2
    interpret = device.platform != "tpu"
    if args.conv is not None:
        args.conv = args.conv or ["1,16384,12288,0,2048,128,0"]
        return conv_lines(args, device, interpret)
    b, hk, hv, s, dk, dv = (int(x) for x in args.shape.split(","))
    chunk, steps = args.chunk, 1 if args.rehearse else args.steps
    cdt = jnp.float32 if interpret else jnp.bfloat16  # the CPU has no bf16 batched products
    n = s // chunk
    sizes = [nb for nb in (int(x) for x in args.blocks.split(",")) if n % nb == 0]

    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(jax.random.normal(ks[0], (b, s, hk, dk))) * dk ** -0.5).astype(cdt)
    k = unit(jax.random.normal(ks[1], (b, s, hk, dk))).astype(cdt)
    v = jax.random.normal(ks[2], (b, s, hv, dv)).astype(cdt)
    g = -0.1 * jax.nn.softplus(jax.random.normal(
        ks[3], (b, s, hv) + ((dk,) if args.channel else ())))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))

    def ms(fn, *xs):
        return round(timed(jax.jit(fn), *xs, steps=steps), 3)

    def whole(rule):
        # all five gradients: with fewer XLA drops what only the others need
        return jax.value_and_grad(lambda *a: jnp.sum(rule(*a) ** 2), argnums=(0, 1, 2, 3, 4))

    # each kernel alone, on what the kernels take: q, k, v token-major with
    # the heads side by side, g and beta a row a value head
    flat = (q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk), v.reshape(b, s, hv * dv),
            g.reshape(b, s, hv * dk) if args.channel else gk.by_head(g), gk.by_head(beta))
    t = jax.jit(lambda k, g, beta: gk._chunk_inverse(k, g, beta, hk, chunk, sizes[0], interpret))(
        flat[1], flat[3], flat[4])
    _, entering = jax.jit(lambda *a: gk._scan_forward(*a, hk, chunk, sizes[0], True, interpret))(
        *flat, t)
    do = jax.random.normal(ks[5], (b, s, hv * dv))
    by_kernel = {gk.INVERSE_KERNEL: {}, gk.FWD_KERNEL: {}, gk.BWD_KERNEL: {}}
    for nb in sizes:
        if nb % max(gk.STACK // chunk, 1) == 0:
            by_kernel[gk.INVERSE_KERNEL][nb] = ms(
                lambda k, g, beta: gk._chunk_inverse(k, g, beta, hk, chunk, nb, interpret),
                flat[1], flat[3], flat[4])
        by_kernel[gk.FWD_KERNEL][nb] = ms(
            lambda *a: gk._scan_forward(*a, hk, chunk, nb, True, interpret), *flat, t)
        by_kernel[gk.BWD_KERNEL][nb] = ms(
            lambda *a: gk._scan_backward(*a, hk, chunk, nb, interpret), *flat, t, entering, do)
    best = tuple(min(by_kernel[name], key=by_kernel[name].get)
                 for name in (gk.INVERSE_KERNEL, gk.FWD_KERNEL, gk.BWD_KERNEL))

    xla_form = ((lambda *a: gd._by_head_blocks(*a, chunk, gd.SUB_CHUNK, cdt)) if args.channel
                else (lambda *a: gd._chunked_xla(*a, chunk, cdt)))
    xla_ms = ms(whole(xla_form), q, k, v, g, beta)
    kernels_ms = ms(whole(lambda *a: gd.chunked_gated_delta_rule(
        *a, chunk=chunk, compute_dtype=cdt, interpret=interpret, blocks=best)), q, k, v, g, beta)

    # the mixer's whole gdn_scan | kda_scan part around the rule, the kernels at
    # the committed table's blocks (what a train step takes)
    # (a rehearsal's mixer takes XLA's form of the rule: _kernel_path's call)
    if args.channel:
        cfg = cdm.ChannelDeltaMoEConfig(lin_heads=hv, lin_k_dim=dk, lin_v_dim=dv, chunk=chunk,
                                        compute_dtype=cdt, max_seq=s)
        lp = {"conv": jax.random.normal(ks[6], (cfg.conv_kernel, cfg.lin_channels)) * 0.5,
              "a_log": jnp.zeros((hv,)), "dt_bias": jnp.zeros((hv * dk,)),
              "o_norm": jnp.ones((dv,))}
        qkv = jax.random.normal(ks[7], (b, s, cfg.lin_channels)).astype(cdt)
        narrow = jax.random.normal(ks[8], (b, s, hv * (dk + dv + 1)))
        mixer_ms = ms(jax.value_and_grad(
            lambda qkv, narrow, lp: jnp.sum(cdm._delta_scan(
                cfg, qkv, narrow[..., :hv * dk] - 3.0, narrow[..., hv * dk:hv * (dk + dv)],
                narrow[..., hv * (dk + dv):], lp).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)), qkv, narrow, lp)
    else:
        cfg = dm.DeltaMoEConfig(lin_k_heads=hk, lin_v_heads=hv, lin_k_dim=dk, lin_v_dim=dv,
                                chunk=chunk, compute_dtype=cdt, max_seq=s)
        lp = {"conv": jax.random.normal(ks[6], (cfg.conv_kernel, cfg.lin_channels)) * 0.5,
              "a_log": jnp.zeros((hv,)), "dt_bias": jnp.ones((hv,)), "gdn_norm": jnp.ones((dv,))}
        qkvz = jax.random.normal(ks[7], (b, s, cfg.lin_channels + hv * dv)).astype(cdt)
        ba = jax.random.normal(ks[8], (b, s, 2 * hv))
        mixer_ms = ms(jax.value_and_grad(
            lambda qkvz, ba, lp: jnp.sum(
                dm._delta_scan(cfg, qkvz, ba, lp).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)), qkvz, ba, lp)

    line = {
        "device": f"{device.platform}:{device.device_kind}", "rehearsal": args.rehearse,
        "shape": [b, hk, hv, s, dk, dv], "chunk": chunk, "dtype": jnp.dtype(cdt).name,
        "what": "forward + every gradient of sum(o**2), ms a call; by_kernel: one kernel "
                "alone; mixer_ms: the family's _delta_scan, the rule inside it",
        "channel": args.channel,
        "xla_ms": xla_ms, "kernels_ms": kernels_ms, "mixer_ms": mixer_ms,
        "around_kernels_ms": round(mixer_ms - kernels_ms, 3), "blocks": list(best),
        "by_kernel": {name: {str(nb): t_ms for nb, t_ms in times.items()}
                      for name, times in by_kernel.items()},
    }
    if not (args.no_write or args.rehearse):
        path = args.out or gd._TUNED_PATH  # producer and consumer share one location
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
        section, meta = ("channel_blocks", "channel_meta") if args.channel else ("blocks", "meta")
        doc.setdefault(section, {})[str(s)] = list(best)
        doc.setdefault(meta, {})[str(s)] = {k_: line[k_] for k_ in (
            "shape", "chunk", "dtype", "xla_ms", "kernels_ms", "mixer_ms", "by_kernel")}
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
