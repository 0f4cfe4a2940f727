"""On-chip flash-attention block-size sweep vs the dense reference.

Times fwd+bwd (value_and_grad of a sum-of-squares: the forward kernel and
the one backward kernel, which share a (block_q, block_k)) and the forward
alone, across block candidates and sequence lengths, against XLA's fused
dense attention — the data behind TransformerConfig.use_flash defaults.
Refuses to run off-TPU (CPU timings say nothing about Mosaic).

    python tools/flash_tune.py [--seqs 512,1024,2048,4096] [--bh 8,4]

The sweeps behind ops/flash_blocks.json's 8192 and 16384 entries, at the
three cells' shapes (latent attention: 192 for q·k, 128 for v; gated
attention 256 | 256; grouped-query 64 | 64 — the dense path cannot hold
8k scores); the table is keyed by the sequence alone, so where two shapes
of one sequence disagree the entry is the one that costs the cells least:

    python tools/flash_tune.py --seqs 8192 --bh 2,32 --dh 192 --dv 128 \
        --blocks 256,512,1024 --no-dense --out chiprun_out/flash_blocks.json
    python tools/flash_tune.py --seqs 16384 --bh 1,16 --dh 256 --dv 256 ...
    python tools/flash_tune.py --seqs 8192 --bh 2,32 --dh 64 --dv 64 ...

``--window W`` sweeps the BANDED kernels (query i sees key j iff 0 <= i - j <
W; ``flash_fwd_win`` / ``flash_bwd_win``) and writes the winners to the
artifact's second table, ``banded``, keyed by "sequence,window"; the plain
entries stay as they are.  The sweep behind its (16384, 2048) entry, the
sliding-window layers of ``trinity_mini_ep16_train16k``; ``--check`` first
holds the kernels at each block pair to the dense mask (out, dQ, dK, dV,
queries a block at a time in f32), and ``--causal-too`` times the full causal
kernels at the sequence's plain entry beside them:

    python tools/flash_tune.py --seqs 16384 --bh 1,32 --dh 128 --window 2048 \
        --blocks 256,512,1024 --no-dense --check --causal-too \
        --out chiprun_out/flash_blocks.json

``--kv-heads N`` makes K and V at N heads (a count that divides the query
heads): the kernels find a query head's key/value head by index map, and
every timing is printed beside the same call on heads REPEATED by the caller
(``jnp.repeat`` and, backward, its transpose's sum: what a mixer paid before
the kernels took grouped heads).  The sizing of Trinity's mixers, 32 | 4:

    python tools/flash_tune.py --seqs 16384 --bh 1,32 --kv-heads 4 --dh 128 \
        --window 2048 --blocks 1024 --no-dense --check --causal-too --no-write

``--mla-heads`` times no flash kernel but the pass that stands before and
after them in the latent-attention mixer (``ops/mla_heads.py``: the four
token-major products → q | k | v head-major, and its transpose), forward alone
and forward + backward, at ``--blocks`` rows a block, beside XLA's form of the
same equations, each held to the other first; times are the device's busy time
in a profile of the calls, and GB/s the bytes the pass must move (every
operand and result once) over them.  JoyAI-LLM-Flash's mixer:

    python tools/flash_tune.py --mla-heads --seqs 8192 --bh 2,32 --blocks 256,512,1024

Every line of every mode prints the entries a head's call computes | the
entries its mask keeps and their ratio (``ops/flash_attention.computed_entries``:
a tile the mask crosses is walked by sub-blocks, so fewer than whole tiles'),
and which of out, dQ, dK, dV are bit for bit the whole-tile path's (``=``) or
equal to rounding (``~``).  ``--sub-blocks 0,128,256,512`` times each line
also at those sub-block sides (0: whole tiles), ``--min-spared`` at those least
shares a kind of tile must spare, ``--strips qk,qq,kk`` at those cuts (forward
then backward: strips of query rows or of keys) — beside the module's
committed constants, the only variant a winner is chosen at; ``--check`` |
``--check-rows`` hold every variant to the dense mask first.  The sweep behind
``SUB_BLOCK``, ``MIN_SPARED`` and ``STRIPS`` (PR 65; its numbers are in
flash_blocks.json's source texts), a line a shape:

    python tools/flash_tune.py --no-dense --no-write --blocks 1024 --check \
        --sub-blocks 0,128,256,512 --min-spared 0.2 --strips qk \
        --seqs 16384 --bh 2,28 --kv-heads 4 --dh 128 --window 4096
    ... --seqs 16384 --bh 1,32 --dh 128 --window 2048
    ... --seqs 8192 --bh 2,32 --dh 192 --dv 128          (and --bh 1,16 --dh 128)
    ... --block-diffusion 4 --seqs 16384 --bh 1,32 --kv-heads 4 --dh 128 \
        --check-rows 3072 --min-spared 0.3,0.45

``--block-diffusion B`` sweeps the BLOCK-DIFFUSION kernels (``flash_fwd_bd`` |
``flash_bwd_bd``: ``--seqs`` are KEY rows, 2L — a noised and a clean copy of L
tokens under ``ops/flash_attention.block_diffusion_visible`` at block length
B) with both copies' queries, times the noised copy's queries alone at the
winner (a last layer's call) and, with ``--causal-too``, the full causal
kernels over the same 2L rows (the yardstick: the mask keeps half a causal
mask's entries); ``--check-rows N`` first holds every tile pair to the dense mask
at N key rows (out, lse, dQ, dK, dV; f32 scores stand whole there, so N is
small).  Winners go to the artifact's third table, ``block_diffusion``, keyed
"key rows,block length".  SDAR's mixer, 32 | 4 heads of 128 over 2 x 8192:

    python tools/flash_tune.py --block-diffusion 4 --seqs 16384 --bh 1,32 --kv-heads 4 \
        --dh 128 --blocks 512,1024 --check-rows 3072 --causal-too --out chiprun_out/flash_blocks_bd.json
"""

import argparse
import contextlib
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from tools.timing import timed  # noqa: E402


def sub_block_variants(args, fa) -> list:
    """The (label, sub-block side, least share spared, strips) a line is timed
    at: the module's committed constants first — the only variant a winner is
    chosen at —, then ``--sub-blocks`` x ``--min-spared`` x ``--strips``.  Side 0
    is the whole-tile path (no tile lines up with a side it cannot hold)."""
    committed = (fa.SUB_BLOCK, fa.MIN_SPARED, "".join(fa.STRIPS[d] for d in ("fwd", "bwd")))
    found = [("committed",) + committed]
    for side in (int(x) for x in args.sub_blocks.split(",") if x):
        for least in [float(x) for x in args.min_spared.split(",") if x] or (committed[1],):
            for strips in [x for x in args.strips.split(",") if x] or (committed[2],):
                label = f"side {side} spared>={least} strips {strips}" if side else "whole tiles"
                if (side, least, strips) != committed and label not in [f[0] for f in found]:
                    found.append((label, side, least, strips))
    return found


@contextlib.contextmanager
def applied(fa, variant):
    """``fa``'s three constants at a variant's, for the calls traced inside."""
    _, side, least, strips = variant
    was = fa.SUB_BLOCK, fa.MIN_SPARED, dict(fa.STRIPS)
    fa.SUB_BLOCK, fa.MIN_SPARED = side or 1 << 30, least
    fa.STRIPS.update(fwd=strips[0], bwd=strips[1])
    try:
        yield
    finally:
        fa.SUB_BLOCK, fa.MIN_SPARED = was[:2]
        fa.STRIPS.update(was[2])


def entries_note(fa, sq, sk, bq, bk, **mask) -> str:
    """"computed | kept entries and their ratio" of a call at the constants in
    force, beside the whole-tile count (``fa.computed_entries``)."""
    computed, kept = fa.computed_entries(sq, sk, bq, bk, **mask)
    whole, _ = fa.computed_entries(sq, sk, bq, bk, whole_tiles=True, **mask)
    return (f"entries a head: computed {computed} | kept {kept} = {kept / computed:.4f} kept "
            f"(whole tiles computed {whole}: {computed / whole:.4f} of them)")


def same_bits(got, want) -> str:
    """Which of a call's results are bit for bit another path's."""
    import jax.numpy as jnp

    return " ".join(f"{name} {'=' if bool(jnp.all(g == w)) else '~'}"
                    for name, g, w in zip(("out", "dQ", "dK", "dV"), got, want))


def time_mla_heads(args) -> int:
    """``--mla-heads``: the pass of ops/mla_heads.py, kernels beside XLA's form."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.ops import mla_heads as mh

    b, h = (int(x) for x in args.bh.split(","))
    n, r, d_v, theta = 128, 64, 128, 32e6
    rng = np.random.default_rng(0)
    make = lambda *dims: jnp.asarray(rng.normal(size=dims).astype(np.float32), jnp.bfloat16)  # noqa: E731

    def pass_ms(fn, *xs):
        """Alone the pass reads 3.2 ms each way (the device's busy time in a
        profile of the calls says the same); inside the cell's step a trace
        shows the same kernels at 1.7–1.9 ms (PERF.md §6 PR 50, §7): size the
        pass from a traced step."""
        return timed(jax.jit(fn), *xs, steps=args.steps)

    for s in (int(x) for x in args.seqs.split(",")):
        xs = (make(b, s, h * n), make(b, s, h * r), make(b, s, h * n), make(b, s, h * d_v),
              make(b, s, r))
        cts = (make(b, h, s, n + r), make(b, h, s, n + r), make(b, h, s, d_v))
        moved = 2 * sum(x.size for x in xs + cts)  # bytes a pass reads and writes, bf16

        def both(forward):
            """``forward`` and its transpose on ``cts``, every array an argument."""
            def run(cts, *xs):
                out, back = jax.vjp(forward, *xs)
                return out, back(cts)
            return run

        xla = lambda *xs: mh._forward(*xs, h, mh.rope_tables(s, r, theta))  # noqa: E731
        want = jax.jit(both(xla))(cts, *xs)
        if not args.rehearse:
            print(f"seq {s} XLA's form: forward {pass_ms(xla, *xs):7.3f} ms, with its transpose "
                  f"{pass_ms(both(xla), cts, *xs):7.3f} ms")
        for rows in (int(x) for x in args.blocks.split(",")):
            if s % rows:
                continue
            mh.BLOCK_ROWS = rows
            kernels = lambda *xs: mh.mla_heads(*xs, h, theta, interpret=args.rehearse)  # noqa: E731
            got = jax.jit(both(kernels))(cts, *xs)
            off = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - w.astype(jnp.float32))))
                      for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
            # the kernels add the shared key's cotangent over the heads in
            # f32 and round once; XLA's form is the same sum in another order
            print(f"seq {s} rows {rows}: kernels off XLA's form by {off:.3g} at most")
            if not off < 0.5:
                raise SystemExit("the kernels disagree with XLA's form")
            if args.rehearse:
                continue
            fwd_ms, ms = pass_ms(kernels, *xs), pass_ms(both(kernels), cts, *xs)
            print(f"seq {s} rows {rows}: forward {fwd_ms:7.3f} ms = {moved / fwd_ms / 1e6:6.1f} "
                  f"GB/s, with its transpose {ms:7.3f} ms = {2 * moved / ms / 1e6:6.1f} GB/s "
                  f"({moved / 1e9:.3f} GB a pass)")
    return 0


def tune_block_diffusion(args) -> int:
    """The block-diffusion kernels' sweep (the module's docstring)."""
    import importlib
    import json

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    b, h = (int(x) for x in args.bh.split(","))
    dh, dv, h_kv, blk = args.dh, args.dv or args.dh, args.kv_heads or h, args.block_diffusion
    blocks = [int(x) for x in args.blocks.split(",")]
    rng = np.random.default_rng(0)

    def operands(sq, sk):
        return tuple(jnp.asarray(rng.normal(size=(b, heads, rows, d)).astype(np.float32) * 0.1,
                                 jnp.bfloat16)
                     for heads, rows, d in ((h, sq, dh), (h_kv, sk, dh), (h_kv, sk, dv)))

    def kernels(bq, bk):
        return lambda q, k, v: fa.block_diffusion_attention_lse(
            q, k, v, blk, block_q=bq, block_k=bk, interpret=args.rehearse)

    def time_fn(fn, *xs, grad=True):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)  # noqa: E731
        f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)) if grad else loss)
        return timed(f, *xs, steps=args.steps)

    def results(fn, *xs):
        """(out, dQ, dK, dV) of the sweep's loss."""
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        (_, out), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(*xs)
        return (out, *grads)

    variants = sub_block_variants(args, fa)
    if args.check_rows:
        sk = args.check_rows
        for sq in (sk, sk // 2):
            q, k, v = operands(sq, sk)
            ct = jnp.asarray(rng.normal(size=q.shape[:3] + (dv,)).astype(np.float32), jnp.bfloat16)
            cl = jnp.asarray(rng.normal(size=q.shape[:3]).astype(np.float32))

            def weighed(fn, q, k, v):
                out, lse = fn(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * ct) + jnp.sum(lse * cl), (out, lse)

            def dense(q, k, v):
                return fa._dense_block_diffusion_lse(
                    *(x.astype(jnp.float32) for x in (q, k, v)), blk, dh ** -0.5)

            with jax.default_matmul_precision("highest"):
                (_, aux), grads = jax.jit(jax.value_and_grad(
                    lambda q, k, v: weighed(dense, q, k, v), argnums=(0, 1, 2),
                    has_aux=True))(q, k, v)
            want = (*aux, *grads)
            for bq, bk, variant in ((bq, bk, x) for bq in blocks for bk in blocks
                                    for x in variants):
                if sq % bq or sk % bk:
                    continue
                with applied(fa, variant):
                    (_, aux), grads = jax.jit(jax.value_and_grad(
                        lambda q, k, v, f=kernels(bq, bk): weighed(f, q, k, v),
                        argnums=(0, 1, 2), has_aux=True))(q, k, v)
                    note = entries_note(fa, sq, sk, bq, bk, block_length=blk)
                off = [float(jnp.linalg.norm(a.astype(jnp.float32) - w)
                             / jnp.linalg.norm(w)) for a, w in zip((*aux, *grads), want)]
                print(f"{sq} queries, {sk} keys, blocks of {blk}, bq={bq} bk={bk} "
                      f"[{variant[0]}]: out, lse, dQ, dK, dV off the dense mask by "
                      f"{' '.join(f'{x:.2e}' for x in off)} (relative L2); {note}", flush=True)
                if not max(off) < 2e-2:  # bf16 operands against f32
                    raise SystemExit("the block-diffusion kernels disagree with the dense mask")

    winners = {}
    for sk in (int(x) for x in args.seqs.split(",")):
        q, k, v = operands(sk, sk)
        if args.causal_too:
            causal = lambda q, k, v: fa.flash_attention(  # noqa: E731
                q, k, v, causal=True, interpret=args.rehearse)
            ms, fwd_ms = time_fn(causal, q, k, v), time_fn(causal, q, k, v, grad=False)
            print(f"{sk} rows full causal kernels at the plain entry: {ms:8.2f} ms "
                  f"(forward alone {fwd_ms:6.2f})", flush=True)
        best = None
        for bq in blocks:
            for bk in blocks:
                if (sk // 2) % bq or sk % bk:
                    continue
                fn = lambda q, k, v, f=kernels(bq, bk): f(q, k, v)[0]  # noqa: E731
                with applied(fa, ("whole tiles", 0, 1.0, "qk")):
                    whole = results(fn, q, k, v)
                tiles = fa._bd_tiles(sk, sk // 2, blk, bq, bk)
                for variant in variants:
                    with applied(fa, variant):
                        try:
                            bits = same_bits(results(fn, q, k, v), whole)
                            ms, fwd_ms = time_fn(fn, q, k, v), time_fn(fn, q, k, v, grad=False)
                            last = time_fn(fn, q[:, :, :sk // 2], k, v)
                        except Exception as e:  # noqa: BLE001
                            print(f"{sk} key rows bq={bq} bk={bk} {variant[0]}: "
                                  f"{type(e).__name__}: {e}"[:300])
                            continue
                        note = entries_note(fa, sk, sk, bq, bk, block_length=blk)
                    tag = ""
                    if variant is variants[0] and (best is None or ms < best[0]):
                        best, tag = (ms, bq, bk, fwd_ms, last), " *"
                    print(f"{sk} key rows bq={bq} bk={bk} [{variant[0]}]: {ms:8.2f} ms (forward "
                          f"alone {fwd_ms:6.2f}; the noised copy's queries alone {last:8.2f}); "
                          f"{tiles['pairs']} tile pairs, grid steps {tiles['steps_f']} | "
                          f"{tiles['steps_b']}; {note}; against whole tiles {bits}{tag}",
                          flush=True)
        if best is not None:
            winners[f"{sk},{blk}"] = {"blocks": [best[1], best[2]], "flash_ms": round(best[0], 3),
                                      "fwd_ms": round(best[3], 3),
                                      "noisy_queries_ms": round(best[4], 3), "bh": args.bh,
                                      "dh": dh, "dv": dv, "kv_heads": h_kv}
    if winners and not args.no_write:
        path = args.out or fa._TUNED_PATH
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
        table, meta = doc.get("block_diffusion", {}), doc.get("block_diffusion_meta", {})
        for entry, w in winners.items():
            table[entry] = w.pop("blocks")
            meta[entry] = w
        with open(path, "w") as f:
            json.dump({**doc, "block_diffusion": table, "block_diffusion_meta": meta}, f, indent=1)
        print(f"wrote {len(winners)} block-diffusion entries -> {path}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="512,1024,2048,4096")
    ap.add_argument("--bh", default="8,4",
                    help="batch,heads used at every seq")
    ap.add_argument("--dh", type=int, default=64, help="head size of q and k")
    ap.add_argument("--dv", type=int, default=0, help="head size of v (0: as --dh)")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="heads of k and v (0: as q's); fewer are also timed repeated by the caller")
    ap.add_argument("--blocks", default="128,256,512")
    ap.add_argument("--no-dense", action="store_true",
                    help="skip the dense reference (its S x S scores do not fit at long S)")
    ap.add_argument("--out", default="",
                    help="where to write the winners (default: ops/flash_blocks.json)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--window", type=int, default=0,
                    help="sweep the banded kernels at this window (0: the full causal ones)")
    ap.add_argument("--check", action="store_true",
                    help="hold every block pair (at every variant) to the dense mask first")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU pre-flight of the control flow: kernels interpreted, nothing "
                         "written, never a timing")
    ap.add_argument("--causal-too", action="store_true",
                    help="with --window: also time the full causal kernels at the plain entry")
    ap.add_argument("--no-write", action="store_true",
                    help="don't persist winners to ops/flash_blocks.json")
    ap.add_argument("--mla-heads", action="store_true",
                    help="time ops/mla_heads.py's pass (heads of 128 | 64 | 128) and nothing else")
    ap.add_argument("--block-diffusion", type=int, default=0,
                    help="sweep the block-diffusion kernels at this block length (--seqs are "
                         "key rows, 2L) and nothing else")
    ap.add_argument("--sub-blocks", default="",
                    help="also time every line at these sub-block sides (0: whole tiles), "
                         "beside the module's committed constants")
    ap.add_argument("--min-spared", default="",
                    help="with --sub-blocks: at these least shares a kind must spare")
    ap.add_argument("--strips", default="",
                    help="with --sub-blocks: at these cuts, forward then backward (qk, qq, kk, kq)")
    ap.add_argument("--check-rows", type=int, default=0,
                    help="with --block-diffusion: first hold every tile pair to the dense mask "
                         "at this many key rows")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if (jax.devices()[0].platform == "tpu") == args.rehearse:
        print("not on TPU — refusing (flash timings need real Mosaic; --rehearse stays off it)")
        return 2
    if args.rehearse:
        args.no_write = True
    if args.mla_heads:
        return time_mla_heads(args)
    if args.block_diffusion:
        return tune_block_diffusion(args)

    import importlib

    from byteps_tpu.ops.flash_attention import flash_attention, _dense_reference

    # ops/__init__ re-exports the flash_attention FUNCTION, which shadows the
    # submodule in from-import; resolve the module itself
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    variants = sub_block_variants(args, fa)
    b, h = (int(x) for x in args.bh.split(","))
    dh, dv = args.dh, args.dv or args.dh
    h_kv = args.kv_heads or h
    group = h // h_kv
    blocks = [int(x) for x in args.blocks.split(",")]
    window = args.window or None

    def dense_banded_grads(q, k, v, ct, block=512):
        """(out, dQ, dK, dV) of sum(out * ct) under the band in f32, a block
        of queries at a time against the ``window + block`` keys that end
        with it (the keys padded by the window at the front: their positions
        are negative and nobody sees them)."""
        s = q.shape[2]
        window = args.window or s  # no window: causal, a band as wide as the sequence
        span, block = min(window, s), min(block, s)
        q, k, v, ct = (x.astype(jnp.float32) for x in (q, k, v, ct))
        pad = ((0, 0), (0, 0), (span, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)

        @jax.jit
        def one(q, k, v, ct, dk, dv_, first):
            cut = lambda x, n: jax.lax.dynamic_slice_in_dim(x, first, n, axis=2)  # noqa: E731

            def loss(qb, kb, vb):
                sc = jnp.einsum("bhqd,bhkd->bhqk", qb, kb, precision="highest") * dh ** -0.5
                qp = first + jnp.arange(block)[:, None]
                kp = first - span + jnp.arange(span + block)[None, :]
                seen = (kp >= 0) & (kp <= qp) & (kp > qp - window)
                p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
                out = jnp.einsum("bhqk,bhkd->bhqd", p, vb, precision="highest")
                return jnp.sum(out * cut(ct, block)), out

            (_, out), (gq, gk, gv) = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
                cut(q, block), cut(k, span + block), cut(v, span + block))
            add = lambda acc, g: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
                acc, cut(acc, span + block) + g, first, axis=2)
            return out, gq, add(dk, gk), add(dv_, gv)

        outs, dqs = [], []
        dk, dv_ = jnp.zeros_like(k), jnp.zeros_like(v)
        for first in range(0, s, block):
            out, gq, dk, dv_ = one(q, k, v, ct, dk, dv_, first)
            outs.append(out)
            dqs.append(gq)
        return jnp.concatenate(outs, 2), jnp.concatenate(dqs, 2), dk[:, :, span:], dv_[:, :, span:]

    def time_fn(fn, *xs, grad=True):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)  # noqa: E731
        f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)) if grad else loss)
        return timed(f, *xs, steps=args.steps)

    def results(fn, q, k, v):
        """(out, dQ, dK, dV) of sum(out * ct), ``--check``'s loss."""
        def weighed(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * ct), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            weighed, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out, *grads)

    def repeated(fn):
        """``fn`` on key/value heads the caller repeats for their groups."""
        return lambda q, k, v: fn(q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1))

    def beside_repeated(fn, *xs):
        if group == 1:
            return ""
        ms, fwd_ms = time_fn(repeated(fn), *xs), time_fn(repeated(fn), *xs, grad=False)
        return f"; heads repeated by the caller {ms:8.2f} ms (forward alone {fwd_ms:6.2f})"

    rng = np.random.default_rng(0)
    winners = {}   # seq -> {blocks, flash_ms, dense_ms}
    for s in (int(x) for x in args.seqs.split(",")):
        q, k, v = (
            jnp.asarray(rng.normal(size=(b, heads, s, d)).astype(np.float32) * 0.1,
                        jnp.bfloat16)
            for heads, d in ((h, dh), (h_kv, dh), (h_kv, dv))
        )
        try:
            if args.no_dense:
                raise RuntimeError("skipped by --no-dense")
            dense_ms = time_fn(
                lambda q, k, v: _dense_reference(q, k, v, True, dh ** -0.5, window), q, k, v
            )
        except Exception as e:  # noqa: BLE001 (dense S^2 can OOM at long S)
            dense_ms = None
            print(f"seq {s}: dense failed ({type(e).__name__})")
        best = None
        ct = jnp.asarray(rng.normal(size=q.shape[:3] + (dv,)).astype(np.float32), jnp.bfloat16)
        if args.check:
            out, dq, *dkv = dense_banded_grads(
                q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1), ct)
            # a key/value head's gradient is the sum over its group of queries
            want = (out, dq, *(x.reshape(b, h_kv, group, s, -1).sum(2) for x in dkv))
        if window and args.causal_too:
            causal = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal=True, interpret=args.rehearse)
            ms, fwd_ms = time_fn(causal, q, k, v), time_fn(causal, q, k, v, grad=False)
            print(f"seq {s} full causal kernels at the plain entry: {ms:8.2f} ms "
                  f"(forward alone {fwd_ms:6.2f}){beside_repeated(causal, q, k, v)}")
        for bq, bk in ((bq, bk) for bq in blocks for bk in blocks):
            if s % bq or s % bk:
                continue
            flash = lambda q, k, v, bq=bq, bk=bk: flash_attention(  # noqa: E731
                q, k, v, causal=True, block_q=bq, block_k=bk, window=window,
                interpret=args.rehearse)
            with applied(fa, ("whole tiles", 0, 1.0, "qk")):
                whole = results(flash, q, k, v)
            for variant in variants:
                with applied(fa, variant):
                    note = entries_note(fa, s, s, bq, bk, window=window)
                    try:
                        got = results(flash, q, k, v)
                        ms = time_fn(flash, q, k, v)
                        fwd_ms = time_fn(flash, q, k, v, grad=False)
                        again = beside_repeated(flash, q, k, v) if variant is variants[0] else ""
                    except Exception as e:  # noqa: BLE001
                        print(f"seq {s} flash bq={bq} bk={bk} [{variant[0]}]: "
                              f"{type(e).__name__}: {e}"[:300])
                        continue
                if args.check:
                    off = [float(jnp.linalg.norm(a.astype(jnp.float32) - w) / jnp.linalg.norm(w))
                           for a, w in zip(got, want)]
                    print(f"seq {s} window {window} bq={bq} bk={bk} [{variant[0]}]: out, dQ, dK, "
                          f"dV off the dense mask by {' '.join(f'{x:.2e}' for x in off)} "
                          "(relative L2)")
                    if not max(off) < 2e-2:  # bf16 operands against f32
                        raise SystemExit("the kernels disagree with the dense mask")
                tag = ""
                if variant is variants[0] and (best is None or ms < best[0]):
                    best = (ms, bq, bk, fwd_ms)
                    tag = " *"
                print(f"seq {s} flash bq={bq} bk={bk} [{variant[0]}]: {ms:8.2f} ms "
                      f"(forward alone {fwd_ms:6.2f}){tag}; {note}; against whole tiles "
                      f"{same_bits(got, whole)}{again}", flush=True)
        if dense_ms is not None:
            print(f"seq {s} dense:               {dense_ms:8.2f} ms")
        if best is not None:
            winners[s] = {
                "blocks": [best[1], best[2]],
                "flash_ms": round(best[0], 3),
                "fwd_ms": round(best[3], 3),
                "dense_ms": None if dense_ms is None else round(dense_ms, 3),
            }
        if best is not None and dense_ms is not None:
            verdict = "flash WINS" if best[0] < dense_ms else "dense wins"
            print(
                f"seq {s}: best flash {best[0]:.2f} ms (bq={best[1]}, "
                f"bk={best[2]}) vs dense {dense_ms:.2f} ms → {verdict}"
            )
    if winners and not args.no_write:
        # persist so the kernels' tuned_blocks() table picks the winners
        # up on the next run (then `python benchmark/run.py --workload
        # joyai_flash_ep32_train8k`, the cell that runs these kernels)
        import json

        path = args.out or fa._TUNED_PATH  # producer/consumer share one location
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
        # a banded sweep fills the second table and leaves the first as it is
        table, notes = ("banded", "banded_meta") if window else ("blocks", "meta")
        blocks = doc.get(table, {})
        meta = doc.get(notes, {})
        for s, w in winners.items():
            entry = f"{s},{window}" if window else str(s)
            blocks[entry] = w["blocks"]
            meta[entry] = {
                "flash_ms": w["flash_ms"], "fwd_ms": w["fwd_ms"],
                "dense_ms": w["dense_ms"],
                "bh": args.bh, "dh": dh, "dv": dv, "kv_heads": h_kv,
            }
        with open(path, "w") as f:
            json.dump({**doc, table: blocks, notes: meta}, f, indent=1)
        print(f"wrote {len(winners)} tuned block entries -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
