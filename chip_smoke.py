#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that byteps_tpu still starts on the chip.

One process holds the chip and drives the system's main paths once, through
the entry points a user calls, at the full width of the models the repo lists:

  leg A  compute plane: ``bps.init()``, BERT-large (24 layers, d 1024, vocab
         30528, seq 128, bf16, remat) through ``build_train_step`` + adamw,
         batch 32 x devices, 5 steps on one fixed batch.
  leg B  exchange plane: ``HybridDataParallel`` over VGG-16 (224x224x3, 1000
         classes, 138 M parameters): every step's f32 gradient crosses
         COPYD2H -> PUSH -> server sum -> PULL -> COPYH2D to a scheduler and a
         server started with the launcher's own command; byte counters must
         equal steps x parameter bytes, every partition's device-to-host
         copy must have been started at submit, and a sum of one comes back
         bit-identical.
  leg C  the Pallas kernels, compiled by Mosaic at the shapes the models use:
         flash attention forward + backward against the dense reference (at
         BERT-large's 16 x 64 and at latent attention's 2 x 32 x 8192 with
         192 for q.k and 128 for v, causal, against a blocked f32 dense
         reference), the onebit packer against the host codec, and an onebit
         key through leg B's engine — each with the Mosaic custom call shown
         in the lowered program, so it is known that the kernel is what ran.
  leg D  only with several devices: leg A again at dp x tp=2, and
         ``__graft_entry__._dryrun_one_mesh`` for {dp, pp=2} and {dp, sp=2}
         on the real devices (pipeline ppermute, ring attention, MoE
         all_to_all over the interconnect).
  leg E  the latent-attention MoE family at the published widths of
         benchmark/configs/joyai_llm_flash_ep32.json, one step through
         ``build_train_step``: its loss and every leaf's gradient — before
         any optimizer — against the float32 plain reference computed there
         (the benchmark builder's blocked ``plain_loss``), relative L2 a
         leaf and its projection on the reference's, the worst leaf named;
         as the state is made, and once more with the experts' choice
         pinned by the selection bias, where no near-tie is left to flip.
  leg F  the gated-delta MoE family at the published widths of
         benchmark/configs/qwen3_next_80b_ep32.json (three gated-delta-rule
         layers and one gated full-attention layer, 16 of 512 softmax-routed
         experts), as leg E: loss and every leaf's gradient against the
         float32 plain reference, routers and routed experts judged apart.
  leg G  the short-convolution MoE family at the published widths of
         benchmark/configs/lfm2_24b_a2b_ep8.json (four double-gated
         short-convolution mixers and one grouped-query attention layer at
         heads of 64, a leading dense layer, 8 of 64 sigmoid-routed experts),
         as leg F.
  leg H  the sliding-window / global-attention MoE family at the published
         widths of benchmark/configs/trinity_mini_ep16.json (four
         sliding-window layers through the banded flash kernels and one
         global layer without positions, gated attention at heads of 128,
         sandwich norms, a leading dense layer, 8 of 128 sigmoid-routed
         experts beside a shared expert), as leg F.
  leg I  the early-routed MoE family at the published widths of
         benchmark/configs/smallthinker_21b_ep8.json (a router that decides
         before the attention, one global layer without positions and three
         sliding-window layers at 7 query heads a key/value head, 8 of 64
         ReLU-gated experts in every layer), as leg F.

Every result line names the platform, device kind, device count and the jax /
jaxlib / libtpu versions.  Step times are printed as information only: they
are not a benchmark.  No leg is wrapped in try/except and nothing retries at a
smaller size: the first failure ends the run with a non-zero exit and no
result line.  Without a TPU the script exits non-zero naming what it found;
``--cpu-dry-run`` is the only CPU mode (cut sizes, kernels interpreted, every
line marked) and exists to pre-flight the control flow before a chip run.

On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
NATIVE_DIR = os.path.join(ROOT, "byteps_tpu", "native")

#: per-device batch of legs A and B, and steps per leg (ISSUE 21)
BATCH_PER_DEVICE = 32
STEPS_A, STEPS_B = 5, 3
#: the CPU children, in start order (launcher/launch.py's DMLC_ROLE values)
ROLES = ("scheduler", "server")

_prefix = "[smoke]"


def say(msg: str) -> None:
    print(f"{_prefix} {msg}", flush=True)


# ---------------------------------------------------------------------------
# compilation accounting (jax.monitoring): how many programs each leg built,
# how long that took, and what the persistent cache did with them
# ---------------------------------------------------------------------------


class CompileStats:
    """Counts XLA compilations and persistent-cache traffic, per program
    name.  Listeners fire on whichever thread compiles (the engine's stage
    threads build programs too), hence the lock."""

    def __init__(self, jax) -> None:
        self._lock = threading.Lock()
        self.n = 0
        self.secs = 0.0
        self.hits = 0
        self.written = 0
        self.by_name = collections.defaultdict(list)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.n += 1
                self.secs += secs
                self.by_name[kw.get("fun_name", "?")].append(secs)

    def _on_event(self, event: str, **kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.written += 1  # recorded only when an entry is written

    def mark(self) -> tuple:
        with self._lock:
            return (self.n, self.secs, self.hits, self.written,
                    {k: len(v) for k, v in self.by_name.items()},
                    time.perf_counter())

    def since(self, mark: tuple) -> str:
        n0, s0, h0, w0, _, t0 = mark
        n, s, h, w = self.n - n0, self.secs - s0, self.hits - h0, self.written - w0
        return (f"{time.perf_counter() - t0:.1f} s in all, compilations {n} "
                f"({h} cache hits, {w} written to the cache, {n - h - w} below "
                f"the cache's floors), {s:.1f} s compiling")

    def slices_since(self, mark: tuple) -> str:
        """The engine's partition programs: ``split_parts`` (one a leaf's
        layout, dispatched in submit) and the ``job.flat[a:b]`` slices of
        sharded and device-codec jobs (on the COPYD2H thread)."""
        seen = mark[4]
        with self._lock:
            new = {k: v[seen.get(k, 0):] for k, v in self.by_name.items()
                   if ("slice" in k or "split_parts" in k) and len(v) > seen.get(k, 0)}
        if not new:
            return "no slice programs compiled"
        count = sum(len(v) for v in new.values())
        longest = max(max(v) for v in new.values())
        return (f"{count} slice programs {sorted(new)} (longest {longest:.3f} s: "
                f"{'below' if longest < 1.0 else 'ABOVE'} the cache's 1 s floor)")


# ---------------------------------------------------------------------------
# set-up that must happen before this process touches JAX
# ---------------------------------------------------------------------------


def rebuild_native(force: bool) -> None:
    """Build libbyteps_tpu.so from the tracked sources ON THIS MACHINE: the
    .so is untracked and travels with a copied tree, so one that is already
    there proves nothing about this host."""
    cmd = ["make", "-C", NATIVE_DIR] + (["-B"] if force else [])
    t0 = time.perf_counter()
    subprocess.run(cmd, check=True, stdout=sys.stderr)
    say(f"native: `make{' -B' if force else ''}` ok in {time.perf_counter() - t0:.1f} s")


def start_ps_children() -> tuple:
    """Scheduler + one server, as the launcher starts them
    (launcher/launch.py: ``python -m byteps_tpu.server`` under DMLC_ROLE).
    They are CPU processes and never need the chip."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    topo = {
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
    }
    children = []
    for role in ROLES:
        children.append(subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"],
            env={**os.environ, **topo, "DMLC_ROLE": role},
            cwd=ROOT, stdout=sys.stderr,
        ))
    os.environ.update(topo, DMLC_ROLE="worker", BYTEPS_FORCE_DISTRIBUTED="1")
    return children


def require_alive(children) -> None:
    for role, proc in zip(ROLES, children):
        if proc.poll() is not None:
            raise SystemExit(f"{role} child exited early with {proc.returncode}")


def check_children(children) -> None:
    """Both still serving, neither ever opened the accelerator runtime
    (importing byteps_tpu.server imports jax but must initialise no
    backend), and the server sums with the native reducer built above."""
    require_alive(children)
    for role, proc in zip(ROLES, children):
        with open(f"/proc/{proc.pid}/maps") as f:
            maps = f.read()
        if "libtpu" in maps:
            raise SystemExit(f"{role} child loaded libtpu: it touched the device")
        if role == "server":
            reducer = "native" if "libbyteps_tpu.so" in maps else "numpy"
            say(f"server reducer: {reducer} (pid {proc.pid}); no child loaded libtpu")
            if reducer != "native":
                raise SystemExit("server child did not load libbyteps_tpu.so")


def stop_children(children) -> None:
    for proc in children:
        if proc.poll() is None:
            proc.terminate()
    for proc in children:
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


# ---------------------------------------------------------------------------
# helpers shared by the legs
# ---------------------------------------------------------------------------


def bytes_in_use() -> list:
    import jax

    return [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]


def device_report(label: str, params, dry: bool) -> None:
    """Per device bytes in use, and over how many devices the parameters
    are spread: nothing may sit on device 0 alone."""
    import jax

    n = jax.device_count()
    spread = min(
        len(leaf.sharding.device_set) for leaf in jax.tree_util.tree_leaves(params)
    )
    in_use = bytes_in_use()  # the CPU backend reports none
    say(f"{label}: every parameter lives on {spread} of {n} devices; "
        f"bytes_in_use per device {in_use}")
    if spread != n:
        raise SystemExit(f"{label}: a parameter sits on {spread} of {n} devices")
    if not (dry or all(in_use)):
        raise SystemExit(f"{label}: a device holds nothing: {in_use}")


def check_losses(label: str, losses, times) -> None:
    say(f"{label}: losses {[round(x, 4) for x in losses]}; step seconds "
        f"{[round(t, 3) for t in times]} (information only; the first "
        "includes compilation; not a benchmark)")
    if not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"{label}: loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise SystemExit(f"{label}: loss did not fall: {losses}")


def require_mosaic(label: str, want: int, dry: bool, jitted, *args) -> None:
    """Prove the kernel is what runs: the lowered program of this very call
    must contain ``want`` Mosaic custom calls."""
    if dry:
        say(f"{label}: Pallas interpreter (no Mosaic on the CPU)")
        return
    found = jitted.lower(*args).as_text().count("tpu_custom_call")
    if found < want:
        raise SystemExit(
            f"{label}: lowered program has {found} Mosaic custom calls, "
            f"expected {want}: the kernel is not what ran"
        )
    say(f"{label}: {found} Mosaic custom call(s) in the lowered program")


# ---------------------------------------------------------------------------
# leg A — compute plane
# ---------------------------------------------------------------------------


def leg_a(axis_sizes: dict, dry: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from byteps_tpu.models.transformer import (
        bert_large, build_train_step, init_params, shard_params,
    )
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    label = f"leg A (BERT-large, mesh {axis_sizes})"
    n = jax.device_count()
    cfg = bert_large(max_seq=128, compute_dtype=jnp.bfloat16)
    batch, seq = BATCH_PER_DEVICE * axis_sizes["dp"], cfg.max_seq
    if dry:  # d_model kept; depth, vocabulary, sequence and batch cut for the CPU
        cfg = dataclasses.replace(cfg, n_layers=1, max_seq=16, vocab_size=2048)
        batch, seq = 2 * axis_sizes["dp"], 16
    mesh = make_training_mesh(n, {"pp": 1, "sp": 1, "tp": 1, **axis_sizes})
    params = shard_params(init_params(cfg, seed=0), cfg, mesh)
    # 3e-4: on this fixed batch the loss falls 0.16 in 5 steps, every step;
    # at 1e-4 it fell 0.05 and not monotonically (my chip runs, PR 21)
    tx = optax.adamw(3e-4)
    opt_state = jax.jit(tx.init)(params)
    step = build_train_step(cfg, mesh, tx)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    targets = jnp.asarray(np.roll(tokens, -1, axis=1))
    tokens = jnp.asarray(tokens)
    losses, times = [], []
    for _ in range(STEPS_A):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        jax.block_until_ready((params, loss))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    say(f"{label}: layers {cfg.n_layers}, batch {batch}, seq {seq}, "
        f"{sum(v.size for v in params.values()) / 1e6:.1f} M parameters")
    check_losses(label, losses, times)
    device_report(label, params, dry)


# ---------------------------------------------------------------------------
# leg B — exchange plane
# ---------------------------------------------------------------------------


def leg_b(dry: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import byteps_tpu as bps
    from byteps_tpu.models.vgg import VGG16
    from byteps_tpu.parallel.hybrid import HybridDataParallel

    n = jax.device_count()
    hw, batch = (32, 2 * n) if dry else (224, BATCH_PER_DEVICE * n)
    label = f"leg B (VGG-16 {hw}x{hw} through the PS plane, dp={n})"
    # dry run: the conv stack kept, the 4096-wide classifier cut for the CPU
    model = VGG16(dtype=jnp.bfloat16, **({"hidden": 256} if dry else {}))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, hw, hw, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 1000, size=(batch,)).astype(np.int32))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x[:1])["params"]

    def loss_fn(p, xy):
        logits = model.apply({"params": p}, xy[0])
        return optax.softmax_cross_entropy_with_integer_labels(logits, xy[1]).mean()

    hdp = HybridDataParallel(
        loss_fn, params, optax.sgd(0.05, momentum=0.9),
        batch_spec=(P("dp"), P("dp")),
    )
    leaves = jax.tree_util.tree_leaves(hdp.params)
    param_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
    before = bps.get_robustness_counters()
    losses, times = [], []
    for _ in range(STEPS_B):
        t0 = time.perf_counter()
        losses.append(hdp.step((x, y)))
        times.append(time.perf_counter() - t0)
    after = bps.get_robustness_counters()
    say(f"{label}: batch {batch}, {len(leaves)} keys, "
        f"{param_bytes / 1e6:.1f} MB of f32 gradient per step")
    check_losses(label, losses, times)
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("d2h_bytes", "wire_tx_bytes", "wire_rx_bytes", "h2d_bytes")}
    say(f"{label}: byte counters over {STEPS_B} steps {moved}; "
        f"steps x parameter bytes = {STEPS_B * param_bytes}")
    if set(moved.values()) != {STEPS_B * param_bytes}:
        raise SystemExit(f"{label}: counters disagree with the gradient size")
    # the gradient is whole on one chip (replicated at dp > 1): COPYD2H only
    # collects copies that submit started, for every partition of every leaf
    from byteps_tpu.common.partition import partition_elements
    from byteps_tpu.core.state import get_state

    part_bytes = get_state().config.partition_bytes
    parts = sum(len(partition_elements(leaf.size, leaf.dtype.itemsize, part_bytes))
                for leaf in leaves)
    started = (after.get("d2h_prefetched_parts", 0)
               - before.get("d2h_prefetched_parts", 0))
    say(f"{label}: {started} device-to-host copies started at submit; "
        f"steps x partitions = {STEPS_B * parts}")
    if started != STEPS_B * parts:
        raise SystemExit(f"{label}: COPYD2H read partitions nobody had started")

    # one worker: the server's sum of one must be the input, bit for bit
    leaf = hdp.params["Dense_2"]["kernel"]
    back = bps.push_pull(leaf, name="smoke.identity", average=False)
    if not (isinstance(back, jax.Array) and back.dtype == leaf.dtype
            and np.array_equal(np.asarray(back), np.asarray(leaf))):
        raise SystemExit(f"{label}: sum of one worker is not bit-identical")
    # information for multi-device hosts: the engine hands results back
    # unsharded (core/engine.py _finalize) and HybridDataParallel.step
    # re-puts them with the parameter's sharding
    say(f"{label}: sum-of-one round trip bit-identical ({leaf.nbytes} bytes); "
        f"the engine returned it on {len(back.sharding.device_set)} of {n} "
        "devices (one host-to-device copy; step() then spreads it "
        "device-to-device)")
    device_report(label, hdp.params, dry)


# ---------------------------------------------------------------------------
# leg C — the Pallas kernels
# ---------------------------------------------------------------------------


def leg_c_flash(dry: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    # ops/__init__ re-exports the flash_attention FUNCTION under the
    # submodule's name, so reach the module itself
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    # BERT-large's attention: 16 heads of 64 at seq 512, bf16
    b, h, s, dh = (1, 2, 256, 64) if dry else (2, 16, 512, 64)
    say(f"leg C flash: shape {(b, h, s, dh)} bf16, blocks {fa.tuned_blocks(s)} "
        f"(ops/flash_blocks.json "
        f"{'present' if os.path.exists(fa._TUNED_PATH) else 'absent'})")
    rng = np.random.default_rng(1)
    q, k, v, ct = (
        jnp.asarray(rng.normal(size=(b, h, s, dh)), jnp.bfloat16) for _ in range(4)
    )
    q32, k32, v32, ct32 = (a.astype(jnp.float32) for a in (q, k, v, ct))

    def close(name, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = np.abs(got - want).max() / np.abs(want).max()
        if not (np.isfinite(got).all() and err < 2e-2):  # bf16: 8 mantissa bits
            raise SystemExit(f"leg C flash {name}: max error {err:.2e} of peak")
        return err

    for causal in (False, True):
        fwd = jax.jit(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=causal, interpret=dry)
        )
        bwd = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                fa.flash_attention(q, k, v, causal=causal, interpret=dry)
                .astype(jnp.float32) * ct32),
            argnums=(0, 1, 2),
        ))
        ref = jax.jit(lambda q, k, v: fa._dense_reference(q, k, v, causal, dh**-0.5))
        ref_bwd = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(ref(q, k, v) * ct32), argnums=(0, 1, 2)
        ))
        tag = f"leg C flash causal={causal}"
        require_mosaic(f"{tag} forward", 1, dry, fwd, q, k, v)
        require_mosaic(f"{tag} backward", 2, dry, bwd, q, k, v)
        e_out = close("forward", fwd(q, k, v), ref(q32, k32, v32))
        e_grads = [close(f"d{n}", g, r) for n, g, r in
                   zip("qkv", bwd(q, k, v), ref_bwd(q32, k32, v32))]
        say(f"{tag}: forward and dq/dk/dv agree with the f32 dense reference "
            f"(max error / peak: out {e_out:.1e}, grads "
            f"{[f'{e:.1e}' for e in e_grads]})")


def leg_c_flash_latent(dry: bool) -> None:
    """The kernel at latent attention's shape (d_qk 192, d_v 128, sequence
    8192, causal) against dense attention in f32 at ``highest`` precision,
    computed a block of queries at a time (8k scores do not fit whole)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    b, h, s, dqk, dv = (1, 2, 512, 192, 128) if dry else (2, 32, 8192, 192, 128)
    block = 128 if dry else 256
    tag = f"leg C flash latent {(b, h, s, dqk, dv)} causal, blocks {fa.tuned_blocks(s)}"
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k = (jax.random.normal(kk, (b, h, s, dqk), jnp.bfloat16) for kk in keys[:2])
    v, ct = (jax.random.normal(kk, (b, h, s, dv), jnp.bfloat16) for kk in keys[2:])
    scale = dqk ** -0.5

    def flash_loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, scale=scale, interpret=dry)
        return jnp.sum(out.astype(jnp.float32) * ct.astype(jnp.float32)), out

    @jax.checkpoint
    def attend(qb, first, k, v):
        scores = jnp.einsum("bhqd,bhkd->bhqk", qb, k) * scale
        seen = jnp.arange(s)[None, :] <= (first + jnp.arange(block))[:, None]
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v)

    def dense_loss(q, k, v):
        with jax.default_matmul_precision("highest"):
            blocks = jnp.moveaxis(q.reshape(b, h, s // block, block, dqk), 2, 0)
            out = lax.map(lambda xs: attend(xs[0], xs[1], k, v),
                          (blocks, block * jnp.arange(s // block)))
            out = jnp.moveaxis(out, 0, 2).reshape(b, h, s, dv)
        return jnp.sum(out * ct.astype(jnp.float32)), out

    flash = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True))
    dense = jax.jit(jax.value_and_grad(dense_loss, argnums=(0, 1, 2), has_aux=True))
    require_mosaic(tag, 2, dry, flash, q, k, v)
    (_, out), grads = flash(q, k, v)
    (_, want), want_grads = dense(*(x.astype(jnp.float32) for x in (q, k, v)))
    errs = {}
    for name, got, ref in zip(("out", "dq", "dk", "dv"), (out, *grads), (want, *want_grads)):
        got, ref = np.asarray(got, np.float32), np.asarray(ref)
        errs[name] = float(np.abs(got - ref).max() / np.abs(ref).max())
        if not (np.isfinite(got).all() and errs[name] < 2e-2):  # bf16: 8 mantissa bits
            raise SystemExit(f"{tag} {name}: max error {errs[name]:.2e} of peak")
    say(f"{tag}: forward and dq/dk/dv agree with the blocked f32 dense reference "
        f"(max error / peak: { {n: f'{e:.1e}' for n, e in errs.items()} })")


def leg_c_onebit(dry: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import byteps_tpu as bps
    from byteps_tpu.compression.impl import OneBitCompressor
    from byteps_tpu.core.state import get_state
    from byteps_tpu.ops.onebit_device import onebit_compress_device, onebit_payload

    # the engine's default partition (BYTEPS_PARTITION_BYTES / 4) and a tail
    part = get_state().config.partition_bytes // 4
    rng = np.random.default_rng(2)
    for n in (part, 12_345):
        g = rng.normal(size=n).astype(np.float32)
        dev = jnp.asarray(g)
        require_mosaic(f"leg C onebit packer n={n}", 1, dry,
                       onebit_compress_device, dev, True, dry)
        payload = onebit_payload(*onebit_compress_device(dev, True, dry))
        host = OneBitCompressor(n, scaling=True).compress(g)
        # sign words byte for byte; the f32 scale is an XLA reduction against
        # the codec's own accumulation order, so it may differ in the last bit
        if payload[4:] != host[4:] or len(payload) != len(host):
            raise SystemExit(f"leg C onebit n={n}: words differ from the host codec")
        np.testing.assert_allclose(
            np.frombuffer(payload[:4], np.float32),
            np.frombuffer(host[:4], np.float32), rtol=1e-6,
        )
        say(f"leg C onebit packer n={n}: {len(payload) - 4} bytes of sign words "
            "byte-equal to the native host codec, scale within 1e-6")

    # through the engine of leg B: a jax.Array under an onebit declaration is
    # packed on the device before D2H and must equal its host-path sibling
    n = part + 12_345
    x = rng.normal(size=n).astype(np.float32)
    for name in ("smoke.onebit.dev", "smoke.onebit.host"):
        bps.declare_tensor(
            name, byteps_compressor_type="onebit",
            byteps_compressor_onebit_scaling="True",
        )
    before = bps.get_robustness_counters().get("d2h_bytes", 0)
    out_dev = bps.push_pull(jnp.asarray(x), name="smoke.onebit.dev", average=False)
    d2h = bps.get_robustness_counters().get("d2h_bytes", 0) - before
    out_host = bps.push_pull(x, name="smoke.onebit.host", average=False)
    if not get_state().engine._device_codecs:
        raise SystemExit("leg C onebit: the engine's device-codec path did not engage")
    if not isinstance(out_dev, jax.Array):
        raise SystemExit("leg C onebit: device path returned a host array")
    np.testing.assert_allclose(np.asarray(out_dev), np.asarray(out_host),
                               rtol=1e-5, atol=1e-7)
    say(f"leg C onebit through the engine: n={n} in two partitions, {d2h} bytes "
        f"crossed D2H for {4 * n} bytes of gradient; device path == host path")


# ---------------------------------------------------------------------------
# leg D — several devices
# ---------------------------------------------------------------------------


def leg_d(dry: bool) -> None:
    import jax

    from __graft_entry__ import _dryrun_one_mesh

    n = jax.device_count()
    leg_a({"dp": n // 2, "tp": 2}, dry)
    for sizes in ({"dp": n // 2, "pp": 2}, {"dp": n // 2, "sp": 2}):
        sizes = {"dp": 1, "pp": 1, "sp": 1, "tp": 1, **sizes}
        say(f"leg D dryrun on the real devices: {_dryrun_one_mesh(sizes)}")
    say(f"leg D: bytes_in_use per device afterwards {bytes_in_use()}")


# ---------------------------------------------------------------------------
# leg E — the latent-attention MoE family at its published widths
# ---------------------------------------------------------------------------


#: leaves whose gradient moves by whole tokens when a near-tie between the
#: 8th and 9th router score falls the other way
ROUTED_LEAVES = ("router", "e_gate", "e_up", "e_down")
#: leg E's limits, each between two readings at the cell's size on the chip
#: (PERF.md section 6, PR 29; tools/latent_moe_precision.py and this leg's own
#: key): the program's largest, and the smallest of the plain reference in
#: bf16 with the norms' statistics, the router and the softmax in bf16 too
#: ("below"), or a planted fault: a halved gradient reads 0.5 in its leaf and
#: in its projection, a lost one 1.
#: As made (7 keys | 6): loss <= 6.4e-5 | 5.7e-5, so 3 x the first alone;
#: routers and routed experts 0.24-0.30 | 0.30-0.36, which near-ties alone
#: nearly reach, so the limit stands against the halved gradient; other
#: leaves at most 0.052-0.058 | 0.064-0.071, median 0.046-0.049 | 0.055-0.062.
#: Choice pinned (3 keys | 2): routed 0.0299-0.0308 | 0.0339-0.0352, other
#: leaves 0.0309-0.0314 | 0.0361-0.0365, median 0.0262-0.0266 | 0.0295-0.0298:
#: half of the noise as made is flipped tokens.  Projection within 0.051 of 1
#: as made and 0.0043 pinned, on both sides.
LEG_E_LIMITS = {
    "as made": {"loss": 1.7e-4, "routed": 0.40, "rest": 0.061, "median": 0.052,
                "projection": 0.15},
    "choice pinned": {"loss": 1.7e-4, "routed": 0.0325, "rest": 0.0338, "median": 0.028,
                      "projection": 0.15},
}
#: the dry run's toy widths prove the control flow, not the limits
DRY_RUN_LIMITS = {"loss": 1e-3, "routed": 0.40, "rest": 0.2, "median": 0.1, "projection": 0.15}


def gradient_readings(got: dict, want: dict) -> dict:
    """How far a gradient lies from the reference's, leaf by leaf (both are
    flat dicts; ``want`` may live on the host).  ``routed`` and ``rest``:
    the largest relative L2 distance among the routers and routed experts,
    and among the other leaves, with its leaf; ``median`` of the other
    leaves; ``projection``: the furthest from 1 of <got, want> / <want, want>
    over all leaves, with its leaf — a halved gradient reads 0.5 there and a
    lost one 0 however many near-ties flipped, since what a flipped token
    adds is nearly orthogonal to the reference; ``zero``: leaves where the
    reference has no gradient (the selection bias), where ``got`` must have
    none either."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def pair(x, y):
        # sums of one kind (a vdot adds up a million products in another order)
        return jnp.sum((x - y) ** 2), jnp.sum(y * y), jnp.sum(x * y), jnp.sum(x * x)

    apart, proj, zero = {}, {}, []
    for k, y in want.items():
        d2, n2, dot, mine = (float(v) for v in pair(got[k], y))
        if n2 == 0.0:
            if mine:
                raise SystemExit(f"{k} has a gradient, the reference none")
            zero.append(k)
            continue
        apart[k], proj[k] = math.sqrt(d2 / n2), dot / n2
    routed = [k for k in apart if k.rsplit(".", 1)[-1] in ROUTED_LEAVES]
    rest = [k for k in apart if k not in routed]

    def worst(keys, value):
        if not keys:  # a family without experts has no routed leaf
            return None
        k = max(keys, key=value)
        return [k, value(k)]

    return {"routed": worst(routed, apart.get), "rest": worst(rest, apart.get),
            "median": sorted(apart[k] for k in rest)[len(rest) // 2],
            "projection": worst(apart, lambda k: abs(proj[k] - 1.0)), "zero": zero}


def keep_gradient():
    """An "optimizer" that keeps the gradient as its state and moves nothing:
    the comparison before the optimizer, through build_train_step itself."""
    import jax
    import jax.numpy as jnp
    import optax

    return optax.GradientTransformation(
        lambda p: jax.tree.map(jnp.zeros_like, p),
        lambda g, state, p=None: (jax.tree.map(jnp.zeros_like, g), g))


def pin_choice(params: dict, cfg: dict) -> dict:
    """The same parameters with a selection bias under which every token
    picks the same ``top_k`` experts, half of them held here: no near-tie is
    left to fall the other way once the input is rounded, so the routers and
    the routed experts can be held like the other leaves; and the held
    experts see 16 times their usual slots — the layer's path under skew."""
    import jax.numpy as jnp

    k, lo, held = cfg["num_experts_per_tok"], cfg["held_expert_lo"], cfg["n_routed_experts"]
    ids = [lo + i for i in range(k // 2)]
    ids += [(lo + held + i) % cfg["router_width"] for i in range(k - k // 2)]
    return {name: jnp.zeros_like(v).at[:, jnp.asarray(ids)].set(10.0)
            if name.endswith("router_bias") else v for name, v in params.items()}


#: leg F's limits.  The first gradient does not separate the nearest
#: precision below at this size (readings on the chip, PR 36,
#: tools/latent_moe_precision.py --config qwen3_next_80b_ep32, 3 seeds and
#: this leg's key: the program | the reference with bf16 statistics — loss
#: <= 3.2e-5 | 3.8e-5; routers and routed experts 0.174-0.198 | 0.197-0.202;
#: other leaves at most 0.062-0.068 | 0.069-0.083, median 0.053-0.057 |
#: 0.058-0.067; projection within 0.026 of 1 on both sides), so these stand
#: above the program with room and against a planted fault: a halved
#: gradient reads 0.5 in its leaf and in its projection, a lost one 1.
#: Precision is held by the cell's reference_update_rtol.
LEG_F_LIMITS = {
    "as made": {"loss": 1.7e-4, "routed": 0.40, "rest": 0.10, "median": 0.07,
                "projection": 0.15},
}


#: leg G's limits.  Here the first gradient does tell the nearest precision
#: below apart (readings on the chip, PR 40, tools/latent_moe_precision.py
#: --config lfm2_24b_a2b_ep8, 3 seeds, and this leg's own key: the program |
#: the reference with bf16 statistics — loss <= 5.3e-5 | 1.1e-4; routers and
#: routed experts 0.212-0.224 | 0.282-0.283, always moe.router; other leaves
#: at most 0.159-0.166 | 0.201-0.204, always moe.norm, whose whole gradient
#: comes through the routed experts (no shared expert), so flipped near-ties
#: reach it; median 0.056-0.059 | 0.071-0.072; projection within 0.029 of 1
#: on both sides), so each limit stands between its two readings; the
#: projection's stands against a planted fault (a halved gradient reads 0.5).
LEG_G_LIMITS = {
    "as made": {"loss": 1.7e-4, "routed": 0.25, "rest": 0.183, "median": 0.065,
                "projection": 0.15},
}


#: leg H's limits.  The first gradient tells the nearest precision below
#: apart here too (readings on the chip, PR 42, tools/latent_moe_precision.py
#: --config trinity_mini_ep16, 3 seeds, and this leg's own key: the program |
#: the reference with bf16 statistics — loss <= 3.4e-5 | 4.3e-5; routers and
#: routed experts 0.181-0.192 | 0.240-0.255, always moe.router; other leaves
#: at most 0.044-0.048 | 0.059-0.066, moe.norm or moe.post_norm (the shared
#: expert carries every token's gradient there whichever way a near-tie
#: falls, so they read a third of lfm2's); median 0.035-0.038 | 0.047-0.051;
#: projection within 0.027 of 1 on both sides), so each limit stands between
#: its two readings; the projection's stands against a planted fault (a halved
#: gradient reads 0.5).
LEG_H_LIMITS = {
    "as made": {"loss": 1.7e-4, "routed": 0.215, "rest": 0.054, "median": 0.043,
                "projection": 0.15},
}


#: leg I's limits.  The first gradient tells the nearest precision below
#: apart here as well (readings on the chip, PR 48,
#: tools/latent_moe_precision.py --config smallthinker_21b_ep8, 3 seeds, and
#: this leg's own key: the program | the reference with bf16 statistics —
#: loss <= 8.6e-6 | 2.0e-5; routers and routed experts 0.074-0.078 |
#: 0.086-0.088, moe.e_gate or win.router (relu's kink beside the near-ties:
#: a gate within rounding of zero opens or shuts a whole hidden unit); other
#: leaves at most 0.064-0.067 | 0.077, always moe.norm, whose whole gradient
#: comes through the routed experts; median 0.022-0.026 | 0.028-0.030;
#: projection within 0.004 of 1 on both sides), so each limit stands between
#: its two readings; the projection's stands against a planted fault (a halved
#: gradient reads 0.5).
LEG_I_LIMITS = {
    "as made": {"loss": 1.7e-4, "routed": 0.082, "rest": 0.072, "median": 0.027,
                "projection": 0.15},
}


def _reference_leg(dry: bool, leg: str, config: str, what, cases, limits: dict) -> None:
    """One step of ``benchmark/configs/<config>.json`` through
    ``build_train_step`` with an optimizer that keeps the gradient: loss and
    every leaf's gradient against the float32 plain reference (the builder's
    blocked ``plain_loss``), once for each of ``cases`` (name → a function of
    (params, cfg) that gives the state to run)."""
    import jax

    from byteps_tpu.comm.mesh import get_global_mesh
    from byteps_tpu.models.transformer import build_train_step

    bench = os.path.join(ROOT, "benchmark")
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    if dry:
        cfg.update(cfg["rehearsal"])
    spec = importlib.util.spec_from_file_location(
        f"smoke_{cfg['builder']}_builder", os.path.join(bench, "builders", f"{cfg['builder']}.py"))
    builder = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(builder)
    label = f"leg {leg} ({what(cfg)}, vocab {cfg['vocab_size']}, " \
            f"{cfg['batch_per_chip']} x {cfg['max_seq']} tokens)"
    if jax.device_count() != 1:
        say(f"{label}: skipped, it is one chip's share and jax has {jax.device_count()}")
        return
    params, batch, _ = builder.make_state(cfg, jax.random.PRNGKey(29), get_global_mesh())
    keep = keep_gradient()
    step = build_train_step(builder._model_config(cfg), builder._mesh4(get_global_mesh()),
                            keep, donate=False)
    reference = jax.jit(jax.value_and_grad(builder.plain_loss(cfg)))
    # a token whose last chosen and first unchosen scores nearly tie picks
    # another expert once its input is rounded to bf16: its whole share of the
    # router's gradient moves to another column, and an expert gains or loses
    # a whole token.  So the routers and the routed experts read ~ sqrt(2 x
    # the share of slots that flipped), the other leaves bf16's noise.  With
    # the choice pinned they read noise too
    for case, make in cases.items():
        state = make(params, cfg)
        t0 = time.perf_counter()
        _, grads, loss = jax.block_until_ready(step(state, keep.init(state), *batch))
        say(f"{label}, {case}: system step in {time.perf_counter() - t0:.1f} s (with "
            f"compilation), loss {float(loss):.6f}")
        want_loss, want = reference(state, batch)
        read = gradient_readings(grads, want)
        del grads, want
        off = abs(float(loss) - float(want_loss)) / abs(float(want_loss))
        say(f"{label}, {case}: loss {float(loss):.6f} against the f32 reference's "
            f"{float(want_loss):.6f} ({off:.1e} apart); gradients by leaf, relative L2 to the "
            f"reference's: routers and routed experts at most {read['routed'][1]:.2e} "
            f"({read['routed'][0]}), the other leaves at most {read['rest'][1]:.2e} "
            f"({read['rest'][0]}), median {read['median']:.2e}; projection on the reference's "
            f"furthest from 1 by {read['projection'][1]:.2e} ({read['projection'][0]}); no "
            f"gradient on either side: {read['zero']}")
        lim = DRY_RUN_LIMITS if dry else limits[case]
        if not (off < lim["loss"] and read["rest"][1] < lim["rest"]
                and read["median"] < lim["median"] and read["routed"][1] < lim["routed"]
                and read["projection"][1] < lim["projection"]):
            raise SystemExit(f"{label}, {case}: further from the reference than {lim} allow")


def leg_e(dry: bool) -> None:
    _reference_leg(
        dry, "E", "joyai_llm_flash_ep32",
        lambda cfg: (f"JoyAI-LLM-Flash share: {cfg['num_hidden_layers']} layers + MTP, "
                     f"{cfg['n_routed_experts']} of {cfg['router_width']} experts"),
        {"as made": lambda params, cfg: params, "choice pinned": pin_choice}, LEG_E_LIMITS)


def leg_f(dry: bool) -> None:
    """The gated-delta MoE family.  Its router has no selection bias to pin
    the choice with, so there is the one case."""
    _reference_leg(
        dry, "F", "qwen3_next_80b_ep32",
        lambda cfg: (f"Qwen3-Next share: {cfg['num_hidden_layers']} layers, one full-attention "
                     f"layer in {cfg['full_attention_interval']}, {cfg['num_experts']} of "
                     f"{cfg['router_width']} experts"),
        {"as made": lambda params, cfg: params}, LEG_F_LIMITS)


def leg_g(dry: bool) -> None:
    """The short-convolution MoE family.  One case, as leg F: the cell's
    selection bias is part of the seeded state, and pinning it is leg E's."""
    _reference_leg(
        dry, "G", "lfm2_24b_a2b_ep8",
        lambda cfg: (f"LFM2-24B-A2B share: {cfg['num_hidden_layers']} layers from entry "
                     f"{cfg['first_layer']} of the published list, {cfg['num_dense_layers']} "
                     f"dense, {cfg['num_experts']} of {cfg['router_width']} experts"),
        {"as made": lambda params, cfg: params}, LEG_G_LIMITS)


def leg_h(dry: bool) -> None:
    """The sliding-window / global-attention MoE family.  One case, as legs F
    and G."""
    _reference_leg(
        dry, "H", "trinity_mini_ep16",
        lambda cfg: (f"Trinity-Mini share: {cfg['num_hidden_layers']} layers from entry "
                     f"{cfg['first_layer']} of the published list, {cfg['num_dense_layers']} "
                     f"dense, window {cfg['sliding_window']}, {cfg['num_experts']} of "
                     f"{cfg['router_width']} experts"),
        {"as made": lambda params, cfg: params}, LEG_H_LIMITS)


def leg_i(dry: bool) -> None:
    """The early-routed MoE family.  One case, as legs F to H: its router has
    no selection bias to pin the choice with."""
    _reference_leg(
        dry, "I", "smallthinker_21b_ep8",
        lambda cfg: (f"SmallThinker-21BA3B share: {cfg['num_hidden_layers']} layers from entry "
                     f"{cfg['first_layer']} of the published lists, window "
                     f"{cfg['sliding_window_size']}, {cfg['moe_num_primary_experts']} of "
                     f"{cfg['router_width']} experts"),
        {"as made": lambda params, cfg: params}, LEG_I_LIMITS)


# ---------------------------------------------------------------------------


def main() -> int:
    global _prefix
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-dry-run", action="store_true",
        help="pre-flight on the CPU at cut sizes with interpreted kernels; "
             "proves the control flow only, never a chip result",
    )
    ap.add_argument("--legs", default="ABCDEFGHI",
                    help="the legs to run, e.g. H (all by default; B's children start anyway)")
    args = ap.parse_args()
    dry, legs = args.cpu_dry_run, set(args.legs.upper())
    if dry:
        _prefix = "[smoke DRY-RUN on the cpu: not a chip result]"
    if not os.path.isdir(NATIVE_DIR):
        raise SystemExit(f"{ROOT} holds no byteps_tpu checkout: nothing to smoke")
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if not dry and pinned and "tpu" not in pinned.lower().split(","):
        # fail before a minute of building: this environment cannot see a TPU
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX_PLATFORMS={pinned!r} pins jax to "
            "another platform (use --cpu-dry-run for the CPU pre-flight)"
        )

    t_start = time.perf_counter()
    rebuild_native(force=not dry)
    children = start_ps_children()  # before this process initialises a backend
    try:
        import jax

        stats = CompileStats(jax)
        import byteps_tpu as bps
        from byteps_tpu.native import HAVE_NATIVE

        if not HAVE_NATIVE:
            raise SystemExit("libbyteps_tpu.so was built but does not load")
        # init() waits for the scheduler's address book, which waits for the
        # server: a child that died importing what this process just imported
        # must fail the run here, not hang it there
        require_alive(children)
        bps.init()  # compile cache placed, dp mesh over every device, PS joined
        dev = jax.devices()[0]
        if (dev.platform == "tpu") == dry:
            raise SystemExit(
                "chip_smoke.py needs a TPU (and --cpu-dry-run needs to be off "
                f"one); jax found platform {dev.platform!r} ({dev.device_kind})"
            )
        import importlib.metadata as md

        import jaxlib

        try:
            libtpu = md.version("libtpu")
        except md.PackageNotFoundError:
            libtpu = "absent"
        n = jax.device_count()
        _prefix += (f" [{dev.platform} {dev.device_kind} x{n} jax {jax.__version__} "
                    f"jaxlib {jaxlib.__version__} libtpu {libtpu}]")
        say(f"compile cache: {jax.config.jax_compilation_cache_dir} "
            f"({'from JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'placed by bps.init()'})")

        def run(leg: str, *fns, note=lambda mark: "") -> None:
            if leg not in legs:
                return
            mark = stats.mark()
            for fn in fns:
                fn()
            say(f"leg {leg}: {stats.since(mark)}{note(mark)}")

        run("A", lambda: leg_a({"dp": n}, dry))
        run("B", lambda: leg_b(dry),
            note=lambda mark: f"; of these, {stats.slices_since(mark)}")
        run("C", lambda: leg_c_flash(dry), lambda: leg_c_flash_latent(dry),
            lambda: leg_c_onebit(dry))
        if n > 1 and n % 2 == 0:
            run("D", lambda: leg_d(dry))
        run("E", lambda: leg_e(dry))
        run("F", lambda: leg_f(dry))
        run("G", lambda: leg_g(dry))
        run("H", lambda: leg_h(dry))
        run("I", lambda: leg_i(dry))

        check_children(children)
        bps.shutdown()
    finally:
        stop_children(children)
    codes = [p.returncode for p in children]
    if any(codes):
        raise SystemExit(f"children did not exit cleanly on terminate: {codes}")
    say(f"legs {''.join(sorted(legs))} passed in {time.perf_counter() - t_start:.0f} s; children "
        f"exited with {codes}")
    result = {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}
    if dry:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
