#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json on the TPU.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data that this file finds by name: the cell's entry
in ``BENCHMARK.json`` names a configuration (``configs/<config>.json``, which
names its builder ``builders/<builder>.py``) and a traffic mix
(``traffic/<traffic>.json``: step path, mesh, child processes, warm-up,
collections, how much to trace); every per-layer metric is ``metrics/<metric>.json`` (a reader
``readers/<reader>.py`` and its arguments).  A new cell, configuration,
traffic mix or metric over an existing reader is new files and new entries;
nothing here is edited.

A run: build the native library, start the traffic's CPU children, then — in
this one process, which alone holds the chips — ``bps.init()``, state on the
device from ``--seed``, the configuration's plain reference for the first
steps on a copy of it, the program's own step, warm-up, and the comparison of
the two (losses, and how far the parameters moved); all of that is
``setup_s``.  Then the window: whole steps in a closed loop for ``--seconds``,
each timed to a ``block_until_ready`` of its loss and parameters, with a
``gc.collect()`` every so many steps where the traffic says so and the
device's memory read between steps.  ``samples_per_s`` is the global batch
times the steps completed over the time they took — all of them, no median,
no trimming.  Every run says the shape of its step times (median, 10th and
90th percentile, the slowest step, the share of the window in steps over
1.25 x the median).  With ``--trace 1`` a few steps inside the window are
profiled and the per-layer metrics are printed instead of the end-to-end ones.

The last line of stdout is one JSON object (correct, attempted, failed,
metrics, device, and breakdown when traced).  Without a TPU, with fewer or
more chips than the cell names, or outside a byteps_tpu checkout, the exit is
non-zero and nothing is printed.  ``--rehearse`` is the only CPU mode: the
configuration's ``rehearsal`` cuts, ``correct: false`` and no metric — it
proves the control flow before chip time is spent, never a number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")  # the newest traced run's profile


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.2f} s] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@functools.cache
def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by file, whatever ``sys.path`` holds."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


xplane = load_module(".", "xplane")  # benchmark/xplane.py: the trace reduction
harness = load_module("readers", "harness")  # the step record's arithmetic


def load_cell(workload: str, rehearse: bool) -> tuple:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no cell {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(ROOT, files[cell["config"]])
    if rehearse:
        config.update(config["rehearsal"])
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    return bench, cell, config, traffic


# ---------------------------------------------------------------------------
# the CPU children of a PS cell (copied from chip_smoke.py: the yardstick may
# not change when the program's scripts do)
# ---------------------------------------------------------------------------


def start_children(roles: list) -> list:
    """Scheduler + server as the launcher starts them (``python -m
    byteps_tpu.server`` under DMLC_ROLE), before this process touches JAX."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    topo = {
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": str(roles.count("server")),
    }
    children = [
        subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"],
            env={**os.environ, **topo, "DMLC_ROLE": role}, cwd=ROOT, stdout=sys.stderr,
        )
        for role in roles
    ]
    os.environ.update(topo, DMLC_ROLE="worker", BYTEPS_FORCE_DISTRIBUTED="1")
    return children


def children_faults(roles: list, children: list) -> list:
    """Why the children do not count as a sound PS plane; empty if they do:
    all alive, none mapped libtpu (they never touch the device), every server
    mapped the native reducer."""
    faults = []
    for role, proc in zip(roles, children):
        if proc.poll() is not None:
            faults.append(f"{role} exited with {proc.returncode}")
            continue
        with open(f"/proc/{proc.pid}/maps") as f:
            maps = f.read()
        if "libtpu" in maps:
            faults.append(f"{role} mapped libtpu")
        if role == "server" and "libbyteps_tpu.so" not in maps:
            faults.append("server did not map libbyteps_tpu.so")
    return faults


def stop_children(children: list) -> None:
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
# compilation accounting (after chip_smoke.CompileStats)
# ---------------------------------------------------------------------------


class CompileWatch:
    """Backend compilations and persistent-cache hits, by jax.monitoring.
    Listeners fire on whichever thread compiles (the engine's stage threads
    build the slice programs), hence the lock."""

    def __init__(self, jax) -> None:
        self._lock = threading.Lock()
        self.n, self.secs, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.n += 1
                self.secs += secs

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    def mark(self) -> tuple:
        with self._lock:
            return self.n, self.secs, self.hits


# ---------------------------------------------------------------------------
# reference, window
# ---------------------------------------------------------------------------


def reference_run(jax, plain_loss, tx, params, batch, steps: int) -> tuple:
    """The first ``steps`` training steps by the plain reference:
    ``jax.value_and_grad`` of the configuration's plain loss and the same
    optax transformation in one ``jax.jit`` — nothing of byteps_tpu.  It works
    on a copy of ``params`` made on the device and donates only that, so the
    state is made once.  Returns the losses and the parameters it ends with;
    the optimizer state dies with this frame."""
    import jax.numpy as jnp
    import optax

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def ref_step(p, s, b):
        loss, grads = jax.value_and_grad(plain_loss)(p, b)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    p = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))(params)
    s, losses = jax.jit(tx.init)(p), []
    for _ in range(steps):
        p, s, loss = ref_step(p, s, batch)
        losses.append(float(loss))
    return losses, p


def _leaf_distances(a, b):
    """``||x - y||`` for every pair of leaves of two like trees."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)


def footprint(jax) -> int:
    """Bytes of HBM held on the fullest device now: buffers in use plus what
    the runtime has reserved for the loaded programs' temporaries (a step's
    activations live there, not among the buffers), both from one
    ``memory_stats()`` reading, so the sum is a state the device was in and
    never more than it has.  0 where the backend keeps no such figures."""
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0) for s in stats)


def run_window(jax, step, seconds: float, plan: dict | None, collect_every: int) -> dict:
    """Whole steps in a closed loop until ``seconds`` have passed; the window
    closes on the first step boundary at or after that.  Where the traffic
    says so, the loop runs a full ``gc.collect()`` after every
    ``collect_every`` steps, inside the window and counted in it, as a
    training script over a step that leaves its buffers in reference cycles
    must.  ``plan`` (traced runs)
    profiles up to ``max_steps`` steps or ``max_seconds`` from the third step
    on; starting and stopping the profiler falls between steps and is taken
    out of the window's length.  At step boundaries, at most four times a
    second, the device's memory footprint is read; the largest reading is the
    run's ``memory_peak_bytes``.  Every step's wall seconds (dispatch to the
    ``block_until_ready`` of its loss and parameters) and every collection's
    are kept in order (``step_s``, ``collect_s``): with the memory readings
    between them they add up to the window, and say whether a slow run had a
    few stalled steps or every step slower."""
    from jax.profiler import ProfileOptions, TraceAnnotation, start_trace, stop_trace

    attempted = failed = traced = 0
    losses, overhead, tracing, t_traced = [], 0.0, False, 0.0
    peak_bytes, t_read = 0, -1.0
    step_s, collect_s = [], []  # each step's and each collection's wall seconds, in order
    t_open = time.perf_counter()
    while True:
        now = time.perf_counter()
        if now - t_read >= 0.25:
            peak_bytes, t_read = max(peak_bytes, footprint(jax)), now
        if tracing and (traced >= plan["max_steps"] or now - t_traced >= plan["max_seconds"]):
            stop_trace()
            tracing, plan = False, None
            overhead += time.perf_counter() - now
        if now - t_open - overhead >= seconds:
            break
        if collect_every and attempted and attempted % collect_every == 0:
            t_collect = time.perf_counter()
            with TraceAnnotation(xplane.COLLECT):
                gc.collect()
            collect_s.append(time.perf_counter() - t_collect)
        if plan and not tracing and attempted >= 2:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = ProfileOptions()
            options.python_tracer_level = 0  # the host's Python frames are not read
            start_trace(TRACE_DIR, profiler_options=options)
            tracing, t_traced = True, time.perf_counter()
            overhead += t_traced - now
        attempted += 1
        t_step = time.perf_counter()
        try:
            with TraceAnnotation(xplane.CALL):
                out = step()
            with TraceAnnotation(xplane.BLOCK):
                loss = float(jax.block_until_ready(out)[0])
        except Exception as e:  # noqa: BLE001 — a failed step is counted, and ends the run
            say(f"step {attempted} raised {type(e).__name__}: {e}")
            failed += 1
            break
        step_s.append(time.perf_counter() - t_step)
        traced += tracing
        losses.append(loss)
        if not math.isfinite(loss):
            failed += 1
    if tracing:
        stop_trace()
    t_close = time.perf_counter()
    return {"attempted": attempted, "failed": failed, "losses": losses,
            "window_s": t_close - t_open - overhead, "traced": traced,
            "peak_bytes": max(peak_bytes, footprint(jax)),
            "step_s": step_s, "collect_s": collect_s}


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU pre-flight at the configuration's rehearsal cuts; "
                         "prints correct: false and no metric")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "byteps_tpu", "native")):
        raise SystemExit(f"{ROOT} holds no byteps_tpu checkout: nothing to measure")
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if not args.rehearse and pinned and "tpu" not in pinned.lower().split(","):
        raise SystemExit(f"the benchmark measures the TPU; JAX_PLATFORMS={pinned!r} "
                         "pins jax elsewhere (--rehearse is the CPU pre-flight)")
    bench, cell, config, traffic = load_cell(args.workload, args.rehearse)
    sys.path[0] = ROOT  # byteps_tpu; and benchmark/ shadows nothing
    subprocess.run(["make", "-C", os.path.join(ROOT, "byteps_tpu", "native")],
                   check=True, stdout=sys.stderr)
    say("native library built")
    roles = traffic["children"]
    children = start_children(roles) if roles else []  # before this process touches JAX
    try:
        result = measure(args, bench, cell, config, traffic, roles, children)
    finally:
        stop_children(children)
    for name, c in result["compared"].items():  # the last lines of stderr
        say(f"compared {name} {c['value']} limit {c['limit']}{'' if c['ok'] else ' NOT CORRECT'}")
    print(json.dumps(result), flush=True)
    return 0


def measure(args, bench, cell, config, traffic, roles, children) -> dict:
    import jax

    watch = CompileWatch(jax)
    import byteps_tpu as bps
    from byteps_tpu.comm.mesh import get_global_mesh

    say("jax and byteps_tpu imported")

    for role, proc in zip(roles, children):
        # init() waits for the scheduler's address book: a child that died
        # importing must fail the run here, not hang it there
        if proc.poll() is not None:
            raise SystemExit(f"{role} child exited early with {proc.returncode}")
    bps.init()  # compile cache placed, dp mesh over every device, PS plane joined
    # the ~40 small eager programs of a PS step and the engine's slice programs
    # each compile in under jax's 1 s floor: store them too, so that only a
    # cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    dev, chips = jax.devices()[0], jax.device_count()
    peaks = load_json(HERE, "peaks.json")["kinds"]
    if (dev.platform == "tpu") == args.rehearse:
        raise SystemExit(f"the benchmark measures the TPU (and --rehearse stays off "
                         f"it); jax found {dev.platform!r} ({dev.device_kind})")
    if not args.rehearse and dev.device_kind not in peaks:
        raise SystemExit(f"no peak figures for device_kind {dev.device_kind!r} in "
                         f"benchmark/peaks.json (known: {sorted(peaks)})")
    mesh = get_global_mesh()
    if chips != cell["chips"] or dict(mesh.shape) != traffic["mesh"]:
        raise SystemExit(f"cell {cell['name']} needs {cell['chips']} chip(s) in mesh "
                         f"{traffic['mesh']}; jax has {chips}, mesh {dict(mesh.shape)}")
    say(f"{cell['name']} on {dev.platform} {dev.device_kind} x{chips}, jax "
        f"{jax.__version__}, compile cache {jax.config.jax_compilation_cache_dir}")

    builder = load_module("builders", config["builder"])
    # any whole number up to a little over 2**31, folded into a 32-bit key
    key = jax.random.fold_in(jax.random.PRNGKey(args.seed & 0x7FFFFFFF), args.seed >> 31)
    warm = traffic["warmup_steps"]
    params, batch, global_batch = builder.make_state(config, key, mesh)
    grad_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    reference, ref_params = reference_run(
        jax, builder.plain_loss(config), builder.make_optimizer(config), params, batch, warm)
    distances = jax.jit(_leaf_distances)
    ref_moved = distances(ref_params, params)  # how far the reference's steps took each leaf
    say(f"reference ran {warm} steps")
    step = builder.build(config, traffic, params, batch, mesh)
    del params
    say("system built")
    warm_losses = []
    for _ in range(warm):
        out = jax.block_until_ready(step())
        warm_losses.append(float(out[0]))
    # the largest share by which a warm-up loss misses the reference's; one that
    # is no number misses by everything (max would drop a NaN that is not first)
    off = max(math.inf if math.isnan(x) else x
              for x in (abs(g - w) / abs(w) for g, w in zip(warm_losses, reference)))
    # the system's parameters against the reference's after the same steps, leaf
    # by leaf, as a share of how far the reference moved that leaf: a lost,
    # halved, doubled or stale gradient shows here at its full size, however
    # little the loss has fallen
    sys_off, moved = jax.device_get((distances(out[1], ref_params), ref_moved))
    del out, ref_params, ref_moved
    apart = {}
    for (path, d), m in zip(jax.tree_util.tree_leaves_with_path(sys_off), jax.tree.leaves(moved)):
        apart[jax.tree_util.keystr(path)] = float(d / m) if m else (math.inf if d else 0.0)
    worst = max(apart, key=lambda k: math.inf if math.isnan(apart[k]) else apart[k])
    whole = math.hypot(*jax.tree.leaves(sys_off)) / math.hypot(*jax.tree.leaves(moved))
    say(f"losses of the first {warm} steps {warm_losses}, reference {reference}, apart by at "
        f"most {off:.2e} of the reference; parameters after them apart from the reference's by "
        f"{whole:.2e} of the reference's own update over all leaves, at most {apart[worst]:.2e} "
        f"in one, {worst}")

    gc.collect()  # every run's window opens on the same state: what warm-up left in cycles is freed
    n0, compile_s, hits = watch.mark()
    before = {"counters": bps.get_robustness_counters(),
              "histograms": bps.get_metrics()["histograms"]}
    setup_s = time.perf_counter() - T_START
    window = run_window(jax, step, args.seconds, traffic["trace"] if args.trace else None,
                        traffic.get("collect_every_steps", 0))
    after = {"counters": bps.get_robustness_counters(),
             "histograms": bps.get_metrics()["histograms"]}
    window_compiles = watch.mark()[0] - n0
    steps = window["attempted"] - window["failed"]
    peak_in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.local_devices())
    say(f"set-up {setup_s:.2f} s ({n0} compilations, {compile_s:.1f} s compiling, "
        f"{hits} cache hits); window {window['window_s']:.3f} s, {steps} steps, "
        f"{window_compiles} compilations inside it, {sum(window['collect_s']):.3f} s of it in "
        f"{len(window['collect_s'])} gc.collect(); footprint "
        f"{window['peak_bytes'] / 2**30:.3f} GiB; loss "
        f"{warm_losses[0]:.6g} at the first warm-up step, {min(window['losses'], default=math.nan):.6g} "
        f"to {max(window['losses'], default=math.nan):.6g} in the window, "
        f"{(window['losses'] or [math.nan])[-1]:.6g} at its end")

    record = harness.step_record(window["step_s"], window["window_s"])
    if record:
        say("steps {steps}: median {p50_ms:.1f} ms, p10 {p10_ms:.1f}, p90 {p90_ms:.1f}, slowest "
            "{slowest_ms:.1f} at index {slowest_index}; {slow:.2f} % of the window in steps over "
            "{x} x the median, {inside:.2f} % in steps, {coll:.2f} % collecting".format(
                **record, slow=record["slow_share"] * 100, x=harness.SLOW,
                inside=record["in_steps_share"] * 100,
                coll=sum(window["collect_s"]) / window["window_s"] * 100))
        say("step ms: " + " ".join(f"{t * 1e3:.0f}" for t in window["step_s"][:400]))

    # ---- correct: decided outside the window --------------------------------
    # every number compared, beside its limit: {name: [value, limit]}; a number
    # passes at or under its limit (the loss has to end under its first value:
    # the largest ratio under 1).  The tolerances and their reasons stand in
    # the configuration's file.  A window without a whole step has failed
    losses = window["losses"]
    compared = {
        "steps_failed": [window["failed"] if losses else max(window["failed"], 1), 0],
        "loss_end_over_first": [losses[-1] / warm_losses[0] if losses else math.inf,
                                math.nextafter(1.0, 0.0)],
        "compiles_in_window": [window_compiles, 0],
        "loss_off_reference": [off, config["reference_rtol"]["value"]],
    }
    tol = config.get("reference_update_rtol")  # absent where the optimizer makes it powerless
    if tol:
        compared["update_off_all_leaves"] = [whole, tol["value"]]
        compared["update_off_worst_leaf"] = [apart[worst], tol["leaf_value"]]
    notes = []
    if traffic["step_path"] == "ps":
        ran = len(losses)  # every step that ran to its end moved its bytes
        if not args.rehearse:
            compared["grad_bytes_off"] = [abs(grad_bytes - config["grad_bytes_per_step"]), 0]
        for name in ("d2h_bytes", "wire_tx_bytes", "wire_rx_bytes", "h2d_bytes"):
            grown = after["counters"].get(name, 0) - before["counters"].get(name, 0)
            compared[f"{name}_off"] = [abs(grown - ran * grad_bytes), 0]
        notes = children_faults(roles, children)
        compared["children_faults"] = [len(notes), 0]
    if args.rehearse:
        notes.append("a rehearsal on the CPU is never a result")
    faults = [name for name, (value, limit) in compared.items() if not value <= limit]
    for note in notes:
        say(f"NOT CORRECT: {note}")
    if faults:  # the comparisons that failed are named again in the run's last lines
        say(f"failed: {', '.join(faults)}; worst leaf {worst}; warm-up losses {warm_losses} "
            f"against the reference's {reference}")
    faults += notes

    # ---- metrics -------------------------------------------------------------
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": chips,
              "memory_peak_bytes": window["peak_bytes"]}
    result = {"correct": not faults, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": {}, "device": device}
    if not args.trace:
        values = {"samples_per_s": steps * global_batch / window["window_s"],
                  "setup_s": setup_s}
        wanted = bench["end_to_end"]
    else:
        # a rehearsal's trace holds no TPU plane: its trace metrics stay out
        reduced = None if args.rehearse else xplane.reduce(xplane.load(TRACE_DIR))
        if reduced:
            if reduced["steps"] != window["traced"]:
                raise SystemExit(f"traced {window['traced']} steps, the trace holds "
                                 f"{reduced['steps']}")
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            result["breakdown"] = {k: reduced[k] for k in ("device_ops", "idle_gaps")}
        run = {
            "compile": {"setup_compile_s": compile_s, "window_compiles": window_compiles},
            "counters": {"before": before["counters"], "after": after["counters"]},
            "histograms": {"before": before["histograms"], "after": after["histograms"]},
            "steps": steps, "window_s": window["window_s"], "global_batch": global_batch,
            "step_s": window["step_s"],
            "flops_per_sample": builder.flops_per_sample(config), "chips": chips,
            "peak_flops_per_s": peaks.get(dev.device_kind, {}).get("bf16_flops_per_s"),
            "peak_hbm_bytes": window["peak_bytes"], "peak_in_use_bytes": peak_in_use,
            "trace": reduced,
        }
        wanted = [m for m in bench["per_layer"]
                  if cell["name"] in m.get("workloads", [cell["name"]])]
        values = {}
        for m in wanted:
            spec = load_json(HERE, "metrics", f"{m['name']}.json")
            values[m["name"]] = load_module("readers", spec["reader"]).read(run, **spec["args"])
    units = {m["name"]: m["unit"] for m in wanted}
    found = {k: {"value": v, "unit": units[k]} for k, v in values.items()
             if k in units and v is not None}
    if args.rehearse:
        result["rehearsal"] = found  # a CPU number never stands under "metrics"
    else:
        result["metrics"] = found
    bps.shutdown()
    # last in the line: what decided ``correct`` (a number json cannot hold, by its name)
    result["compared"] = {name: {"value": v if math.isfinite(v) else repr(v), "limit": limit,
                                 "ok": name not in faults}
                          for name, (v, limit) in compared.items()}
    return result


if __name__ == "__main__":
    raise SystemExit(main())
