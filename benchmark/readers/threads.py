"""Reader ``threads``: the worker's threads on the PS hop, in the profiler's trace.

Loads the newest ``.xplane.pb`` under ``.bench_trace/`` as ``phases`` does
(``benchmark/xplane.py`` and ``phases.py`` by file; the same window: first
``bench.step.call`` start to last ``bench.step.block`` end, device 0) but
keeps the host's events BY THREAD, and looks inside the caller's
``bps.hybrid.hop_wait`` only.  ``serving`` spans are a thread's work on the
hop: ``bps.stage.*`` (a stage thread's service of one task),
``bps.recv.frame.*`` (a receive thread's, header to the callback's return)
and ``bps.engine.finalize``.  Per traced step:

``hop_uncovered_ms``: time in which device 0 ran nothing and NO serving span
was open on any thread: the hop waits for the server, the wire or a
transfer, and no worker thread could have done anything sooner.
``threads_in_service``: the threads' time in service inside the hop (a
thread's nested or touching spans counted once) / the hop's own length:
1.0 is one thread at a time, as one GIL would have it if none of the
service were spent in the kernel.

A program whose receive threads open no ``bps.recv.frame.*`` span (the parent
of the PR that brought them) reads nothing: without them the account has a
hole exactly where a reply is received.  So does a run with no TPU trace.
"""

from __future__ import annotations

import functools
import glob
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
HOP = "bps.hybrid.hop_wait"
RECV = "bps.recv.frame."
SERVING = ("bps.stage.", RECV, "bps.engine.finalize")


@functools.cache
def _phases():
    """readers/phases.py by file: its window, idle intervals, overlap, xplane."""
    spec = importlib.util.spec_from_file_location(
        "bench_threads_phases", os.path.join(HERE, "phases.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def _load(path: str, mtime: float) -> dict:
    """``{"threads": {(plane, line): the bps.* events of that thread as (name,
    start_s, end_s)}, "bench": the harness's, "ops": device 0's operations}``."""
    from jax.profiler import ProfileData

    xp = _phases()._xplane()
    out, devices = {"threads": {}, "bench": [], "ops": []}, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == xp.OPS_LINE:
                    devices[int(plane.name.rsplit(":", 1)[1])] = xp._events(line)
        elif plane.name.startswith("/host:"):
            for index, line in enumerate(plane.lines):
                events = xp._events(line)
                out["bench"] += [e for e in events if e[0].startswith("bench.")]
                spans = [e for e in events if e[0].startswith(xp.PHASE)]
                if spans:
                    out["threads"][plane.name, index, line.name] = spans
    if devices:
        out["ops"] = devices[min(devices)]
    return out


def newest_trace() -> dict | None:
    paths = sorted(glob.glob(os.path.join(_phases().TRACE_DIR, "**", "*.xplane.pb"),
                             recursive=True))
    return _load(paths[-1], os.path.getmtime(paths[-1])) if paths else None


def _intersect(xs: list, ys: list) -> list:
    """What two sorted lists of disjoint intervals share, as such a list."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            out.append((max(a, ys[k][0]), min(b, ys[k][1])))
            k += 1
    return out


def measure(trace: dict, quantity: str):
    phases = _phases()
    union = phases._xplane().union
    lo, hi, steps = phases.window(trace["bench"])
    every = [e for spans in trace["threads"].values() for e in spans]
    hop = union([e for e in every if e[0] == HOP], lo, hi)
    if not steps or not hop or not any(e[0].startswith(RECV) for e in every):
        return None
    served = [_intersect(union([e for e in spans if e[0].startswith(SERVING)], lo, hi), hop)
              for spans in trace["threads"].values()]
    seconds = lambda intervals: sum(b - a for a, b in intervals)  # noqa: E731
    if quantity == "threads_in_service":
        return sum(seconds(s) for s in served) / seconds(hop)
    if quantity == "hop_uncovered_ms":
        idle = _intersect(phases._idle(trace["ops"], lo, hi), hop)
        anyone = union([("", a, b) for s in served for a, b in s], lo, hi)
        return (seconds(idle) - phases._overlap(idle, anyone)) / steps * 1e3
    raise ValueError(f"threads reader has no quantity {quantity!r}")


def read(run: dict, quantity: str):
    if not run.get("trace"):  # a rehearsal's trace holds no TPU plane
        return None
    trace = newest_trace()
    return measure(trace, quantity) if trace else None
