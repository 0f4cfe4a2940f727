"""From a profiler trace to numbers: the one reduction every PR is read by.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into plain
tuples; everything after that is arithmetic on ``(name, start_s, end_s)``
lists, so benchmark/tests can check it on a hand-built trace.  Nothing here
comes from ``byteps_tpu/profiler.py``.

The traced window is what the harness's own annotations bound: it wraps every
traced step's dispatch in ``bench.step.call`` and the wait for its loss and
parameters in ``bench.step.block`` (``jax.profiler.TraceAnnotation``, so they
sit on the profiler's clock beside the device's operations).
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

CALL, BLOCK, COLLECT = "bench.step.call", "bench.step.block", "bench.collect"
#: host annotations kept: the harness's, and the program's phases (tracing.span)
HOST_PREFIXES = ("bench.", "bps.")
PHASE = "bps."
#: lines of a device plane: operations, and the programs they belong to
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def load(trace_dir: str) -> dict:
    """``{"devices": {ordinal: {"ops": [...], "modules": [...]}}, "host": [...]}``
    with events as ``(name, start_s, end_s)``; host events are the harness's
    ``bench.*`` annotations and the program's ``bps.*`` phases, of every
    thread."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(paths[-1]).planes:
        dev = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                kind = "ops" if line.name == OPS_LINE else "modules"
                slot = out["devices"].setdefault(int(dev.group(1)), {"ops": [], "modules": []})
                slot[kind].extend(_events(line))
            elif plane.name.startswith("/host:"):
                out["host"].extend(e for e in _events(line) if e[0].startswith(HOST_PREFIXES))
    return out


def _events(line) -> list:
    return [(_op_name(e.name), e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def _op_name(text: str) -> str:
    """An operation is named by its whole HLO line, ``%psum.2 = f32[8]{0}
    all-reduce(...)``.  What stays the same from run to run is the name before
    the ``=``; the opcode is added where the name does not say it, so that a
    reader can ask for every ``all-reduce``."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    opcode = re.search(r"(?<=\s)([a-z][a-z0-9-]*)\(", " " + rest)
    if opcode and opcode.group(1) not in name:
        name += ":" + opcode.group(1)
    return name


def union(intervals, lo: float, hi: float) -> list:
    """Disjoint, sorted ``(start, end)`` covering what ``intervals`` cover
    inside ``[lo, hi]``."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for _, a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_seconds(ops, lo: float, hi: float) -> dict:
    """Seconds per operation name inside ``[lo, hi]``, a parent (a ``while``
    around its body) charged only for what its children leave uncovered."""
    total = collections.defaultdict(float)
    stack = []  # [name, end, seconds still its own]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            total[name] += max(own, 0.0)

    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    close(float("inf"))
    return dict(total)


def _label(text: str) -> str:
    """A program's name without its run id, in the alphabet of a metric name."""
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", re.sub(r"\(\d+\)$", "", text)).strip("_")


def idle_gaps(busy, modules, host, lo: float, hi: float) -> dict:
    """Seconds of device idleness inside ``[lo, hi]`` by what surrounded it:
    ``<what the host was doing>:<program before>_-_<program after>``.  The
    host is doing the innermost (shortest) of the program's ``bps.*`` phases
    open on any thread at the gap's middle; where none is, the innermost of
    the harness's ``bench.*`` annotations; else ``outside_step``.  ``busy``
    is ``union``'s output."""
    modules = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in modules]
    edges = [lo] + [t for pair in busy for t in pair] + [hi]
    host = sorted(host, key=lambda h: h[1])
    nxt, open_now = 0, []  # gaps come in time order: one sweep over the host's events
    total = collections.defaultdict(float)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a <= 0:
            continue
        mid = (a + b) / 2
        while nxt < len(host) and host[nxt][1] <= mid:
            open_now.append(host[nxt])
            nxt += 1
        open_now = [h for h in open_now if mid < h[2]]
        inside = [h for h in open_now if h[0].startswith(PHASE)] or open_now
        doing = min(inside, key=lambda h: h[2] - h[1])[0] if inside else "outside_step"
        i = bisect.bisect_right(starts, mid)  # programs run one after another
        before = _label(modules[i - 1][0]) if i else "window_start"
        after = _label(modules[i][0]) if i < len(modules) else "window_end"
        total[f"{doing}:{before}_-_{after}"] += b - a
    return dict(total)


def reduce(trace: dict) -> dict:
    """Everything the ``trace`` reader and the result line take from one
    traced window; times in seconds.  Per-device figures are device 0's,
    ``busy_s`` the mean over the devices that ran anything."""
    calls = [h for h in trace["host"] if h[0] == CALL]
    blocks = [h for h in trace["host"] if h[0] == BLOCK]
    if not calls or not blocks or not trace["devices"]:
        raise ValueError(
            f"trace has {len(calls)} {CALL}, {len(blocks)} {BLOCK} events and "
            f"{len(trace['devices'])} device planes: nothing to reduce"
        )
    lo, hi = min(h[1] for h in calls), max(h[2] for h in blocks)
    busy = {d: union(v["ops"], lo, hi) for d, v in sorted(trace["devices"].items())}
    busy_s = {d: sum(b - a for a, b in iv) for d, iv in busy.items()}
    first = min(busy)
    op_seconds = self_seconds(trace["devices"][first]["ops"], lo, hi)
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]  # noqa: E731
    gaps = idle_gaps(busy[first], trace["devices"][first]["modules"], trace["host"], lo, hi)
    return {
        "steps": len(calls),
        "window_s": hi - lo,
        "busy_s": sum(busy_s.values()) / len(busy_s),
        "busy0_s": busy_s[first],
        "op_seconds": op_seconds,
        "device_ops": [[_label(k), v] for k, v in top(op_seconds)],
        "idle_gaps": [[k, v] for k, v in top(gaps)],
    }
