"""Reader ``phases``: the program's own phases in the profiler's trace.

The program wraps its phases in ``jax.profiler.TraceAnnotation``s named
``bps.<span>`` (``byteps_tpu/core/tracing.span``) and its compiled steps in
``jax.named_scope``s (``forward``, ``grad_sync``, ``optimizer``), so the one
trace that holds the device's timeline holds them too.  This reader loads the
newest ``.xplane.pb`` under ``.bench_trace/`` itself, bounds the window as
``benchmark/xplane.py``'s ``reduce`` does (first ``bench.step.call`` start to
last ``bench.step.block`` end, device 0) and gives, per traced step:

``idle_in_ms``: time in which no operation ran on device 0 and a span named
``match`` was open on some thread (overlapping spans counted once).
``idle_unattributed_share``: % of device 0's idle time that no ``bps.*``
phase covers; the span of the whole step (``bps.hybrid.step``) is no phase:
it would cover whatever its children leave unnamed.  ``scope_ms``: self time
(a ``while`` charged for what its body leaves) of one device's operations
whose scope path files under ``match`` = ``forward``, ``backward`` or
``optimizer``: the largest over the devices.  Every chip of a data-parallel
step runs the same programs, and the profiler loses events of a busy device,
never invents them: on four chips device 0, which also runs the hop's ≈ 170
small programs a step, kept 1 of 5 ``jit_hybrid_apply`` runs in a traced
window where the three others kept all 5 (PERF.md §6, PR 33).

A program without such spans or without the ``forward`` scope (the parent of
the PR that brought them) gives None for each, and so does a run with no TPU
trace.
"""

from __future__ import annotations

import collections
import functools
import glob
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(HERE)), ".bench_trace")
WHOLE_STEP = "bps.hybrid.step"


@functools.cache
def _xplane():
    """benchmark/xplane.py by file: ``union``, ``self_seconds``, the names."""
    spec = importlib.util.spec_from_file_location(
        "bench_xplane", os.path.join(os.path.dirname(HERE), "xplane.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---- the event metadata's stats, which ProfileData does not show -----------------
# An operation's scope path (its HLO ``op_name``) is the stat ``tf_op`` of the
# event's *metadata*; ``jax.profiler.ProfileData`` gives an event's own stats
# only.  So the metadata tables are read from the file's protobuf wire format:
# XSpace.planes=1; XPlane.name=2 .event_metadata=4 .stat_metadata=5 (map
# entries: key=1, value=2); XEventMetadata.name=2 .stats=5; XStatMetadata.name=2;
# XStat.metadata_id=1 .str_value=5 .ref_value=7.  Lines and events are skipped.


def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field, value)`` of one message; a length-delimited value is a view."""
    i, size = 0, len(buf)
    while i < size:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            length, i = _varint(buf, i)
            value, i = buf[i:i + length], i + length
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _map_value(entry):
    return next(v for f, v in _fields(entry) if f == 2)


def scope_paths(data: bytes) -> dict:
    """``{event name: scope path}`` over the device planes of a serialized
    XSpace: the ``tf_op`` stat of every event metadata that has one."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(_map_value(v))
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        for meta in events:
            event_name, path = "", None
            for f, v in _fields(meta):
                if f == 2:
                    event_name = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        path = (bytes(stat[5]).decode() if 5 in stat
                                else stat_names.get(stat.get(7), ""))
            if path:
                out[event_name] = path
    return out


def classify(path: str) -> str | None:
    """``forward``, ``backward`` or ``optimizer`` for a scope path such as
    ``jit(step)/transpose(jvp(forward))/dot_general:``.  Whatever runs under
    ``transpose(`` — recomputed forward operations too — is backward."""
    if "transpose(" in path:
        return "backward"
    parts = path.split("/")
    if "optimizer" in parts:
        return "optimizer"
    if any(p in ("forward", "jvp(forward)") for p in parts):
        return "forward"
    return None


# ---- one trace, loaded once ------------------------------------------------------


@functools.cache
def _load(path: str, mtime: float) -> dict:
    """``{"spans": the bps.* events of every host thread as (name, start_s,
    end_s), "bench": the harness's, "ops": device 0's operations under their
    whole names, "device_ops": every device's by ordinal, "paths":
    scope_paths}``."""
    from jax.profiler import ProfileData

    xp = _xplane()
    with open(path, "rb") as f:
        data = f.read()
    out = {"spans": [], "bench": [], "ops": [], "paths": scope_paths(data)}
    devices = {}
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == xp.OPS_LINE:
                    devices[int(plane.name.rsplit(":", 1)[1])] = [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = xp._events(line)
                out["bench"] += [e for e in events if e[0].startswith("bench.")]
                out["spans"] += [e for e in events if e[0].startswith("bps.")]
    if devices:
        out["ops"], out["device_ops"] = devices[min(devices)], devices
    return out


def newest_trace() -> dict | None:
    paths = sorted(glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"), recursive=True))
    return _load(paths[-1], os.path.getmtime(paths[-1])) if paths else None


# ---- arithmetic on (name, start_s, end_s) lists ----------------------------------


def window(bench: list) -> tuple:
    """``(lo, hi, steps)`` as ``xplane.reduce`` bounds the traced steps."""
    xp = _xplane()
    calls = [h for h in bench if h[0] == xp.CALL]
    blocks = [h for h in bench if h[0] == xp.BLOCK]
    if not calls or not blocks:
        return 0.0, 0.0, 0
    return min(h[1] for h in calls), max(h[2] for h in blocks), len(calls)


def _covered(spans: list, keep, lo: float, hi: float) -> list:
    """Disjoint intervals in which a span that ``keep`` accepts was open on
    any thread."""
    return _xplane().union([e for e in spans if keep(e[0])], lo, hi)


def _idle(ops: list, lo: float, hi: float) -> list:
    edges = [lo] + [t for pair in _xplane().union(ops, lo, hi) for t in pair] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _overlap(xs: list, ys: list) -> float:
    """Seconds that two sorted lists of disjoint intervals share."""
    total, j = 0.0, 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            total += min(b, ys[k][1]) - max(a, ys[k][0])
            k += 1
    return total


def measure(trace: dict, quantity: str, match: str = ""):
    lo, hi, steps = window(trace["bench"])
    if not steps:
        return None
    if quantity == "scope_ms":
        found = []
        for ops in trace.get("device_ops", {0: trace["ops"]}).values():
            filed = collections.Counter()
            for name, own in _xplane().self_seconds(ops, lo, hi).items():
                filed[classify(trace["paths"].get(name, ""))] += own
            # a program from before the scopes has transpose( (jax's own) and
            # no forward: it reads nothing.  One with scopes whose update XLA
            # fused into the backward pass reads 0 under optimizer
            if filed["forward"]:
                found.append(filed[match] / steps * 1e3)
        return max(found, default=None)
    if quantity == "idle_unattributed_share":
        named = _covered(trace["spans"], lambda n: n != WHOLE_STEP, lo, hi)
        idle = _idle(trace["ops"], lo, hi)
        idle_s = sum(b - a for a, b in idle)
        if not named or not idle_s:
            return None
        return (1.0 - _overlap(idle, named) / idle_s) * 100.0
    if quantity == "idle_in_ms":
        covered = _covered(trace["spans"], lambda n: n == match, lo, hi)
        if not covered:
            return None
        return _overlap(_idle(trace["ops"], lo, hi), covered) / steps * 1e3
    raise ValueError(f"phases reader has no quantity {quantity!r}")


def read(run: dict, quantity: str, match: str = ""):
    if not run.get("trace"):  # a rehearsal's trace holds no TPU plane
        return None
    trace = newest_trace()
    return measure(trace, quantity, match) if trace else None
