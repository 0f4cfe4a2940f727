"""Chrome-trace timeline of communication stages + distributed spans.

Two event families share one tracer (docs/observability.md):

- **Stage envelopes** (:meth:`Tracer.record`) — the reference's tracing
  subsystem (global.cc:448-564, docs/timeline.md): per named tensor, per
  pipeline stage, ``{start, duration}`` intervals between
  trace_start_step and trace_end_step, one trace row per tensor.
- **Spans** (:meth:`Tracer.record_span`) — cross-process distributed
  tracing: every engine task gets a (trace id, span id) pair, the ids
  ride each framed RPC in the optional trace-context header field
  (transport.py), and the server stamps child spans
  (recv→sum→publish→reply) that share the worker's trace id.
  ``tools/trace_merge.py`` stitches the per-process files into one
  Perfetto-loadable timeline joined on those ids.

Emission is ``<dir>/<local_rank>/comm.json`` in Chrome trace-event
format (load via chrome://tracing or Perfetto).  ``flush()`` writes the
CURRENT window and clears the buffer, so :func:`profile` can
capture any number of windows per process (the pre-observability tracer
had a one-shot latch: the second flush silently dropped all events).

Host stages are stamped by the pipeline engine; device-side collective
timing is XLA's domain (use jax.profiler for that) — the tracer records
the host-visible envelope, which is what the reference records too.

**Phases on the profiler's clock** (:class:`span`, :func:`profile`): the
program's own phases — the two-level step's and the engine's stages — are
``jax.profiler.TraceAnnotation`` events named ``bps.<name>``, so they sit in
the profiler's ``.xplane.pb`` beside the device's operations; every span
also lands in the ``span_seconds{name}`` histogram, and in this tracer's
file when it is on.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import random
import threading
import time
import weakref
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation, start_trace, stop_trace

from byteps_tpu.core.flightrec import (
    ensure_process_recorder,
    get_process_recorder,
    host_deltas,
    host_readings,
    watch_gc,
)
from byteps_tpu.core.telemetry import HeldHistogram, metrics

_id_rng = random.SystemRandom()


def new_trace_id() -> int:
    """Nonzero 63-bit id for a trace or span.  SystemRandom: training
    code may have seeded the global RNG for data order, and two workers
    seeding identically must never mint colliding trace ids."""
    return _id_rng.getrandbits(63) | 1


def span_args(trace_id: int, span_id: int, parent_id: int = 0,
              **extra) -> dict:
    """Canonical args dict for a span event — hex strings so Perfetto's
    JSON importer (which parses large ints as doubles) never rounds an
    id."""
    args = {"trace": format(trace_id, "x"), "span": format(span_id, "x")}
    if parent_id:
        args["parent"] = format(parent_id, "x")
    args.update(extra)
    return args


class Tracer:
    #: in-memory event cap: span events are window-free, so a long run
    #: with tracing on must not grow the buffer unboundedly — beyond the
    #: cap new events are dropped (counted; flush logs the loss)
    MAX_EVENTS = 1 << 18

    def __init__(
        self,
        enabled: bool = False,
        start_step: int = 10,
        end_step: int = 20,
        trace_dir: str = ".",
        local_rank: int = 0,
        process_name: str = "",
        spans_enabled: bool = True,
    ) -> None:
        self.enabled = enabled
        self.start_step = start_step
        self.end_step = end_step
        self.trace_dir = trace_dir
        self.local_rank = local_rank
        #: BYTEPS_TRACE_SPANS gate: False keeps the per-tensor stage
        #: envelopes but drops span events (and wire trace context)
        self.spans_enabled = spans_enabled
        #: cross-process identity stamped on span events ("worker0",
        #: "server1"); set once the scheduler assigns a rank
        self.process_name = process_name or f"rank{local_rank}"
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._dropped = 0  # events past MAX_EVENTS since the last flush
        self._steps: Dict[str, int] = {}  # per-tensor version counter

    def _active(self, step: int) -> bool:
        return self.enabled and self.start_step <= step <= self.end_step

    def step_of(self, name: str) -> int:
        with self._lock:
            return self._steps.get(name, 0)

    def bump_step(self, name: str) -> int:
        with self._lock:
            s = self._steps.get(name, 0) + 1
            self._steps[name] = s
            return s

    def _append_locked(self, event: dict) -> None:
        """Caller holds ``self._lock``.  Enforces MAX_EVENTS: a capped
        buffer drops (and counts) instead of growing until OOM — span
        events have no step window, so a long tracing-on run would
        otherwise accumulate forever between flushes."""
        if len(self._events) >= self.MAX_EVENTS:
            self._dropped += 1
            return
        self._events.append(event)

    def record(self, name: str, stage: str, start: float, dur: float, step: int) -> None:
        """One complete-event per (tensor, stage) interval
        (global.cc:478-530 emits type 'X' events keyed the same way)."""
        if not self._active(step):
            return
        with self._lock:
            self._append_locked(
                {
                    "name": stage,
                    "cat": "comm",
                    "ph": "X",
                    "ts": start * 1e6,
                    "dur": dur * 1e6,
                    "pid": name,  # one trace row per tensor, like the reference
                    "tid": stage,
                }
            )

    # --- distributed spans (docs/observability.md) -----------------------

    def record_span(self, track: str, name: str, start: float, dur: float,
                    args: Optional[dict] = None) -> None:
        """One complete-event span on this process's timeline.  ``track``
        groups related spans on one row (tensor name, "engine", …);
        ``args`` should come from :func:`span_args` so merge joins work.
        Timestamps are wall-clock (``time.time()``) so per-host worker
        and server spans align on one merged timeline."""
        if not self.enabled or not self.spans_enabled:
            return
        with self._lock:
            self._append_locked(
                {
                    "name": name,
                    "cat": "span",
                    "ph": "X",
                    "ts": start * 1e6,
                    "dur": dur * 1e6,
                    "pid": self.process_name,
                    "tid": track,
                    "args": args or {},
                }
            )

    def record_instant(self, track: str, name: str,
                       args: Optional[dict] = None,
                       ts: Optional[float] = None) -> None:
        """Zero-duration marker (chaos fault tags, eviction moments)."""
        if not self.enabled or not self.spans_enabled:
            return
        with self._lock:
            self._append_locked(
                {
                    "name": name,
                    "cat": "span",
                    "ph": "i",
                    "s": "t",  # thread-scoped instant
                    "ts": (time.time() if ts is None else ts) * 1e6,
                    "pid": self.process_name,
                    "tid": track,
                    "args": args or {},
                }
            )

    def pending_events(self) -> int:
        with self._lock:
            return len(self._events)

    def flush(self) -> str:
        """Write the current window and clear the buffer; returns the
        output path, or "" when disabled or nothing was recorded.
        Multiple windows per process are supported: each
        :func:`profile` exit flushes its own window.  A window
        NEVER clobbers an earlier one — when ``comm.json`` already
        exists in the target directory (e.g. the shutdown flush landing
        in a dir a profiler window already used), the new window goes to
        ``comm.<n>.json``; ``tools/trace_merge.py`` globs ``comm*.json``
        so every window joins the merged timeline."""
        if not self.enabled:
            return ""
        with self._lock:
            if not self._events:
                return ""
            events, self._events = self._events, []
            dropped, self._dropped = self._dropped, 0
        if dropped:
            from byteps_tpu.common import logging as bpslog

            bpslog.warning(
                "tracer dropped %d events past the %d-event buffer cap "
                "(flush more often, or narrow the trace window)",
                dropped, self.MAX_EVENTS,
            )
        out_dir = os.path.join(self.trace_dir, str(self.local_rank))
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, "comm.json")
        n = 2
        while os.path.exists(path):
            path = os.path.join(out_dir, f"comm.{n}.json")
            n += 1
        payload = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            payload["otherData"] = {"dropped_events": dropped}
        with open(path, "w") as f:
            json.dump(payload, f)
        return path


#: process-global tracer — set by init_state (workers) / PSServer
#: (servers) so layers without runtime-state access (chaos van, PS
#: client) can stamp events on the owning process's timeline
_process_tracer: Optional[Tracer] = None


def set_process_tracer(tracer: Optional[Tracer]) -> None:
    global _process_tracer
    _process_tracer = tracer


def get_process_tracer() -> Optional[Tracer]:
    return _process_tracer


# --- phases on the profiler's clock ---------------------------------------

#: ``span_seconds{name}`` of every span name this process has closed, kept at
#: hand: a span's exit is one bisect, not a label set sorted and hashed under
#: the registry's lock (the handles outlive ``metrics().reset()``)
_span_hists: Dict[str, HeldHistogram] = {}

#: the innermost open :class:`span` of each thread, as ``(trace id, span
#: id)``; set only while the process tracer records spans
_current = threading.local()


#: the suffix a thread's spans take where a call site asks for it (a stage's
#: second thread over one queue: ``core/engine.py`` ``_loop``)
_thread = threading.local()


def tag_thread(suffix: str) -> None:
    """Name this thread's share of spans that several threads make: a call
    site that appends :func:`thread_tag` to a span's name keeps one series a
    thread (``rpc.send.PUSH`` | ``rpc.send.PUSH.1``), so each stays a part
    of that thread's own wall clock."""
    _thread.suffix = suffix


def thread_tag() -> str:
    return getattr(_thread, "suffix", "")


def current_span() -> Optional[Tuple[int, int]]:
    """``(trace id, span id)`` of the innermost :class:`span` open on this
    thread, or None (no span open, or the process tracer is off)."""
    return getattr(_current, "ids", None)


class span:
    """One phase of the program, ``with span("hybrid.hop_wait"): ...``:

    - a ``jax.profiler.TraceAnnotation`` named ``bps.<name>`` with ``attrs``
      as its stats: the phase lands in the profiler's trace, on its clock
      and on this thread (free while no profiler session runs);
    - one observation of its duration in ``span_seconds{name}``, always;
    - while the process :class:`Tracer` records spans, a span event on this
      thread's track.  Its parent is ``parent`` (a ``(trace id, span id)``
      pair: a stage thread names the task it serves) or else the innermost
      span open on this thread, whose trace id it shares: every span of one
      training step carries the step's id, and so does every job the step
      submits (``engine.submit`` reads :func:`current_span`).
    """

    __slots__ = ("name", "attrs", "parent", "started", "ended", "_annotation",
                 "_wall", "_ids", "_outer")

    def __init__(self, name: str, parent: Optional[Tuple[int, int]] = None,
                 **attrs) -> None:
        self.name = name
        self.attrs = attrs
        self.parent = parent
        self._annotation = TraceAnnotation("bps." + name, **attrs)

    def __enter__(self) -> "span":
        tracer = _process_tracer
        self._ids = None
        if tracer is not None and tracer.enabled and tracer.spans_enabled:
            self._outer = current_span()
            parent = self.parent or self._outer
            trace_id = parent[0] if parent else new_trace_id()
            self._ids = _current.ids = (trace_id, new_trace_id())
            self._wall = time.time()
        self._annotation.__enter__()
        self.started = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        # ``started`` and ``ended`` bound what span_seconds holds, on
        # time.perf_counter(): a caller that accounts for the time BETWEEN
        # its spans (a stage loop) reads them instead of a clock of its own
        self.ended = time.perf_counter()
        dur = self.ended - self.started
        self._annotation.__exit__(*exc)
        hist = _span_hists.get(self.name)
        if hist is None:
            hist = _span_hists[self.name] = metrics().held(
                "span_seconds", {"name": self.name})
        hist.observe(dur)
        if self._ids is not None:
            _current.ids = self._outer
            parent = self.parent or self._outer
            tracer = _process_tracer
            if tracer is not None:
                tracer.record_span(
                    threading.current_thread().name, self.name, self._wall, dur,
                    span_args(*self._ids, parent[1] if parent else 0,
                              **self.attrs),
                )
        return False


# --- a thread's service on two clocks --------------------------------------

#: the clocks of a sampled service, by name so that a test can stand others in
_wall_clock = time.perf_counter
_cpu_clock = time.thread_time


class _Sampling(threading.local):
    """``acc``: what the releasing calls of this thread's open SAMPLED service
    have taken so far, as ``[wall, cpu, brackets open]``; None where the
    thread is in no sampled service (the class's own, so that a thread which
    never sampled finds it without an AttributeError inside)."""

    acc: Optional[list] = None


_sampling = _Sampling()


class _Unsampled:
    """What :func:`releasing` hands a thread that is in no sampled service:
    one shared object that does nothing on the way in or out."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_UNSAMPLED = _Unsampled()


def releasing(name: Optional[str] = None):
    """Bracket of a call that lets the GIL go while it blocks, ``with
    releasing(): sock.sendmsg(...)`` (a socket's ``sendmsg`` | ``recv_into``,
    a lock's acquire, the wait for a device transfer, ``device_put``, a large
    numpy copy, a ctypes call).  Outside a sampled service (:class:`sampled`)
    it is one thread-local read and one branch: no clock is read and nothing
    is made.  Inside one, the bracket's wall and CPU time are added to the
    service's released part; a bracket inside a bracket adds nothing of its
    own.

    With ``name`` it is also a :class:`span` — ``bps.<name>`` on the
    profiler's clock and ``span_seconds{name}`` — so that a device's idle gap
    under a stage is named by the call the stage thread was blocked in."""
    acc = _sampling.acc
    if acc is None:
        return _UNSAMPLED if name is None else span(name)
    return _Releasing(acc, span(name) if name else None)


class _Releasing:
    """A :func:`releasing` bracket inside a sampled service."""

    __slots__ = ("_span", "_acc", "_wall", "_cpu")

    def __init__(self, acc: list, named: Optional["span"]) -> None:
        self._acc = acc
        self._span = named

    def __enter__(self) -> "_Releasing":
        if self._span is not None:
            self._span.__enter__()
        acc = self._acc
        acc[2] += 1
        if acc[2] == 1:
            self._wall, self._cpu = _wall_clock(), _cpu_clock()
        return self

    def __exit__(self, *exc) -> bool:
        acc = self._acc
        acc[2] -= 1
        if acc[2] == 0:
            # the CPU interval INSIDE the wall interval, here and in
            # sampled.end(): cpu <= wall whatever a clock reading costs or
            # charges (a thread_time() call is 6 us on a TPU v5e host, whose
            # CPU clock moves in ticks of 10 ms: read after the wall clock,
            # a tick that fell in the last reading made a 25 us service read
            # 227 % on the CPU; PERF.md section 6 PR 71).  So every pair of
            # intervals leaves about one call of wall outside the CPU: a
            # sampled service reads gilwait one call long and one call short
            # a bracket
            acc[1] += _cpu_clock() - self._cpu
            acc[0] += _wall_clock() - self._wall
        if self._span is not None:
            self._span.__exit__(*exc)
        return False


class sampled:
    """One thread's services, one in ``every`` split on two clocks.

    ``begin()`` ... ``end()`` bound a service; in a sampled one the wall clock
    W and the thread's CPU clock C are read at both ends, and every
    :class:`releasing` bracket the thread passes adds its own two readings to
    Wr and Cr.  ``stage_sample_seconds{stage, clock}`` then holds

    - ``cpu`` = C and ``wall`` = W;
    - ``held`` = C - Cr: on the CPU outside every releasing call, which a
      Python thread can only be while it HOLDS the GIL;
    - ``gilwait`` = (W - Wr) - (C - Cr): off the CPU outside every releasing
      call — the interpreter's hand-over (or the OS taking the core): a
      lower bound of the thread's wait for the GIL.

    What is left of W is Cr (on the CPU inside a releasing call: the
    kernel's copy of a ``sendmsg``) and Wr - Cr (blocked in the call, plus the
    wait to retake the GIL on the way out); held + gilwait + Wr = W.  ``held``
    errs upward by whatever releasing call has no bracket.  Threads of one
    kind (a link's receive threads, a server's serve threads) give the same
    ``stage`` and share its series; each makes its own ``sampled``."""

    CLOCKS = ("cpu", "wall", "held", "gilwait")
    #: a thread_time pair is 12 us on the chip's host, and a sampled service
    #: reads one at its ends and one a bracket, holding the GIL: 16 + 14 a
    #: bracket us.  At one in 16 (the rate up to PR 70, when a sample was one
    #: pair) that was 3 ms a VGG-16 step over the nine hop threads and
    #: `samples_per_s` read 2.3 % lower on four chips; at one in 61 it is
    #: under a millisecond (PERF.md section 6 PR 71).  Not rarer still: that
    #: host's thread CPU clock moves in ticks of 10 ms, a sampled service
    #: reads 0 or one tick, and only the sum over hundreds of them is a
    #: share.  A prime, so the sample walks through a step's tasks whatever
    #: their number
    EVERY = 61

    __slots__ = ("_hists", "_every", "_n", "_acc", "_wall", "_cpu")

    def __init__(self, stage: str, every: Optional[int] = None) -> None:
        self._hists = [
            metrics().held("stage_sample_seconds", {"stage": stage, "clock": c})
            for c in self.CLOCKS
        ]
        self._every = every or self.EVERY
        self._n = 0
        self._acc: Optional[list] = None

    def begin(self) -> None:
        self._n += 1
        if self._n % self._every == 0:
            self._acc = _sampling.acc = [0.0, 0.0, 0]
            self._wall, self._cpu = _wall_clock(), _cpu_clock()

    def end(self) -> None:
        acc = self._acc
        if acc is None:
            return
        cpu, wall = _cpu_clock() - self._cpu, _wall_clock() - self._wall
        self._acc = _sampling.acc = None
        held = cpu - acc[1]
        for hist, value in zip(
                self._hists, (cpu, wall, held, (wall - acc[0]) - held)):
            hist.observe(value)


#: threads alive by kind (``threads_alive{kind}``): what a kind's account of
#: a window has to add up to
_alive: Dict[str, int] = {}
_alive_lock = threading.Lock()


def _count_alive(kind: str, by: int) -> None:
    with _alive_lock:
        _alive[kind] = n = _alive.get(kind, 0) + by
    metrics().gauge_set("threads_alive", n, {"kind": kind})


class thread_account:
    """The wall clock of one thread that serves one frame or task after
    another, as the server's serve and engine threads do: every moment
    between ``__init__`` and ``close()`` is in one observation of
    ``thread_seconds{kind, state="service" | "idle"}`` — ``begin()`` ends an
    idle stretch and ``end()`` a service, ``tick()`` cuts an idle stretch
    that may last (a poll's timeout) so that a window's edge cuts little —
    and one service in ``sampled.EVERY`` is split by :class:`sampled` under
    ``stage=kind``.
    One series a KIND of thread: threads come and go with their connections,
    ``threads_alive{kind}`` says how many there are, and the sum is read."""

    __slots__ = ("_kind", "_service", "_idle", "_sample", "_mark")

    def __init__(self, kind: str) -> None:
        self._kind = kind
        self._service, self._idle = (
            metrics().held("thread_seconds", {"kind": kind, "state": s})
            for s in ("service", "idle"))
        self._sample = sampled(kind)
        self._mark = time.perf_counter()  # up to here all time is accounted
        _count_alive(kind, 1)

    def tick(self) -> None:
        now = time.perf_counter()
        self._idle.observe(now - self._mark)
        self._mark = now

    def begin(self) -> None:
        self.tick()
        self._sample.begin()

    def end(self) -> None:
        self._sample.end()
        now = time.perf_counter()
        self._service.observe(now - self._mark)
        self._mark = now

    def close(self) -> None:
        self.tick()
        _count_alive(self._kind, -1)


class stepped:
    """The seam around a compiled training step: what ``jax.jit`` made, called
    through two spans and a clock, so that the compiled path accounts for the
    host's side of a step as the two-level step's ``hybrid.*`` spans do.

    A call is ``span("train.dispatch", step=n, wall_ns=...)`` around the
    jitted call — the two stats ``hybrid.step`` carries, which tie the
    profiler's clock to ``time.time()`` — and, where the step has something
    to take off its outputs on the host (``fold``: ``build_train_step``'s
    routing statistics), ``span("train.fold")`` around that.

    **The step clock.**  Every entry takes the time since the previous entry:
    in a closed loop, and in a pipelined one that the runtime's queue
    throttles, that IS the previous step's time.  It is observed in
    ``train_step_interval_seconds`` and split: ``dispatch_s`` and ``fold_s``
    as the spans read them, and ``caller_s``, the rest — the caller's wait
    for the device and whatever else it does between two steps, which the
    program cannot span.  With it go :func:`flightrec.host_readings`' growth
    over the interval.  All of that is the evidence of the process's
    ``FlightRecorder.record_interval``, whose ``slow_step`` rule says on
    stderr which side of the device a step of several times the median was on
    (docs/observability.md "Reading a slow step"); ``BYTEPS_FLIGHT_STEPS=0``
    turns the rule off and leaves the spans and histograms.

    ``first``, where given, is called with the FIRST call's arguments and
    returns the arguments the jitted function is called with
    (``build_train_step``: an optimizer state that arrives uncommitted is
    committed, so that the second call finds the first's program); every
    later call pays one test for it.

    **A loop's last step** has no next entry to end it: :meth:`close` does,
    called for every live step by :func:`close_steps` at ``bps.shutdown()``
    and at interpreter exit, so that step reaches the rule too — with
    whatever the caller did between it and the close in its ``caller_s``.

    One thread calls a step.  Every attribute the jitted function has
    (``lower``, ``trace``, ``eval_shape`` …) is this object's too.
    """

    def __init__(self, jitted: Callable, fold: Optional[Callable] = None,
                 first: Optional[Callable] = None) -> None:
        self._jitted = jitted
        self._fold = fold
        self._first = first
        self._n = 0
        self._entered: Optional[float] = None  # the previous entry
        self._readings: tuple = ()
        self._dispatch_s = self._fold_s = 0.0
        self._interval = metrics().held("train_step_interval_seconds")
        watch_gc()
        with _open_steps_lock:
            _open_steps.add(self)

    def __getattr__(self, name: str):
        if name == "_jitted":  # not made yet: a copy, an unpickling
            raise AttributeError(name)
        return getattr(self._jitted, name)

    def __call__(self, *args, **kwargs):
        now, readings = time.perf_counter(), host_readings()
        if self._entered is not None:
            self._account(now - self._entered, readings)
        self._entered, self._readings = now, readings
        self._n += 1
        with span("train.dispatch", step=self._n, wall_ns=time.time_ns()) as call:
            if self._first is not None:
                args, self._first = self._first(*args), None
            out = self._jitted(*args, **kwargs)
        self._dispatch_s, self._fold_s = call.ended - call.started, 0.0
        if self._fold is not None:
            with span("train.fold") as fold:
                out = self._fold(out)
            self._fold_s = fold.ended - fold.started
        return out

    def close(self) -> None:
        """End the interval the last entry opened; the next call, if one
        comes, starts a loop of its own."""
        if self._entered is not None:
            self._account(time.perf_counter() - self._entered, host_readings(),
                          closed=True)
            self._entered = None

    def _account(self, interval: float, readings: tuple,
                 closed: bool = False) -> None:
        """The step that the previous entry began, now that it is over
        (``closed``: ended by :meth:`close`, and the record says so)."""
        self._interval.observe(interval)
        recorder = get_process_recorder() or ensure_process_recorder()
        if recorder.enabled:
            recorder.record_interval(interval, {
                "step": self._n, "dispatch_s": self._dispatch_s,
                "fold_s": self._fold_s,
                "caller_s": interval - self._dispatch_s - self._fold_s,
                **({"closed": True} if closed else {}),
                **host_deltas(self._readings, readings)})


#: every live :class:`stepped`, for :func:`close_steps`
_open_steps: "weakref.WeakSet[stepped]" = weakref.WeakSet()
_open_steps_lock = threading.Lock()


def close_steps() -> None:
    """Close the open interval of every compiled step (``bps.shutdown()``,
    interpreter exit): a loop's last step reaches the ``slow_step`` rule."""
    with _open_steps_lock:
        steps = list(_open_steps)
    for step in steps:
        step.close()


atexit.register(close_steps)


@contextlib.contextmanager
def profile(log_dir: str) -> Iterator[None]:
    """Capture an XLA profile of the block into ``log_dir`` — the device's
    operations and every ``bps.*`` span on one clock — and, where the
    process tracer is on, flush its current window into the same directory
    on exit.  Any number of windows per process; cross-process span files
    merge via ``tools/trace_merge.py`` (docs/observability.md)."""
    start_trace(log_dir)
    try:
        yield
    finally:
        stop_trace()
        tracer = _process_tracer
        if tracer is not None and tracer.enabled:
            tracer.trace_dir = log_dir
            tracer.flush()
