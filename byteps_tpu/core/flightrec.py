"""Flight recorder + anomaly trigger engine (docs/observability.md
"Flight recorder & doctor").

Every diagnosis surface this repo grew so far — wire spans, per-stage
dwell histograms, per-server labeled counters, the cluster aggregate —
is *pull*-shaped: an operator runs trace_merge or bps_top after the
incident.  At fleet scale the incident is over before anyone attaches a
profiler.  This module closes the loop:

- :class:`FlightRecorder` keeps an always-on bounded ring
  (``BYTEPS_FLIGHT_STEPS``, default 256; 0 disables) of per-step
  records stamped by the engine at round completion (and per heartbeat
  beat on servers).  Each record is ONE registry delta — step wall
  time, per-stage dwell deltas, per-server-rank RPC p99/retry/giveup
  deltas, wire tx/rx bytes, fused/compressed counts, robustness-event
  deltas, and the membership/map epoch + scheduler incarnation the step
  ran under.  No tracing required; the record costs a counter snapshot
  and a handful of bucket subtractions.  A compiled training step
  (``core/tracing.stepped``) stamps a lighter one, ``record_interval``:
  the time from one call to the next and what the host did meanwhile
  (:func:`host_readings`), no registry delta.
- A **trigger engine** evaluates a small rule table on every record:
  ``slow_step`` (rolling median × ``BYTEPS_FLIGHT_SLOW_FACTOR``),
  ``straggler_server`` (one rank's RPC p99 ≫ the median of its peers),
  ``hot_stripe`` (one native reducer's sum time ≫ its siblings, fed
  from ``native_stripe_sum_seconds{stripe}``), ``queue_stall`` (a
  stage's dwell p99 past ``BYTEPS_FLIGHT_STALL_S``),
  ``degraded_flip`` (``control_plane_degraded`` 0→1), and
  ``corruption_storm`` (a burst of ``wire_checksum_fail`` rejections or
  a connection dropped over its mismatch limit — docs/robustness.md
  "Wire integrity").  A firing rule
  bumps ``flight_trigger{rule}`` and dumps a rate-limited **diagnostic
  bundle** directory (``BYTEPS_FLIGHT_DIR``): the full ledger as
  JSONL, a metrics snapshot, config/env state, the trigger evidence,
  and a trace flush when tracing is on — everything
  ``tools/bps_doctor.py`` needs to rank a diagnosis offline.
- Each node piggybacks a compact **ledger tail** on its existing
  heartbeat (idempotent: the scheduler dedupes by step index), so the
  scheduler's :class:`ClusterFlight` holds a cluster-wide step matrix —
  who is the straggler *this* step, not last week's average — and
  exports it to ``tools/bps_top.py`` via the aggregate registry.
"""

from __future__ import annotations

import bisect
import gc
import json
import operator
import os
import resource
import statistics
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from byteps_tpu.core.telemetry import (
    HeldHistogram,
    _state_percentile,
    counters,
    metrics,
)

#: counter families copied (as nonzero deltas) into every record's
#: ``events`` map — the robustness story of the step, one dict read
EVENT_COUNTERS = (
    "resync_attempt", "resync_giveup", "resync_replayed_rounds",
    "worker_evicted", "server_evicted",
    "migration_keys_moved", "migration_keys_received", "migration_failed",
    "wrong_owner_redirect", "wrong_owner_served",
    "sched_reconnect", "sched_rejoin", "sched_stale_book",
    "degraded_jobs", "push_dedup", "rpc_deadline_expired", "rpc_retry",
    "rpc_giveup", "conn_revive",
    "chaos_drop", "chaos_delay", "chaos_disconnect", "chaos_truncate",
    "chaos_corrupt", "chaos_payload_corrupt",
    "wire_checksum_fail", "wire_checksum_conn_drop",
    "native_checksum_fail", "native_checksum_conn_drop",
)

#: histogram families whose per-label deltas feed the record (and the
#: trigger rules): (family name, label key, record field)
_HIST_FAMILIES = (
    ("stage_dwell_seconds", "stage", "stages"),
    ("rpc_round_trip_seconds", "server", "rpc"),
    ("native_stripe_sum_seconds", "stripe", "stripes"),
)

#: record keys kept in the compact heartbeat-tail form (plus "rpc" p99s)
_COMPACT_KEYS = ("step", "k", "t", "dur", "deg", "trig", "job")


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    try:
        return float(v) if v not in (None, "") else default
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    try:
        return int(v) if v not in (None, "") else default
    except ValueError:
        return default


# --- what the host did meanwhile -------------------------------------------
#
# A slow step's first question is which side of the device it was on.  Five
# readings at a step's boundary answer it by their growth over the step: the
# process's and the calling thread's CPU seconds, the switches the machine
# forced on the process and the ones it made itself, the pages it had to
# fetch back, and the collector's pauses.

#: [a running collection's start, the pauses' sum, their count]
_gc_pauses = [0.0, 0.0, 0]
#: (generation, pause) of the collections since the last reading
_gc_unobserved: deque = deque(maxlen=4096)
_gc_hists: Dict[int, HeldHistogram] = {}


def _on_gc(phase: str, info: dict) -> None:
    # inside the collector, on whichever thread allocated last and under
    # whatever lock that thread holds (a histogram's, the registry's): this
    # takes none, and the next reading observes the pause
    if phase == "start":
        _gc_pauses[0] = time.perf_counter()
        return
    pause = time.perf_counter() - _gc_pauses[0]
    _gc_pauses[1] += pause
    _gc_pauses[2] += 1
    _gc_unobserved.append((info.get("generation", 0), pause))


def watch_gc() -> None:
    """Count the collector's pauses from now on (``gc.callbacks``, once a
    process): their sum and count go into :func:`host_readings`, and each
    reading observes the pauses since the one before in
    ``gc_pause_seconds{generation}``."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def _observe_gc_pauses() -> None:
    while _gc_unobserved:
        try:
            generation, pause = _gc_unobserved.popleft()
        except IndexError:  # another thread's reading took the last one
            return
        hist = _gc_hists.get(generation)
        if hist is None:
            hist = _gc_hists[generation] = metrics().held(
                "gc_pause_seconds", {"generation": str(generation)})
        hist.observe(pause)


#: the names :func:`host_deltas` gives the growth of :func:`host_readings`
HOST_DELTAS = ("cpu_process_s", "cpu_thread_s", "nivcsw", "nvcsw", "majflt",
               "gc_s", "gc_n")


def host_readings() -> tuple:
    """The calling thread's reading of :data:`HOST_DELTAS`' sources, now: two
    system calls (a CPU clock is one on the chip's host, 5.8 µs: PERF.md §6
    PR 38; the process's CPU seconds are ``getrusage``'s own user + system,
    what ``time.process_time()`` would read with a third)."""
    _observe_gc_pauses()
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return (ru.ru_utime + ru.ru_stime, time.thread_time(), ru.ru_nivcsw,
            ru.ru_nvcsw, ru.ru_majflt, _gc_pauses[1], _gc_pauses[2])


def host_deltas(before: tuple, after: tuple) -> dict:
    return dict(zip(HOST_DELTAS, map(operator.sub, after, before)))


def _rounded(values: dict) -> dict:
    return {k: round(v, 6) if isinstance(v, float) else v
            for k, v in values.items()}


class _Rolling:
    """The last ``maxlen`` durations and their median, kept in order: a step
    pays two bisects, not a sort (the compiled step's seam runs the
    ``slow_step`` rule at every call)."""

    def __init__(self, maxlen: int = 64) -> None:
        self._maxlen = maxlen
        self._arrived: deque = deque()
        self._ordered: List[float] = []

    def __len__(self) -> int:
        return len(self._ordered)

    def append(self, value: float) -> None:
        if len(self._arrived) == self._maxlen:
            gone = self._arrived.popleft()
            del self._ordered[bisect.bisect_left(self._ordered, gone)]
        self._arrived.append(value)
        bisect.insort(self._ordered, value)

    def median(self) -> float:
        half, odd = divmod(len(self._ordered), 2)
        if odd:
            return self._ordered[half]
        return (self._ordered[half - 1] + self._ordered[half]) / 2


class FlightRecorder:
    """Always-on per-step ring + node-side trigger rules.

    One instance per process (see :func:`ensure_process_recorder`);
    worker engines stamp a record at round completion
    (``record_step(dur)``), server control loops stamp one per
    heartbeat beat (``record_step()`` — rules that need a step duration
    skip).  All reads go through the process metrics registry, so
    in-process test fleets (worker + server sharing one registry)
    produce one coherent ledger.
    """

    def __init__(
        self,
        cfg=None,
        context_fn: Optional[Callable[[], dict]] = None,
        registry=None,
        counter_store=None,
        tracer=None,
        capacity: Optional[int] = None,
    ) -> None:
        self.capacity = (
            capacity if capacity is not None
            else getattr(cfg, "flight_steps", None)
            if cfg is not None and getattr(cfg, "flight_steps", None) is not None
            else _env_int("BYTEPS_FLIGHT_STEPS", 256)
        )
        self.slow_factor = (
            getattr(cfg, "flight_slow_factor", None)
            or _env_float("BYTEPS_FLIGHT_SLOW_FACTOR", 3.0)
        )
        self.stall_s = (
            getattr(cfg, "flight_stall_s", None)
            or _env_float("BYTEPS_FLIGHT_STALL_S", 5.0)
        )
        self.bundle_dir = (
            getattr(cfg, "flight_dir", None)
            or os.environ.get("BYTEPS_FLIGHT_DIR")
            or os.path.join(getattr(cfg, "trace_dir", ".") or ".",
                            "flight_bundles")
        )
        _fb = getattr(cfg, "flight_bundle_s", None) if cfg is not None else None
        self.bundle_interval_s = (
            float(_fb) if _fb is not None
            else _env_float("BYTEPS_FLIGHT_BUNDLE_S", 60.0)
        )
        #: fleet-central upload (BYTEPS_FLIGHT_UPLOAD, docs/
        #: observability.md): dumped trigger bundles additionally queue
        #: a COMPACT form (rule + evidence + firing record) that the
        #: heartbeat loop ships to the scheduler's BYTEPS_FLIGHT_DIR —
        #: tuner decisions and their trigger evidence land in one place
        self.upload = bool(
            getattr(cfg, "flight_upload", False)
            or os.environ.get("BYTEPS_FLIGHT_UPLOAD", "").lower()
            not in ("", "0", "false", "no", "off")
        )
        self._uploads: List[dict] = []
        #: per-job step-time SLO (docs/async.md): a completed step
        #: slower than this fires slo_breach (0 = rule off)
        self.slo_s = (
            getattr(cfg, "job_slo_s", None)
            if cfg is not None and getattr(cfg, "job_slo_s", None)
            else _env_float("BYTEPS_JOB_SLO_S", 0.0)
        )
        #: min prior samples before the rolling-median rules may fire
        self.min_history = 8
        self._context_fn = context_fn
        self._registry = registry if registry is not None else metrics()
        self._counters = (
            counter_store if counter_store is not None else counters()
        )
        self._tracer = tracer
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, self.capacity or 1))
        self._step = 0
        # delta baselines (one per source family; clamped at zero so a
        # test-style counters().reset() mid-flight can't go negative)
        self._base_counts: Dict[str, int] = {}
        self._base_labeled: Dict[str, Dict[tuple, int]] = {}
        self._base_hists: Dict[Tuple[str, tuple], Tuple[List[int], float, int]] = {}
        # rule state: the rolling median's history, a kind of record each
        # (an engine round and a compiled step's interval are two clocks)
        self._durs: Dict[str, _Rolling] = {"step": _Rolling(), "train": _Rolling()}
        self._host: Optional[tuple] = None  # host_readings() at the last step
        self._last_degraded: Optional[int] = None
        #: rule → (when its last bundle was dumped, that firing's ``dur``)
        self._last_fire: Dict[str, Tuple[float, float]] = {}
        self.bundles_written: List[str] = []

    # --- properties ------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    # --- recording -------------------------------------------------------

    def record_step(self, dur: Optional[float] = None) -> Optional[dict]:
        """Stamp one ledger record: the registry delta since the last
        record, plus the step wall time (worker rounds) and the control
        context.  Evaluates the trigger rules; returns the record (None
        when disabled).  Never raises into the data path."""
        if not self.enabled:
            return None
        try:
            return self._record_step(dur)
        except Exception as e:  # noqa: BLE001 — observability ≠ a crash
            from byteps_tpu.common import logging as bpslog

            bpslog.warning("flight recorder step failed: %r", e)
            return None

    def _record_step(self, dur: Optional[float]) -> dict:
        ctx = {}
        if self._context_fn is not None:
            try:
                ctx = self._context_fn() or {}
            except Exception:  # noqa: BLE001
                ctx = {}
        rec: dict = {
            "k": "step" if dur is not None else "beat",
            "t": time.time(),
            "dur": dur,
            "epoch": int(ctx.get("epoch", 0)),
            "map_epoch": int(ctx.get("map_epoch", 0)),
            "incarnation": int(ctx.get("incarnation", 0)),
            "deg": int(ctx.get("degraded", 0)),
            # multi-tenant dimension (docs/async.md): which job this
            # node's steps belong to (0 = single-tenant default) — the
            # per-tenant SLO rule and the cluster step matrix slice on it
            "job": int(ctx.get("job", 0)),
            "trig": [],
        }
        with self._lock:
            self._step += 1
            rec["step"] = self._step
            self._delta_counters(rec)
            self._delta_hists(rec)
            if dur is not None:
                # rounds end on whichever thread delivered the last reply:
                # the process's readings, not that thread's CPU clock
                now, before = host_readings(), self._host
                self._host = now
                if before is not None:
                    rec["host"] = host_deltas(before, now)
                    del rec["host"]["cpu_thread_s"]
            self._ring.append(rec)
        if dur is not None:
            self._registry.gauge_set("node_step_seconds", dur)
        self._evaluate(rec)
        if dur is not None:
            with self._lock:
                self._durs["step"].append(dur)
        return rec

    def record_interval(self, dur: float, host: dict) -> Optional[dict]:
        """The light entry of a compiled step (``core/tracing.stepped``): the
        time from one call of the step to the next and what the host did
        meanwhile (``host``: the step's number, its ``dispatch_s``, ``fold_s``
        and ``caller_s``, :func:`host_deltas`) go into the ring and to the
        ``slow_step`` rule.  No registry delta: a counter snapshot waits for
        the routing statistics of the step in flight
        (``parallel/moe.RoutingCounters``) and would serialise the host with
        the device — so a firing's bundle holds no ``metrics.json`` either.
        Never raises into the step."""
        if not self.enabled:
            return None
        rec = {"k": "train", "t": time.time(), "dur": dur, "host": host,
               "trig": []}
        try:
            with self._lock:
                self._step += 1
                rec["step"] = self._step
                self._ring.append(rec)
            evidence = _rule_slow_step(self, rec)
            if evidence is not None:
                self._fire("slow_step", evidence, rec)
            self._durs["train"].append(dur)  # the step's one thread
        except Exception as e:  # noqa: BLE001 — observability ≠ a crash
            from byteps_tpu.common import logging as bpslog

            bpslog.warning("flight recorder interval failed: %r", e)
        return rec

    def _delta_counters(self, rec: dict) -> None:
        """Nonzero counter deltas since the previous record.  Caller
        holds the lock."""
        flat = self._counters.snapshot()
        events = {}
        for name in EVENT_COUNTERS:
            d = flat.get(name, 0) - self._base_counts.get(name, 0)
            if d > 0:
                events[name] = d
        rec["events"] = events
        for name, field in (
            ("wire_tx_bytes", "tx"), ("wire_rx_bytes", "rx"),
            ("fused_frames", "fused"), ("fused_keys", "fused_keys"),
            ("wire_bytes_saved", "comp_saved"),
        ):
            rec[field] = max(0, flat.get(name, 0) - self._base_counts.get(name, 0))
        self._base_counts = flat
        # per-server retry/giveup slices ride into the rpc map below
        labeled = self._counters.snapshot_labeled()
        self._labeled_delta = {}
        for name in ("rpc_retry", "rpc_giveup"):
            per = labeled.get(name, {})
            base = self._base_labeled.get(name, {})
            d = {}
            for lkey, v in per.items():
                dd = v - base.get(lkey, 0)
                if dd > 0:
                    d[dict(lkey).get("server", "?")] = dd
            self._labeled_delta[name] = d
        self._base_labeled = {
            n: dict(per) for n, per in labeled.items()
            if n in ("rpc_retry", "rpc_giveup")
        }

    def _delta_hists(self, rec: dict) -> None:
        """Per-label bucket deltas for the watched histogram families →
        ``{label_value: {"n", "s", "p99"}}``.  Caller holds the lock."""
        states = self._registry._hist_states()
        wanted = {fam: (lab, field) for fam, lab, field in _HIST_FAMILIES}
        for fam, (lab, field) in wanted.items():
            rec[field] = {}
        for (name, lkey), st in states.items():
            if name not in wanted:
                continue
            lab, field = wanted[name]
            bounds, cnts, vsum, count = st
            base = self._base_hists.get((name, lkey))
            if base is None:
                d_counts, d_sum, d_count = list(cnts), vsum, count
            else:
                d_counts = [max(0, a - b) for a, b in zip(cnts, base[0])]
                d_sum = max(0.0, vsum - base[1])
                d_count = max(0, count - base[2])
            self._base_hists[(name, lkey)] = (list(cnts), vsum, count)
            if d_count <= 0:
                continue
            lv = dict(lkey).get(lab, "?")
            rec[field][lv] = {
                "n": d_count,
                "s": round(d_sum, 9),
                "p99": round(_state_percentile(tuple(bounds), d_counts, 0.99), 9),
            }
        # fold the labeled retry/giveup deltas into the rpc map so the
        # straggler evidence carries them
        for rank, v in (getattr(self, "_labeled_delta", {}) or {}).get(
            "rpc_retry", {}
        ).items():
            rec["rpc"].setdefault(rank, {"n": 0, "s": 0.0, "p99": 0.0})
            rec["rpc"][rank]["retry"] = v
        for rank, v in (getattr(self, "_labeled_delta", {}) or {}).get(
            "rpc_giveup", {}
        ).items():
            rec["rpc"].setdefault(rank, {"n": 0, "s": 0.0, "p99": 0.0})
            rec["rpc"][rank]["giveup"] = v

    # --- ledger access ---------------------------------------------------

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def ledger_tail(self, limit: int = 16) -> List[dict]:
        """The last ``limit`` records in compact wire form — the
        heartbeat piggyback.  Idempotent by design: every beat re-ships
        the window and the scheduler dedupes by step index, so a lost
        beat costs nothing."""
        with self._lock:
            recs = list(self._ring)[-max(1, limit):]
        out = []
        for r in recs:
            c = {k: r.get(k) for k in _COMPACT_KEYS}
            c["rpc"] = {
                rank: v.get("p99", 0.0) for rank, v in (r.get("rpc") or {}).items()
            }
            # per-stage dwell delta, compacted to {stage: [n, seconds]}:
            # the scheduler's fusion-threshold walk reads WHERE each
            # step's time went (docs/autotune.md "Fusion-threshold
            # walk"), not just how many packs crossed the wire
            st = {
                name: [v.get("n", 0), v.get("s", 0.0)]
                for name, v in (r.get("stages") or {}).items()
            }
            if st:
                c["st"] = st
            out.append(c)
        return out

    def take_uploads(self) -> List[dict]:
        """Drain the pending compact-bundle uploads (the heartbeat loop
        attaches them to the next beat as the ``fb`` field); a failed
        beat gives them back via :meth:`requeue_uploads`."""
        with self._lock:
            ups, self._uploads = self._uploads, []
            return ups

    def requeue_uploads(self, ups: List[dict]) -> None:
        with self._lock:
            self._uploads = (list(ups) + self._uploads)[-8:]

    # --- trigger engine --------------------------------------------------

    def _evaluate(self, rec: dict) -> None:
        for rule, fn in _RULES:
            try:
                ev = fn(self, rec)
            except Exception:  # noqa: BLE001 — a rule bug must not kill a step
                continue
            if ev is not None:
                self._fire(rule, ev, rec)

    def _fire(self, rule: str, evidence: dict, rec: dict) -> None:
        rec["trig"].append(rule)
        self._counters.bump("flight_trigger", labels={"rule": rule})
        now, dur = time.monotonic(), rec.get("dur") or 0.0
        last = self._last_fire.get(rule)
        if last is not None and now - last[0] < self.bundle_interval_s and not (
                rule == "slow_step" and dur > last[1] * self.slow_factor):
            # rate limiter holds: counted, not dumped.  A step slower again by
            # the rule's own factor than the one that was dumped passes: a
            # step of 182 ms among 59 took the limit five steps before one
            # of 2957 (PERF.md §6 PR 54)
            return
        self._last_fire[rule] = (now, dur)
        try:
            path = self.dump_bundle(rule, evidence, rec)
        except Exception as e:  # noqa: BLE001
            from byteps_tpu.common import logging as bpslog

            bpslog.warning("flight bundle dump failed: %r", e)
            return
        self._counters.bump("flight_bundle")
        if self.upload:
            with self._lock:
                self._uploads.append({
                    "rule": rule,
                    "step": rec.get("step", 0),
                    "t": rec.get("t"),
                    "evidence": evidence,
                    "record": {k: rec.get(k) for k in _COMPACT_KEYS},
                    "bundle": os.path.basename(path),
                })
                # bounded: a heartbeat outage must not grow this forever
                del self._uploads[:-8]
        from byteps_tpu.common import logging as bpslog

        # the evidence rides the line: the bundle stays on the machine that
        # wrote it, a run's stderr comes back (one line a dumped bundle, so
        # under the bundle's own rate limit)
        bpslog.warning(
            "flight trigger %s fired at step %d: %s — diagnostic bundle: %s "
            "(inspect with: python tools/bps_doctor.py %s)",
            rule, rec["step"], json.dumps(evidence, default=str), path, path,
        )

    def dump_bundle(self, rule: str, evidence: dict, rec: dict) -> str:
        """Write one diagnostic bundle directory and return its path:
        ``trigger.json`` (rule + evidence + firing record),
        ``ledger.jsonl`` (the whole ring), ``metrics.json`` (full
        registry snapshot), ``config.json`` (BYTEPS_*/DMLC_* env +
        control context) — the exact input ``tools/bps_doctor.py``
        loads.  If tracing is on, the current trace window is flushed
        so the span view of the incident survives too."""
        ts = time.strftime("%Y%m%d-%H%M%S")
        path = os.path.join(
            self.bundle_dir, f"{ts}-step{rec['step']}-{rule}-{os.getpid()}"
        )
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "trigger.json"), "w") as f:
            json.dump(
                {"rule": rule, "evidence": evidence, "record": rec,
                 "time": time.time(), "pid": os.getpid()},
                f, indent=2, default=str,
            )
        with open(os.path.join(path, "ledger.jsonl"), "w") as f:
            for r in self.snapshot():
                f.write(json.dumps(r, default=str) + "\n")
        if rec.get("k") != "train":  # see record_interval
            with open(os.path.join(path, "metrics.json"), "w") as f:
                json.dump(self._registry.snapshot(), f, indent=2, default=str)
        env = {
            k: v for k, v in os.environ.items()
            if k.startswith(("BYTEPS_", "DMLC_"))
        }
        ctx = {}
        if self._context_fn is not None:
            try:
                ctx = self._context_fn() or {}
            except Exception:  # noqa: BLE001
                ctx = {}
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump({"env": env, "context": ctx}, f, indent=2, default=str)
        tracer = self._tracer
        if tracer is None:
            from byteps_tpu.core.tracing import get_process_tracer

            tracer = get_process_tracer()
        if tracer is not None and getattr(tracer, "enabled", False):
            try:
                trace_file = tracer.flush()
                with open(os.path.join(path, "trace_window.json"), "w") as f:
                    json.dump({"flushed_to": trace_file}, f)
            except Exception:  # noqa: BLE001
                pass
        self.bundles_written.append(path)
        return path


# --- the node-side rule table ---------------------------------------------
#
# Each rule: fn(recorder, record) → evidence dict (fire) or None.  Kept
# as plain functions so tests can drive them on synthetic records, and
# small on purpose: these run on every step of every node.


def _rule_slow_step(rec: "FlightRecorder", r: dict) -> Optional[dict]:
    """This step took ≫ the rolling median of recent steps of its kind.  The
    evidence names what a reader asks first.  Of an engine round: the stage
    whose dwell grew most against the step before, the retries and expired
    deadlines of this step, and what the host did meanwhile.  Of a compiled
    step's interval (``record_interval``): which of dispatch, fold and the
    caller's own time was the largest, and what the host did meanwhile
    (docs/observability.md "Reading a slow step")."""
    dur = r.get("dur")
    durs = rec._durs.get(r.get("k"), ())  # a beat has no duration and no history
    if len(durs) < rec.min_history:
        return None
    med = durs.median()
    if not (med > 0 and dur > med * rec.slow_factor):
        return None
    host = r.get("host") or {}
    if r.get("k") == "train":
        parts = {k: host[k] for k in ("dispatch_s", "fold_s", "caller_s")}
        return _rounded({"step": host["step"], "interval_s": dur, "median_s": med,
                         **parts, "where": max(parts, key=parts.get)[:-2],
                         # ended by shutdown | exit, not by a next step: what
                         # the caller did after its loop is in caller_s
                         **({"closed": True} if host.get("closed") else {}),
                         **{k: host[k] for k in HOST_DELTAS}})
    evidence = {"dur": dur, "median": round(med, 6), "factor": rec.slow_factor,
                **_rounded(host)}
    with rec._lock:
        earlier = [x for x in rec._ring if x is not r and x.get("k") == "step"]
    before = earlier[-1].get("stages", {}) if earlier else {}
    grown = {
        stage: v["s"] - before.get(stage, {}).get("s", 0.0)
        for stage, v in (r.get("stages") or {}).items()
    }
    if grown:
        stage = max(grown, key=grown.get)
        evidence["stage"] = stage
        evidence["stage_dwell_grew_s"] = round(grown[stage], 6)
    events = r.get("events") or {}
    for name in ("rpc_retry", "rpc_deadline_expired"):
        evidence[name] = events.get(name, 0)
    return evidence


def _rule_straggler_server(rec: "FlightRecorder", r: dict) -> Optional[dict]:
    """One server rank's RPC p99 this step ≫ the median of its peers."""
    cells = [
        (rank, v) for rank, v in (r.get("rpc") or {}).items()
        if rank != "?" and v.get("n", 0) > 0
    ]
    if len(cells) < 2:
        return None
    worst_rank, worst = max(cells, key=lambda kv: kv[1]["p99"])
    others = [v["p99"] for rank, v in cells if rank != worst_rank]
    med = statistics.median(others)
    # floor at the first latency bucket: loopback noise (p99s of tens
    # of µs) must never mint a straggler
    if worst["p99"] >= rec.slow_factor * max(med, 1e-4):
        return {
            "rank": worst_rank, "p99": worst["p99"],
            "peer_median_p99": round(med, 6),
            "retry": worst.get("retry", 0), "giveup": worst.get("giveup", 0),
        }
    return None


def _rule_hot_stripe(rec: "FlightRecorder", r: dict) -> Optional[dict]:
    """One native reducer stripe's summation time ≫ its siblings (fed
    from ``native_stripe_sum_seconds{stripe}`` deltas)."""
    cells = [
        (s, v) for s, v in (r.get("stripes") or {}).items()
        if v.get("n", 0) > 0
    ]
    if len(cells) < 2:
        return None
    worst_stripe, worst = max(cells, key=lambda kv: kv[1]["s"])
    others = [v["s"] for s, v in cells if s != worst_stripe]
    med = statistics.median(others)
    if worst["s"] >= rec.slow_factor * max(med, 1e-3):
        total = sum(v["s"] for _, v in cells)
        return {
            "stripe": worst_stripe, "sum_seconds": round(worst["s"], 6),
            "sibling_median": round(med, 6),
            "share": round(worst["s"] / max(total, 1e-12), 3),
        }
    return None


def _rule_queue_stall(rec: "FlightRecorder", r: dict) -> Optional[dict]:
    """A pipeline stage's dwell p99 this step exceeds the stall bound
    (``BYTEPS_FLIGHT_STALL_S``) — tasks are parking, not flowing."""
    hot = {
        st: v for st, v in (r.get("stages") or {}).items()
        if v.get("n", 0) > 0 and v["p99"] >= rec.stall_s
    }
    if not hot:
        return None
    worst = max(hot, key=lambda st: hot[st]["p99"])
    return {"stage": worst, "p99": hot[worst]["p99"], "stall_s": rec.stall_s}


def _rule_degraded_flip(rec: "FlightRecorder", r: dict) -> Optional[dict]:
    """``control_plane_degraded`` flipped 0→1: the scheduler link just
    died and the reconnect machine took over."""
    prev, rec._last_degraded = rec._last_degraded, r.get("deg", 0)
    if r.get("deg", 0) and not prev and prev is not None:
        return {"degraded": 1, "incarnation": r.get("incarnation", 0)}
    return None


def _rule_slo_breach(rec: "FlightRecorder", r: dict) -> Optional[dict]:
    """Per-tenant SLO (docs/async.md): a completed step blew the
    configured ``BYTEPS_JOB_SLO_S`` bound.  Unlike slow_step (relative
    to the rolling median — a uniformly slow job never fires it), this
    is the ABSOLUTE latency contract a tenant declared, so a bulk
    neighbor saturating the shared fleet shows up here first."""
    dur = r.get("dur")
    if dur is None or rec.slo_s <= 0 or dur <= rec.slo_s:
        return None
    return {
        "job": r.get("job", 0), "dur": dur, "slo_s": rec.slo_s,
        "over": round(dur / rec.slo_s, 3),
    }


#: checksum-mismatch deltas in ONE step/beat record at or above this
#: fire corruption_storm — a single flipped bit is the retry machinery's
#: job, a burst means the path itself is bad (NIC/DRAM going)
_CORRUPT_STORM_MIN = 3


def _rule_corruption_storm(rec: "FlightRecorder", r: dict) -> Optional[dict]:
    """Wire-integrity rejections are BURSTING (docs/robustness.md "Wire
    integrity"): many CRC32C mismatches landed inside one step/beat
    window, or a connection blew through its mismatch limit — a bad
    NIC/link is actively flipping bits, not a one-off cosmic ray."""
    ev = r.get("events") or {}
    # both engines: the C++ engine's rejections surface as native_* via
    # the counter-provider seam, same window, same record
    fails = (ev.get("wire_checksum_fail", 0)
             + ev.get("native_checksum_fail", 0))
    drops = (ev.get("wire_checksum_conn_drop", 0)
             + ev.get("native_checksum_conn_drop", 0))
    if fails < _CORRUPT_STORM_MIN and not drops:
        return None
    return {
        "checksum_fails": fails,
        "conn_drops": drops,
        "injected": ev.get("chaos_payload_corrupt", 0),
    }


_RULES: Tuple[Tuple[str, Callable], ...] = (
    ("slow_step", _rule_slow_step),
    ("straggler_server", _rule_straggler_server),
    ("hot_stripe", _rule_hot_stripe),
    ("queue_stall", _rule_queue_stall),
    ("degraded_flip", _rule_degraded_flip),
    ("slo_breach", _rule_slo_breach),
    ("corruption_storm", _rule_corruption_storm),
)


# --- scheduler-side cluster step matrix -----------------------------------


class ClusterFlight:
    """The scheduler's cluster-wide step matrix, fed by the compact
    ledger tails every node piggybacks on its heartbeat.  Dedupe is by
    per-node step index (tails are re-shipped windows).  Evaluates ONE
    scheduler-side rule — which worker is the straggler *this* step —
    and exports it to the aggregate scrape surface
    (``cluster_straggler_rank``; -1 = no straggler)."""

    def __init__(self, factor: Optional[float] = None,
                 depth: int = 64) -> None:
        self.factor = factor or _env_float("BYTEPS_FLIGHT_SLOW_FACTOR", 3.0)
        self._lock = threading.Lock()
        self._matrix: Dict[Tuple[str, int], deque] = {}
        self._last_step: Dict[Tuple[str, int], int] = {}
        self._depth = depth
        self.straggler_rank = -1
        self._registry = None

    def attach(self, registry) -> None:
        """Register the matrix's gauges on the scheduler's aggregate
        registry (idempotent)."""
        self._registry = registry
        registry.gauge_fn(
            "cluster_straggler_rank", lambda: float(self.straggler_rank)
        )

    def merge(self, role: str, rank: int, records: List[dict]) -> int:
        """Fold one node's heartbeat tail in; returns how many records
        were NEW (the rest were re-shipped window overlap)."""
        key = (role, int(rank))
        fresh = 0
        with self._lock:
            dq = self._matrix.setdefault(key, deque(maxlen=self._depth))
            last = self._last_step.get(key, 0)
            steps = []
            for r in records or ():
                try:
                    steps.append((int(r.get("step", 0)), r))
                except (TypeError, ValueError):
                    continue
            # restart detection: a LIVE node's tail always contains its
            # newest record, so a tail whose maximum step sits below the
            # dedupe cursor means the node's recorder restarted (process
            # restart / shutdown()+init() rejoin at the same rank).  The
            # dead incarnation's rows and cursor must not ghost-feed the
            # straggler rule or drop the reborn node's records forever.
            if steps and max(s for s, _ in steps) < last:
                dq.clear()
                last = 0
            for step, r in steps:
                if step <= last:
                    continue
                last = step
                dq.append(dict(r))
                fresh += 1
            self._last_step[key] = last
        if fresh:
            self._evaluate()
        return fresh

    def forget(self, role: str, rank: int) -> None:
        """Drop one node's row from the matrix — called when the
        scheduler evicts it, so a dead rank's frozen last-step duration
        stops feeding the straggler median."""
        key = (role, int(rank))
        with self._lock:
            self._matrix.pop(key, None)
            self._last_step.pop(key, None)
        self._evaluate()

    def _evaluate(self) -> None:
        """Scheduler-side straggler-node rule: the worker whose latest
        step wall time ≫ the median of its peers' latest steps."""
        with self._lock:
            durs = {}
            for (role, rank), dq in self._matrix.items():
                if role != "worker":
                    continue
                for r in reversed(dq):
                    if r.get("k") == "step" and r.get("dur") is not None:
                        durs[rank] = float(r["dur"])
                        break
        prev = self.straggler_rank
        if len(durs) < 2:
            self.straggler_rank = -1
            return
        worst_rank = max(durs, key=durs.get)
        others = [d for rk, d in durs.items() if rk != worst_rank]
        med = statistics.median(others)
        if durs[worst_rank] >= self.factor * max(med, 1e-4):
            self.straggler_rank = worst_rank
        else:
            self.straggler_rank = -1
        if self.straggler_rank >= 0 and self.straggler_rank != prev:
            if self._registry is not None:
                self._registry.counters.bump(
                    "flight_trigger", labels={"rule": "straggler_node"}
                )

    def matrix(self) -> Dict[str, List[dict]]:
        """``{"<role><rank>": [compact records, oldest first]}`` — the
        live surface ``bps_doctor --live`` and tests read."""
        with self._lock:
            return {
                f"{role}{rank}": list(dq)
                for (role, rank), dq in self._matrix.items()
            }


# --- process-global accessor ----------------------------------------------

_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def get_process_recorder() -> Optional[FlightRecorder]:
    return _recorder


def set_process_recorder(rec: Optional[FlightRecorder]) -> None:
    global _recorder
    with _recorder_lock:
        _recorder = rec


def release_process_recorder(context_fn) -> None:
    """Drop the process recorder iff ``context_fn`` is the one it was
    created with — how a stopping PSServer releases a recorder IT
    installed without clobbering one owned by a live worker runtime in
    the same process (the worker path releases via shutdown_state).  A
    stale recorder would leak a dead node's context — and its knob
    snapshot — into the next init cycle."""
    global _recorder
    with _recorder_lock:
        # == not `is`: each `self._flight_context` access builds a fresh
        # bound-method object; equality compares (__self__, __func__)
        if _recorder is not None and _recorder._context_fn == context_fn:
            _recorder = None


def ensure_process_recorder(cfg=None, context_fn=None,
                            tracer=None) -> FlightRecorder:
    """Create the process flight recorder if none exists yet (in-process
    test fleets: the first role to come up — worker state or a PSServer
    — owns it; later roles share the ring, which matches the shared
    metrics registry those fleets already run on)."""
    global _recorder
    with _recorder_lock:
        if _recorder is None:
            _recorder = FlightRecorder(
                cfg=cfg, context_fn=context_fn, tracer=tracer
            )
        elif _recorder._context_fn is None and context_fn is not None:
            # a compiled step made that one before a PS plane was joined:
            # the plane's recorder is made from its configuration
            _recorder = FlightRecorder(
                cfg=cfg, context_fn=context_fn, tracer=tracer
            )
        return _recorder
