"""Metrics plane: push/pull speed telemetry, robustness counters, and the
cluster-scrapeable metrics registry (docs/observability.md).

Three layers, grown in place:

- :class:`PushPullSpeed` — the reference's ``BytePSGlobal::PushPullSpeed``
  (global.cc:697-752): a windowed MB/s counter over recent push_pull byte
  volume, exposed as ``bps.get_pushpull_speed()``.  Gate:
  ``BYTEPS_TELEMETRY_ON``.
- :class:`RobustnessCounters` (:func:`counters`) — named monotonic
  counters for data-plane degradation events, always on.  Since the
  observability PR they optionally carry a LABEL dimension (e.g.
  ``server="2"``) so a single sick peer is visible; flat totals are kept
  for back-compat (``get_robustness_counters``).
- :class:`MetricsRegistry` (:func:`metrics`) — counters + gauges +
  fixed-bucket histograms with p50/p90/p99 snapshots, a Prometheus text
  exposition endpoint (``BYTEPS_METRICS_PORT``), and delta snapshots that
  piggyback on the scheduler heartbeat so the scheduler can serve a
  cluster-wide aggregate.

Every metric name must appear in the docs/observability.md catalog —
``tools/check_metrics_doc.py`` (a tier-1 test) fails the build otherwise.
"""

from __future__ import annotations

import bisect
import json
import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

WINDOW_SEC = 10.0  # reference uses a 10-second window (global.cc:703)


class PushPullSpeed:
    def __init__(self, enabled: bool = True) -> None:
        self.enabled = enabled
        self._lock = threading.Lock()
        self._events: Deque[Tuple[float, int]] = deque()
        self._total_bytes = 0

    def record(self, nbytes: int) -> None:
        if not self.enabled:
            return
        now = time.monotonic()
        with self._lock:
            self._events.append((now, nbytes))
            self._total_bytes += nbytes
            self._evict(now)

    def _evict(self, now: float) -> None:
        while self._events and now - self._events[0][0] > WINDOW_SEC:
            _, nb = self._events.popleft()
            self._total_bytes -= nb

    def mbps(self) -> float:
        """Windowed MB/s (returns 0 when disabled or idle)."""
        now = time.monotonic()
        with self._lock:
            self._evict(now)
            if not self._events:
                return 0.0
            span = max(now - self._events[0][0], 1e-6)
            return self._total_bytes / span / 1e6


def _label_key(labels: Optional[Dict[str, str]]) -> Tuple[Tuple[str, str], ...]:
    """Canonical, hashable form of a label set."""
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _render_labels(key: Tuple[Tuple[str, str], ...]) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in key)
    return "{" + inner + "}"


class RobustnessCounters:
    """Named monotonic counters for data-plane degradation events.

    Canonical names (consumers may add others; the full catalog with
    per-name guidance lives in docs/observability.md):

    - ``rpc_retry``            — a push/pull/init attempt was re-sent
    - ``rpc_deadline_expired`` — a per-RPC deadline fired (hung server)
    - ``rpc_giveup``           — retries exhausted; error surfaced
    - ``conn_revive``          — a dead server connection was rebuilt
    - ``push_dedup``           — server suppressed a replayed push
    - ``degraded_jobs``        — engine jobs failed with DegradedError

    Recovery plane (docs/robustness.md "healing flow"; labeled per
    server rank like the rpc_* family):

    - ``resync_attempt``        — in-place heals started after a give-up
    - ``resync_replayed_rounds``— journaled push rounds replayed because
      the server's exactly-once ledger never absorbed them
    - ``resync_giveup``         — heals that failed; the caller fell
      back to the global re-init path
    - ``init_replay_ack``       — server acked a replayed INIT from its
      completed-barrier record (dropped-ack idempotency token)
    - ``worker_evicted`` / ``server_evicted`` — evictions observed from
      the scheduler's membership broadcasts (cumulative)
    - ``chaos_drop`` / ``chaos_delay`` / ``chaos_disconnect`` /
      ``chaos_truncate`` / ``chaos_corrupt`` — injected faults

    Small-tensor fusion (docs/fusion.md):

    - ``wire_rpc``             — data-plane frames actually sent (every
      async push/pull/fused attempt, retries included) — what fusion
      lowers, fused against unfused
    - ``fused_frames``         — multi-key Op.FUSED frames shipped
    - ``fused_keys``           — member partitions carried by those frames
      (``fused_keys / fused_frames`` = achieved pack density)
    - ``fusion_flush_full`` / ``fusion_flush_idle`` /
      ``fusion_flush_cycle`` — why each pack left the buffer (capacity
      reached / pipeline drained / BYTEPS_FUSION_CYCLE_MS backstop) —
      the first knob to read when tuning threshold vs. cycle
    - ``fused_fallback``       — packs downgraded to per-key unfused
      RPCs (server resize under the pack, or fused retries exhausted)
    - ``fused_reply_malformed`` — fused replies that failed to decode
      (routed to the frame's error path instead of the recv lane)

    The worker's device boundary (``core/engine.py``):

    - ``d2h_bytes`` / ``h2d_bytes`` — bytes COPYD2H read off the device
      and COPYH2D put back, a partition at a time (a raw jax round grows
      each by the tensor's bytes)
    - ``h2d_sharded_parts``    — partitions whose bytes COPYH2D shared
      out over the devices of the sharding their tensor was submitted
      in: dealt whole to one of them in turn, or cut evenly over them
      (one ``device_put`` each; ``h2d_bytes`` counts it once)

    ``bump(name, n, labels={"server": "2"})`` additionally records the
    count under that label set: ``rpc_retry``/``rpc_deadline_expired``/
    ``conn_revive`` carry a per-server-rank dimension so ONE sick server
    stands out of the flat total.  ``snapshot()`` stays flat ints
    (back-compat); :meth:`snapshot_labeled` exposes the dimension.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        # name → {label_key_tuple: count}; flat totals above INCLUDE these
        self._labeled: Dict[str, Dict[tuple, int]] = {}
        # External counter providers (docs/observability.md): zero-arg
        # callables returning {name: monotonic int}, merged into
        # snapshot()/get() — how the GIL-free C++ engine's counters
        # (native/__init__.py native_server_counters) reach the same
        # scrape surface without the data plane ever calling into
        # Python.  Each provider carries a baseline captured at reset()
        # so test-style reset semantics hold even though the native
        # counters themselves are never cleared.  Providers are invoked
        # UNDER self._lock (they are microsecond ctypes reads and must
        # not call back into this object — see register_provider), which
        # makes snapshot/reset/absorb mutually exclusive: a scrape can
        # never double-count a concurrently-absorbed provider.
        self._providers: Dict[int, tuple] = {}  # id → (fn, baseline)
        # totals folded in from absorbed (stopped) providers; cleared by
        # reset() like the flat counters
        self._frozen: Dict[str, int] = {}

    def bump(self, name: str, n: int = 1,
             labels: Optional[Dict[str, str]] = None,
             flat: bool = True) -> None:
        """``flat=False`` records only the labeled slice — used when the
        flat total is accounted separately (scheduler delta merge, where
        the unlabeled delta already includes the labeled bumps)."""
        with self._lock:
            if flat:
                self._counts[name] = self._counts.get(name, 0) + n
            if labels:
                key = _label_key(labels)
                per = self._labeled.setdefault(name, {})
                per[key] = per.get(key, 0) + n

    def set_floor(self, name: str, value: int) -> None:
        """Raise a counter to ``value`` if below it — used for cumulative
        totals observed from broadcasts, which may be re-delivered."""
        with self._lock:
            if self._counts.get(name, 0) < value:
                self._counts[name] = value

    def register_provider(self, fn) -> None:
        """Merge an external monotonic counter source (e.g. one native
        C++ server instance) into this store's snapshots.  ``fn`` must
        be fast (it runs under this store's lock — a microsecond ctypes
        read, not I/O), non-reentrant (it may not call back into
        counters()), and tolerate being called after its source stopped
        (return {})."""
        with self._lock:
            self._providers[id(fn)] = (fn, {})

    def unregister_provider(self, fn) -> None:
        with self._lock:
            self._providers.pop(id(fn), None)

    def absorb_provider(self, fn) -> None:
        """Fold a provider's final values (above its reset baseline)
        into the frozen-totals dict and unregister it — called before
        the provider's source is torn down so totals survive a server
        stop().  Runs entirely under the lock, so a concurrent scrape
        sees the totals through EITHER the live provider OR the frozen
        dict, never both (no double-count), and the registry does not
        grow with stopped servers."""
        with self._lock:
            entry = self._providers.pop(id(fn), None)
            if entry is None:
                return
            fn_live, base = entry
            try:
                vals = fn_live() or {}
            except Exception:  # noqa: BLE001
                vals = {}
            for name, v in vals.items():
                d = int(v) - base.get(name, 0)
                if d > 0:
                    self._frozen[name] = self._frozen.get(name, 0) + d

    def _provider_totals_locked(self) -> Dict[str, int]:
        """Frozen totals + every live provider's counters above its
        reset baseline.  Caller holds the lock (providers are contract-
        bound to be microsecond reads, see register_provider)."""
        total = dict(self._frozen)
        for fn, base in self._providers.values():
            try:
                vals = fn() or {}
            except Exception:  # noqa: BLE001 — a dead provider can't break scrape
                continue
            for name, v in vals.items():
                d = int(v) - base.get(name, 0)
                if d > 0:
                    total[name] = total.get(name, 0) + d
        return total

    def get(self, name: str) -> int:
        with self._lock:
            ext = (
                self._provider_totals_locked()
                if self._providers or self._frozen else {}
            )
            return self._counts.get(name, 0) + ext.get(name, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self._counts)
            if self._providers or self._frozen:
                for name, v in self._provider_totals_locked().items():
                    out[name] = out.get(name, 0) + v
            return out

    def snapshot_labeled(self) -> Dict[str, Dict[tuple, int]]:
        """{name: {((label, value), ...): count}} for the labeled slice."""
        with self._lock:
            return {n: dict(per) for n, per in self._labeled.items()}

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._labeled.clear()
            self._frozen.clear()
            # re-baseline live providers so their post-reset deltas start
            # at zero (the native counters themselves are never cleared)
            for key, (fn, _base) in list(self._providers.items()):
                try:
                    self._providers[key] = (fn, dict(fn() or {}))
                except Exception:  # noqa: BLE001
                    self._providers[key] = (fn, {})


# Default latency buckets (seconds): 100µs → ~algo 100s, exponential —
# wide enough for a local UDS round trip and a cross-region DCN stall in
# the same histogram.
LATENCY_BUCKETS: Tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 100.0,
)

#: pack-density buckets (member counts per fused frame)
COUNT_BUCKETS: Tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256)

#: request-size buckets (bytes) — MUST match native/hist.h kSizeBounds
#: (the native engine's per-key request-size histograms merge into the
#: same family, and bucket-merge needs identical bounds)
SIZE_BUCKETS: Tuple[float, ...] = (
    64, 256, 1024, 4096, 16384, 65536, 262144, 1048576, 4194304, 16777216,
)

#: compression wire-ratio buckets (compressed bytes / raw bytes): dense
#: below 1.0 where the codecs live (onebit ~0.03, topk 2k/n, dithering
#: ~0.25), with >1 buckets so inflation — the adaptive policy's disable
#: signal — is visible in the same histogram
RATIO_BUCKETS: Tuple[float, ...] = (
    0.01, 0.02, 0.05, 0.1, 0.2, 0.35, 0.5, 0.75, 0.9, 1.0, 1.5, 2.0,
)


class Histogram:
    """Fixed-bucket histogram with cheap percentile snapshots.

    Buckets are CUMULATIVE upper bounds (Prometheus ``le`` semantics)
    with an implicit +Inf bucket.  ``observe`` is one bisect + two adds
    under a lock — cheap enough to stay always-on in the data plane.
    Percentiles interpolate linearly inside the bucket that crosses the
    rank; observations past the last finite bound report that bound
    (the histogram's honest resolution limit).
    """

    __slots__ = ("name", "bounds", "_counts", "_sum", "_count", "_lock")

    def __init__(self, name: str,
                 buckets: Tuple[float, ...] = LATENCY_BUCKETS) -> None:
        self.name = name
        self.bounds: Tuple[float, ...] = tuple(float(b) for b in buckets)
        self._counts = [0] * (len(self.bounds) + 1)  # +1 = the +Inf bucket
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        i = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self._counts[i] += 1
            self._sum += value
            self._count += 1

    def snapshot(self) -> dict:
        """{"count", "sum", "buckets": [(le, cumulative_count), ...]}
        with a trailing ("+Inf", count) entry."""
        with self._lock:
            counts = list(self._counts)
            total, s = self._count, self._sum
        out, cum = [], 0
        for le, c in zip(self.bounds, counts):
            cum += c
            out.append((le, cum))
        out.append((float("inf"), total))
        return {"count": total, "sum": s, "buckets": out}

    def percentile(self, q: float) -> float:
        """q in [0, 1]; 0.0 on an empty histogram."""
        with self._lock:
            counts = list(self._counts)
        return _state_percentile(self.bounds, counts, q)

    def merge_counts(self, bucket_counts: List[int], vsum: float,
                     count: int) -> None:
        """Fold another histogram's RAW (non-cumulative) per-bucket counts
        in — the scheduler-side aggregation path.  Lengths must match."""
        with self._lock:
            for i, c in enumerate(bucket_counts[: len(self._counts)]):
                self._counts[i] += int(c)
            self._sum += vsum
            self._count += count

    def raw_counts(self) -> List[int]:
        with self._lock:
            return list(self._counts)

    def raw_state(self) -> Tuple[List[int], float, int]:
        """(non-cumulative bucket counts, sum, count) read under ONE lock
        acquisition — the delta path needs the three consistent with each
        other, or a racing observe() would ship a count with no bucket
        and skew the aggregate's percentiles until the next beat."""
        with self._lock:
            return list(self._counts), self._sum, self._count


def _state_percentile(bounds, counts, q: float) -> float:
    """Linear-interpolated percentile of a raw (bounds, per-bucket
    counts) state — the ONE interpolation both Histogram.percentile and
    the combined local+provider read path (MetricsRegistry._hist_states)
    use, operating directly on the state so a scrape never builds
    throwaway Histogram objects."""
    total = sum(counts)
    if total == 0:
        return 0.0
    rank = q * total
    prev_le, prev_cum, cum = 0.0, 0, 0
    for i, c in enumerate(counts):
        le = bounds[i] if i < len(bounds) else float("inf")
        cum += int(c)
        if cum >= rank and cum > prev_cum:
            if le == float("inf"):
                return bounds[-1] if bounds else prev_le
            span = cum - prev_cum
            frac = (rank - prev_cum) / span if span else 1.0
            return prev_le + (le - prev_le) * min(1.0, max(0.0, frac))
        prev_le, prev_cum = (0.0 if le == float("inf") else le), cum
    return bounds[-1] if bounds else 0.0


class HeldHistogram:
    """One histogram of a constant label set, kept at hand by a per-task
    path (a stage loop, a span name, an RPC's op): ``observe`` is a bisect
    and the histogram's own lock, where ``MetricsRegistry.observe`` sorts
    and hashes the labels under the registry's lock every time.  The
    histogram is made at the first observation, and made again after a
    ``MetricsRegistry.reset()`` (which moves ``generation`` on): nothing is
    observed into a histogram the registry has dropped."""

    __slots__ = ("_registry", "_name", "_labels", "_buckets", "_hist",
                 "_generation")

    def __init__(self, registry: "MetricsRegistry", name: str,
                 labels: Optional[Dict[str, str]],
                 buckets: Tuple[float, ...]) -> None:
        self._registry = registry
        self._name = name
        self._labels = labels
        self._buckets = buckets
        self._hist: Optional[Histogram] = None
        self._generation = -1

    def get(self) -> Histogram:
        """The live histogram (made now where it is not there yet: a
        family that must read 0 rather than be absent calls this once)."""
        generation = self._registry.generation
        if self._generation != generation:
            # the histogram first: a racing observe then finds either the
            # old pair or fetches the same new histogram itself
            self._hist = self._registry.histogram(
                self._name, self._labels, self._buckets)
            self._generation = generation
        return self._hist

    def observe(self, value: float) -> None:
        if self._generation != self._registry.generation:
            self.get()
        self._hist.observe(value)


class MetricsRegistry:
    """Counters + gauges + histograms behind one scrape surface.

    Counters live in a :class:`RobustnessCounters` (so the pre-existing
    ``counters()`` surface IS the registry's counter store).  Histograms
    are keyed by (name, label set) — each label combination gets its own
    bucket array; exposition groups them under one metric family.
    Gauges are either set values or zero-argument callables sampled at
    render time.
    """

    def __init__(self, counter_store: Optional[RobustnessCounters] = None) -> None:
        self.counters = counter_store if counter_store is not None else RobustnessCounters()
        self._lock = threading.Lock()
        self._hists: Dict[Tuple[str, tuple], Histogram] = {}
        #: moved on by reset(): a HeldHistogram re-makes its histogram then
        self.generation = 0
        # Histogram providers (docs/observability.md) — the twin of the
        # counter-provider seam in RobustnessCounters: zero-arg callables
        # returning raw-bucket records, merged into every read surface.
        # id → (fn, baseline captured at reset())
        self._hist_providers: Dict[int, tuple] = {}
        # gauges keyed by (name, label set), like histograms — label
        # combinations form one exposition family (the striped native
        # engine's native_stripe_queue_depth{stripe} is the first user)
        self._gauges: Dict[Tuple[str, tuple], float] = {}
        self._gauge_fns: Dict[Tuple[str, tuple], Callable[[], float]] = {}
        # delta baseline for heartbeat piggyback.  Normally one consumer
        # per process (the heartbeat loop), but in-process test clusters
        # run worker + server beats against one shared registry — the
        # lock keeps each increment shipped exactly once.
        self._delta_lock = threading.Lock()
        self._requeued: List[dict] = []  # failed-send deltas to re-ship
        self._shipped_counts: Dict[str, int] = {}
        self._shipped_labeled: Dict[str, Dict[tuple, int]] = {}
        self._shipped_hists: Dict[Tuple[str, tuple], Tuple[List[int], float, int]] = {}
        self._shipped_gauges: Dict[Tuple[str, tuple], float] = {}
        # the consumer token of the last reship_for() — a new scheduler
        # incarnation rebases the delta baselines exactly once even when
        # several beat loops share this registry (in-process fleets)
        self._reship_token = None

    # --- registration / recording ---------------------------------------

    def histogram(self, name: str, labels: Optional[Dict[str, str]] = None,
                  buckets: Tuple[float, ...] = LATENCY_BUCKETS) -> Histogram:
        key = (name, _label_key(labels))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = Histogram(name, buckets)
            return h

    def observe(self, name: str, value: float,
                labels: Optional[Dict[str, str]] = None,
                buckets: Tuple[float, ...] = LATENCY_BUCKETS) -> None:
        self.histogram(name, labels, buckets).observe(value)

    def held(self, name: str, labels: Optional[Dict[str, str]] = None,
             buckets: Tuple[float, ...] = LATENCY_BUCKETS) -> HeldHistogram:
        """A handle on one (name, label set) for a caller that observes it
        per task: keep the handle, not the labels (:class:`HeldHistogram`)."""
        return HeldHistogram(self, name, labels, buckets)

    def gauge_set(self, name: str, value: float,
                  labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._gauges[(name, _label_key(labels))] = float(value)

    def gauge_fn(self, name: str, fn: Callable[[], float],
                 labels: Optional[Dict[str, str]] = None) -> None:
        """Lazy gauge: ``fn()`` is sampled at exposition time."""
        with self._lock:
            self._gauge_fns[(name, _label_key(labels))] = fn

    def gauge_remove(self, name: str,
                     labels: Optional[Dict[str, str]] = None) -> None:
        """Drop one gauge series — how a stopping source (a native
        server's per-stripe depth feeds) leaves the scrape surface
        instead of exporting a dead callable forever."""
        key = (name, _label_key(labels))
        with self._lock:
            self._gauges.pop(key, None)
            self._gauge_fns.pop(key, None)

    # --- histogram providers (native C++ engines) ------------------------

    def register_hist_provider(self, fn) -> None:
        """Merge an external histogram source into every read surface —
        the histogram twin of ``RobustnessCounters.register_provider``
        (docs/observability.md): how the GIL-free C++ engines' fixed-
        bucket histograms (the ``native_*`` families) reach
        ``get_metrics()``, the Prometheus exposition, and the heartbeat
        cluster aggregate without the data plane ever calling into
        Python.

        ``fn`` is a zero-arg callable returning an iterable of records
        ``{"name", "labels", "le", "b", "sum", "count"}`` where ``b``
        holds RAW (non-cumulative) per-bucket counts INCLUDING the +Inf
        slot (``len(b) == len(le) + 1``).  Bounds must match the Python
        family's buckets for the merge to compose.  ``fn`` must be
        cheap (a ctypes read + small JSON parse), and tolerate being
        called after its source stopped (return []).  A baseline is
        captured at :meth:`reset` so test-style reset semantics hold
        even though native histograms are never cleared."""
        with self._lock:
            self._hist_providers[id(fn)] = (fn, {})

    def unregister_hist_provider(self, fn) -> None:
        with self._lock:
            self._hist_providers.pop(id(fn), None)

    def absorb_hist_provider(self, fn) -> None:
        """Fold a provider's final values (above its reset baseline)
        into local histograms and unregister it — called before the
        provider's source is torn down (native server/client stop) so
        totals survive.  The combined totals are unchanged by the fold,
        so heartbeat deltas stay continuous across the absorb."""
        with self._lock:
            entry = self._hist_providers.pop(id(fn), None)
        if entry is None:
            return
        fn_live, base = entry
        try:
            recs = list(fn_live() or [])
        except Exception:  # noqa: BLE001 — a dead source has nothing to fold
            recs = []
        for key, st in self._hist_records_states(recs).items():
            name, lkey = key
            if not self._apply_baseline(st, base.get(key)):
                continue
            bounds, counts, vsum, count = st
            h = self.histogram(name, labels=dict(lkey) or None, buckets=bounds)
            if h.bounds == bounds:
                h.merge_counts(counts, vsum, count)

    @staticmethod
    def _apply_baseline(st, base) -> bool:
        """Subtract a :meth:`reset` baseline from a provider state
        ``[bounds, counts, sum, count]`` in place (clamped at zero);
        False when nothing remains above the baseline.  The ONE
        subtraction the absorb and scrape paths share, so their
        semantics can't diverge."""
        if base is not None:
            st[1] = [max(0, a - x) for a, x in zip(st[1], base[0])]
            st[2] = max(0.0, st[2] - base[1])
            st[3] = max(0, st[3] - base[2])
        return st[3] > 0

    @staticmethod
    def _hist_records_states(recs) -> Dict[Tuple[str, tuple], list]:
        """Provider records → {(name, label-key): [bounds, counts, sum,
        count]}, malformed records dropped, duplicate (name, labels)
        entries (several providers feeding one family) summed."""
        out: Dict[Tuple[str, tuple], list] = {}
        for rec in recs or ():
            try:
                name = str(rec["name"])
                lkey = _label_key(rec.get("labels") or None)
                bounds = tuple(float(b) for b in rec["le"])
                counts = [int(c) for c in rec["b"]]
                vsum = float(rec["sum"])
                count = int(rec["count"])
            except (KeyError, TypeError, ValueError):
                continue
            if len(counts) != len(bounds) + 1 or count < 0:
                continue
            cur = out.get((name, lkey))
            if cur is None:
                out[(name, lkey)] = [bounds, counts, vsum, count]
            elif cur[0] == bounds:
                cur[1] = [a + b for a, b in zip(cur[1], counts)]
                cur[2] += vsum
                cur[3] += count
        return out

    def _hist_states(self) -> Dict[Tuple[str, tuple], list]:
        """(name, label-key) → [bounds, raw_counts, sum, count] across
        local histograms AND live providers (above their reset
        baselines) — the ONE combined read path snapshot(), the
        Prometheus render, and the heartbeat delta all share, so every
        surface reports the same totals.  Providers are invoked OUTSIDE
        the registry lock (they parse JSON off a ctypes read)."""
        with self._lock:
            hists = dict(self._hists)
            providers = list(self._hist_providers.values())
        out: Dict[Tuple[str, tuple], list] = {}
        for (name, lkey), h in hists.items():
            counts, vsum, count = h.raw_state()
            out[(name, lkey)] = [h.bounds, counts, vsum, count]
        for fn, base in providers:
            try:
                recs = list(fn() or [])
            except Exception:  # noqa: BLE001 — a dead provider can't break scrape
                continue
            for key, st in self._hist_records_states(recs).items():
                if not self._apply_baseline(st, base.get(key)):
                    continue
                bounds, counts, vsum, count = st
                cur = out.get(key)
                if cur is None:
                    out[key] = [bounds, counts, vsum, count]
                elif tuple(cur[0]) == bounds:
                    cur[1] = [a + x for a, x in zip(cur[1], counts)]
                    cur[2] += vsum
                    cur[3] += count
        return out

    def reset(self) -> None:
        with self._lock:
            self._hists.clear()
            self.generation += 1
            self._gauges.clear()
            self._gauge_fns.clear()
            providers = list(self._hist_providers.items())
        # re-baseline live histogram providers so their post-reset
        # deltas start at zero (native histograms are never cleared).
        # fn() parses JSON off a ctypes read — call it OUTSIDE the
        # registry lock (same rule as _hist_states) so a slow native
        # read can't stall every observe/scrape in the process.
        rebased = []
        for key, (fn, _base) in providers:
            try:
                base = {
                    k: (st[1], st[2], st[3])
                    for k, st in self._hist_records_states(
                        list(fn() or [])
                    ).items()
                }
            except Exception:  # noqa: BLE001
                base = {}
            rebased.append((key, fn, base))
        with self._lock:
            for key, fn, base in rebased:
                # a provider absorbed/unregistered while unlocked must
                # not be resurrected
                if key in self._hist_providers:
                    self._hist_providers[key] = (fn, base)
        with self._delta_lock:
            self._requeued.clear()
            self._shipped_counts.clear()
            self._shipped_labeled.clear()
            self._shipped_hists.clear()
            self._shipped_gauges = {}
            self._reship_token = None
        self.counters.reset()

    # --- snapshots -------------------------------------------------------

    def snapshot(self, labels: Optional[Dict[str, str]] = None) -> dict:
        """Full structured snapshot: counters (flat + labeled), gauges,
        histogram percentiles — the in-process observability surface
        (``bps.get_metrics()``).  Histograms are the COMBINED view:
        local observations plus live histogram providers (the native
        C++ engines' ``native_*`` families).

        ``labels`` are added to every series' own — how a server answers
        ``Op.METRICS`` (``{role="server", rank=...}``), so that its series
        stand beside the asking worker's under names of their own; a flat
        counter then is a labeled one.  Reading moves nothing: the
        heartbeat delta's baseline stays where the last beat left it."""
        extra = _label_key(labels)
        rendered = _render_labels
        if extra:
            def rendered(lkey: tuple) -> str:
                return _render_labels(tuple(sorted(dict(lkey + extra).items())))

        with self._lock:
            gauges = dict(self._gauges)
            gauge_fns = dict(self._gauge_fns)
        out = {
            "counters": self.counters.snapshot(),
            "counters_labeled": {
                name: {rendered(k) or "{}": v for k, v in per.items()}
                for name, per in self.counters.snapshot_labeled().items()
            },
            "gauges": {
                name + rendered(lkey): v
                for (name, lkey), v in gauges.items()
            },
            "histograms": {},
        }
        if extra:
            for name, value in out["counters"].items():
                out["counters_labeled"].setdefault(name, {})[rendered(())] = value
            out["counters"] = {}
        for (name, lkey), fn in gauge_fns.items():
            try:
                out["gauges"][name + rendered(lkey)] = float(fn())
            except Exception:  # noqa: BLE001 — a broken gauge can't break scrape
                continue
        for (name, lkey), st in self._hist_states().items():
            bounds, counts, vsum, count = st
            out["histograms"][name + rendered(lkey)] = {
                "count": count,
                "sum": vsum,
                "p50": _state_percentile(bounds, counts, 0.50),
                "p90": _state_percentile(bounds, counts, 0.90),
                "p99": _state_percentile(bounds, counts, 0.99),
            }
        return out

    # --- Prometheus text exposition --------------------------------------

    def render_prometheus(self, prefix: str = "byteps_") -> str:
        """Text exposition format 0.0.4.  Histograms export the classic
        ``_bucket``/``_sum``/``_count`` family PLUS ``_p50``/``_p90``/
        ``_p99`` gauges so a bare ``curl`` (no PromQL) already answers
        "how slow is the tail"."""
        lines: List[str] = []
        flat = self.counters.snapshot()
        labeled = self.counters.snapshot_labeled()
        for name in sorted(flat):
            metric = f"{prefix}{name}_total"
            lines.append(f"# TYPE {metric} counter")
            lines.append(f"{metric} {flat[name]}")
            if labeled.get(name):
                # the per-label breakdown is a SEPARATE family: the flat
                # total already includes the labeled bumps, so exporting
                # both under one name would make sum() double-count
                # (Prometheus series of one metric must be label-disjoint)
                lmetric = f"{prefix}{name}_labeled_total"
                lines.append(f"# TYPE {lmetric} counter")
                for lkey in sorted(labeled[name]):
                    lines.append(
                        f"{lmetric}{_render_labels(lkey)} {labeled[name][lkey]}"
                    )
        with self._lock:
            gauges = dict(self._gauges)
            gauge_fns = dict(self._gauge_fns)
        for gkey, fn in gauge_fns.items():
            try:
                gauges[gkey] = float(fn())
            except Exception:  # noqa: BLE001
                continue
        # label combinations group under one TYPE line per family, like
        # the histogram exposition below
        g_fams: Dict[str, List[Tuple[tuple, float]]] = {}
        for (name, lkey), v in gauges.items():
            g_fams.setdefault(name, []).append((lkey, v))
        for name in sorted(g_fams):
            metric = f"{prefix}{name}"
            lines.append(f"# TYPE {metric} gauge")
            for lkey, v in sorted(g_fams[name]):
                lines.append(f"{metric}{_render_labels(lkey)} {v}")
        # combined local + provider histograms (native_* families merge
        # into the same exposition the Python engines feed)
        by_family: Dict[str, List[Tuple[tuple, list]]] = {}
        for (name, lkey), st in self._hist_states().items():
            by_family.setdefault(name, []).append((lkey, st))
        for name in sorted(by_family):
            metric = f"{prefix}{name}"
            lines.append(f"# TYPE {metric} histogram")
            for lkey, (bounds, counts, vsum, count) in sorted(
                by_family[name], key=lambda kv: kv[0]
            ):
                cum = 0
                for le, c in zip(bounds, counts):
                    cum += c
                    labels = dict(lkey) | {"le": repr(float(le))}
                    lines.append(
                        f"{metric}_bucket{_render_labels(_label_key(labels))} {cum}"
                    )
                labels = dict(lkey) | {"le": "+Inf"}
                lines.append(
                    f"{metric}_bucket{_render_labels(_label_key(labels))} {count}"
                )
                lines.append(f"{metric}_sum{_render_labels(lkey)} {vsum}")
                lines.append(f"{metric}_count{_render_labels(lkey)} {count}")
            for q, tag in ((0.50, "p50"), (0.90, "p90"), (0.99, "p99")):
                qmetric = f"{metric}_{tag}"
                lines.append(f"# TYPE {qmetric} gauge")
                for lkey, (bounds, counts, _vsum, _count) in sorted(
                    by_family[name], key=lambda kv: kv[0]
                ):
                    lines.append(
                        f"{qmetric}{_render_labels(lkey)} "
                        f"{_state_percentile(bounds, counts, q)}"
                    )
        return "\n".join(lines) + "\n"

    # --- heartbeat delta piggyback (worker/server → scheduler) -----------

    def delta_snapshot(self) -> dict:
        """Counter/histogram increments since the previous call — the
        payload piggybacked on the scheduler heartbeat.  One consumer per
        process (the heartbeat loop); gauges are sent as current values.
        Empty dict when nothing changed (the heartbeat then ships no
        payload at all)."""
        with self._delta_lock:
            return self._delta_snapshot_locked()

    def _delta_snapshot_locked(self) -> dict:
        out: dict = {}
        flat = self.counters.snapshot()
        labeled = self.counters.snapshot_labeled()
        c_delta = {}
        for name, v in flat.items():
            d = v - self._shipped_counts.get(name, 0)
            if d:
                c_delta[name] = d
        if c_delta:
            out["c"] = c_delta
        lc_delta: Dict[str, Dict[str, int]] = {}
        for name, per in labeled.items():
            shipped = self._shipped_labeled.get(name, {})
            for lkey, v in per.items():
                d = v - shipped.get(lkey, 0)
                if d:
                    lc_delta.setdefault(name, {})[json.dumps(lkey)] = d
        if lc_delta:
            out["lc"] = lc_delta
        # combined local + provider histograms: the native engines'
        # families ride the same heartbeat deltas toward the scheduler
        # aggregate as everything else
        h_delta = []
        for (name, lkey), st in self._hist_states().items():
            bounds, raw, vsum, count = st
            prev = self._shipped_hists.get(
                (name, lkey), ([0] * len(raw), 0.0, 0)
            )
            d_counts = [a - b for a, b in zip(raw, prev[0])]
            d_count = count - prev[2]
            if d_count < 0 or any(d < 0 for d in d_counts):
                # a provider is mid-absorb (popped from the registry but
                # not yet folded into local histograms): combined totals
                # transiently went backwards.  Ship nothing and KEEP the
                # old baseline — the fold restores the totals, and the
                # next beat's delta stays exact.  Lowering the baseline
                # here would re-ship the provider's whole history.
                continue
            if d_count > 0:
                h_delta.append({
                    "name": name,
                    "l": [list(kv) for kv in lkey],
                    "le": list(bounds),
                    "b": d_counts,
                    "s": vsum - prev[1],
                    "n": d_count,
                })
            self._shipped_hists[(name, lkey)] = (raw, vsum, count)
        if h_delta:
            out["h"] = h_delta
        # gauges ship as CURRENT values when they changed (or appeared)
        # since the last beat, plus removal markers for series a stopping
        # source dropped — so the scheduler aggregate tracks e.g. each
        # server's owned-key count through a migration without a dead
        # rank's frozen gauge lingering (docs/observability.md)
        with self._lock:
            cur = dict(self._gauges)
            for key, fn in self._gauge_fns.items():
                try:
                    cur[key] = float(fn())
                except Exception:  # noqa: BLE001 — broken gauge ≠ broken beat
                    continue
        g_delta = [
            {"n": name, "l": [list(kv) for kv in lkey], "v": v}
            for (name, lkey), v in cur.items()
            if self._shipped_gauges.get((name, lkey)) != v
        ]
        if g_delta:
            out["g"] = g_delta
        gone = [
            {"n": name, "l": [list(kv) for kv in lkey]}
            for (name, lkey) in self._shipped_gauges
            if (name, lkey) not in cur
        ]
        if gone:
            out["gr"] = gone
        self._shipped_gauges = cur
        self._shipped_counts = flat
        self._shipped_labeled = labeled
        # fold back any delta whose heartbeat FAILED to send: its
        # increments were already consumed from the baselines above and
        # must ride the next successful beat, not vanish
        requeued, self._requeued = self._requeued, []
        for old in requeued:
            for name, d in (old.get("c") or {}).items():
                out.setdefault("c", {})
                out["c"][name] = out["c"].get(name, 0) + int(d)
            for name, per in (old.get("lc") or {}).items():
                dst = out.setdefault("lc", {}).setdefault(name, {})
                for lkey_json, d in per.items():
                    dst[lkey_json] = dst.get(lkey_json, 0) + int(d)
            if old.get("h"):
                # merge_delta adds records independently, so duplicate
                # (name, labels) entries in one payload sum correctly
                out.setdefault("h", []).extend(old["h"])
            # gauges are current-value: requeued records go FIRST so a
            # fresher value of the same series in this beat wins — and a
            # requeued record is DROPPED outright when this beat carries
            # the opposite kind for the same series (the receiver applies
            # all "g" then all "gr" per payload, so a stale requeued
            # removal would otherwise delete a series that just
            # reappeared, and a stale requeued value would resurrect one
            # that was just removed)
            fresh = {
                field: {
                    (r.get("n"), tuple(map(tuple, r.get("l") or ())))
                    for r in out.get(field) or ()
                }
                for field in ("g", "gr")
            }
            for field, opposite in (("g", "gr"), ("gr", "g")):
                keep = [
                    r for r in old.get(field) or ()
                    if (r.get("n"), tuple(map(tuple, r.get("l") or ())))
                    not in fresh[opposite]
                ]
                if keep:
                    out[field] = keep + list(out.get(field, []))
        return out

    def reship_for(self, token) -> bool:
        """Re-arm the delta baselines so the NEXT :meth:`delta_snapshot`
        ships the FULL history (counters, labeled slices, histograms)
        and re-registers every gauge — called when the heartbeat
        consumer changed identity (a new scheduler incarnation whose
        aggregate started empty; the dead one took the old baselines'
        aggregate to its grave, docs/robustness.md "Control-plane
        recovery").

        Idempotent per ``token``: in-process test fleets run several
        beat loops (worker + servers) against ONE shared registry, and
        only the first loop to observe the new incarnation may rebase —
        a second rebase would re-ship increments the first full
        snapshot already delivered, double-counting them in the new
        aggregate.  Returns True when the rebase actually happened.
        Requeued failed-send deltas are dropped (their increments are
        subsumed by the full re-ship)."""
        with self._delta_lock:
            if token == self._reship_token:
                return False
            self._reship_token = token
            self._requeued.clear()
            self._shipped_counts.clear()
            self._shipped_labeled.clear()
            self._shipped_hists.clear()
            self._shipped_gauges = {}
            return True

    def requeue_delta(self, delta: dict) -> None:
        """Give back a delta whose send failed; the next
        :meth:`delta_snapshot` includes it (at-least-once delivery of
        increments toward the scheduler aggregate)."""
        if not delta:
            return
        with self._delta_lock:
            self._requeued.append(delta)

    def merge_delta(self, delta: dict,
                    labels: Optional[Dict[str, str]] = None) -> None:
        """Fold one node's delta into this (scheduler-side aggregate)
        registry.  ``labels`` (e.g. {"role": "worker", "rank": "1"}) tag
        the counter contributions so a sick node stays visible in the
        aggregate; histograms merge flat (cluster-wide latency shape)."""
        for name, d in (delta.get("c") or {}).items():
            self.counters.bump(str(name), int(d), labels=labels)
        for name, per in (delta.get("lc") or {}).items():
            for lkey_json, d in per.items():
                try:
                    node_labels = dict(tuple(kv) for kv in json.loads(lkey_json))
                except (ValueError, TypeError):
                    node_labels = {}
                if labels:
                    node_labels.update(labels)
                # flat=False: the unlabeled "c" delta above already
                # carried these bumps — re-adding would double-count
                self.counters.bump(
                    str(name), int(d), labels=node_labels, flat=False
                )
        for rec in delta.get("h") or ():
            try:
                bounds = tuple(float(b) for b in rec["le"])
                node_labels = dict(tuple(kv) for kv in rec.get("l") or ())
                h = self.histogram(
                    str(rec["name"]), labels=node_labels or None,
                    buckets=bounds,
                )
                h.merge_counts(
                    [int(c) for c in rec["b"]], float(rec["s"]), int(rec["n"])
                )
            except (KeyError, ValueError, TypeError):
                continue  # malformed delta: drop, never poison the scrape
        # gauges: current values, node labels merged with the sender tag
        # (so cluster_map_epoch sits next to each server's
        # server_owned_keys{rank} in the bps_top view); "gr" drops series
        # a stopping source removed (a drained server's owned-key gauge)
        for rec in delta.get("g") or ():
            try:
                node_labels = dict(tuple(kv) for kv in rec.get("l") or ())
                if labels:
                    node_labels.update(labels)
                self.gauge_set(
                    str(rec["n"]), float(rec["v"]),
                    labels=node_labels or None,
                )
            except (KeyError, ValueError, TypeError):
                continue
        for rec in delta.get("gr") or ():
            try:
                node_labels = dict(tuple(kv) for kv in rec.get("l") or ())
                if labels:
                    node_labels.update(labels)
                self.gauge_remove(str(rec["n"]), labels=node_labels or None)
            except (KeyError, ValueError, TypeError):
                continue


class MetricsHTTPServer:
    """Tiny threaded HTTP exposition server for one render callback.

    Binds ``port`` (0 = ephemeral); when the requested port is taken —
    several byteps processes sharing one host and one
    ``BYTEPS_METRICS_PORT`` — falls back to an ephemeral port and logs
    the actual one, so every process still gets a scrape surface.
    """

    def __init__(self, port: int, render: Callable[[], str],
                 host: str = "0.0.0.0") -> None:
        import http.server

        render_fn = render

        class _Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 — http.server API
                try:
                    body = render_fn().encode()
                except Exception as e:  # noqa: BLE001
                    self.send_response(500)
                    self.end_headers()
                    self.wfile.write(repr(e).encode())
                    return
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # scrapes must not spam stderr
                pass

        try:
            self._httpd = http.server.ThreadingHTTPServer((host, port), _Handler)
        except OSError:
            from byteps_tpu.common import logging as bpslog

            self._httpd = http.server.ThreadingHTTPServer((host, 0), _Handler)
            bpslog.warning(
                "BYTEPS_METRICS_PORT=%d in use; serving metrics on %d instead",
                port, self._httpd.server_address[1],
            )
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="bps-metrics-http",
            daemon=True,
        )
        self._thread.start()

    def close(self) -> None:
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except OSError:
            pass


def serve_metrics(port: int, render: Optional[Callable[[], str]] = None,
                  host: str = "0.0.0.0") -> MetricsHTTPServer:
    """Start the Prometheus exposition endpoint; default renders the
    process-global registry."""
    return MetricsHTTPServer(
        port, render if render is not None else metrics().render_prometheus,
        host=host,
    )


_counters = RobustnessCounters()
_registry = MetricsRegistry(counter_store=_counters)


def counters() -> RobustnessCounters:
    """The process-global robustness counter set."""
    return _counters


def metrics() -> MetricsRegistry:
    """The process-global metrics registry (counters + gauges +
    histograms behind one scrape surface)."""
    return _registry
