"""CPU parameter-server engine.

Re-design of byteps/server/server.cc (SURVEY §2.3) for the TPU build's DCN
PS hop:

- one KV handler per connection thread feeding N engine threads
  (``BYTEPS_SERVER_ENGINE_THREAD``, server.cc:485-497), each owning a
  priority queue; key→thread via least-loaded assignment cached per key
  (server.h:154-178);
- push: first arrival of a round copies (COPY_FIRST), later arrivals sum
  (SUM_RECV); when all workers arrived (ALL_RECV) the merged result is
  published and buffered pulls are answered (server.cc:296-375);
- pull: answered immediately if the requested round is complete, else
  queued (server.cc:376-409);
- init push doubles as a cross-worker barrier (server.cc:266-295);
- sync vs async mode (``BYTEPS_ENABLE_ASYNC``): async sums straight into
  the store and answers pulls immediately — parameter-store semantics
  (server.cc:315-319);
- anti-starvation scheduling (``BYTEPS_SERVER_ENABLE_SCHEDULE``): pop the
  key with the fewest accumulated pushes first (queue.h:49-97).

The reduction itself calls the native C++ reducer when built (SURVEY build
plan §3), with a numpy fallback.
"""

from __future__ import annotations

import heapq
import itertools
import json
import socket
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from byteps_tpu.common.config import Config
from byteps_tpu.common.types import (
    DataType,
    RequestType,
    decode_command_type,
    to_numpy_dtype,
)
from byteps_tpu.comm.transport import (
    FramePool,
    Message,
    Op,
    close_socket,
    connect,
    listen,
    recv_body,
    recv_header_ex,
    recv_message,
    release_frame,
    send_message,
)
from byteps_tpu.comm.rendezvous import GROUP_ALL
from byteps_tpu.core.tracing import releasing, thread_account


def _apply_lr_to_chain(codec, lr: float) -> None:
    """Walk a compressor decorator chain, feeding lr to every EF stage."""
    c = codec
    while c is not None:
        setter = getattr(c, "set_lr", None)
        if setter is not None:
            setter(lr)
        c = getattr(c, "inner", None)


class _KeyState:
    __slots__ = (
        "store",
        "accum",
        "recv_count",
        "store_version",
        "pushed_total",
        "pending_pulls",
        "fused_waiters",
        "init_waiters",
        "init_done",
        "push_seen",
        "dtype",
        "compressor_kwargs",
        "compressor",
        "pull_payload",
        "pull_version",
        "raw_payload",
        "raw_version",
        "lent",
        "lent_accum",
        "migrated_to",
        "migrate_epoch",
        "job",
        "async_mode",
        "staleness",
        "opt_rule",
        "opt_rule_name",
        "opt_hp",
        "opt_step",
        "opt_seeded",
        "req_bytes",
        "lock",
    )

    def __init__(self) -> None:
        self.store: Optional[np.ndarray] = None
        self.accum: Optional[np.ndarray] = None
        self.recv_count = 0
        self.store_version = 0
        self.pushed_total = 0
        # (version, conn, send_lock, seq, wants_compressed, rowsparse_req)
        self.pending_pulls: List[
            Tuple[int, socket.socket, threading.Lock, int, bool, Optional[bytes]]
        ] = []
        # fused-frame pull halves parked on this key:
        # (version, _FusedReply, slot, wants_compressed) — filled at round
        # publish; a completed reply rides the same flush list as pulls
        self.fused_waiters: List[Tuple[int, "_FusedReply", int, bool]] = []
        # (worker_flag, conn, send_lock, seq, token); worker_flag 0 =
        # anonymous, token 0 = tokenless (pre-recovery-plane client)
        self.init_waiters: List[
            Tuple[int, socket.socket, threading.Lock, int, int]
        ] = []
        # init-idempotency ledger (docs/robustness.md): worker_flag → the
        # token (msg.version on INIT: epoch-scoped per-(key, worker) init
        # sequence) whose barrier COMPLETED.  A replayed INIT — the
        # worker's retry after its ack was dropped AFTER the barrier
        # released — arrives with the SAME token and is acked from this
        # record instead of re-parked; its peers, already released, would
        # never re-init the key, so re-parking stranded the retrier until
        # its budget died.  Elastic rejoin mints a different token (new
        # epoch / new client salt), so a genuine new barrier still parks.
        self.init_done: Dict[int, int] = {}
        # replay dedupe (docs/robustness.md): worker_flag → newest summed
        # push version.  Per (key, worker) versions are strictly
        # increasing (the engine's round gate), so a replayed push — the
        # worker's retry after a lost ack or dropped frame — arrives with
        # version <= the recorded one and is acked WITHOUT re-summing:
        # retried summation stays exactly-once.
        self.push_seen: Dict[int, int] = {}
        self.dtype: Optional[np.dtype] = None
        self.compressor_kwargs: Dict[str, str] = {}
        self.compressor = None  # server-side chain (no momentum)
        self.pull_payload: Optional[bytes] = None  # compressed merged result
        self.pull_version = -1
        self.raw_payload: Optional[bytes] = None   # round-cached raw bytes
        self.raw_version = -1
        # replies not yet sent that are a VIEW of a store buffer (lend):
        # of the published round's (``store``), and of the round before's,
        # which the publish turned into ``accum``.  The next round's first
        # push may not write there while one is out (own_accum)
        self.lent = 0
        self.lent_accum = 0
        # elastic resharding tombstone (docs/robustness.md "migration
        # flow"): rank this key's state was shipped to (None = lives
        # here), and the map epoch of the last migration event in either
        # direction — stamped into WRONG_OWNER redirects so a stale-map
        # worker knows which book to wait for before chasing
        self.migrated_to: Optional[int] = None
        self.migrate_epoch = 0
        # multi-tenant + async profile (docs/async.md): the job id the
        # key is namespaced under (top 16 key bits; set at _key_state),
        # whether its INIT declared the ASYNC profile (pushes apply
        # immediately, pulls serve current state), and the bounded-
        # staleness window for its pulls (-1 = unbounded; 0 = a pull at
        # round v waits until every job worker applied round v —
        # sequential consistency)
        self.job = 0
        self.async_mode = False
        self.staleness = -1
        # server-side optimizer plane (docs/architecture.md "Server-side
        # optimizer"): the INIT profile's bit 1 declares an update rule
        # (server/update_rules.py) for this key — workers push gradients
        # and pull UPDATED PARAMETERS.  opt_step counts completed rounds
        # (0 = the parameter seed round hasn't published yet); opt_seeded
        # is the async-mode per-worker seed ledger (each worker's first
        # push carries its initial params, adopted once, never summed).
        # All of it lives behind ks.lock like the rest of the round
        # state, ships in MIGRATE_STATE, and survives the re-init
        # barrier (store contents do too).
        self.opt_rule = None  # update_rules.UpdateRule instance
        self.opt_rule_name: Optional[str] = None
        self.opt_hp: Dict[str, Any] = {}
        self.opt_step = 0
        self.opt_seeded: set = set()
        # cumulative data-plane request bytes (docs/autotune.md): fed by
        # _enqueue on the serve threads, read per heartbeat by the
        # hot-key report.  Bare += across threads may lose an increment
        # under contention — load *statistics*, not an exact ledger.
        self.req_bytes = 0
        self.lock = threading.Lock()

    def wire_payload(self, compressed: bool, async_mode: bool = False,
                     lend: bool = False):
        """What a puller receives, honoring ITS requested wire format:
        compressed pulls get the codec-compressed merged result
        (server.cc:92-118), default pulls get raw bytes — mixed-config
        workers on one key stay correct.  In async mode the store mutates
        every push, so both formats encode on demand.

        ``lend``: the caller sends the payload itself and hands it back
        (:meth:`give_back`) when the send is over.  A sync key's raw
        round is then the published store's own bytes, a ``memoryview``
        and no copy: nothing writes a published store in place (a round
        sums into ``accum``; :meth:`own_accum` keeps a lent buffer from
        becoming that), so the view is the round for as long as it is
        held.  Where the store does change in place (async mode, a
        server-side update rule) and for fused slots, raw bytes are
        serialized ONCE per round and served to every puller from the
        cache — the reference caches response KVPairs for the same reason
        (avoid per-request copies / re-registration, server.cc:39-80)."""
        if compressed and self.compressor is not None:
            if async_mode:
                return self.compressor.compress(self.store)
            # version-gated like the raw cache: a round whose LAST push was
            # uncompressed skips the publish-time compression, so a stale
            # pull_payload must never be served for the new round
            if self.pull_version != self.store_version:
                self.pull_payload = self.compressor.compress(self.store)
                self.pull_version = self.store_version
            return self.pull_payload
        from byteps_tpu.core.telemetry import counters

        if async_mode:
            counters().bump("host_buffers_fresh", labels=_REPLY_SITE)
            return self.store.tobytes()
        if lend and self.opt_rule is None:
            counters().bump("host_buffers_reused", labels=_REPLY_SITE)
            self.lent += 1
            return memoryview(self.store).cast("B")
        if self.raw_version != self.store_version:
            counters().bump("host_buffers_fresh", labels=_REPLY_SITE)
            self.raw_payload = self.store.tobytes()
            self.raw_version = self.store_version
        return self.raw_payload

    def set_buffers(self, store, accum) -> None:
        """New store and accumulator (INIT, a migration in or out): what
        was lent of the old ones stays with the old ones."""
        self.store, self.accum = store, accum
        self.lent = self.lent_accum = 0

    def give_back(self, view: memoryview) -> None:
        """A lent round's reply was sent, or never will be."""
        with self.lock:
            if view.obj is self.store:
                self.lent -= 1
            elif view.obj is self.accum:
                self.lent_accum -= 1
            # else: the buffer was retired while lent (own_accum)

    def own_accum(self) -> None:
        """Before a round's first write into ``accum`` (caller holds the
        lock): where a reply still views that buffer — it was the store a
        round ago, and a writer queue or a slow socket has not sent it
        yet — leave it to the reply and sum into a fresh one."""
        if self.lent_accum:
            from byteps_tpu.core.telemetry import counters

            counters().bump("host_buffers_fresh", labels=_REPLY_SITE)
            self.accum = np.empty_like(self.accum)
            self.lent_accum = 0

    def publish_swap(self) -> None:
        """The summed round becomes the store; the round before it, with
        whatever replies still view it, the next round's accumulator."""
        self.store, self.accum = self.accum, self.store
        self.lent, self.lent_accum = 0, self.lent


_REPLY_SITE = {"site": "reply"}


class _FusedReply:
    """Accumulator for one Op.FUSED frame's multi-key response.

    Sub-keys' rounds complete independently (another worker's push to key
    A can publish while key B still waits), possibly on different engine
    threads — each completed member fills its slot, and the LAST fill
    (exactly one, lock-guarded) makes the whole frame sendable.  The
    response leaves as ONE frame so the worker's single seq/deadline/retry
    state resolves atomically for every member."""

    __slots__ = (
        "conn", "send_lock", "seq", "route_key", "keys", "slots",
        "versions", "remaining", "aborted", "lock",
    )

    def __init__(self, conn, send_lock, seq: int, route_key: int,
                 keys: List[int]) -> None:
        self.conn = conn
        self.send_lock = send_lock
        self.seq = seq
        self.route_key = route_key
        self.keys = keys
        self.slots: List[Optional[bytes]] = [None] * len(keys)
        self.versions = [0] * len(keys)
        self.remaining = len(keys)
        # set when the frame was answered OUT of band (WRONG_OWNER
        # redirect / migration park): later round publishes must not fill
        # slots into a seq the worker already resolved — a second
        # response on one seq would corrupt the client's demux
        self.aborted = False
        self.lock = threading.Lock()

    def fill(self, slot: int, payload: bytes, version: int) -> bool:
        """Record one member's merged round; True exactly once — when this
        fill completed the frame (the caller then queues the send)."""
        with self.lock:
            if self.aborted or self.slots[slot] is not None:
                return False  # aborted frame / duplicate publish race
            self.slots[slot] = payload
            self.versions[slot] = version
            self.remaining -= 1
            return self.remaining == 0

    def abort(self) -> bool:
        """Mark the frame as answered out of band; True exactly once
        (the winner sends the out-of-band reply on this seq)."""
        with self.lock:
            if self.aborted or self.remaining == 0:
                return False  # already aborted, or the reply already left
            self.aborted = True
            return True

    def send(self) -> None:
        from byteps_tpu.comm.transport import encode_fused_reply

        body = encode_fused_reply(
            list(zip(self.keys, self.versions, self.slots))
        )
        send_message(
            self.conn,
            Message(Op.FUSED, key=self.route_key, seq=self.seq, payload=body),
            self.send_lock,
        )


class _EngineQueue:
    """Priority queue per engine thread (server/queue.h).

    With scheduling enabled, pops the task whose key has the fewest
    accumulated pushes (anti-starvation, queue.h:49-97); otherwise FIFO.

    Multi-tenant dimension (docs/async.md): tasks carry the JOB their
    key is namespaced under, and the queue runs weighted fair queuing
    ACROSS jobs — each job's lane accumulates served bytes divided by
    its weight (the book's per-job ``priority``), and the pop serves
    the lane with the lowest virtual time.  With a single job (the
    pre-tenancy default) the WFQ layer is inert and the order is
    identical to the classic per-thread queue, so a bulk tenant's
    backlog can never sit in front of a latency tenant's requests
    beyond its weighted share.
    """

    def __init__(self, enable_schedule: bool, weight_fn=None) -> None:
        self.enable_schedule = enable_schedule
        self._weight_fn = weight_fn or (lambda job: 1.0)
        self._cv = threading.Condition()
        #: job → [heap, vtime]; the heap entries are
        #: (prio, arrival counter, item, cost bytes)
        self._lanes: Dict[int, list] = {}
        self._counter = itertools.count()
        self._size = 0

    def _weight(self, job: int) -> float:
        try:
            return max(0.001, float(self._weight_fn(job)))
        except Exception:  # noqa: BLE001 — a QoS lookup bug ≠ a stall
            return 1.0

    def put(self, prio: int, item, job: int = 0, cost: int = 1) -> None:
        with self._cv:
            lane = self._lanes.get(job)
            if lane is None:
                lane = self._lanes[job] = [[], 0.0]
            if not lane[0]:
                # WFQ virtual-time join (see core/scheduler.py): an
                # idle tenant re-activates at the live clock floor —
                # neither a monopoly debt nor a starvation credit
                active = [
                    ln[1] / self._weight(j)
                    for j, ln in self._lanes.items() if ln[0]
                ]
                if active:
                    lane[1] = max(lane[1], min(active) * self._weight(job))
            heapq.heappush(
                lane[0],
                (prio if self.enable_schedule else 0,
                 next(self._counter), item, max(1, cost)),
            )
            self._size += 1
            self._cv.notify()

    def get(self, timeout: Optional[float] = None):
        # wait_for (not a single wait): a spurious wakeup must re-wait the
        # remaining budget, not cost a whole idle poll tick of tail latency
        with self._cv:
            self._cv.wait_for(lambda: self._size > 0, timeout)
            if self._size == 0:
                return None
            job = min(
                (j for j, ln in self._lanes.items() if ln[0]),
                key=lambda j: self._lanes[j][1] / self._weight(j),
            )
            lane = self._lanes[job]
            _prio, _cnt, item, cost = heapq.heappop(lane[0])
            lane[1] += cost
            self._size -= 1
            return item


class _ConnWriter:
    """Per-connection reply writer — tenant response isolation
    (docs/async.md).

    The engine threads used to write replies INLINE; on a shared fleet
    that is a cross-tenant head-of-line block no queue discipline can
    fix: a bulk tenant whose (shaped / congested) socket buffer is full
    parks the engine thread in ``sendall`` mid-item, and every other
    tenant's queued requests wait out the block — WFQ reorders the
    queue, not a thread stuck in a syscall.  With QoS active, engine
    replies route through one writer thread per connection instead, so
    a slow tenant's wire backs up ITS OWN writer only.

    Bounded: past ``max_bytes`` of queued replies the producer blocks
    (the engine thread then waits on that one conn — the pre-writer
    behavior — rather than the process growing without bound; the
    admission quota upstream keeps a metered tenant far from the cap).
    The writer reaps itself after ``idle_s`` without traffic; a dead or
    reaped writer is replaced lazily by :meth:`PSServer._reply_writer`.
    """

    __slots__ = ("_q", "_cv", "_bytes", "max_bytes", "idle_s", "dead")

    def __init__(self, max_bytes: int = 16 << 20,
                 idle_s: float = 5.0) -> None:
        self._q: List = []
        self._cv = threading.Condition()
        self._bytes = 0
        self.max_bytes = max_bytes
        self.idle_s = idle_s
        self.dead = False
        threading.Thread(
            target=self._loop, name="ps-reply-writer", daemon=True
        ).start()

    def submit(self, fn, nbytes: int) -> bool:
        """Queue one send closure; False when this writer is dead (the
        caller creates a fresh one).  Blocks past the byte cap."""
        with self._cv:
            while not self.dead and self._bytes >= self.max_bytes:
                self._cv.wait(0.1)
            if self.dead:
                return False
            self._q.append((fn, nbytes))
            self._bytes += nbytes
            self._cv.notify_all()
            return True

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._q:
                    if not self._cv.wait(self.idle_s) and not self._q:
                        self.dead = True  # idle: reap this thread
                        return
                fn, nbytes = self._q.pop(0)
            try:
                fn()
            except (ConnectionError, OSError):
                # conn died: drop the backlog — the peer's retry path
                # owns recovery, exactly as with inline sends
                with self._cv:
                    self.dead = True
                    self._q.clear()
                    self._bytes = 0
                    self._cv.notify_all()
                return
            with self._cv:
                self._bytes -= nbytes
                self._cv.notify_all()


class _QuotaBucket:
    """Per-job admission meter (``BYTEPS_JOB_QUOTA_MBPS``,
    docs/async.md): a virtual-wire token bucket over request payload
    bytes.  ``reserve(n)`` returns how long the caller must DEFER the
    request before serving it — excess traffic is delayed (backpressure
    through the socket, exactly like a slow link), never dropped, so
    retry/dedupe semantics are untouched."""

    __slots__ = ("rate", "burst_s", "_free_at", "lock")

    def __init__(self, mbps: float, burst_s: float = 0.25) -> None:
        self.rate = max(1.0, mbps * 1e6)  # bytes/s (megaBYTES/s knob)
        self.burst_s = burst_s
        self._free_at = 0.0
        self.lock = threading.Lock()

    def reserve(self, nbytes: int) -> float:
        with self.lock:
            now = time.monotonic()
            # idle credit is capped at one burst window: a job that went
            # quiet may burst briefly, not bank unlimited backlog
            self._free_at = max(self._free_at, now - self.burst_s)
            admit_at = self._free_at
            self._free_at += nbytes / self.rate
            return max(0.0, admit_at - now)


class PSServer:
    #: an engine thread's longest wait for its queue before it looks at the
    #: stop flag again (and the coarsest its idle account's edges get)
    _POLL_S = 0.2

    def __init__(self, cfg: Config, host: str = "127.0.0.1") -> None:
        from byteps_tpu.comm.van import get_van

        self.cfg = cfg
        # worker-facing listener rides the selected van (BYTEPS_VAN:
        # tcp | uds); the published address encodes the scheme, so clients
        # dial the right transport with no configuration
        self._van = get_van()
        self._sock, self.host, self.port = self._van.listen(host)
        self._keys: Dict[int, _KeyState] = {}
        self._keys_lock = threading.Lock()
        # EF residual lr broadcast by workers (lr-update flag on
        # REGISTER_COMPRESSOR); chains registered later inherit it
        self._ef_lr = 1.0
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        # key→engine-thread least-loaded assignment (server.h:154-178)
        self._tid_cache: Dict[int, int] = {}
        self._tid_load: List[int] = [0] * max(1, cfg.server_engine_threads)
        self._tid_lock = threading.Lock()
        # --- multi-tenant plane (docs/async.md) ---
        # per-job membership (worker FLAGS = rank+1) + QoS adopted from
        # every book's ``jobs`` map: per-key rounds/barriers complete
        # against the key's JOB population, the engine queues weight
        # service per job, and the admission meter defers a job's
        # requests past its quota
        self._job_workers: Dict[int, set] = {}
        self._job_qos: Dict[int, dict] = {}
        self._job_quota: Dict[int, _QuotaBucket] = {}
        self._qos_active = False
        # per-connection reply writers (tenant response isolation): with
        # QoS active, engine threads hand replies to one writer thread
        # per conn instead of blocking in sendall on a slow tenant's
        # socket — see _ConnWriter
        self._writers: Dict[int, _ConnWriter] = {}
        self._writers_lock = threading.Lock()
        self._queues = [
            _EngineQueue(cfg.server_enable_schedule,
                         weight_fn=self._job_weight)
            for _ in range(max(1, cfg.server_engine_threads))
        ]
        self.rank: Optional[int] = None
        self.num_workers = cfg.num_worker
        # zombie fence (docs/robustness.md): worker flags (rank+1) the
        # scheduler's latest book lists as LIVE; None = no book seen yet /
        # book without ranks → fence off.  Pushes from evicted ranks are
        # rejected so a stalled-but-alive worker cannot pollute rounds
        # sized for the shrunken membership.
        self._live_worker_flags: Optional[set] = None
        self._sched_conn: Optional[socket.socket] = None
        # control-plane recovery state (docs/robustness.md): newest
        # scheduler incarnation / membership epoch seen (reported back
        # on rejoin re-REGISTER), the last-adopted map epoch, and the
        # deliberate-shutdown flag that stops the reconnect machine from
        # chasing a scheduler that ORDERED this server to stop
        self.sched_incarnation = 0
        self.membership_epoch = 0
        self._map_epoch = 0
        self._sched_shutdown = False
        self._reducer = _make_reducer()
        # --- elastic resharding (docs/robustness.md "migration flow") ---
        # ownership = epoch-stamped consistent-hash ring over server
        # RANKS, adopted from scheduler books.  On a map change this
        # server ships every re-homed key's state to its new owner
        # (Op.MIGRATE_STATE) and answers stale-map requests with
        # Op.WRONG_OWNER; requests for keys whose migration is inbound
        # park until the state lands (bounded by BYTEPS_MIGRATE_DEADLINE_S).
        self.reshard = cfg.elastic_reshard
        self._ownership = None       # current OwnershipMap (or None)
        self._prev_ownership = None  # the map before the last adoption
        self._own_lock = threading.Lock()
        self._peer_addrs: Dict[int, Tuple[str, int]] = {}
        self._awaiting: Dict[int, List] = {}  # key → parked (t, msg, conn, lock)
        self._awaiting_lock = threading.Lock()
        self._awaiting_sweeper: Optional[threading.Thread] = None
        import os

        from byteps_tpu.common.config import resolve_node_uid

        self._debug = os.environ.get("BYTEPS_SERVER_DEBUG", "0") == "1"
        # stable identity for scheduler rejoin matching (the listen address
        # is also stable, but a restarted server gets a fresh ephemeral port)
        self.node_uid = resolve_node_uid()
        # observability plane (docs/observability.md): the server emits
        # child spans (recv→sum→publish→reply) joined to worker traces by
        # the wire-propagated ids, plus sum/publish latency histograms
        # and a Prometheus endpoint.  The tracer writes its own
        # "server<rank>" subdir so a same-host worker's file is never
        # clobbered; tools/trace_merge.py stitches them.
        from byteps_tpu.core.tracing import Tracer, get_process_tracer, set_process_tracer

        self.tracer = Tracer(
            enabled=cfg.trace_on,
            trace_dir=cfg.trace_dir,
            local_rank="server",
            process_name="server",
            spans_enabled=cfg.trace_spans,
        )
        if get_process_tracer() is None:
            # a dedicated server process tags chaos faults on this tracer;
            # in-process test clusters keep the worker's tracer
            set_process_tracer(self.tracer)
        # flight recorder (docs/observability.md "Flight recorder &
        # doctor"): dedicated server processes own the process recorder;
        # in-process fleets share whichever role created it first (they
        # already share one metrics registry, so the ledger is coherent)
        from byteps_tpu.core.flightrec import ensure_process_recorder

        ensure_process_recorder(
            cfg, context_fn=self._flight_context, tracer=self.tracer
        )
        self._metrics_http = None

    def _flight_context(self) -> dict:
        """Control-plane context stamped into every flight record."""
        from byteps_tpu.core.telemetry import metrics

        # GIL-atomic dict read of the gauge the reconnect machine sets
        deg = metrics()._gauges.get(("control_plane_degraded", ()), 0)
        return {
            "epoch": getattr(self, "membership_epoch", 0),
            "map_epoch": getattr(self, "_map_epoch", 0),
            "incarnation": getattr(self, "sched_incarnation", 0),
            "degraded": int(deg),
        }

    # --- lifecycle -------------------------------------------------------

    def start(self, register: bool = True) -> None:
        for i, q in enumerate(self._queues):
            t = threading.Thread(
                target=self._engine_loop, args=(q,), name=f"ps-engine-{i}", daemon=True
            )
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._accept_loop, name="ps-accept", daemon=True)
        t.start()
        self._threads.append(t)
        if self.cfg.metrics_port > 0 and self._metrics_http is None:
            from byteps_tpu.core.telemetry import serve_metrics

            self._metrics_http = serve_metrics(self.cfg.metrics_port)
        if register:
            self._register_with_scheduler()

    def stop(self) -> None:
        self._stop.set()
        if self._metrics_http is not None:
            self._metrics_http.close()
            self._metrics_http = None
        # release the flight recorder iff THIS server installed it (a
        # worker-owned one in an in-process fleet stays); leaving a dead
        # server's recorder — its context closure and knob snapshot —
        # would poison the next init cycle's ensure_process_recorder
        from byteps_tpu.core.flightrec import release_process_recorder

        release_process_recorder(self._flight_context)
        if self.reshard and self.rank is not None:
            # ownership gauges describe a live server only — drop the
            # series (in-process fleets reuse the registry across
            # instances; a dead rank's frozen gauge would mislead)
            from byteps_tpu.core.telemetry import metrics

            labels = {"rank": str(self.rank)}
            metrics().gauge_remove("server_owned_keys", labels=labels)
            metrics().gauge_remove("server_map_epoch", labels=labels)
        self.tracer.flush()
        try:
            self._sock.close()  # listener: no peer to FIN
        except OSError:
            pass
        from byteps_tpu.comm.van import UNIX_PREFIX, strip_chaos

        host = strip_chaos(self.host)  # chaos:uds publishes chaos+unix://
        if host.startswith(UNIX_PREFIX):
            import os

            try:
                os.unlink(host[len(UNIX_PREFIX):])
            except OSError:
                pass
        close_socket(self._sched_conn)

    def _register_with_scheduler(self) -> None:
        """ps::StartPS + barrier equivalent (server.cc:500-509)."""
        conn = self._sched_register_once(initial=True)
        # degraded-state gauge exists from bring-up (docs/robustness.md)
        from byteps_tpu.core.telemetry import metrics

        metrics().gauge_set("control_plane_degraded", 0)
        # global barrier before serving (server.cc:506) — initial
        # bring-up only; a REJOIN after scheduler restart / link loss
        # must not barrier (the cluster is mid-training, nobody pairs)
        send_message(conn, Message(Op.BARRIER, flags=GROUP_ALL))
        recv_message(conn)
        # This thread owns the scheduler connection from here on: periodic
        # heartbeat (ps-lite heartbeats, SURVEY §5.3) when enabled, and in
        # all cases the reader for unsolicited control messages — RESIZE_SEQ
        # address books and the scale-down SHUTDOWN must be honored even
        # with heartbeats disabled (BYTEPS_HEARTBEAT_INTERVAL=0), and
        # promptly (a book parked until the next heartbeat tick would keep
        # the zombie fence / worker count stale for a whole interval).
        threading.Thread(
            target=self._control_plane_loop, args=(conn,),
            name="ps-heartbeat", daemon=True,
        ).start()

    def _sched_register_once(self, initial: bool = True):
        """Dial the scheduler and REGISTER; adopt the reply book and
        return the connected control socket.  ``initial=False`` is the
        control-plane recovery path (docs/robustness.md): the payload
        additionally reports this server's last-known rank and the
        membership/map epochs it acted under, so a RESTARTED scheduler
        can reconstruct its registration table and fence its first
        books above everything this node already saw."""
        from byteps_tpu.comm.transport import connect_control

        conn = connect_control(self.cfg.ps_root_uri, self.cfg.ps_root_port)
        try:
            payload = {
                "role": "server",
                "host": self.host,
                "port": self.port,
                "uid": self.node_uid,
            }
            if not initial:
                omap = getattr(self, "_ownership", None)
                payload.update({
                    "last_rank": self.rank,
                    "epoch": self.membership_epoch,
                    "map_epoch": max(
                        int(omap.epoch) if omap is not None else 0,
                        int(getattr(self, "_map_epoch", 0) or 0),
                    ),
                    # live reconnect: no bring-up barrier follows, so no
                    # recovered-conn barrier bypass may be armed
                    "reconnect": True,
                })
                # last-observed fleet tuning + placement overrides: a
                # reborn scheduler's tuner re-adopts these before its
                # first books (AutoTuner.adopt_rejoin_report), so the
                # overridden keys this server holds stay put
                rep = dict(getattr(self, "_seen_tuning", None) or {})
                ov = getattr(self, "_seen_ring_overrides", None)
                if ov:
                    rep["ring_overrides"] = dict(ov)
                if rep:
                    payload["tuning"] = rep
            send_message(
                conn, Message(Op.REGISTER, payload=json.dumps(payload).encode())
            )
            resp = recv_message(conn)
            if resp.status != 0:
                err = json.loads(resp.payload.decode()).get(
                    "error", "register refused"
                )
                raise RuntimeError(f"scheduler refused registration: {err}")
            book = json.loads(resp.payload.decode())
            if not self._fence_book(book):
                # a zombie scheduler still bound to the address answered;
                # redial — its restarted successor owns the port
                raise ConnectionError("book from a stale scheduler incarnation")
        except BaseException:
            close_socket(conn)
            raise
        if self._sched_conn is not None and self._sched_conn is not conn:
            close_socket(self._sched_conn)  # dead link's fd: don't leak it
        self._sched_conn = conn
        self.rank = book["rank"]
        self._adopt_jobs(book)  # before any round-completion check
        if initial:
            self.num_workers = book["num_workers"]
        else:
            # rejoin mid-training: a stale worker count must complete
            # partial rounds / release now-full barriers, same as a
            # RESIZE book would
            self.update_num_workers(book["num_workers"])
        self._adopt_worker_ranks(book)
        self._adopt_book(book)  # initial ownership map (no keys yet)
        self._note_book(book)
        # cross-process span identity (getattr keeps borrowed use safe;
        # both PSServer and NativePSServer carry a tracer — the native
        # wrapper's is fed by the engine's span-ring drain)
        tracer = getattr(self, "tracer", None)
        if tracer is not None:
            tracer.process_name = f"server{self.rank}"
            tracer.local_rank = f"server{self.rank}"
        return conn

    def _fence_book(self, book: dict) -> bool:
        """Incarnation fence (docs/robustness.md "Control-plane
        recovery"): refuse a book from an OLDER scheduler incarnation
        than one already acted on — a zombie scheduler racing its
        restarted successor must not roll the topology back.  Adopts a
        newer incarnation on accept; unstamped books (older schedulers)
        always pass."""
        from byteps_tpu.core.telemetry import counters

        inc = int(book.get("sched_incarnation", 0) or 0)
        known = int(getattr(self, "sched_incarnation", 0) or 0)
        if inc and known and inc < known:
            counters().bump("sched_stale_book")
            return False
        if inc > known:
            self.sched_incarnation = inc
        return True

    def _note_book(self, book: dict) -> None:
        """Track the newest membership AND map epochs seen — reported
        back on a rejoin re-REGISTER so a reborn scheduler fences above
        them.  The map epoch is tracked independently of the resharding
        feature: even a reshard-off server has OBSERVED the epoch, and
        the successor must never re-emit it."""
        epoch = book.get("epoch")
        if epoch is not None and int(epoch) > getattr(self, "membership_epoch", 0):
            self.membership_epoch = int(epoch)
        me = book.get("map_epoch")
        if me is not None and int(me) >= getattr(self, "_map_epoch", 0):
            self._map_epoch = int(me)
            # newest placement overrides observed: reported back on a
            # rejoin re-REGISTER (with the tuning section below) so a
            # reborn scheduler re-adopts placement instead of migrating
            # every overridden key home on its first book
            self._seen_ring_overrides = dict(
                book.get("ring_overrides") or {}
            )
        t = book.get("tuning")
        if isinstance(t, dict):
            try:
                te = int(t.get("epoch", 0) or 0)
            except (TypeError, ValueError):
                te = 0
            if te >= int(getattr(self, "_seen_tuning_epoch", 0) or 0):
                self._seen_tuning_epoch = te
                self._seen_tuning = dict(t)
        self._adopt_tuning(book)

    def _adopt_tuning(self, book: dict) -> None:
        """Note a book's ``tuning`` section (docs/autotune.md).  The
        server's only fleet-tuned knobs today are placement overrides
        (which ride the ownership fields, adopted in _adopt_book); what
        this arms is the heartbeat **hot-key report** — the rebalance
        policy's input.  Tracks the book, both directions: a book
        WITHOUT the section (tuner toggled off, or a reborn scheduler
        without BYTEPS_AUTOTUNE) disarms, so beats return to the
        byte-identical legacy wire instead of shipping reports nobody
        consumes.  (Re-)arming re-baselines the per-key counters so the
        first report carries only traffic observed under the armed
        tuner, not the accumulated gap."""
        on = isinstance(book.get("tuning"), dict)
        if on and not getattr(self, "_tuning_on", False) and hasattr(
            self, "_keys_lock"
        ):
            with self._keys_lock:
                self._hot_last = {
                    k: ks.req_bytes for k, ks in self._keys.items()
                }
        self._tuning_on = on

    def _hot_report(self):
        """Per-beat hot-key report for the scheduler's autotuner: the
        per-key request-byte DELTAS since the last beat (top 8 + the
        total) and the owned-key count.  Called from the control-plane
        thread only.  Includes redirected traffic on tombstoned keys —
        stale-map chatter IS load this server served."""
        if not getattr(self, "_tuning_on", False):
            return None
        last = getattr(self, "_hot_last", None)
        if last is None:
            last = {}
        with self._keys_lock:
            cur = {k: ks.req_bytes for k, ks in self._keys.items()}
            owned = sum(
                1 for ks in self._keys.values()
                if ks.store is not None and ks.migrated_to is None
            )
        self._hot_last = cur
        if not cur:
            return None
        deltas = {}
        total = 0
        for k, v in cur.items():
            d = v - last.get(k, 0)
            if d > 0:
                deltas[k] = d
                total += d
        top = sorted(deltas.items(), key=lambda kv: -kv[1])[:8]
        return {
            "total": int(total),
            "keys": [[int(k), int(v)] for k, v in top],
            "owned": int(owned),
        }

    def _handle_control(self, conn, msg) -> None:
        from byteps_tpu.comm.rendezvous import RESIZE_SEQ

        if msg.op == Op.ADDRBOOK and msg.seq == RESIZE_SEQ:
            book = json.loads(msg.payload.decode())
            if not self._fence_book(book):
                return  # stale-incarnation book refused (zombie fence)
            self._note_book(book)
            self._adopt_jobs(book)  # membership map BEFORE round checks
            self.update_num_workers(book["num_workers"])
            self._adopt_worker_ranks(book)
            # ownership adoption LAST: a drain book's migration wave
            # (and eventual stop) must see the settled worker count
            self._adopt_book(book)
            return
        if msg.op == Op.SHUTDOWN:
            # elastic scale-down dropped this server from the book;
            # stop serving (stop() joins threads — run it off-thread).
            # Flag first: the ConnectionError below must read as a
            # deliberate exit, not a link loss to reconnect from.
            self._sched_shutdown = True
            threading.Thread(target=self.stop, daemon=True).start()
            raise ConnectionError("scheduler requested shutdown")
        # PING responses and anything else: drained, no action

    def _control_plane_loop(self, conn) -> None:
        """Heartbeat + prompt control-message delivery on one thread:
        select() waits for control traffic between beats, so RESIZE
        books apply within ~0.3s instead of a heartbeat interval.

        Link loss hands off to :meth:`_sched_reconnect` instead of
        exiting — control_plane_degraded mode (docs/robustness.md): the
        data plane keeps serving on the last-adopted book while this
        thread redials and re-REGISTERs, and the first beat to a NEW
        scheduler incarnation ships the FULL metric history (the dead
        scheduler took the delta baselines' aggregate to its grave)."""
        import select as _select

        from byteps_tpu.core.telemetry import metrics

        hb = self.cfg.heartbeat_interval
        beat_incarnation = None
        while not self._stop.is_set():
            next_beat = time.monotonic() + hb if hb > 0 else None
            delta: dict = {}
            pend_ups = None
            try:
                while not self._stop.is_set():
                    now = time.monotonic()
                    if next_beat is not None and now >= next_beat:
                        inc = getattr(self, "sched_incarnation", 0)
                        if inc != beat_incarnation:
                            # new consumer: re-arm the delta baselines so
                            # this beat carries everything (idempotent
                            # per incarnation — in-process fleets share
                            # one registry across several beat loops)
                            metrics().reship_for(inc)
                            beat_incarnation = inc
                        # flight recorder: servers have no training
                        # rounds, so the beat IS the step — one ledger
                        # record per beat gives the hot-stripe and
                        # queue-stall rules a cadence, and the compact
                        # tail rides this beat into the scheduler's
                        # cluster step matrix (docs/observability.md
                        # "Flight recorder & doctor")
                        from byteps_tpu.core.flightrec import (
                            get_process_recorder,
                        )

                        rec = get_process_recorder()
                        if rec is not None and rec.enabled:
                            rec.record_step()
                        # metric deltas piggyback on the beat — the
                        # scheduler aggregates them cluster-wide
                        # (docs/observability.md), same as the workers
                        delta = metrics().delta_snapshot()
                        if rec is not None and rec.enabled:
                            tail = rec.ledger_tail()
                            if tail:
                                delta["fr"] = tail
                            hb_ups = rec.take_uploads()
                            if hb_ups:
                                # fleet-central bundle upload
                                # (BYTEPS_FLIGHT_UPLOAD); failed beats
                                # give these back in the except below
                                delta["fb"] = hb_ups
                                pend_ups = hb_ups
                        # hot-key report (docs/autotune.md): armed only
                        # after a book carried a tuning section — legacy
                        # beats stay byte-identical.  getattr: this loop
                        # is borrowed by NativePSServer, which has no
                        # key table and ships no report (the native
                        # engine cannot migrate state, so the rebalance
                        # policy never considers it).
                        hot_fn = getattr(self, "_hot_report", None)
                        if hot_fn is not None:
                            hot = hot_fn()
                            if hot:
                                delta["hot"] = hot
                        send_message(
                            conn,
                            Message(
                                Op.PING,
                                payload=json.dumps(delta).encode()
                                if delta else b"",
                            ),
                        )
                        delta = {}  # delivered (send_all returned)
                        pend_ups = None
                        next_beat = now + hb
                    readable, _, _ = _select.select([conn], [], [], 0.3)
                    if readable:
                        self._handle_control(conn, recv_message(conn))
            except (ConnectionError, OSError, ValueError):
                # a delta consumed but not delivered rides the next
                # successful beat instead of vanishing
                metrics().requeue_delta(delta)
                if pend_ups:
                    from byteps_tpu.core.flightrec import (
                        get_process_recorder,
                    )

                    fr = get_process_recorder()
                    if fr is not None:
                        fr.requeue_uploads(pend_ups)
                if self._stop.is_set() or getattr(self, "_sched_shutdown", False):
                    return
                conn = self._sched_reconnect()
                if conn is None:
                    return  # terminal: data plane continues on last book

    def _sched_reconnect(self):
        """Redial + re-REGISTER with bounded backoff
        (BYTEPS_SCHED_RECONNECT_RETRIES/_BACKOFF_S); returns the fresh
        control socket, or None once the budget is spent (the legacy
        terminal behavior — the data plane keeps serving)."""
        from byteps_tpu.comm.retry import Backoff
        from byteps_tpu.common import logging as bpslog
        from byteps_tpu.core.telemetry import counters, metrics

        metrics().gauge_set("control_plane_degraded", 1)
        if self.cfg.sched_reconnect_retries <= 0:
            return None  # reconnect disabled: scheduler-link loss is final
        backoff = Backoff(
            base=max(0.05, self.cfg.sched_reconnect_backoff_s), cap=10.0
        )
        for _ in range(self.cfg.sched_reconnect_retries):
            if self._stop.is_set():
                return None
            counters().bump("sched_reconnect")
            try:
                conn = self._sched_register_once(initial=False)
            except (ConnectionError, OSError, RuntimeError, ValueError):
                if self._stop.wait(backoff.next_delay()):
                    return None
                continue
            counters().bump("sched_rejoin")
            metrics().gauge_set("control_plane_degraded", 0)
            return conn
        bpslog.warning(
            "server rank=%s: scheduler reconnect gave up after %d "
            "attempts — control plane down for good (data plane "
            "continues on the last book)",
            self.rank, self.cfg.sched_reconnect_retries,
        )
        return None

    def _adopt_worker_ranks(self, book: dict) -> None:
        """Refresh the zombie fence from a scheduler book.  Books without
        a rank list (older schedulers) disable the fence."""
        ranks = book.get("worker_ranks")
        self._live_worker_flags = (
            {r + 1 for r in ranks if 0 <= r < 255} if ranks is not None
            else None
        )

    # --- multi-tenant plane (docs/async.md) ------------------------------

    def _adopt_jobs(self, book: dict) -> None:
        """Adopt a book's per-job membership + QoS map: each job's
        worker flags size that job's rounds/barriers, its priority
        weights the engine queues, and a declared quota (MB/s) arms the
        admission meter.  Books without a ``jobs`` field (older
        schedulers) leave the single-tenant behavior in place."""
        jobs = book.get("jobs")
        if not isinstance(jobs, dict):
            return
        workers: Dict[int, set] = {}
        qos: Dict[int, dict] = {}
        for raw_job, info in jobs.items():
            try:
                job = int(raw_job)
            except (TypeError, ValueError):
                continue
            flags = {
                r + 1 for r in (info.get("workers") or []) if 0 <= r < 255
            }
            if flags:
                workers[job] = flags
            qos[job] = {
                "priority": max(1, int(info.get("priority", 1) or 1)),
                "quota_mbps": max(
                    0.0, float(info.get("quota_mbps", 0) or 0)
                ),
            }
        self._job_workers = workers
        self._job_qos = qos
        # the WFQ lanes engage only when some tenant actually DECLARED
        # QoS (a priority above the default or a quota): with no
        # declaration the engine queues stay job-blind — byte-fair
        # service is a policy change, and "QoS off" must mean the exact
        # legacy order (the baseline tests/test_multitenant.py's demo runs)
        self._qos_active = any(
            q["priority"] > 1 or q["quota_mbps"] > 0 for q in qos.values()
        )
        # (re-)arm the admission meters; a quota change replaces the
        # bucket (fresh burst window) and a dropped quota disarms it
        quota: Dict[int, _QuotaBucket] = {}
        from byteps_tpu.core.telemetry import metrics

        for job, q in qos.items():
            mbps = q["quota_mbps"]
            if mbps <= 0:
                continue
            old = self._job_quota.get(job)
            quota[job] = (
                old if old is not None and abs(old.rate - mbps * 1e6) < 1.0
                else _QuotaBucket(mbps)
            )
            metrics().gauge_set(
                "server_job_quota_mbps", mbps, labels={"job": str(job)}
            )
        for job in self._job_quota:
            if job not in quota:
                # the job's quota was dropped: the ceiling gauge must
                # go with it, or dashboards keep scoring utilization
                # against a limit that no longer exists
                metrics().gauge_remove(
                    "server_job_quota_mbps", labels={"job": str(job)}
                )
        self._job_quota = quota

    def _job_weight(self, job: int) -> float:
        """WFQ weight of a tenant in the engine queues (the book's
        per-job ``priority``; 1.0 for unknown jobs)."""
        q = self._job_qos.get(job)
        return float(q["priority"]) if q else 1.0

    def _workers_for_ks(self, ks: "_KeyState") -> int:
        """The worker population a key's rounds and init barriers
        complete against: its JOB's registered workers when the book
        carries a membership map, else the fleet total (single-tenant
        behavior)."""
        flags = self._job_workers.get(ks.job)
        return len(flags) if flags else self.num_workers

    def _async_ks(self, ks: "_KeyState") -> bool:
        """Whether a key runs the async profile: its INIT declared it
        (per-key, docs/async.md), or the whole server runs legacy
        ``BYTEPS_ENABLE_ASYNC`` mode."""
        return ks.async_mode or self.cfg.enable_async

    def _min_applied_locked(self, ks: "_KeyState") -> int:
        """The slowest job worker's newest APPLIED push version for an
        async key — what the bounded-staleness gate compares pull
        rounds against.  Workers that never pushed count as version 0.
        Caller holds ``ks.lock``."""
        flags = self._job_workers.get(ks.job)
        if flags:
            return min(ks.push_seen.get(w, 0) for w in flags)
        n = self._workers_for_ks(ks)
        if n <= 0:
            return 0
        vals = sorted(ks.push_seen.values(), reverse=True)[:n]
        vals += [0] * (n - len(vals))
        return min(vals)

    def _staleness_ready_locked(self, ks: "_KeyState", version: int) -> bool:
        """Bounded-staleness gate (docs/async.md): a pull at round
        ``version`` may be served iff every job worker's applied-push
        version is within ``ks.staleness`` rounds of it.  -1 =
        unbounded (pure async); 0 degenerates to sequential
        consistency.  Caller holds ``ks.lock``."""
        if ks.staleness < 0:
            return True
        return self._min_applied_locked(ks) >= version - ks.staleness

    def _flush_async_waiters_locked(self, ks: "_KeyState") -> List:
        """Pulls (and fused pull-halves) parked behind the staleness
        bound whose gate now opens — called after an async push applied
        (the peer push IS the unblocking event) and after a membership
        shrink.  Caller holds ``ks.lock``; returns the flush list."""
        return self._drain_waiters_locked(
            ks, lambda v: self._staleness_ready_locked(ks, v),
            async_mode=True,
        )

    def _drain_waiters_locked(self, ks: "_KeyState", ready,
                              async_mode: bool) -> List:
        """The ONE pending-pull/fused-waiter drain, shared by the sync
        round publish and the async staleness flush — only the
        readiness predicate and the wire-payload mode differ.  A
        malformed row-sparse gather drops THAT puller's connection and
        keeps serving the rest.  Caller holds ``ks.lock``."""
        flush: List = []
        still_pending = []
        for entry in ks.pending_pulls:
            version, pconn, plock, pseq, pcomp, rs_req = entry
            if ready(version):
                try:
                    payload = (
                        self._rowsparse_gather(ks, rs_req)
                        if rs_req is not None
                        else ks.wire_payload(pcomp, async_mode, lend=True)
                    )
                except RuntimeError:
                    close_socket(pconn)
                    continue
                flush.append(
                    (pconn, plock, pseq, payload, ks.store_version)
                )
            else:
                still_pending.append(entry)
        ks.pending_pulls = still_pending
        still_fused = []
        for version, reply, slot, pcomp in ks.fused_waiters:
            if ready(version):
                if reply.fill(
                    slot, ks.wire_payload(pcomp, async_mode),
                    ks.store_version,
                ):
                    flush.append(reply)
            else:
                still_fused.append((version, reply, slot, pcomp))
        ks.fused_waiters = still_fused
        return flush

    # --- elastic resharding (docs/robustness.md "migration flow") --------

    def _adopt_book(self, book: dict) -> None:
        """Adopt a book's ownership map.  A NEWER map epoch starts a
        migration wave: every key this server holds whose new owner is
        another rank is shipped there (store + exactly-once ledger +
        init-token record) over Op.MIGRATE_STATE.  A ``drain`` book
        (scale-down) excludes this server from the rank list, so the wave
        empties the whole store and then stops the server."""
        if not self.reshard or self.rank is None:
            return
        epoch = book.get("map_epoch")
        ranks = book.get("server_ranks")
        if epoch is None or not ranks:
            return
        drain = bool(book.get("drain"))
        servers = [tuple(s) for s in (book.get("servers") or [])]
        from byteps_tpu.common.hashing import OwnershipMap

        with self._own_lock:
            cur = self._ownership
            if cur is not None and int(epoch) <= cur.epoch and not drain:
                return  # stale or repeated book
            new_map = OwnershipMap(
                ranks, epoch=int(epoch), vnodes=self.cfg.ring_vnodes,
                # autotuner rebalance (docs/autotune.md): per-key
                # placement overrides are part of the versioned map —
                # the wave below ships any key the override re-homes
                overrides=book.get("ring_overrides"),
            )
            self._prev_ownership = cur
            self._ownership = new_map
            self._map_epoch = new_map.epoch
            self._peer_addrs = {
                int(r): servers[i]
                for i, r in enumerate(ranks)
                if i < len(servers)
            }
        self._update_owned_gauge()
        # the wave dials peers and ships payloads: off the control thread
        threading.Thread(
            target=self._migrate_wave, args=(new_map, drain),
            name="ps-migrate", daemon=True,
        ).start()

    def _migrate_wave(self, new_map, drain: bool) -> None:
        """Ship every re-homed key to its new owner.  Keys are shipped
        one at a time over a per-destination connection; each key's
        requests are served normally until the instant its state is
        snapshotted (atomically with the tombstone, under the key lock),
        redirected afterwards — the handoff window per key is one RPC,
        not a cluster barrier.  Failed shipments RETRY with backoff —
        on scale-up the destination is typically still coming up when
        the book lands (its listener binds before it registers, but the
        book beats its accept loop by a beat), and giving up would
        strand the key: the new owner parks requests for a migration
        that never comes until the degraded fallback re-creates the key
        from scratch, split-braining it against this server's stale
        copy.  A scale-up wave stops retrying when a newer map
        supersedes it; a drain wave (scale-down book) retries until the
        store is empty, and only then stops the server: stopping with
        unshipped keys would LOSE their state, so a server that cannot
        drain stays up — off the book, still authoritative — until an
        operator (or a later book) resolves it."""
        from byteps_tpu.common import logging as bpslog

        total_moved = 0
        for attempt in range(120 if drain else 40):
            conns: Dict[int, Any] = {}
            moved = failed = 0
            try:
                with self._keys_lock:
                    keys = sorted(self._keys)
                for key in keys:
                    if self._stop.is_set():
                        return
                    if self._ownership is not new_map and not drain:
                        return  # superseded: the newer map's wave owns truth
                    with self._keys_lock:
                        ks = self._keys.get(key)
                    if ks is None:
                        continue
                    owner = (self._ownership or new_map).owner(key)
                    if owner == self.rank:
                        continue
                    ok = self._migrate_key(key, ks, owner, new_map.epoch, conns)
                    if ok:
                        moved += 1
                    elif ok is False:
                        failed += 1
            finally:
                for sock in conns.values():
                    close_socket(sock)
            total_moved += moved
            self._update_owned_gauge()
            if moved or failed:
                bpslog.warning(
                    "server rank=%s migration wave (epoch %d): "
                    "moved=%d failed=%d",
                    self.rank, new_map.epoch, moved, failed,
                )
            if not failed:
                break
            # retry: the destination was unreachable (still coming up,
            # or itself mid-rebuild) — back off and re-ship
            if self._stop.wait(min(2.0, 0.25 * (attempt + 1))):
                return
        if drain and not self._stop.is_set():
            if failed:
                bpslog.warning(
                    "server rank=%s drain INCOMPLETE (%d keys stuck) — "
                    "staying up to preserve their state",
                    self.rank, failed,
                )
                return
            bpslog.warning(
                "server rank=%s drained (%d keys shipped) — stopping",
                self.rank, total_moved,
            )
            self.stop()

    def _migrate_key(self, key: int, ks: _KeyState, owner: int,
                     epoch: int, conns: Dict[int, Any]):
        """Ship ONE key's authoritative state to ``owner``.  Returns True
        (moved), False (failed — this server stays authoritative), or
        None (nothing to ship).  The snapshot and the redirect tombstone
        are taken in one lock section, so every push either lands before
        the snapshot (and ships inside it) or redirects after — no sum is
        ever lost in the window."""
        import struct as _struct

        from byteps_tpu.comm.transport import encode_migrate_state
        from byteps_tpu.core.telemetry import counters, metrics

        addr = self._peer_addrs.get(owner)
        with ks.lock:
            if ks.migrated_to is not None:
                return None  # already shipped by an earlier wave
            pend, ks.pending_pulls = ks.pending_pulls, []
            fusedw, ks.fused_waiters = ks.fused_waiters, []
            initw, ks.init_waiters = ks.init_waiters, []
            if ks.store is None:
                # no state to ship (key never completed an init barrier
                # here) — just strand-proof the parked waiters: their
                # workers chase to the new owner and init THERE
                self._redirect_waiters(key, epoch, owner, pend, fusedw, initw)
                return None
            if addr is None:
                ks.pending_pulls, ks.fused_waiters, ks.init_waiters = (
                    pend, fusedw, initw
                )
                counters().bump("migration_failed")
                return False
            meta = {
                "key": int(key),
                "epoch": int(epoch),
                "dtype": str(ks.dtype),
                "store_version": int(ks.store_version),
                "recv_count": int(ks.recv_count),
                "pushed_total": int(ks.pushed_total),
                "push_seen": {str(w): int(v) for w, v in ks.push_seen.items()},
                "init_done": {str(w): int(v) for w, v in ks.init_done.items()},
                "compressor_kwargs": dict(ks.compressor_kwargs),
                # async profile rides the migration (docs/async.md): the
                # new owner must keep applying pushes immediately and
                # gating pulls on the same staleness bound
                "async_mode": bool(ks.async_mode),
                "staleness": int(ks.staleness),
            }
            store_b = ks.store.tobytes()
            accum_b = ks.accum.tobytes() if ks.recv_count else b""
            meta["store_nbytes"] = len(store_b)
            meta["accum_nbytes"] = len(accum_b)
            # server-side optimizer state moves WITH the store
            # (docs/architecture.md): slot arrays ride as raw tails
            # behind the accumulator (decode_migrate_extra) so the
            # trajectory continues bitwise at the new owner; the codec's
            # pinned (meta, store, accum) 3-tuple is untouched.
            extra_b = b""
            if ks.opt_rule is not None:
                slot_blobs = ks.opt_rule.slot_bytes()
                meta["opt_rule"] = str(ks.opt_rule_name)
                meta["opt_hp"] = dict(ks.opt_hp)
                meta["opt_step"] = int(ks.opt_step)
                meta["opt_seeded"] = sorted(int(w) for w in ks.opt_seeded)
                meta["opt_slot_nbytes"] = [len(b) for b in slot_blobs]
                extra_b = b"".join(slot_blobs)
            # tombstone BEFORE the wire hop: requests from here on get
            # WRONG_OWNER, so no push can mutate state already serialized
            ks.migrated_to = owner
            ks.migrate_epoch = epoch
        # parked waiters chase to the new owner like any stale-map request
        self._redirect_waiters(key, epoch, owner, pend, fusedw, initw)
        t0 = time.time()
        ok = False
        try:
            sock = conns.get(owner)
            if sock is None:
                sock = connect(addr[0], addr[1],
                               timeout=self.cfg.migrate_deadline_s)
                sock.settimeout(max(1.0, self.cfg.migrate_deadline_s))
                conns[owner] = sock
            send_message(sock, Message(
                Op.MIGRATE_STATE, key=key, version=epoch,
                payload=encode_migrate_state(meta, store_b, accum_b)
                + extra_b,
            ))
            resp = recv_message(sock)
            # status 3 = "already authoritative at destination" (an
            # earlier attempt landed but its ack was lost, or the key
            # was re-created there): the key is home — drop our copy
            ok = resp.op == Op.MIGRATE_STATE and resp.status in (0, 3)
        except (ConnectionError, OSError, ValueError, _struct.error) as e:
            from byteps_tpu.common import logging as bpslog

            bpslog.warning(
                "server rank=%s: shipping key %d to rank %s failed: %s",
                self.rank, key, owner, e,
            )
            sock = conns.pop(owner, None)
            close_socket(sock)
        if not ok:
            # roll back: this server stays authoritative (workers that
            # already chased will bounce back through their retry path);
            # a later wave re-attempts the shipment
            with ks.lock:
                ks.migrated_to = None
            counters().bump("migration_failed")
            return False
        with ks.lock:
            # keep the tombstone, free the bulk
            ks.set_buffers(None, None)
            ks.push_seen = {}
            ks.init_done = {}
            ks.pull_payload = None
            ks.pull_version = -1
            ks.raw_payload = None
            ks.raw_version = -1
            ks.compressor = None
            ks.opt_rule = None
            ks.opt_rule_name = None
            ks.opt_hp = {}
            ks.opt_step = 0
            ks.opt_seeded = set()
        counters().bump("migration_keys_moved")
        metrics().observe("migration_key_seconds", time.time() - t0)
        return True

    def _redirect_waiters(self, key: int, epoch: int, owner: int,
                          pending_pulls=(), fused_waiters=(),
                          init_waiters=()) -> None:
        """Answer parked requests of a migrating key with WRONG_OWNER so
        their workers chase to the new owner instead of waiting on state
        that just left this server."""
        from byteps_tpu.comm.transport import encode_wrong_owner

        payload = encode_wrong_owner(epoch, owner)
        for _v, pconn, plock, pseq, _c, _rs in pending_pulls:
            try:
                send_message(pconn, Message(
                    Op.WRONG_OWNER, key=key, seq=pseq, version=epoch,
                    payload=payload,
                ), plock)
            except (ConnectionError, OSError):
                continue
        seen: set = set()
        for _v, reply, _slot, _c in fused_waiters:
            if id(reply) in seen:
                continue
            seen.add(id(reply))
            if reply.abort():
                try:
                    send_message(reply.conn, Message(
                        Op.WRONG_OWNER, key=reply.route_key, seq=reply.seq,
                        version=epoch, payload=payload,
                    ), reply.send_lock)
                except (ConnectionError, OSError):
                    pass
        for _wid, wconn, wlock, wseq, _tok in init_waiters:
            try:
                send_message(wconn, Message(
                    Op.WRONG_OWNER, key=key, seq=wseq, version=epoch,
                    payload=payload,
                ), wlock)
            except (ConnectionError, OSError):
                continue

    def _redirect_locked(self, key: int, ks: Optional[_KeyState]):
        """(epoch, owner) when this server must redirect a request for
        ``key``, else None.  Caller holds ``ks.lock`` (the check must be
        atomic with the summation it gates — the migration wave takes the
        same lock for its snapshot+tombstone).

        A key this server still HOLDS serves normally even when the new
        map re-homes it (the pre-ship window): the wave's snapshot will
        carry those sums.  Redirects fire for shipped keys (tombstone)
        and for keys this server never held under a map that homes them
        elsewhere (a stale-map worker)."""
        if not self.reshard:
            return None
        if ks is not None and ks.migrated_to is not None:
            return (ks.migrate_epoch, ks.migrated_to)
        omap = self._ownership
        if omap is None or self.rank is None:
            return None
        owner = omap.owner(key)
        if owner == self.rank:
            return None
        if ks is not None and ks.store is not None:
            return None  # pre-ship window: still authoritative
        return (omap.epoch, owner)

    def _send_wrong_owner(self, conn, send_lock, msg: Message, ro) -> None:
        from byteps_tpu.comm.transport import encode_wrong_owner
        from byteps_tpu.core.telemetry import counters

        epoch, owner = ro
        counters().bump("wrong_owner_served")
        send_message(conn, Message(
            Op.WRONG_OWNER, key=msg.key, seq=msg.seq, version=epoch,
            payload=encode_wrong_owner(epoch, owner),
        ), send_lock)

    def _should_park(self, key: int) -> bool:
        """True when a request for an uninitialized key should PARK: the
        current map homes the key here and its previous owner is alive,
        so a migration is (or will be) inbound.  False when the previous
        owner was evicted — nothing will ever arrive, and the worker's
        re-init path must own the key's rebirth."""
        if not self.reshard or self.rank is None:
            return False
        omap = self._ownership
        if omap is None or omap.owner(key) != self.rank:
            return False
        prev = self._prev_ownership
        if prev is not None:
            old = prev.owner(key)
            if old != self.rank and old not in omap.ranks:
                return False  # old owner crashed out: state is gone
        return True

    def _park_awaiting(self, key: int, msg: Message, conn, send_lock) -> None:
        """Park one request until the key's migration lands (re-enqueued
        by _handle_migrate) or BYTEPS_MIGRATE_DEADLINE_S expires (the
        sweeper drops the connection back to the worker's retry path)."""
        with self._awaiting_lock:
            self._awaiting.setdefault(key, []).append(
                (time.monotonic(), msg, conn, send_lock)
            )
            if self._awaiting_sweeper is None:
                t = threading.Thread(
                    target=self._awaiting_sweep_loop,
                    name="ps-migrate-park", daemon=True,
                )
                self._awaiting_sweeper = t
                t.start()

    def _awaiting_sweep_loop(self) -> None:
        while not self._stop.wait(0.25):
            cutoff = time.monotonic() - max(0.5, self.cfg.migrate_deadline_s)
            doomed: List = []
            with self._awaiting_lock:
                for key in list(self._awaiting):
                    keep = []
                    for entry in self._awaiting[key]:
                        (doomed if entry[0] < cutoff else keep).append(entry)
                    if keep:
                        self._awaiting[key] = keep
                    else:
                        del self._awaiting[key]
            for _t, _msg, conn, _sl in doomed:
                # migration never landed: hand the request back to the
                # worker's retry/heal path via a dropped connection
                close_socket(conn)

    def _handle_migrate(self, msg: Message, conn, send_lock) -> None:
        """Op.MIGRATE_STATE: install one key's authoritative state from
        its old owner, ack, and wake any requests parked on the key.
        Idempotent under sender retry (a same-epoch duplicate with an
        older store_version acks without clobbering newer local state),
        and ordered by MIGRATION EPOCH across events: a newer-epoch
        shipment installs over tombstoned remains — store_version
        counters are NOT comparable across init generations (a key
        re-created from scratch restarts its numbering), so cross-event
        ordering rides the epoch, while an older-epoch straggler never
        clobbers newer state or clears a newer tombstone.  A key that
        is already LIVE here is refused-as-complete (status 3) — see
        the inline comment."""
        import struct as _struct

        from byteps_tpu.comm.transport import decode_migrate_state
        from byteps_tpu.core.telemetry import counters

        if not self.reshard:
            send_message(conn, Message(
                Op.MIGRATE_STATE, key=msg.key, seq=msg.seq, status=1,
            ), send_lock)
            return
        try:
            meta, store_b, accum_b = decode_migrate_state(msg.payload)
            key = int(meta["key"])
            epoch = int(meta.get("epoch", msg.version))
            dtype = np.dtype(str(meta["dtype"]))
            store_version = int(meta.get("store_version", 0))
            extra_b = b""
            if meta.get("opt_rule"):
                from byteps_tpu.comm.transport import decode_migrate_extra

                extra_b = decode_migrate_extra(msg.payload, meta)
        except (KeyError, ValueError, TypeError, UnicodeDecodeError,
                _struct.error):
            close_socket(conn)  # malformed control frame: drop, like resync
            return
        omap = self._ownership
        if (omap is not None and self.rank is not None
                and omap.epoch > epoch and omap.owner(key) != self.rank):
            # the sender's map is OLDER than ours and the key belongs
            # elsewhere under the current one: refuse — the sender's next
            # wave (it will adopt our epoch's book too) re-ships it to
            # the right owner, instead of us installing state we would
            # immediately have to forward
            send_message(conn, Message(
                Op.MIGRATE_STATE, key=key, seq=msg.seq, status=2,
            ), send_lock)
            return
        ks = self._key_state(key)
        already_home = False
        with ks.lock:
            if ks.store is not None and ks.migrated_to is None:
                # the key is already LIVE here.  In every in-order
                # migration the receiver holds nothing or a tombstone —
                # live state means this shipment is a duplicate (the
                # first attempt landed but its ack was lost/slow), a
                # late chaos-delayed frame, or a stale copy trying to
                # resurrect itself over a key the degraded fallback
                # re-created here (whose version numbering restarted, so
                # store_version comparisons against it are meaningless —
                # installing would serve stale rounds to every pull).
                # Refuse-as-complete: status 3 tells the sender the key
                # is home — drop your copy, keep your tombstone.
                already_home = True
            else:
                self._install_migrated_locked(
                    ks, epoch, dtype, store_version, meta, store_b, accum_b,
                    extra_b,
                )
        if already_home:
            send_message(conn, Message(
                Op.MIGRATE_STATE, key=key, seq=msg.seq, status=3,
            ), send_lock)
            return
        counters().bump("migration_keys_received")
        send_message(conn, Message(
            Op.MIGRATE_STATE, key=key, seq=msg.seq,
        ), send_lock)
        with self._awaiting_lock:
            parked = self._awaiting.pop(key, [])
        for _t, m, c, sl in parked:
            # metered=True: these requests were accounted (and
            # admission-delayed) on their ORIGINAL arrival — the
            # migration park must not charge the tenant twice
            self._enqueue(m, c, sl, metered=True)
        self._update_owned_gauge()

    def _install_migrated_locked(self, ks: _KeyState, epoch: int, dtype,
                                 store_version: int, meta: dict,
                                 store_b: bytes, accum_b: bytes,
                                 extra_b: bytes = b"") -> None:
        """Install one migrated key state under ``ks.lock`` (split out of
        :meth:`_handle_migrate` so the reply never rides inside the key
        lock).  Ordering rules in the caller's docstring."""
        prev_epoch = ks.migrate_epoch
        if epoch < prev_epoch:
            # straggling duplicate of an OLDER migration event: ack (the
            # sender's retry completes) but leave newer local state —
            # and any newer tombstone — untouched
            return
        ks.migrated_to = None  # the key lives here now
        ks.migrate_epoch = epoch
        if (ks.store is None or epoch > prev_epoch
                or store_version >= ks.store_version):
            ks.dtype = dtype
            store = np.frombuffer(store_b, dtype=dtype).copy()
            ks.set_buffers(
                store,
                np.frombuffer(accum_b, dtype=dtype).copy()
                if accum_b else np.zeros_like(store),
            )
            ks.store_version = store_version
            ks.recv_count = int(meta.get("recv_count", 0))
            ks.pushed_total = int(meta.get("pushed_total", 0))
            ks.push_seen = {
                int(w): int(v)
                for w, v in (meta.get("push_seen") or {}).items()
            }
            ks.init_done = {
                int(w): int(v)
                for w, v in (meta.get("init_done") or {}).items()
            }
            ks.compressor_kwargs = {
                str(k): str(v)
                for k, v in (meta.get("compressor_kwargs") or {}).items()
            }
            if meta.get("async_mode"):
                ks.async_mode = True
                ks.staleness = max(-1, int(meta.get("staleness", -1)))
            # server-side optimizer state: rebuild the rule and reload
            # its slots from the raw tail so the trajectory continues
            # bitwise at this owner (tests/test_reshard.py pins it)
            ks.opt_rule = None
            ks.opt_rule_name = None
            ks.opt_hp = {}
            ks.opt_step = 0
            ks.opt_seeded = set()
            if meta.get("opt_rule"):
                from byteps_tpu.server import update_rules

                hp = meta.get("opt_hp") or {}
                rule = update_rules.make_rule(
                    meta["opt_rule"], hp, store.size, dtype
                )
                blobs: List[bytes] = []
                off = 0
                for nb in meta.get("opt_slot_nbytes") or ():
                    blobs.append(extra_b[off : off + int(nb)])
                    off += int(nb)
                rule.load_slot_bytes(blobs)
                ks.opt_rule = rule
                ks.opt_rule_name = str(meta["opt_rule"])
                ks.opt_hp = dict(hp)
                ks.opt_step = int(meta.get("opt_step", 0))
                ks.opt_seeded = {
                    int(w) for w in (meta.get("opt_seeded") or ())
                }
            ks.compressor = None
            if ks.compressor_kwargs:
                from byteps_tpu.compression.registry import create_compressor

                ks.compressor = create_compressor(
                    ks.compressor_kwargs, store.size, server=True
                )
                _apply_lr_to_chain(ks.compressor, self._ef_lr)
            ks.pull_payload = None
            ks.pull_version = -1
            ks.raw_payload = None
            ks.raw_version = -1

    def _update_owned_gauge(self) -> None:
        """``server_owned_keys`` / ``server_map_epoch`` gauges, labeled
        by rank — heartbeat deltas carry them to the scheduler aggregate
        so tools/bps_top.py can watch a migration settle."""
        if not self.reshard or self.rank is None:
            return
        from byteps_tpu.core.telemetry import metrics

        with self._keys_lock:
            states = list(self._keys.values())
        n = sum(
            1 for ks in states
            if ks.store is not None and ks.migrated_to is None
        )
        labels = {"rank": str(self.rank)}
        metrics().gauge_set("server_owned_keys", n, labels=labels)
        omap = self._ownership
        if omap is not None:
            metrics().gauge_set("server_map_epoch", omap.epoch, labels=labels)

    # --- connection plane ------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            if conn.family == socket.AF_INET:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            from byteps_tpu.comm.shaping import maybe_shape

            conn = maybe_shape(conn)  # response direction of a shaped link
            t = threading.Thread(target=self._serve_conn, args=(conn,), daemon=True)
            t.start()
            self._threads.append(t)

    def _serve_conn(self, conn: socket.socket) -> None:
        send_lock = threading.Lock()
        try:
            self._serve_conn_loop(conn, send_lock)
        finally:
            # close on every exit path: a plain socket would be GC'd, but
            # a ShapedSocket is pinned by its delivery thread until
            # close() — without this every shaped connection leaks a
            # thread + fd.  Engine threads racing a late response into
            # the closed conn already tolerate the OSError.
            try:
                conn.close()
            except OSError:
                pass

    def _serve_conn_loop(self, conn: socket.socket, send_lock) -> None:
        from byteps_tpu.comm.transport import (
            ChecksumError,
            LosslessError,
            checksum_conn_limit,
        )

        ck_limit = checksum_conn_limit()
        ck_fails = 0
        # this connection's receive buffers: a pushed partition lands in
        # memory the process already holds (transport.FramePool)
        pool = FramePool()
        # this thread's wall clock (tracing.thread_account, kind "serve"):
        # idle = blocked for the next header, service = header parsed →
        # frame enqueued or answered (payload receive, integrity, _enqueue)
        account = thread_account("serve")
        try:
            while not self._stop.is_set():
                header = recv_header_ex(conn)
                account.begin()
                try:
                    if not self._serve_frame(conn, send_lock, header, pool):
                        return
                except (ChecksumError, LosslessError) as e:
                    # end-to-end wire integrity (docs/robustness.md "Wire
                    # integrity"): a flipped payload bit that survived
                    # TCP's checksum, or a lossless container that failed
                    # to decode.  The frame is fully consumed, so DROP it
                    # without a reply — the worker's deadline/retry + the
                    # exactly-once ledger heal it bitwise, never a silent
                    # wrong-bytes install — and escalate repeated
                    # corruption to a connection drop so the client
                    # revives (possibly bad NIC/path).
                    from byteps_tpu.core.telemetry import counters

                    ck_fails += 1
                    name = ("wire_lossless_fail"
                            if isinstance(e, LosslessError)
                            else "wire_checksum_fail")
                    counters().bump(name, labels={
                        "side": "server",
                        "op": getattr(e.op, "name", str(e.op)),
                    })
                    if ck_limit and ck_fails >= ck_limit:
                        counters().bump("wire_checksum_conn_drop")
                        return
                finally:
                    account.end()
        except (ConnectionError, OSError):
            return
        finally:
            account.close()

    def _serve_frame(self, conn, send_lock, header: tuple,
                     pool: FramePool) -> bool:
        """One frame on its connection's serve thread, from the parsed
        header on: receive the rest and enqueue it for its engine thread or
        answer it here; False when the peer said SHUTDOWN."""
        msg = recv_body(conn, header, pool)
        if msg.op in (Op.PUSH, Op.PULL, Op.INIT, Op.FUSED):
            self._enqueue(msg, conn, send_lock)
        elif msg.op == Op.RESYNC_QUERY:
            # recovery plane (docs/robustness.md): answered inline —
            # a read-mostly snapshot of the exactly-once ledger,
            # and the asking worker is stalled on it
            self._handle_resync(msg, conn, send_lock)
        elif msg.op == Op.MIGRATE_STATE:
            # resharding plane: a peer server ships one key's
            # authoritative state — installed inline (the sender
            # blocks on the ack, and parked requests wake here)
            self._handle_migrate(msg, conn, send_lock)
        elif msg.op == Op.REGISTER_COMPRESSOR and msg.flags & 1:
            # lr update for every EF chain (flag bit 0; payload =
            # big-endian f64) — the wire replacement for the
            # reference's lr.s mmap (vanilla_error_feedback.h:44-58).
            # Malformed sizes are acked and ignored like the C++
            # engine (ps_server.cc payload.size()==8 guard)
            import struct as _struct

            if len(msg.payload) == 8:
                (lr,) = _struct.unpack("!d", msg.payload)
                self._ef_lr = lr  # late-registered chains inherit it
                with self._keys_lock:
                    chains = [ks.compressor for ks in self._keys.values()]
                for c in chains:
                    _apply_lr_to_chain(c, lr)
            send_message(conn, Message(Op.REGISTER_COMPRESSOR, seq=msg.seq), send_lock)
        elif msg.op == Op.REGISTER_COMPRESSOR:
            # compressor registration init-push (server.cc:228-257);
            # server chain skips momentum (compressor_registry.cc:44);
            # payload is key=value lines (shared with the C++ server)
            from byteps_tpu.compression.registry import create_compressor

            ks = self._key_state(msg.key)
            kwargs = dict(
                ln.split("=", 1)
                for ln in msg.payload.decode().splitlines() if "=" in ln
            )
            with ks.lock:
                ks.compressor_kwargs = kwargs
                size = ks.store.size if ks.store is not None else 0
                ks.compressor = create_compressor(kwargs, size, server=True)
                _apply_lr_to_chain(ks.compressor, self._ef_lr)
            send_message(conn, Message(Op.REGISTER_COMPRESSOR, seq=msg.seq), send_lock)
        elif msg.op == Op.METRICS:
            # observability plane (docs/observability.md "One scrape, both
            # ends"): this process's registry as it stands, for the worker
            # whose get_metrics() asked; a read, so the heartbeat's delta
            # ships what it would have shipped
            from byteps_tpu.core.telemetry import metrics

            snapshot = metrics().snapshot(
                labels={"role": "server", "rank": str(self.rank)})
            send_message(conn, Message(Op.METRICS, seq=msg.seq,
                                       payload=json.dumps(snapshot).encode()),
                         send_lock)
        elif msg.op == Op.PING:
            send_message(conn, Message(Op.PING, seq=msg.seq), send_lock)
        elif msg.op == Op.SHUTDOWN:
            send_message(conn, Message(Op.SHUTDOWN, seq=msg.seq), send_lock)
            return False
        return True

    def _child_span(self, trace, key: int, name: str, t0: float,
                    dur: float, **extra) -> None:
        """One server-side child span joined to a worker span: same trace
        id, parent = the wire-propagated worker span id.  ``trace`` is
        the (trace_id, parent_span_id) pair off the frame; no-op for
        untraced frames or a disabled tracer."""
        if trace is None or not (self.tracer.enabled and self.tracer.spans_enabled):
            return
        from byteps_tpu.core.tracing import new_trace_id, span_args

        self.tracer.record_span(
            f"key{key}", name, t0, dur,
            span_args(trace[0], new_trace_id(), parent_id=trace[1], **extra),
        )

    def _key_state(self, key: int) -> _KeyState:
        from byteps_tpu.common.tenancy import job_of_key

        with self._keys_lock:
            ks = self._keys.get(key)
            if ks is None:
                ks = self._keys[key] = _KeyState()
                ks.job = job_of_key(key)
            return ks

    def _thread_for(self, key: int, length: int) -> int:
        with self._tid_lock:
            tid = self._tid_cache.get(key)
            if tid is None:
                tid = int(np.argmin(self._tid_load))
                self._tid_cache[key] = tid
            self._tid_load[tid] += length
            return tid

    def _enqueue(self, msg: Message, conn, send_lock,
                 metered: bool = False) -> None:
        ks = self._key_state(msg.key)
        ks.req_bytes += len(msg.payload)  # hot-key load surface
        job = ks.job
        if job and not metered:
            # per-tenant accounting + admission (docs/async.md): the
            # job's data-plane bytes feed the utilization surface, and
            # a declared quota DELAYS excess requests (token bucket) —
            # INIT/control frames never meter (a barrier must not
            # starve behind a bulk push backlog)
            from byteps_tpu.core.telemetry import counters

            labels = {"job": str(job)}
            counters().bump("server_job_requests", labels=labels)
            counters().bump(
                "server_job_bytes", len(msg.payload), labels=labels
            )
            bucket = self._job_quota.get(job)
            if bucket is not None and msg.op != Op.INIT:
                delay = bucket.reserve(len(msg.payload))
                if delay > 0:
                    # admission BACKPRESSURE, not a parked copy: hold
                    # this connection's serve thread (a data conn is
                    # single-tenant) so the overloaded job's own frame
                    # stream throttles — exactly a slower link.  A
                    # parked-copy design double-charged the bucket when
                    # a client deadline/retry re-sent the frame and
                    # accumulated duplicate payloads server-side; here
                    # overload self-clocks (the sleep throttles
                    # arrivals, so per-frame delay stays ~one
                    # serialization slot) and dedupe semantics are the
                    # plain retry path's.
                    counters().bump("job_quota_deferred", labels=labels)
                    if self._stop.wait(delay):
                        return
        tid = self._thread_for(msg.key, len(msg.payload))
        # anti-starvation: fewest accumulated pushes first (queue.h:49-97).
        # The wall-clock stamp bounds the "recv" child span: engine-queue
        # dwell is part of the server-side latency a worker observes.
        self._queues[tid].put(
            ks.pushed_total, (msg, conn, send_lock, time.time()),
            job=job if self._qos_active else 0, cost=len(msg.payload),
        )


    # --- engine plane ----------------------------------------------------

    def _engine_loop(self, q: _EngineQueue) -> None:
        # this thread's wall clock (tracing.thread_account, kind "engine"):
        # service = dequeued → handler returned (sum, publish, the reply's
        # send_message, _flush_pulls), idle = its queue empty
        account = thread_account("engine")
        try:
            while not self._stop.is_set():
                item = q.get(timeout=self._POLL_S)
                if item is None:
                    account.tick()
                    continue
                account.begin()
                try:
                    self._engine_serve(*item)
                finally:
                    account.end()
        finally:
            account.close()

    def _engine_serve(self, msg: Message, conn, send_lock, t_enq) -> None:
        """One dequeued frame on its engine thread."""
        try:
            if msg.op == Op.INIT:
                self._handle_init(msg, conn, send_lock)
            elif msg.op == Op.PUSH:
                self._handle_push(msg, conn, send_lock, t_enq)
            elif msg.op == Op.PULL:
                self._handle_pull(msg, conn, send_lock, t_enq)
            elif msg.op == Op.FUSED:
                self._handle_fused(msg, conn, send_lock, t_enq)
        except (ConnectionError, OSError):
            pass
        except Exception as e:  # noqa: BLE001
            # A malformed request (truncated compressed payload, skewed
            # dtype, out-of-range topk index, …) must never kill the
            # engine thread — every key pinned to it would stop being
            # served.  Drop the offending connection, mirroring the
            # native server's malformed-payload handling.
            from byteps_tpu.common import logging as bpslog

            bpslog.warning(
                "dropping connection after malformed request key=%d op=%d: %r",
                msg.key, int(msg.op), e,
            )
            close_socket(conn)  # FIN even while the serve thread recvs

    def _handle_init(self, msg: Message, conn, send_lock) -> None:
        """Init push = allocate + cross-worker barrier (server.cc:266-295).
        Payload: u64 nelems + u32 dtype, network order — plus the
        OPTIONAL async-profile extension (docs/async.md): u8 profile
        (bit 0 = async) + i32 staleness bound.  Sync keys never send
        the extension, so pre-async decoders (and the native C++
        engine, which rejects it) see the classic 12-byte frame."""
        import struct

        n, dtype_id = struct.unpack_from("!QI", msg.payload, 0)
        async_profile = False
        staleness = -1
        opt_declared = False
        opt_name: Optional[str] = None
        opt_hp: Dict[str, Any] = {}
        if len(msg.payload) >= 17:
            profile, staleness = struct.unpack_from("!Bi", msg.payload, 12)
            async_profile = bool(profile & 1)
            # bit 1: the server-side optimizer profile — rule name +
            # canonical-JSON hyperparams follow at offset 17
            # (transport.decode_server_opt_block).  A malformed block is
            # a status=1 rejection, never a silent downgrade to SUM.
            if profile & 2:
                from byteps_tpu.comm.transport import decode_server_opt_block
                from byteps_tpu.server import update_rules

                try:
                    opt_name, hp_raw = decode_server_opt_block(
                        msg.payload, 17
                    )
                    opt_hp = update_rules.parse_hp(hp_raw)
                    opt_declared = True
                except ValueError as exc:
                    self._reject_server_opt(msg, conn, send_lock, exc)
                    return
        ks = self._key_state(msg.key)
        wid = msg.flags
        token = msg.version
        created = False
        with ks.lock:
            # per-key async profile + staleness bound, adopted from
            # EVERY init: a re-init generation that drops the extension
            # returns the key to sync semantics (KeyState outlives
            # client shutdown()/init() cycles, so a sticky flag would
            # leave a nominally-sync rerun training async).  Every job
            # worker's INIT carries the same declaration (the env /
            # declare kwargs are job-wide), so last-writer-wins is
            # deterministic.
            ks.async_mode = async_profile
            ks.staleness = max(-1, int(staleness)) if async_profile else -1
            redirect = self._redirect_locked(msg.key, ks)
            if redirect is None and ks.store is None:
                created = True
                dtype = to_numpy_dtype(DataType(dtype_id))
                ks.dtype = dtype
                ks.set_buffers(np.zeros(n, dtype=dtype),
                               np.zeros(n, dtype=dtype))
            # server-opt profile, adopted from EVERY init like async_mode
            # above: a re-init without the extension returns the key to
            # plain SUM semantics.  Same (rule, hp) keeps the live slots
            # and step count across re-init barriers (elastic resizes
            # re-declare every key); a changed config rebuilds from
            # zero-state — documented in docs/architecture.md.
            if redirect is None:
                if opt_declared:
                    from byteps_tpu.server import update_rules

                    if not update_rules.same_config(
                        ks.opt_rule, opt_name, opt_hp
                    ):
                        try:
                            ks.opt_rule = update_rules.make_rule(
                                opt_name, opt_hp, len(ks.store), ks.dtype
                            )
                        except ValueError as exc:
                            ks.opt_rule = None
                            ks.opt_rule_name = None
                            ks.opt_hp = {}
                            ks.opt_step = 0
                            ks.opt_seeded = set()
                            self._reject_server_opt(
                                msg, conn, send_lock, exc
                            )
                            return
                        ks.opt_rule_name = opt_name
                        ks.opt_hp = dict(opt_hp)
                        ks.opt_step = 0
                        ks.opt_seeded = set()
                elif ks.opt_rule is not None:
                    ks.opt_rule = None
                    ks.opt_rule_name = None
                    ks.opt_hp = {}
                    ks.opt_step = 0
                    ks.opt_seeded = set()
            # init-idempotency (docs/robustness.md): a replayed INIT whose
            # barrier already COMPLETED — the retry of a dropped ack after
            # the barrier released — is acked from the completed-barrier
            # record.  Parking it would strand the worker: its peers were
            # released and will never re-init this key, so the barrier
            # stays short until the retry budget dies.
            if redirect is not None:
                replay_ack = False
                waiters = None
            elif wid and token and ks.init_done.get(wid) == token:
                from byteps_tpu.core.telemetry import counters

                counters().bump("init_replay_ack")
                replay_ack = True
            else:
                replay_ack = False
                # keyed by worker identity: a REPLAYED init (retry after a
                # lost ack / torn connection) replaces this worker's waiter
                # entry — appending it again would double-count one worker
                # and release the barrier short.  Anonymous inits (wid 0)
                # keep appending.
                entry = (wid, conn, send_lock, msg.seq, token)
                if wid:
                    for i, w in enumerate(ks.init_waiters):
                        if w[0] == wid:
                            ks.init_waiters[i] = entry
                            break
                    else:
                        ks.init_waiters.append(entry)
                else:
                    ks.init_waiters.append(entry)
                waiters = self._complete_init_barrier_locked(ks)
        if redirect is not None:
            # the map homes this key elsewhere: the worker's init chases
            # to the new owner (state, if any, migrated there)
            self._send_wrong_owner(conn, send_lock, msg, redirect)
            return
        if created:
            self._update_owned_gauge()
        if replay_ack:
            send_message(
                conn, Message(Op.INIT, key=msg.key, seq=msg.seq), send_lock
            )
            return
        if waiters is None:
            return
        self._release_init_waiters(msg.key, waiters)

    def _reject_server_opt(self, msg: Message, conn, send_lock, exc) -> None:
        """status=1 INIT rejection for a server-opt profile this engine
        cannot honor (unknown rule, non-floating store, torn block) —
        the client raises with the why; never a silent SUM downgrade."""
        from byteps_tpu.common import logging as bpslog
        from byteps_tpu.core.telemetry import counters

        counters().bump("server_opt_reject")
        bpslog.warning(
            "rejecting server-opt INIT for key %d: %s", msg.key, exc
        )
        try:
            send_message(
                conn,
                Message(Op.INIT, key=msg.key, seq=msg.seq, status=1),
                send_lock,
            )
        except (ConnectionError, OSError):
            pass

    def _complete_init_barrier_locked(self, ks: "_KeyState"):
        """If the key's init barrier is full, consume it and reset the
        round state; returns the waiters to release, or None if the
        barrier is still short.  The barrier completes against the
        key's JOB population (docs/async.md) — a tenant's init must
        never wait for another job's workers.  Caller holds ks.lock."""
        if not (0 < self._workers_for_ks(ks) <= len(ks.init_waiters)):
            return None
        waiters, ks.init_waiters = ks.init_waiters, []
        # record each waiter's init token: a retried INIT landing AFTER
        # this release is acked from the record instead of re-parked
        # (dropped-ack idempotency, see _handle_init).  The ledger is
        # REPLACED, not merged — tokens from an older generation must not
        # false-ack a new generation's genuine barrier.
        ks.init_done = {
            w[0]: w[4] for w in waiters if w[0] and w[4]
        }
        # A completed init barrier (re-)establishes round numbering:
        # after an elastic resize/resume EVERY worker re-inits and
        # restarts versions at 1 (ReDeclareTensor semantics,
        # global.cc:431-436), so stale sync-round state from the
        # previous generation must not gate the new sequence.  Store
        # CONTENTS survive (async parameter store across resume).
        ks.store_version = 0
        ks.recv_count = 0
        ks.pending_pulls = []
        # parked fused pull-halves are from the abandoned generation too —
        # their frames' round numbering no longer matches (same policy as
        # pending_pulls: dropped, the worker's retry/deadline path owns it)
        ks.fused_waiters = []
        # the new generation restarts versions at 1, so the replay
        # ledger from the previous generation must not mark its
        # first-round pushes as duplicates
        ks.push_seen = {}
        # round caches are stamped with version numbers that the
        # new generation will REUSE — a stale cache would serve
        # the previous generation's bytes as the new round
        ks.pull_payload = None
        ks.pull_version = -1
        ks.raw_payload = None
        ks.raw_version = -1
        return waiters

    @staticmethod
    def _release_init_waiters(key: int, waiters) -> None:
        for _wid, wconn, wlock, wseq, _token in waiters:
            try:
                send_message(wconn, Message(Op.INIT, key=key, seq=wseq), wlock)
            except (ConnectionError, OSError):
                # one dead waiter (it may be mid-retry on a fresh
                # connection) must not strand the releases behind it
                continue

    @staticmethod
    def _parse_rowsparse(payload: bytes, dtype, with_values: bool):
        """RS wire format (kRowSparsePushPull, common.h:267-271): header
        ``!II`` (nrows, row_len) + nrows big-endian u32 row indices
        [+ nrows*row_len values in the key's dtype, native order — same
        byte order as dense payloads]."""
        import struct

        nrows, row_len = struct.unpack_from("!II", payload, 0)
        idx = np.frombuffer(payload, dtype=">u4", count=nrows, offset=8).astype(
            np.int64
        )
        if not with_values:
            return nrows, row_len, idx, None
        vals = np.frombuffer(
            payload, dtype=dtype, count=nrows * row_len, offset=8 + 4 * nrows
        ).reshape(nrows, row_len)
        return nrows, row_len, idx, vals

    def _is_replayed_push_locked(self, ks: "_KeyState", msg: Message) -> bool:
        """Exactly-once summation under client retry (caller holds
        ks.lock).  The ledger holds (worker → newest SUMMED version); per
        (key, worker) versions are strictly increasing (engine round
        gate), so an arriving version <= the record is a retransmit whose
        original WAS summed — ack it, don't re-sum.  Anonymous pushes
        (flags 0: legacy callers, ranks ≥ 255) are never deduped.

        Read-only: the caller records via :meth:`_record_push_locked`
        only AFTER the summation succeeded — recording first would mark a
        push whose sum then RAISED as already-summed, and its retry would
        be falsely acked (lost contribution).

        Also the zombie fence: a push from a worker the scheduler has
        EVICTED (rank absent from the latest book's live set) raises —
        the engine loop drops the connection, so a stalled-but-alive
        worker cannot pollute rounds sized for the shrunken membership;
        it learns of its expulsion through the dropped connection."""
        wid = msg.flags
        if not wid or msg.version <= 0:
            return False
        live = self._live_worker_flags
        if live is not None and wid not in live:
            raise RuntimeError(
                f"push from evicted worker (flag {wid}, key {msg.key})"
            )
        if msg.version <= ks.push_seen.get(wid, 0):
            from byteps_tpu.core.telemetry import counters

            counters().bump("push_dedup")
            return True
        return False

    @staticmethod
    def _record_push_locked(ks: "_KeyState", msg: Message) -> None:
        """Mark (worker, version) as summed — call under ks.lock, after
        the summation completed without raising."""
        if msg.flags and msg.version > 0:
            ks.push_seen[msg.flags] = msg.version

    def _reply_writer(self, conn) -> _ConnWriter:
        """The connection's reply writer, created (or replaced after a
        reap/death) lazily."""
        key = id(conn)
        with self._writers_lock:
            w = self._writers.get(key)
            if w is None or w.dead:
                # opportunistic sweep: idle-reaped / dead-conn writers
                # must not accumulate for the life of the server (one
                # per connection ever seen, under reconnect churn)
                for k in [k for k, ww in self._writers.items() if ww.dead]:
                    del self._writers[k]
                w = self._writers[key] = _ConnWriter()
            return w

    def _send_reply(self, conn, msg: Message, send_lock) -> None:
        """Send one engine-thread reply.  QoS active → routed through
        the connection's writer so a slow tenant's socket never blocks
        the shared engine thread (docs/async.md); otherwise the classic
        inline send, bit-identical single-tenant behavior.  A payload
        that is a lent view of its key's store (``wire_payload(...,
        lend=True)``) is handed back when the send is over, sent or not."""
        if not self._qos_active:
            self._send_lent(conn, msg, send_lock)
            return
        self._submit_reply(
            conn, lambda: self._send_lent(conn, msg, send_lock),
            len(msg.payload) + 64,
        )

    def _send_lent(self, conn, msg: Message, send_lock) -> None:
        try:
            send_message(conn, msg, send_lock)
        finally:
            if isinstance(msg.payload, memoryview):
                self._key_state(msg.key).give_back(msg.payload)

    def _submit_reply(self, conn, fn, nbytes: int) -> None:
        """Queue one reply closure on the conn's writer, replacing a
        writer that died/reaped between lookup and submit (the reply
        must not vanish into a dead thread — the peer would wait out a
        whole deadline for nothing)."""
        if not self._reply_writer(conn).submit(fn, nbytes):
            self._reply_writer(conn).submit(fn, nbytes)

    def _flush_pulls(self, key: int, flush: List) -> None:
        """Answer flushed pending pulls — 5-tuples for plain pulls,
        :class:`_FusedReply` objects for completed fused frames —
        tolerating dead pullers: one torn connection (its worker is
        already re-pulling on a fresh one) must not strand the responses
        queued behind it.  Under QoS the sends ride each connection's
        reply writer (tenant response isolation)."""
        for entry in flush:
            try:
                if isinstance(entry, _FusedReply):
                    if self._qos_active:
                        self._submit_reply(
                            entry.conn, entry.send,
                            sum(len(s) for s in entry.slots if s) + 64,
                        )
                    else:
                        entry.send()
                    continue
                pconn, plock, pseq, payload, ver = entry
                self._send_reply(
                    pconn,
                    Message(Op.PULL, key=key, payload=payload, seq=pseq,
                            version=ver),
                    plock,
                )
            except (ConnectionError, OSError):
                continue

    def _sum_push_locked(self, ks: "_KeyState", msg: Message,
                         compressed: bool, arr) -> None:
        """One (sub-)push's summation under ``ks.lock`` — shared by the
        plain PUSH and fused paths so both stay behaviorally identical:
        async mode sums into the live store; sync mode COPY_FIRSTs /
        SUM_RECVs into the accumulator.  Records the replay-ledger entry
        only AFTER the summation succeeded (a sum that raises must leave
        the retry eligible)."""
        is_async = self._async_ks(ks)
        if not is_async and ks.recv_count == 0:
            ks.own_accum()  # the round's first write is below
        if is_async:
            if ks.opt_rule is not None:
                # async server-opt: the rule fires per push (no round
                # barrier to average at); the SSP gate then bounds the
                # PARAMETER version a pull may observe.  Each worker's
                # FIRST push carries its initial params (the
                # DistributedOptimizer seed contract) — the first copy
                # is adopted verbatim, later seeds are identical and
                # dropped, and a rejoiner (already in the ledger) goes
                # straight back to gradient pushes.
                grad = (
                    ks.compressor.decompress(msg.payload, ks.store.size)
                    if compressed else arr
                )
                wid = msg.flags
                if wid not in ks.opt_seeded:
                    if not ks.opt_seeded:
                        ks.store[:] = grad
                    ks.opt_seeded.add(wid)
                else:
                    ks.opt_step += 1
                    ks.opt_rule.apply(ks.store, grad, 1, ks.opt_step)
                    from byteps_tpu.core.telemetry import counters

                    counters().bump("server_opt_updates")
                ks.store_version += 1
            elif compressed:
                # async mode: parameter store, sum deltas in place
                # (server.cc:315-319)
                ks.compressor.sum_into(msg.payload, ks.store)
                ks.store_version += 1
            else:
                with releasing():
                    self._reducer(ks.store, arr)
                ks.store_version += 1
        elif ks.opt_rule is not None and ks.opt_step == 0:
            # sync server-opt seed round: every worker pushes the SAME
            # initial params; adopt the first copy VERBATIM — an
            # average of N identical float32 copies is not bitwise the
            # original ((N*x)/N rounds), and the seed must be bitwise
            # the worker's initial state for trajectory parity.
            if ks.recv_count == 0:
                if compressed:
                    ks.accum[:] = ks.compressor.decompress(
                        msg.payload, ks.accum.size
                    )
                else:
                    ks.accum[: len(arr)] = arr
            ks.recv_count += 1
        elif compressed:
            # decompress-then-sum (server.cc:92-118)
            if ks.recv_count == 0:
                ks.accum[:] = ks.compressor.decompress(msg.payload, ks.accum.size)
            else:
                ks.compressor.sum_into(msg.payload, ks.accum)
            ks.recv_count += 1
        elif ks.recv_count == 0:
            # numpy copies a partition, and the reducer sums one, without
            # the GIL: releasing calls both (tracing.releasing)
            with releasing():
                ks.accum[: len(arr)] = arr  # COPY_FIRST (server.cc:296)
            ks.recv_count += 1
        else:
            with releasing():
                self._reducer(ks.accum, arr)  # SUM_RECV
            ks.recv_count += 1
        ks.pushed_total += 1
        self._record_push_locked(ks, msg)

    def _handle_push(self, msg: Message, conn, send_lock,
                     t_enq: Optional[float] = None) -> None:
        ks = self._key_state(msg.key)
        rtype, dtype_id = decode_command_type(msg.cmd)
        if rtype == RequestType.ROW_SPARSE_PUSH_PULL:
            return self._handle_push_rowsparse(msg, conn, send_lock, ks)
        if self._debug:
            # per-request key log (BYTEPS_SERVER_DEBUG, server.cc:120-144)
            from byteps_tpu.common import logging as bpslog

            bpslog.info(
                "server push key=%d len=%d v=%d recv=%d/%d",
                msg.key, len(msg.payload), msg.version, ks.recv_count + 1,
                self.num_workers,
            )
        compressed = (
            rtype == RequestType.COMPRESSED_PUSH_PULL and ks.compressor is not None
        )
        arr = None
        if not compressed:
            arr = np.frombuffer(msg.payload, dtype=to_numpy_dtype(DataType(dtype_id)))
        from byteps_tpu.core.telemetry import metrics

        t_start = time.time()
        if t_enq is not None:
            # engine-queue dwell: the frame's wait between the serve
            # thread and this engine thread
            self._child_span(msg.trace, msg.key, "recv", t_enq,
                             t_start - t_enq)
        flush: List = []
        dedupe = False
        published = 0.0
        with ks.lock:
            redirect = self._redirect_locked(msg.key, ks)
            if redirect is None and ks.store is None:
                if self._should_park(msg.key):
                    # migration inbound: hold the push until the state
                    # lands (re-enqueued by _handle_migrate), bounded by
                    # the park sweeper's deadline
                    self._park_awaiting(msg.key, msg, conn, send_lock)
                    return
                # RuntimeError (not ConnectionError): the engine loop's
                # generic handler DROPS the connection so the worker errors
                # out instead of waiting forever for an ack (matches the
                # native server's return-false-drop)
                raise RuntimeError(f"push for uninitialized key {msg.key}")
            if redirect is not None:
                pass  # replied below, outside the lock
            elif self._is_replayed_push_locked(ks, msg):
                dedupe = True  # ack-only (below): the original was summed
            elif self._async_ks(ks):
                self._sum_push_locked(ks, msg, compressed, arr)
                # this push may be the one a staleness-parked pull was
                # waiting on — the "unblocks on peer push" contract
                # (docs/async.md)
                flush.extend(self._flush_async_waiters_locked(ks))
            else:
                self._sum_push_locked(ks, msg, compressed, arr)
                if ks.recv_count >= self._workers_for_ks(ks):
                    p0 = time.time()
                    flush.extend(self._publish_round_locked(ks, compressed))
                    published = time.time() - p0
        if redirect is not None:
            self._send_wrong_owner(conn, send_lock, msg, redirect)
            return
        # the push's last holder lets go here: its bytes are in the
        # accumulator (or were there already: a replay), and ``arr`` was
        # the only view of them.  A parked push returned above, frame
        # in hand; a codec's payload is left to its reference count
        arr = None
        if not compressed:
            release_frame(msg.payload)
        t_summed = time.time()
        sum_dur = (t_summed - t_start) - published
        metrics().observe("server_sum_seconds", max(0.0, sum_dur))
        self._child_span(msg.trace, msg.key, "sum", t_start,
                         max(0.0, sum_dur), dedupe=dedupe)
        if published:
            metrics().observe("server_publish_seconds", published)
            self._child_span(msg.trace, msg.key, "publish",
                             t_summed - published, published)
        self._send_reply(conn, Message(Op.PUSH, key=msg.key, seq=msg.seq, version=msg.version), send_lock)
        self._child_span(msg.trace, msg.key, "reply", t_summed,
                         time.time() - t_summed)
        self._flush_pulls(msg.key, flush)

    def _handle_fused(self, msg: Message, conn, send_lock,
                      t_enq: Optional[float] = None) -> None:
        """Op.FUSED: unpack one multi-key fused frame, run every sub-push
        through the per-(worker, key) exactly-once ledger, and answer with
        ONE multi-key reply once every member's round is published.

        Frame-level retry safety falls out per key: the frame carries one
        worker flag and each member its own round version, so a
        retransmitted frame (lost reply, deadline teardown) re-sums
        nothing whose original landed — dedupe is atomic per member key,
        partial processing included (members summed before a mid-frame
        error are ledger-recorded; the retry skips exactly those).

        The pull halves that cannot answer yet (peer workers still owe
        their round) park as ``fused_waiters`` on each key; round publish
        fills them, and the LAST filled slot queues the one reply frame."""
        from byteps_tpu.comm.transport import decode_fused_push, decode_fused_spans

        members = decode_fused_push(msg.payload)
        if not members:
            raise RuntimeError("empty fused frame")
        if self._debug:
            from byteps_tpu.common import logging as bpslog

            bpslog.info(
                "server fused frame keys=%d bytes=%d v0=%d",
                len(members), len(msg.payload), members[0][2],
            )
        # member span ids from the fused body's optional trailer: each
        # member's "sum" child span parents onto ITS worker-side span
        # (the pack's own span rides the outer header and bounds recv)
        member_spans = decode_fused_spans(msg.payload) if msg.trace else None
        t_start = time.time()
        if t_enq is not None:
            self._child_span(msg.trace, msg.key, "recv", t_enq,
                             t_start - t_enq, keys=len(members))
        from byteps_tpu.core.telemetry import metrics

        reply = _FusedReply(
            conn, send_lock, msg.seq, msg.key, [m[0] for m in members]
        )
        for slot, (key, cmd, version, payload) in enumerate(members):
            ks = self._key_state(key)
            rtype, dtype_id = decode_command_type(cmd)
            if rtype == RequestType.ROW_SPARSE_PUSH_PULL:
                raise RuntimeError("row-sparse members cannot fuse")
            sub = Message(
                Op.PUSH, key=key, payload=payload, cmd=cmd,
                version=version, flags=msg.flags,
            )
            compressed = (
                rtype == RequestType.COMPRESSED_PUSH_PULL
                and ks.compressor is not None
            )
            arr = None
            if not compressed:
                arr = np.frombuffer(
                    payload, dtype=to_numpy_dtype(DataType(dtype_id))
                )
            flush: List = []
            dedupe = False
            published = 0.0
            park = False
            t_m0 = time.time()
            with ks.lock:
                redirect = self._redirect_locked(key, ks)
                if redirect is None and ks.store is None:
                    if self._should_park(key):
                        park = True
                    else:
                        raise RuntimeError(
                            f"push for uninitialized key {key}"
                        )
                if redirect is None and not park:
                    is_async = self._async_ks(ks)
                    if self._is_replayed_push_locked(ks, sub):
                        dedupe = True
                    else:
                        self._sum_push_locked(ks, sub, compressed, arr)
                        if is_async:
                            flush.extend(
                                self._flush_async_waiters_locked(ks)
                            )
                        elif ks.recv_count >= self._workers_for_ks(ks):
                            p0 = time.time()
                            flush.extend(
                                self._publish_round_locked(ks, compressed)
                            )
                            published = time.time() - p0
                    # this member's pull half: answered now if its round
                    # is published (async mode: when within the
                    # staleness bound), else parked on the key
                    if (
                        self._staleness_ready_locked(ks, version)
                        if is_async else version <= ks.store_version
                    ):
                        if reply.fill(
                            slot,
                            ks.wire_payload(compressed, is_async),
                            ks.store_version,
                        ):
                            flush.append(reply)
                    else:
                        ks.fused_waiters.append(
                            (version, reply, slot, compressed)
                        )
            if redirect is not None or park:
                # abandon the FRAME: members already summed are in the
                # exactly-once ledger, so the worker's unfuse-fallback
                # replay (or the frame's later re-enqueue) re-sums
                # nothing — the handoff stays exactly-once per member.
                # abort() fences the reply so fused_waiters parked by
                # earlier members can never answer the resolved seq —
                # and only the abort WINNER answers it out of band (the
                # migration wave's _redirect_waiters races this path for
                # the same frame; a loser sending too would put two
                # responses on one seq and corrupt the client's demux).
                if reply.abort():
                    if redirect is not None:
                        self._send_wrong_owner(conn, send_lock, msg, redirect)
                    else:
                        self._park_awaiting(key, msg, conn, send_lock)
                return
            t_m1 = time.time()
            sum_dur = max(0.0, (t_m1 - t_m0) - published)
            metrics().observe("server_sum_seconds", sum_dur)
            if published:
                metrics().observe("server_publish_seconds", published)
            if msg.trace is not None:
                # parent on the MEMBER's worker span when the trailer
                # carried one; the pack span otherwise
                parent = (
                    member_spans[slot]
                    if member_spans is not None else msg.trace[1]
                )
                self._child_span(
                    (msg.trace[0], parent), key, "sum", t_m0, sum_dur,
                    dedupe=dedupe, fused=True,
                )
                if published:
                    self._child_span(
                        (msg.trace[0], parent), key, "publish",
                        t_m1 - published, published, fused=True,
                    )
            self._flush_pulls(key, flush)
        # every member was a copy out of the frame (decode_fused_push
        # slices a bytearray) and every one is summed: the frame is
        # consumed.  A parked or redirected frame returned above, whole
        release_frame(msg.payload)
        # no unconditional "reply" span here: the ONE fused reply leaves
        # when its last member's round publishes — which may be this call
        # (flushed above) or a later worker's push entirely

    def _handle_push_rowsparse(self, msg: Message, conn, send_lock, ks) -> None:
        """Row-sparse push (RequestType::kRowSparsePushPull,
        common.h:267-271): scatter-sum (indices, values) rows into the
        dense store — the embedding-gradient path.  Round semantics match
        the dense path: one push per worker per round; rows untouched by
        every worker aggregate to zero for that round."""
        flush: List = []
        with ks.lock:
            redirect = self._redirect_locked(msg.key, ks)
            if redirect is None and ks.store is None:
                if self._should_park(msg.key):
                    self._park_awaiting(msg.key, msg, conn, send_lock)
                    return
                raise RuntimeError(f"push for uninitialized key {msg.key}")
            if redirect is not None:
                pass  # replied below, outside the lock
            else:
                self._sum_rowsparse_locked(ks, msg, flush)
        if redirect is not None:
            self._send_wrong_owner(conn, send_lock, msg, redirect)
            return
        self._send_reply(
            conn, Message(Op.PUSH, key=msg.key, seq=msg.seq, version=msg.version),
            send_lock,
        )
        self._flush_pulls(msg.key, flush)

    def _sum_rowsparse_locked(self, ks, msg: Message, flush: List) -> None:
        """One row-sparse push's summation under ``ks.lock`` (split out of
        :meth:`_handle_push_rowsparse` so the resharding redirect check
        can gate it like the dense path)."""
        nrows, row_len, idx, vals = self._parse_rowsparse(
            msg.payload, ks.dtype, with_values=True
        )
        if row_len == 0 or ks.store.size % row_len:
            raise RuntimeError(
                f"rowsparse row_len {row_len} does not divide "
                f"store size {ks.store.size} (key {msg.key})"
            )
        total_rows = ks.store.size // row_len
        if nrows and int(idx.max()) >= total_rows:
            raise RuntimeError(
                f"rowsparse index {int(idx.max())} >= {total_rows} rows"
            )
        if self._is_replayed_push_locked(ks, msg):
            pass  # ack-only: the original scatter-sum already landed
        elif self._async_ks(ks):
            # async parameter store: scatter deltas in place
            np.add.at(ks.store.reshape(total_rows, row_len), idx, vals)
            ks.store_version += 1
            ks.pushed_total += 1
            self._record_push_locked(ks, msg)
            flush.extend(self._flush_async_waiters_locked(ks))
        else:
            if ks.recv_count == 0:
                # sparse COPY_FIRST: rows this worker does NOT touch
                # must start the round at zero, not last round's sum
                ks.own_accum()
                ks.accum[:] = 0
            # np.add.at accumulates duplicate indices correctly
            np.add.at(ks.accum.reshape(total_rows, row_len), idx, vals)
            ks.recv_count += 1
            ks.pushed_total += 1
            self._record_push_locked(ks, msg)
            if ks.recv_count >= self._workers_for_ks(ks):
                flush.extend(self._publish_round_locked(ks, False))

    def _rowsparse_gather(self, ks: "_KeyState", req_payload: bytes) -> bytes:
        """Serve an RS pull: gather the requested rows from the store."""
        nrows, row_len, idx, _ = self._parse_rowsparse(
            req_payload, ks.dtype, with_values=False
        )
        if row_len == 0 or ks.store.size % row_len:
            raise RuntimeError(f"rowsparse pull row_len {row_len} invalid")
        total_rows = ks.store.size // row_len
        if nrows and int(idx.max()) >= total_rows:
            raise RuntimeError("rowsparse pull index out of range")
        return ks.store.reshape(total_rows, row_len)[idx].tobytes()

    def _publish_round_locked(self, ks: "_KeyState", compressed: bool) -> List:
        """ALL_RECV: publish the round, flush buffered pulls
        (server.cc:348-375).  Caller holds ks.lock; returns the flush list.

        Server-opt keys publish PARAMETERS, not sums: the rule fires
        here, exactly once per completed round — replayed pushes were
        deduped before they could re-count toward the barrier
        (_is_replayed_push_locked), so a retry storm can never fire the
        rule twice for one round.  The fused path funnels into this
        same hook, so fusion composes for free."""
        if ks.opt_rule is not None and not self._async_ks(ks):
            if ks.opt_step == 0:
                # seed round: accum holds the workers' (identical)
                # initial params verbatim — adopt them as the store
                ks.publish_swap()
            else:
                # accum = raw gradient sum; averaging happens inside
                # the rule (same float op order as the worker engine's
                # _finalize divide — the low bits are the contract)
                ks.opt_rule.apply(
                    ks.store, ks.accum, self._workers_for_ks(ks),
                    ks.opt_step,
                )
                from byteps_tpu.core.telemetry import counters

                counters().bump("server_opt_updates")
            ks.opt_step += 1
        else:
            ks.publish_swap()
        ks.store_version += 1
        ks.recv_count = 0
        if compressed:
            # compress the merged result once per round for pull responses
            # (server.cc:348-370)
            ks.pull_payload = ks.compressor.compress(ks.store)
            ks.pull_version = ks.store_version
        # answer buffered pulls + fill parked fused reply slots (a fill
        # that COMPLETES its frame queues the whole reply for send)
        return self._drain_waiters_locked(
            ks, lambda v: v <= ks.store_version, async_mode=False,
        )

    def update_num_workers(self, n: int) -> None:
        """Adopt a resized worker population (elastic scale-up/down).  A
        round that already has >= n pushes completes immediately — on
        scale-down the departed workers' contributions will never arrive.
        Likewise an init barrier that is now full releases immediately:
        survivors blocked in the init RPC must not wait forever for an
        evicted worker's INIT."""
        self.num_workers = n
        for key, ks in list(self._keys.items()):
            with ks.lock:
                waiters = self._complete_init_barrier_locked(ks)
            if waiters:
                self._release_init_waiters(key, waiters)
        for key, ks in list(self._keys.items()):
            flush: List = []
            with ks.lock:
                if ks.store is None:
                    pass
                elif self._async_ks(ks):
                    # a membership shrink can open the staleness gate
                    # (the departed worker no longer counts toward the
                    # slowest-peer minimum)
                    flush = self._flush_async_waiters_locked(ks)
                elif 0 < self._workers_for_ks(ks) <= ks.recv_count:
                    flush = self._publish_round_locked(
                        ks, ks.compressor is not None
                    )
            self._flush_pulls(key, flush)

    def _handle_resync(self, msg: Message, conn, send_lock) -> None:
        """Op.RESYNC_QUERY (docs/robustness.md "healing flow"): report the
        authoritative per-key round/ledger state so a worker that
        exhausted its retries can compute exactly which journaled pushes
        this server never absorbed — ``seen`` is the newest version of
        THAT worker's pushes in the exactly-once ledger, so the worker
        replays only versions above it and pulls what it missed.  Pure
        read; the replayed pushes themselves go through the normal PUSH
        path (ledger dedupe, zombie fence, round publish) unchanged."""
        import struct as _struct

        from byteps_tpu.comm.transport import (
            decode_resync_query,
            encode_resync_state,
        )

        t0 = time.time()
        try:
            wid, keys = decode_resync_query(msg.payload)
        except (ValueError, UnicodeDecodeError, _struct.error):
            # malformed recovery frame: drop the connection, same policy
            # as a malformed data-plane request (the worker's heal path
            # sees the death and retries or falls back)
            close_socket(conn)
            return
        if not keys:
            with self._keys_lock:
                keys = list(self._keys)
        out = {}
        for key in keys:
            with self._keys_lock:
                ks = self._keys.get(key)
            if ks is None:
                continue
            with ks.lock:
                if ks.store is None:
                    continue
                out[key] = {
                    "store_version": ks.store_version,
                    "seen": ks.push_seen.get(wid, 0) if wid else 0,
                    "recv_count": ks.recv_count,
                    "init": True,
                }
        send_message(
            conn,
            Message(Op.RESYNC_STATE, key=msg.key, seq=msg.seq,
                    payload=encode_resync_state(out)),
            send_lock,
        )
        # the heal's server-side half joins the worker's resync span on
        # the merged Perfetto timeline (docs/observability.md)
        self._child_span(msg.trace, msg.key, "resync", t0,
                         time.time() - t0, keys=len(out))

    def _handle_pull(self, msg: Message, conn, send_lock,
                     t_enq: Optional[float] = None) -> None:
        ks = self._key_state(msg.key)
        rtype, _ = decode_command_type(msg.cmd)
        wants_compressed = rtype == RequestType.COMPRESSED_PUSH_PULL
        rowsparse = rtype == RequestType.ROW_SPARSE_PUSH_PULL
        t_start = time.time()
        if t_enq is not None:
            self._child_span(msg.trace, msg.key, "recv", t_enq,
                             t_start - t_enq)
        with ks.lock:
            redirect = self._redirect_locked(msg.key, ks)
            if redirect is None and ks.store is None:
                if self._should_park(msg.key):
                    self._park_awaiting(msg.key, msg, conn, send_lock)
                    return
                raise RuntimeError(f"pull for uninitialized key {msg.key}")
            is_async = self._async_ks(ks)
            if redirect is not None:
                ready = False  # replied below (never parked on this key)
            elif is_async:
                # async profile: current state, gated only by the
                # bounded-staleness window (docs/async.md) — a pull past
                # the bound parks until the lagging peer's push applies
                ready = self._staleness_ready_locked(ks, msg.version)
            else:
                ready = msg.version <= ks.store_version
            if redirect is not None:
                pass
            elif ready:
                payload = (
                    self._rowsparse_gather(ks, msg.payload)
                    if rowsparse
                    else ks.wire_payload(wants_compressed, is_async,
                                         lend=True)
                )
                ver = ks.store_version
            else:
                # parked: the round publish answers it; the worker-side
                # PULL span keeps the whole wait attributable, so no
                # server span is stamped for the park itself
                ks.pending_pulls.append(
                    (msg.version, conn, send_lock, msg.seq, wants_compressed,
                     msg.payload if rowsparse else None)
                )
                return
        if redirect is not None:
            self._send_wrong_owner(conn, send_lock, msg, redirect)
            return
        t_ready = time.time()
        self._send_reply(
            conn, Message(Op.PULL, key=msg.key, payload=payload, seq=msg.seq, version=ver), send_lock
        )
        self._child_span(msg.trace, msg.key, "reply", t_ready,
                         time.time() - t_ready)


class NativePSServer:
    """Python control shell around the C++ data plane (ps_server.cc).

    The C++ engine owns the worker-facing socket (framing, KV rounds,
    compression, summation — no GIL); this wrapper does what ps-lite's van
    does for the reference server: scheduler registration, the init
    barrier, and heartbeats.  Enable with ``BYTEPS_SERVER_NATIVE=1``.
    """

    def __init__(self, cfg: Config, host: str = "127.0.0.1") -> None:
        import os as _os

        from byteps_tpu.comm.shaping import shaping_enabled, warn_native_bypass_once

        if shaping_enabled():
            # directly-constructed native server under shaping env: honor
            # the explicit choice but say the link will be half-shaped
            warn_native_bypass_once(
                "NativePSServer responses bypass the shaper (half-shaped link)"
            )
        van = _os.environ.get("BYTEPS_VAN", "tcp")
        # chaos:<inner> composes with the native engine: the engine
        # listens on the INNER van and the published address carries the
        # chaos+ prefix, so dialing workers wrap their side in the fault
        # layer (comm/chaos.py).  Injection is client-side only — the
        # C++ response direction stays clean, same one-sidedness the
        # 2-worker demo uses deliberately (docs/robustness.md).
        chaos = van.startswith("chaos:")
        if chaos:
            van = van[len("chaos:"):]
        if van not in ("tcp", "uds", "shm"):
            raise RuntimeError(
                f"BYTEPS_VAN={van!r} unknown; native engine speaks "
                "tcp | uds | shm (or chaos:<those>)"
            )
        from byteps_tpu.native import get_lib

        lib = get_lib()
        if lib is None:
            raise RuntimeError(
                "native server requested but libbyteps_tpu.so unavailable "
                "(make -C byteps_tpu/native)"
            )
        if van != "tcp" and not hasattr(lib, "bps_native_server_start_unix"):
            raise RuntimeError(
                f"BYTEPS_VAN={van!r} needs a rebuilt native lib "
                "(make -C byteps_tpu/native)"
            )
        self._lib = lib
        self.cfg = cfg
        self._uds_path: Optional[str] = None
        if van == "tcp":
            self.host = host
            self.port = lib.bps_native_server_start(
                0, cfg.num_worker, int(cfg.enable_async)
            )
            self._id = self.port
        else:
            # same published-address scheme as the Python server's vans:
            # clients dial the right transport from the address alone
            import tempfile
            import uuid

            from byteps_tpu.comm.van import SHM_PREFIX, UNIX_PREFIX, _check_shm_arch

            if van == "shm":
                _check_shm_arch()
            base = _os.environ.get("BYTEPS_SOCKET_PATH", tempfile.gettempdir())
            path = _os.path.join(
                base, f"byteps_native_{_os.getpid()}_{uuid.uuid4().hex[:8]}.sock"
            )
            self._id = lib.bps_native_server_start_unix(
                path.encode(), cfg.num_worker, int(cfg.enable_async),
                int(van == "shm"),
            )
            self._uds_path = path
            self.host = (SHM_PREFIX if van == "shm" else UNIX_PREFIX) + path
            self.port = 0
        if self._id < 0:
            raise RuntimeError("bps_native_server_start failed")
        if chaos:
            from byteps_tpu.comm.van import CHAOS_PREFIX

            self.host = CHAOS_PREFIX + self.host
        self.rank: Optional[int] = None
        self.num_workers = cfg.num_worker
        self._live_worker_flags: Optional[set] = None
        # multi-tenant book state (the borrowed _adopt_jobs writes these;
        # the C++ data plane itself rejects job-namespaced frames)
        self._job_workers: Dict[int, set] = {}
        self._job_qos: Dict[int, dict] = {}
        self._job_quota: Dict[int, "_QuotaBucket"] = {}
        self._stop = threading.Event()
        self._sched_conn: Optional[socket.socket] = None
        # control-plane recovery state (docs/robustness.md) — same
        # surface as PSServer; the borrowed control-plane methods below
        # read/write these
        self.sched_incarnation = 0
        self.membership_epoch = 0
        self._map_epoch = 0
        self._sched_shutdown = False
        self._metrics_http = None
        from byteps_tpu.common.config import resolve_node_uid

        self.node_uid = resolve_node_uid()
        # merge the engine's counters into the process scrape surface
        # (get_robustness_counters / Prometheus families / heartbeat
        # deltas) so GIL-free runs aren't metrics-blind
        from byteps_tpu.core.telemetry import counters, metrics
        from byteps_tpu.native import (
            native_server_counters,
            native_server_histograms,
            native_server_set_trace,
        )

        sid = self._id
        self._counters_provider = lambda: native_server_counters(sid)
        counters().register_provider(self._counters_provider)
        # …and the engine's histograms (per-key sum latency / request
        # sizes, publish latency) through the histogram-provider seam —
        # native_* families land in get_metrics(), Prometheus, and the
        # heartbeat cluster aggregate (docs/observability.md)
        self._hist_provider = lambda: native_server_histograms(sid)
        metrics().register_hist_provider(self._hist_provider)
        # per-stripe task backlog of the key-striped reducer plane, one
        # gauge series per reducer (docs/fusion.md hot-stripe note): a
        # persistently deep stripe while its siblings idle means the key
        # hash is aliasing hot keys onto one reducer.  Sampled lazily at
        # exposition time; the stripe closures share one short-lived
        # snapshot so a scrape costs one ctypes read, not one per stripe.
        # The `server` label keys the series to THIS instance — benches
        # run several NativePSServers in one process (scaling_bench
        # threads mode), and unlabeled series would overwrite each other
        # at registration and tear each other down at stop().
        from byteps_tpu.native import native_server_stripe_depths

        self._stripe_count = len(native_server_stripe_depths(sid))
        self._gauge_labels = {"server": str(sid)}
        depth_cache = {"t": 0.0, "depths": ()}
        depth_mu = threading.Lock()

        def _stripe_depth(i: int) -> float:
            now = time.monotonic()
            with depth_mu:
                if now - depth_cache["t"] > 0.05:
                    depth_cache["depths"] = native_server_stripe_depths(sid)
                    depth_cache["t"] = now
                depths = depth_cache["depths"]
            return float(depths[i]) if i < len(depths) else 0.0

        for i in range(self._stripe_count):
            metrics().gauge_fn(
                "native_stripe_queue_depth",
                lambda i=i: _stripe_depth(i),
                labels={"stripe": str(i), **self._gauge_labels},
            )
        # span plane (docs/observability.md): the C++ engine stamps the
        # same recv→sum→publish→reply child spans the Python server
        # does, buffered in a native ring; this wrapper drains them into
        # a process tracer that writes the same server<rank>/comm.json
        # file tools/trace_merge.py stitches.
        from byteps_tpu.core.tracing import Tracer, get_process_tracer, set_process_tracer

        self.tracer = Tracer(
            enabled=cfg.trace_on,
            trace_dir=cfg.trace_dir,
            local_rank="server",
            process_name="server",
            spans_enabled=cfg.trace_spans,
        )
        if get_process_tracer() is None:
            set_process_tracer(self.tracer)
        # flight recorder: same surface as PSServer — the borrowed
        # control loop stamps one beat record per heartbeat (the native
        # hot-stripe gauges/histograms above are exactly what its
        # hot_stripe rule reads)
        from byteps_tpu.core.flightrec import ensure_process_recorder

        ensure_process_recorder(
            cfg, context_fn=self._flight_context, tracer=self.tracer
        )
        native_server_set_trace(sid, cfg.trace_on and cfg.trace_spans)
        self._span_drain_thread: Optional[threading.Thread] = None
        if cfg.trace_on and cfg.trace_spans:
            self._span_drain_thread = threading.Thread(
                target=self._span_drain_loop, name="bps-native-span-drain",
                daemon=True,
            )
            self._span_drain_thread.start()

    def _drain_spans_once(self) -> int:
        """Replay the engine's buffered child-span records into the
        tracer.  Child span ids are minted HERE (nothing references
        them — children parent onto the wire-propagated worker span
        ids, server.py _child_span parity), so the C++ side never needs
        an id generator.  ``engine: "native"`` tags each span so
        ``trace_merge.py --critical-path`` can attribute per engine."""
        from byteps_tpu.core.tracing import new_trace_id, span_args
        from byteps_tpu.native import (
            NATIVE_SPAN_KINDS,
            SPAN_FLAG_DEDUPE,
            SPAN_FLAG_FUSED,
            native_server_drain_spans,
        )

        recs = native_server_drain_spans(self._id)
        for rec in recs:
            kind = int(rec["kind"])
            name = (
                NATIVE_SPAN_KINDS[kind]
                if 0 <= kind < len(NATIVE_SPAN_KINDS) else f"kind{kind}"
            )
            flags = int(rec["flags"])
            extra = {"engine": "native", "key": int(rec["key"])}
            if name == "sum":
                extra["dedupe"] = bool(flags & SPAN_FLAG_DEDUPE)
            if flags & SPAN_FLAG_FUSED:
                extra["fused"] = True
            # each reducer stripe gets its own Perfetto thread lane so
            # the merged timeline shows per-reducer occupancy (a hot
            # stripe is one crowded lane); serve/control-thread spans
            # (stripe -1: fused decode, resync answers) keep the per-key
            # rows the pre-striping engine used
            stripe = int(rec["stripe"])
            if stripe >= 0:
                track = f"stripe{stripe}"
                extra["stripe"] = stripe
            else:
                track = f"key{int(rec['key'])}"
            self.tracer.record_span(
                track, name, float(rec["ts"]), float(rec["dur"]),
                span_args(int(rec["trace"]), new_trace_id(),
                          parent_id=int(rec["parent"]), **extra),
            )
        return len(recs)

    def _span_drain_loop(self) -> None:
        while not self._stop.wait(0.1):
            try:
                self._drain_spans_once()
            except Exception:  # noqa: BLE001 — the observer must not die loudly
                return

    def native_counters(self) -> dict:
        """This instance's engine-side counters (``native_*`` names) —
        also merged into :func:`byteps_tpu.get_robustness_counters`."""
        from byteps_tpu.native import native_server_counters

        return native_server_counters(self._id)

    def update_num_workers(self, n: int) -> None:
        """Adopt a resized worker population in the C++ engine (the beat
        thread calls this on RESIZE_SEQ books, as for the Python server)."""
        self.num_workers = n
        self._lib.bps_native_server_set_num_workers(self._id, n)

    def _adopt_worker_ranks(self, book: dict) -> None:
        """Refresh the zombie fence from a scheduler book, mirrored into
        the C++ engine (per-push live-rank checks run natively).  Books
        without a rank list disable the fence, as on the Python server."""
        PSServer._adopt_worker_ranks(self, book)  # type: ignore[arg-type]
        import ctypes as _ct

        flags = self._live_worker_flags
        if flags is None:
            self._lib.bps_native_server_set_live_workers(self._id, None, -1)
            return
        arr = (_ct.c_uint8 * max(1, len(flags)))(*sorted(flags))
        self._lib.bps_native_server_set_live_workers(
            self._id, arr, len(flags)
        )

    def _adopt_book(self, book: dict) -> None:
        """Ship a book's ownership map into the C++ engine (docs/
        robustness.md "migration flow"): the ring's sorted (point, rank)
        arrays plus this server's rank and the map epoch.  The engine
        then answers WRONG_OWNER for keys the map homes elsewhere — the
        split-brain guard for map-epoch skew — but it cannot export or
        import key state, so a drain book (scale-down) is REFUSED loudly:
        stopping would lose every held key, and elastically resharded
        fleets should run Python-engine servers (ROADMAP)."""
        if not self.cfg.elastic_reshard or self.rank is None:
            return
        epoch = book.get("map_epoch")
        ranks = book.get("server_ranks")
        if epoch is None or not ranks:
            return
        from byteps_tpu.common import logging as bpslog

        if book.get("drain"):
            bpslog.warning(
                "native server rank=%s received a DRAIN book but cannot "
                "migrate state — staying up to preserve it (use "
                "Python-engine servers with BYTEPS_ELASTIC_RESHARD)",
                self.rank,
            )
            return
        if book.get("ring_overrides") and not getattr(
            self, "_warned_overrides", False
        ):
            # the C++ ownership check is ring-only; it cannot ship or
            # receive key state either, so the tuner's rebalance policy
            # never sources or targets native ranks (they send no hot
            # reports) — this fires only in unsupported mixed fleets
            self._warned_overrides = True
            bpslog.warning(
                "native server rank=%s: book carries ring_overrides "
                "(autotune rebalance) which the C++ engine cannot honor "
                "— run Python-engine servers with BYTEPS_AUTOTUNE "
                "rebalance (docs/autotune.md)", self.rank,
            )
        if not hasattr(self._lib, "bps_native_server_set_ownership"):
            bpslog.warning(
                "native lib predates the resharding plane; ownership "
                "map not adopted (rebuild byteps_tpu/native)"
            )
            return
        import ctypes as _ct

        from byteps_tpu.common.hashing import HashRing

        pts = HashRing(ranks, vnodes=self.cfg.ring_vnodes).points()
        n = len(pts)
        hashes = (_ct.c_uint64 * n)(*[h for h, _ in pts])
        rks = (_ct.c_int32 * n)(*[r for _, r in pts])
        self._lib.bps_native_server_set_ownership(
            self._id, int(self.rank), int(epoch) & 0xFFFFFFFF, n,
            hashes, rks,
        )
        if int(epoch) > self._map_epoch:
            self._map_epoch = int(epoch)  # reported on rejoin re-REGISTER

    # control-plane machinery shared with the Python server — this class
    # is a wrapper around the C++ engine, not a PSServer subclass, so the
    # reconnect/fence/register helpers are borrowed as unbound methods
    # (they only touch the state surface both classes carry)
    _register_with_scheduler = PSServer._register_with_scheduler
    _sched_register_once = PSServer._sched_register_once
    _control_plane_loop = PSServer._control_plane_loop
    _flight_context = PSServer._flight_context
    _sched_reconnect = PSServer._sched_reconnect
    _handle_control = PSServer._handle_control
    _fence_book = PSServer._fence_book
    _note_book = PSServer._note_book
    # tuning-section awareness only (docs/autotune.md): the flag is
    # harmless here — with no _hot_report the borrowed control loop
    # never ships a hot report, keeping native ranks out of the
    # rebalance policy's candidate set
    _adopt_tuning = PSServer._adopt_tuning
    # multi-tenant book map (docs/async.md): adopted for observability
    # only — the C++ data plane REJECTS job-namespaced frames (clean
    # status=1 echo), so the weights/quotas never engage natively
    _adopt_jobs = PSServer._adopt_jobs

    def start(self, register: bool = True) -> None:
        # scrape surface with the C++ data plane: the process-global
        # registry carries control-plane counters/gauges PLUS the
        # engine's own counters and histograms via the provider seams
        if self.cfg.metrics_port > 0 and self._metrics_http is None:
            from byteps_tpu.core.telemetry import serve_metrics

            self._metrics_http = serve_metrics(self.cfg.metrics_port)
        if register:
            # identical control-plane bring-up to the Python server
            self._register_with_scheduler()
            # the scheduler's address book wins over launch-time env
            # (PSServer adopts book["num_workers"]; mirror it in the engine)
            self._lib.bps_native_server_set_num_workers(self._id, self.num_workers)

    def stop(self) -> None:
        self._stop.set()
        if self._metrics_http is not None:
            self._metrics_http.close()
            self._metrics_http = None
        # flight recorder: release iff this instance installed it (same
        # rule as PSServer.stop)
        from byteps_tpu.core.flightrec import release_process_recorder

        release_process_recorder(self._flight_context)
        # freeze the engine's final counter values BEFORE the instance
        # id disappears, so post-stop snapshots keep everything the
        # GIL-free plane counted (and a racing scrape can't double-count)
        from byteps_tpu.core.telemetry import counters, metrics

        counters().absorb_provider(self._counters_provider)
        metrics().absorb_hist_provider(self._hist_provider)
        # backlog gauges describe a live engine only — drop the series
        # rather than export a dead callable forever
        for i in range(self._stripe_count):
            metrics().gauge_remove(
                "native_stripe_queue_depth",
                labels={"stripe": str(i), **self._gauge_labels},
            )
        if self._span_drain_thread is not None:
            self._span_drain_thread.join(timeout=2.0)
            self._span_drain_thread = None
        # final span drain + flush while the instance still exists: the
        # engine's last buffered children must reach server<rank>/comm.json
        # or the merged timeline loses the server half of the tail (drain
        # until empty — one call returns at most one ctypes batch, and a
        # burst backlog can hold several)
        try:
            while self._drain_spans_once():
                pass
        except Exception:  # noqa: BLE001
            pass
        self._lib.bps_native_server_stop(self._id)
        self.tracer.flush()
        close_socket(self._sched_conn)


def _make_reducer():
    """Native C++ summation when available (cpu_reducer.cc equivalent),
    numpy otherwise."""
    try:
        from byteps_tpu.native import cpu_reducer

        return cpu_reducer.sum_into
    except Exception:
        def _numpy_sum(dst: np.ndarray, src: np.ndarray) -> None:
            np.add(dst[: len(src)], src, out=dst[: len(src)])

        return _numpy_sum


def _serve_until_signaled(node) -> None:
    """Park the entry-point thread; SIGTERM/SIGINT run ``node.stop()``
    first — a plain kill would otherwise skip the trace flush and the
    metrics-endpoint teardown, losing the server-side half of every
    cross-process timeline (docs/observability.md)."""
    import signal

    done = threading.Event()

    def _graceful(_signum, _frame):
        try:
            node.stop()
        finally:
            done.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _graceful)
        except ValueError:
            pass  # non-main thread (embedded use): no handler, park only
    done.wait()


def run_server() -> None:
    """Process entry: become scheduler or server per DMLC_ROLE
    (server/__init__.py:21-27)."""
    from byteps_tpu.common.config import Config
    from byteps_tpu.comm.rendezvous import Scheduler

    cfg = Config.from_env()
    if cfg.role == "scheduler":
        sched = Scheduler(
            cfg.num_worker, cfg.num_server, port=cfg.ps_root_port,
            dead_node_timeout=cfg.dead_node_timeout_s,
            rejoin_window=cfg.sched_rejoin_window_s,
        )
        sched.start()
        _serve_until_signaled(sched)
        return
    elif cfg.role == "server":
        import os

        from byteps_tpu.comm.shaping import shaping_enabled, warn_native_bypass_once

        if os.environ.get("BYTEPS_SERVER_NATIVE", "0") == "1" and shaping_enabled():
            # same gate as the client side: the C++ engine's response
            # direction would bypass the shaper, yielding a half-shaped
            # link that "measures" a DCN that exists one way only
            warn_native_bypass_once(
                "ignoring BYTEPS_SERVER_NATIVE=1, using the Python engine"
            )
            srv = PSServer(cfg, host=cfg.node_host or "127.0.0.1")
        elif os.environ.get("BYTEPS_SERVER_NATIVE", "0") == "1":
            srv = NativePSServer(cfg, host=cfg.node_host or "127.0.0.1")
        else:
            srv = PSServer(cfg, host=cfg.node_host or "127.0.0.1")
        srv.start()
        _serve_until_signaled(srv)
    else:
        raise SystemExit(f"run_server: unsupported role {cfg.role!r}")
