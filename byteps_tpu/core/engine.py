"""Worker-side host pipeline engine.

Re-design of the reference's stage loops (core_loops.cc) for the TPU PS
path.  On GPU the pipeline is COORDINATE→REDUCE→COPYD2H→PUSH→PULL→COPYH2D→
BROADCAST with NCCL + CUDA events; on TPU the intra-slice REDUCE/BROADCAST
are XLA collectives inside the jitted step, so the *host* pipeline is:

    COPYD2H  (device→host staging of the host-shard)
    COMPRESS (optional, spliced when a compressor is registered —
              operations.cc:199-204)
    PUSH     (DCN → PS server, priority-scheduled)
    PULL     (DCN ← PS server)
    DECOMPRESS
    COPYH2D  (host→device, one partition at a time as its PULL or
              DECOMPRESS lands; the job's last partition then assembles
              the leaf on the device, in the sharding the tensor was
              submitted in — the caller's next step consumes it)

Each stage is a ScheduledQueue + worker thread; PUSH/PULL completion is
driven by PS-client callbacks, mirroring how ps-lite callbacks drive
``FinishOrProceed`` (core_loops.cc:31-137).  Priority order means small,
front-of-model gradients overtake bulky back-of-model ones — BytePS's
scheduling core idea.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from byteps_tpu.common.config import Config
from byteps_tpu.common.partition import partition_tensor, validate_rowsparse
from byteps_tpu.comm.transport import release_frame
from byteps_tpu.common.registry import get_registry
from byteps_tpu.common.types import (
    QueueType,
    RequestType,
    Status,
    TensorTableEntry,
    get_command_type,
    to_datatype,
)
from byteps_tpu.core.ready_table import ReadyTable
from byteps_tpu.core.scheduler import ScheduledQueue
from byteps_tpu.core.tracing import releasing


@functools.cache
def _assemble_program(sharding=None):
    """``(parts, shape) → leaf`` as ONE compiled program per distinct
    partition layout (jit keys it by the parts' shapes and shardings and by
    ``shape``); an eager concatenate + reshape would dispatch two and hold
    the flat copy between them.  With ``sharding`` (a job's kept one,
    ``_Placement``) the leaf comes out in it: what COPYH2D spread over the
    devices is gathered first, array by array, so XLA writes one
    all-gather each and their concatenation — left to place the gather
    itself, XLA:TPU concatenates the shards by pad and maximum and holds
    9.3 GB of temporaries for a 411 MB leaf of 101 partitions (against
    0.41 GB this way; both compiled for a described v5e 2x2).  Built on
    first use: importing the engine starts no backend."""
    import jax
    import jax.numpy as jnp

    everywhere = None if sharding is None else _partition_shardings(sharding)[1]

    def assemble_parts(parts, shape):
        if everywhere is not None:
            parts = [jax.lax.with_sharding_constraint(p, everywhere) for p in parts]
        return jnp.concatenate(parts).reshape(shape)

    return jax.jit(assemble_parts, static_argnums=1, out_shardings=sharding)


@functools.cache
def _partition_shardings(sharding) -> tuple:
    """``(split, everywhere)`` for the flat partitions of a job whose
    result goes to ``sharding``: over a one-axis mesh of its devices in
    their assignment order, an array cut evenly along that axis, and one
    whole on every device.  Made once a sharding."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(sharding._device_assignment), ("parts",))
    return (NamedSharding(mesh, PartitionSpec("parts")),
            NamedSharding(mesh, PartitionSpec()))


class _Placement:
    """Where COPYH2D puts the partitions of a raw or host-codec jax job whose
    tensor came in fully replicated, so that the result is MADE in that
    sharding: every partition leaves host memory once, the sharding's n
    devices take a share each over their own host links, and the assemble
    program gathers (``_assemble_program``).

    A transfer costs the stage thread ≈ 0.1 ms beyond its bytes (PERF.md
    §6 PR 67: one put of 4 MB 0.26 ms, the same bytes cut in four 0.63), so
    a partition is cut only where it must be.  The partitions of full
    length — all but a tensor's last — are DEALT, whole, to the devices in
    turn, as many as make whole runs of n; a run is then one array cut
    evenly over the devices with nothing moved (``stitched``).  What is left
    over — fewer than n partitions and the short last one — is put cut
    evenly over the devices, or whole on each where n does not divide its
    length.  With one device (a mesh of one chip) everything is "whole on
    each": the plain put, on the sharding the caller will ask for."""

    __slots__ = ("sharding", "split", "everywhere", "devices", "whole", "dealt")

    def __init__(self, sharding, partitions) -> None:
        self.sharding = sharding
        self.split, self.everywhere = _partition_shardings(sharding)
        self.devices = sharding._device_assignment
        n = len(self.devices)
        self.whole = partitions[0].length if partitions else 0
        full = sum(p.length == self.whole for p in partitions)
        self.dealt = full // n * n if n > 1 else 0

    @staticmethod
    def kept(sharding) -> bool:
        """Is a fully replicated tensor's ``sharding`` one to make the
        result in?  Over several of this process's devices, or one device
        named by a ``NamedSharding`` (a mesh of one chip).  Not a plain
        array's: that comes back on the default device, uncommitted, as
        it went in."""
        from jax.sharding import NamedSharding

        return sharding.is_fully_addressable and (
            sharding.num_devices > 1 or isinstance(sharding, NamedSharding))

    def where(self, offset: int, length: int) -> tuple:
        """→ (the ``device_put`` target of the partition at ``offset``,
        whether its bytes are shared out over the devices)."""
        n = len(self.devices)
        index = offset // self.whole
        if index < self.dealt:
            return self.devices[index % n], True
        if n > 1 and length % n == 0:
            return self.split, True
        return self.everywhere, False

    def stitched(self, parts: list) -> list:
        """A job's device partitions in offset order, each run of dealt
        ones as the one array it is on the devices."""
        import jax

        n = len(self.devices)
        run = (n * self.whole,)
        return [jax.make_array_from_single_device_arrays(run, self.split, parts[i:i + n])
                for i in range(0, self.dealt, n)] + parts[self.dealt:]


@functools.cache
def _split_program():
    """``(flat, bounds) → partitions``: the mirror of ``_assemble_program``,
    ONE compiled program per distinct partition layout where an eager
    ``flat[lo:hi]`` a partition would dispatch one program each from the
    caller's thread."""
    import jax
    from jax import lax

    def split_parts(flat, bounds):
        return [lax.slice(flat, (lo,), (hi,)) for lo, hi in bounds]

    return jax.jit(split_parts, static_argnums=1)


def _assemble(parts: list, shape: tuple, placement=None):
    """A job's device partitions, in offset order, as one array of its
    submitted shape — in the job's sharding, where it has a placement."""
    if placement is None:
        if len(parts) == 1:
            return parts[0].reshape(shape)
        return _assemble_program()(parts, shape)
    return _assemble_program(placement.sharding)(placement.stitched(parts), shape)


_TARGET_SITE = {"site": "pull_target"}


class _Job:
    """One push_pull invocation: shared state across its partitions."""

    __slots__ = (
        "name", "ctx", "flat", "result", "dtype_id", "average", "handle",
        "pending", "lock", "shape", "np_dtype", "is_jax", "version", "t0",
        "rowsparse", "device_codec", "device_parts", "failed", "trace_id",
        "parent_span", "step_counted", "d2h_parts", "holds_target",
        "placement", "__weakref__",
    )

    def __init__(self, name, ctx, flat, result, dtype_id, average, handle,
                 pending, shape, np_dtype, is_jax, version, rowsparse=None,
                 device_codec=False, d2h_parts=None, holds_target=False,
                 placement=None):
        self.name = name
        self.ctx = ctx
        # the tensor as one flat array the COPYD2H thread slices: a numpy
        # view, or the jax array of a device-codec or sharded job.  None
        # where ``d2h_parts`` holds the partitions instead
        self.flat = flat
        # raw jax jobs whose tensor one chip holds whole: offset → the
        # partition as a single-device jax.Array whose copy to the host
        # was started in submit (``_start_d2h``); COPYD2H pops each one,
        # so the device slice dies when its task leaves the stage
        self.d2h_parts = d2h_parts
        # such a job's result is made in the sharding its tensor came in:
        # where COPYH2D puts each partition (None: on the default device)
        self.placement = placement
        # the host buffer every PULL lands in.  ``holds_target``: it is the
        # tensor's own (``ctx.pull_target``), lent to this job until its
        # last partition is on the device (``_return_target``)
        self.result = result
        self.holds_target = holds_target
        self.dtype_id = dtype_id
        self.average = average
        self.handle = handle
        self.pending = pending
        self.lock = threading.Lock()
        self.shape = shape
        self.np_dtype = np_dtype
        self.is_jax = is_jax
        self.version = version
        self.t0 = time.time()
        # row-sparse jobs: {"push_payload": bytes, "pull_req": bytes}
        # (kRowSparsePushPull, common.h:267-271)
        self.rowsparse = rowsparse
        # device-codec jobs compress before D2H and decode after H2D: they
        # own no host result buffer (the gradient never exists
        # uncompressed on the host)
        self.device_codec = device_codec
        # jax jobs: offset → the partition as a jax.Array, put there by
        # COPYH2D (raw and host-codec jobs) or decoded there by DECOMPRESS
        # (device-codec jobs); assembled on DEVICE in _finalize
        self.device_parts = {} if is_jax else None
        # set when ANY task of this job fails: the abort fence the PS
        # client checks before (re)sending — a pending retry timer from
        # an abandoned round must not replay into the re-initialized
        # next generation (its cleared dedupe ledger would re-sum it)
        self.failed = False
        # distributed tracing: one trace id per push_pull invocation;
        # every partition task's span joins it (0 = tracing off).  A job
        # submitted inside a tracing.span (a training step) takes that
        # span's trace id and names it as its tasks' parent
        self.trace_id = 0
        self.parent_span = 0
        # once-guard for the flight recorder's step accounting: a job
        # leaves the in-flight count exactly once whether it finalized
        # or several of its tasks raced into _fail_job
        self.step_counted = False


class _FusionGroup:
    """One flushed fusion pack: member tasks + their staged payloads,
    shipped as a single multi-key Op.FUSED RPC.  Member keys are unique
    within a pack (the per-key round gate admits at most one in-flight
    round per key, and a round has one task per key)."""

    __slots__ = ("members", "done", "lock")

    def __init__(self, members: List[tuple]) -> None:
        self.members = members  # [(task, payload buffer)]
        self.done = False  # once-guard: deliver/on_error both race here
        self.lock = threading.Lock()


class _FusionBuffer:
    """Accumulating pack for one destination server."""

    __slots__ = ("members", "nbytes", "max_priority", "oldest")

    def __init__(self) -> None:
        self.members: List[tuple] = []
        self.nbytes = 0
        self.max_priority = -(1 << 62)
        self.oldest = time.monotonic()


class _Fuser:
    """Per-destination-server fusion buffers — the FUSE stage's state.

    Small partitions (≤ BYTEPS_FUSION_THRESHOLD bytes) are packed here by
    destination server instead of each paying its own framed RPC, deadline
    arm, and retry state.  Flush triggers (each bumps a
    ``fusion_flush_<reason>`` counter):

    - ``full``:  the pack reached BYTEPS_FUSION_BYTES — ship it.
    - ``idle``:  the FUSE queue drained, so no more smalls are coming from
      this burst; holding the pack any longer would only add latency.
      This keeps sequential single-tensor rounds near-zero-overhead.
    - ``cycle``: a member has waited BYTEPS_FUSION_CYCLE_MS — the latency
      backstop for workloads whose FUSE queue never quite drains.
    """

    def __init__(self, engine: "PipelineEngine") -> None:
        self._engine = engine
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        #: (destination server, job) → accumulating pack
        self._bufs: Dict[tuple, _FusionBuffer] = {}
        self._cycle_thread: Optional[threading.Thread] = None

    def add(self, task: TensorTableEntry, payload) -> None:
        # packs are keyed by (destination server, JOB): a process
        # hosting several tenants (byteps_job declare kwarg) must not
        # mix jobs in one frame — the pack competes in the WFQ, spends
        # gate credits, and is admission-metered under ONE job, so a
        # mixed pack would ride the wrong tenant's share
        bkey = (self._engine.client.server_for(task.key), task.job)
        full = None
        with self._lock:
            buf = self._bufs.get(bkey)
            if buf is None:
                buf = self._bufs[bkey] = _FusionBuffer()
                # wake the cycle thread: it sleeps indefinitely while
                # every buffer is empty, and must now arm this pack's
                # BYTEPS_FUSION_CYCLE_MS deadline
                self._cv.notify()
            buf.members.append((task, payload))
            buf.nbytes += len(payload)
            buf.max_priority = max(buf.max_priority, task.priority)
            if buf.nbytes >= self._engine.cfg.fusion_bytes:
                full = self._bufs.pop(bkey)
        if full is not None:
            self._emit(full, "full")
        self._ensure_cycle_thread()

    def drain_idle(self) -> None:
        """The FUSE queue is empty: flush every pack now."""
        with self._lock:
            bufs, self._bufs = self._bufs, {}
        for buf in bufs.values():
            self._emit(buf, "idle")

    def _ensure_cycle_thread(self) -> None:
        if self._cycle_thread is not None:
            return
        with self._lock:
            if self._cycle_thread is not None:
                return
            t = threading.Thread(
                target=self._cycle_loop, name="bps-fusion-cycle", daemon=True
            )
            self._cycle_thread = t
        t.start()

    def _cycle_loop(self) -> None:
        """BYTEPS_FUSION_CYCLE_MS backstop, event-driven: sleeps until the
        OLDEST live pack's deadline (woken by add() when a pack is born),
        not on a fixed half-cycle poll — an idle fuser costs ~2 wakeups/s,
        not a permanent kHz tick."""
        cycle_s = max(0.0005, self._engine.cfg.fusion_cycle_ms / 1e3)
        stop = self._engine._stop
        while not stop.is_set():
            aged = []
            with self._cv:
                if not self._bufs:
                    # idle: nothing to age — park until add() notifies
                    # (bounded so engine shutdown is noticed promptly)
                    self._cv.wait(0.5)
                    continue
                now = time.monotonic()
                soonest = min(b.oldest for b in self._bufs.values()) + cycle_s
                if soonest > now:
                    self._cv.wait(soonest - now)
                    continue
                for bkey in [
                    k for k, b in self._bufs.items()
                    if now - b.oldest >= cycle_s
                ]:
                    aged.append(self._bufs.pop(bkey))
            for buf in aged:
                self._emit(buf, "cycle")

    def _emit(self, buf: _FusionBuffer, reason: str) -> None:
        """Hand the pack to the PUSH queue as ONE group task.  The group
        inherits the MAX priority of its members (fusion must never defeat
        priority scheduling: a pack holding one urgent front-layer gradient
        outranks every bulkier push below that urgency) and the summed
        length (credit accounting); ``gate_exempt`` skips the per-key round
        gate the members already passed at the FUSE queue."""
        from byteps_tpu.core.telemetry import COUNT_BUCKETS, counters, metrics

        counters().bump(f"fusion_flush_{reason}")
        # pack-quality histograms (docs/observability.md): density tells
        # whether the threshold actually coalesces (p50 of 1 = fusion is
        # pure overhead), flush age is the latency the pack COST its
        # oldest member — the two knobs BYTEPS_FUSION_BYTES /
        # BYTEPS_FUSION_CYCLE_MS trade against each other
        metrics().observe(
            "fused_pack_keys", len(buf.members), buckets=COUNT_BUCKETS
        )
        metrics().observe(
            "fused_flush_age_seconds", time.monotonic() - buf.oldest
        )
        members = buf.members
        group = TensorTableEntry(
            tensor_name="<fused>",
            key=members[0][0].key,
            priority=buf.max_priority,
            version=0,
            length=sum(t.length for t, _ in members),
            total_partnum=len(members),
            queue_list=[QueueType.PUSH],
            context=_FusionGroup(members),
            gate_exempt=True,
            # members share one process (= one tenant); the pack
            # competes in the WFQ under its members' job
            job=members[0][0].job,
        )
        self._engine.queues[QueueType.PUSH].add_task(group)


class _StageIdle:
    """What a stage thread does between two services, by cause.

    ``ScheduledQueue.get_task`` runs each of its waits in this context:
    one wait is one observation in ``stage_idle_seconds{stage, why}`` with
    ``why`` = ``starved`` | ``gated`` (the poll ticks of an idle process
    too) and one profiler annotation ``bpswait.stage.<STAGE>.<why>``.  The
    prefix is not ``bps.``: a wait is no phase, and a reader that names
    device idle time after the phases open would find one under every gap.
    What is left between two services is ``why="dequeue"``: taking the
    queue's lock against the threads that add to it, the scan for an
    eligible task, the loop's own bookkeeping, and however long the thread
    waited for the GIL in them (:meth:`rest`, on the clock readings the
    service spans made).  So a stage thread's service + starved + gated +
    dequeue IS its wall clock, over any window."""

    WAITS = ("starved", "gated")

    def __init__(self, stage: str) -> None:
        from jax.profiler import TraceAnnotation

        from byteps_tpu.core.telemetry import metrics

        self._annotate = TraceAnnotation
        self._names = {w: f"bpswait.stage.{stage}.{w}" for w in self.WAITS}
        self._hists = {
            w: metrics().held("stage_idle_seconds", {"stage": stage, "why": w})
            for w in self.WAITS + ("dequeue",)
        }
        for hist in self._hists.values():
            hist.get()  # a cause that never occurs reads 0, not nothing
        self._why = self.WAITS[0]
        self._waited = 0.0  # in waits since ``mark``
        self.mark = time.perf_counter()  # up to here all time is accounted

    def __call__(self, why: str) -> "_StageIdle":
        self._why = why
        return self

    def __enter__(self) -> None:
        self._annotation = self._annotate(self._names[self._why])
        self._annotation.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        self._hists[self._why].observe(dur)
        self._waited += dur
        return False

    def rest(self, until: float, resume: float) -> None:
        """The thread was free from ``mark`` to ``until`` and is accounted
        for again from ``resume`` (a service's start and end; one moment
        for a poll tick): what of that was no wait is the dequeue."""
        self._hists["dequeue"].observe(until - self.mark - self._waited)
        self._waited = 0.0
        self.mark = resume


class _StripedStage:
    """N parallel queues for a stage, striped by key.

    The reference offloads COMPRESS/DECOMPRESS to a thread pool
    (``BYTEPS_THREADPOOL_SIZE``, core_loops.cc:498-536); striping by key
    keeps each key's stateful EF/momentum codec on one thread so rounds of
    the same key never race while different keys compress in parallel.
    """

    def __init__(self, queue_type: QueueType, n: int) -> None:
        self.queue_type = queue_type
        self.stripes = [ScheduledQueue(queue_type) for _ in range(max(1, n))]

    def add_task(self, task: TensorTableEntry) -> None:
        self.stripes[task.key % len(self.stripes)].add_task(task)

    def report_finish(self, task: TensorTableEntry) -> None:
        self.stripes[task.key % len(self.stripes)].report_finish(task)


def _stages(compressed: bool, fused: bool) -> list:
    """One partition's host pipeline (PS path), the one place that
    decides it.

    ``compressed``: the key has a codec, host or device — COMPRESS and
    DECOMPRESS are spliced in (operations.cc:199-204).  A device codec
    emits the exact wire encoding on the device, so COPYD2H already
    lands ``task.compressed`` and COMPRESS is a pass-through: the
    sequence is the same, the difference is where the packing ran.

    ``fused``: the partition's wire size fits ``BYTEPS_FUSION_THRESHOLD``
    — FUSE takes PUSH's place: the multi-key fused RPC carries both
    halves of the round trip, and PULL delivers the fanned-out reply
    slice locally (docs/fusion.md).  A compressed member's cmd carries
    ``RequestType.COMPRESSED_PUSH_PULL``, so the server sums it through
    the key's codec chain and the reply slot comes back codec-compressed
    for DECOMPRESS (docs/gradient-compression.md "Compressed wire path",
    "Device path")."""
    return [
        QueueType.COPYD2H,
        *([QueueType.COMPRESS] if compressed else []),
        QueueType.FUSE if fused else QueueType.PUSH,
        QueueType.PULL,
        *([QueueType.DECOMPRESS] if compressed else []),
        QueueType.COPYH2D,
    ]


class PipelineEngine:
    #: monotonically increasing engine-instance id: the tensor registry
    #: (and each ctx's ``initialized`` flag) outlives shutdown()/init()
    #: cycles, but servers started by a LATER init() have fresh stores —
    #: a ctx initialized under a previous engine must re-run its
    #: init-push barrier, exactly like an elastic server resize
    _epoch_counter = itertools.count()
    #: a stage thread's longest wait for its queue before it looks at the
    #: stop flag again (and the coarsest the idle account's edges get)
    _POLL_S = 0.2

    def __init__(self, cfg: Config, ps_client, telemetry=None, tracer=None,
                 flightrec=None) -> None:
        self.cfg = cfg
        self.client = ps_client
        self.telemetry = telemetry
        self.tracer = tracer
        # flight recorder (docs/observability.md "Flight recorder &
        # doctor"): the engine stamps one ledger record per completed
        # round — when the in-flight job count drains back to zero —
        # carrying the step wall time.  None / capacity 0 = off.
        self._flight = flightrec
        self._step_lock = threading.Lock()
        self._step_open = 0
        self._step_t0 = 0.0
        self._epoch = next(PipelineEngine._epoch_counter)
        self._stop = threading.Event()
        credit = cfg.scheduling_credit
        pool = max(1, cfg.threadpool_size)
        # PUSH round-order gate (the ReadyTable rendezvous of
        # scheduled_queue.cc:48-79, re-purposed for the single-process TPU
        # worker): counts[key] = highest round allowed to leave the PUSH
        # queue.  Concurrent jobs on one name carry caller-chosen
        # priorities, so without the gate a later round could overtake an
        # earlier round of the same key — the server aggregates per round
        # of arrivals, so cross-round interleaving corrupts sums (and a
        # reordered pair can deadlock: the later round's pull waits on a
        # round the earlier push never gets to start).  Completions advance
        # the allowance.
        self._push_ready = ReadyTable(ready_count=1, name="push")
        self._seeded: set = set()  # keys whose gate this engine has seeded
        disc = cfg.scheduling
        # per-tenant QoS in the stage queues (docs/async.md): this
        # process's job registers its weighted share, and an optional
        # per-job in-flight byte budget bounds the tenant the way the
        # global credit bounds the queue.  With one job per process
        # (the default) the WFQ layer is inert.
        from byteps_tpu.core.scheduler import set_job_weight

        set_job_weight(cfg.job_id, max(1, cfg.job_priority))
        job_credits = (
            {cfg.job_id: cfg.job_credit_bytes}
            if cfg.job_credit_bytes > 0 else None
        )
        from byteps_tpu.core.telemetry import metrics as _metrics

        # stage_dwell_seconds{stage} at hand: _proceed observes one a task
        # and stage, on whichever thread finished it
        self._dwelt = {
            qt: _metrics().held("stage_dwell_seconds", {"stage": qt.name})
            for qt in QueueType
        }
        self.queues: Dict[QueueType, Any] = {
            QueueType.COPYD2H: ScheduledQueue(QueueType.COPYD2H, discipline=disc),
            QueueType.COMPRESS: _StripedStage(QueueType.COMPRESS, pool),
            QueueType.PUSH: ScheduledQueue(
                QueueType.PUSH,
                credit_bytes=credit,
                ready_table=self._push_ready,
                version_gated=True,
                discipline=disc,
                job_credits=job_credits,
            ),
            # FUSE shares the PUSH round gate: a fused member obeys the
            # same per-key round order as an unfused push — the gate just
            # moves to where the small partition leaves the pipeline
            QueueType.FUSE: ScheduledQueue(
                QueueType.FUSE,
                ready_table=self._push_ready,
                version_gated=True,
                discipline=disc,
                job_credits=job_credits,
            ),
            QueueType.PULL: ScheduledQueue(QueueType.PULL, discipline=disc),
            QueueType.DECOMPRESS: _StripedStage(QueueType.DECOMPRESS, pool),
            QueueType.COPYH2D: ScheduledQueue(QueueType.COPYH2D, discipline=disc),
        }
        self._fuser = _Fuser(self)
        # recovery plane (docs/robustness.md "healing flow"): bounded
        # journal of emitted push payloads, replayed by the PS client's
        # resync heal when a live server reports rounds it never
        # absorbed.  (Re)configured per engine so a previous generation's
        # entries can never replay into this one's round numbering.
        from byteps_tpu.comm.journal import configure_journal

        self._journal = configure_journal(cfg.journal_rounds, cfg.journal_bytes)
        # small tasks submitted but not yet handed to the fusion buffer:
        # the idle-flush decision needs this because queue.pending() can't
        # see a task COPYD2H has popped but not finished staging
        self._staged_smalls = 0
        self._fuse_lock = threading.Lock()
        self._threads: List[threading.Thread] = []
        self._init_lock = threading.Lock()
        self._target_lock = threading.Lock()  # every ctx's pull_target
        # per-key stateful codec chains (per-partition compressor
        # instantiation, operations.cc:283-414)
        self._compressors: Dict[int, object] = {}
        # per-key DEVICE codec adapters (core/device_codec.py): for bare
        # codec chains on jax inputs, COMPRESS runs on-device BEFORE the
        # D2H so the host boundary moves the compressed payload — the
        # inversion of the reference's CPU-post-staging compress
        # (core_loops.cc:498-536; SURVEY §7's genuine TPU improvement)
        self._device_codecs: Dict[int, object] = {}
        # adaptive compression (BYTEPS_COMPRESSION_AUTO): keys whose
        # observed wire ratio made the codec a loss — their later rounds
        # take the raw pipeline (the codec chain and the server-side
        # registration stay put: servers serve raw pushes/pulls on a
        # codec-registered key correctly, the mixed-config rule, so the
        # policy needs no wire coordination).  _auto_stats accumulates
        # (rounds, compressed bytes, raw bytes) per key until the verdict.
        self._compression_auto_off: set = set()
        self._auto_stats: Dict[int, list] = {}
        self._compression_lr: float = 1.0
        self._lr_sent_to_servers: float = 1.0
        # --- fleet tuning adoption (docs/autotune.md) ---
        # the scheduler's autotuner ships a versioned ``tuning`` section
        # in every book; the PS client replays it here.  Fleet codec
        # disables are tracked per codec name → the keys THIS engine
        # disabled for it, so a rollback re-enables exactly those.
        self._fuse_enabled = cfg.fusion_threshold > 0
        # the launch value: a tuning section WITHOUT a fusion_threshold
        # field means "untouched/legacy" — adoption restores this, so a
        # reborn scheduler's empty tuning state (or a rollback to the
        # pre-tuner value) actually lands fleet-wide
        self._launch_fusion_threshold = cfg.fusion_threshold
        self._codec_names: Dict[int, str] = {}
        self._fleet_codec_off: Dict[str, set] = {}
        # third tuner arm (docs/gradient-compression.md "Lossless frame
        # compression"): keys whose lossy codec lost the auto verdict
        # push raw — the entropy probe in _push_once checks whether the
        # raw bytes are compressible losslessly and, if so, stamps the
        # key's later pushes with the wire-level lossless container.
        # Python wire only (the native client's send path never frames).
        self._lossless_keys: set = set()
        self._lossless_probed: set = set()
        self._fleet_codec_lossless: Dict[str, set] = {}
        self._tuning_lock = threading.Lock()
        # the fleet fusion-threshold gauge feeds the tuner's walk (the
        # scheduler reads the aggregate's max as the fleet value)
        _metrics().gauge_set("fusion_threshold_bytes", cfg.fusion_threshold)
        add_listener = getattr(ps_client, "add_tuning_listener", None)
        if add_listener is not None:
            add_listener(self._apply_tuning)
        # tensor names whose last job failed degraded: their next submit
        # re-runs the init-push barrier, which resets the key's round
        # numbering on the (possibly healed) owners — without this the
        # abandoned round leaves client and server version counters
        # skewed and every later pull of that key pends forever
        self._reinit_names: set = set()

    # --- lifecycle -------------------------------------------------------

    def start(self) -> None:
        """Spawn one loop thread per host stage (BytePSGlobal::Start,
        global.cc:299-317).  The COMPRESS/DECOMPRESS striped pools spawn
        lazily when the first codec registers — uncompressed workers don't
        pay for 2×threadpool_size idle pollers.

        PUSH alone has as many threads as the server links can feed at
        once (``PSClient.push_senders``, from the links' kind: two over
        split TCP links, one over a unix, shm, shaped or native link): a
        4 MB ``sendmsg`` is the kernel's copy made by the calling thread,
        and one thread making a step's 162 of them one after another was
        the PS hop (PERF.md §6 PR 39).  All of them serve the ONE PUSH
        queue, so there is one priority order at dequeue, one credit
        budget and one round gate; the gate, not a thread's FIFO, keeps a
        key's rounds in order."""
        senders = getattr(self.client, "push_senders", None)
        stages = [
            (QueueType.COPYD2H, self._copy_d2h_once, 1),
            (QueueType.PUSH, self._push_once,
             senders() if senders is not None else 1),
            (QueueType.PULL, self._pull_once, 1),
            (QueueType.COPYH2D, self._copy_h2d_once, 1),
        ]
        if self.cfg.fusion_threshold > 0:
            # fusion off (the default) spawns no FUSE poller — the stage
            # only exists when small partitions can actually route to it
            stages.insert(1, (QueueType.FUSE, self._fuse_once, 1))
        for qt, fn, threads in stages:
            self._spawn_stage(qt, fn, threads)

    def _spawn_stage(self, qt: QueueType, fn, threads: int = 1) -> None:
        """A thread a stripe of a striped stage; ``threads`` threads over
        the one queue of any other, the first under the stage's own names
        and thread i ≥ 1 under ``<STAGE>.<i>`` (:meth:`_loop`)."""
        q = self.queues[qt]
        if isinstance(q, _StripedStage):
            loops = [(sq, 0) for sq in q.stripes]
        else:
            loops = [(q, i) for i in range(threads)]
        for si, (sq, index) in enumerate(loops):
            t = threading.Thread(
                target=self._loop, args=(sq, fn, index),
                name=f"bps-{qt.name}-{si}", daemon=True,
            )
            t.start()
            self._threads.append(t)

    def _ensure_compress_threads(self) -> None:
        """First codec registration → bring up the striped pools."""
        if getattr(self, "_compress_started", False):
            return
        self._compress_started = True
        self._spawn_stage(QueueType.COMPRESS, self._compress_once)
        self._spawn_stage(QueueType.DECOMPRESS, self._decompress_once)

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)
        self._threads = []

    def _loop(self, q: ScheduledQueue, fn, index: int = 0) -> None:
        from byteps_tpu.core.telemetry import counters, metrics
        from byteps_tpu.core.tracing import sampled, span, tag_thread

        # values at hand only: nothing is formatted, and no label set is
        # hashed, per task on this path.  The thread's wall clock is its
        # service (span_seconds of "stage.<STAGE>") and what it does between
        # two services, by cause (docs/observability.md "Reading a hop
        # thread by thread").  A stage's second thread over one queue (PUSH
        # over TCP) keeps an account of its own under "<STAGE>.1": summed
        # under one name, two threads would read twice the wall clock.  The
        # sends it makes (rpc.send.<OP>) take the same suffix
        suffix = f".{index}" if index else ""
        tag_thread(suffix)
        name = q.queue_type.name + suffix
        span_name = "stage." + name
        waited = metrics().held("stage_wait_seconds", {"stage": name})
        # one service in sampled.EVERY on two clocks, the thread's CPU time
        # and the wall, inside and outside the calls that let the GIL go:
        # how much of a service the thread runs, how much of it holding the
        # GIL, and how long it waited to get it back
        sample = sampled(name)
        idle = _StageIdle(name)
        while not self._stop.is_set():
            task = q.get_task(timeout=self._POLL_S, waiting=idle)
            if task is None:
                now = time.perf_counter()
                idle.rest(now, now)
                continue
            # enqueue → picked up: what the task WAITED for this stage;
            # stage_dwell_seconds (enqueue → done, _proceed) holds the
            # wait and the service together
            if task.enqueued_at:
                waited.observe(time.monotonic() - task.enqueued_at)
            # the task's SERVICE on this stage thread; the wall it does not
            # spend on the CPU it is blocked (a full socket buffer, a device
            # transfer, a lane's send lock) or waiting for the GIL
            serving = span(span_name, parent=self._task_trace(task),
                           key=task.key, tensor=task.tensor_name)
            try:
                with serving:
                    sample.begin()
                    try:
                        fn(task)
                    finally:
                        sample.end()
            except Exception as e:  # surface errors on the handle
                self._fail_task(
                    task, q.queue_type, repr(e),
                    degraded=isinstance(e, (ConnectionError, OSError)),
                )
            if index:
                # how much of the stage its second thread takes (PUSH alone
                # has one: :meth:`start`)
                counters().bump("push_second_sender_parts")
            idle.rest(serving.started, serving.ended)

    # --- submission ------------------------------------------------------

    def submit(
        self,
        name: str,
        tensor: Any,
        average: bool,
        priority: int,
        version: int,
        handle: int,
    ) -> None:
        """EnqueueTensor equivalent (operations.cc:182-281): lazily init the
        tensor (key range + server-side allocation barrier), partition, and
        drop every partition into the first stage queue.

        ``tensor`` may be a live jax Array: it is NOT materialized here —
        shape/dtype metadata is enough to partition, so the caller returns
        while the device is still computing.  Where one chip holds the
        whole of it (single-device or fully replicated) and no device
        codec packs it, every partition's device→host copy is STARTED
        here, from that chip's copy (``_start_d2h``), and the COPYD2H
        thread only collects; a sharded tensor and a device-codec job are
        sliced on that thread, a partition at a time.
        """
        import jax

        registry = get_registry()
        ctx = registry.declare(name)
        is_jax = isinstance(tensor, jax.Array)
        if is_jax:
            flat = None  # made below, once the job's path is known
            np_dtype, n_elements = np.dtype(tensor.dtype), tensor.size
        else:
            flat = np.ascontiguousarray(np.asarray(tensor)).reshape(-1)
            np_dtype, n_elements = flat.dtype, flat.size
        dtype_id = int(to_datatype(np_dtype))

        def build_partitions(c):
            partition_tensor(c, n_elements, np_dtype.itemsize, self.cfg.partition_bytes)

        def on_first_init():
            self._maybe_setup_compression(ctx, np_dtype, n_elements * np_dtype.itemsize)

        self._prepare_round(ctx, dtype_id, n_elements, build_partitions, on_first_init)
        # server-opt tensors pull UPDATED PARAMETERS, not gradient sums:
        # the worker-side divide must not fire (the declared rule folds
        # averaging server-side, same float op order)
        if self._server_opt_profile(ctx)[0]:
            average = False
        # jax input + bare codec chain ⇒ the device path: compress before
        # D2H, decode after H2D, assemble the result on device — no host
        # result buffer is ever written, so don't allocate one (the whole
        # point is that the gradient never exists uncompressed on host)
        on_device = (
            is_jax
            and bool(ctx.partitions)
            and all(p.key in self._device_codecs for p in ctx.partitions)
        )
        result, holds_target = None, False
        if not on_device:
            result, holds_target = self._pull_target(
                ctx, is_jax, n_elements, np_dtype)
        d2h_parts = placement = None
        if is_jax and not on_device and tensor.is_fully_replicated:
            d2h_parts = self._start_d2h(tensor.addressable_data(0), ctx.partitions)
            if _Placement.kept(tensor.sharding):
                placement = _Placement(tensor.sharding, ctx.partitions)
        elif is_jax:
            flat = tensor.reshape(-1)  # device-side metadata op, async
        job = _Job(
            name, ctx, flat, result, dtype_id, average, handle,
            pending=len(ctx.partitions), shape=np.shape(tensor),
            np_dtype=np_dtype, is_jax=is_jax, version=ctx.version,
            device_codec=on_device, d2h_parts=d2h_parts,
            holds_target=holds_target, placement=placement,
        )
        # small-tensor fusion routing, per partition: uncompressed
        # partitions gauge their RAW size against the threshold;
        # compressed partitions — host OR device codec — gauge their
        # WIRE size (codec wire_nbytes — the bytes that actually ride
        # the frame), so a 256KB tensor whose onebit payload is 8KB
        # fuses like any small tensor (docs/gradient-compression.md
        # "Compressed wire path" / "Device path").  A fused device
        # member rides the frame exactly like a host-compressed one
        # (COMPRESSED_PUSH_PULL cmd), and its reply slot feeds the
        # device decoder — the fused path never touches the host result
        # buffer device jobs deliberately don't allocate.
        fuse_limit = self.cfg.fusion_threshold
        itemsize = np_dtype.itemsize
        self._stamp_job_trace(job)
        self._step_begin()
        for part in ctx.partitions:
            if on_device:
                codec = self._device_codecs[part.key]
            elif (part.key in self._compressors
                  and part.key not in self._compression_auto_off):
                codec = self._compressors[part.key]
            else:
                codec = None
            wire_est = (
                part.length * itemsize if codec is None else codec.wire_nbytes()
            )
            small = bool(fuse_limit) and wire_est <= fuse_limit
            if small:
                with self._fuse_lock:
                    self._staged_smalls += 1
            task = TensorTableEntry(
                tensor_name=name,
                key=part.key,
                priority=priority,
                version=ctx.version,
                offset=part.offset,
                length=part.length,
                total_partnum=len(ctx.partitions),
                queue_list=_stages(codec is not None, small),
                context=job,
                fuse_staged=small,
                job=ctx.job,
            )
            self._stamp_task_trace(task, job)
            self.queues[QueueType.COPYD2H].add_task(task)

    def _pull_target(self, ctx, engine_consumes: bool, n_elements: int,
                     np_dtype) -> tuple:
        """The host buffer a job's PULLs land in → ``(buffer, lent)``.

        A jax job never hands its host result to anybody: COPYH2D puts
        each partition on the device and the handle returns device
        arrays.  Such a job borrows the tensor's one buffer
        (``ctx.pull_target``, made by its first round) and gives it back
        in ``_finalize``, once every partition's put is COMPLETE — so a
        steady round writes no page the process did not hold.  A numpy
        caller keeps what it is handed, and a second job in flight on one
        name finds the buffer out: both get a fresh one (counted:
        ``host_buffers_fresh`` / ``host_buffers_reused``,
        ``site="pull_target"``)."""
        from byteps_tpu.core.telemetry import counters

        lent = False
        if engine_consumes:
            with self._target_lock:
                if not ctx.pull_target_lent:
                    lent = ctx.pull_target_lent = True
                    buf = ctx.pull_target
                    if (buf is not None and buf.size == n_elements
                            and buf.dtype == np_dtype):
                        counters().bump("host_buffers_reused", labels=_TARGET_SITE)
                        return buf, True
        counters().bump("host_buffers_fresh", labels=_TARGET_SITE)
        buf = np.empty(n_elements, dtype=np_dtype)
        if lent:  # the tensor's first round (or its first of this size)
            ctx.pull_target = buf
        return buf, lent

    def _return_target(self, job: _Job, reusable: bool) -> None:
        """``job`` is done with the tensor's pull target.  ``reusable``:
        every byte of it that anybody reads has been read (the caller saw
        each partition's put complete).  A failed round's buffer is
        dropped instead: a late reply may still land in its sinks."""
        if not job.holds_target:
            return
        job.holds_target = False
        with self._target_lock:
            if not reusable:
                job.ctx.pull_target = None
            job.ctx.pull_target_lent = False

    @staticmethod
    def _start_d2h(leaf, partitions) -> dict:
        """Every partition of ``leaf`` (a single-device jax.Array, possibly
        still being computed) on its way to the host: offset → a device
        array whose ``copy_to_host_async`` is issued, in partition order.
        The split and the copies queue on the device behind whatever still
        computes ``leaf``, then stream back to back with no host round
        trip between partitions; each host buffer is one partition and
        owns its memory, as ``np.asarray(slice)`` always was
        (comm/journal.py keeps such a buffer by reference).  A leaf of one
        partition is copied as it is: no slice and no reshape program."""
        if len(partitions) == 1:
            parts = [leaf]
        else:
            bounds = tuple((p.offset, p.offset + p.length) for p in partitions)
            parts = _split_program()(leaf.reshape(-1), bounds)
        for part in parts:
            part.copy_to_host_async()
        return {p.offset: part for p, part in zip(partitions, parts)}

    def _prepare_round(self, ctx, dtype_id, n_elements, build_partitions,
                       on_first_init=None):
        """Shared per-submit bookkeeping for dense AND row-sparse paths:
        run (or, after an elastic server resize, RE-run) the init-push
        barrier, then advance the version and seed the PUSH round gate.

        - First init: build partitions, init every key (the blocking
          init-push doubles as the cross-worker barrier, operations.cc:
          283-414), then ``on_first_init`` (compressor setup).
        - server_generation mismatch (elastic resize): keys re-homed via
          the hash fns, so the init barrier re-runs against the new owners
          (their stores start fresh), compressor configs re-ship, and the
          version sequence restarts (the barrier reset server-side round
          counters) with the round gate re-seeded to match.  Under
          BYTEPS_ELASTIC_RESHARD this path never fires for a resize: the
          client does NOT bump server_generation when a book carries an
          ownership map (ps_client._rebuild_servers), because the servers
          migrate each re-homed key's state — store, exactly-once ledger,
          init-token record — to its new owner (docs/robustness.md
          "migration flow"), so the version sequence continues in place
          and pushes simply chase WRONG_OWNER redirects to the new home.
        - Gate seeding is per ENGINE, not per ctx-init: the registry (and
          its version counters) outlive shutdown()/init() cycles, while
          each engine starts with a fresh ReadyTable — a reused tensor name
          must start from its CURRENT version, not 1, or its tasks would
          never become eligible."""
        with self._init_lock:
            if ctx.partitions:
                declared = sum(p.length for p in ctx.partitions)
                if declared != n_elements:
                    # silent acceptance would scatter the new tensor into
                    # stores sized for the old one — garbage sums
                    raise ValueError(
                        f"tensor {ctx.name!r} re-used with a different size: "
                        f"declared {declared} elements, got {n_elements} "
                        "(name-keyed tensors keep a stable shape; use a "
                        "distinct name per tensor)"
                    )
            gen = getattr(self.client, "server_generation", 0)
            if (not ctx.initialized or ctx.server_generation != gen
                    or ctx.engine_epoch != self._epoch
                    or ctx.name in self._reinit_names):
                # engine_epoch mismatch: the registry survived a
                # shutdown()/init() cycle but this engine's servers are
                # new (fresh stores) — re-run the init barrier exactly
                # like a server resize, or the first push would hit an
                # uninitialized key and the server would drop the conn
                if not ctx.partitions:
                    build_partitions(ctx)
                if ctx.engine_epoch != self._epoch:
                    # whatever held the tensor's pull target died with the
                    # engine before this one (the registry outlives it)
                    with self._target_lock:
                        ctx.pull_target, ctx.pull_target_lent = None, False
                if self._journal is not None:
                    # the barrier below restarts this key's round
                    # numbering: journaled payloads from the old
                    # numbering must never replay into the new one
                    for part in ctx.partitions:
                        self._journal.clear_key(part.key)
                is_async, staleness = self._async_profile(ctx)
                # the async kwargs ride only on async inits: sync keys
                # keep the classic call shape (and the classic 12-byte
                # wire payload), so stub clients and old transports
                # never see the extension
                akw = (
                    {"async_profile": True, "staleness": staleness}
                    if is_async else {}
                )
                opt_name, opt_hp = self._server_opt_profile(ctx)
                if opt_name:
                    # server-opt profile rides the same INIT extension
                    # (profile-byte bit 1 + rule block); "average" ships
                    # as a hyperparam because the divide now happens
                    # server-side, inside the rule
                    hp = dict(opt_hp)
                    hp.setdefault("average", True)
                    akw["server_opt"] = opt_name
                    akw["server_opt_hp"] = hp
                for part in ctx.partitions:
                    if self._traced():
                        from byteps_tpu.core.tracing import (
                            new_trace_id,
                            span_args,
                        )

                        t_id, s_id = new_trace_id(), new_trace_id()
                        t0 = time.time()
                        self.client.init_tensor(
                            part.key, part.length, dtype_id,
                            trace=(t_id, s_id), **akw,
                        )
                        self.tracer.record_span(
                            ctx.name, "INIT", t0, time.time() - t0,
                            span_args(t_id, s_id, key=part.key),
                        )
                    else:
                        self.client.init_tensor(
                            part.key, part.length, dtype_id, **akw,
                        )
                if ctx.initialized:
                    if (on_first_init is not None and not any(
                            p.key in self._compressors
                            for p in ctx.partitions)):
                        # registry-surviving tensor on a NEW engine (a
                        # shutdown()/init() cycle): this engine holds no
                        # codec chains for it, so re-run the compressor
                        # setup like a first init — reshipping an empty
                        # chain set would silently drop the tensor to
                        # raw for the rest of the process
                        on_first_init()
                    else:
                        self._reship_compressors(ctx)
                    ctx.version = 0
                    for part in ctx.partitions:
                        self._seeded.discard(part.key)
                elif on_first_init is not None:
                    on_first_init()
                ctx.initialized = True
                ctx.server_generation = gen
                ctx.engine_epoch = self._epoch
                self._reinit_names.discard(ctx.name)
            ctx.version += 1
            for part in ctx.partitions:
                if part.key not in self._seeded:
                    self._seeded.add(part.key)
                    self._push_ready.set_ready_count(part.key, ctx.version)

    def submit_rowsparse(
        self,
        name: str,
        indices: Any,
        values: Any,
        total_rows: int,
        average: bool,
        priority: int,
        version: int,
        handle: int,
    ) -> None:
        """Row-sparse push_pull (RequestType::kRowSparsePushPull,
        common.h:267-271): push (indices, values) rows of a
        ``(total_rows, row_len)`` tensor; the server scatter-sums into the
        dense store and the pull gathers the SAME indices back — the
        embedding-gradient path.  One key, no partitioning (the reference
        likewise exempts sparse tensors from byte partitioning)."""
        import struct

        idx, vals = validate_rowsparse(indices, values, total_rows)
        nrows, row_len = vals.shape
        dtype_id = int(to_datatype(vals.dtype))

        registry = get_registry()
        ctx = registry.declare(name)
        if self._server_opt_profile(ctx)[0]:
            # the row-sparse wire path scatter-sums rows into the dense
            # store; a server-side rule would update against a partial
            # accumulator — refuse instead of training wrong
            raise ValueError(
                f"tensor {name!r}: the server-side optimizer profile "
                "does not support row-sparse push_pull (dense only)"
            )

        def build_partitions(c):
            from byteps_tpu.common.types import Partition

            c.partitions = [
                Partition(
                    key=c.key_for_part(0), offset=0, length=total_rows * row_len
                )
            ]

        self._prepare_round(ctx, dtype_id, total_rows * row_len, build_partitions)
        key = ctx.partitions[0].key

        header = struct.pack("!II", nrows, row_len)
        idx_wire = idx.astype(">u4").tobytes()
        rowsparse = {
            "push_payload": header + idx_wire + vals.tobytes(),
            "pull_req": header + idx_wire,
        }
        result = np.empty(nrows * row_len, dtype=vals.dtype)
        job = _Job(
            name, ctx, None, result, dtype_id, average, handle,
            pending=1, shape=(nrows, row_len), np_dtype=vals.dtype,
            is_jax=False, version=ctx.version, rowsparse=rowsparse,
        )
        self._stamp_job_trace(job)
        self._step_begin()
        task = TensorTableEntry(
            tensor_name=name,
            key=key,
            priority=priority,
            version=ctx.version,
            offset=0,
            length=total_rows * row_len,
            total_partnum=1,
            queue_list=[QueueType.PUSH, QueueType.PULL],
            context=job,
            job=ctx.job,
        )
        self._stamp_task_trace(task, job)
        self.queues[QueueType.PUSH].add_task(task)

    def _maybe_setup_compression(self, ctx, np_dtype: np.dtype, nbytes: int) -> None:
        """Instantiate per-partition codec chains and ship the config to the
        owning servers (InitTensor's kCompressedPushPull push,
        operations.cc:396-408).  Engages only for fp32 tensors at least
        BYTEPS_MIN_COMPRESS_BYTES large (global.cc:137)."""
        from byteps_tpu.compression.registry import create_compressor

        has_cfg = any(
            k in ctx.kwargs
            for k in ("byteps_compressor_type", "compressor")
        )
        if not has_cfg or np_dtype != np.float32:
            return
        if nbytes < self.cfg.min_compress_bytes:
            return
        ctype = str(
            ctx.kwargs.get("byteps_compressor_type")
            or ctx.kwargs.get("compressor") or "?"
        )
        for part in ctx.partitions:
            codec = create_compressor(ctx.kwargs, part.length, server=False)
            if codec is None:
                return
            self._ensure_compress_threads()
            self._compressors[part.key] = codec
            # codec identity for the fleet consensus plane
            # (docs/autotune.md): the per-key local verdicts are labeled
            # with it, and a fleet codec_off decision matches keys by it
            self._codec_names[part.key] = ctype
            with self._tuning_lock:
                if ctype in self._fleet_codec_off:
                    # registered AFTER the fleet flipped this codec off:
                    # join the decision immediately
                    self._fleet_codec_off[ctype].add(part.key)
                    self._compression_auto_off.add(part.key)
            # a chain created after set_compression_lr must still honor it
            self._apply_lr_to_chain(codec, self._compression_lr)
            # BYTEPS_COMPRESSION_AUTO, static fast path: every shipped
            # codec's wire format is size-deterministic (wire_static →
            # wire_nbytes() is EXACT, not a bound), so the policy verdict
            # is computable at registration — no probe rounds, no
            # compressed bytes wasted discovering that k ≈ n.  The probe
            # path survives only for data-dependent codecs
            # (wire_static=False — custom chains whose payload size
            # varies with the gradient).
            if self.cfg.compression_auto and getattr(
                codec, "wire_static", False
            ):
                self._auto_static_verdict(part.key, codec)
            self.client.register_compressor(part.key, ctx.kwargs)
            from byteps_tpu.core.device_codec import device_codec_for

            dc = device_codec_for(ctx.kwargs, part.length)
            if dc is not None:
                self._device_codecs[part.key] = dc
        self._maybe_send_lr()

    def _reship_compressors(self, ctx) -> None:
        """After a server resize, re-register each partition's compressor
        config with the key's (possibly new) owning server; local chains —
        and their EF/momentum state — are kept."""
        shipped = False
        for part in ctx.partitions:
            if part.key in self._compressors:
                self.client.register_compressor(part.key, ctx.kwargs)
                shipped = True
        if shipped:
            # new server-side chains start at lr=1; resend the current lr
            self._lr_sent_to_servers = 1.0
            self._maybe_send_lr()

    @staticmethod
    def _apply_lr_to_chain(codec, lr: float) -> None:
        c = codec
        while c is not None:
            setter = getattr(c, "set_lr", None)
            if setter is not None:
                setter(lr)
            c = getattr(c, "inner", None)

    def set_compression_lr(self, lr: float) -> None:
        """Feed the current learning rate to every error-feedback stage —
        the worker-side chains here AND the server-side chains over the
        wire (replaces the reference's ``lr.s`` mmap,
        vanilla_error_feedback.h:44-58 — EF residual scaling tracks lr).

        Order-independent: an lr set before any compressor exists is
        remembered and applied when chains are created (worker side) /
        sent when the first chain registers (server side); repeat calls
        with an unchanged lr produce no wire traffic."""
        self._compression_lr = lr
        for codec in list(self._compressors.values()):
            self._apply_lr_to_chain(codec, lr)
        self._maybe_send_lr()

    def _maybe_send_lr(self) -> None:
        if self._compressors and self._compression_lr != self._lr_sent_to_servers:
            self.client.set_compression_lr(self._compression_lr)
            self._lr_sent_to_servers = self._compression_lr

    def _async_profile(self, ctx) -> tuple:
        """(async?, staleness bound) for a tensor's keys (docs/async.md):
        the declare-time ``byteps_async`` / ``byteps_staleness`` kwargs
        override the process-wide ``BYTEPS_ASYNC`` /
        ``BYTEPS_STALENESS_BOUND`` — per-key profiles on one worker."""
        raw = ctx.kwargs.get("byteps_async")
        if raw is None or raw == "":
            is_async = self.cfg.async_mode
        else:
            is_async = str(raw).lower() not in ("0", "false", "no", "off")
        if not is_async:
            return False, -1
        raw_s = ctx.kwargs.get("byteps_staleness")
        bound = (
            int(raw_s) if raw_s not in (None, "")
            else self.cfg.staleness_bound
        )
        return True, max(-1, bound)

    def _server_opt_profile(self, ctx) -> tuple:
        """(rule name or None, hyperparam dict) for a tensor's keys
        (docs/architecture.md "Server-side optimizer"): the declare-time
        ``byteps_server_opt`` / ``byteps_server_opt_hp`` kwargs override
        the process-wide ``BYTEPS_SERVER_OPT`` / ``BYTEPS_SERVER_OPT_HP``
        — per-tensor rules on one worker.  ``byteps_server_opt`` accepts
        a rule name, or a falsy string to force a tensor back to plain
        SUM under a fleet-wide rule."""
        raw = ctx.kwargs.get("byteps_server_opt")
        if raw is None or raw == "":
            name = self.cfg.server_opt
        elif str(raw).lower() in ("0", "false", "no", "off"):
            name = ""
        else:
            name = str(raw).strip().lower()
        if not name:
            return None, {}
        hp_raw = ctx.kwargs.get("byteps_server_opt_hp")
        if hp_raw in (None, ""):
            hp_raw = self.cfg.server_opt_hp
        if isinstance(hp_raw, dict):
            hp = dict(hp_raw)
        else:
            from byteps_tpu.server.update_rules import parse_hp

            hp = parse_hp(hp_raw)
        return name, hp

    @staticmethod
    def _job_labels(job: int):
        """``{"job": ...}`` for a tenant task, None for the default
        namespace — job 0 mints no extra label series, so single-tenant
        deployments see exactly the pre-tenancy families."""
        return {"job": str(job)} if job else None

    # --- observability helpers (docs/observability.md) -------------------

    def _step_begin(self) -> None:
        """One push_pull job entered the pipeline.  The first job after
        a quiescent stretch opens a new step window; the flight
        recorder stamps a ledger record when the count drains back to
        zero (round completion)."""
        with self._step_lock:
            if self._step_open == 0:
                self._step_t0 = time.monotonic()
            self._step_open += 1

    def _step_end(self, job: _Job) -> None:
        """A job left the pipeline (finalized OR failed) — exactly once
        per job.  Draining the in-flight count to zero completes the
        step: the flight recorder takes its registry delta and runs the
        trigger rules on it."""
        with job.lock:
            if job.step_counted:
                return
            job.step_counted = True
        with self._step_lock:
            if self._step_open <= 0:
                return
            self._step_open -= 1
            done = self._step_open == 0
            dur = time.monotonic() - self._step_t0
        if done:
            if self.cfg.job_id:
                # per-tenant step-time slice (docs/async.md): the
                # histogram feeds the cluster aggregate's per-job p99,
                # the gauge is the live value bps_top sparklines.  Job 0
                # (the single-tenant default) mints no extra series.
                from byteps_tpu.core.telemetry import metrics

                labels = {"job": str(self.cfg.job_id)}
                metrics().observe("job_step_seconds", dur, labels=labels)
                metrics().gauge_set(
                    "job_step_last_seconds", dur, labels=labels
                )
            if self._flight is not None and self._flight.enabled:
                self._flight.record_step(dur)

    def _traced(self) -> bool:
        return (
            self.tracer is not None
            and self.tracer.enabled
            and getattr(self.tracer, "spans_enabled", True)
        )

    def _stamp_job_trace(self, job: _Job) -> None:
        """Give a job its trace id (submit runs on the caller's thread):
        the step's, where the caller is inside a ``tracing.span``, so the
        step's phases, the job's stage spans and the server's child spans
        join one trace; a fresh one otherwise."""
        if self._traced():
            from byteps_tpu.core.tracing import current_span, new_trace_id

            job.trace_id, job.parent_span = current_span() or (new_trace_id(), 0)

    def _stamp_task_trace(self, task: TensorTableEntry, job: _Job) -> None:
        """Give a partition task its span under the job's trace.  The
        span id is FIXED for the task's lifetime: every RPC attempt
        (retries included) carries the same id, so the server's
        dedupe-annotated child spans join the right worker span."""
        if job.trace_id:
            from byteps_tpu.core.tracing import new_trace_id

            task.trace_id = job.trace_id
            task.span_id = new_trace_id()

    def _task_trace(self, task: TensorTableEntry):
        """Wire trace context for a task's RPCs, or None when off."""
        return (task.trace_id, task.span_id) if task.trace_id else None

    # --- stage bodies ----------------------------------------------------

    def _proceed(self, task: TensorTableEntry) -> None:
        """FinishOrProceed (core_loops.cc:31-137): stamp the finished stage,
        advance to the next queue or finish the partition."""
        finished = task.queue_list.pop(0)
        job: _Job = task.context
        if self.cfg.debug_sample_tensor and self.cfg.debug_sample_tensor in job.name:
            # value sampling per stage (BYTEPS_DEBUG_SAMPLE_TENSOR,
            # core_loops.cc:37-67) — the race-diagnosis tool
            from byteps_tpu.common import logging as bpslog

            if job.device_codec and finished in (
                QueueType.DECOMPRESS, QueueType.COPYH2D,
            ):
                # device-codec jobs never write job.result — the decoded
                # partition lives on device; sample it (device_get) rather
                # than the uninitialized host buffer
                part = job.device_parts.get(task.offset)
                buf = None if part is None else np.asarray(part)
            elif finished in (QueueType.DECOMPRESS, QueueType.COPYH2D) or (
                finished == QueueType.PULL and task.compressed is None
            ):
                # pull-side stages: sample what came BACK.  For compressed
                # tensors job.result is only written at DECOMPRESS, so the
                # PULL stage is skipped (payload is codec wire bytes).
                buf = job.result[task.offset : task.offset + task.length]
            elif finished == QueueType.PULL:
                buf = None
            else:
                buf = task.cpubuff
            if buf is not None and buf.size:
                bpslog.info(
                    "sample %s key=%d stage=%s v=%d norm=%.6g first=%.6g",
                    job.name, task.key, finished.name, task.version,
                    float(np.linalg.norm(buf.astype(np.float64))), float(buf[0]),
                )
        if self.tracer is not None and self.tracer.enabled:
            self.tracer.record(
                job.name, finished.name, job.t0, time.time() - job.t0, job.version
            )
        # per-stage dwell, ENQUEUE→done: the latency dimension the flat
        # counters never had — p99 here names the stalled stage directly
        if task.enqueued_at:
            self._dwelt[finished].observe(time.monotonic() - task.enqueued_at)
        if task.trace_id and self._traced():
            from byteps_tpu.core.tracing import span_args

            self.tracer.record_span(
                job.name, finished.name, task.enqueued_wall,
                time.time() - task.enqueued_wall,
                span_args(task.trace_id, task.span_id, job.parent_span,
                          key=task.key, version=task.version),
            )
        self.queues[finished].report_finish(task)
        if task.queue_list:
            self.queues[task.queue_list[0]].add_task(task)
            return
        # partition fully round-tripped (push ACKed AND pull answered):
        # re-arm the key's PUSH gate so the next round may leave.  Re-arming
        # any earlier would let the server publish round N+1 before this
        # round's pull was served — the server hands pulls the LATEST
        # completed round (version <= store_version, server.cc:376-409)
        self._push_ready.add_ready_count(task.key)
        self.queues[QueueType.PUSH].notify()
        self.queues[QueueType.FUSE].notify()
        with job.lock:
            job.pending -= 1
            done = job.pending == 0
        if done:
            # close the step window BEFORE the handle completes: a
            # synchronous trainer resubmits the moment mark_done wakes
            # it, and a _step_begin racing in ahead of _step_end would
            # merge two rounds into one record (and skew the slow-step
            # rolling median)
            self._step_end(job)
            from byteps_tpu.core.tracing import span

            step = (job.trace_id, job.parent_span) if job.trace_id else None
            with span("engine.finalize", parent=step):
                self._finalize(job)

    def _fail_job(self, job: _Job, status: Status) -> None:
        from byteps_tpu.core.state import get_state

        self._return_target(job, reusable=False)
        # step window closes before the handle completes — same
        # resubmission race as the _finalize path
        self._step_end(job)
        get_state().handles.mark_done(job.handle, None, status)

    def _fail_task(self, task: TensorTableEntry, stage: QueueType,
                   reason: str, degraded: bool = False) -> None:
        """Fail a task exactly once: return credits, advance the key's
        round allowance (a failed round can never advance it by completing),
        and surface the error on the handle — callers must never hang in
        synchronize() on a dead cluster.

        ``degraded`` (connection-class failures): the handle raises
        DegradedError — retryable — and the tensor is marked for a forced
        re-init barrier on its next submit.  The abandoned round skewed
        the key's version sequence between client and (possibly new)
        servers; the barrier resets both sides so a resubmitted step's
        pulls can actually complete instead of pending forever.

        Two paths can race here for one task — a stage-thread exception and
        the dead-connection error callback — so the job lock + task.failed
        guard makes the second a no-op (credits and the version allowance
        must not be double-counted)."""
        job = task.context
        if isinstance(job, _FusionGroup):
            # a GROUP task failing (stage-thread exception escaping
            # _push_group) has no job/handle of its own — return its
            # credit once and route the failure to its members, which own
            # all the real accounting.  Without this branch the generic
            # path would touch _Job-only fields and kill the PUSH stage
            # thread, stalling the whole pipeline.
            with job.lock:
                if job.done:
                    return
                job.done = True
            self.queues[QueueType.PUSH].report_finish(task)
            for mtask, _ in job.members:
                self._fail_task(mtask, QueueType.FUSE, reason, degraded=degraded)
            return
        with job.lock:
            if task.failed:
                return
            task.failed = True
            job.failed = True  # abort fence: stops sibling tasks' retries
        # a FUSE-routed task that died before reaching the fusion buffer
        # must leave the staging window, or the pinned counter disables
        # idle flushing forever
        self._unstage_small(task)
        self.queues[stage].report_finish(task)
        self._push_ready.add_ready_count(task.key)
        self.queues[QueueType.PUSH].notify()
        self.queues[QueueType.FUSE].notify()
        if degraded:
            from byteps_tpu.core.telemetry import counters

            counters().bump("degraded_jobs")
            self._reinit_names.add(job.name)
            self._fail_job(job, Status.Degraded(f"{stage.name}: {reason}"))
        else:
            self._fail_job(job, Status.Aborted(f"{stage.name}: {reason}"))

    def _finalize(self, job: _Job) -> None:
        """All partitions done: assemble, reshape, hand back.

        A jax job arrives with every partition already on the device
        (``device_parts``: COPYH2D put it there, or a device codec decoded
        it there), so what is left is a device-side concatenate + reshape
        and the release of what the job held.  A numpy job (the
        torch/TF/MXNet plugins) averages and reshapes its host buffer
        (the plugin-side div by size, torch/ops.cc:78-91)."""
        from byteps_tpu.core.state import get_state

        if job.is_jax:
            parts = [job.device_parts[off] for off in sorted(job.device_parts)]
            job.device_parts = job.result = None
            if job.holds_target:
                # the next round's PULLs overwrite the bytes these puts
                # read: a put is done with them when its array is ready.
                # All but the newest finished while later partitions were
                # still on the wire
                import jax

                jax.block_until_ready(parts)
                self._return_target(job, reusable=True)
            out = _assemble(parts, job.shape, job.placement)
            del parts
            if job.device_codec and job.average:
                # raw and host-codec partitions were averaged on the host,
                # before their put (_h2d)
                out = out / self.client.num_workers
            get_state().handles.mark_done(job.handle, out)
            return
        out = job.result
        if job.average and np.issubdtype(job.np_dtype, np.floating):
            out = out / self.client.num_workers
        get_state().handles.mark_done(job.handle, out.reshape(job.shape))

    def _h2d(self, buf: np.ndarray, average: bool, where=None):
        """One host buffer onto the device (a partition on the COPYH2D
        thread; the healed tensor in heal_degraded) — the default one, or
        ``where`` its job's placement says: ONE ``device_put`` either way.
        The average is taken in place first — the same divide as the numpy
        path's ``out / num_workers``, on bytes still in cache, and none
        with one worker (``x / 1 == x`` bit for bit).  device_put returns
        with the transfer issued; jax keeps ``buf`` alive until it
        completes, and nothing writes those bytes before then: a buffer
        that outlives its job (the tensor's pull target) goes back only
        when every put of the round is ready (``_finalize``)."""
        import jax

        from byteps_tpu.core.telemetry import counters

        n = self.client.num_workers
        if average and n != 1 and np.issubdtype(buf.dtype, np.floating):
            with releasing():
                np.divide(buf, n, out=buf)
        counters().bump("h2d_bytes", buf.nbytes)
        with releasing("copyh2d.put"):
            return jax.device_put(buf, where)

    # --- recovery plane (docs/robustness.md "healing flow") --------------

    def heal_degraded(self, name: str, tensor: Any, average: bool):
        """In-place recovery for a tensor whose last job failed degraded
        while the cluster topology stayed put (one-sided degradation):
        resync every owning server — replaying the journaled pushes they
        never absorbed, which completes the abandoned round with the
        ORIGINAL payloads — then pull the published round and hand the
        caller the result it would have gotten fault-free.  Peers never
        block and no re-init barrier runs; on success the tensor's
        forced-re-init mark is cleared so its next submit continues the
        version sequence in place.

        Returns the aggregated (and averaged/reshaped) result, or None
        when in-place heal is not possible — topology changed under the
        job (the cluster-coherent re-init path owns that), compressed or
        device-codec keys (their pull needs the codec pipeline), resync
        refused (server restarted, journal gap, pre-parity binary), or the
        healed round's pull timed out.  The caller then falls back to
        the resubmit-with-re-init path, which is the pre-recovery
        behavior."""
        registry = get_registry()
        if not registry.is_declared(name):
            return None
        ctx = registry.get(name)
        gen = getattr(self.client, "server_generation", 0)
        with self._init_lock:
            if (name not in self._reinit_names or not ctx.initialized
                    or ctx.engine_epoch != self._epoch
                    or ctx.server_generation != gen or not ctx.partitions):
                return None
        if any(
            p.key in self._compressors or p.key in self._device_codecs
            for p in ctx.partitions
        ):
            return None
        import jax

        is_jax = isinstance(tensor, jax.Array)
        np_dtype = (
            np.dtype(tensor.dtype) if hasattr(tensor, "dtype")
            else np.asarray(tensor).dtype
        )
        shape = np.shape(tensor)
        total = sum(p.length for p in ctx.partitions)
        if int(np.prod(shape, dtype=np.int64)) != total:
            return None
        dtype_id = int(to_datatype(np_dtype))
        version = ctx.version
        # 1. resync each owning server: the replay of journaled pushes is
        # what completes the abandoned round server-side
        route_keys: Dict[int, int] = {}
        for p in ctx.partitions:
            try:
                route_keys.setdefault(self.client.server_for(p.key), p.key)
            except (ValueError, ZeroDivisionError, IndexError):
                return None
        for key in route_keys.values():
            if not self.client.resync_in_place(key):
                return None
        # 2. pull the (now completable) round into a fresh result buffer;
        # the pull's own retry/heal machinery applies per attempt
        result = np.empty(total, dtype=np_dtype)
        timeout = max(
            10.0,
            self.cfg.resync_deadline_s
            + (self.cfg.rpc_deadline_s or 1.0) * (self.cfg.rpc_retries + 1),
        )
        from byteps_tpu.comm.ps_client import _ZERO_COPIED

        # issue every partition's pull first, then wait: one round-trip
        # (and at worst one timeout) for the whole tensor, not P of them
        pending = []
        for p in ctx.partitions:
            done = threading.Event()
            box: dict = {}
            sink = memoryview(result).cast("B")[
                p.offset * np_dtype.itemsize
                : (p.offset + p.length) * np_dtype.itemsize
            ]

            def on_pull(payload, _box=box, _done=done):
                _box["payload"] = payload
                _done.set()

            self.client.pull(
                p.key, version, on_pull, dtype_id=dtype_id, sink=sink,
                on_error=lambda _done=done: _done.set(),
            )
            pending.append((p, done, box))
        deadline = time.monotonic() + timeout
        for p, done, box in pending:
            if not done.wait(max(0.0, deadline - time.monotonic())) or (
                "payload" not in box
            ):
                return None  # round still incomplete: fall back to re-init
            payload = box["payload"]
            if payload is not _ZERO_COPIED:
                arr = np.frombuffer(payload, dtype=np_dtype)
                result[p.offset : p.offset + p.length] = arr[: p.length]
        with self._init_lock:
            self._reinit_names.discard(name)
        if is_jax:
            return self._h2d(result, average).reshape(shape)
        out = result
        if average and np.issubdtype(np_dtype, np.floating):
            out = out / self.client.num_workers
        return out.reshape(shape)

    def _copy_d2h_once(self, task: TensorTableEntry) -> None:
        """Per-partition device→host staging (COPYD2H, core_loops.cc:378-443).

        A jax partition whose copy ``submit`` started (``job.d2h_parts``)
        is only COLLECTED here: ``np.asarray`` waits, without the GIL, for
        a transfer that is complete or in flight, and the device slice is
        dropped.  The PUSH thread is already sending early partitions over
        DCN while later ones are still coming off the device (and while
        the caller's next jitted step runs).  A sharded jax tensor is
        sliced and read here, a partition at a time; numpy inputs take a
        zero-copy slice view.

        Device-codec jobs invert the reference's order (compress AFTER
        staging, core_loops.cc:498-536): the Pallas/jnp packer runs on the
        DEVICE slice first, and what crosses the device→host boundary here
        is the compressed payload — 32× less for onebit."""
        from byteps_tpu.core.telemetry import counters

        job: _Job = task.context
        if job.device_codec:
            dc = self._device_codecs[task.key]
            sl = job.flat[task.offset : task.offset + task.length]
            task.compressed = dc.compress(sl)  # D2H of the packed payload
            # the headline device-path number: bytes that actually
            # crossed the device→host boundary — compressed, vs the
            # host path's raw staging below (docs/observability.md)
            counters().bump("d2h_bytes", len(task.compressed))
            self._proceed(task)
            return
        if job.d2h_parts is not None:
            # the wait for the copy in flight, by name: a gap of the device
            # under this stage is the chip's copy or the thread's Python
            with releasing("copyd2h.wait"):
                task.cpubuff = np.asarray(job.d2h_parts.pop(task.offset)).reshape(-1)
            counters().bump("d2h_prefetched_parts")
        else:
            sl = job.flat[task.offset : task.offset + task.length]
            if isinstance(sl, np.ndarray):
                task.cpubuff = sl
            else:
                with releasing("copyd2h.wait"):
                    task.cpubuff = np.asarray(sl)
        if job.is_jax:
            counters().bump("d2h_bytes", task.cpubuff.nbytes)
        self._proceed(task)

    def _unstage_small(self, task: TensorTableEntry) -> None:
        """A FUSE-routed task left the staging window: it reached the
        fusion buffer (visible to the drain) or died upstream.  Exactly
        once per task — the idle-flush check (staged == 0 AND FUSE queue
        empty) must neither miss a small still in COPYD2H/COMPRESS nor
        stay pinned by one that failed there.  The test-and-clear runs
        under the fuse lock: _fuse_once and a racing _fail_task (a
        sibling's failure fanning out mid-stage) must not both
        decrement, or the counter goes negative and idle flush never
        fires again."""
        with self._fuse_lock:
            if task.fuse_staged:
                task.fuse_staged = False
                self._staged_smalls -= 1

    def _compress_once(self, task: TensorTableEntry) -> None:
        """COMPRESS stage (core_loops.cc:498-536): run the codec chain on
        the staged partition.  Stripe routing (key % pool size in
        _StripedStage) pins each key to one thread, so a key's stateful
        EF/momentum buffers never race across rounds while different keys
        compress in parallel."""
        if task.compressed is not None:
            # already packed on device in COPYD2H; stage is a pass-through
            # so traces keep the reference pipeline shape
            self._proceed(task)
            return
        codec = self._compressors[task.key]
        raw_nbytes = task.cpubuff.nbytes
        task.compressed = codec.compress(task.cpubuff)
        # wire-savings telemetry + the adaptive-compression policy feed
        # (docs/gradient-compression.md "Codec auto-selection")
        self._note_compression(task.key, raw_nbytes, len(task.compressed))
        self._proceed(task)

    def _apply_tuning(self, t: dict) -> None:
        """Adopt one fleet ``tuning`` section (docs/autotune.md) —
        invoked by the PS client on every newer-epoch book (and once at
        registration with the current section).  The fusion threshold
        is a single int store each submit() reads fresh, so adoption is
        atomic per round; codec flips move keys in/out of the
        auto-off set under the tuning lock."""
        from byteps_tpu.common import logging as bpslog
        from byteps_tpu.core.telemetry import counters, metrics

        ft = t.get("fusion_threshold")
        if ft is None:
            # field absent = "untouched": restore the launch value (a
            # reborn scheduler's fresh tuning state, or a tuner that
            # reverted to pre-tuner placement, must actually land)
            ft = self._launch_fusion_threshold
        if self._fuse_enabled:
            # never turns fusion ON from 0: the FUSE stage only exists
            # when the launch config enabled it (start() spawns no
            # poller otherwise) — the tuner's policy holds the same line
            try:
                ft = int(ft)
            except (TypeError, ValueError):
                ft = 0
            if ft > 0 and ft != self.cfg.fusion_threshold:
                bpslog.warning(
                    "autotune: fleet fusion threshold %d -> %d bytes",
                    self.cfg.fusion_threshold, ft,
                )
                self.cfg.fusion_threshold = ft
                metrics().gauge_set("fusion_threshold_bytes", ft)
        off = {str(n) for n in (t.get("codec_off") or ())}
        with self._tuning_lock:
            for name in sorted(off - set(self._fleet_codec_off)):
                keys = {
                    k for k, n in self._codec_names.items()
                    if n == name and k not in self._compression_auto_off
                }
                self._fleet_codec_off[name] = keys
                self._compression_auto_off.update(keys)
                if keys:
                    counters().bump(
                        "tune_codec_off", len(keys), labels={"codec": name}
                    )
                bpslog.warning(
                    "autotune: fleet codec consensus disabled %r "
                    "(%d local keys flip to raw)", name, len(keys),
                )
            for name in sorted(set(self._fleet_codec_off) - off):
                # rollback: re-enable exactly the keys the FLEET
                # decision disabled — locally-verdicted keys stay off
                keys = self._fleet_codec_off.pop(name)
                self._compression_auto_off.difference_update(keys)
                bpslog.warning(
                    "autotune: fleet codec decision on %r rolled back "
                    "(%d keys compress again)", name, len(keys),
                )
            # third arm (docs/gradient-compression.md "Lossless frame
            # compression"): adopt the fleet's codec_lossless names —
            # this engine's raw-pushing keys under a named codec start
            # shipping the wire lossless container.  Gated on the SAME
            # master switch as the probe: a worker with
            # BYTEPS_WIRE_LOSSLESS off ignores the names entirely so a
            # mixed-knob fleet never emits frames its peers can't want.
            from byteps_tpu.comm.transport import wire_lossless_enabled

            lz = {str(n) for n in (t.get("codec_lossless") or ())}
            if not wire_lossless_enabled():
                lz = set()
            for name in sorted(lz - set(self._fleet_codec_lossless)):
                keys = {
                    k for k, n in self._codec_names.items()
                    if n == name
                    and k in self._compression_auto_off
                    and k not in self._lossless_keys
                }
                self._fleet_codec_lossless[name] = keys
                self._lossless_keys.update(keys)
                if keys:
                    counters().bump(
                        "tune_codec_lossless", len(keys),
                        labels={"codec": name},
                    )
                bpslog.warning(
                    "autotune: fleet lossless arm on %r "
                    "(%d local raw keys ship the lossless frame)",
                    name, len(keys),
                )
            for name in sorted(set(self._fleet_codec_lossless) - lz):
                # rollback mirrors codec_off: exactly the fleet-marked
                # keys drop the transform; probe-verdicted keys keep it
                keys = self._fleet_codec_lossless.pop(name)
                self._lossless_keys.difference_update(keys)
                bpslog.warning(
                    "autotune: fleet lossless arm on %r rolled back "
                    "(%d keys push plain raw again)", name, len(keys),
                )

    def _auto_static_verdict(self, key: int, codec) -> None:
        """Registration-time verdict of the adaptive-compression policy
        for a size-deterministic codec: the exact wire ratio is
        ``wire_nbytes() / raw fp32 bytes``, so the key's fate is known
        before any round ships.  Either way the probe is marked complete
        (``_auto_stats[key] = None``) so ``_note_compression`` never
        accumulates probe state for it."""
        from byteps_tpu.core.telemetry import RATIO_BUCKETS, counters, metrics

        ratio = codec.wire_nbytes() / max(1, codec.size * 4)
        metrics().observe("compression_ratio", ratio, buckets=RATIO_BUCKETS)
        self._auto_stats[key] = None  # probe complete at registration
        if ratio < self.cfg.compression_auto_ratio:
            return
        self._compression_auto_off.add(key)
        # codec-labeled so the scheduler's codec_consensus policy can
        # count verdicts per codec per worker (docs/autotune.md); the
        # flat family keeps the pre-tuner totals
        counters().bump(
            "compression_auto_off",
            labels={"codec": self._codec_names.get(key, "?")},
        )
        from byteps_tpu.common import logging as bpslog

        bpslog.warning(
            "compression auto-disabled for key %d at registration: static "
            "wire ratio %.3f >= %.3f (BYTEPS_COMPRESSION_AUTO; codec wire "
            "size is deterministic, no probe rounds needed); rounds push "
            "raw", key, ratio, self.cfg.compression_auto_ratio,
        )

    def _lossless_probe(self, key: int, payload) -> None:
        """Third arm of the adaptive-compression policy (docs/gradient-
        compression.md "Lossless frame compression"): a key whose lossy
        codec lost the auto verdict pushes raw — probe ONE raw payload's
        byte entropy and, when it reads compressible (at or below
        BYTEPS_LOSSLESS_ENTROPY bits/byte), trial-run the wire lossless
        container.  A real win (>= 10% smaller) turns the transform on
        for this key's later pushes and casts the codec-labeled
        ``compression_auto_lossless`` vote the scheduler's
        codec_lossless quorum counts (docs/autotune.md).  One probe per
        key per engine; requires BYTEPS_WIRE_LOSSLESS so a fleet that
        keeps the wire feature off never sees a flagged frame."""
        self._lossless_probed.add(key)
        from byteps_tpu.comm.transport import wire_lossless_enabled

        if not wire_lossless_enabled():
            return
        from byteps_tpu.compression.lossless import (
            MIN_BYTES,
            byte_entropy,
            compress_frame,
            lossless_entropy_cutoff,
        )

        raw = bytes(payload[:65536])
        if len(raw) < MIN_BYTES:
            return
        ent = byte_entropy(raw)
        if ent > lossless_entropy_cutoff():
            return
        comp = compress_frame(raw)
        if len(comp) * 10 > len(raw) * 9:
            return  # entropy looked low but the LZ pass found no win
        with self._tuning_lock:
            self._lossless_keys.add(key)
        from byteps_tpu.core.telemetry import counters

        counters().bump(
            "compression_auto_lossless",
            labels={"codec": self._codec_names.get(key, "?")},
        )
        from byteps_tpu.common import logging as bpslog

        bpslog.warning(
            "lossless arm enabled for key %d: raw push entropy %.2f "
            "bits/byte, trial container %.2fx (BYTEPS_COMPRESSION_AUTO "
            "third arm); later pushes ship the wire lossless frame",
            key, ent, len(raw) / max(1, len(comp)),
        )

    def _note_compression(self, key: int, raw_nbytes: int,
                          comp_nbytes: int) -> None:
        """Record one compression's observed wire outcome and, with
        BYTEPS_COMPRESSION_AUTO on, run the per-key policy: after the
        probe rounds a key whose mean wire ratio (compressed/raw) is at
        or above the cutoff stops compressing — its later rounds take
        the raw pipeline (tiny tensors, k too close to n, codec overhead
        beating the savings).  Worker-local and per-key: the server
        serves raw traffic on a codec-registered key correctly (the
        mixed-config rule), so no wire coordination is needed.  Runs on
        the key's COMPRESS stripe thread, so per-key stats never race."""
        from byteps_tpu.core.telemetry import RATIO_BUCKETS, counters, metrics

        if comp_nbytes < raw_nbytes:
            counters().bump("wire_bytes_saved", raw_nbytes - comp_nbytes)
        # unlabeled on purpose: a per-key label would mint one histogram
        # series per compressed partition (unbounded cardinality — every
        # other label in the registry is bounded); the policy keeps its
        # per-key state in _auto_stats, and per-key wire sizes are
        # observable server-side via native_request_bytes{key}
        metrics().observe(
            "compression_ratio", comp_nbytes / max(1, raw_nbytes),
            buckets=RATIO_BUCKETS,
        )
        if not self.cfg.compression_auto or key in self._compression_auto_off:
            return
        st = self._auto_stats.get(key, False)
        if st is None:
            return  # probe complete, verdict was KEEP — stop tracking
        if st is False:
            st = self._auto_stats[key] = [0, 0, 0]
        st[0] += 1
        st[1] += comp_nbytes
        st[2] += raw_nbytes
        if st[0] < self.cfg.compression_auto_rounds:
            return
        ratio = st[1] / max(1, st[2])
        if ratio < self.cfg.compression_auto_ratio:
            self._auto_stats[key] = None  # keep the codec; one verdict
            return
        self._auto_stats.pop(key, None)
        from byteps_tpu.common import logging as bpslog

        # one verdict per key per engine (either way): the shipped
        # codecs' wire sizes are size-deterministic, so the observed
        # ratio cannot drift across the cutoff later
        self._compression_auto_off.add(key)
        counters().bump(
            "compression_auto_off",
            labels={"codec": self._codec_names.get(key, "?")},
        )
        bpslog.warning(
            "compression auto-disabled for key %d: observed wire "
            "ratio %.3f >= %.3f over %d rounds (BYTEPS_COMPRESSION_"
            "AUTO); later rounds push raw", key, ratio,
            self.cfg.compression_auto_ratio, st[0],
        )

    def _fuse_once(self, task: TensorTableEntry) -> None:
        """FUSE stage: stage a small partition into its destination
        server's fusion buffer instead of issuing a per-key push RPC.
        Compressed members (the COMPRESSED_FUSED pipeline) stage their
        codec wire bytes — what rides the member slot is exactly what an
        unfused compressed push would have sent.  Tasks leave the FUSE
        queue in priority order (and round-gated per key, same as PUSH),
        so packs fill highest-priority-first; the flushed group then
        re-enters the PUSH queue carrying the max member priority."""
        if task.compressed is not None:
            payload = task.compressed
        else:
            buf = task.cpubuff
            payload = (
                buf.data.cast("B") if buf.flags.c_contiguous
                else buf.tobytes()
            )
        self._fuser.add(task, payload)
        self._unstage_small(task)
        with self._fuse_lock:
            staging = self._staged_smalls
        if staging == 0 and self.queues[QueueType.FUSE].pending() == 0:
            # pipeline drained: every submitted small has reached the
            # buffer and none wait in the FUSE queue — this burst is over,
            # ship what we have rather than paying the cycle-timer latency
            # on every quiet round.  (Checking the FUSE queue alone is not
            # enough: the upstream stages feed us one task at a time and a
            # popped-but-unstaged task is invisible to pending() — that's
            # what the _staged_smalls counter tracks.)
            self._fuser.drain_idle()

    def _push_group(self, group_task: TensorTableEntry, group: _FusionGroup) -> None:
        """Ship one fusion pack as a single multi-key Op.FUSED RPC and fan
        the multi-key reply back out to the member tasks' PULL stages."""
        from byteps_tpu.core.telemetry import counters

        members = group.members

        def finish_group() -> bool:
            """Group bookkeeping exactly once (credit return); True for
            the winner of the deliver/on_error race."""
            with group.lock:
                if group.done:
                    return False
                group.done = True
            self.queues[QueueType.PUSH].report_finish(group_task)
            return True

        # the pack was grouped under the server mapping at FUSE time; an
        # elastic resize may have re-homed members since.  A frame whose
        # members no longer share a destination would ship keys to a
        # server that never initialized them — unfuse instead (per-key
        # pushes re-route per retry, surviving the resize like the
        # unfused path always has)
        sids = {self.client.server_for(mtask.key) for mtask, _ in members}
        if len(sids) > 1:
            if finish_group():
                self._unfuse_members(group, "server set resized under pack")
            return

        # per-member compressed flag: the member cmd Cantor-encodes the
        # request type, so a compressed member rides the SAME fused frame
        # as raw siblings with COMPRESSED_PUSH_PULL in its cmd — the
        # server routes it through the key's codec chain (decompress or
        # sparse-sum) and returns its reply slot codec-compressed.  Old
        # decoders already parse the cmd field, so no new wire bit is
        # needed (docs/gradient-compression.md "Compressed wire path").
        wire = [
            (
                mtask.key,
                get_command_type(
                    RequestType.COMPRESSED_PUSH_PULL
                    if mtask.compressed is not None
                    else RequestType.DEFAULT_PUSH_PULL,
                    mtask.context.dtype_id,
                ),
                mtask.version,
                payload,
            )
            for mtask, payload in members
        ]
        nbytes = sum(len(p) for _, _, _, p in wire)
        if self.telemetry is not None:
            self.telemetry.record(nbytes)
        counters().bump("fused_frames")
        counters().bump("fused_keys", len(members))
        counters().bump("wire_tx_bytes", nbytes,
                        labels=self._job_labels(group_task.job))
        if self._journal is not None:
            # each member journals individually: a resync replay re-sends
            # them as plain per-key pushes, which the server sums through
            # the same per-(worker, key) ledger a fused member uses
            for key, cmd, version, payload in wire:
                self._journal.record(key, version, cmd, payload, fused=True)

        # pack span: its own trace (members each belong to their jobs'
        # traces; their span ids ride the fused body's trailer so the
        # server can stamp per-member children) — fixed per frame, so a
        # RETRIED frame keeps the pack span and every member span
        pack_trace = None
        member_spans = None
        t_pack = time.time()
        if self._traced():
            from byteps_tpu.core.tracing import new_trace_id

            pack_trace = (new_trace_id(), new_trace_id())
            member_spans = [mtask.span_id for mtask, _ in members]

        def deliver(replies: list) -> None:
            if not finish_group():
                return
            if pack_trace is not None:
                from byteps_tpu.core.tracing import span_args

                self.tracer.record_span(
                    "<fused>", "FUSED_RPC", t_pack, time.time() - t_pack,
                    span_args(pack_trace[0], pack_trace[1],
                              keys=len(members)),
                )
            by_key = {key: payload for key, _ver, payload in replies}
            for mtask, _ in members:
                payload = by_key.get(mtask.key)
                if payload is None or mtask.context.failed:
                    self._fail_task(
                        mtask, QueueType.FUSE,
                        "fused reply missing member key"
                        if payload is None else "job aborted",
                        degraded=True,
                    )
                    continue
                mtask.fused_reply = payload
                self._proceed(mtask)  # FUSE done → PULL delivers locally

        def on_error() -> None:
            # fused retries exhausted (or the reply was malformed): fall
            # back to per-key unfused push+pull rather than failing the
            # members outright — per-key RPCs re-route on every retry, so
            # whatever broke the FRAME (resize mid-retry, a server that
            # can't serve fused traffic) doesn't have to cost the step.
            # A genuinely dead cluster still fails through the unfused
            # path's own retry budget, same as it always did.
            if not finish_group():
                return
            self._unfuse_members(group, "fused frame failed")

        self.client.push_fused(
            wire,
            cb=deliver,
            on_error=on_error,
            # the frame is abandoned only when EVERY member's job is —
            # one live member keeps the whole pack (and its siblings'
            # cleanup-by-delivery) in flight
            abort_check=lambda: all(m.context.failed for m, _ in members),
            trace=pack_trace,
            member_spans=member_spans,
        )

    def _unfuse_members(self, group: _FusionGroup, reason: str) -> None:
        """Fall back to per-key unfused push+pull for every live member of
        a pack that can't (or repeatedly didn't) ship as one frame.  The
        member re-enters the PUSH queue in place of its FUSE stage — its
        round allowance still holds (version gates are never consumed), so
        this is exactly the pipeline the partition would have taken with
        fusion off.  One-way: a fallback push that fails again surfaces
        through the normal per-task error path, no re-fusing loop."""
        from byteps_tpu.core.telemetry import counters

        counters().bump("fused_fallback")
        for mtask, _ in group.members:
            if mtask.context.failed or (
                not mtask.queue_list or mtask.queue_list[0] != QueueType.FUSE
            ):
                self._fail_task(
                    mtask, QueueType.FUSE, f"unfuse fallback: {reason}",
                    degraded=True,
                )
                continue
            mtask.queue_list[0] = QueueType.PUSH
            self.queues[QueueType.PUSH].add_task(mtask)

    def _push_once(self, task: TensorTableEntry) -> None:
        """Priority-ordered ZPush (RunPushLoopOnce, core_loops.cc:538-582)."""
        job = task.context
        if isinstance(job, _FusionGroup):
            self._push_group(task, job)
            return
        if job.rowsparse is not None:
            payload = job.rowsparse["push_payload"]
            rtype = RequestType.ROW_SPARSE_PUSH_PULL
        elif task.compressed is not None:
            payload = task.compressed
            rtype = RequestType.COMPRESSED_PUSH_PULL
        else:
            # zero-copy send: hand the staged partition's buffer straight
            # to the scatter-gather sendmsg (no tobytes() copy); fall back
            # to a copy only for non-contiguous staging buffers
            buf = task.cpubuff
            payload = (
                buf.data.cast("B")
                if buf.flags.c_contiguous
                else buf.tobytes()
            )
            rtype = RequestType.DEFAULT_PUSH_PULL
            if (
                self.cfg.compression_auto
                and task.key in self._compression_auto_off
                and task.key not in self._lossless_probed
            ):
                self._lossless_probe(task.key, payload)
        # third tuner arm: a raw-pushing key the entropy probe (or a
        # fleet codec_lossless decision) marked ships inside the wire
        # lossless container.  Compressed/rowsparse payloads never
        # qualify — the lossy codec already owns their bytes.
        lossless = (
            rtype == RequestType.DEFAULT_PUSH_PULL
            and task.key in self._lossless_keys
        ) or None
        if self.telemetry is not None:
            self.telemetry.record(len(payload))
        from byteps_tpu.core.telemetry import counters

        counters().bump("wire_tx_bytes", len(payload),
                        labels=self._job_labels(task.job))
        if self._journal is not None:
            # recovery plane: journal the exact wire payload BEFORE the
            # send, so a give-up on this very RPC can already replay it
            self._journal.record(
                task.key, task.version,
                get_command_type(rtype, job.dtype_id), payload,
            )
        self.client.push(
            task.key, payload, job.dtype_id, task.version,
            cb=lambda: self._proceed(task),
            request_type=rtype, lossless=lossless,
            on_error=lambda: self._fail_task(
                task, QueueType.PUSH, "server connection lost", degraded=True
            ),
            abort_check=lambda: job.failed,
            trace=self._task_trace(task),
        )

    def _pull_once(self, task: TensorTableEntry) -> None:
        """ZPull into the result buffer (RunPullLoopOnce,
        core_loops.cc:584-618)."""
        job: _Job = task.context
        # compressed-ness is a property of the TASK's pipeline, not of the
        # key: an auto-disabled key keeps its registered codec chain but
        # its later rounds ride the raw pipeline, and the pull must match
        # what this round's push actually sent
        compressed = (
            len(task.queue_list) > 1
            and task.queue_list[1] == QueueType.DECOMPRESS
        )
        if task.fused_reply is not None:
            # fused member: the multi-key reply already carried this key's
            # merged round — deliver locally, no wire pull.  Compressed
            # members' reply slots are codec-compressed (the server
            # compressed the merged round once); route them to DECOMPRESS
            # exactly like an unfused compressed pull's payload.
            payload = task.fused_reply
            task.fused_reply = None
            if self.telemetry is not None:
                self.telemetry.record(len(payload))
            from byteps_tpu.core.telemetry import counters

            counters().bump("wire_rx_bytes", len(payload),
                            labels=self._job_labels(task.job))
            if compressed:
                task.compressed = payload  # decoded by DECOMPRESS stage
            else:
                arr = np.frombuffer(payload, dtype=job.np_dtype)
                job.result[task.offset : task.offset + task.length] = (
                    arr[: task.length]
                )
            self._proceed(task)
            return

        if job.rowsparse is not None:
            def on_rs_pull(payload: bytes) -> None:
                from byteps_tpu.core.telemetry import counters

                if self.telemetry is not None:
                    self.telemetry.record(len(payload))
                counters().bump("wire_rx_bytes", len(payload),
                            labels=self._job_labels(task.job))
                arr = np.frombuffer(payload, dtype=job.np_dtype)
                job.result[: arr.size] = arr
                del arr
                release_frame(payload)  # copied out: its last holder
                self._proceed(task)

            self.client.pull(
                task.key, task.version, on_rs_pull, dtype_id=job.dtype_id,
                request_type=RequestType.ROW_SPARSE_PUSH_PULL,
                payload=job.rowsparse["pull_req"],
                on_error=lambda: self._fail_task(
                    task, QueueType.PULL, "server connection lost",
                    degraded=True,
                ),
                abort_check=lambda: job.failed,
                trace=self._task_trace(task),
            )
            return

        # zero-copy receive target: the partition's byte range of the
        # result buffer — the aggregated payload lands there directly
        # (ZPull into the caller's SArray, core_loops.cc:584-618)
        sink = None
        if not compressed:
            sink = memoryview(job.result).cast("B")[
                task.offset * job.np_dtype.itemsize
                : (task.offset + task.length) * job.np_dtype.itemsize
            ]

        def on_pull(payload) -> None:
            from byteps_tpu.comm.ps_client import _ZERO_COPIED
            from byteps_tpu.core.telemetry import counters

            # actual WIRE bytes: a zero-copy sink is always the full
            # uncompressed partition; otherwise len(payload) is the
            # real (possibly compressed) transfer size
            nbytes = (
                task.length * job.np_dtype.itemsize
                if payload is _ZERO_COPIED
                else len(payload)
            )
            if self.telemetry is not None:
                self.telemetry.record(nbytes)
            counters().bump("wire_rx_bytes", nbytes,
                            labels=self._job_labels(task.job))
            if payload is _ZERO_COPIED:
                pass  # already in job.result via the sink
            elif compressed:
                task.compressed = payload  # decoded by DECOMPRESS stage
            else:
                # fallback (response length differed from the sink)
                arr = np.frombuffer(payload, dtype=job.np_dtype)
                job.result[task.offset : task.offset + task.length] = arr[: task.length]
                del arr
                release_frame(payload)  # copied out: its last holder
            self._proceed(task)

        self.client.pull(
            task.key, task.version, on_pull, dtype_id=job.dtype_id,
            request_type=RequestType.COMPRESSED_PUSH_PULL
            if compressed else RequestType.DEFAULT_PUSH_PULL,
            sink=sink,
            on_error=lambda: self._fail_task(
                task, QueueType.PULL, "server connection lost", degraded=True
            ),
            abort_check=lambda: job.failed,
            trace=self._task_trace(task),
        )

    def _decompress_once(self, task: TensorTableEntry) -> None:
        """DECOMPRESS stage: decode the pulled merged payload
        (core_loops.cc:620-648).

        Device-codec jobs decode on DEVICE: the compressed payload is what
        crosses host→device (jnp.asarray inside the adapter), and the
        decoded partition stays on device for _finalize's assembly."""
        job: _Job = task.context
        if job.device_codec:
            from byteps_tpu.core.telemetry import counters

            dc = self._device_codecs[task.key]
            counters().bump("h2d_bytes", len(task.compressed))
            part = dc.decompress(task.compressed, task.length)
            with job.lock:
                job.device_parts[task.offset] = part
            self._proceed(task)
            return
        codec = self._compressors[task.key]
        arr = codec.decompress(task.compressed, task.length)
        job.result[task.offset : task.offset + task.length] = arr[: task.length]
        del arr
        # the pulled round is decoded into the result: its frame's last
        # holder (a fused slot or a push's own payload is no frame)
        release_frame(task.compressed)
        task.compressed = None
        self._proceed(task)

    def _copy_h2d_once(self, task: TensorTableEntry) -> None:
        """Per-partition host→device DMA (COPYH2D, core_loops.cc:650-753):
        the mirror of COPYD2H.  A jax job's pulled (or host-decoded)
        partition is averaged in place and put on the device here, on THIS
        stage thread, as it lands — so the copy of partition k runs beside
        the PULL of partition k+1, and what follows a job's last PULL is
        one partition's copy and _finalize's device-side assemble.  numpy
        jobs keep their result on the host and device-codec jobs already
        decoded theirs on the device: both pass through."""
        from byteps_tpu.core.telemetry import counters

        job: _Job = task.context
        if job.is_jax and not job.device_codec:
            where = None
            if job.placement is not None:
                where, shared = job.placement.where(task.offset, task.length)
                if shared:
                    counters().bump("h2d_sharded_parts")
            part = self._h2d(
                job.result[task.offset : task.offset + task.length],
                job.average, where,
            )
            with job.lock:
                job.device_parts[task.offset] = part
        self._proceed(task)
