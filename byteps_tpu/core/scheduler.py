"""Priority-scheduled stage queue with per-tenant weighted fairness.

Re-design of ``BytePSScheduledQueue`` (scheduled_queue.cc):

- tasks sorted by (priority desc, key asc)  (scheduled_queue.cc:82-102)
- optional credit scheduling: a byte budget of in-flight work
  (BYTEPS_SCHEDULING_CREDIT, scheduled_queue.cc:26-46); finished tasks
  return their credits (reportFinish, scheduled_queue.cc:197-203)
- optional ReadyTable gate: tasks whose key is not ready are skipped
  (getTask, scheduled_queue.cc:125-163)

Priority semantics: the plugins assign priority = -declared_index so
gradients produced *last* in backprop (front layers) are communicated
*first*, hiding them behind the next step's early forward — the core BytePS
scheduling insight (OSDI'20 §4; mxnet/__init__.py:52-74).

Multi-tenant dimension (docs/async.md): tasks carry the JOB their key is
namespaced under (common/tenancy.py), and the queue runs weighted fair
queuing ACROSS jobs before the classic priority order applies WITHIN a
job.  Each job accumulates a virtual time — bytes served divided by its
weight (``BYTEPS_JOB_PRIORITY``; :func:`set_job_weight`) — and the pop
always serves the eligible job with the LOWEST virtual time:

- **starvation-freedom**: a weight-1 bulk job's virtual time eventually
  falls below a weight-100 latency job's (the latency job accumulates
  service too), so every tenant always progresses;
- **no priority inversion**: a bulk job's giant task.priority values
  cannot outrank another tenant — task priority only orders tasks of
  the SAME job, while the cross-job order is the weighted share.

With a single job in the queue (the default: one process = one tenant)
the virtual-time layer is inert and the order is bit-identical to the
classic (priority desc, key asc) scheduler.  Per-job gate credits
(``BYTEPS_JOB_CREDIT_BYTES``) bound each tenant's in-flight bytes the
way the global credit bounds the whole queue.
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Callable, ContextManager, Dict, List, Optional

from byteps_tpu.common.types import QueueType, TensorTableEntry
from byteps_tpu.core.ready_table import ReadyTable

#: process-wide job → WFQ weight table (higher = larger share under
#: contention).  One process normally hosts one job and registers its
#: own BYTEPS_JOB_PRIORITY at engine start; in-process multi-tenant
#: fleets (tests, embedded runs) register every job they host.
_job_weights: Dict[int, float] = {}
_job_weights_lock = threading.Lock()


def set_job_weight(job: int, weight: float) -> None:
    """Register a tenant's weighted share (BYTEPS_JOB_PRIORITY)."""
    with _job_weights_lock:
        _job_weights[int(job)] = max(0.001, float(weight))


def get_job_weight(job: int) -> float:
    with _job_weights_lock:
        return _job_weights.get(int(job), 1.0)


class _JobLane:
    """One tenant's slice of a queue: its sorted task list plus the WFQ
    virtual-time account."""

    __slots__ = ("job", "tasks", "vtime", "inflight")

    def __init__(self, job: int) -> None:
        self.job = job
        self.tasks: List[TensorTableEntry] = []
        self.vtime = 0.0
        self.inflight = 0  # bytes this job currently has in flight


class ScheduledQueue:
    def __init__(
        self,
        queue_type: QueueType,
        credit_bytes: int = 0,
        ready_table: Optional[ReadyTable] = None,
        itemsize: int = 4,
        version_gated: bool = False,
        discipline: str = "priority",
        job_credits: Optional[Dict[int, int]] = None,
    ) -> None:
        if discipline not in ("priority", "fifo"):
            raise ValueError(
                f"BYTEPS_SCHEDULING={discipline!r} unknown; use priority|fifo"
            )
        #: "fifo" = strict arrival order — the ablation baseline proving the
        #: priority scheduler's wall-clock win (OVERLAP artifact); matches a
        #: reference build with scheduling disabled
        self.discipline = discipline
        self.queue_type = queue_type
        self.credit_enabled = credit_bytes > 0
        self._credits = credit_bytes
        #: per-tenant in-flight byte budgets (BYTEPS_JOB_CREDIT_BYTES);
        #: a job with no entry is bounded only by the global credit
        self._job_credits: Dict[int, int] = dict(job_credits or {})
        self._ready_table = ready_table
        # version-gated mode: a task is eligible iff its round number is at
        # or below the table's per-key allowance (counts[key] = highest
        # version allowed).  Enforces per-key ROUND ORDER, so a later
        # high-priority round can never overtake an earlier round of the
        # same key — priority still reorders across keys (the scheduling
        # win), never within one.
        self._version_gated = version_gated
        self._itemsize = itemsize
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        #: job → lane; insertion order is the FIFO tiebreak across jobs
        self._lanes: Dict[int, _JobLane] = {}

    def bind_ready_table(self, table: ReadyTable) -> None:
        self._ready_table = table

    def _lane_locked(self, job: int) -> _JobLane:
        lane = self._lanes.get(job)
        if lane is None:
            lane = self._lanes[job] = _JobLane(job)
        return lane

    def add_task(self, task: TensorTableEntry) -> None:
        # stage-entry stamps: the dwell histogram measures ENQUEUE→done
        # per stage, and span events start here — so queue wait (the
        # thing priority scheduling and credits actually change) is part
        # of every stage's recorded latency, not silently dropped
        task.enqueued_at = time.monotonic()
        task.enqueued_wall = time.time()
        with self._cv:
            lane = self._lane_locked(task.job)
            if not lane.tasks:
                # a (re-)activating tenant joins at the floor of the
                # live virtual clock: it must neither inherit a huge
                # service debt from its idle stretch (monopolizing the
                # queue) nor a huge credit (being starved while the
                # others catch up) — standard WFQ virtual-time join,
                # in NORMALIZED units (service / weight)
                active = [
                    ln.vtime / get_job_weight(ln.job)
                    for ln in self._lanes.values()
                    if ln.tasks and ln is not lane
                ]
                if active:
                    lane.vtime = max(
                        lane.vtime, min(active) * get_job_weight(lane.job)
                    )
            if self.discipline == "fifo":
                lane.tasks.append(task)
            else:
                # (priority desc, key asc) — scheduled_queue.cc:82-102;
                # bisect keeps insertion O(log n) compare + O(n) shift
                # instead of re-sorting the whole queue per task
                bisect.insort(
                    lane.tasks, task, key=lambda t: (-t.priority, t.key)
                )
            self._cv.notify_all()

    def _eligible(self, task: TensorTableEntry, lane: _JobLane) -> bool:
        nbytes = task.length * self._itemsize
        if self.credit_enabled and nbytes > self._credits:
            return False
        job_cap = self._job_credits.get(task.job)
        if job_cap is not None and lane.inflight + nbytes > job_cap:
            # this tenant's in-flight byte budget is spent — its tasks
            # wait for report_finish to return credits, while OTHER
            # tenants' tasks stay poppable (the whole point of the
            # per-job dimension)
            return False
        if task.gate_exempt:
            # fusion GROUP task: its members each passed their own per-key
            # round gate before being packed, and the pack's route key is
            # just the first member's — gating the group under that one key
            # would stall (or deadlock) the other members' rounds.  The
            # group still competes on priority (it inherits the max of its
            # members) and still spends credit, so fusion never defeats
            # priority scheduling or the in-flight byte budget.
            return True
        if self._ready_table is not None:
            if self._version_gated:
                if task.version > self._ready_table.get_count(task.key):
                    return False
            elif not self._ready_table.is_ready(task.key):
                return False
        return True

    def get_task(self, timeout: Optional[float] = None,
                 waiting: Optional[Callable[[str], ContextManager]] = None,
                 ) -> Optional[TensorTableEntry]:
        """Pop the highest-priority eligible task of the least-served
        tenant; None on timeout.

        Re-waits the remaining budget after a wakeup that finds nothing
        eligible (spurious, or an ineligible add) — a single wait would
        hand the stage loop a None and cost a full idle poll tick.

        ``waiting(why)`` gives the context every wait runs in, so the
        stage loop can time and name it while the queue keeps no metric:
        ``"starved"`` — the lanes held no task (the stage before has
        delivered nothing) — or ``"gated"`` — they held tasks and none was
        eligible (the round gate, ``scheduling_credit``, a tenant's
        ``job_credit_bytes``)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while True:
                task = self._pop_eligible()
                if task is not None:
                    return task
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    return None
                if waiting is None:
                    self._cv.wait(remaining)
                    continue
                held = any(ln.tasks for ln in self._lanes.values())
                with waiting("gated" if held else "starved"):
                    self._cv.wait(remaining)

    def _pop_eligible(self) -> Optional[TensorTableEntry]:
        # tenants in virtual-time order (ties broken by lane insertion
        # order — stable, so a single-job queue is exactly the classic
        # scheduler); within the chosen tenant, classic (priority desc,
        # key asc) order.  A tenant whose head tasks are all gated does
        # not block the others: the scan falls through to the next lane.
        lanes = sorted(
            (ln for ln in self._lanes.values() if ln.tasks),
            key=lambda ln: ln.vtime / get_job_weight(ln.job),
        )
        for lane in lanes:
            for i, t in enumerate(lane.tasks):
                if self._eligible(t, lane):
                    lane.tasks.pop(i)
                    nbytes = t.length * self._itemsize
                    if self.credit_enabled:
                        self._credits -= nbytes
                    if self._job_credits:
                        # tracked only when a tenant budget exists —
                        # report_finish's default fast path never
                        # decrements, so don't accumulate here either
                        lane.inflight += nbytes
                    # the service unit is BYTES (min 1 so zero-length
                    # control tasks still advance the clock): a tenant's
                    # share is of the wire, not of the pop count
                    lane.vtime += max(1, nbytes)
                    if (self._ready_table is not None
                            and not self._version_gated
                            and not t.gate_exempt):
                        # classic rendezvous consumes the accumulated
                        # signals (scheduled_queue.cc:125-163); the
                        # version gate keeps its allowance — completions
                        # advance it instead
                        self._ready_table.clear_ready_count(t.key)
                    return t
        return None

    def get_task_by_key(self, key: int) -> Optional[TensorTableEntry]:
        """Signal-directed dequeue (getTask(key),
        scheduled_queue.cc:165-190)."""
        with self._cv:
            for lane in self._lanes.values():
                for i, t in enumerate(lane.tasks):
                    if t.key == key:
                        return lane.tasks.pop(i)
        return None

    def report_finish(self, task: TensorTableEntry) -> None:
        """Return credits (scheduled_queue.cc:197-203) — global and the
        task's tenant budget.  No-op when neither credit dimension is
        armed (the default): the hot per-task completion path must not
        pay a lock + wakeup for bookkeeping nobody reads."""
        if not self.credit_enabled and not self._job_credits:
            return
        nbytes = task.length * self._itemsize
        with self._cv:
            if self.credit_enabled:
                self._credits += nbytes
            lane = self._lanes.get(task.job)
            if lane is not None:
                lane.inflight = max(0, lane.inflight - nbytes)
            self._cv.notify_all()

    def notify(self) -> None:
        """Wake waiters (ready-table state changed externally)."""
        with self._cv:
            self._cv.notify_all()

    def pending(self) -> int:
        with self._lock:
            return sum(len(ln.tasks) for ln in self._lanes.values())
