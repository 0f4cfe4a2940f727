"""Named-tensor registry with stable key assignment.

TPU-native equivalent of the reference's tensor declaration machinery
(global.cc:412-436, operations.cc:283-317):

- every communicated tensor is *declared* by name, receiving a monotonically
  increasing ``declared_key``;
- the key range ``declared_key << 16`` leaves room for up to 2^16 partitions
  per tensor (operations.cc:306);
- ``redeclare_all()`` replays declarations in original order so key
  assignment is stable across elastic suspend/resume generations
  (ReDeclareTensor, global.cc:431-436).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional

from byteps_tpu.common.types import DataType, Partition

MAX_PARTS_PER_TENSOR = 1 << 16


@dataclasses.dataclass
class TensorContext:
    """Per-declared-tensor state (``BPSContext``, common.h:177-205)."""

    name: str
    declared_key: int
    dtype: Optional[DataType] = None
    num_elements: int = 0
    partitions: List[Partition] = dataclasses.field(default_factory=list)
    initialized: bool = False
    # compression kwargs attached at declare time
    # (ops.py:82-120 in the mxnet plugin; RegisterCompressor global.cc:438-445)
    kwargs: Dict[str, str] = dataclasses.field(default_factory=dict)
    # profiling attachment points (SURVEY §5.1)
    version: int = 0
    # PS-client server-list generation this ctx last ran its init-push
    # barrier against; a mismatch (elastic server resize) re-inits the
    # key on its new owning server before the next use
    server_generation: int = 0
    # Engine instance that last ran this ctx's init barrier: the registry
    # outlives shutdown()/init() cycles but each init() starts servers
    # with fresh stores, so a ctx from a previous engine must re-init
    # (-1 = never)
    engine_epoch: int = -1
    # multi-tenant namespace (common/tenancy.py): the job id carried in
    # the top 16 bits of every wire key this tensor communicates under.
    # Stamped at declare time from BYTEPS_JOB_ID (per-tensor overridable
    # via the byteps_job declare kwarg); job 0 keys are bit-identical to
    # the pre-tenancy layout.
    job: int = 0
    # the tensor's pull target (core/engine.py ``_pull_target``): ONE host
    # buffer of the whole tensor for its life, which every round of a jax
    # job lands in and COPYH2D reads; ``pull_target_lent`` while a job
    # holds it.  None until a jax job needs it, and after a failed round
    pull_target: Any = None
    pull_target_lent: bool = False

    @property
    def base_key(self) -> int:
        from byteps_tpu.common.tenancy import job_key

        return job_key(self.job, self.declared_key << 16)

    def key_for_part(self, i: int) -> int:
        if i >= MAX_PARTS_PER_TENSOR:
            raise ValueError(
                f"tensor {self.name!r} would need partition index {i} "
                f">= {MAX_PARTS_PER_TENSOR}"
            )
        return self.base_key + i


class TensorRegistry:
    """Thread-safe name→context table with stable key replay."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._contexts: Dict[str, TensorContext] = {}
        self._order: List[str] = []  # declaration order for redeclare
        self._next_key = 0

    def is_declared(self, name: str) -> bool:
        with self._lock:
            return name in self._contexts

    def declare(self, name: str, **kwargs: str) -> TensorContext:
        """Declare (or fetch) a named tensor (IsTensorDeclared +
        DeclareTensor, global.cc:412-429).  The tensor's key namespace
        (its job id, docs/async.md) is fixed at first declaration:
        ``byteps_job`` in the kwargs overrides the process-wide
        ``BYTEPS_JOB_ID`` — the in-process multi-job hook tests and
        embedded fleets use."""
        with self._lock:
            ctx = self._contexts.get(name)
            if ctx is not None:
                if kwargs:
                    ctx.kwargs.update(kwargs)
                return ctx
            ctx = TensorContext(
                name=name, declared_key=self._next_key, kwargs=dict(kwargs),
                job=self._job_for(kwargs),
            )
            self._next_key += 1
            self._contexts[name] = ctx
            self._order.append(name)
            return ctx

    @staticmethod
    def _job_for(kwargs: dict) -> int:
        """Resolve a declaration's job id: explicit ``byteps_job`` kwarg
        wins, else the process config's ``BYTEPS_JOB_ID``."""
        raw = kwargs.get("byteps_job")
        if raw is not None:
            return max(0, int(raw))
        from byteps_tpu.common.config import get_config

        return get_config().job_id

    def get(self, name: str) -> TensorContext:
        with self._lock:
            return self._contexts[name]

    def contexts_in_order(self) -> List[TensorContext]:
        with self._lock:
            return [self._contexts[n] for n in self._order]

    def redeclare_all(self) -> None:
        """Replay declarations in original order after an elastic resume so
        every generation assigns identical keys (global.cc:431-436).  Clears
        runtime state (partitions, init flags) but preserves name→key."""
        with self._lock:
            order = list(self._order)
            old = self._contexts
            self._contexts = {}
            self._next_key = 0
            for name in order:
                prev = old[name]
                ctx = TensorContext(
                    name=name, declared_key=self._next_key,
                    kwargs=dict(prev.kwargs), job=prev.job,
                )
                self._next_key += 1
                self._contexts[name] = ctx
            self._order = order

    def clear(self) -> None:
        with self._lock:
            self._contexts.clear()
            self._order.clear()
            self._next_key = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._contexts)


_registry: Optional[TensorRegistry] = None
_registry_lock = threading.Lock()


def get_registry() -> TensorRegistry:
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = TensorRegistry()
        return _registry


def reset_registry() -> TensorRegistry:
    global _registry
    with _registry_lock:
        _registry = TensorRegistry()
        return _registry
