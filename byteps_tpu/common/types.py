"""Core enums and small value types.

TPU-native re-design of the reference's byteps/common/common.h:
- ``DataType``       (common.h:59-72, mshadow-ordered dtype enum)
- ``QueueType``      (common.h:88-102, the 12 pipeline stages)
- ``RequestType``    (common.h:267-271)
- ``Status``         (common.h:108-160 equivalent)
- ``TensorTableEntry`` task struct (common.h:221-264)
- Cantor-pairing command encoding (common.cc:98)
- ``align()``        (common.h:281-285)

On TPU the device-side stages (NCCL reduce/broadcast, CUDA copies) collapse
into XLA-compiled collectives, but the *host* pipeline for the PS path keeps
the same staged structure so priority scheduling, tracing, and compression
have well-defined attachment points.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, Optional

import numpy as np


class DataType(enum.IntEnum):
    """Wire dtype ids, mshadow-ordered for parity (common.h:59-72)."""

    FLOAT32 = 0
    FLOAT64 = 1
    FLOAT16 = 2
    UINT8 = 3
    INT32 = 4
    INT8 = 5
    INT64 = 6
    # TPU-native addition: bfloat16 is the native accumulation-friendly
    # 16-bit type on the MXU; the reference has no bf16 (CUDA-era fp16 only).
    BFLOAT16 = 7


_NP_TO_DT = {
    np.dtype(np.float32): DataType.FLOAT32,
    np.dtype(np.float64): DataType.FLOAT64,
    np.dtype(np.float16): DataType.FLOAT16,
    np.dtype(np.uint8): DataType.UINT8,
    np.dtype(np.int32): DataType.INT32,
    np.dtype(np.int8): DataType.INT8,
    np.dtype(np.int64): DataType.INT64,
}

_DT_TO_NP = {v: k for k, v in _NP_TO_DT.items()}

_DT_SIZE = {
    DataType.FLOAT32: 4,
    DataType.FLOAT64: 8,
    DataType.FLOAT16: 2,
    DataType.UINT8: 1,
    DataType.INT32: 4,
    DataType.INT8: 1,
    DataType.INT64: 8,
    DataType.BFLOAT16: 2,
}


def to_datatype(dtype: Any) -> DataType:
    """Map a numpy/jax dtype to the wire ``DataType``."""
    name = np.dtype(dtype).name if not str(dtype) == "bfloat16" else "bfloat16"
    if name == "bfloat16":
        return DataType.BFLOAT16
    try:
        return _NP_TO_DT[np.dtype(dtype)]
    except KeyError as e:
        raise TypeError(f"unsupported dtype: {dtype!r}") from e


def dtype_size(dt: DataType) -> int:
    """Bytes per element (common.cc:23-47 equivalent)."""
    return _DT_SIZE[dt]


def to_numpy_dtype(dt: DataType) -> np.dtype:
    if dt == DataType.BFLOAT16:
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return _DT_TO_NP[dt]


class QueueType(enum.IntEnum):
    """Host pipeline stages, mirroring the reference's 12-stage enum
    (common.h:88-102).  On TPU:

    - REDUCE / BROADCAST are XLA reduce-scatter / all-gather over ICI
      (compiled, not host-threaded) in the pure-collective path, but remain
      explicit host stages in the PS path where only a shard per host goes
      over DCN.
    - PCIE_REDUCE has no TPU analogue (no PCIe switch hierarchy); it is kept
      in the enum for wire/trace parity but never scheduled.
    - COPYD2H / COPYH2D are jax device_get/device_put of the host shard.
    """

    COORDINATE_REDUCE = 0
    REDUCE = 1
    COPYD2H = 2
    PCIE_REDUCE = 3
    COMPRESS = 4
    PUSH = 5
    PULL = 6
    DECOMPRESS = 7
    COPYH2D = 8
    COORDINATE_PUSH = 9
    COORDINATE_BROADCAST = 10
    BROADCAST = 11
    # TPU-native addition (no reference analogue): small-tensor fusion.
    # Partitions below BYTEPS_FUSION_THRESHOLD bytes take FUSE instead of
    # PUSH — the stage packs same-server partitions into one multi-key
    # Op.FUSED frame, and the fused reply fans back out into each
    # member's PULL stage (docs/fusion.md).
    FUSE = 12


QUEUE_NUM = len(QueueType)


class RequestType(enum.IntEnum):
    """PS request flavors (common.h:267-271)."""

    DEFAULT_PUSH_PULL = 0
    ROW_SPARSE_PUSH_PULL = 1
    COMPRESSED_PUSH_PULL = 2


def get_command_type(requestType: RequestType, dtype: int) -> int:
    """Cantor pairing of (request, dtype) → command id (common.cc:98)."""
    a = int(requestType)
    b = int(dtype)
    return (a + b) * (a + b + 1) // 2 + b


def decode_command_type(cmd: int) -> tuple[RequestType, int]:
    """Inverse Cantor pairing (server-side decode, server.cc:205-230)."""
    w = int(((8 * cmd + 1) ** 0.5 - 1) / 2)
    t = w * (w + 1) // 2
    b = cmd - t
    a = w - b
    return RequestType(a), b


class StatusType(enum.IntEnum):
    OK = 0
    UNKNOWN_ERROR = 1
    PRECONDITION_ERROR = 2
    ABORTED = 3
    INVALID_ARGUMENT = 4
    IN_PROGRESS = 5
    # the data plane degraded under the operation (server lost, retries
    # exhausted, membership shrank) — retrying the STEP is safe and may
    # succeed once the cluster heals (docs/robustness.md)
    DEGRADED = 6


class DegradedError(RuntimeError):
    """A push_pull failed because the PS data plane degraded mid-flight —
    a server died or hung past its retry budget, or the membership
    changed under the operation.

    Subclasses ``RuntimeError`` so pre-existing handlers keep working.
    Resubmitting the same step is SAFE: the abandoned round was never
    published (no worker consumed it), the engine re-runs the key's
    init barrier against the healed topology on the next submit, and
    the server dedupes any replayed pushes — summation stays
    exactly-once.  ``BYTEPS_DEGRADED_STEP_RETRIES`` makes the
    synchronous API retry automatically (api.py).
    """


@dataclasses.dataclass
class Status:
    """Operation status (common.h:108-160)."""

    type: StatusType = StatusType.OK
    reason: str = ""

    @staticmethod
    def OK() -> "Status":
        return Status(StatusType.OK)

    @staticmethod
    def InProgress() -> "Status":
        return Status(StatusType.IN_PROGRESS)

    @staticmethod
    def Aborted(msg: str) -> "Status":
        return Status(StatusType.ABORTED, msg)

    @staticmethod
    def Degraded(msg: str) -> "Status":
        return Status(StatusType.DEGRADED, msg)

    @staticmethod
    def PreconditionError(msg: str) -> "Status":
        return Status(StatusType.PRECONDITION_ERROR, msg)

    def ok(self) -> bool:
        return self.type == StatusType.OK

    def in_progress(self) -> bool:
        return self.type == StatusType.IN_PROGRESS


ALIGN_BYTES = 64


def align(size: int, alignment: int = ALIGN_BYTES) -> int:
    """Round ``size`` up to a multiple of ``alignment`` (common.h:281-285).

    The reference aligns shm buffers for AVX loads; we keep 64B alignment so
    host-side C++ reducers can use full-width vector loads.
    """
    return ((size + alignment - 1) // alignment) * alignment


@dataclasses.dataclass
class Partition:
    """One partition of a declared tensor: a contiguous [offset, offset+length)
    element range assigned its own communication key (operations.cc:306-317)."""

    key: int
    offset: int  # element offset into the flat tensor
    length: int  # element count


@dataclasses.dataclass
class TensorTableEntry:
    """One in-flight communication task for one partition
    (common.h:221-264).  Host-engine unit of scheduling."""

    tensor_name: str
    key: int
    priority: int = 0
    version: int = 0
    offset: int = 0
    length: int = 0
    total_partnum: int = 1
    queue_list: list = dataclasses.field(default_factory=list)
    # host staging buffer (numpy view of the partition)
    cpubuff: Optional[np.ndarray] = None
    # compressed payload, set by the COMPRESS stage
    compressed: Optional[bytes] = None
    callback: Optional[Callable[[Status], None]] = None
    context: Any = None
    # once-guard: a task may be failed from two racing paths (stage-thread
    # exception AND dead-connection callback); only the first wins
    failed: bool = False
    # fusion (QueueType.FUSE): the member's slice of a fused reply, set
    # when the multi-key response fans out — its PULL stage then delivers
    # locally instead of issuing a wire pull
    fused_reply: Optional[bytes] = None
    # scheduler flag: skip the ready-table gate (fusion GROUP tasks — the
    # members already passed their per-key round gates at the FUSE queue,
    # re-gating the pack under its route key would deadlock it)
    gate_exempt: bool = False
    # fusion staging accounting: True from submit (a FUSE-routed task
    # enters the engine's staged-smalls window) until the task reaches
    # the fusion buffer or dies — the engine's idle-flush check must
    # never miss a small that is still upstream of the FUSE queue
    # (in COPYD2H, or in COMPRESS on the compressed-fused pipeline)
    fuse_staged: bool = False
    # distributed tracing (docs/observability.md): the job's trace id and
    # this partition-task's span id — propagated on every framed RPC the
    # task issues, so server-side child spans join the worker timeline.
    # 0 = tracing off.
    trace_id: int = 0
    span_id: int = 0
    # stamped by ScheduledQueue.add_task on every stage entry: monotonic
    # for the stage-dwell histogram (ENQUEUE→done), wall-clock for the
    # span timeline (cross-process alignment)
    enqueued_at: float = 0.0
    enqueued_wall: float = 0.0
    # multi-tenant dimension (common/tenancy.py): the job id the task's
    # key is namespaced under — the scheduler's per-tenant weighted-fair
    # queues and per-job gate credits key on it (docs/async.md)
    job: int = 0

    def current_stage(self) -> Optional[QueueType]:
        return self.queue_list[0] if self.queue_list else None
