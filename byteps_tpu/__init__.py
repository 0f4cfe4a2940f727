"""byteps_tpu — a TPU-native distributed training framework.

A from-scratch re-design of the capabilities of BytePS (bytedance/byteps,
OSDI'20) for TPUs: a Horovod-compatible named-tensor ``push_pull`` API,
hierarchical communication (XLA collectives over ICI inside a slice, a
parameter-server-style CPU aggregation service over DCN between slices),
tensor partitioning, priority-based communication scheduling, gradient
compression with error feedback and momentum, sync/async training, elastic
suspend/resume, and Chrome-trace profiling.

Public API parity surface (reference: byteps/common/__init__.py:52-139,
byteps/torch/__init__.py:226-266):

    init / shutdown / suspend / resume
    rank / size / local_rank / local_size
    declare_tensor / push_pull / push_pull_async / poll / synchronize
    DistributedOptimizer / broadcast_parameters / broadcast_object
    get_pushpull_speed

The compute data plane is JAX/XLA (psum_scatter + all_gather over a
``jax.sharding.Mesh``); the host-side runtime (scheduler, PS transport,
reducers, codecs) is native C++ reached via ctypes.
"""

from byteps_tpu.common.config import Config, get_config, reset_config
from byteps_tpu.common.registry import TensorRegistry, get_registry
from byteps_tpu.api import (
    init,
    shutdown,
    suspend,
    resume,
    rank,
    size,
    local_rank,
    local_size,
    declare_tensor,
    push_pull,
    push_pull_async,
    push_pull_rowsparse,
    push_pull_rowsparse_async,
    poll,
    synchronize,
    broadcast_parameters,
    broadcast_object,
    get_pushpull_speed,
    get_robustness_counters,
    get_metrics,
    get_metrics_text,
    set_compression_lr,
)
from byteps_tpu.common.types import DegradedError
from byteps_tpu.optim import DistributedOptimizer, distributed_optimizer

__version__ = "0.1.0"

_SUBMODULES = (
    "api", "optim", "checkpoint", "callbacks", "cross_barrier", "data",
    "mixed_precision", "compression", "models", "ops",
    "parallel", "comm", "core", "common", "server", "launcher", "native",
    "haiku_plugin",
)


def __getattr__(name: str):
    """Lazy submodule access: ``bps.checkpoint.save(...)`` without an
    explicit import (heavy deps like orbax/torch load on first touch)."""
    if name in _SUBMODULES:
        import importlib

        mod = importlib.import_module(f"byteps_tpu.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'byteps_tpu' has no attribute {name!r}")

__all__ = [
    "Config",
    "get_config",
    "reset_config",
    "TensorRegistry",
    "get_registry",
    "init",
    "shutdown",
    "suspend",
    "resume",
    "rank",
    "size",
    "local_rank",
    "local_size",
    "declare_tensor",
    "push_pull",
    "push_pull_async",
    "push_pull_rowsparse",
    "push_pull_rowsparse_async",
    "poll",
    "synchronize",
    "broadcast_parameters",
    "broadcast_object",
    "get_pushpull_speed",
    "get_robustness_counters",
    "get_metrics",
    "get_metrics_text",
    "set_compression_lr",
    "DistributedOptimizer",
    "distributed_optimizer",
]
