"""Public Horovod-compatible API.

Parity surface with the reference's Python entry points
(common/__init__.py:52-139, torch/__init__.py:226-466, torch/ops.py:38-236).

Semantics on TPU (single-controller JAX):

- *Local* (intra-slice) reduction is device-side: use the traceable
  collectives (:mod:`byteps_tpu.comm.collectives`) or
  :class:`byteps_tpu.optim.DistributedOptimizer`, which compile to ICI
  collectives.  This replaces the reference's per-process NCCL ranks.
- *Cross-worker* (inter-host) reduction is what this module's host-level
  ``push_pull`` does: partition → stage to host → PS push/pull over DCN →
  back to device.  With one worker it is the identity, matching the
  reference's 1-worker semantics (tests/test_mxnet.py:30-126).
"""

from __future__ import annotations

import json
import os
import pickle
from typing import Any, Optional

import numpy as np

from byteps_tpu.common.config import get_config
from byteps_tpu.common.registry import get_registry
from byteps_tpu.core.state import get_state, init_state, require_state, shutdown_state


def init(lazy: bool = True) -> None:
    """Initialize the runtime (byteps_init / byteps_lazy_init,
    operations.cc:41-94)."""
    init_state()


def shutdown() -> None:
    """Tear down threads and connections (byteps_shutdown,
    operations.cc:89-94)."""
    shutdown_state()


def suspend() -> None:
    """Elastic suspend: stop engine/PS but keep tensor declarations so a
    later resume() re-assigns identical keys (operations.cc:114-119)."""
    shutdown_state()


def resume(
    num_workers: Optional[int] = None,
    num_servers: Optional[int] = None,
    global_rank: Optional[int] = None,
) -> None:
    """Elastic resume: rewrite topology env then re-init and replay tensor
    declarations in original order (common/__init__.py:75-82,
    operations.cc:96-112, ReDeclareTensor global.cc:431-436)."""
    if num_workers is not None:
        os.environ["DMLC_NUM_WORKER"] = str(num_workers)
    if num_servers is not None:
        os.environ["DMLC_NUM_SERVER"] = str(num_servers)
    if global_rank is not None:
        os.environ["BYTEPS_GLOBAL_RANK"] = str(global_rank)
    st = get_state()
    st.resuming = True
    try:
        get_registry().redeclare_all()
        init_state(fresh_env=True)
    finally:
        st.resuming = False


def rank() -> int:
    """Global worker rank (common/__init__.py:96-103)."""
    cfg = get_config()
    return cfg.global_rank if cfg.global_rank is not None else cfg.worker_id


def size() -> int:
    """Number of workers (common/__init__.py:105-112)."""
    return get_config().num_worker


def local_rank() -> int:
    return get_config().local_rank


def local_size() -> int:
    return get_config().local_size


def declare_tensor(name: str, **kwargs: str) -> int:
    """Declare a named tensor ahead of communication, optionally carrying
    compression kwargs (byteps_declare_tensor, mxnet/ops.py:82-120);
    returns the stable declared key.

    Server-side optimizer (docs/architecture.md "Server-side
    optimizer"): ``byteps_server_opt="sgd"|"momentum"|"adam"`` declares
    the tensor's keys with a server-side update rule (workers push
    gradients, pull updated parameters), overriding the process-wide
    ``BYTEPS_SERVER_OPT``; ``byteps_server_opt_hp`` carries its
    hyperparams as a JSON string or a dict (dicts are canonicalized to
    JSON here — registry kwargs are strings on the wire)."""
    raw = kwargs.get("byteps_server_opt")
    if raw is not None:
        rule = str(raw).strip().lower()
        if rule and rule not in ("0", "false", "no", "off"):
            # fail at DECLARE, not at the first push's INIT: the rule
            # registry is local, so a typo'd name should not travel to
            # the server before erroring
            from byteps_tpu.server.update_rules import RULE_NAMES

            if rule not in RULE_NAMES:
                raise ValueError(
                    f"unknown server update rule {rule!r} "
                    f"(have {RULE_NAMES})"
                )
    ctx = get_registry().declare(name, **{
        k: (json.dumps(v, sort_keys=True) if isinstance(v, dict) else str(v))
        for k, v in kwargs.items()
    })
    return ctx.declared_key


def push_pull_async(
    tensor: Any,
    name: str,
    average: bool = True,
    priority: int = 0,
    version: int = 0,
) -> int:
    """Start a cross-worker push_pull; returns a pollable handle
    (byteps_push_pull / DoPushPull, torch/ops.cc:99-113).

    The result (same shape/dtype as input) is retrieved by
    :func:`synchronize`.
    """
    st = require_state()
    cfg = st.config
    get_registry().declare(name)
    handle = st.handles.allocate()
    if not cfg.is_distributed:
        # Non-distributed role set skips push/pull loops entirely
        # (operations.cc:46-53): identity.
        st.handles.mark_done(handle, tensor)
        return handle
    # The tensor is handed to the engine UN-materialized: device→host
    # staging happens per partition on the COPYD2H stage thread, so this
    # call returns while the device computation producing the gradient may
    # still be in flight (the reference's ready-event + COPYD2H stream
    # overlap, core_loops.cc:378-443).
    st.engine.submit(
        name=name,
        tensor=tensor,
        average=average,
        priority=priority,
        version=version,
        handle=handle,
    )
    return handle


def poll(handle: int) -> bool:
    """True when the async op has completed (ops.py poll, handle_manager)."""
    return require_state().handles.poll(handle)


def synchronize(handle: int) -> Any:
    """Block until completion and return the reduced tensor
    (ops.py:214-236)."""
    return require_state().handles.wait_and_clear(handle)


def push_pull(
    tensor: Any,
    name: str,
    average: bool = True,
    priority: int = 0,
) -> Any:
    """Synchronous cross-worker push_pull (sum over workers, then average
    when ``average=True``).

    ``name`` is required: it is the cross-process aggregation key, so it
    must be identical on every worker (an auto-generated per-process name
    could never match up).  The reference likewise keys on names
    (torch/__init__.py:139: ``Gradient.<param name>``).

    Degraded-step policy (docs/robustness.md): when the data plane
    degrades mid-step — a server died past its retry budget — the handle
    raises :class:`~byteps_tpu.common.types.DegradedError`.  With
    ``BYTEPS_DEGRADED_STEP_RETRIES`` > 0 this wrapper first routes the
    failure through the in-place recovery plane (the engine resyncs the
    live servers, replays the journaled pushes they never absorbed, and
    pulls the completed round — docs/robustness.md "healing flow"); only
    when in-place heal is impossible does it resubmit the step up to that
    many times (with backoff, so the elastic rebuild can land) through
    the full re-init barrier.  Resubmission is exactly-once safe — the
    abandoned round was never published and the next submit re-runs the
    key's init barrier.  Default 0: the error propagates and the
    training loop decides.
    """
    retries = get_config().degraded_step_retries
    if retries <= 0:
        return synchronize(
            push_pull_async(tensor, name, average=average, priority=priority)
        )
    from byteps_tpu.common.types import DegradedError
    from byteps_tpu.comm.retry import Backoff

    bo = Backoff(base=0.25, cap=2.0)
    for attempt in range(retries + 1):
        try:
            return synchronize(
                push_pull_async(tensor, name, average=average, priority=priority)
            )
        except (DegradedError, ConnectionError) as e:
            # ConnectionError covers the submit-time init barrier hitting
            # a not-yet-evicted dead server — same transient class, and
            # the user opted into step retries
            if attempt >= retries:
                raise
            if isinstance(e, DegradedError):
                # in-place heal first: if the degradation was one-sided
                # (every live peer sailed on), the journal replay
                # completes the abandoned round with its ORIGINAL
                # payloads and the pulled result is exactly the
                # fault-free one — no re-init barrier, peers never block
                st = require_state()
                if st.engine is not None:
                    healed = st.engine.heal_degraded(name, tensor, average)
                    if healed is not None:
                        return healed
            import time as _time

            _time.sleep(bo.next_delay())


def push_pull_rowsparse_async(
    indices: Any,
    values: Any,
    name: str,
    total_rows: int,
    average: bool = True,
    priority: int = 0,
) -> int:
    """Start a row-sparse push_pull (RequestType::kRowSparsePushPull,
    common.h:267-271): push ``values`` rows at ``indices`` of a
    ``(total_rows, row_len)`` tensor; the server scatter-sums all workers'
    rows into the dense store, and the result (same ``indices``, gathered
    after the round completes) is retrieved by :func:`synchronize` as a
    ``(len(indices), row_len)`` array — the embedding-gradient path."""
    st = require_state()
    cfg = st.config
    get_registry().declare(name)
    handle = st.handles.allocate()
    if not cfg.is_distributed:
        # same semantics as the 1-worker PS path — scatter-add then gather,
        # so duplicate indices accumulate and bad indices raise identically
        # (shared validator keeps the two paths in lockstep)
        from byteps_tpu.common.partition import validate_rowsparse

        idx, vals = validate_rowsparse(indices, values, total_rows)
        dense = np.zeros((total_rows, vals.shape[1]), dtype=vals.dtype)
        np.add.at(dense, idx, vals)
        st.handles.mark_done(handle, dense[idx])
        return handle
    st.engine.submit_rowsparse(
        name=name,
        indices=indices,
        values=values,
        total_rows=total_rows,
        average=average,
        priority=priority,
        version=0,
        handle=handle,
    )
    return handle


def push_pull_rowsparse(
    indices: Any,
    values: Any,
    name: str,
    total_rows: int,
    average: bool = True,
    priority: int = 0,
) -> Any:
    """Synchronous row-sparse push_pull; see
    :func:`push_pull_rowsparse_async`."""
    return synchronize(
        push_pull_rowsparse_async(
            indices, values, name, total_rows, average=average, priority=priority
        )
    )


def broadcast_parameters(params: Any, root_rank: int = 0) -> Any:
    """Sync a pytree of parameters from ``root_rank`` to all workers.

    Reference trick (torch/__init__.py:268-299): non-root zeroes its copy,
    then an unaveraged push_pull sum leaves root's values everywhere.
    """
    import jax

    st = require_state()
    if not st.config.is_distributed:
        return params

    # Launch every leaf async, then synchronize — overlaps all round-trips
    # the way the reference broadcasts with async handles
    # (torch/__init__.py:268-299).
    def start_leaf(path, leaf):
        name = "Parameter." + "/".join(str(p) for p in path)
        arr = np.asarray(leaf)
        if rank() != root_rank:
            arr = np.zeros_like(arr)
        return push_pull_async(arr, name=name, average=False)

    handles = jax.tree_util.tree_map_with_path(start_leaf, params)

    def finish_leaf(handle, leaf):
        out = synchronize(handle)
        return jax.numpy.asarray(out, dtype=leaf.dtype) if hasattr(leaf, "dtype") else out

    return jax.tree_util.tree_map(finish_leaf, handles, params)


def broadcast_object(obj: Any, root_rank: int = 0, name: str = "obj") -> Any:
    """Broadcast an arbitrary picklable object (broadcast_object,
    torch/__init__.py:302-466: cloudpickle → byte tensor → push_pull).
    Two-phase: length first, then payload, both as unaveraged sums with
    non-root contributing zeros."""
    st = require_state()
    if not st.config.is_distributed:
        return obj
    payload = pickle.dumps(obj) if rank() == root_rank else b""
    ln = np.array([len(payload)], dtype=np.int64)
    if rank() != root_rank:
        ln = np.zeros_like(ln)
    total = int(push_pull(ln, name=f"{name}.len", average=False)[0])
    buf = np.zeros(total, dtype=np.uint8)
    if rank() == root_rank:
        buf[:] = np.frombuffer(payload, dtype=np.uint8)
    out = push_pull(buf, name=f"{name}.data", average=False)
    return pickle.loads(np.asarray(out, dtype=np.uint8).tobytes())


def set_compression_lr(lr: float) -> None:
    """Propagate the optimizer's learning rate into error-feedback
    compressor chains (the reference's ``lr.s`` shared file,
    vanilla_error_feedback.h:44-58).  No-op when nothing is compressed
    or the engine isn't running."""
    st = require_state()
    if st.engine is not None:
        st.engine.set_compression_lr(lr)


def get_pushpull_speed() -> float:
    """Windowed push/pull MB/s (common/__init__.py:131-139)."""
    st = require_state()
    return st.telemetry.mbps() if st.telemetry else 0.0


def get_robustness_counters() -> dict:
    """Snapshot of the data-plane degradation counters: retries, deadline
    expiries, connection revivals, replay dedupes, observed evictions,
    injected chaos faults, and the recovery plane's ``resync_attempt`` /
    ``resync_replayed_rounds`` / ``resync_giveup`` heal outcomes
    (docs/robustness.md).  Process-wide; usable before :func:`init`
    (counters exist independently of runtime state).

    FLAT totals only, for back-compat — the per-peer dimension (which
    server a retry/deadline/revive hit) is in :func:`get_metrics` under
    ``counters_labeled`` (docs/observability.md)."""
    from byteps_tpu.core.telemetry import counters

    return counters().snapshot()


def get_metrics() -> dict:
    """Structured snapshot of the full metrics registry: flat + labeled
    counters, gauges, and histogram p50/p90/p99 summaries (RPC round
    trips, per-stage dwell, server sum/publish latency, fused pack
    density — the catalog lives in docs/observability.md).  Process-wide;
    usable before :func:`init`.

    In an initialised distributed worker the snapshot also holds every
    linked server's registry as it stands AT the call, under the labels
    ``{role="server", rank="<r>"}``: its histograms among ``histograms``,
    its counters among ``counters_labeled``, its gauges among ``gauges``
    (``counters`` stays this process's own).  One control request a
    server, answered within ``PSClient.METRICS_WAIT_S`` or left out: a dead
    server, or one that does not know the request, costs that wait and the
    call returns this process's registry."""
    from byteps_tpu.core.telemetry import metrics

    snapshot = metrics().snapshot()
    client = get_state().ps_client
    if client is not None:
        for theirs in client.server_metrics():
            for section in ("histograms", "gauges"):
                snapshot[section].update(theirs.get(section, {}))
            for name, per in theirs.get("counters_labeled", {}).items():
                snapshot["counters_labeled"].setdefault(name, {}).update(per)
    return snapshot


def get_metrics_text() -> str:
    """The Prometheus text exposition this process would serve on
    ``BYTEPS_METRICS_PORT`` — for logging a scrape without running the
    HTTP endpoint (docs/observability.md)."""
    from byteps_tpu.core.telemetry import metrics

    return metrics().render_prometheus()
