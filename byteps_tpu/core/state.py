"""Process-wide runtime state — the ``BytePSGlobal`` equivalent
(global.h:52-225, global.cc:105-403).

Owns: config snapshot, device mesh, tensor registry, handle table, the host
pipeline engine (distributed mode only), PS client, telemetry and tracer.
``init_state()`` is the body of ``byteps_lazy_init`` (operations.cc:41-88):
it selects which host loops exist based on role and distributed-ness.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from byteps_tpu.common.config import Config, get_config, reset_config
from byteps_tpu.common.registry import TensorRegistry, get_registry
from byteps_tpu.core.handle_manager import HandleManager


class RuntimeState:
    def __init__(self) -> None:
        self.config: Optional[Config] = None
        self.mesh = None
        self.registry: Optional[TensorRegistry] = None
        self.handles = HandleManager()
        self.engine = None  # core.engine.PipelineEngine (distributed mode)
        self.ps_client = None  # comm.ps_client.PSClient
        self.flightrec = None  # core.flightrec.FlightRecorder
        self.telemetry = None  # core.telemetry.PushPullSpeed
        self.tracer = None  # core.tracing.Tracer
        self.metrics_http = None  # core.telemetry.MetricsHTTPServer
        self.initialized = False
        self.resuming = False
        # stable across suspend/resume so the scheduler matches the rejoin
        # to this worker's previous registration (not another live worker's);
        # resolved lazily at first init so a BYTEPS_NODE_UID set after import
        # still wins
        self.node_uid: Optional[str] = None
        self._lock = threading.Lock()


_state = RuntimeState()


def get_state() -> RuntimeState:
    return _state


_jax_distributed_up = False


def _init_jax_distributed(cfg: Config) -> None:
    """Bring up the JAX distributed runtime (multi-host pod slices;
    SURVEY §5.8: scheduler node ↔ jax.distributed coordinator).

    On Cloud TPU pods ``jax.distributed.initialize()`` auto-detects
    everything from instance metadata; elsewhere (multi-process CPU
    clusters, custom deployments) the coordinator must be explicit:

        BYTEPS_JAX_COORDINATOR=host:port
        BYTEPS_JAX_NUM_PROCESSES (default DMLC_NUM_WORKER)
        BYTEPS_JAX_PROCESS_ID    (default BYTEPS_GLOBAL_RANK/DMLC_WORKER_ID)

    The runtime survives suspend/resume (re-initializing the coordination
    service would drop every other host's connection; the reference's
    ps-lite similarly keeps its Postoffice across byteps_resume)."""
    global _jax_distributed_up
    if _jax_distributed_up:
        return
    import jax

    kwargs = {}
    coord = os.environ.get("BYTEPS_JAX_COORDINATOR", "")
    if coord:
        # empty-string env values (a common way to "unset" in env files)
        # fall back like missing ones
        pid = os.environ.get("BYTEPS_JAX_PROCESS_ID") or (
            cfg.global_rank if cfg.global_rank is not None else cfg.worker_id
        )
        nprocs = os.environ.get("BYTEPS_JAX_NUM_PROCESSES") or cfg.num_worker
        kwargs = dict(
            coordinator_address=coord,
            num_processes=int(nprocs),
            process_id=int(pid),
        )
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        # tolerate a runtime someone else already brought up (jax's
        # message: "distributed.initialize should only be called once.")
        if "once" not in str(e).lower() and "already" not in str(e).lower():
            raise
    _jax_distributed_up = True


#: the checkout (or install prefix) that holds the ``byteps_tpu`` package
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache a home before the first
    program is built; returns the directory in force.

    Placed from outside when ``JAX_COMPILATION_CACHE_DIR`` is set — jax
    reads the variable itself and nothing is set in code.  Otherwise
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    how a later process finds the entries again — never a temporary name,
    a pid or a time."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(_CHECKOUT, ".jax_cache")
        )
    return jax.config.jax_compilation_cache_dir


def init_state(fresh_env: bool = True) -> RuntimeState:
    """Bring the process up (global.cc:105-297 + operations.cc:41-88)."""
    from byteps_tpu.comm.mesh import build_mesh, set_global_mesh
    from byteps_tpu.core.telemetry import PushPullSpeed
    from byteps_tpu.core.tracing import Tracer

    st = _state
    with st._lock:
        if st.initialized:
            return st
        # byteps_init re-reads env on every (re-)init — elastic resume
        # rewrites DMLC_* then re-initializes (operations.cc:96-112)
        cfg = reset_config() if fresh_env else get_config()
        st.config = cfg
        # log level tracks the env this runtime was started under, not
        # whichever import first loaded the logging module
        from byteps_tpu.common import logging as bpslog

        bpslog.apply_env_level()
        st.registry = get_registry()
        # multi-host JAX runtime (pod slices): opt-in coordinator bring-up —
        # the scheduler-node analogue for the ICI/DCN collective plane
        # (SURVEY §5.8: coordinator ↔ jax.distributed.initialize)
        if os.environ.get("BYTEPS_JAX_DISTRIBUTED", "0") == "1":
            _init_jax_distributed(cfg)
        place_compile_cache()
        st.mesh = build_mesh(cfg.mesh_shape)
        set_global_mesh(st.mesh)
        st.telemetry = PushPullSpeed(enabled=cfg.telemetry_on)
        st.tracer = Tracer(
            enabled=cfg.trace_on,
            start_step=cfg.trace_start_step,
            end_step=cfg.trace_end_step,
            trace_dir=cfg.trace_dir,
            local_rank=cfg.local_rank,
            spans_enabled=cfg.trace_spans,
        )
        # observability plane (docs/observability.md): chaos/ps layers
        # stamp events on the process tracer; the Prometheus endpoint
        # serves the process-global registry; push/pull MB/s rides along
        # as a lazy gauge so a scrape sees throughput next to latency
        from byteps_tpu.core.telemetry import metrics, serve_metrics
        from byteps_tpu.core.tracing import set_process_tracer

        set_process_tracer(st.tracer)
        metrics().gauge_fn("pushpull_mbps", st.telemetry.mbps)
        if cfg.metrics_port > 0 and st.metrics_http is None:
            st.metrics_http = serve_metrics(cfg.metrics_port)
        if cfg.is_distributed:
            # Distributed mode: bring up the PS client (rendezvous with the
            # scheduler, learn server addresses) and the staged host engine
            # (the loops the reference starts in BytePSGlobal::Start,
            # global.cc:299-403).
            from byteps_tpu.common.config import resolve_node_uid
            from byteps_tpu.comm.ps_client import PSClient
            from byteps_tpu.core.engine import PipelineEngine

            if st.node_uid is None:
                st.node_uid = resolve_node_uid()
            st.ps_client = PSClient(cfg, node_uid=st.node_uid)
            st.ps_client.connect()
            # cross-process span identity: the scheduler-assigned rank
            # names this process's track in merged timelines
            if st.ps_client.rank is not None:
                st.tracer.process_name = f"worker{st.ps_client.rank}"
            # flight recorder (docs/observability.md "Flight recorder &
            # doctor"): the engine stamps a ledger record per completed
            # round; the context closure lets each record carry the
            # membership/map epoch + scheduler incarnation it ran under
            from byteps_tpu.core.flightrec import ensure_process_recorder

            client = st.ps_client

            def _flight_ctx(c=client, job=cfg.job_id):
                return {
                    "epoch": c.membership_epoch,
                    "map_epoch": max(c.map_epoch, c._seen_map_epoch),
                    "incarnation": c.sched_incarnation,
                    "degraded": 0 if c._sched_up.is_set() else 1,
                    # multi-tenant dimension (docs/async.md): per-step
                    # records carry the job for the slo_breach rule and
                    # the cluster matrix's per-tenant slice
                    "job": job,
                }

            st.flightrec = ensure_process_recorder(
                cfg, context_fn=_flight_ctx, tracer=st.tracer
            )
            st.engine = PipelineEngine(
                cfg, st.ps_client, st.telemetry, st.tracer,
                flightrec=st.flightrec,
            )
            st.engine.start()
        st.initialized = True
        return st


def shutdown_state() -> None:
    """Tear down (byteps_shutdown → global.cc:319-403)."""
    st = _state
    with st._lock:
        if not st.initialized:
            return
        if st.engine is not None:
            st.engine.stop()
            st.engine = None
        if st.ps_client is not None:
            st.ps_client.close()
            st.ps_client = None
        # a compiled loop's last step ends here, while the recorder that
        # judges it is still the process's
        from byteps_tpu.core.tracing import close_steps

        close_steps()
        if st.flightrec is not None:
            # drop the process recorder: its context closure holds the
            # closed client, and the next init owns a fresh ring
            from byteps_tpu.core.flightrec import (
                get_process_recorder,
                set_process_recorder,
            )

            if get_process_recorder() is st.flightrec:
                set_process_recorder(None)
            st.flightrec = None
        if st.tracer is not None:
            st.tracer.flush()
        if st.metrics_http is not None:
            st.metrics_http.close()
            st.metrics_http = None
        st.handles.clear()
        st.initialized = False


def require_state() -> RuntimeState:
    if not _state.initialized:
        raise RuntimeError("byteps_tpu not initialized; call byteps_tpu.init()")
    return _state
