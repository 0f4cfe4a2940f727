"""Environment-variable configuration system.

The reference is configured purely through env vars (docs/env.md; SURVEY §5.6)
— no config files, no argparse in the core.  We keep the same knob names where
they still make sense on TPU, add TPU-specific ones under the same prefix,
and expose everything as one typed, reloadable ``Config`` object.

Reference consumption points cited per field.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def resolve_node_uid(explicit: Optional[str] = None) -> str:
    """Stable node identity for scheduler rejoin matching: explicit value
    (runtime state persists one across suspend/resume) > ``BYTEPS_NODE_UID``
    env (operator-assigned, survives process restart) > fresh uuid."""
    import uuid

    return explicit or os.environ.get("BYTEPS_NODE_UID") or uuid.uuid4().hex


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else default


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v in (None, ""):
        return default
    return v.lower() not in ("0", "false", "no", "off")


def _env_str(name: str, default: str) -> str:
    v = os.environ.get(name)
    return v if v not in (None, "") else default


@dataclasses.dataclass
class Config:
    """Process-wide configuration snapshot.

    Call :func:`get_config` for the cached instance; :func:`reset_config`
    re-reads the environment (used by elastic ``resume()`` which rewrites
    DMLC_* env before re-init, common/__init__.py:75-82 in the reference).
    """

    # --- topology (DMLC_*, docs/env.md:1-37) ---
    role: str = "worker"  # worker | server | scheduler | joint
    num_worker: int = 1
    num_server: int = 0
    worker_id: int = 0
    ps_root_uri: str = "127.0.0.1"
    ps_root_port: int = 9000
    node_host: str = ""

    # --- local identity (communicator.cc:67-83) ---
    local_rank: int = 0
    local_size: int = 1
    global_rank: Optional[int] = None

    # --- pipeline tuning ---
    partition_bytes: int = 4096000  # BYTEPS_PARTITION_BYTES (global.cc:42,134)
    scheduling_credit: int = 0  # BYTEPS_SCHEDULING_CREDIT (scheduled_queue.cc:35); 0 = unlimited
    # queue discipline: "priority" = (priority desc, key asc) — the OSDI'20
    # scheduler; "fifo" = strict arrival order, the ablation baseline
    # (equivalent to the reference built without scheduling)
    scheduling: str = "priority"  # BYTEPS_SCHEDULING
    min_compress_bytes: int = 65536  # BYTEPS_MIN_COMPRESS_BYTES (global.cc:43,137)
    threadpool_size: int = 4  # BYTEPS_THREADPOOL_SIZE (global.cc:216)

    # --- adaptive compression (docs/gradient-compression.md "Compressed
    # wire path") ---
    # telemetry-driven codec selection: the COMPRESS stage tracks each
    # key's observed wire ratio (compressed bytes / raw bytes) and, after
    # the probe rounds, DISABLES the codec for keys where compression is
    # a loss (ratio above the cutoff — tiny tensors, k too close to n,
    # codec overhead beating the savings).  Disabling is worker-local and
    # per-key: the server's chain stays registered and serves raw pushes/
    # pulls for that key correctly (mixed-config rule), so no wire
    # coordination is needed.  Off by default — the configured codec is
    # a user decision until the operator opts into the policy.
    compression_auto: bool = False  # BYTEPS_COMPRESSION_AUTO
    # observed-ratio cutoff: a key whose mean wire ratio over the probe
    # rounds is >= this stops compressing (1.0 = only when compression
    # INFLATES the payload; the 0.9 default also drops near-break-even
    # codecs that pay CPU for <10% wire savings)
    compression_auto_ratio: float = 0.9  # BYTEPS_COMPRESSION_AUTO_RATIO
    # rounds observed per key before the policy verdict
    compression_auto_rounds: int = 3  # BYTEPS_COMPRESSION_AUTO_ROUNDS

    # --- small-tensor fusion (docs/fusion.md) ---
    # partitions at or below this many BYTES take the FUSE stage: same-
    # server neighbors are packed into one multi-key Op.FUSED RPC instead
    # of per-key push+pull pairs — the hot path stops paying per-message
    # overhead for bias/layernorm-sized gradients.  0 disables fusion
    # (every partition keeps its own RPC).  BOTH server engines speak
    # Op.FUSED (the C++ data plane since the native-parity port); off by
    # default purely because coalescing only pays on many-small-key
    # workloads (docs/fusion.md tuning note).
    fusion_threshold: int = 0  # BYTEPS_FUSION_THRESHOLD
    # fusion buffer capacity per destination server; a full buffer
    # flushes immediately
    fusion_bytes: int = 262144  # BYTEPS_FUSION_BYTES
    # max milliseconds a buffered partition may wait for more neighbors
    # before the pack is flushed anyway (latency backstop; the buffer
    # also flushes eagerly whenever the FUSE queue drains)
    fusion_cycle_ms: float = 2.0  # BYTEPS_FUSION_CYCLE_MS

    # --- key→server sharding (global.cc:158-180, 566-677) ---
    key_hash_fn: str = "djb2"  # naive | built_in | djb2 | sdbm | mixed
    enable_mixed_mode: bool = False
    mixed_mode_bound: int = 101  # global.cc:576-578 default
    built_in_hash_coef: int = 1

    # --- server (server.cc:412-456) ---
    server_engine_threads: int = 4  # BYTEPS_SERVER_ENGINE_THREAD
    server_enable_schedule: bool = False  # BYTEPS_SERVER_ENABLE_SCHEDULE
    enable_async: bool = False  # BYTEPS_ENABLE_ASYNC

    # --- multi-tenancy + asynchrony (docs/async.md) ---
    # job id this process belongs to (0 = the default single-tenant
    # namespace): every declared tensor's keys carry it in the top 16
    # bits of the wire key, so several jobs share one PS fleet without
    # key collisions (common/tenancy.py).  Nonzero jobs are a
    # Python-engine-only surface — the C++ server rejects their frames
    # cleanly (ROADMAP: native multi-tenant parity).
    job_id: int = 0  # BYTEPS_JOB_ID
    # weighted share of this job in the scheduler queues (client WFQ)
    # and the server's per-job service weighting — higher = more of the
    # fleet under contention.  Shares are proportional, never absolute:
    # a weight-1 job always progresses (starvation-free WFQ).
    job_priority: int = 1  # BYTEPS_JOB_PRIORITY
    # server-side admission quota for this job's request bytes, in
    # megaBYTES/s (same unit family as BYTEPS_VAN_RATE_MBYTES_S); 0 =
    # unlimited.  Excess requests are DELAYED (token bucket), never
    # dropped — job_quota_deferred counts the deferrals.
    job_quota_mbps: float = 0.0  # BYTEPS_JOB_QUOTA_MBPS
    # per-tenant gate credits in the client scheduler queues: this job's
    # in-flight byte budget (0 = only the global BYTEPS_SCHEDULING_CREDIT
    # applies).  The per-job dimension matters when one queue carries
    # several tenants (in-process fleets, tests).
    job_credit_bytes: int = 0  # BYTEPS_JOB_CREDIT_BYTES
    # async push_pull profile (docs/async.md): this worker's keys are
    # initialized async — the server applies pushes immediately to the
    # authoritative store and pulls return current state, no round
    # barrier.  Per-tensor overridable via declare kwargs
    # (byteps_async="0"/"1").
    async_mode: bool = False  # BYTEPS_ASYNC
    # bounded staleness for async keys (SSP): a pull at round v parks
    # until every peer worker's applied-push version is >= v - N.
    # -1 = unbounded (pure async); 0 degenerates to sequential
    # consistency (every pull waits for all of its round's pushes).
    staleness_bound: int = -1  # BYTEPS_STALENESS_BOUND
    # server-side optimizer plane (docs/architecture.md "Server-side
    # optimizer"): "" = off (servers SUM, workers own the optimizer);
    # a rule name ("sgd" / "momentum" / "adam") declares every float
    # tensor's INIT with the server-opt profile — workers push
    # gradients and pull UPDATED PARAMETERS.  Per-tensor overridable
    # via declare kwargs (byteps_server_opt="adam",
    # byteps_server_opt_hp={"lr": 0.001}).  Python-engine servers
    # only; the native engine rejects the profile cleanly.
    server_opt: str = ""  # BYTEPS_SERVER_OPT
    # JSON hyperparams for the fleet-wide BYTEPS_SERVER_OPT rule, e.g.
    # '{"lr": 0.01, "momentum": 0.9}' — per-tensor kwargs win.
    server_opt_hp: str = ""  # BYTEPS_SERVER_OPT_HP
    # per-job step-time SLO in seconds (0 = off): a completed step
    # slower than this fires the flight recorder's slo_breach trigger
    # (rate-limited bundle, flight_trigger{rule="slo_breach"}).
    job_slo_s: float = 0.0  # BYTEPS_JOB_SLO_S
    # --- failure detection (ps-lite heartbeats, SURVEY §5.3) ---
    heartbeat_interval: float = 5.0  # BYTEPS_HEARTBEAT_INTERVAL; 0 disables
    # scheduler-side liveness policy: a registered node whose heartbeat
    # age exceeds this is evicted from the membership (book re-broadcast,
    # rounds re-sized) — 0 disables eviction (ages stay observable via
    # Op.QUERY, the pre-policy behavior)
    dead_node_timeout_s: float = 0.0  # BYTEPS_DEAD_NODE_TIMEOUT_S

    # --- control-plane recovery (docs/robustness.md "Control-plane
    # recovery") ---
    # scheduler-link loss no longer latches the node dead: a reconnect
    # state machine redials DMLC_PS_ROOT_URI:PORT this many times
    # (after the first loss) while the data plane keeps training on the
    # last-adopted book.  0 restores the legacy terminal latch.
    sched_reconnect_retries: int = 20  # BYTEPS_SCHED_RECONNECT_RETRIES
    # exponential-backoff base between redials (full jitter, capped 10s)
    sched_reconnect_backoff_s: float = 0.5  # BYTEPS_SCHED_RECONNECT_BACKOFF_S
    # scheduler-side rejoin grace: a RESTARTED scheduler (one whose
    # registrants report a prior incarnation) waits this long for every
    # previously-reported rank to re-REGISTER before adopting the
    # partial population and emitting books — slow reconnectors are not
    # mass-evicted at rebirth.  Irrelevant on a fresh first boot.
    sched_rejoin_window_s: float = 15.0  # BYTEPS_SCHED_REJOIN_WINDOW_S

    # --- per-RPC deadlines + idempotent retry (self-healing data plane) ---
    # attempts AFTER the first before a push/pull/init surfaces its error
    rpc_retries: int = 2  # BYTEPS_RPC_RETRIES; 0 restores fail-fast
    # per-attempt deadline: a server that neither answers nor closes the
    # connection within this window is treated as failed (the connection
    # is torn down and the RPC retried).  0 disables the timer — only
    # connection death then triggers retry; hung servers are left to the
    # scheduler's eviction policy.
    rpc_deadline_s: float = 0.0  # BYTEPS_RPC_DEADLINE_S
    # exponential-backoff base between attempts (full jitter, capped 2s)
    rpc_backoff_s: float = 0.1  # BYTEPS_RPC_BACKOFF_S
    # separate deadline for the init-push barrier, whose ack legitimately
    # waits for every PEER worker: must exceed worst-case worker skew, so
    # it is NOT covered by rpc_deadline_s.  0 = none (default); chaos
    # tests set it small to heal dropped init acks.
    init_deadline_s: float = 0.0  # BYTEPS_INIT_DEADLINE_S
    # synchronous push_pull resubmits a DegradedError'd step this many
    # times (exactly-once safe; api.py) before surfacing the error
    degraded_step_retries: int = 0  # BYTEPS_DEGRADED_STEP_RETRIES

    # --- recovery plane (docs/robustness.md "healing flow") ---
    # rounds of emitted push payloads retained per key by the worker-side
    # round journal (comm/journal.py); a worker that exhausts its RPC
    # retries against a LIVE server replays exactly the journaled rounds
    # the server reports missing (Op.RESYNC_QUERY) and rejoins in place.
    # 0 disables journaling (resync then heals only lost-ack give-ups).
    journal_rounds: int = 2  # BYTEPS_JOURNAL_ROUNDS
    # total byte cap across all journaled payloads; oldest rounds evicted
    journal_bytes: int = 64 << 20  # BYTEPS_JOURNAL_BYTES
    # wall-clock budget for one heal attempt (server resync query +
    # journal replay); 0 disables the in-place heal entirely — give-ups
    # surface DegradedError immediately, the pre-recovery behavior
    resync_deadline_s: float = 5.0  # BYTEPS_RESYNC_DEADLINE_S

    # --- elastic server resharding (docs/robustness.md "migration flow") ---
    # live key migration on server join/leave: ownership is an
    # epoch-stamped consistent-hash ring, old owners ship each re-homed
    # key's state (store + exactly-once ledger + init tokens) to the new
    # owner over Op.MIGRATE_STATE, and stale-map workers chase
    # Op.WRONG_OWNER redirects — no cluster-wide re-init barrier.  Off
    # (default): a server resize re-homes keys via the hash fns and
    # forces the re-init barrier (the pre-resharding behavior).
    elastic_reshard: bool = False  # BYTEPS_ELASTIC_RESHARD
    # virtual nodes per server rank on the ownership ring (also fn="ring")
    ring_vnodes: int = 64  # BYTEPS_RING_VNODES
    # how long a new owner parks requests for a key whose migration is
    # inbound before dropping them back to the caller's retry path
    migrate_deadline_s: float = 10.0  # BYTEPS_MIGRATE_DEADLINE_S

    # --- transport (ps-lite van lanes) ---
    # parallel TCP connections per server, partitions striped across them
    # by key — the implementable analogue of the reference's RDMA/UCX
    # multi-lane vans (setup.py:312-330) for DCN-class cross-host links
    # where one stream cannot fill the pipe.  1 = single stream (default).
    tcp_streams: int = 1  # BYTEPS_TCP_STREAMS
    # C++ worker data plane (native/ps_client.cc): framing, demux, and
    # payload receive on GIL-free lane threads — the core_loops.cc:538-618
    # analogue.  Applies to tcp/uds server links when the native lib is
    # built; the shm van keeps the Python client (mmap bulk path).
    native_client: bool = False  # BYTEPS_NATIVE_CLIENT

    # --- flight recorder + anomaly triggers (docs/observability.md
    # "Flight recorder & doctor") ---
    # always-on bounded ring of per-step records stamped by the engine
    # at round completion (servers stamp per heartbeat beat); 0 disables
    # the recorder AND the trigger engine entirely
    flight_steps: int = 256  # BYTEPS_FLIGHT_STEPS
    # slow-step / straggler / hot-stripe sensitivity: a step (or one
    # peer's p99) must exceed the rolling/peer median by this factor
    flight_slow_factor: float = 3.0  # BYTEPS_FLIGHT_SLOW_FACTOR
    # queue-stall bound: a stage dwell p99 past this many seconds in one
    # step fires the queue_stall trigger
    flight_stall_s: float = 5.0  # BYTEPS_FLIGHT_STALL_S
    # where triggered diagnostic bundles land ("" = <trace_dir>/flight_bundles)
    flight_dir: str = ""  # BYTEPS_FLIGHT_DIR
    # per-rule bundle rate limit: one dump per rule per this many seconds
    # (triggers past the limit still count in flight_trigger{rule})
    flight_bundle_s: float = 60.0  # BYTEPS_FLIGHT_BUNDLE_S
    # upload dumped trigger bundles (compact form) over the control
    # plane into the SCHEDULER's BYTEPS_FLIGHT_DIR — fleet-central
    # incident evidence beside the autotuner's decision bundles
    flight_upload: bool = False  # BYTEPS_FLIGHT_UPLOAD

    # --- debug / trace / observability (global.cc:113-124; docs/observability.md) ---
    log_level: str = "WARNING"
    trace_on: bool = False
    trace_start_step: int = 10
    trace_end_step: int = 20
    trace_dir: str = "."
    # distributed spans (docs/observability.md): with tracing on, engine
    # tasks get trace/span ids that ride every framed RPC and the server
    # stamps child spans.  BYTEPS_TRACE_SPANS=0 keeps the classic
    # per-tensor stage envelopes but drops span events + wire context.
    trace_spans: bool = True  # BYTEPS_TRACE_SPANS
    telemetry_on: bool = False
    # Prometheus text exposition port, served per process (worker,
    # server, and the scheduler's cluster aggregate).  0 disables.  When
    # several processes share a host and the port is taken, the process
    # falls back to an ephemeral port and logs it.
    metrics_port: int = 0  # BYTEPS_METRICS_PORT
    force_distributed: bool = False  # BYTEPS_FORCE_DISTRIBUTED (global.cc:149-152)
    debug_sample_tensor: str = ""

    # --- TPU-native additions (no reference analogue) ---
    mesh_shape: str = ""  # e.g. "dp:8" or "dp:4,tp:2" — override auto mesh
    ici_reduce: str = "scatter_gather"  # scatter_gather | psum
    compression_device: str = "auto"  # auto | device | host

    @property
    def size(self) -> int:
        return self.num_worker

    @property
    def is_distributed(self) -> bool:
        """Distributed mode engages the PS path (global.cc:149-152): more
        than one worker, or BYTEPS_FORCE_DISTRIBUTED for the single-worker
        fake-cluster test topology."""
        return self.num_worker > 1 or self.force_distributed

    @property
    def is_root(self) -> bool:
        """Local root does the PS networking (global.cc:286-287).  The
        reference picks the *highest* local rank as root
        (communicator.cc:94)."""
        return self.local_rank == self.local_size - 1

    @staticmethod
    def from_env() -> "Config":
        return Config(
            role=_env_str("DMLC_ROLE", "worker"),
            num_worker=_env_int("DMLC_NUM_WORKER", 1),
            num_server=_env_int("DMLC_NUM_SERVER", 0),
            worker_id=_env_int("DMLC_WORKER_ID", 0),
            ps_root_uri=_env_str("DMLC_PS_ROOT_URI", "127.0.0.1"),
            ps_root_port=_env_int("DMLC_PS_ROOT_PORT", 9000),
            node_host=_env_str("DMLC_NODE_HOST", ""),
            local_rank=_env_int("BYTEPS_LOCAL_RANK", 0),
            local_size=_env_int("BYTEPS_LOCAL_SIZE", 1),
            global_rank=(
                int(os.environ["BYTEPS_GLOBAL_RANK"])
                if os.environ.get("BYTEPS_GLOBAL_RANK")
                else None
            ),
            partition_bytes=_env_int("BYTEPS_PARTITION_BYTES", 4096000),
            scheduling_credit=_env_int("BYTEPS_SCHEDULING_CREDIT", 0),
            scheduling=os.environ.get("BYTEPS_SCHEDULING", "priority"),
            min_compress_bytes=_env_int("BYTEPS_MIN_COMPRESS_BYTES", 65536),
            threadpool_size=_env_int("BYTEPS_THREADPOOL_SIZE", 4),
            compression_auto=_env_bool("BYTEPS_COMPRESSION_AUTO"),
            compression_auto_ratio=float(
                os.environ.get("BYTEPS_COMPRESSION_AUTO_RATIO", "0.9")
                or "0.9"
            ),
            compression_auto_rounds=max(
                1, _env_int("BYTEPS_COMPRESSION_AUTO_ROUNDS", 3)
            ),
            fusion_threshold=max(0, _env_int("BYTEPS_FUSION_THRESHOLD", 0)),
            fusion_bytes=max(1, _env_int("BYTEPS_FUSION_BYTES", 262144)),
            fusion_cycle_ms=max(0.0, float(
                os.environ.get("BYTEPS_FUSION_CYCLE_MS", "2") or "2"
            )),
            key_hash_fn=_env_str("BYTEPS_KEY_HASH_FN", "djb2"),
            enable_mixed_mode=_env_bool("BYTEPS_ENABLE_MIXED_MODE"),
            mixed_mode_bound=_env_int("BYTEPS_MIXED_MODE_BOUND", 101),
            built_in_hash_coef=_env_int("BYTEPS_BUILT_IN_HASH_COEF", 1),
            server_engine_threads=_env_int("BYTEPS_SERVER_ENGINE_THREAD", 4),
            server_enable_schedule=_env_bool("BYTEPS_SERVER_ENABLE_SCHEDULE"),
            enable_async=_env_bool("BYTEPS_ENABLE_ASYNC"),
            job_id=min(
                (1 << 16) - 1, max(0, _env_int("BYTEPS_JOB_ID", 0))
            ),
            job_priority=max(1, _env_int("BYTEPS_JOB_PRIORITY", 1)),
            job_quota_mbps=max(0.0, float(
                os.environ.get("BYTEPS_JOB_QUOTA_MBPS", "0") or "0"
            )),
            job_credit_bytes=max(0, _env_int("BYTEPS_JOB_CREDIT_BYTES", 0)),
            async_mode=_env_bool("BYTEPS_ASYNC"),
            staleness_bound=max(-1, _env_int("BYTEPS_STALENESS_BOUND", -1)),
            server_opt=_env_str("BYTEPS_SERVER_OPT", "").strip().lower(),
            server_opt_hp=_env_str("BYTEPS_SERVER_OPT_HP", ""),
            job_slo_s=max(0.0, float(
                os.environ.get("BYTEPS_JOB_SLO_S", "0") or "0"
            )),
            heartbeat_interval=float(
                os.environ.get("BYTEPS_HEARTBEAT_INTERVAL", "5") or "5"
            ),
            dead_node_timeout_s=float(
                os.environ.get("BYTEPS_DEAD_NODE_TIMEOUT_S", "0") or "0"
            ),
            sched_reconnect_retries=max(
                0, _env_int("BYTEPS_SCHED_RECONNECT_RETRIES", 20)
            ),
            sched_reconnect_backoff_s=float(
                os.environ.get("BYTEPS_SCHED_RECONNECT_BACKOFF_S", "0.5")
                or "0.5"
            ),
            sched_rejoin_window_s=float(
                os.environ.get("BYTEPS_SCHED_REJOIN_WINDOW_S", "15") or "15"
            ),
            rpc_retries=max(0, _env_int("BYTEPS_RPC_RETRIES", 2)),
            rpc_deadline_s=float(
                os.environ.get("BYTEPS_RPC_DEADLINE_S", "0") or "0"
            ),
            rpc_backoff_s=float(
                os.environ.get("BYTEPS_RPC_BACKOFF_S", "0.1") or "0.1"
            ),
            init_deadline_s=float(
                os.environ.get("BYTEPS_INIT_DEADLINE_S", "0") or "0"
            ),
            degraded_step_retries=max(
                0, _env_int("BYTEPS_DEGRADED_STEP_RETRIES", 0)
            ),
            journal_rounds=max(0, _env_int("BYTEPS_JOURNAL_ROUNDS", 2)),
            journal_bytes=max(1, _env_int("BYTEPS_JOURNAL_BYTES", 64 << 20)),
            resync_deadline_s=float(
                os.environ.get("BYTEPS_RESYNC_DEADLINE_S", "5") or "5"
            ),
            elastic_reshard=_env_bool("BYTEPS_ELASTIC_RESHARD"),
            ring_vnodes=max(1, _env_int("BYTEPS_RING_VNODES", 64)),
            migrate_deadline_s=float(
                os.environ.get("BYTEPS_MIGRATE_DEADLINE_S", "10") or "10"
            ),
            tcp_streams=max(1, _env_int("BYTEPS_TCP_STREAMS", 1)),
            native_client=_env_bool("BYTEPS_NATIVE_CLIENT"),
            flight_steps=max(0, _env_int("BYTEPS_FLIGHT_STEPS", 256)),
            flight_slow_factor=max(1.1, float(
                os.environ.get("BYTEPS_FLIGHT_SLOW_FACTOR", "3") or "3"
            )),
            flight_stall_s=max(0.001, float(
                os.environ.get("BYTEPS_FLIGHT_STALL_S", "5") or "5"
            )),
            flight_dir=_env_str("BYTEPS_FLIGHT_DIR", ""),
            flight_upload=_env_bool("BYTEPS_FLIGHT_UPLOAD"),
            flight_bundle_s=max(0.0, float(
                os.environ.get("BYTEPS_FLIGHT_BUNDLE_S", "60") or "60"
            )),
            log_level=_env_str("BYTEPS_LOG_LEVEL", "WARNING"),
            trace_on=_env_bool("BYTEPS_TRACE_ON"),
            trace_start_step=_env_int("BYTEPS_TRACE_START_STEP", 10),
            trace_end_step=_env_int("BYTEPS_TRACE_END_STEP", 20),
            trace_dir=_env_str("BYTEPS_TRACE_DIR", "."),
            trace_spans=_env_bool("BYTEPS_TRACE_SPANS", True),
            telemetry_on=_env_bool("BYTEPS_TELEMETRY_ON"),
            metrics_port=max(0, _env_int("BYTEPS_METRICS_PORT", 0)),
            force_distributed=_env_bool("BYTEPS_FORCE_DISTRIBUTED"),
            debug_sample_tensor=_env_str("BYTEPS_DEBUG_SAMPLE_TENSOR", ""),
            mesh_shape=_env_str("BYTEPS_TPU_MESH", ""),
            ici_reduce=_env_str("BYTEPS_TPU_ICI_REDUCE", "scatter_gather"),
            compression_device=_env_str("BYTEPS_TPU_COMPRESSION_DEVICE", "auto"),
        )


_config: Optional[Config] = None


def get_config() -> Config:
    global _config
    if _config is None:
        _config = Config.from_env()
    return _config


def reset_config() -> Config:
    """Re-read the environment (elastic resume path)."""
    global _config
    _config = Config.from_env()
    return _config


def set_config(cfg: Config) -> None:
    global _config
    _config = cfg


def clear_config() -> None:
    """Drop the cached snapshot; the next get_config() re-reads env."""
    global _config
    _config = None
