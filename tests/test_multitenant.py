"""Async & multi-tenant plane (docs/async.md): job-id key namespacing,
the async push_pull profile with bounded staleness, per-tenant QoS, and
the per-tenant SLO surface.

Layers under test:

- tenancy key codec + registry namespacing (job 0 bit-identical);
- client scheduler WFQ: starvation-freedom, no priority inversion,
  per-job gate credits, single-job order unchanged;
- server engine-queue WFQ + the admission quota bucket;
- wire-level async profile against a live PSServer: immediate apply,
  exactly-once under replay, bounded-staleness park/unblock,
  `BYTEPS_STALENESS_BOUND=0` = sequential consistency, per-job round
  sizing (two jobs with different worker counts on one server);
- native interop: the C++ engine rejects job-namespaced frames and
  async-profile INITs with the clean status=1 echo, stream stays framed;
- slo_breach trigger: fires on an absolute SLO violation, exactly one
  bundle under the rate limiter;
- the acceptance demo: a latency-sensitive sync job and a bulk job
  share 2 shaped Python-engine servers — QoS on keeps the latency
  job's p99 within 1.5x its solo baseline while QoS off does not, the
  slo_breach trigger fires under contention (one bundle), and the
  async tenant's state stays the exact sum of applied pushes under
  injected chaos retries (`chaos_soak.py --multi-tenant`).
"""

import json
import os
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.common.tenancy import (
    JOB_SHIFT,
    MAX_JOB_ID,
    base_key,
    job_key,
    job_of_key,
)
from byteps_tpu.common.types import (
    DataType,
    QueueType,
    RequestType,
    TensorTableEntry,
    get_command_type,
)
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.comm.transport import (
    Message,
    Op,
    close_socket,
    connect,
    recv_message,
    send_message,
)
from byteps_tpu.core.scheduler import ScheduledQueue, set_job_weight
from byteps_tpu.core.telemetry import counters
from byteps_tpu.server.server import PSServer, _EngineQueue, _QuotaBucket

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, int(DataType.FLOAT32))


# --- tenancy key codec -----------------------------------------------------


class TestTenancyKeys:
    def test_roundtrip(self):
        k = job_key(7, (3 << 16) | 2)
        assert job_of_key(k) == 7
        assert base_key(k) == (3 << 16) | 2
        assert k >> JOB_SHIFT == 7

    def test_job_zero_is_identity(self):
        assert job_key(0, 12345) == 12345
        assert job_of_key(12345) == 0

    def test_bounds(self):
        with pytest.raises(ValueError):
            job_key(MAX_JOB_ID + 1, 0)
        with pytest.raises(ValueError):
            job_key(1, 1 << JOB_SHIFT)  # key already carries job bits

    def test_registry_namespaces_keys(self):
        from byteps_tpu.common.registry import TensorRegistry

        reg = TensorRegistry()
        a = reg.declare("t", byteps_job="3")
        b = reg.declare("u")  # default job (BYTEPS_JOB_ID unset → 0)
        assert a.job == 3 and job_of_key(a.key_for_part(0)) == 3
        assert base_key(a.key_for_part(1)) == (a.declared_key << 16) + 1
        assert b.job == 0 and b.key_for_part(0) == b.declared_key << 16

    def test_redeclare_keeps_job(self):
        from byteps_tpu.common.registry import TensorRegistry

        reg = TensorRegistry()
        reg.declare("t", byteps_job="5")
        reg.redeclare_all()
        assert reg.get("t").job == 5


# --- client scheduler WFQ --------------------------------------------------


def _task(job: int, key: int, priority: int = 0, length: int = 25) -> TensorTableEntry:
    return TensorTableEntry(
        tensor_name=f"j{job}.k{key}", key=key, priority=priority,
        length=length, queue_list=[QueueType.PUSH], job=job,
    )


class TestSchedulerWFQ:
    def test_single_job_order_unchanged(self):
        q = ScheduledQueue(QueueType.PUSH)
        for prio, key in [(0, 3), (5, 1), (5, 2), (1, 9)]:
            q.add_task(_task(0, key, priority=prio))
        order = [q.get_task(0.1).key for _ in range(4)]
        assert order == [1, 2, 9, 3]  # (priority desc, key asc)

    def test_starvation_freedom(self):
        # a weight-10 latency tenant cannot starve a weight-1 bulk
        # tenant: the bulk job's pops interleave at its weighted share
        set_job_weight(11, 10)
        set_job_weight(22, 1)
        q = ScheduledQueue(QueueType.PUSH)
        for i in range(30):
            q.add_task(_task(11, 100 + i))
        for i in range(3):
            q.add_task(_task(22, 200 + i))
        seq = [q.get_task(0.1).job for _ in range(33)]
        first_bulk = seq.index(22)
        assert first_bulk < 25, f"bulk tenant starved: first pop {first_bulk}"
        assert seq.count(22) == 3  # every bulk task eventually popped

    def test_no_priority_inversion(self):
        # bulk tasks with GIANT task priorities queued first must not
        # delay the latency tenant's pop beyond its share: task
        # priority only orders WITHIN a job
        set_job_weight(11, 100)
        set_job_weight(22, 1)
        q = ScheduledQueue(QueueType.PUSH)
        for i in range(10):
            q.add_task(_task(22, 300 + i, priority=10**6))
        q.add_task(_task(11, 1, priority=0))
        first_two = [q.get_task(0.1).job for _ in range(2)]
        assert 11 in first_two, (
            f"latency tenant delayed past its share: {first_two}"
        )

    def test_per_job_gate_credits(self):
        # job 22's in-flight bytes capped at 150 (itemsize 4, length 30
        # = 120B per task): a second task waits for report_finish while
        # another tenant keeps flowing
        set_job_weight(11, 1)
        set_job_weight(22, 1)
        q = ScheduledQueue(QueueType.PUSH, job_credits={22: 150})
        t1, t2 = _task(22, 1, length=30), _task(22, 2, length=30)
        q.add_task(t1)
        q.add_task(t2)
        q.add_task(_task(11, 3, length=30))
        got1 = q.get_task(0.1)
        assert got1.job == 22
        nxt = q.get_task(0.1)
        assert nxt.job == 11, "other tenants must flow past a spent budget"
        assert q.get_task(0.1) is None  # job 22's budget is spent
        q.report_finish(got1)
        assert q.get_task(0.1).key == 2  # credits returned → eligible


# --- server engine queue + quota bucket ------------------------------------


class TestServerQoS:
    def test_engine_queue_single_lane_fifo(self):
        q = _EngineQueue(enable_schedule=False)
        for i in range(3):
            q.put(0, f"item{i}")
        assert [q.get(0.1) for _ in range(3)] == ["item0", "item1", "item2"]

    def test_engine_queue_wfq_across_jobs(self):
        weights = {1: 10.0, 2: 1.0}
        q = _EngineQueue(enable_schedule=False,
                         weight_fn=lambda j: weights.get(j, 1.0))
        for i in range(5):
            q.put(0, f"bulk{i}", job=2, cost=1000)
        q.put(0, "latency", job=1, cost=10)
        first_two = [q.get(0.1) for _ in range(2)]
        assert "latency" in first_two
        rest = [q.get(0.1) for _ in range(4)]
        assert all(r.startswith("bulk") for r in rest)

    def test_quota_bucket_defers_past_rate(self):
        # a request is admitted when the virtual wire is free; its own
        # serialization time extends the wire, so sustained overload
        # defers every FOLLOWING request
        b = _QuotaBucket(1.0)  # 1 MB/s, 0.25s burst
        assert b.reserve(200_000) == 0.0  # inside the burst window
        b.reserve(500_000)  # occupies the wire for ~0.45s
        d = b.reserve(100_000)
        assert d > 0.2, f"overload not deferred: {d}"

    def test_server_quota_defers_then_serves(self):
        srv = PSServer(Config(num_worker=1, num_server=1))
        srv.start(register=False)
        try:
            srv._adopt_jobs({"jobs": {"5": {
                "workers": [0], "priority": 1, "quota_mbps": 0.5,
            }}})
            key = job_key(5, 7 << 16)
            w = connect(srv.host, srv.port)
            _init([(w, 1)], key, 65536)
            payload = np.ones(65536, dtype=np.float32).tobytes()  # 256KB
            t0 = time.monotonic()
            for v in (1, 2):
                send_message(w, Message(
                    Op.PUSH, key=key, seq=v, flags=1, version=v,
                    cmd=CMD_F32, payload=payload,
                ))
                msg = recv_message(w)
                assert msg.op == Op.PUSH and msg.status == 0
            took = time.monotonic() - t0
            from byteps_tpu.core.telemetry import counters

            labeled = counters().snapshot_labeled().get(
                "job_quota_deferred", {}
            )
            deferred = sum(
                v for lkey, v in labeled.items()
                if dict(lkey).get("job") == "5"
            )
            assert deferred >= 1, "second 256KB push at 0.5MB/s not metered"
            assert took > 0.1, f"deferral should have delayed: {took}"
            close_socket(w)
        finally:
            srv.stop()


# --- wire-level async profile ----------------------------------------------


def _init(socks_flags, key: int, n: int, async_profile=False,
          staleness=-1):
    payload = struct.pack("!QI", n, int(DataType.FLOAT32))
    if async_profile:
        payload += struct.pack("!Bi", 1, staleness)
    for i, (sock, flag) in enumerate(socks_flags):
        send_message(sock, Message(
            Op.INIT, key=key, seq=900 + i, flags=flag, version=i + 1,
            payload=payload,
        ))
    for sock, _ in socks_flags:
        msg = recv_message(sock)
        assert msg.op == Op.INIT and msg.status == 0


def _push(sock, key, version, arr, flag):
    send_message(sock, Message(
        Op.PUSH, key=key, seq=1000 + version, flags=flag, version=version,
        cmd=CMD_F32, payload=arr.tobytes(),
    ))
    msg = recv_message(sock)
    assert msg.op == Op.PUSH and msg.status == 0


def _pull(sock, key, version):
    send_message(sock, Message(
        Op.PULL, key=key, seq=2000 + version, version=version, cmd=CMD_F32,
    ))
    msg = recv_message(sock)
    assert msg.op == Op.PULL
    return np.frombuffer(msg.payload, dtype=np.float32), msg.version


class TestAsyncProfile:
    def _server(self, workers=1):
        srv = PSServer(Config(num_worker=workers, num_server=1))
        srv.start(register=False)
        return srv

    def test_sync_init_stays_sync(self):
        srv = self._server()
        try:
            w = connect(srv.host, srv.port)
            _init([(w, 1)], 3 << 16, 8)
            ks = srv._key_state(3 << 16)
            assert not ks.async_mode and ks.staleness == -1
            close_socket(w)
        finally:
            srv.stop()

    def test_async_pushes_apply_immediately(self):
        srv = self._server()
        KEY, N = job_key(4, 1 << 16), 16
        try:
            w = connect(srv.host, srv.port)
            _init([(w, 1)], KEY, N, async_profile=True)
            ks = srv._key_state(KEY)
            assert ks.async_mode and ks.staleness == -1
            assert ks.job == 4
            g1 = np.arange(N, dtype=np.float32)
            g2 = np.full(N, 2.0, dtype=np.float32)
            _push(w, KEY, 1, g1, flag=1)
            out, ver = _pull(w, KEY, 1)
            np.testing.assert_array_equal(out, g1)
            assert ver == 1
            _push(w, KEY, 2, g2, flag=1)
            out, ver = _pull(w, KEY, 2)
            np.testing.assert_array_equal(out, g1 + g2)  # cumulative store
            assert ver == 2
            close_socket(w)
        finally:
            srv.stop()

    def test_async_replay_dedupes(self):
        srv = self._server()
        KEY, N = job_key(4, 2 << 16), 8
        try:
            w = connect(srv.host, srv.port)
            _init([(w, 1)], KEY, N, async_profile=True)
            g = np.ones(N, dtype=np.float32)
            _push(w, KEY, 1, g, flag=1)
            _push(w, KEY, 1, g, flag=1)  # retransmit: ack, no re-sum
            out, ver = _pull(w, KEY, 1)
            np.testing.assert_array_equal(out, g)
            assert ver == 1, "replay must not advance the version"
            close_socket(w)
        finally:
            srv.stop()

    def test_staleness_pull_parks_and_peer_push_unblocks(self):
        # bound 0 (sequential consistency): w1's pull of round 1 parks
        # until w2's round-1 push APPLIES — the unblocking event is the
        # peer push itself
        srv = self._server(workers=2)
        KEY, N = job_key(6, 1 << 16), 8
        try:
            w1 = connect(srv.host, srv.port)
            w2 = connect(srv.host, srv.port)
            _init([(w1, 1), (w2, 2)], KEY, N, async_profile=True,
                  staleness=0)
            ks = srv._key_state(KEY)
            assert ks.staleness == 0
            g1 = np.ones(N, dtype=np.float32)
            g2 = np.full(N, 3.0, dtype=np.float32)
            _push(w1, KEY, 1, g1, flag=1)
            box = {}

            def puller():
                box["out"], box["ver"] = _pull(w1, KEY, 1)

            t = threading.Thread(target=puller, daemon=True)
            t.start()
            t.join(timeout=0.4)
            assert t.is_alive(), "pull served past the staleness bound"
            _push(w2, KEY, 1, g2, flag=2)  # the unblocking peer push
            t.join(timeout=5)
            assert not t.is_alive(), "peer push did not release the pull"
            np.testing.assert_array_equal(box["out"], g1 + g2)
            close_socket(w1)
            close_socket(w2)
        finally:
            srv.stop()

    def test_staleness_bound_allows_lag_within_window(self):
        # bound 1: a pull at round 2 is served while the slowest peer
        # has only applied round 1 (lag 1 <= bound)
        srv = self._server(workers=2)
        KEY, N = job_key(6, 2 << 16), 4
        try:
            w1 = connect(srv.host, srv.port)
            w2 = connect(srv.host, srv.port)
            _init([(w1, 1), (w2, 2)], KEY, N, async_profile=True,
                  staleness=1)
            g = np.ones(N, dtype=np.float32)
            _push(w2, KEY, 1, g, flag=2)
            _push(w1, KEY, 1, g, flag=1)
            _push(w1, KEY, 2, g, flag=1)
            out, _ver = _pull(w1, KEY, 2)  # min applied = 1 >= 2 - 1
            np.testing.assert_array_equal(out, 3 * g)
            close_socket(w1)
            close_socket(w2)
        finally:
            srv.stop()

    def test_unbounded_staleness_never_parks(self):
        srv = self._server(workers=2)
        KEY, N = job_key(6, 3 << 16), 4
        try:
            w1 = connect(srv.host, srv.port)
            w2 = connect(srv.host, srv.port)
            _init([(w1, 1), (w2, 2)], KEY, N, async_profile=True,
                  staleness=-1)
            g = np.ones(N, dtype=np.float32)
            _push(w1, KEY, 1, g, flag=1)
            _push(w1, KEY, 2, g, flag=1)  # peer never pushed at all
            out, _ver = _pull(w1, KEY, 5)
            np.testing.assert_array_equal(out, 2 * g)
            close_socket(w1)
            close_socket(w2)
        finally:
            srv.stop()

    def test_reinit_without_extension_returns_key_to_sync(self):
        # KeyState outlives client shutdown()/init() cycles: a fresh
        # generation's SYNC init (classic 12-byte payload) must CLEAR a
        # previously-declared async profile, or the rerun silently
        # trains async (review finding)
        srv = self._server()
        KEY, N = job_key(4, 9 << 16), 4
        try:
            w = connect(srv.host, srv.port)
            _init([(w, 1)], KEY, N, async_profile=True, staleness=2)
            ks = srv._key_state(KEY)
            assert ks.async_mode and ks.staleness == 2
            # new generation, fresh token, no extension → sync again
            payload = struct.pack("!QI", N, int(DataType.FLOAT32))
            send_message(w, Message(Op.INIT, key=KEY, seq=950, flags=1,
                                    version=77, payload=payload))
            assert recv_message(w).op == Op.INIT
            assert not ks.async_mode and ks.staleness == -1
            close_socket(w)
        finally:
            srv.stop()

    def test_per_job_round_sizing(self):
        # one server, two tenants with DIFFERENT worker counts: job 1
        # (2 workers) completes sync rounds with 2 pushes, job 2 (1
        # worker) with 1 — the fleet total (3) never gates either
        srv = self._server(workers=3)
        srv._adopt_jobs({"jobs": {
            "1": {"workers": [0, 1], "priority": 1, "quota_mbps": 0},
            "2": {"workers": [2], "priority": 1, "quota_mbps": 0},
        }})
        K1, K2, N = job_key(1, 1 << 16), job_key(2, 1 << 16), 4
        try:
            w1 = connect(srv.host, srv.port)
            w2 = connect(srv.host, srv.port)
            w3 = connect(srv.host, srv.port)
            _init([(w1, 1), (w2, 2)], K1, N)
            _init([(w3, 3)], K2, N)
            g = np.ones(N, dtype=np.float32)
            # job 2's round publishes with ONE push
            _push(w3, K2, 1, g, flag=3)
            out, _ = _pull(w3, K2, 1)
            np.testing.assert_array_equal(out, g)
            # job 1's round needs BOTH of its workers (not job 2's)
            _push(w1, K1, 1, g, flag=1)
            box = {}

            def puller():
                box["out"], _ = _pull(w1, K1, 1)

            t = threading.Thread(target=puller, daemon=True)
            t.start()
            t.join(timeout=0.3)
            assert t.is_alive(), "job-1 round published short"
            _push(w2, K1, 1, 2 * g, flag=2)
            t.join(timeout=5)
            assert not t.is_alive()
            np.testing.assert_array_equal(box["out"], 3 * g)
            for s in (w1, w2, w3):
                close_socket(s)
        finally:
            srv.stop()


# --- native interop --------------------------------------------------------


class TestNativeInterop:
    def test_native_rejects_job_and_async_frames(self):
        from conftest import have_native_parity_server

        if not have_native_parity_server():
            pytest.skip("native lib not built")
        from byteps_tpu.native import get_lib, native_server_counters

        lib = get_lib()
        port = lib.bps_native_server_start(0, 1, 0)
        assert port > 0
        try:
            s = connect("127.0.0.1", port)
            # async-profile INIT → clean status=1 echo
            payload = struct.pack("!QI", 8, 0) + struct.pack("!Bi", 1, 2)
            send_message(s, Message(Op.INIT, key=5, seq=1, flags=1,
                                    version=7, payload=payload))
            r = recv_message(s)
            assert r.op == Op.INIT and r.status != 0
            # job-namespaced PUSH → clean status=1 echo
            jkey = job_key(3, 5)
            send_message(s, Message(Op.PUSH, key=jkey, seq=2, flags=1,
                                    version=1, cmd=CMD_F32,
                                    payload=b"\x00" * 32))
            r = recv_message(s)
            assert r.op == Op.PUSH and r.status != 0 and r.key == jkey
            # the stream stayed framed: a plain PING still round-trips
            send_message(s, Message(Op.PING, seq=3))
            r = recv_message(s)
            assert r.op == Op.PING and r.status == 0
            ctrs = native_server_counters(port)
            assert ctrs.get("native_job_reject", 0) >= 1
            assert ctrs.get("native_async_reject", 0) >= 1
            close_socket(s)
        finally:
            lib.bps_native_server_stop(port)

    def test_client_surfaces_refused_init(self):
        # the CLIENT side of the clean rejection: a status!=0 INIT echo
        # (native server refusing a job-namespaced or async key) must
        # raise, not read as a successful barrier — training on would
        # run the whole job against uninitialized state (review finding)
        from byteps_tpu.comm.ps_client import PSClient

        client = object.__new__(PSClient)
        client.rank = 0
        client.membership_epoch = 0
        client._init_seq_lock = threading.Lock()
        client._init_seqs = {}
        client._init_salt = 1
        client._blocking_request_retrying = (
            lambda key, mk, errmsg, use_deadline=True: Message(
                Op.INIT, key=key, status=1
            )
        )
        with pytest.raises(RuntimeError, match="Python-engine"):
            client.init_tensor(job_key(3, 1 << 16), 8, 0)
        with pytest.raises(RuntimeError, match="Python-engine"):
            client.init_tensor(1 << 16, 8, 0, async_profile=True)
        with pytest.raises(RuntimeError, match="refused"):
            client.init_tensor(1 << 16, 8, 0)


# --- slo_breach trigger ----------------------------------------------------


class TestSloBreach:
    def _recorder(self, monkeypatch, tmp_path, slo="0.1"):
        from byteps_tpu.core.flightrec import FlightRecorder
        from byteps_tpu.core.telemetry import MetricsRegistry, RobustnessCounters

        monkeypatch.setenv("BYTEPS_JOB_SLO_S", slo)
        monkeypatch.setenv("BYTEPS_FLIGHT_DIR", str(tmp_path))
        reg, ctr = MetricsRegistry(), RobustnessCounters()
        rec = FlightRecorder(
            capacity=32, registry=reg, counter_store=ctr,
            context_fn=lambda: {"job": 9},
        )
        return rec, ctr

    def test_fires_once_under_rate_limiter(self, monkeypatch, tmp_path):
        rec, ctr = self._recorder(monkeypatch, tmp_path)
        for _ in range(5):
            rec.record_step(0.02)  # within SLO: no fire
        assert not rec.bundles_written
        r = rec.record_step(0.5)  # deliberate violation
        assert "slo_breach" in r["trig"] and r["job"] == 9
        r2 = rec.record_step(0.6)  # second breach inside the window
        assert "slo_breach" in r2["trig"]
        fired = sum(
            v for lkey, v in ctr.snapshot_labeled().get(
                "flight_trigger", {}
            ).items()
            if dict(lkey).get("rule") == "slo_breach"
        )
        assert fired == 2  # every breach counted...
        slo_bundles = [p for p in rec.bundles_written if "slo_breach" in p]
        assert len(slo_bundles) == 1  # ...but exactly ONE bundle dumped

    def test_off_by_default(self, monkeypatch, tmp_path):
        rec, ctr = self._recorder(monkeypatch, tmp_path, slo="0")
        r = rec.record_step(99.0)
        assert "slo_breach" not in r["trig"]


# --- acceptance demo -------------------------------------------------------


#: shaped link rate (MB/s) — slow enough that a bulk flood visibly
#: queues, fast enough that three phases stay under half a minute
_RATE_MBYTES_S = 8.0

_TENANT_WORKER = r"""
import json, os, sys, time
import numpy as np
import byteps_tpu as bps
from byteps_tpu.core.flightrec import get_process_recorder
from byteps_tpu.core.telemetry import counters

role, steps, dim = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
bps.init()
x = np.ones(dim, dtype=np.float32)
bps.push_pull(x, name=f"qos.{role}", average=False)  # init barriers, first allocation
if role == "latency":
    # measure INSIDE the contended window: the bulk neighbour's first
    # multi-MB round takes ~1 s on the shaped link (same delay in every
    # phase, so the baselines stay comparable)
    time.sleep(1.5)
times = []
for s in range(steps):
    t0 = time.monotonic()
    bps.push_pull(x, name=f"qos.{role}", average=False)
    times.append(time.monotonic() - t0)
labeled = counters().snapshot_labeled().get("flight_trigger", {})
rec = get_process_recorder()
print("TENANT_RESULT " + json.dumps({
    "times": times,
    "slo_breach_fired": sum(v for lkey, v in labeled.items()
                            if dict(lkey).get("rule") == "slo_breach"),
    "slo_bundles": sum(1 for p in (rec.bundles_written if rec is not None else ())
                       if "-slo_breach-" in p),
}), flush=True)
bps.shutdown()
"""


def _by_job(name):
    """The in-process servers' labeled counter ``name`` as {job: count}."""
    return {
        int(dict(lkey)["job"]): v
        for lkey, v in counters().snapshot_labeled().get(name, {}).items()
    }


def _run_phase(bulk, steps, lat_priority=1, bulk_quota=0.0, lat_slo_s=0.0):
    """One fleet (scheduler + 2 Python-engine servers in this process) on a
    rate-shaped loopback link, the jobs as subprocess workers with their
    own ``BYTEPS_JOB_ID``: the latency job (job 1) steps ``steps`` times,
    the bulk job (job 2) floods until it is stopped.  Returns the latency
    job's step-time tail and what the servers' admission meter did."""
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BYTEPS_VAN": "tcp",
        "BYTEPS_VAN_RATE_MBYTES_S": str(_RATE_MBYTES_S),
        # one engine thread per server: the shared service point where a
        # bulk backlog can actually sit in front of the latency job
        "BYTEPS_SERVER_ENGINE_THREAD": "1",
        # many in-flight bulk partitions = a real backlog
        "BYTEPS_PARTITION_BYTES": str(256 * 1024),
        # a shaping buffer SMALLER than a bulk reply: every 256KB pull
        # reply occupies the sender until the wire drains, so the
        # head-of-line block QoS's reply writers remove is deterministic
        "BYTEPS_VAN_SHAPE_BUF_KB": "64",
        "BYTEPS_HEARTBEAT_INTERVAL": "1",
        "BYTEPS_FORCE_DISTRIBUTED": "1",
        "DMLC_NUM_WORKER": "2" if bulk else "1",
        "DMLC_NUM_SERVER": "2",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
    }
    env.pop("BYTEPS_JOB_ID", None)
    os.environ.update({k: env[k] for k in (
        "BYTEPS_VAN", "BYTEPS_VAN_RATE_MBYTES_S", "BYTEPS_VAN_SHAPE_BUF_KB",
        "BYTEPS_SERVER_ENGINE_THREAD", "BYTEPS_PARTITION_BYTES",
        "DMLC_NUM_WORKER", "DMLC_NUM_SERVER", "DMLC_PS_ROOT_URI",
    )})
    sched = Scheduler(num_workers=2 if bulk else 1, num_servers=2, host="127.0.0.1")
    sched.start()
    env["DMLC_PS_ROOT_PORT"] = os.environ["DMLC_PS_ROOT_PORT"] = str(sched.port)
    fleet = [PSServer(Config.from_env()) for _ in range(2)]
    for srv in fleet:
        threading.Thread(target=srv.start, daemon=True).start()
    before = {n: _by_job(n) for n in ("job_quota_deferred", "server_job_requests")}

    def spawn(role, job, wsteps, dim, priority, quota, slo=0.0):
        return subprocess.Popen(
            [sys.executable, "-c", _TENANT_WORKER, role, str(wsteps), str(dim)],
            env={**env, "BYTEPS_JOB_ID": str(job), "BYTEPS_JOB_PRIORITY": str(priority),
                 "BYTEPS_JOB_QUOTA_MBPS": str(quota), "BYTEPS_JOB_SLO_S": str(slo)},
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, cwd=REPO,
        )

    procs = [spawn("latency", 1, steps, 1 << 14, lat_priority, 0.0, lat_slo_s)]
    if bulk:  # steps "forever"; stopped once the latency job has measured
        procs.append(spawn("bulk", 2, 10_000, 1 << 20, 1, bulk_quota))
    try:
        out, _ = procs[0].communicate(timeout=600)
        assert procs[0].returncode == 0, "the latency worker failed"
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
        for srv in fleet:
            srv.stop()
        sched.stop()
    stats = next(json.loads(line[len("TENANT_RESULT "):])
                 for line in out.splitlines() if line.startswith("TENANT_RESULT "))
    # floor-interpolated: at n = 60 the p99 is the second-worst sample,
    # so one OS scheduling blip does not make the tail
    times = sorted(stats.pop("times"))
    stats["p99_ms"] = times[int(0.99 * (len(times) - 1))] * 1e3
    for n, was in before.items():
        now = _by_job(n)
        stats[n] = {job: now[job] - was.get(job, 0) for job in now}
    return stats


@pytest.fixture
def _env_guard():
    """_run_phase mutates process env for its in-process fleet;
    restore it so later tests see the pristine environment."""
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


class TestMultiTenantDemo:
    """The acceptance demo (docs/async.md): two jobs — a
    latency-sensitive sync job and a bulk job — share 2 Python-engine
    servers on a rate-shaped link."""

    def test_qos_keeps_latency_job_p99_flat(self, _env_guard):
        # 60 measured steps span several bulk reply cycles, so the
        # contended phase's tail carries MULTIPLE collisions (one
        # collision would vanish into the floor-interpolated p99)
        solo = _run_phase(bulk=False, steps=60)
        noqos = _run_phase(bulk=True, steps=60, lat_slo_s=0.04)
        # a quarter-rate bulk quota: the admission meter keeps the bulk
        # backlog shallow, so the latency job's tail rides almost
        # entirely on its own wire
        qos = _run_phase(bulk=True, steps=60, lat_priority=100, bulk_quota=2.0)
        # what the servers DID, which no load on this machine moves: with
        # a quota declared the meter held the bulk job's frames back and
        # never the latency job's; with none declared it held nobody's
        assert qos["job_quota_deferred"].get(2, 0) > 0, qos
        assert qos["job_quota_deferred"].get(1, 0) == 0, qos
        assert not any(noqos["job_quota_deferred"].values()), noqos
        assert noqos["server_job_requests"].get(2, 0) > 0, noqos  # the flood arrived
        # what the latency job FELT, as ratios of host-clock tails (a
        # loaded machine stretches all three; 1.5 | 1.5 when it is quiet):
        # QoS off, the flood blows its tail; QoS on, the tail comes back
        assert noqos["p99_ms"] > 1.25 * solo["p99_ms"], (
            f"no contention to protect against: solo {solo} noqos {noqos}"
        )
        assert qos["p99_ms"] <= max(2.0 * solo["p99_ms"], 0.8 * noqos["p99_ms"]), (
            f"QoS failed to protect the latency job: solo {solo} noqos {noqos} qos {qos}"
        )
        # the deliberate SLO violation fired, and the rate limiter let
        # exactly one bundle through
        assert noqos["slo_breach_fired"] >= 1, noqos
        assert noqos["slo_bundles"] == 1, noqos

    def test_async_tenant_exact_under_chaos_retries(self, _env_guard):
        # the async job's final pulled state equals the sum of ALL
        # applied pushes — no losses, ledger dedupe intact under
        # injected drops/retries (asserted bitwise inside the soak,
        # plus monotone store_version progress)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "chaos_soak.py"),
             "--multi-tenant", "--steps", "15", "--seed", "11"],
            capture_output=True, text=True, timeout=240,
            cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, (
            f"multi-tenant soak failed:\n{proc.stdout}\n{proc.stderr}"
        )
        assert "CHAOS SOAK OK" in proc.stdout
