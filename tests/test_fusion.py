"""Small-tensor fusion: multi-key RPC coalescing (docs/fusion.md).

Layers under test:

- wire codec round-trips (transport.encode/decode_fused_*)
- scheduler semantics: fusion groups are gate-exempt and inherit the max
  member priority
- end-to-end correctness on a fake cluster: fused results are bitwise
  identical to unfused, with measurably fewer wire RPCs
- the exactly-once ledger under fused replay: a re-sent fused frame never
  double-sums any member key (direct wire-level test, 2 fake workers)
- chaos schedule: fusion stays bitwise-exact when fused frames are
  dropped and retried under a fixed seed
"""

import struct
import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
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
    connect,
    decode_fused_push,
    decode_fused_reply,
    encode_fused_push,
    encode_fused_reply,
    recv_message,
    send_message,
)
from byteps_tpu.core.telemetry import counters
from byteps_tpu.server.server import PSServer


from conftest import (
    ENGINE_STRIPES,
    ENGINE_STRIPES_IDS,
    make_ps_server,
    require_engine,
    set_stripes,
)


class TestFusedWire:
    def test_push_frame_roundtrip(self):
        members = [
            (7, 3, 1, b"abc"),
            (1 << 40, 0, 9, b""),
            (2, 11, 2, bytes(range(256))),
        ]
        assert decode_fused_push(encode_fused_push(members)) == members

    def test_reply_frame_roundtrip(self):
        members = [(5, 1, b"xy"), (6, 2, b"\x00" * 64)]
        assert decode_fused_reply(encode_fused_reply(members)) == members

    def test_truncated_frame_rejected(self):
        body = encode_fused_push([(1, 0, 1, b"payload")])
        with pytest.raises(ValueError, match="truncated"):
            decode_fused_push(body[:-3])


class TestStageLists:
    """``engine._stages`` decides a partition's pipeline; these are the four
    sequences the engine's five lists held (a device codec takes the
    compressed ones)."""

    Q = QueueType

    @pytest.mark.parametrize("compressed,fused,want", [
        (False, False, [Q.COPYD2H, Q.PUSH, Q.PULL, Q.COPYH2D]),
        (True, False, [Q.COPYD2H, Q.COMPRESS, Q.PUSH, Q.PULL, Q.DECOMPRESS, Q.COPYH2D]),
        (False, True, [Q.COPYD2H, Q.FUSE, Q.PULL, Q.COPYH2D]),
        (True, True, [Q.COPYD2H, Q.COMPRESS, Q.FUSE, Q.PULL, Q.DECOMPRESS, Q.COPYH2D]),
    ], ids=["raw", "compressed", "fused", "compressed_fused"])
    def test_sequence(self, compressed, fused, want):
        from byteps_tpu.core.engine import _stages

        got = _stages(compressed, fused)
        assert got == want
        assert _stages(compressed, fused) is not got  # a task pops from its own list


class TestFusionScheduling:
    def test_gate_exempt_skips_version_gate(self):
        from byteps_tpu.core.ready_table import ReadyTable
        from byteps_tpu.core.scheduler import ScheduledQueue

        table = ReadyTable(ready_count=1)
        q = ScheduledQueue(
            QueueType.PUSH, ready_table=table, version_gated=True
        )
        gated = TensorTableEntry(tensor_name="t", key=1, version=5)
        q.add_task(gated)
        assert q.get_task(timeout=0.05) is None  # allowance 0 < version 5
        group = TensorTableEntry(
            tensor_name="<fused>", key=1, version=5, gate_exempt=True
        )
        q.add_task(group)
        assert q.get_task(timeout=1.0) is group  # exempt pops immediately

    def test_group_inherits_max_member_priority(self):
        """A flushed pack outranks everything below its most urgent
        member — fusion must never defeat priority scheduling."""
        from types import SimpleNamespace

        from byteps_tpu.core.engine import _Fuser
        from byteps_tpu.core.scheduler import ScheduledQueue

        stub = SimpleNamespace(
            cfg=Config(fusion_bytes=1 << 30, fusion_cycle_ms=1000.0),
            client=SimpleNamespace(server_for=lambda key: 0),
            _stop=threading.Event(),
            queues={QueueType.PUSH: ScheduledQueue(QueueType.PUSH)},
        )
        fuser = _Fuser(stub)
        t_low = TensorTableEntry(tensor_name="a", key=1, priority=-9, length=4)
        t_hi = TensorTableEntry(tensor_name="b", key=2, priority=3, length=4)
        fuser.add(t_low, b"x" * 16)
        fuser.add(t_hi, b"y" * 16)
        fuser.drain_idle()
        stub._stop.set()  # stops the cycle thread
        group = stub.queues[QueueType.PUSH].get_task(timeout=1.0)
        assert group is not None and group.gate_exempt
        assert group.priority == 3
        assert group.length == 8
        assert len(group.context.members) == 2


@pytest.fixture(params=["python", "native"])
def fusion_cluster(request, monkeypatch):
    """1 worker / 2 servers, fusion enabled (threshold 16KB), over BOTH
    server engines — the ``native`` param id keeps the conftest
    native-hang guards armed for those runs."""
    engine = request.param
    require_engine(engine)
    monkeypatch.setenv("BYTEPS_FUSION_THRESHOLD", "16384")
    monkeypatch.setenv("BYTEPS_FUSION_CYCLE_MS", "2")
    if engine == "native":
        monkeypatch.setenv("BYTEPS_SERVER_NATIVE", "1")
    sched = Scheduler(num_workers=1, num_servers=2, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "2")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    servers = [make_ps_server(engine, Config.from_env()) for _ in range(2)]
    for srv in servers:
        threading.Thread(target=srv.start, daemon=True).start()
    yield {"scheduler": sched, "servers": servers, "engine": engine}
    for srv in servers:
        srv.stop()
    sched.stop()


class TestFusionCluster:
    def test_fused_identity_and_rpc_reduction(self, fusion_cluster):
        """Many small tensors in flight fuse into few frames; results are
        bitwise identical to the inputs (1 worker ⇒ sum = input)."""
        import byteps_tpu as bps

        bps.init()
        rng = np.random.default_rng(7)
        xs = [
            rng.standard_normal(500 + 31 * i).astype(np.float32)
            for i in range(48)
        ]
        # round 1 runs the init barriers (serialized, unfuseable)
        hs = [
            bps.push_pull_async(x, name=f"fuse.{i}", average=False)
            for i, x in enumerate(xs)
        ]
        for h in hs:
            bps.synchronize(h)
        counters().reset()
        hs = [
            bps.push_pull_async(x * 3, name=f"fuse.{i}", average=False)
            for i, x in enumerate(xs)
        ]
        for i, h in enumerate(hs):
            np.testing.assert_array_equal(
                np.asarray(bps.synchronize(h)), xs[i] * 3
            )
        snap = counters().snapshot()
        assert snap.get("fused_keys", 0) == 48, snap
        assert snap.get("fused_frames", 0) >= 1
        # 48 unfused keys would cost 96 wire RPCs; fused frames collapse
        # the round trips at least 2×
        assert snap.get("wire_rpc", 0) <= 48, snap
        if fusion_cluster["engine"] == "native":
            # the frames really were served by the C++ engine, and its
            # counters reach the shared scrape surface.  >= not ==: the
            # server counts every frame UNPACK, so a benign deadline
            # retransmit (members then deduped) inflates it past the
            # worker-side pack count
            assert snap.get("native_fused_frames", 0) >= 1, snap
            assert snap.get("native_fused_keys", 0) >= 48, snap
        bps.shutdown()

    def test_mixed_small_and_large(self, fusion_cluster, monkeypatch):
        """Partitioned large tensors keep per-key RPCs while their small
        tail and small siblings fuse — one job can hold both."""
        monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "65536")
        import byteps_tpu as bps

        bps.init()
        big = np.arange(1 << 16, dtype=np.float32)  # 256KB → 4 partitions
        small = np.linspace(-1, 1, 300).astype(np.float32)
        for step in range(3):
            hb = bps.push_pull_async(big + step, name="mix.big", average=False)
            hs = bps.push_pull_async(small * (step + 1), name="mix.small",
                                     average=False)
            np.testing.assert_array_equal(
                np.asarray(bps.synchronize(hb)), big + step
            )
            np.testing.assert_array_equal(
                np.asarray(bps.synchronize(hs)), small * (step + 1)
            )
        bps.shutdown()

    def test_priority_still_respected_with_fusion(self, fusion_cluster):
        """Smoke: caller-chosen priorities with fusion on complete
        correctly (ordering is exercised by the scheduler unit test)."""
        import byteps_tpu as bps

        bps.init()
        xs = [np.full(64, i, dtype=np.float32) for i in range(8)]
        hs = [
            bps.push_pull_async(x, name=f"prio.{i}", priority=-i,
                                average=False)
            for i, x in enumerate(xs)
        ]
        for i, h in enumerate(hs):
            np.testing.assert_array_equal(np.asarray(bps.synchronize(h)), xs[i])
        bps.shutdown()


class TestFusedFallback:
    def test_failed_frame_falls_back_to_unfused(self, fusion_cluster):
        """A pack whose fused RPC errors out (retries exhausted, malformed
        reply, resize under the pack) downgrades to per-key unfused
        push+pull instead of failing the step — the members re-enter the
        PUSH queue and complete through the classic path."""
        import byteps_tpu as bps
        from byteps_tpu.core.state import get_state

        bps.init()
        x0 = np.arange(128, dtype=np.float32)
        bps.push_pull(x0, name="fb.a", average=False)  # init round
        client = get_state().ps_client

        def broken_push_fused(members, cb, on_error=None, abort_check=None,
                              **kwargs):
            on_error()  # every fused frame "exhausts its retries"

        orig = client.push_fused
        client.push_fused = broken_push_fused
        counters().reset()
        try:
            out = bps.push_pull(x0 * 5, name="fb.a", average=False)
            np.testing.assert_array_equal(np.asarray(out), x0 * 5)
        finally:
            client.push_fused = orig
        snap = counters().snapshot()
        assert snap.get("fused_fallback", 0) >= 1, snap
        bps.shutdown()


class TestFusedReplayDedupe:
    @pytest.mark.parametrize(("engine", "stripes"), ENGINE_STRIPES,
                             ids=ENGINE_STRIPES_IDS)
    def test_resent_fused_frame_never_double_sums(self, engine, stripes,
                                                  monkeypatch):
        """Wire-level exactly-once: worker 1 sends a fused frame TWICE
        (the retry case — e.g. its reply was dropped); worker 2 completes
        the rounds with plain pushes.  Every reply must carry the sum of
        exactly one contribution per worker per key — over BOTH server
        engines (the per-(worker, key) ledger is ported to the C++ data
        plane) and over striped (4) AND single-reducer (1) native lanes
        (the ledger now lives per stripe shard)."""
        require_engine(engine)
        set_stripes(monkeypatch, stripes)
        cfg = Config(num_worker=2, num_server=1)
        if engine == "native":
            from byteps_tpu.server.server import NativePSServer

            srv = NativePSServer(cfg)  # data plane live on construction
            base_dedupe = counters().get("native_push_dedup")
        else:
            srv = PSServer(cfg)
            srv.start(register=False)
        KEY_A, KEY_B = 101, 202
        N = 64
        cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                               int(DataType.FLOAT32))
        a1 = np.arange(N, dtype=np.float32)
        b1 = np.full(N, 2.5, dtype=np.float32)
        a2 = np.ones(N, dtype=np.float32) * 10
        b2 = np.ones(N, dtype=np.float32) * -3
        try:
            w1 = connect(srv.host, srv.port)
            w2 = connect(srv.host, srv.port)
            # init barrier: both workers declare both keys
            init = struct.pack("!QI", N, int(DataType.FLOAT32))
            for key in (KEY_A, KEY_B):
                send_message(w1, Message(Op.INIT, key=key, seq=key, flags=1,
                                         payload=init))
                send_message(w2, Message(Op.INIT, key=key, seq=key, flags=2,
                                         payload=init))
            for sock in (w1, w2):
                for _ in (KEY_A, KEY_B):
                    assert recv_message(sock).op == Op.INIT
            # worker 1: fused frame for both keys, round 1 — sent TWICE
            frame = encode_fused_push([
                (KEY_A, cmd, 1, a1.tobytes()),
                (KEY_B, cmd, 1, b1.tobytes()),
            ])
            send_message(w1, Message(Op.FUSED, key=KEY_A, seq=11, flags=1,
                                     cmd=2, payload=frame))
            send_message(w1, Message(Op.FUSED, key=KEY_A, seq=12, flags=1,
                                     cmd=2, payload=frame))
            # worker 2 completes both rounds with plain pushes
            send_message(w2, Message(Op.PUSH, key=KEY_A, seq=21, flags=2,
                                     cmd=cmd, version=1,
                                     payload=a2.tobytes()))
            send_message(w2, Message(Op.PUSH, key=KEY_B, seq=22, flags=2,
                                     cmd=cmd, version=1,
                                     payload=b2.tobytes()))
            for _ in range(2):
                assert recv_message(w2).op == Op.PUSH  # acks
            # worker 1 receives BOTH fused replies (the retry is answered
            # from the published round, not re-summed)
            sums = {KEY_A: a1 + a2, KEY_B: b1 + b2}
            for _ in range(2):
                msg = recv_message(w1)
                assert msg.op == Op.FUSED
                reply = decode_fused_reply(msg.payload)
                assert [k for k, _, _ in reply] == [KEY_A, KEY_B]
                for key, _ver, payload in reply:
                    got = np.frombuffer(payload, dtype=np.float32)
                    # bitwise equality — a double-summed replay would
                    # show 2×worker-1's contribution
                    np.testing.assert_array_equal(got, sums[key])
            if engine == "native":
                # the retried frame's members were suppressed by the C++
                # engine's ledger (acceptance: native dedupe-hit > 0)
                assert (
                    counters().get("native_push_dedup") - base_dedupe >= 2
                )
            from byteps_tpu.comm.transport import close_socket

            close_socket(w1)
            close_socket(w2)
        finally:
            srv.stop()


class TestFusionChaos:
    @pytest.fixture(autouse=True)
    def _canonical_chaos_schedule(self):
        """The fault RNG is keyed by (seed, process-global connection
        index): without a reset the injected schedule depends on how
        many chaos connections EARLIER tests opened, and this suite's
        ``[native-s4]`` lane flaked in some sub-suite combinations
        (CHANGES.md PR 9).  Resetting pins one canonical schedule —
        identical under any pytest selection."""
        from byteps_tpu.comm.chaos import reset_conn_indices, reset_fault_budget

        reset_conn_indices()
        reset_fault_budget()
        yield

    @pytest.mark.parametrize(("engine", "stripes"), ENGINE_STRIPES,
                             ids=ENGINE_STRIPES_IDS)
    def test_fused_frames_bitwise_exact_under_chaos(self, engine, stripes,
                                                    monkeypatch):
        """The acceptance schedule with fusion ON: chaos:tcp, fixed seed,
        5% frame drops — dropped fused frames and dropped fused replies
        are healed by the single per-frame deadline/retry state, and the
        ledger keeps every member key exactly-once (sums stay bitwise
        equal to the inputs; a double-sum would return 2x).  Runs over
        BOTH server engines: under ``native`` the chaos layer wraps the
        worker side of each connection (the C++ listener stays clean —
        the same one-sidedness the 2-worker demo uses)."""
        require_engine(engine)
        set_stripes(monkeypatch, stripes)
        monkeypatch.setenv("BYTEPS_VAN", "chaos:tcp")
        monkeypatch.setenv("BYTEPS_CHAOS_SEED", "4242")
        monkeypatch.setenv("BYTEPS_CHAOS_DROP", "0.05")
        monkeypatch.setenv("BYTEPS_RPC_DEADLINE_S", "0.3")
        monkeypatch.setenv("BYTEPS_INIT_DEADLINE_S", "0.5")
        monkeypatch.setenv("BYTEPS_RPC_RETRIES", "6")
        monkeypatch.setenv("BYTEPS_RPC_BACKOFF_S", "0.05")
        monkeypatch.setenv("BYTEPS_CONNECT_RETRY_S", "0.2")
        monkeypatch.setenv("BYTEPS_DEGRADED_STEP_RETRIES", "3")
        monkeypatch.setenv("BYTEPS_FUSION_THRESHOLD", "16384")
        monkeypatch.setenv("BYTEPS_FUSION_CYCLE_MS", "2")
        counters().reset()

        sched = Scheduler(num_workers=1, num_servers=2, host="127.0.0.1")
        sched.start()
        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_NUM_SERVER", "2")
        monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
        monkeypatch.setenv("BYTEPS_HEARTBEAT_INTERVAL", "0.2")
        servers = [make_ps_server(engine, Config.from_env()) for _ in range(2)]
        for srv in servers:
            threading.Thread(target=srv.start, daemon=True).start()

        import byteps_tpu as bps

        failures = {}

        def train():
            try:
                bps.init()
                rng = np.random.default_rng(3)
                names = [f"chaos.fuse.{k}" for k in range(6)]
                for step in range(20):
                    xs = {
                        name: rng.standard_normal(199 + 17 * i).astype(
                            np.float32
                        )
                        for i, name in enumerate(names)
                    }
                    hs = {
                        name: bps.push_pull_async(x, name=name, average=False)
                        for name, x in xs.items()
                    }
                    for name, h in hs.items():
                        out = np.asarray(bps.synchronize(h))
                        np.testing.assert_array_equal(out, xs[name])
            except BaseException as e:  # noqa: BLE001
                failures["err"] = e

        t = threading.Thread(target=train, daemon=True)
        t.start()
        t.join(timeout=120)
        try:
            assert not t.is_alive(), "training hung under the chaos schedule"
            assert "err" not in failures, f"training failed: {failures['err']!r}"
            snap = counters().snapshot()
            assert snap.get("chaos_drop", 0) > 0, f"no drops injected: {snap}"
            assert snap.get("rpc_retry", 0) > 0, f"no retries observed: {snap}"
            assert snap.get("fused_frames", 0) > 0, f"nothing fused: {snap}"
            if engine == "native":
                assert snap.get("native_fused_frames", 0) > 0, snap
        finally:
            bps.shutdown()
            for srv in servers:
                srv.stop()
            sched.stop()
