"""Elastic suspend/resume against a LIVE cluster.

The reference's elasticity contract (SURVEY §5.3): suspend tears down the
worker runtime, resume re-registers with the still-running scheduler
(recovery path), replays tensor declarations for stable keys, and traffic
continues.  The recovery barrier must release immediately — the rest of
the cluster is mid-training, not waiting (a deadlock fixed in round 1).
"""

import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.server.server import PSServer


@pytest.fixture
def live_cluster(monkeypatch):
    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    srv = PSServer(Config.from_env())
    threading.Thread(target=srv.start, daemon=True).start()
    yield
    srv.stop()
    sched.stop()


class TestElasticAgainstLiveCluster:
    def test_suspend_resume_continues_traffic(self, live_cluster):
        import byteps_tpu as bps

        bps.init()
        keys = {n: bps.declare_tensor(n) for n in ("g0", "g1", "g2")}
        out = bps.push_pull(np.ones(32, np.float32), name="g0", average=False)
        np.testing.assert_allclose(np.asarray(out), 1.0)

        bps.suspend()
        bps.resume(num_workers=1)  # recovery rejoin — must not deadlock

        # keys stable across the generation (ReDeclareTensor semantics)
        for n, k in keys.items():
            assert bps.declare_tensor(n) == k
        out2 = bps.push_pull(np.full(32, 2.0, np.float32), name="g0", average=False)
        np.testing.assert_allclose(np.asarray(out2), 2.0)
        bps.shutdown()

    def test_double_resume(self, live_cluster):
        import byteps_tpu as bps

        bps.init()
        bps.push_pull(np.ones(8, np.float32), name="t", average=False)
        for _ in range(2):
            bps.suspend()
            bps.resume(num_workers=1)
            out = bps.push_pull(np.ones(8, np.float32), name="t", average=False)
            np.testing.assert_allclose(np.asarray(out), 1.0)
        bps.shutdown()

    def test_liveness_reflects_rejoin(self, live_cluster, monkeypatch):
        import byteps_tpu as bps
        from byteps_tpu.core.state import get_state

        bps.init()
        bps.suspend()
        bps.resume(num_workers=1)
        live = get_state().ps_client.query_cluster()
        assert live["worker"][0] < 5.0  # fresh stamp from the new connection
        bps.shutdown()


class TestMultiWorkerRejoinIdentity:
    def test_rejoin_matches_by_node_uid_not_address(self):
        """Workers register with host=''/port=0; a rejoin must be matched to
        the SAME worker's previous registration (by its persisted node uid),
        never aliased onto another live worker (round-1 advisory:
        rendezvous matched on (host, port), handing every rejoiner the
        first worker's rank)."""
        from byteps_tpu.comm.ps_client import PSClient
        from byteps_tpu.server.server import PSServer

        sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
        sched.start()
        env = {
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_NUM_SERVER": "1",
            "BYTEPS_FORCE_DISTRIBUTED": "1",
        }
        import os

        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            cfg = Config.from_env()
            srv = PSServer(cfg)
            threading.Thread(target=srv.start, daemon=True).start()

            w0 = PSClient(cfg, node_uid="uid-w0")
            w1 = PSClient(cfg, node_uid="uid-w1")
            t0 = threading.Thread(target=w0.connect, daemon=True)
            t0.start()
            w1.connect()
            t0.join(10)
            ranks = {w0.node_uid: w0.rank, w1.node_uid: w1.rank}
            assert sorted(ranks.values()) == [0, 1]

            # w1 dies and rejoins with the same uid → must get ITS rank back
            w1_rank = ranks["uid-w1"]
            w1.close()
            w1b = PSClient(cfg, node_uid="uid-w1")
            w1b.connect()
            assert w1b.rank == w1_rank
            assert w1b.is_recovery

            # w0 (still live) keeps a fresh liveness stamp under its own rank
            live = w1b.query_cluster()
            assert set(live["worker"]) == {0, 1}

            # an unknown uid after the book is full is NOT a recovery match
            # for an existing entry — it must not steal w0's rank
            w0.close()
            w0b = PSClient(cfg, node_uid="uid-w0")
            w0b.connect()
            assert w0b.rank == ranks["uid-w0"]
            w0b.close()
            w1b.close()
            srv.stop()
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        sched.stop()

    def test_dead_slot_adoption_broadcasts_epoch_to_survivors(self):
        """Satellite fix: adopting a dead member's slot changes the
        slot's IDENTITY, so surviving peers must receive a membership
        broadcast (epoch bump) instead of staying oblivious — previously
        the adoption path notified nobody."""
        import os
        import time

        from byteps_tpu.comm.ps_client import PSClient
        from byteps_tpu.server.server import PSServer

        sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
        sched.start()
        env = {
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_NUM_SERVER": "1",
            "BYTEPS_FORCE_DISTRIBUTED": "1",
        }
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            cfg = Config.from_env()
            srv = PSServer(cfg)
            threading.Thread(target=srv.start, daemon=True).start()
            w0 = PSClient(cfg, node_uid="adopt-w0")
            w1 = PSClient(cfg, node_uid="adopt-w1")
            t0 = threading.Thread(target=w0.connect, daemon=True)
            t0.start()
            w1.connect()
            t0.join(10)
            epoch_before = w0.membership_epoch
            w1.close()  # dies
            time.sleep(0.3)
            w_new = PSClient(cfg)  # fresh uid → adopts w1's dead slot
            w_new.connect()
            assert w_new.is_recovery
            # the SURVIVOR hears about the identity change
            for _ in range(100):
                if w0.membership_epoch > epoch_before:
                    break
                time.sleep(0.05)
            assert w0.membership_epoch > epoch_before, (
                "surviving peer never notified of dead-slot adoption"
            )
            assert sched.epoch == w0.membership_epoch
            w0.close()
            w_new.close()
            srv.stop()
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        sched.stop()

    def test_unknown_uid_restart_adopts_dead_slot(self):
        """A restarted process that lost its uuid (BYTEPS_NODE_UID unset)
        must adopt a dead member's slot — and must never be left hanging
        with no ADDRBOOK reply."""
        import os
        import time

        from byteps_tpu.comm.ps_client import PSClient
        from byteps_tpu.server.server import PSServer

        sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
        sched.start()
        env = {
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_NUM_SERVER": "1",
            "BYTEPS_FORCE_DISTRIBUTED": "1",
        }
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            cfg = Config.from_env()
            srv = PSServer(cfg)
            threading.Thread(target=srv.start, daemon=True).start()
            w0 = PSClient(cfg, node_uid="alpha")
            w1 = PSClient(cfg, node_uid="beta")
            t0 = threading.Thread(target=w0.connect, daemon=True)
            t0.start()
            w1.connect()
            t0.join(10)
            beta_rank = w1.rank
            w1.close()  # shutdown() sends FIN so the scheduler notices
            time.sleep(0.5)
            w_new = PSClient(cfg)  # fresh random uid
            done = threading.Event()
            threading.Thread(
                target=lambda: (w_new.connect(), done.set()), daemon=True
            ).start()
            assert done.wait(10), "unknown-uid register hung (no ADDRBOOK)"
            assert w_new.rank == beta_rank and w_new.is_recovery
            w0.close()
            w_new.close()
            srv.stop()
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        sched.stop()


class TestElasticWorldSizeChange:
    def test_scale_down_then_up(self):
        """2→1→2 workers across resume with a LIVE scheduler:
        stable keys, scheduler address book actually changes, servers adopt
        the new worker count, and traffic continues at every size."""
        import os
        import time

        from byteps_tpu.comm.ps_client import PSClient
        from byteps_tpu.server.server import PSServer

        sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
        sched.start()
        env = {
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_NUM_SERVER": "1",
            "BYTEPS_FORCE_DISTRIBUTED": "1",
            "BYTEPS_HEARTBEAT_INTERVAL": "0.1",
        }
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            cfg2 = Config.from_env()
            srv = PSServer(cfg2)
            threading.Thread(target=srv.start, daemon=True).start()

            w0 = PSClient(cfg2, node_uid="w0")
            w1 = PSClient(cfg2, node_uid="w1")
            t0 = threading.Thread(target=w0.connect, daemon=True)
            t0.start()
            w1.connect()
            t0.join(10)
            for _ in range(50):
                if srv.num_workers == 2:
                    break
                time.sleep(0.1)
            assert srv.num_workers == 2

            # traffic at size 2: both push, both get the sum
            import struct as _s

            def roundtrip(client, key, value, version, n=64):
                done = threading.Event()
                box = []
                payload = np.full(n, value, np.float32).tobytes()
                client.push(key, payload, 0, version, cb=lambda: done.set())
                assert done.wait(10)
                got = threading.Event()
                client.pull(key, version, lambda p: (box.append(p), got.set()))
                assert got.wait(10)
                return np.frombuffer(box[0], np.float32)

            _ti = threading.Thread(
                target=lambda: w0.init_tensor(101, 64, 0), daemon=True
            )
            _ti.start()
            w1.init_tensor(101, 64, 0)
            _ti.join(10)
            r = []
            tA = threading.Thread(
                target=lambda: r.append(roundtrip(w0, 101, 1.0, 1)), daemon=True
            )
            tA.start()
            out1 = roundtrip(w1, 101, 2.0, 1)
            tA.join(10)
            np.testing.assert_allclose(out1, 3.0)

            # ---- scale DOWN to 1 worker: w1 leaves, w0 resumes with nw=1
            w1.close()
            w0.close()
            time.sleep(0.3)
            os.environ["DMLC_NUM_WORKER"] = "1"
            cfg1 = Config.from_env()
            w0b = PSClient(cfg1, node_uid="w0")
            w0b.connect()
            assert w0b.is_recovery and w0b.rank == 0
            assert sched.num_workers == 1  # address book actually changed
            for _ in range(50):
                if srv.num_workers == 1:
                    break
                time.sleep(0.1)
            assert srv.num_workers == 1  # server adopted the resize
            # solo traffic completes (a 2-worker round would hang forever)
            out2 = roundtrip(w0b, 101, 5.0, 2)
            np.testing.assert_allclose(out2, 5.0)

            # ---- scale UP back to 2: w0 resumes with nw=2, new worker joins
            w0b.close()
            time.sleep(0.3)
            os.environ["DMLC_NUM_WORKER"] = "2"
            cfg2b = Config.from_env()
            w0c = PSClient(cfg2b, node_uid="w0")
            w0c.connect()
            assert w0c.rank == 0
            assert sched.num_workers == 2
            w2 = PSClient(cfg2b, node_uid="w2-new")  # brand-new member
            w2.connect()
            assert w2.rank == 1  # lowest free rank, not a stolen one
            for _ in range(50):
                if srv.num_workers == 2:
                    break
                time.sleep(0.1)
            assert srv.num_workers == 2
            # traffic at size 2 again, same key (stable across generations)
            r2 = []
            tB = threading.Thread(
                target=lambda: r2.append(roundtrip(w0c, 101, 10.0, 3)), daemon=True
            )
            tB.start()
            out3 = roundtrip(w2, 101, 20.0, 3)
            tB.join(10)
            np.testing.assert_allclose(out3, 30.0)

            w0c.close()
            w2.close()
            srv.stop()
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        sched.stop()


class TestElasticServerResize:
    def test_server_scale_up_then_down(self):
        """1→2→1 SERVERS across resume (the reference's
        resume(num_servers) rewrites DMLC_NUM_SERVER,
        common/__init__.py:75-82): the resuming worker's register parks
        until the new server joins, a LIVE worker adopts the resize from a
        RESIZE_SEQ book (connection rebuild + server_generation bump), keys
        re-home via the hash fns and re-init on their new owners, sums stay
        correct at every size, and scale-down SHUTDOWNs the dropped server."""
        import os
        import time

        from byteps_tpu.comm.ps_client import PSClient

        sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
        sched.start()
        env = {
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_NUM_SERVER": "1",
            "BYTEPS_FORCE_DISTRIBUTED": "1",
            "BYTEPS_HEARTBEAT_INTERVAL": "0.1",
        }
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)

        # chosen to spread across 2 servers under the default hash fn
        KEYS = [100, 101, 102, 103]

        def roundtrip(client, key, value, version, n=64):
            done = threading.Event()
            box = []
            payload = np.full(n, value, np.float32).tobytes()
            client.push(key, payload, 0, version, cb=lambda: done.set())
            assert done.wait(10)
            got = threading.Event()
            client.pull(key, version, lambda p: (box.append(p), got.set()))
            assert got.wait(10)
            return np.frombuffer(box[0], np.float32)

        def init_all(wa, wb, version_keys=KEYS):
            """Both workers run the blocking init barrier for every key."""
            ts = [
                threading.Thread(
                    target=lambda k=k: wa.init_tensor(k, 64, 0), daemon=True
                )
                for k in version_keys
            ]
            for t in ts:
                t.start()
            for k in version_keys:
                wb.init_tensor(k, 64, 0)
            for t in ts:
                t.join(10)

        def sum_round(wa, wb, version):
            """Both workers push (1.0, 2.0) on every key; both must pull 3.0."""
            outs = []
            t = threading.Thread(
                target=lambda: outs.append(
                    [roundtrip(wa, k, 1.0, version) for k in KEYS]
                ),
                daemon=True,
            )
            t.start()
            for k in KEYS:
                np.testing.assert_allclose(roundtrip(wb, k, 2.0, version), 3.0)
            t.join(15)
            assert outs, "worker A round did not complete"
            for arr in outs[0]:
                np.testing.assert_allclose(arr, 3.0)

        try:
            cfg1 = Config.from_env()
            srv0 = PSServer(cfg1)
            threading.Thread(target=srv0.start, daemon=True).start()

            w0 = PSClient(cfg1, node_uid="w0")
            w1 = PSClient(cfg1, node_uid="w1")
            t0 = threading.Thread(target=w0.connect, daemon=True)
            t0.start()
            w1.connect()
            t0.join(10)
            assert w0.num_servers == 1 and len(w0._servers) == 1

            init_all(w0, w1)
            sum_round(w0, w1, version=1)

            # ---- scale UP to 2 servers: w0 resumes with ns=2 (parked until
            # the new server registers); w1 stays LIVE and adopts via
            # RESIZE_SEQ
            w0.close()
            time.sleep(0.3)
            os.environ["DMLC_NUM_SERVER"] = "2"
            cfg2 = Config.from_env()
            w0b = PSClient(cfg2, node_uid="w0")
            boxes = []
            tc = threading.Thread(
                target=lambda: boxes.append(w0b.connect()), daemon=True
            )
            tc.start()
            time.sleep(0.5)
            assert not boxes  # parked: no address book until server 2 joins
            assert sched.num_servers == 2

            srv1 = PSServer(cfg2)
            threading.Thread(target=srv1.start, daemon=True).start()
            tc.join(15)
            assert not tc.is_alive(), "parked register never flushed"
            assert w0b.num_servers == 2 and len(w0b._servers) == 2

            # live worker w1 adopted the resize
            for _ in range(100):
                if w1.server_generation == 1:
                    break
                time.sleep(0.1)
            assert w1.server_generation == 1
            assert w1.num_servers == 2 and len(w1._servers) == 2

            # keys re-home across BOTH servers; re-init then sum correctly
            homes = {w1.server_for(k) for k in KEYS}
            assert homes == {0, 1}, f"keys did not spread: {homes}"
            # every worker re-ran the init barrier → round numbering
            # restarts at 1 on the new generation's stores
            init_all(w0b, w1)
            sum_round(w0b, w1, version=1)

            # ---- scale DOWN to 1 server: w1 resumes with ns=1; the
            # scheduler SHUTDOWNs the dropped rank-1 server; w0b stays live
            w1.close()
            time.sleep(0.3)
            os.environ["DMLC_NUM_SERVER"] = "1"
            cfg1b = Config.from_env()
            w1b = PSClient(cfg1b, node_uid="w1")
            w1b.connect()
            assert w1b.num_servers == 1 and len(w1b._servers) == 1
            assert sched.num_servers == 1

            for _ in range(100):
                if srv1._stop.is_set():
                    break
                time.sleep(0.1)
            assert srv1._stop.is_set(), "dropped server was not shut down"

            for _ in range(100):
                if w0b.server_generation == 1:
                    break
                time.sleep(0.1)
            assert w0b.server_generation == 1
            assert w0b.num_servers == 1 and len(w0b._servers) == 1

            init_all(w0b, w1b)
            sum_round(w0b, w1b, version=1)

            w0b.close()
            w1b.close()
            srv0.stop()
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        sched.stop()


class TestEngineServerGenerationReinit:
    def test_submit_reinits_after_generation_bump(self):
        """The engine re-runs a key's init-push barrier (and compressor
        re-ship) when the client's server_generation changes — the lazy
        re-home step of an elastic server resize."""
        from byteps_tpu.common.config import Config
        from byteps_tpu.common.registry import get_registry
        from byteps_tpu.core.engine import PipelineEngine

        class StubClient:
            server_generation = 0

            def __init__(self):
                self.inits = []

            def init_tensor(self, key, n, dt):
                self.inits.append(key)

        get_registry().clear()
        client = StubClient()
        eng = PipelineEngine(Config.from_env(), client)  # never started
        x = np.ones(8, np.float32)
        eng.submit("g.resize", x, average=False, priority=0, version=0, handle=1)
        first = list(client.inits)
        assert first, "initial submit must init"
        eng.submit("g.resize", x, average=False, priority=0, version=0, handle=2)
        assert client.inits == first, "same generation must not re-init"
        client.server_generation = 1
        eng.submit("g.resize", x, average=False, priority=0, version=0, handle=3)
        assert client.inits == first * 2, "generation bump must re-init"
        get_registry().clear()


class TestInvoluntaryServerFailure:
    def test_server_crash_mid_traffic_evicts_and_heals(self, monkeypatch):
        """Involuntary failure under the chaos van (docs/robustness.md):
        a PSServer is killed mid-training on a 1-worker/2-server cluster
        with frame drops injected.  The scheduler's liveness policy must
        evict it within BYTEPS_DEAD_NODE_TIMEOUT_S (visible in telemetry),
        the worker must fail over to the surviving server (RESIZE book →
        rebuild → re-init), and training must resume with exact sums —
        i.e. no replayed push was double-summed and no step hung."""
        from byteps_tpu.core.telemetry import counters

        monkeypatch.setenv("BYTEPS_VAN", "chaos:tcp")
        monkeypatch.setenv("BYTEPS_CHAOS_SEED", "77")
        monkeypatch.setenv("BYTEPS_CHAOS_DROP", "0.03")
        monkeypatch.setenv("BYTEPS_RPC_DEADLINE_S", "0.3")
        monkeypatch.setenv("BYTEPS_INIT_DEADLINE_S", "0.5")
        monkeypatch.setenv("BYTEPS_RPC_RETRIES", "3")
        monkeypatch.setenv("BYTEPS_RPC_BACKOFF_S", "0.05")
        monkeypatch.setenv("BYTEPS_CONNECT_RETRY_S", "0.2")
        monkeypatch.setenv("BYTEPS_DEGRADED_STEP_RETRIES", "8")
        monkeypatch.setenv("BYTEPS_HEARTBEAT_INTERVAL", "0.1")
        monkeypatch.setenv("BYTEPS_DEAD_NODE_TIMEOUT_S", "0.8")
        counters().reset()

        sched = Scheduler(num_workers=1, num_servers=2, host="127.0.0.1")
        sched.start()
        assert sched.dead_node_timeout == 0.8  # env-derived policy
        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_NUM_SERVER", "2")
        monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
        servers = [PSServer(Config.from_env()) for _ in range(2)]
        for srv in servers:
            threading.Thread(target=srv.start, daemon=True).start()

        import byteps_tpu as bps
        from byteps_tpu.core.state import get_state

        failures = {}
        crashed = threading.Event()

        def train():
            try:
                bps.init()
                # keys sized to spread over both servers
                names = ["inv.a", "inv.b", "inv.c"]
                for step in range(24):
                    for name in names:
                        x = np.full(129, float(step + 1), np.float32)
                        out = bps.push_pull(x, name=name, average=False)
                        # exact: a double-summed replay would give 2x
                        np.testing.assert_array_equal(np.asarray(out), x)
                    if step == 5:
                        # hard-kill server 1: listener + conns drop, the
                        # heartbeat stops — involuntary, mid-traffic
                        servers[1].stop()
                        crashed.set()
            except BaseException as e:  # noqa: BLE001
                failures["err"] = e

        t = threading.Thread(target=train, daemon=True)
        t.start()
        t.join(timeout=120)
        try:
            assert not t.is_alive(), "training hung after the server crash"
            assert "err" not in failures, f"training failed: {failures['err']!r}"
            assert crashed.is_set()
            # eviction happened and is observable end to end
            assert sched.eviction_totals["server"] == 1
            assert sched.num_servers == 1
            snap = bps.get_robustness_counters()
            assert snap.get("server_evicted", 0) == 1, f"telemetry: {snap}"
            # the worker's client adopted the shrunken membership
            assert get_state().ps_client.membership_epoch >= 1
            assert get_state().ps_client.num_servers == 1
        finally:
            bps.shutdown()
            for srv in servers:
                srv.stop()
            sched.stop()


class TestEvictionBarrierScrub:
    def test_dead_waiter_scrubbed_so_survivors_pair_up(self):
        """A node that died INSIDE a barrier must have its waiter entry
        scrubbed at eviction — otherwise the stale entry releases the
        shrunken barrier early for one survivor and strands the other in
        the next round (review finding)."""
        import os
        import time

        from byteps_tpu.comm.ps_client import PSClient
        from byteps_tpu.comm.rendezvous import GROUP_WORKERS
        from byteps_tpu.server.server import PSServer

        env = {
            "DMLC_NUM_WORKER": "3",
            "DMLC_NUM_SERVER": "1",
            "BYTEPS_FORCE_DISTRIBUTED": "1",
            "BYTEPS_HEARTBEAT_INTERVAL": "0.1",
            "BYTEPS_DEAD_NODE_TIMEOUT_S": "0.6",
        }
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        sched = Scheduler(num_workers=3, num_servers=1, host="127.0.0.1")
        sched.start()
        os.environ["DMLC_PS_ROOT_URI"] = "127.0.0.1"
        os.environ["DMLC_PS_ROOT_PORT"] = str(sched.port)
        old.setdefault("DMLC_PS_ROOT_URI", None)
        old.setdefault("DMLC_PS_ROOT_PORT", None)
        try:
            cfg = Config.from_env()
            srv = PSServer(cfg)
            threading.Thread(target=srv.start, daemon=True).start()
            ws = [PSClient(cfg, node_uid=f"bs-w{i}") for i in range(3)]
            ts = [
                threading.Thread(target=w.connect, daemon=True) for w in ws[:2]
            ]
            for t in ts:
                t.start()
            ws[2].connect()
            for t in ts:
                t.join(10)

            # w2 enters a workers barrier, then dies mid-wait (its
            # barrier call raises ConnectionError on close — expected)
            def doomed_barrier():
                try:
                    ws[2].barrier(GROUP_WORKERS)
                except ConnectionError:
                    pass

            threading.Thread(target=doomed_barrier, daemon=True).start()
            time.sleep(0.3)  # its waiter is registered at the scheduler
            ws[2].close()
            for _ in range(100):
                if sched.eviction_totals["worker"] == 1:
                    break
                time.sleep(0.05)
            assert sched.eviction_totals["worker"] == 1

            # the two survivors must pair up in ONE barrier round — with
            # the dead waiter left behind, one of them would be stranded
            done = [threading.Event(), threading.Event()]

            def bar(i):
                ws[i].barrier(GROUP_WORKERS)
                done[i].set()

            for i in range(2):
                threading.Thread(target=bar, args=(i,), daemon=True).start()
            assert done[0].wait(10) and done[1].wait(10), (
                "survivor stranded: stale dead waiter skewed the barrier"
            )
            for w in ws[:2]:
                w.close()
            srv.stop()
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        sched.stop()


class TestZombieWorkerFence:
    def test_push_from_evicted_rank_rejected_and_replay_after_failed_sum_resummed(self):
        """Two server-side guards around the replay ledger
        (docs/robustness.md): (1) a push from a rank absent from the
        latest book's live set raises (engine drops the connection) —
        the stalled-but-alive worker cannot pollute shrunken rounds;
        (2) the ledger records AFTER summation, so a push whose sum
        RAISED is not falsely deduped on retry."""
        import numpy as np

        from byteps_tpu.comm.transport import Message, Op
        from byteps_tpu.server.server import PSServer, _KeyState

        srv = PSServer.__new__(PSServer)
        srv._live_worker_flags = {1}  # only rank 0 is live
        ks = _KeyState()
        ks.store = np.zeros(4, np.float32)

        zombie = Message(Op.PUSH, key=1, version=3, flags=2)  # rank 1: evicted
        with ks.lock:
            with pytest.raises(RuntimeError, match="evicted"):
                srv._is_replayed_push_locked(ks, zombie)

        live = Message(Op.PUSH, key=1, version=3, flags=1)
        with ks.lock:
            # first sight: not a replay — and NOT yet recorded (the sum
            # could still fail); the same message stays fresh until the
            # caller records it post-sum
            assert not srv._is_replayed_push_locked(ks, live)
            assert not srv._is_replayed_push_locked(ks, live)
            srv._record_push_locked(ks, live)  # sum succeeded
            assert srv._is_replayed_push_locked(ks, live)  # replay now

        # fence off (no book / legacy scheduler): anonymous + any rank ok
        srv._live_worker_flags = None
        with ks.lock:
            assert not srv._is_replayed_push_locked(ks, zombie)

    def test_adopt_worker_ranks_from_book(self):
        from byteps_tpu.server.server import PSServer

        srv = PSServer.__new__(PSServer)
        srv._adopt_worker_ranks({"worker_ranks": [0, 2]})
        assert srv._live_worker_flags == {1, 3}
        srv._adopt_worker_ranks({})  # legacy book: fence off
        assert srv._live_worker_flags is None


class TestRebuildRetrySupersede:
    def test_rollback_book_cancels_pending_rebuild_retry(self):
        """A failed server-set rebuild schedules a delayed retry; if the
        resize is then ROLLED BACK (a newer book matching the live set —
        which spawns no rebuild), the retry must cancel instead of
        applying the stale topology over the correct one."""
        import socket as socket_mod

        from byteps_tpu.comm.ps_client import PSClient

        pc = PSClient.__new__(PSClient)
        pc.cfg = Config.from_env()
        pc._stop = threading.Event()
        pc._rebuild_lock = threading.Lock()
        pc._applied_token = 0
        pc._book_token = 0
        pc._servers = []
        pc._server_addrs = [("127.0.0.1", 1)]  # the "current" (old) set
        pc.num_servers = 1
        pc.server_generation = 0
        pc.zero_copy_pulls = 0

        # reserve a port and keep it CLOSED so the first rebuild fails
        probe = socket_mod.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()

        # token 1: resize book to the unreachable server → fails, retries
        pc._book_token = 1
        pc._rebuild_servers(1, [("127.0.0.1", port)], token=1)
        assert pc._applied_token == 0 and pc._server_addrs == [("127.0.0.1", 1)]

        # now the retry COULD succeed (server comes up)…
        srv = socket_mod.socket()
        srv.bind(("127.0.0.1", port))
        srv.listen(4)
        try:
            # …but token 2 — a rollback book matching the live set —
            # arrives first (the sched thread spawns a rebuild for EVERY
            # book; the matching one marks applied without reconnecting)
            pc._book_token = 2
            pc._rebuild_servers(1, [("127.0.0.1", 1)], token=2)
            assert pc._applied_token == 2
            assert pc.server_generation == 0, "no-op book must not churn"

            time.sleep(3.5)  # past the 2s retry window
            assert pc._applied_token == 2, "stale retry must not apply"
            assert pc._server_addrs == [("127.0.0.1", 1)]
            assert pc.server_generation == 0
        finally:
            pc._stop.set()
            srv.close()
