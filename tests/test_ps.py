"""PS-path tests using the single-host fake-cluster pattern.

Mirrors the reference's MetaTest harness (tests/meta_test.py:26-86):
scheduler + server run in-process (daemon threads), the worker is this
process with BYTEPS_FORCE_DISTRIBUTED=1 so a 1-worker job still exercises
the full PS path (global.cc:149-152).  A subprocess test covers true
multi-worker summation.
"""

import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.server.server import NativePSServer, PSServer


@pytest.fixture(
    params=[
        "python", "native", "python-uds", "python-shm",
        "native-uds", "native-shm",
    ]
)
def fake_cluster(request, monkeypatch):
    """Scheduler + 1 server in-process; this process becomes the worker.
    Parametrized over the full engine × transport matrix: the Python and
    C++ engines each behind the tcp, uds, and shm vans — every PS test
    runs against every combination (the native-shm column is the no-GIL
    engine composed with the zero-copy transport)."""
    engine, _, van = request.param.partition("-")
    if engine == "native":
        from byteps_tpu.native import HAVE_NATIVE, get_lib

        if not HAVE_NATIVE:
            pytest.skip("native lib not built")
        if van and not hasattr(get_lib(), "bps_native_server_start_unix"):
            pytest.skip("native lib predates unix/shm listener")
    if van == "shm":
        import platform

        if platform.machine() not in ("x86_64", "AMD64", "i686"):
            pytest.skip("shm van requires x86-64 (TSO store ordering)")
    if van:
        monkeypatch.setenv("BYTEPS_VAN", van)
    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")

    scfg = Config.from_env()
    srv = NativePSServer(scfg) if engine == "native" else PSServer(scfg)
    t = threading.Thread(target=srv.start, daemon=True)  # registration blocks on barrier
    t.start()
    yield {"scheduler": sched, "server": srv}
    srv.stop()
    sched.stop()


class TestFakeCluster:
    def test_push_pull_identity_via_ps(self, fake_cluster):
        """1 worker ⇒ push_pull through the real PS = identity
        (test_mxnet.py:30-126 semantics)."""
        import byteps_tpu as bps

        bps.init()
        for dtype in (np.float32, np.float64, np.int32):
            x = (np.arange(100, dtype=dtype) - 50) * 3
            out = bps.push_pull(x, name=f"ps.t.{np.dtype(dtype).name}")
            np.testing.assert_allclose(np.asarray(out), x)
        bps.shutdown()

    def test_multi_round(self, fake_cluster):
        import byteps_tpu as bps

        bps.init()
        for step in range(5):
            x = np.full(64, float(step), dtype=np.float32)
            out = bps.push_pull(x, name="ps.round")
            np.testing.assert_allclose(np.asarray(out), x)
        bps.shutdown()

    def test_partitioned_tensor(self, fake_cluster, monkeypatch):
        """Large tensor split into many keys (BYTEPS_PARTITION_BYTES,
        operations.cc:140-180) must reassemble exactly."""
        monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "256")
        import byteps_tpu as bps

        bps.init()
        x = np.random.default_rng(3).normal(size=2000).astype(np.float32)
        out = bps.push_pull(x, name="ps.big")
        np.testing.assert_allclose(np.asarray(out), x)
        from byteps_tpu.common.registry import get_registry

        parts = get_registry().get("ps.big").partitions
        assert len(parts) > 10  # really partitioned
        bps.shutdown()

    def test_async_overlapped_handles(self, fake_cluster):
        import byteps_tpu as bps

        bps.init()
        xs = [np.full(32, i, dtype=np.float32) for i in range(8)]
        handles = [
            bps.push_pull_async(x, name=f"ps.async.{i}", priority=-i)
            for i, x in enumerate(xs)
        ]
        for i, h in enumerate(handles):
            np.testing.assert_allclose(np.asarray(bps.synchronize(h)), xs[i])
        bps.shutdown()

    def test_broadcast_object_via_ps(self, fake_cluster):
        import byteps_tpu as bps

        bps.init()
        obj = {"lr": 0.5, "name": "adam", "betas": (0.9, 0.999)}
        assert bps.broadcast_object(obj, root_rank=0, name="opt_state") == obj
        bps.shutdown()

    def test_telemetry_records_bytes(self, fake_cluster, monkeypatch):
        monkeypatch.setenv("BYTEPS_TELEMETRY_ON", "1")
        import byteps_tpu as bps

        bps.init()
        x = np.ones(10000, dtype=np.float32)
        bps.push_pull(x, name="ps.speed")
        assert bps.get_pushpull_speed() > 0.0
        bps.shutdown()

    def test_trace_emitted(self, fake_cluster, monkeypatch, tmp_path):
        monkeypatch.setenv("BYTEPS_TRACE_ON", "1")
        monkeypatch.setenv("BYTEPS_TRACE_START_STEP", "0")
        monkeypatch.setenv("BYTEPS_TRACE_END_STEP", "100")
        monkeypatch.setenv("BYTEPS_TRACE_DIR", str(tmp_path))
        import byteps_tpu as bps

        bps.init()
        bps.push_pull(np.ones(16, dtype=np.float32), name="ps.traced")
        bps.shutdown()
        import json

        trace_file = tmp_path / "0" / "comm.json"
        assert trace_file.exists()
        events = json.loads(trace_file.read_text())["traceEvents"]
        stages = {e["name"] for e in events}
        assert "PUSH" in stages and "PULL" in stages


class TestMultiServer:
    """Key→server sharding end-to-end: a partitioned tensor's keys spread
    across two servers (EncodeDefaultKey semantics, global.cc:628-677) and
    reassemble exactly."""

    def test_two_servers_partitioned_tensor(self, monkeypatch):
        sched = Scheduler(num_workers=1, num_servers=2, host="127.0.0.1")
        sched.start()
        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_NUM_SERVER", "2")
        monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
        monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "512")
        servers = [PSServer(Config.from_env()) for _ in range(2)]
        for srv in servers:
            threading.Thread(target=srv.start, daemon=True).start()

        import byteps_tpu as bps

        bps.init()
        x = np.random.default_rng(7).normal(size=4000).astype(np.float32)
        out = bps.push_pull(x, name="ms.big", average=False)
        np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)

        # both servers actually own keys
        from byteps_tpu.common.registry import get_registry
        from byteps_tpu.core.state import get_state

        client = get_state().ps_client
        parts = get_registry().get("ms.big").partitions
        owners = {client.server_for(p.key) for p in parts}
        assert owners == {0, 1}, f"keys all landed on {owners}"
        # server-side stores agree with the split
        total = sum(
            ks.store.size for srv in servers for ks in srv._keys.values()
        )
        assert total == x.size
        bps.shutdown()
        for srv in servers:
            srv.stop()
        sched.stop()


class TestCompressionOverPS:
    """End-to-end gradient compression through the real PS path — the
    reference's compression tests run a full fake cluster the same way
    (tests/test_onebit.py + meta_test.py with BYTEPS_MIN_COMPRESS_BYTES=0)."""

    def test_topk_full_k_is_lossless_identity(self, fake_cluster, monkeypatch):
        monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
        import byteps_tpu as bps

        bps.init()
        n = 256
        bps.declare_tensor(
            "c.topk", byteps_compressor_type="topk", byteps_compressor_k=str(n)
        )
        x = np.random.default_rng(0).normal(size=n).astype(np.float32)
        out = bps.push_pull(x, name="c.topk", average=False)
        np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)
        bps.shutdown()

    def test_onebit_signs_through_ps(self, fake_cluster, monkeypatch):
        monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
        import byteps_tpu as bps
        from byteps_tpu.compression.impl import OneBitCompressor

        bps.init()
        n = 128
        bps.declare_tensor(
            "c.onebit",
            byteps_compressor_type="onebit",
            byteps_compressor_onebit_scaling="True",
        )
        x = np.random.default_rng(1).normal(size=n).astype(np.float32)
        out = np.asarray(bps.push_pull(x, name="c.onebit", average=False))
        # 1 worker ⇒ server stores decompress(compress(x)); pull returns
        # compress of that again — simulate the double codec pass
        sim = OneBitCompressor(n, scaling=True)
        once = sim.decompress(sim.compress(x), n)
        sim2 = OneBitCompressor(n, scaling=True)
        expected = sim2.decompress(sim2.compress(once), n)
        np.testing.assert_allclose(out, expected, rtol=1e-6)
        bps.shutdown()

    def test_ef_chain_trajectory_matches_simulation(self, fake_cluster, monkeypatch):
        """Multi-round randomk+EF through the PS must bit-match an
        in-process simulation of the worker→server→worker codec chain
        (the reference's numpy re-simulation strategy)."""
        monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
        import byteps_tpu as bps
        from byteps_tpu.compression.registry import create_compressor

        bps.init()
        n, rounds = 64, 5
        kwargs = {
            "byteps_compressor_type": "randomk",
            "byteps_compressor_k": "16",
            "byteps_ef_type": "vanilla",
            "byteps_seed": "77",
        }
        bps.declare_tensor("c.ef", **kwargs)
        worker_sim = create_compressor(kwargs, n, server=False)
        server_sim = create_compressor(kwargs, n, server=True)
        rng = np.random.default_rng(2)
        for r in range(rounds):
            g = rng.normal(size=n).astype(np.float32)
            out = np.asarray(bps.push_pull(g, name="c.ef", average=False))
            pushed = worker_sim.compress(g)
            merged = worker_sim.decompress(pushed, n)  # 1 worker: sum = self
            pulled = server_sim.compress(merged)
            expected = server_sim.decompress(pulled, n)
            np.testing.assert_allclose(out, expected, rtol=1e-6, err_msg=f"round {r}")
        bps.shutdown()


    def test_ef_lr_reaches_server_chains(self, fake_cluster, monkeypatch):
        """bps.set_compression_lr must scale the EF residual on BOTH
        sides of the wire: the worker chain directly, the server chain
        via the lr-update control message (the reference's lr.s mmap,
        vanilla_error_feedback.h:44-58).  Proven numerically: a mid-run
        lr change must keep the PS trajectory bit-matched to a
        simulation whose sims get set_lr at the same step."""
        monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
        import byteps_tpu as bps
        from byteps_tpu.compression.registry import create_compressor

        bps.init()
        n, rounds = 64, 6
        kwargs = {
            "byteps_compressor_type": "randomk",
            "byteps_compressor_k": "16",
            "byteps_ef_type": "vanilla",
            "byteps_seed": "99",
        }
        # lr set BEFORE any chain exists anywhere: must be remembered,
        # applied to worker chains on creation and shipped with the
        # first registration (the trainer's first step does exactly this)
        bps.set_compression_lr(0.5)
        bps.declare_tensor("c.eflr", **kwargs)
        worker_sim = create_compressor(kwargs, n, server=False)
        server_sim = create_compressor(kwargs, n, server=True)
        worker_sim.set_lr(0.5)
        server_sim.set_lr(0.5)
        rng = np.random.default_rng(3)

        def roundtrip(name, g, wsim, ssim, r):
            out = np.asarray(bps.push_pull(g, name=name, average=False))
            pushed = wsim.compress(g)
            merged = wsim.decompress(pushed, n)
            pulled = ssim.compress(merged)
            expected = ssim.decompress(pulled, n)
            np.testing.assert_allclose(
                out, expected, rtol=1e-6, err_msg=f"{name} round {r}"
            )

        for r in range(rounds):
            if r == 2:  # mid-run change after chains exist on both sides
                bps.set_compression_lr(0.25)
                worker_sim.set_lr(0.25)
                server_sim.set_lr(0.25)
            roundtrip("c.eflr", rng.normal(size=n).astype(np.float32), worker_sim, server_sim, r)

        # a tensor declared AFTER the lr changes must inherit 0.25 on
        # both sides (late-registered chains)
        kwargs2 = dict(kwargs, byteps_seed="101")
        bps.declare_tensor("c.eflr2", **kwargs2)
        wsim2 = create_compressor(kwargs2, n, server=False)
        ssim2 = create_compressor(kwargs2, n, server=True)
        wsim2.set_lr(0.25)
        ssim2.set_lr(0.25)
        for r in range(3):
            roundtrip("c.eflr2", rng.normal(size=n).astype(np.float32), wsim2, ssim2, r)
        bps.shutdown()

    def test_async_mode_with_compression(self, monkeypatch):
        """Async parameter-store mode + codec: pulls must come back in the
        puller's requested wire format (compressed on demand).  The async
        flag must be set before the server starts — worker and server modes
        have to agree (as in the reference, both read BYTEPS_ENABLE_ASYNC)."""
        monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
        monkeypatch.setenv("BYTEPS_ENABLE_ASYNC", "1")
        sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
        sched.start()
        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_NUM_SERVER", "1")
        monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
        srv = PSServer(Config.from_env())
        threading.Thread(target=srv.start, daemon=True).start()
        import byteps_tpu as bps

        bps.init()
        n = 128
        bps.declare_tensor(
            "c.async", byteps_compressor_type="topk", byteps_compressor_k=str(n)
        )
        x = np.random.default_rng(4).normal(size=n).astype(np.float32)
        out1 = np.asarray(bps.push_pull(x, name="c.async", average=False))
        out2 = np.asarray(bps.push_pull(x, name="c.async", average=False))
        # async store accumulates: round1 = x, round2 = 2x (topk k=n lossless)
        np.testing.assert_allclose(out1, x, rtol=1e-6)
        np.testing.assert_allclose(out2, 2 * x, rtol=1e-6)
        bps.shutdown()
        srv.stop()
        sched.stop()


_WORKER_SCRIPT = textwrap.dedent(
    """
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import byteps_tpu as bps

    bps.init()
    r = bps.rank()
    x = np.full(50, float(r + 1), dtype=np.float32)
    out = bps.push_pull(x, name="grad.sum", average=False)
    expected = np.full(50, 1.0 + 2.0, dtype=np.float32)  # 2 workers: 1+2
    assert np.allclose(np.asarray(out), expected), (r, out[:4])
    avg = bps.push_pull(x, name="grad.avg", average=True)
    assert np.allclose(np.asarray(avg), expected / 2), (r, avg[:4])
    bps.shutdown()
    print(f"WORKER_{r}_OK")
    """
)


class TestMultiWorker:
    @pytest.mark.parametrize("server_kind", ["python", "native"])
    def test_two_workers_sum(self, tmp_path, server_kind):
        """True cross-worker aggregation: 2 worker subprocesses push
        different values; both must receive the sum (the PS's whole job,
        server.cc:296-375).  Runs against BOTH engines — the native
        ALL_RECV round + pending-pull flush (ps_server.cc) is the
        trickiest concurrency in the repo and needs real 2-worker load."""
        if server_kind == "native":
            from byteps_tpu.native import HAVE_NATIVE

            if not HAVE_NATIVE:
                pytest.skip("native lib not built")
        sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
        sched.start()
        env_common = {
            **os.environ,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_NUM_SERVER": "1",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": "/root/repo",
        }
        scfg = Config.from_env()
        scfg.num_worker = 2
        scfg.num_server = 1
        scfg.ps_root_uri = "127.0.0.1"
        scfg.ps_root_port = sched.port
        srv = NativePSServer(scfg) if server_kind == "native" else PSServer(scfg)
        threading.Thread(target=srv.start, daemon=True).start()

        script = tmp_path / "worker.py"
        script.write_text(_WORKER_SCRIPT)
        procs = [
            subprocess.Popen(
                [sys.executable, str(script)],
                env={**env_common, "BYTEPS_GLOBAL_RANK": str(i)},
                cwd="/root/repo",
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        outs = [p.communicate(timeout=120)[0] for p in procs]
        srv.stop()
        sched.stop()
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"worker {i} failed:\n{out}"
        combined = "".join(outs)
        assert "WORKER_0_OK" in combined and "WORKER_1_OK" in combined


class TestServerDeath:
    @pytest.mark.parametrize(
        "server_kind", ["python", "native", "python+nc", "native+nc"]
    )
    def test_sigkill_server_fails_handles_not_hangs(
        self, monkeypatch, tmp_path, server_kind
    ):
        """Failure detection (SURVEY §5.3): SIGKILL the server subprocess
        mid-job; subsequent push_pulls must surface a RuntimeError on the
        handle within the test timeout — never hang in synchronize().
        Exercises the dead-connection callback chain end to end
        (ps_client._recv_loop → engine._fail_task → handle status), for
        both server engines (the worker-side plumbing is engine-agnostic,
        but the kill timing differs).  The ``+nc`` variants run the
        worker on the C++ client (native/ps_client.cc last-lane drain)."""
        server_kind, _, nc = server_kind.partition("+")
        if nc:
            from byteps_tpu.native import get_lib

            lib = get_lib()
            if lib is None or not hasattr(lib, "bpsc_drain"):
                pytest.skip("native client lib not built")
            monkeypatch.setenv("BYTEPS_NATIVE_CLIENT", "1")
        if server_kind == "native":
            from byteps_tpu.native import HAVE_NATIVE

            if not HAVE_NATIVE:
                pytest.skip("native lib not built")
            monkeypatch.setenv("BYTEPS_SERVER_NATIVE", "1")
        sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
        sched.start()
        env = {
            **os.environ,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "1",
            "DMLC_NUM_SERVER": "1",
            "DMLC_ROLE": "server",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": "/root/repo",
        }
        srv = subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"],
            env=env,
            cwd="/root/repo",
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_NUM_SERVER", "1")
        monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
        import byteps_tpu as bps

        try:
            bps.init()
            x = np.ones(64, np.float32)
            out = bps.push_pull(x, name="chaos.g", average=False)
            np.testing.assert_allclose(np.asarray(out), x)

            srv.kill()
            srv.wait(timeout=10)

            deadline = time.time() + 60
            with pytest.raises(RuntimeError, match="push_pull failed"):
                while time.time() < deadline:
                    bps.push_pull(x, name="chaos.g", average=False)
        finally:
            bps.shutdown()
            if srv.poll() is None:
                srv.kill()
            sched.stop()


class TestSchedulerDeath:
    def test_data_plane_survives_and_rejoins_restarted_scheduler(self, monkeypatch):
        """SIGKILL the scheduler subprocess mid-job: the data plane rides
        direct worker↔server connections and must keep aggregating, while
        control-plane calls (query_cluster) raise ConnectionError for as
        long as the node is in control_plane_degraded mode — including
        calls made AFTER the link died, which previously registered
        waiters nobody would ever wake.  The death is no longer terminal
        (docs/robustness.md "Control-plane recovery"): once a successor
        scheduler binds the same address, the reconnect machine
        re-registers and control-plane calls work again."""
        port_probe = __import__("socket").socket()
        port_probe.bind(("127.0.0.1", 0))
        port = port_probe.getsockname()[1]
        port_probe.close()
        # fast redials so the rejoin half of the test stays quick
        monkeypatch.setenv("BYTEPS_SCHED_RECONNECT_BACKOFF_S", "0.1")
        monkeypatch.setenv("BYTEPS_SCHED_RECONNECT_RETRIES", "100")
        monkeypatch.setenv("BYTEPS_CONNECT_RETRY_S", "0.2")
        env = {
            **os.environ,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": "1",
            "DMLC_NUM_SERVER": "1",
            "DMLC_ROLE": "scheduler",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": "/root/repo",
        }
        sched_proc = subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"],
            env=env,
            cwd="/root/repo",
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        import socket as _socket

        deadline = time.time() + 30
        while time.time() < deadline:  # wait for the subprocess to bind
            try:
                _socket.create_connection(("127.0.0.1", port), timeout=1).close()
                break
            except OSError:
                time.sleep(0.2)
        else:
            raise RuntimeError("scheduler subprocess never bound its port")

        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_NUM_SERVER", "1")
        monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
        scfg = Config.from_env()
        srv = PSServer(scfg)
        threading.Thread(target=srv.start, daemon=True).start()
        import byteps_tpu as bps

        try:
            bps.init()
            x = np.ones(32, np.float32)
            out = bps.push_pull(x, name="sched.chaos", average=False)
            np.testing.assert_allclose(np.asarray(out), x)

            sched_proc.kill()
            sched_proc.wait(timeout=10)
            time.sleep(0.5)  # let the recv loop observe the FIN/RST

            # data plane: still aggregating over the live server link
            out2 = bps.push_pull(x, name="sched.chaos", average=False)
            np.testing.assert_allclose(np.asarray(out2), x)

            # control plane: fail fast while degraded, even well after
            # the death (no waiter may park on a dead link)
            from byteps_tpu.core.state import require_state

            client = require_state().ps_client
            for _ in range(3):
                with pytest.raises(ConnectionError):
                    client.query_cluster()

            # the latch is no longer terminal: restart the scheduler on
            # the SAME address — the reconnect machine re-registers and
            # the control plane comes back
            sched_proc = subprocess.Popen(
                [sys.executable, "-m", "byteps_tpu.server"],
                env=env,
                cwd="/root/repo",
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            deadline = time.time() + 60
            live = None
            while time.time() < deadline:
                try:
                    live = client.query_cluster()
                    break
                except ConnectionError:
                    time.sleep(0.5)
            assert live is not None, "control plane never rejoined"
            assert 0 in live["worker"] and 0 in live["server"]
            # data plane still exact through the whole episode
            out3 = bps.push_pull(x, name="sched.chaos", average=False)
            np.testing.assert_allclose(np.asarray(out3), x)
        finally:
            bps.shutdown()
            if sched_proc.poll() is None:
                sched_proc.kill()
            srv.stop()


class TestServerScheduling:
    """BYTEPS_SERVER_ENABLE_SCHEDULE (queue.h:49-97) must be honored by
    BOTH engines: with scheduling on and multiple engine threads, traffic
    still aggregates correctly (the knob reorders service, never results)."""

    @pytest.mark.parametrize("server_kind", ["python", "native"])
    def test_schedule_knob_correct_sums(self, tmp_path, server_kind, monkeypatch):
        if server_kind == "native":
            from byteps_tpu.native import HAVE_NATIVE

            if not HAVE_NATIVE:
                pytest.skip("native lib not built")
        monkeypatch.setenv("BYTEPS_SERVER_ENABLE_SCHEDULE", "1")
        monkeypatch.setenv("BYTEPS_SERVER_ENGINE_THREAD", "2")
        monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "512")
        sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
        sched.start()
        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_NUM_SERVER", "1")
        monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
        scfg = Config.from_env()
        srv = NativePSServer(scfg) if server_kind == "native" else PSServer(scfg)
        threading.Thread(target=srv.start, daemon=True).start()
        try:
            import byteps_tpu as bps

            bps.init()
            rng = np.random.default_rng(11)
            for step in range(4):
                for name in ("sched.a", "sched.b", "sched.c"):
                    x = rng.normal(size=700).astype(np.float32)
                    out = bps.push_pull(x, name=name, average=False)
                    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)
            bps.shutdown()
        finally:
            srv.stop()
            sched.stop()


_RS_WORKER_SCRIPT = textwrap.dedent(
    """
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import byteps_tpu as bps

    bps.init()
    r = bps.rank()
    # worker 0 touches rows {0, 2}; worker 1 touches rows {1, 2}:
    # disjoint rows pass through, row 2 sums across workers
    if r == 0:
        idx = np.array([0, 2], np.int64)
        vals = np.stack([np.full(8, 1.0), np.full(8, 10.0)]).astype(np.float32)
    else:
        idx = np.array([1, 2], np.int64)
        vals = np.stack([np.full(8, 2.0), np.full(8, 20.0)]).astype(np.float32)
    out = bps.push_pull_rowsparse(idx, vals, name="emb.grad", total_rows=16,
                                  average=False)
    assert out.shape == (2, 8), out.shape
    if r == 0:
        assert np.allclose(out[0], 1.0), out[0]   # row 0: only w0
        assert np.allclose(out[1], 30.0), out[1]  # row 2: 10 + 20
    else:
        assert np.allclose(out[0], 2.0), out[0]   # row 1: only w1
        assert np.allclose(out[1], 30.0), out[1]
    # averaged round on the same key
    avg = bps.push_pull_rowsparse(idx, vals, name="emb.grad", total_rows=16,
                                  average=True)
    assert np.allclose(avg[1], 15.0), avg[1]
    bps.shutdown()
    print(f"RS_WORKER_{r}_OK")
    """
)


class TestRowSparse:
    def test_rowsparse_identity_one_worker(self, fake_cluster):
        """1 worker ⇒ RS push_pull returns the pushed rows
        (kRowSparsePushPull, common.h:267-271) — runs against every
        engine/van combination via the fixture."""
        import byteps_tpu as bps

        bps.init()
        idx = np.array([3, 0, 7], np.int64)
        vals = np.arange(12, dtype=np.float32).reshape(3, 4) + 1.0
        out = bps.push_pull_rowsparse(
            idx, vals, name="rs.id", total_rows=10, average=False
        )
        np.testing.assert_allclose(out, vals)
        bps.shutdown()

    def test_rowsparse_duplicate_indices_accumulate(self, fake_cluster):
        """Duplicate indices in one push scatter-ADD (np.add.at semantics);
        the pull then gathers the summed row for each occurrence."""
        import byteps_tpu as bps

        bps.init()
        idx = np.array([5, 5], np.int64)
        vals = np.stack(
            [np.full(4, 1.0), np.full(4, 2.0)]
        ).astype(np.float32)
        out = bps.push_pull_rowsparse(
            idx, vals, name="rs.dup", total_rows=8, average=False
        )
        np.testing.assert_allclose(out, 3.0)  # both gathers see row5 = 1+2
        bps.shutdown()

    def test_rowsparse_multi_round_and_untouched_rows_reset(self, fake_cluster):
        """Round 2 must not inherit round 1's rows (sparse COPY_FIRST
        zeroes the accumulator): a row touched only in round 1 reads 0 in
        round 2."""
        import byteps_tpu as bps

        bps.init()
        idx1 = np.array([1], np.int64)
        v1 = np.full((1, 4), 7.0, np.float32)
        out1 = bps.push_pull_rowsparse(idx1, v1, name="rs.rounds", total_rows=4,
                                       average=False)
        np.testing.assert_allclose(out1, 7.0)
        idx2 = np.array([2, 1], np.int64)
        v2 = np.stack([np.full(4, 5.0), np.zeros(4)]).astype(np.float32)
        out2 = bps.push_pull_rowsparse(idx2, v2, name="rs.rounds", total_rows=4,
                                       average=False)
        np.testing.assert_allclose(out2[0], 5.0)
        np.testing.assert_allclose(out2[1], 0.0)  # round 1's 7.0 is gone
        bps.shutdown()

    def test_rowsparse_validation(self, fake_cluster):
        import byteps_tpu as bps

        bps.init()
        with pytest.raises(ValueError, match="out of range"):
            bps.push_pull_rowsparse(
                np.array([9], np.int64), np.ones((1, 4), np.float32),
                name="rs.bad", total_rows=4,
            )
        with pytest.raises(ValueError, match="indices"):
            bps.push_pull_rowsparse(
                np.array([[1]], np.int64), np.ones((1, 4), np.float32),
                name="rs.bad2", total_rows=4,
            )
        bps.shutdown()

    @pytest.mark.parametrize("server_kind", ["python", "native"])
    def test_two_workers_rowsparse_sum(self, tmp_path, server_kind):
        """Cross-worker RS aggregation: disjoint rows pass through, shared
        rows sum — against BOTH server engines."""
        if server_kind == "native":
            from byteps_tpu.native import HAVE_NATIVE

            if not HAVE_NATIVE:
                pytest.skip("native lib not built")
        sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
        sched.start()
        env_common = {
            **os.environ,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_NUM_SERVER": "1",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": "/root/repo",
        }
        scfg = Config.from_env()
        scfg.num_worker = 2
        scfg.num_server = 1
        scfg.ps_root_uri = "127.0.0.1"
        scfg.ps_root_port = sched.port
        srv = NativePSServer(scfg) if server_kind == "native" else PSServer(scfg)
        threading.Thread(target=srv.start, daemon=True).start()

        script = tmp_path / "rs_worker.py"
        script.write_text(_RS_WORKER_SCRIPT)
        procs = [
            subprocess.Popen(
                [sys.executable, str(script)],
                env={**env_common, "BYTEPS_GLOBAL_RANK": str(i)},
                cwd="/root/repo",
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        outs = [p.communicate(timeout=120)[0] for p in procs]
        srv.stop()
        sched.stop()
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rs worker {i} failed:\n{out}"
        combined = "".join(outs)
        assert "RS_WORKER_0_OK" in combined and "RS_WORKER_1_OK" in combined


class TestZeroCopyVan:
    def test_pull_lands_zero_copy_through_engine(self, fake_cluster):
        """The engine registers the result slice as the pull sink, so
        aggregated payloads are received INTO the caller's buffer — the
        zero-copy pull path must actually fire on plain dense traffic."""
        import byteps_tpu as bps
        from byteps_tpu.core.state import get_state

        bps.init()
        x = np.arange(4096, dtype=np.float32)
        out = bps.push_pull(x, name="zc.t", average=False)
        np.testing.assert_allclose(np.asarray(out), x)
        assert get_state().ps_client.zero_copy_pulls > 0
        bps.shutdown()

    def test_sendmsg_partial_sends_reassemble(self):
        """The scatter-gather send loop must survive arbitrary partial
        sendmsg returns without corrupting the frame."""
        from byteps_tpu.comm.transport import Message, Op, send_message

        class ChunkySock:
            """sendmsg that transmits at most 7 bytes per call."""

            def __init__(self):
                self.data = bytearray()

            def sendmsg(self, bufs):
                take = 7
                sent = 0
                for b in bufs:
                    chunk = bytes(b[: take - sent])
                    self.data += chunk
                    sent += len(chunk)
                    if sent >= take:
                        break
                return sent

        payload = bytes(range(256)) * 3
        sock = ChunkySock()
        send_message(sock, Message(Op.PUSH, key=9, payload=payload, seq=5))
        from byteps_tpu.comm.transport import HEADER_SIZE

        assert len(sock.data) == HEADER_SIZE + len(payload)
        assert bytes(sock.data[HEADER_SIZE:]) == payload

    def test_numpy_buffer_payload_no_tobytes(self, fake_cluster):
        """A contiguous numpy buffer travels as a memoryview (no copy) and
        the wire bytes are identical to the tobytes() framing."""
        import byteps_tpu as bps

        bps.init()
        x = np.random.default_rng(0).normal(size=2000).astype(np.float32)
        out = bps.push_pull(x, name="zc.mv", average=False)
        np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)
        bps.shutdown()


class TestStripedTcpVan:
    """BYTEPS_TCP_STREAMS>1: partitions stripe across parallel TCP
    connections per server (the multi-lane RDMA/UCX van analogue,
    reference setup.py:312-330)."""

    @pytest.mark.parametrize("server_kind", ["python", "native"])
    def test_partitioned_multi_round_over_stripes(
        self, server_kind, monkeypatch
    ):
        if server_kind == "native":
            from byteps_tpu.native import HAVE_NATIVE

            if not HAVE_NATIVE:
                pytest.skip("native lib not built")
        monkeypatch.setenv("BYTEPS_TCP_STREAMS", "4")
        # small partitions → many keys → every lane carries traffic
        monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "4096")
        sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
        sched.start()
        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_NUM_SERVER", "1")
        monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
        scfg = Config.from_env()
        srv = NativePSServer(scfg) if server_kind == "native" else PSServer(scfg)
        threading.Thread(target=srv.start, daemon=True).start()
        try:
            import byteps_tpu as bps

            bps.init()
            assert bps.size() == 1
            from byteps_tpu.core.state import get_state

            client = get_state().ps_client
            assert len(client._servers[0].stripes) == 4
            import jax.numpy as jnp

            x = np.arange(20000, dtype=np.float32)  # ~20 partitions
            for r in range(3):
                out = bps.push_pull(jnp.asarray(x) * (r + 1), name="g.striped")
                np.testing.assert_allclose(np.asarray(out), x * (r + 1))
            bps.shutdown()
        finally:
            srv.stop()
            sched.stop()

    def test_stripes_die_together(self, monkeypatch):
        """Killing the server mid-flight must fail pending handles (not
        hang) even with multiple lanes — one dead lane poisons all.

        With the self-healing layer (docs/robustness.md) a push on the
        poisoned connection then REVIVES it (the server is still alive)
        and succeeds; with retries disabled it fails fast as before —
        both contracts are pinned here."""
        monkeypatch.setenv("BYTEPS_TCP_STREAMS", "3")
        monkeypatch.setenv("BYTEPS_RPC_RETRIES", "0")  # legacy fail-fast
        sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
        sched.start()
        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_NUM_SERVER", "1")
        monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
        scfg = Config.from_env()
        srv = PSServer(scfg)
        threading.Thread(target=srv.start, daemon=True).start()
        try:
            from byteps_tpu.comm.ps_client import PSClient

            client = PSClient(Config.from_env(), node_uid="striped-death")
            client.connect()
            sc = client._servers[0]
            assert len(sc.stripes) == 3
            client.init_tensor(7, 256, 0)
            # kill ONE lane: its recv loop must poison the whole striped
            # connection (close_all + mark_dead), not leave a half-dead
            # link that strands keys hashed to the dead lane
            from byteps_tpu.comm.transport import close_socket as _close

            _close(sc.stripes[1][0])
            deadline = time.monotonic() + 10
            while not sc.dead and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sc.dead, "one dead lane must mark the whole conn dead"
            failed = threading.Event()
            client.push(
                7, np.zeros(256, np.float32).tobytes(), 0, 1,
                cb=lambda *a: None, on_error=failed.set,
            )
            assert failed.wait(5), "push on dead conn must fail, not hang"

            # self-healing contract: with retries enabled the same push
            # revives the connection (server still alive) and SUCCEEDS
            client.cfg.rpc_retries = 2
            healed = threading.Event()
            died = threading.Event()
            client.push(
                7, np.zeros(256, np.float32).tobytes(), 0, 2,
                cb=healed.set, on_error=died.set,
            )
            assert healed.wait(10), "retry+revive must heal a dead conn"
            assert not died.is_set()
            assert not client._servers[0].dead  # fresh lanes in place
            client.close()
        finally:
            srv.stop()
            sched.stop()


class TestReinitCycle:
    """shutdown() → init() against a NEW cluster must re-run every key's
    init-push barrier: the tensor registry (and each ctx) deliberately
    outlives init cycles for stable key replay, but a fresh cluster's
    stores are empty — a skipped init means the first push hits an
    uninitialized key and the server drops the connection.  Regression:
    found by an end-to-end drive running two clusters in one process
    (engine_epoch, core/engine.py _prepare_round)."""

    @pytest.mark.parametrize("engine", ["python", "native"])
    def test_same_name_across_two_clusters(self, engine, monkeypatch):
        if engine == "native":
            from byteps_tpu.native import HAVE_NATIVE

            if not HAVE_NATIVE:
                pytest.skip("native lib not built")

        def one_cluster(value: float) -> None:
            sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
            sched.start()
            monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
            monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
            monkeypatch.setenv("DMLC_NUM_WORKER", "1")
            monkeypatch.setenv("DMLC_NUM_SERVER", "1")
            monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
            scfg = Config.from_env()
            srv = NativePSServer(scfg) if engine == "native" else PSServer(scfg)
            threading.Thread(target=srv.start, daemon=True).start()
            try:
                import byteps_tpu as bps

                bps.init()
                x = np.full(4096, value, dtype=np.float32)
                # same tensor name both cycles — the second cluster's
                # server has never seen it
                out = bps.push_pull(x, name="ps.reinit_cycle")
                np.testing.assert_allclose(np.asarray(out), x)
                bps.shutdown()
            finally:
                srv.stop()
                sched.stop()

        one_cluster(1.0)
        one_cluster(2.0)


class TestStripedReducerConcurrency:
    """Barrier-in-sum detector for the key-striped native engine: two
    keys on DIFFERENT stripes must sum concurrently.  The probe is
    ordering, not timing thresholds: one connection sends a huge push
    (a multi-millisecond memcpy/sum) then a tiny one; the serve thread
    enqueues them in arrival order, so

    - stripes=1 (one reducer, FIFO ring): the tiny ack ALWAYS trails
      the huge one — the deterministic control;
    - stripes=2 with the keys on different reducers: the tiny sum
      finishes while the huge one is still running, so its ack arrives
      first.  A global lock (or any barrier) inside the sum path would
      serialize them and flip the order back.
    """

    BIG_N = 8 << 20  # 32 MB of f32: several ms of memcpy/sum per round
    SMALL_N = 1024

    def _two_keys_two_stripes(self):
        from byteps_tpu.native import key_stripe

        big = 0
        for k in range(1, 64):
            if key_stripe(k, 2) != key_stripe(big, 2):
                return big, k
        pytest.fail("key_stripe maps 64 dense keys onto one stripe")

    def _ack_order(self, stripes: int, monkeypatch, rounds: int = 3) -> list:
        """[first-acked key per round] for N rounds of big-then-small."""
        import struct as _struct

        from byteps_tpu.common.types import (
            DataType, RequestType, get_command_type,
        )
        from byteps_tpu.comm.transport import (
            Message, Op, close_socket, connect, recv_message, send_message,
        )

        monkeypatch.setenv("BYTEPS_SERVER_STRIPES", str(stripes))
        cfg = Config(num_worker=1, num_server=1)
        srv = NativePSServer(cfg)
        first_acks = []
        try:
            sock = connect(srv.host, srv.port)
            cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                   int(DataType.FLOAT32))
            key_big, key_small = self._two_keys_two_stripes()
            for key, n in ((key_big, self.BIG_N), (key_small, self.SMALL_N)):
                send_message(sock, Message(
                    Op.INIT, key=key, seq=key, flags=1,
                    payload=_struct.pack("!QI", n, int(DataType.FLOAT32)),
                ))
                assert recv_message(sock).op == Op.INIT
            big = np.ones(self.BIG_N, dtype=np.float32)
            small = np.ones(self.SMALL_N, dtype=np.float32)
            for rnd in range(1, rounds + 1):
                send_message(sock, Message(
                    Op.PUSH, key=key_big, seq=10 * rnd, flags=1, cmd=cmd,
                    version=rnd, payload=big.tobytes(),
                ))
                send_message(sock, Message(
                    Op.PUSH, key=key_small, seq=10 * rnd + 1, flags=1,
                    cmd=cmd, version=rnd, payload=small.tobytes(),
                ))
                acks = [recv_message(sock) for _ in range(2)]
                assert {m.op for m in acks} == {Op.PUSH}
                first_acks.append(acks[0].key)
            close_socket(sock)
        finally:
            srv.stop()
        return first_acks, key_big, key_small

    def test_native_two_stripes_sum_concurrently(self, monkeypatch):
        from byteps_tpu.native import HAVE_NATIVE

        if not HAVE_NATIVE:
            pytest.skip("native lib not built")
        # control: one reducer is strict FIFO — the huge push acks first
        # in every round (this also pins the probe's assumptions: same
        # stripe ⇒ ordered)
        order1, key_big, _ = self._ack_order(1, monkeypatch)
        assert order1 == [key_big] * 3, (
            f"single-stripe FIFO violated: {order1}"
        )
        # striped: the tiny sum overtakes the in-flight huge sum on the
        # other reducer.  The control above pins that a serialized
        # engine is strictly FIFO — big-then-small on one connection
        # can NEVER ack small first through a barriered sum path — so a
        # single overtake proves concurrency.  Several rounds with a
        # >=1 bar stays robust on a loaded few-core box where the other
        # reducer doesn't always win the race for a core (the 2-of-3
        # bar flaked under full-suite load).
        order2, key_big, key_small = self._ack_order(2, monkeypatch, rounds=6)
        overtakes = sum(1 for k in order2 if k == key_small)
        assert overtakes >= 1, (
            f"keys on different stripes never overtook: {order2} — a "
            "barrier is serializing the sum path"
        )
