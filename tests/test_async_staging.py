"""Async device↔host staging: the engine must overlap per-partition D2H
with PUSH (the reference's COPYD2H stream + push pipelining,
core_loops.cc:378-443, 650-753 — SURVEY §7's 'riskiest performance item'),
and ``push_pull_async`` must return without materializing the device
tensor on the caller thread."""

import functools
import sys
import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.server.server import PSServer


@pytest.fixture
def small_partition_cluster(monkeypatch):
    """Fake cluster with tiny partitions so one tensor becomes many keys."""
    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "4096")  # 1024 f32 per part
    srv = PSServer(Config.from_env())
    threading.Thread(target=srv.start, daemon=True).start()
    yield
    srv.stop()
    sched.stop()


class TestStagingOverlap:
    def test_push_starts_before_last_d2h_ends(self, small_partition_cluster):
        """With N partitions flowing COPYD2H→PUSH on separate stage threads,
        the first PUSH must hit the wire before the LAST partition finishes
        its device→host copy — that is the pipelining the priority
        scheduler exists for."""
        import jax.numpy as jnp

        import byteps_tpu as bps
        from byteps_tpu.common.types import QueueType
        from byteps_tpu.core.state import get_state

        bps.init()
        engine = get_state().engine
        events = []
        ev_lock = threading.Lock()

        orig_proceed = engine._proceed
        orig_push = engine.client.push

        def rec_proceed(task):
            stage = task.queue_list[0] if task.queue_list else None
            if stage == QueueType.COPYD2H:
                with ev_lock:
                    events.append(("d2h_done", task.key, time.perf_counter()))
            orig_proceed(task)

        def rec_push(key, payload, dtype_id, version, cb, **kw):
            with ev_lock:
                events.append(("push", key, time.perf_counter()))
            return orig_push(key, payload, dtype_id, version, cb, **kw)

        engine._proceed = rec_proceed
        engine.client.push = rec_push
        overlapped = False
        # COPYD2H now only collects copies that submit started: on the CPU
        # it never blocks, so it would run through all its tasks inside one
        # 5 ms switch interval before the woken PUSH thread got the GIL
        switch_interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)
        try:
            # A loaded box can starve the stage threads long enough that
            # one round drains every D2H before the first push fires —
            # retry the measurement; genuinely serialized pipelining
            # fails all rounds.
            for _attempt in range(3):
                with ev_lock:
                    events.clear()
                x = jnp.arange(256 * 1024, dtype=jnp.float32)  # 256 partitions
                out = bps.push_pull(x, name="overlap.x", average=False)
                np.testing.assert_allclose(
                    np.asarray(out), np.arange(256 * 1024, dtype=np.float32)
                )
                with ev_lock:
                    d2h = [t for kind, _, t in events if kind == "d2h_done"]
                    push = [t for kind, _, t in events if kind == "push"]
                assert len(d2h) == 256 and len(push) == 256
                if min(push) < max(d2h):
                    overlapped = True
                    break
        finally:
            sys.setswitchinterval(switch_interval)
            engine._proceed = orig_proceed
            engine.client.push = orig_push
            bps.shutdown()

        assert overlapped, (
            "no overlap: every push happened after all D2H copies finished"
            " in all 3 rounds"
        )

    def test_async_returns_before_materialization(self, small_partition_cluster):
        """push_pull_async on a jax array whose producing computation is
        still in flight must return without waiting for it — the D2H wait
        happens on the engine's stage thread, not the caller's.  An order
        of events, not a clock: the array is not ready when the call is
        made and STILL not ready when it returns."""
        import jax
        import jax.numpy as jnp

        import byteps_tpu as bps

        bps.init()

        @functools.partial(jax.jit, static_argnums=1)
        def heavy(a, rounds):
            for _ in range(rounds):
                a = a @ a / jnp.linalg.norm(a)
            return a.reshape(-1)[: 8 * 1024]

        a = jnp.eye(1500, dtype=jnp.float32) + 0.01
        seen = []
        try:
            # a box fast or loaded enough to finish the compute inside the
            # call proves nothing either way: give it more to compute
            for attempt, rounds in enumerate((30, 120, 480)):
                jax.block_until_ready(heavy(a, rounds))  # compiled, not timed
                x = heavy(a * (1.0001 + attempt), rounds)  # new input: runs again
                in_flight_at_call = not x.is_ready()
                h = bps.push_pull_async(x, name=f"overlap.async.{attempt}", average=False)
                in_flight_at_return = not x.is_ready()
                out = bps.synchronize(h)
                assert out.shape == (8 * 1024,)
                np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
                seen.append((in_flight_at_call, in_flight_at_return))
                if in_flight_at_call and in_flight_at_return:
                    break
        finally:
            bps.shutdown()
        assert (True, True) in seen, (
            "push_pull_async never returned while its tensor was still being "
            f"computed: (in flight at the call, at its return) = {seen}"
        )

    def test_numpy_path_still_identity(self, small_partition_cluster):
        import byteps_tpu as bps

        bps.init()
        x = np.linspace(-1, 1, 5000).astype(np.float32)
        out = bps.push_pull(x, name="overlap.np", average=False)
        np.testing.assert_allclose(np.asarray(out), x)
        bps.shutdown()


def _placed(x: np.ndarray, placement: str):
    """``x`` as the engine may be handed it: on one device, whole on every
    device of the forced CPU mesh (a dp step's gradient), or cut over them
    along its first axis (a tensor-parallel leaf)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    if placement == "numpy":
        return x
    if placement == "single":
        return jax.device_put(x, jax.devices()[0])
    mesh = Mesh(np.array(jax.devices()), ("d",))
    spec = PartitionSpec() if placement == "replicated" else PartitionSpec("d")
    return jax.device_put(x, NamedSharding(mesh, spec))


class TestCopyStartsAtSubmit:
    """COPYD2H is a wait: where one chip holds the whole tensor, ``submit``
    starts every partition's copy from that chip (``_start_d2h``) and the
    stage thread collects — counts and equality only, on the CPU."""

    @pytest.mark.parametrize("shape", [(8, 64), (8, 375)], ids=["one_part", "three_parts"])
    @pytest.mark.parametrize("placement", ["single", "replicated", "sharded", "numpy"])
    def test_parts_copied_from_one_chip(self, small_partition_cluster, monkeypatch,
                                        placement, shape):
        import jax

        import byteps_tpu as bps
        from byteps_tpu.core.engine import PipelineEngine
        from byteps_tpu.core.telemetry import counters

        assert len(jax.devices()) > 1  # conftest's forced mesh
        x = np.random.default_rng(32).standard_normal(shape).astype(np.float32)
        n_parts = -(-x.nbytes // 4096)
        assert n_parts == {(8, 64): 1, (8, 375): 3}[shape]
        started = []  # (the dict handed to the job, its parts' shardings)
        real = PipelineEngine._start_d2h

        def spy(leaf, partitions):
            parts = real(leaf, partitions)
            started.append((parts, [part.sharding for part in parts.values()]))
            return parts

        monkeypatch.setattr(PipelineEngine, "_start_d2h", staticmethod(spy))
        names = ("d2h_bytes", "d2h_prefetched_parts", "journal_ref_bytes",
                 "journal_copy_bytes", "h2d_bytes")
        bps.init()
        try:
            before = {k: counters().get(k) for k in names}
            out = bps.push_pull(_placed(x, placement), average=True,
                                name=f"submit_copy.{placement}.{n_parts}")
            grew = {k: counters().get(k) - before[k] for k in names}
        finally:
            bps.shutdown()
        # one worker: the sum is the tensor, and x / 1 == x bit for bit
        assert np.shape(out) == shape
        np.testing.assert_array_equal(np.asarray(out), x)
        on_device = placement != "numpy"
        prefetched = placement in ("single", "replicated")
        assert grew == {
            "d2h_bytes": x.nbytes if on_device else 0,
            "d2h_prefetched_parts": n_parts if prefetched else 0,
            # a jax partition's staging array is referenced, whichever way
            # it reached the host; a numpy one may alias the caller's array
            "journal_ref_bytes": x.nbytes if on_device else 0,
            "journal_copy_bytes": 0 if on_device else x.nbytes,
            "h2d_bytes": x.nbytes if on_device else 0,
        }
        assert len(started) == (1 if prefetched else 0)
        for parts, shardings in started:
            assert len(shardings) == n_parts
            # no program ran on the mesh to read a tensor one chip holds
            assert all(len(sh.device_set) == 1 for sh in shardings), shardings
            assert parts == {}  # each device slice dropped as its task left COPYD2H


class TestPushRoundOrdering:
    def test_concurrent_rounds_stay_ordered_per_key(self, small_partition_cluster):
        """Two in-flight jobs on the SAME name with different priorities:
        the ReadyTable PUSH gate must keep each key's rounds ordered on the
        wire (a higher-priority later round must not overtake an earlier
        round of the same key mid-aggregation)."""
        import byteps_tpu as bps
        from byteps_tpu.core.state import get_state

        bps.init()
        engine = get_state().engine
        sent = []
        lock = threading.Lock()
        orig_push = engine.client.push

        def rec_push(key, payload, dtype_id, version, cb, **kw):
            with lock:
                sent.append((key, version))
            return orig_push(key, payload, dtype_id, version, cb, **kw)

        engine.client.push = rec_push
        try:
            x = np.ones(8 * 1024, dtype=np.float32)  # 8 partitions
            # low-priority round 1, then high-priority round 2 immediately
            h1 = bps.push_pull_async(x, name="rounds.g", average=False, priority=-5)
            h2 = bps.push_pull_async(x * 2, name="rounds.g", average=False, priority=50)
            r1 = bps.synchronize(h1)
            r2 = bps.synchronize(h2)
            np.testing.assert_allclose(np.asarray(r1), 1.0)
            np.testing.assert_allclose(np.asarray(r2), 2.0)
        finally:
            engine.client.push = orig_push
            bps.shutdown()

        per_key = {}
        for key, version in sent:
            per_key.setdefault(key, []).append(version)
        assert per_key, "no pushes recorded"
        for key, versions in per_key.items():
            assert versions == sorted(versions), (
                f"key {key} rounds reordered on the wire: {versions}"
            )


class TestReinitKeyReuse:
    def test_shutdown_init_reuse_name(self, small_partition_cluster):
        """shutdown() then init() with the same tensor name: the registry
        (and its version counters) persist, the new engine's round gate
        must seed from the CURRENT version — regression for a deadlock
        where reused names were never eligible in the fresh PUSH queue."""
        import byteps_tpu as bps

        bps.init()
        x = np.ones(2048, np.float32)
        out = bps.push_pull(x, name="reinit.g", average=False)
        np.testing.assert_allclose(np.asarray(out), 1.0)
        bps.shutdown()

        bps.init()  # fresh engine, same registry
        out2 = bps.push_pull(x * 4, name="reinit.g", average=False)
        np.testing.assert_allclose(np.asarray(out2), 4.0)
        bps.shutdown()
