"""A steady round of the PS hop touches no host buffer the process did not
already hold (ISSUE 34): the worker's pull target, the frames both sides
receive into, the server's pull reply; and a step leaves nothing for the
cycle collector.  Counts, identities and orders of events — no clock."""

import gc
import itertools
import os
import socket
import struct
import subprocess
import sys
import textwrap
import threading
import time
import weakref

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.common.types import DataType, RequestType, get_command_type
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.comm.transport import (
    POOL_MIN_BYTES,
    Frame,
    FramePool,
    Message,
    Op,
    close_socket,
    connect,
    decode_fused_push,
    encode_fused_push,
    recv_message,
    release_frame,
    send_message,
)
from byteps_tpu.core.telemetry import counters
from byteps_tpu.server.server import PSServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PART = POOL_MIN_BYTES  # one partition: the smallest frame a pool serves
N = PART // 4  # its f32 elements
CMD = get_command_type(RequestType.DEFAULT_PUSH_PULL, int(DataType.FLOAT32))
SITES = ("pull_target", "frame", "reply")


def site_counts() -> dict:
    """{(kind, site): count} of the two host-buffer counters."""
    labeled = counters().snapshot_labeled()
    return {
        (kind, site): labeled.get(f"host_buffers_{kind}", {}).get((("site", site),), 0)
        for kind in ("fresh", "reused") for site in SITES
    }


def grown(before: dict) -> dict:
    return {k: v - before[k] for k, v in site_counts().items() if v != before[k]}


@pytest.fixture
def cluster(monkeypatch):
    """1 worker / 1 server in-process; a partition is one pooled frame."""
    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", str(PART))
    srv = PSServer(Config.from_env())
    threading.Thread(target=srv.start, daemon=True).start()
    yield srv
    srv.stop()
    sched.stop()


@pytest.fixture
def bare_server():
    """A server with no scheduler, for a test that speaks the wire itself."""
    srv = PSServer(Config(num_worker=1, num_server=1))
    srv.start(register=False)
    yield srv
    srv.stop()


def _send_frame(sock, fill: int, n: int = PART, op=Op.PUSH) -> None:
    send_message(sock, Message(op, key=1, seq=fill, payload=bytes([fill]) * n))


# --- the frame pool ---------------------------------------------------------


class TestFramePool:
    def test_a_released_frame_is_the_next_one_taken_and_counted(self):
        a, b = socket.socketpair()
        pool = FramePool()
        before = site_counts()
        _send_frame(a, 1)
        first = recv_message(b, pool).payload
        assert isinstance(first, Frame) and bytes(first) == b"\x01" * PART
        assert release_frame(first) is True
        assert release_frame(first) is False  # exactly once
        _send_frame(a, 2)
        second = recv_message(b, pool).payload
        assert second is first and bytes(second) == b"\x02" * PART
        assert grown(before) == {("fresh", "frame"): 1, ("reused", "frame"): 1}
        a.close(), b.close()

    def test_small_blocks_and_poolless_receives_stay_plain(self):
        a, b = socket.socketpair()
        pool = FramePool()
        before = site_counts()
        _send_frame(a, 3, n=PART - 1)
        small = recv_message(b, pool).payload
        _send_frame(a, 4)
        plain = recv_message(b).payload
        for payload in (small, plain):
            assert type(payload) is bytearray and release_frame(payload) is False
        assert release_frame(b"bytes") is False
        assert grown(before) == {}
        a.close(), b.close()

    @pytest.mark.parametrize("holder", ["stored_snapshot", "fused_member"])
    def test_a_held_frame_never_changes_and_returns_once(self, holder):
        """What holds a payload keeps it through any number of later frames
        of its size; the holder's release is the only way back."""
        a, b = socket.socketpair()
        pool = FramePool()
        if holder == "fused_member":
            body = encode_fused_push([(7, CMD, 1, b"\x05" * (PART // 2)),
                                      (8, CMD, 1, b"\x06" * (PART // 2))])
            send_message(a, Message(Op.FUSED, key=7, seq=1, payload=body))
            frame = recv_message(b, pool).payload
            held = decode_fused_push(frame)  # the server's members
            want = [bytes(m[3]) for m in held]
            assert release_frame(frame)  # consumed: every member is a copy
            size = len(body)
        else:
            _send_frame(a, 5)
            frame = held = recv_message(b, pool).payload  # kept as it is
            want, size = b"\x05" * PART, PART
        later = []
        for fill in range(10, 14):
            _send_frame(a, fill, n=size)
            later.append(recv_message(b, pool).payload)
            assert bytes(later[-1]) == bytes([fill]) * size
        if holder == "fused_member":
            assert later[0] is frame  # the buffer went round ...
            assert [bytes(m[3]) for m in held] == want  # ... the members did not
        else:
            assert all(f is not held for f in later)
            assert bytes(held) == want
            assert release_frame(held) is True and release_frame(held) is False
        a.close(), b.close()

    def test_a_parked_push_keeps_its_frame_until_it_is_summed(self, bare_server, monkeypatch):
        """The server parks a push for a key whose state is on its way
        (resharding): the message holds its frame through the pushes that
        follow on the same connection, and gives it back when it is summed."""
        srv = bare_server
        given = []
        real_give = FramePool.give

        def spy(pool, frame):
            ok = real_give(pool, frame)
            given.append((id(frame), ok))
            return ok

        monkeypatch.setattr(FramePool, "give", spy)
        monkeypatch.setattr(srv, "_should_park", lambda key: key == 2)
        w = connect(srv.host, srv.port)
        init = struct.pack("!QI", N, int(DataType.FLOAT32))
        send_message(w, Message(Op.INIT, key=1, seq=1, flags=1, payload=init))
        assert recv_message(w).op == Op.INIT
        parked_bytes = np.full(N, 2.0, np.float32).tobytes()
        send_message(w, Message(Op.PUSH, key=2, seq=2, flags=1, cmd=CMD, version=1,
                                payload=parked_bytes))
        for version in (1, 2, 3):  # the frames that follow it, same size
            send_message(w, Message(Op.PUSH, key=1, seq=10 + version, flags=1, cmd=CMD,
                                    version=version,
                                    payload=np.full(N, version, np.float32).tobytes()))
            assert recv_message(w).op == Op.PUSH
        deadline = time.monotonic() + 10
        while 2 not in srv._awaiting and time.monotonic() < deadline:
            time.sleep(0.01)
        (_t, msg, conn, lock), = srv._awaiting.pop(2)
        assert isinstance(msg.payload, Frame) and bytes(msg.payload) == parked_bytes
        assert id(msg.payload) not in [i for i, _ in given]
        # the state lands (here: an INIT), the parked push is served
        send_message(w, Message(Op.INIT, key=2, seq=3, flags=1, payload=init))
        assert recv_message(w).op == Op.INIT
        srv._enqueue(msg, conn, lock)
        assert recv_message(w).op == Op.PUSH
        assert given.count((id(msg.payload), True)) == 1
        assert release_frame(msg.payload) is False
        np.testing.assert_array_equal(srv._keys[2].store, np.full(N, 2.0, np.float32))
        close_socket(w)


# --- the server's reply -----------------------------------------------------


class TestReplyIsTheStore:
    def _round(self, w, version: int, value: float) -> None:
        send_message(w, Message(Op.PUSH, key=1, seq=100 + version, flags=1, cmd=CMD,
                                version=version,
                                payload=np.full(N, value, np.float32).tobytes()))
        assert recv_message(w).op == Op.PUSH

    def test_a_reply_held_through_later_rounds_carries_its_own(self, bare_server):
        """A reply that has not left yet (a writer queue, a slow socket) views
        the round it was made for, however many pushes arrive meanwhile: the
        next round never sums into a buffer that is out on loan."""
        srv = bare_server
        w = connect(srv.host, srv.port)
        send_message(w, Message(Op.INIT, key=1, seq=1, flags=1,
                                payload=struct.pack("!QI", N, int(DataType.FLOAT32))))
        assert recv_message(w).op == Op.INIT
        self._round(w, 1, 1.0)
        ks = srv._keys[1]
        before = site_counts()
        with ks.lock:
            held = ks.wire_payload(False, lend=True)  # round 1's reply, unsent
        assert isinstance(held, memoryview) and held.obj is ks.store
        self._round(w, 2, 2.0)  # sums into the other buffer
        self._round(w, 3, 3.0)  # would sum into round 1's: it is lent
        np.testing.assert_array_equal(np.frombuffer(held, np.float32), 1.0)
        np.testing.assert_array_equal(ks.store, 3.0)
        assert grown(before) == {("reused", "reply"): 1, ("fresh", "reply"): 1,
                                 ("reused", "frame"): 2}
        ks.give_back(held)  # its buffer was retired: nothing to count down
        assert (ks.lent, ks.lent_accum) == (0, 0)
        # and a reply that IS sent costs no buffer, round after round
        before = site_counts()
        for version in (4, 5, 6):
            self._round(w, version, float(version))
            send_message(w, Message(Op.PULL, key=1, seq=200 + version, cmd=CMD,
                                    version=version))
            reply = recv_message(w)
            np.testing.assert_array_equal(np.frombuffer(reply.payload, np.float32), version)
        assert grown(before) == {("reused", "reply"): 3, ("reused", "frame"): 3}
        # the worker has its reply before the engine thread is back from the send
        deadline = time.monotonic() + 5
        while ks.lent and time.monotonic() < deadline:
            time.sleep(0.005)
        assert (ks.lent, ks.lent_accum) == (0, 0)
        close_socket(w)

    def test_a_store_that_changes_in_place_is_copied(self, bare_server):
        """Async mode sums every push into the store itself: its reply is a
        snapshot, as before."""
        srv = bare_server
        w = connect(srv.host, srv.port)
        init = struct.pack("!QI", N, int(DataType.FLOAT32)) + struct.pack("!Bi", 1, -1)
        send_message(w, Message(Op.INIT, key=1, seq=1, flags=1, payload=init))
        assert recv_message(w).op == Op.INIT
        self._round(w, 1, 1.0)
        ks = srv._keys[1]
        assert ks.async_mode
        with ks.lock:
            held = ks.wire_payload(False, async_mode=True, lend=True)
        assert isinstance(held, bytes)
        self._round(w, 2, 2.0)
        np.testing.assert_array_equal(np.frombuffer(held, np.float32), 1.0)
        np.testing.assert_array_equal(ks.store, 3.0)
        close_socket(w)


# --- the worker's pull target -----------------------------------------------


def _leaf(value: float, parts: int = 3) -> np.ndarray:
    return np.full(parts * N, value, np.float32)


class TestPullTarget:
    @pytest.mark.parametrize("caller", ["jax", "numpy", "jax_async_mode"])
    def test_two_rounds_back_to_back_give_each_its_own_sums(self, cluster, caller):
        """One result buffer a tensor, for a job whose result the engine
        consumes; a numpy caller owns what it is handed.  Round 1's result
        is still round 1's after round 2 went through the same bytes."""
        import jax.numpy as jnp

        import byteps_tpu as bps
        from byteps_tpu.common.registry import get_registry

        bps.init()
        try:
            name = f"target.{caller}"
            if caller == "jax_async_mode":
                bps.declare_tensor(name, byteps_async="1")
            wrap = (lambda x: x) if caller == "numpy" else jnp.asarray
            before = site_counts()
            h1 = bps.push_pull_async(wrap(_leaf(1.0)), name=name, average=False)
            h2 = bps.push_pull_async(wrap(_leaf(2.0)), name=name, average=False)
            r1, r2 = bps.synchronize(h1), bps.synchronize(h2)
            r3 = bps.push_pull(wrap(_leaf(4.0)), name=name, average=False)
            # async mode: the store is a running sum of what was pushed
            want = (1.0, 3.0, 7.0) if caller == "jax_async_mode" else (1.0, 2.0, 4.0)
            for got, value in zip((r1, r2, r3), want):
                np.testing.assert_array_equal(np.asarray(got), value)
            ctx = get_registry().declare(name)
            got = grown(before)
            if caller == "numpy":
                assert ctx.pull_target is None
                assert not np.shares_memory(r1, r2) and not np.shares_memory(r2, r3)
                assert got[("fresh", "pull_target")] == 3
                assert ("reused", "pull_target") not in got
            else:
                # h2 was submitted while h1 held the tensor's buffer: it got
                # one of its own; round 3 found the tensor's free again
                assert ctx.pull_target is not None and not ctx.pull_target_lent
                assert got[("fresh", "pull_target")] == 2
                assert got[("reused", "pull_target")] == 1
        finally:
            bps.shutdown()

    def test_the_target_goes_back_only_after_every_put_is_complete(self, cluster, monkeypatch):
        """Order of events, not a clock: when a job returns the tensor's
        buffer, ``block_until_ready`` has returned for every partition
        COPYH2D put on the device from it."""
        import jax
        import jax.numpy as jnp

        import byteps_tpu as bps
        from byteps_tpu.core.engine import PipelineEngine

        events = []
        real_block = jax.block_until_ready
        real_h2d = PipelineEngine._h2d
        real_return = PipelineEngine._return_target

        def h2d(self, buf, average, where=None):
            part = real_h2d(self, buf, average, where)
            events.append(("put", id(part)))
            return part

        def block(tree):
            out = real_block(tree)
            events.extend(("ready", id(x)) for x in jax.tree_util.tree_leaves(tree))
            return out

        def returned(self, job, reusable):
            if job.holds_target:
                events.append(("returned", reusable))
            return real_return(self, job, reusable)

        monkeypatch.setattr(PipelineEngine, "_h2d", h2d)
        monkeypatch.setattr(PipelineEngine, "_return_target", returned)
        monkeypatch.setattr(jax, "block_until_ready", block)
        bps.init()
        try:
            for value in (1.0, 2.0):
                out = bps.push_pull(jnp.asarray(_leaf(value)), name="target.order",
                                    average=False)
                np.testing.assert_array_equal(np.asarray(out), value)
        finally:
            bps.shutdown()
        rounds = [i for i, e in enumerate(events) if e == ("returned", True)]
        assert len(rounds) == 2
        start = 0
        for end in rounds:
            puts = {x for kind, x in events[start:end] if kind == "put"}
            ready = {x for kind, x in events[start:end] if kind == "ready"}
            assert len(puts) == 3 and puts <= ready
            start = end + 1

    def test_a_failed_round_does_not_hand_its_buffer_on(self, cluster):
        """A late reply may still land in an abandoned round's sinks: the
        next round gets a buffer of its own."""
        import jax.numpy as jnp

        import byteps_tpu as bps
        from byteps_tpu.common.registry import get_registry
        from byteps_tpu.core.state import get_state

        bps.init()
        try:
            engine = get_state().engine
            out = bps.push_pull(jnp.asarray(_leaf(1.0)), name="target.fail", average=False)
            np.testing.assert_array_equal(np.asarray(out), 1.0)
            ctx = get_registry().declare("target.fail")
            first = ctx.pull_target
            assert first is not None
            job = type("J", (), {"holds_target": True, "ctx": ctx})()
            ctx.pull_target_lent = True  # as a job in flight holds it
            engine._return_target(job, reusable=False)
            assert ctx.pull_target is None and not ctx.pull_target_lent
            out = bps.push_pull(jnp.asarray(_leaf(2.0)), name="target.fail", average=False)
            np.testing.assert_array_equal(np.asarray(out), 2.0)
            assert ctx.pull_target is not None and ctx.pull_target is not first
        finally:
            bps.shutdown()


_TWO_WORKER_SCRIPT = textwrap.dedent(
    """
    import os, sys
    import numpy as np
    import jax.numpy as jnp
    import byteps_tpu as bps
    from byteps_tpu.core.telemetry import counters

    r = int(os.environ["BYTEPS_GLOBAL_RANK"])
    n = int(os.environ["LEAF_ELEMENTS"])
    bps.init()
    outs = []
    for rnd in (1, 2, 3):
        x = jnp.full((n,), float(rnd * (r + 1)), jnp.float32)
        outs.append(bps.push_pull(x, name="two.g", average=False))
    # every round its own sum, read AFTER the later rounds went through
    # the same host buffer: worker 0 pushed rnd, worker 1 2*rnd
    for rnd, out in zip((1, 2, 3), outs):
        np.testing.assert_array_equal(np.asarray(out), 3.0 * rnd)
    labeled = counters().snapshot_labeled()
    site = (("site", "pull_target"),)
    assert labeled["host_buffers_fresh"][site] == 1, labeled
    assert labeled["host_buffers_reused"][site] == 2, labeled
    bps.shutdown()
    print(f"WORKER_{r}_OK")
    """
)


def test_two_workers_two_rounds_through_one_buffer_each(tmp_path):
    sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
    sched.start()
    env = {
        **os.environ,
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(sched.port),
        "DMLC_NUM_WORKER": "2", "DMLC_NUM_SERVER": "1", "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO, "BYTEPS_PARTITION_BYTES": str(PART),
        "LEAF_ELEMENTS": str(3 * N),
    }
    scfg = Config.from_env()
    scfg.num_worker, scfg.num_server = 2, 1
    scfg.ps_root_uri, scfg.ps_root_port = "127.0.0.1", sched.port
    srv = PSServer(scfg)
    threading.Thread(target=srv.start, daemon=True).start()
    script = tmp_path / "worker.py"
    script.write_text(_TWO_WORKER_SCRIPT)
    before = site_counts()
    procs = [
        subprocess.Popen([sys.executable, str(script)],
                         env={**env, "BYTEPS_GLOBAL_RANK": str(i)}, cwd=REPO,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(2)
    ]
    outs = [p.communicate(timeout=180)[0] for p in procs]
    # the server's own counters (this process): 2 workers x 3 rounds x 3 keys
    # of replies, every one a view of a store
    server_side = grown(before)
    srv.stop()
    sched.stop()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0 and f"WORKER_{i}_OK" in out, f"worker {i}:\n{out}"
    assert server_side[("reused", "reply")] == 18
    assert ("fresh", "reply") not in server_side
    frames = server_side.get(("fresh", "frame"), 0) + server_side[("reused", "frame")]
    assert frames == 18 and server_side[("reused", "frame")] >= 9


# --- a steady step ----------------------------------------------------------


def _hybrid(leaf_parts: int = 2):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from byteps_tpu.parallel.hybrid import HybridDataParallel

    rng = np.random.default_rng(34)
    d = leaf_parts * N // 64
    params = {"w1": rng.normal(0, 0.1, (64, d)).astype(np.float32),
              "w2": rng.normal(0, 0.1, (d, 8)).astype(np.float32)}
    batch = (rng.normal(size=(8, 64)).astype(np.float32),
             rng.normal(size=(8, 8)).astype(np.float32))
    hdp = HybridDataParallel(
        lambda p, b: jnp.mean((jnp.tanh(b[0] @ p["w1"]) @ p["w2"] - b[1]) ** 2),
        params, optax.sgd(0.05), mesh=Mesh(np.array(jax.devices()[:1]), ("dp",)),
        batch_spec=(P("dp"), P("dp")))
    return hdp, batch


def warm_steps(monkeypatch, hdp, batch, steps: int = 3, depth: int = 2) -> list:
    """``steps`` warm-up steps that leave every frame pool as deep as a step
    can ever need it.  A pool is as deep as the frames its connection held at
    once, and whether the server still holds partition 0's frame when
    partition 1's arrives is the threads' timing: so here the server lets go
    of no frame until ``depth`` are out together (w1's two partitions; w2 is
    under the pooled size).  Left to timing, the first step to overlap the
    two, warm-up or counted, would make the pool's second frame."""
    from byteps_tpu.server import server as server_mod

    take, release = FramePool.take, server_mod.release_frame
    taken, reached = itertools.count(1), threading.Event()

    def counted_take(pool, n):
        if next(taken) >= depth:
            reached.set()
        return take(pool, n)

    def held_release(payload):
        if isinstance(payload, Frame):
            reached.wait(30)
        return release(payload)

    with monkeypatch.context() as m:
        m.setattr(FramePool, "take", counted_take)
        m.setattr(server_mod, "release_frame", held_release)
        return [hdp.step(batch) for _ in range(steps)]


#: five steady steps' growth: w1 is two partitions of PART bytes, w2 one
#: small one (no pooled frame); nothing fresh
STEADY = {("reused", "pull_target"): 10, ("reused", "frame"): 10,
          ("reused", "reply"): 15}


def test_a_steady_step_makes_no_fresh_buffer(cluster, monkeypatch):
    """Three warm-up rounds, then five in which every pull target, every
    received frame and every reply is memory the process already held."""
    import byteps_tpu as bps

    bps.init()
    try:
        hdp, batch = _hybrid()
        start = site_counts()
        losses = warm_steps(monkeypatch, hdp, batch)
        before = site_counts()
        losses += [hdp.step(batch) for _ in range(5)]
        got, ever = grown(before), grown(start)
    finally:
        bps.shutdown()
    assert losses[-1] < losses[0]
    assert got == STEADY
    assert ever[("fresh", "frame")] == 2, ever


def test_a_step_leaves_nothing_for_the_collector(cluster, monkeypatch):
    """With the collector off, a step's jobs and gradients die by reference
    count when ``step`` returns, and a collection then finds nothing of the
    engine's or the client's making."""
    import byteps_tpu as bps
    from byteps_tpu.core import engine as engine_mod

    bps.init()
    try:
        hdp, batch = _hybrid()
        for _ in range(3):
            hdp.step(batch)
        seen = []
        real_submit = engine_mod.PipelineEngine.submit
        real_job = engine_mod._Job.__init__

        def submit(self, name, tensor, *a, **k):
            seen.append(weakref.ref(tensor))
            return real_submit(self, name, tensor, *a, **k)

        def job_init(self, *a, **k):
            real_job(self, *a, **k)
            seen.append(weakref.ref(self))

        monkeypatch.setattr(engine_mod.PipelineEngine, "submit", submit)
        monkeypatch.setattr(engine_mod._Job, "__init__", job_init)
        gc.collect()
        gc.disable()
        try:
            for _ in range(3):
                hdp.step(batch)
            # the stage and receive threads name their last task until
            # they loop: give them the moment that takes, not a collection
            deadline = time.monotonic() + 5
            while any(r() is not None for r in seen) and time.monotonic() < deadline:
                time.sleep(0.01)
            alive = [type(r()).__name__ for r in seen if r() is not None]
            gc.set_debug(gc.DEBUG_SAVEALL)
            found = gc.collect()
            ours = [type(o).__qualname__ for o in gc.garbage
                    if type(o).__module__.startswith("byteps_tpu")]
        finally:
            gc.set_debug(0)
            gc.garbage.clear()
            gc.enable()
    finally:
        bps.shutdown()
    assert len(seen) == 3 * 2 * 2  # steps x leaves x (gradient, job)
    assert alive == []
    assert ours == [] and found == 0
