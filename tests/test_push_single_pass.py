"""One pass over a pushed partition on each side of the wire.

Receiver: the payload of a frame is the buffer it was received into
(``transport.recv_payload``) — no second allocation, whatever the van and
with or without the CRC32C block.  Sender: ``RoundJournal.record`` keeps a
reference where nothing can write the payload's buffer (``bytes``, a
read-only view of a read-only buffer: the engine's staging array of a jax
job) and copies where something can (a numpy job's partition may alias the
caller's array).  ``journal_ref_bytes`` / ``journal_copy_bytes`` say which;
the byte and round bounds count both alike; a replay sends bit for bit what
the first send sent.

CPU only: in-process scheduler + server as ``tests/test_ps.py`` starts
them, and ``tests/test_resync.py``'s chaos schedule for the replay."""

import functools
import gc
import signal
import threading
import tracemalloc
import weakref

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.common.types import DataType, RequestType, get_command_type
from byteps_tpu.comm.journal import RoundJournal
from byteps_tpu.comm.transport import Message, Op, recv_message, send_message
from byteps_tpu.comm.van import get_van
from byteps_tpu.core.telemetry import counters

CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, int(DataType.FLOAT32))
FRAME_BYTES = 4_096_000  # the engine's default partition


def limit(seconds: int):
    """This case's own time limit: a hang fails the case, not the run."""

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            def alarm(_signum, _frame):
                raise TimeoutError(f"{fn.__name__} exceeded {seconds}s")

            timed = threading.current_thread() is threading.main_thread()
            if timed:
                prev = signal.signal(signal.SIGALRM, alarm)
                signal.alarm(seconds)
            try:
                return fn(*args, **kwargs)
            finally:
                if timed:
                    signal.alarm(0)
                    signal.signal(signal.SIGALRM, prev)

        return run

    return wrap


def _journal_counts() -> tuple:
    return counters().get("journal_ref_bytes"), counters().get("journal_copy_bytes")


def _readonly(arr: np.ndarray) -> np.ndarray:
    arr.flags.writeable = False
    return arr


# --- the receiver ----------------------------------------------------------


def _need_shm(van: str) -> None:
    if van == "shm":
        import platform

        if platform.machine() not in ("x86_64", "AMD64", "i686"):
            pytest.skip("shm van requires x86-64 (TSO store ordering)")


@pytest.mark.parametrize("checksum", [False, True], ids=["plain", "crc32c"])
@pytest.mark.parametrize("van", ["tcp", "uds", "shm"])
@limit(60)
def test_received_payload_is_the_receive_buffer(van, checksum):
    """A 4 MB frame comes back equal to what was sent, as a buffer the
    message owns, and receiving it allocates one frame's worth — not the
    two that ``bytearray`` + ``bytes(buf)`` took."""
    _need_shm(van)
    sent = np.random.default_rng(7).integers(
        0, 256, FRAME_BYTES, dtype=np.uint8
    ).tobytes()
    listener, host, port = get_van(van).listen("127.0.0.1")
    accepted = {}

    def accept():
        accepted["conn"] = listener.accept()[0]

    t = threading.Thread(target=accept, daemon=True)
    t.start()
    client = get_van(van).connect(host, port, timeout=15)
    t.join(15)
    server = accepted["conn"]
    try:
        # warm both directions (the shm van's handshake and ring mapping,
        # the native CRC's first load) outside the traced region
        send_message(client, Message(Op.PUSH, key=1, payload=b"warm", checksum=checksum))
        assert recv_message(server).payload == b"warm"
        msg = Message(Op.PUSH, key=5, seq=9, cmd=CMD_F32, version=3,
                      payload=sent, checksum=checksum)
        sender = threading.Thread(target=send_message, args=(client, msg), daemon=True)
        tracemalloc.start()
        try:
            sender.start()
            got = recv_message(server)
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        sender.join(15)
        assert (got.op, got.key, got.seq, got.cmd, got.version) == (Op.PUSH, 5, 9, CMD_F32, 3)
        assert isinstance(got.payload, bytearray)
        assert got.payload == sent
        assert peak < 1.25 * FRAME_BYTES, f"peak {peak} for a {FRAME_BYTES}-byte frame"
        # every reader takes it as it is
        assert np.frombuffer(got.payload, dtype=np.float32).nbytes == FRAME_BYTES
    finally:
        for s in (client, server, listener):
            try:
                s.close()
            except OSError:
                pass


# --- the journal -----------------------------------------------------------


@limit(30)
def test_readonly_view_is_kept_by_reference():
    """No second copy; the counters say so; the exporter outlives the task
    that dropped it, and dies when its round is evicted."""
    j = RoundJournal(max_rounds=2, max_bytes=1 << 20)
    staging = _readonly(np.arange(1000, dtype=np.float32))
    alive = weakref.ref(staging)
    ref0, copy0 = _journal_counts()
    j.record(3, 1, CMD_F32, staging.data.cast("B"))
    ref1, copy1 = _journal_counts()
    assert (ref1 - ref0, copy1 - copy0) == (4000, 0)
    (entry,) = j.entries_after(3, 0)
    assert isinstance(entry.payload, memoryview) and entry.payload.obj is staging
    assert j.stats()["bytes"] == 4000
    wire = staging.tobytes()
    del staging, entry
    gc.collect()
    assert alive() is not None  # the journal's view holds it
    assert bytes(j.entries_after(3, 0)[0].payload) == wire
    j.clear_key(3)
    gc.collect()
    assert alive() is None
    # bytes stay free, as before
    blob = b"x" * 100
    j.record(4, 1, CMD_F32, blob)
    assert j.entries_after(4, 0)[0].payload is blob
    assert _journal_counts() == (ref1 + 100, copy1)


@pytest.mark.parametrize("case", [
    "writable", "readonly-view-of-writable", "readonly-view-of-bytearray",
    "window-of-readonly", "not-bytes-shaped",
])
@limit(30)
def test_anything_that_can_change_or_pins_more_is_copied(case):
    """Overwriting the caller's array after the record leaves the entry
    bit-equal to the first send."""
    j = RoundJournal(max_rounds=2, max_bytes=1 << 20)
    base = np.arange(256, dtype=np.float32)
    if case == "writable":
        view = base.data.cast("B")
    elif case == "readonly-view-of-writable":
        view = _readonly(base[:]).data.cast("B")
    elif case == "readonly-view-of-bytearray":
        backing = bytearray(base.tobytes())
        base = np.frombuffer(backing, dtype=np.float32)
        view = memoryview(backing).toreadonly()
    elif case == "window-of-readonly":
        whole = _readonly(np.arange(4096, dtype=np.float32))
        base = whole[:256]
        view = base.data.cast("B")
    else:
        view = _readonly(base.copy()).data  # format 'f': len != nbytes
    first = bytes(view)
    ref0, copy0 = _journal_counts()
    j.record(8, 1, CMD_F32, view)
    assert _journal_counts() == (ref0, copy0 + len(first))
    if base.flags.writeable:
        base[:] = -1.0  # the caller reuses its array after synchronize
    (entry,) = j.entries_after(8, 0)
    assert type(entry.payload) is bytes and entry.payload == first


@limit(30)
def test_bounds_count_referenced_entries():
    """Eviction by rounds and by bytes lets go of referenced staging."""
    part = 1000  # bytes
    refs = []

    def staged():
        a = _readonly(np.zeros(part, dtype=np.uint8))
        refs.append(weakref.ref(a))
        return a.data

    # depth: the third round of one key evicts the first
    j = RoundJournal(max_rounds=2, max_bytes=1 << 20)
    for v in (1, 2, 3):
        j.record(1, v, CMD_F32, staged())
    gc.collect()
    assert [e.version for e in j.entries_after(1, 0)] == [2, 3]
    assert j.stats() == {"keys": 1, "rounds": 2, "bytes": 2 * part, "evicted": 1}
    assert [r() is not None for r in refs] == [False, True, True]
    # bytes: 2.5 partitions of room hold two, across keys, oldest out first
    del refs[:]
    j = RoundJournal(max_rounds=8, max_bytes=2 * part + part // 2)
    for key in (10, 11, 12, 13):
        j.record(key, 1, CMD_F32, staged())
    gc.collect()
    assert sorted(j.keys()) == [12, 13]
    assert j.stats()["bytes"] == 2 * part and j.stats()["evicted"] == 2
    assert [r() is not None for r in refs] == [False, False, True, True]


# --- through the engine ----------------------------------------------------


@pytest.fixture
def cluster(monkeypatch):
    """Scheduler + one Python server in-process; this process is the worker."""
    from byteps_tpu.comm.rendezvous import Scheduler
    from byteps_tpu.server.server import PSServer

    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    for k, v in {
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(sched.port),
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        "BYTEPS_FORCE_DISTRIBUTED": "1",
        "BYTEPS_PARTITION_BYTES": "4096",
    }.items():
        monkeypatch.setenv(k, v)
    started = []

    def start_server():
        srv = PSServer(Config.from_env())
        threading.Thread(target=srv.start, daemon=True).start()
        started.append(srv)
        return srv

    yield start_server
    for srv in started:
        srv.stop()
    sched.stop()


@pytest.mark.parametrize("jax_input", [True, False], ids=["jax", "numpy"])
@limit(60)
def test_push_pull_of_three_partitions(cluster, jax_input):
    """A jax tensor's staged partitions are referenced, byte for byte of
    the tensor; a numpy tensor's, which alias the caller's array, copied."""
    import jax.numpy as jnp

    import byteps_tpu as bps

    cluster()
    bps.init()
    try:
        x = np.random.default_rng(1).standard_normal(2 * 1024 + 500).astype(np.float32)
        ref0, copy0 = _journal_counts()
        out = bps.push_pull(jnp.asarray(x) if jax_input else x,
                            name=f"single_pass.three.{jax_input}", average=False)
        np.testing.assert_array_equal(np.asarray(out), x)
        ref1, copy1 = _journal_counts()
        grew = (x.nbytes, 0) if jax_input else (0, x.nbytes)
        assert (ref1 - ref0, copy1 - copy0) == grew
        from byteps_tpu.comm.journal import get_journal

        assert get_journal().stats()["bytes"] == x.nbytes
        snap = bps.get_robustness_counters()
        assert snap["journal_ref_bytes" if jax_input else "journal_copy_bytes"] >= x.nbytes
    finally:
        bps.shutdown()


@limit(90)
def test_replay_after_dropped_connection_sends_referenced_bytes(cluster, monkeypatch):
    """``tests/test_resync.py``'s one-sided schedule: every PUSH frame is
    dropped until one push's whole retry budget is spent, the worker heals
    in place and replays the round from the journal — whose entry is a
    reference to the jax staging array, not a copy.  One worker, no
    average: what comes back is what the replay sent, bit for bit."""
    import jax.numpy as jnp

    from byteps_tpu.comm.chaos import reset_fault_budget

    for k, v in {
        "BYTEPS_VAN": "chaos:tcp",
        "BYTEPS_CHAOS_SEED": "5",
        "BYTEPS_CHAOS_DROP": "1.0",
        "BYTEPS_CHAOS_OPS": str(int(Op.PUSH)),
        "BYTEPS_CHAOS_FAULT_BUDGET": "3",  # first attempt + 2 retries
        "BYTEPS_HEARTBEAT_INTERVAL": "0.2",
        "BYTEPS_RPC_DEADLINE_S": "0.3",
        "BYTEPS_RPC_RETRIES": "2",
        "BYTEPS_RPC_BACKOFF_S": "0.05",
        "BYTEPS_INIT_DEADLINE_S": "1.0",
        "BYTEPS_CONNECT_RETRY_S": "0.2",
    }.items():
        monkeypatch.setenv(k, v)
    counters().reset()
    reset_fault_budget()

    import byteps_tpu as bps

    cluster()
    try:
        bps.init()
        rng = np.random.default_rng(0)
        for _ in range(3):
            x = rng.standard_normal(777).astype(np.float32)  # one partition: one push's budget
            out = bps.push_pull(jnp.asarray(x), name="single_pass.heal", average=False)
            # a double-summed replay would return 2x, a torn one garbage
            np.testing.assert_array_equal(np.asarray(out), x)
        snap = bps.get_robustness_counters()
        assert snap.get("chaos_drop", 0) == 3, snap
        assert snap.get("resync_replayed_rounds", 0) == 1, snap
        assert snap.get("rpc_giveup", 0) == 0 and snap.get("degraded_jobs", 0) == 0, snap
        assert snap.get("journal_ref_bytes", 0) == 3 * x.nbytes, snap
        assert snap.get("journal_copy_bytes", 0) == 0, snap
    finally:
        bps.shutdown()
        reset_fault_budget()
