"""The PUSH stage's two senders (ISSUE 39): over a split TCP link two
threads serve the ONE PUSH queue, each with a push lane of its own; every
other link keeps one sender.  Counts, identities, bitwise results and each
thread's own account — in-process scheduler + server over loopback, no
clock but each case's own time limit."""

import json
import os
import threading
import time

import numpy as np
import pytest
from test_hop_account import hist
from test_lane_split import _Cluster, _link, lane_bytes, lane_growth, within

from byteps_tpu.common.types import QueueType
from byteps_tpu.comm import ps_client
from byteps_tpu.comm.transport import Op, close_socket
from byteps_tpu.core.telemetry import counters, metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SECOND = "push_second_sender_parts"
SMALL = 4096  # bytes a partition where only order and sums matter
BIG = 1 << 20  # where PUSH has to be the slowest stage, so both senders stay busy


def senders() -> list:
    return sorted(t.name for t in threading.enumerate() if t.name.startswith("bps-PUSH-"))


def second() -> int:
    return counters().snapshot().get(SECOND, 0)


# --- (a) the same sums as one sender, and a key's rounds in order --------------

TENSORS = 4  # x 3 partitions = 12 keys
ROUNDS = 50


def _train(monkeypatch, n_senders: int) -> tuple:
    """``ROUNDS`` rounds of four three-partition tensors, two rounds in
    flight, priorities mixed by round; one tensor under a server-side
    momentum rule, so what comes back depends on the order of its rounds.
    Numpy tensors: a numpy caller keeps the buffer it is handed, where a
    jax round's pull target is lent again to the round after it.  Returns
    the results, the PUSH frames as the server took them in, the second
    sender's count and the senders' names."""
    import byteps_tpu as bps

    monkeypatch.setattr(ps_client, "PUSH_SENDERS", n_senders)
    rng = np.random.default_rng(39)
    with _Cluster(monkeypatch, BYTEPS_PARTITION_BYTES=str(SMALL)) as cluster:
        arrived, enqueue = [], cluster.srv._enqueue

        def recording(msg, *args, **kwargs):
            if msg.op == Op.PUSH:
                arrived.append((msg.key, msg.version))
            return enqueue(msg, *args, **kwargs)

        cluster.srv._enqueue = recording
        bps.init()
        bps.declare_tensor("senders.t0", byteps_server_opt="momentum",
                           byteps_server_opt_hp={"lr": 0.01})
        before, results, in_flight = second(), [], []
        for r in range(ROUNDS):
            xs = [rng.standard_normal(3 * SMALL // 4).astype(np.float32) for _ in range(TENSORS)]
            handles = [
                bps.push_pull_async(x, name=f"senders.t{i}", average=False,
                                    priority=(7 * i + 3 * r) % 5 - 2)
                for i, x in enumerate(xs)
            ]
            results += [np.array(bps.synchronize(h)) for h in in_flight]
            in_flight = handles
        results += [np.array(bps.synchronize(h)) for h in in_flight]
        grown, names = second() - before, senders()
        bps.shutdown()
    return results, arrived, grown, names


@within(240)
def test_two_senders_sum_what_one_does_and_keep_a_keys_rounds_in_order(monkeypatch):
    two, arrived, by_second, names = _train(monkeypatch, 2)
    assert names == ["bps-PUSH-0", "bps-PUSH-1"]
    assert 0 < by_second < ROUNDS * TENSORS * 3  # both senders served
    one, _, none_by_second, one_name = _train(monkeypatch, 1)
    assert one_name == ["bps-PUSH-0"] and none_by_second == 0
    assert len(two) == len(one) == ROUNDS * TENSORS
    for got, want in zip(two, one):
        np.testing.assert_array_equal(got, want)
    # the server took every push in, and never a key's round n + 1 before n
    by_key = {}
    for key, version in arrived:
        by_key.setdefault(key, []).append(version)
    assert len(by_key) == TENSORS * 3 and len(arrived) == ROUNDS * TENSORS * 3
    for key, versions in by_key.items():
        assert versions == sorted(versions) and len(set(versions)) == ROUNDS, key


# --- (b) how the work and the bytes split --------------------------------------


@within(240)
def test_the_second_sender_takes_about_half_and_every_byte_leaves_on_a_push_lane(monkeypatch):
    import jax.numpy as jnp

    import byteps_tpu as bps

    parts, rounds = 32, 4
    rng = np.random.default_rng(3)
    with _Cluster(monkeypatch, BYTEPS_PARTITION_BYTES=str(BIG)):
        bps.init()
        x = rng.standard_normal(parts * BIG // 4).astype(np.float32)
        np.testing.assert_array_equal(  # the warm-up round: programs, the init barrier
            np.array(bps.push_pull(jnp.asarray(x), name="senders.big", average=False)), x)
        lanes, before = lane_bytes(), second()
        first, first1 = (hist("span_seconds", name=n)["count"] for n in ("stage.PUSH", "stage.PUSH.1"))
        for _ in range(rounds):
            out = np.array(bps.push_pull(jnp.asarray(x), name="senders.big", average=False))
            np.testing.assert_array_equal(out, x)
        deadline = time.monotonic() + 5  # the last task's span closes a moment after its round
        while (time.monotonic() < deadline and second() - before
               + hist("span_seconds", name="stage.PUSH")["count"] - first < rounds * parts):
            time.sleep(0.005)
        by_second = second() - before
        by_first = hist("span_seconds", name="stage.PUSH")["count"] - first
        moved, link = lane_growth(lanes), _link()
        bps.shutdown()
    assert by_first + by_second == rounds * parts  # the two senders' parts are all of them
    assert 0.3 * rounds * parts <= by_second <= 0.7 * rounds * parts, by_second
    assert by_second == hist("span_seconds", name="stage.PUSH.1")["count"] - first1
    assert (len(link.stripes), len(link.pull_stripes)) == (2, 2)
    # the step's bytes exactly, out on the push lanes and back on the pull lane
    assert moved == {("push", "tx"): rounds * parts * BIG, ("pull", "rx"): rounds * parts * BIG}


# --- (c) links with one socket keep one sender ---------------------------------


@pytest.mark.parametrize(("link", "env"), [
    ("uds", {"BYTEPS_VAN": "uds"}),
    ("shm", {"BYTEPS_VAN": "shm"}),
    ("shaped", {"BYTEPS_VAN_DELAY_MS": "0.1"}),
])
@within(120)
def test_a_link_of_one_socket_has_one_sender(monkeypatch, link, env):
    import jax.numpy as jnp

    import byteps_tpu as bps

    if link == "shm":
        import platform

        if platform.machine() not in ("x86_64", "AMD64", "i686"):
            pytest.skip("shm van requires x86-64 (TSO store ordering)")
    x = np.arange(3 * SMALL // 4, dtype=np.float32)
    with _Cluster(monkeypatch, BYTEPS_PARTITION_BYTES=str(SMALL), **env):
        bps.init()
        before = second()
        out = np.array(bps.push_pull(jnp.asarray(x), name="senders.one", average=False))
        names, sc, grown = senders(), _link(), second() - before
        bps.shutdown()
    np.testing.assert_array_equal(out, x)
    assert names == ["bps-PUSH-0"] and grown == 0
    assert sc.push_senders == 1 and len(sc.lanes()) == 1 and sc.pull_stripes is sc.stripes


def test_the_native_link_feeds_one_sender():
    """``_NativeServerConn`` sends on native threads: one Python sender; and
    a client's senders are the least its links can feed."""
    assert ps_client._NativeServerConn.push_senders == 1
    client = ps_client.PSClient.__new__(ps_client.PSClient)
    split, one = (type("Link", (), {"push_senders": n})() for n in (2, 1))
    for links, want in [([], 1), ([split], 2), ([split, split], 2), ([split, one], 1)]:
        client._servers = links
        assert client.push_senders() == want


# --- (d) one credit budget over both senders -----------------------------------


@within(120)
def test_the_credit_budget_bounds_the_bytes_in_flight_over_both_senders(monkeypatch):
    import jax.numpy as jnp

    import byteps_tpu as bps
    from byteps_tpu.core.state import get_state

    part, parts, budget_parts = 64 << 10, 24, 3
    x = np.arange(parts * part // 4, dtype=np.float32)
    with _Cluster(monkeypatch, BYTEPS_PARTITION_BYTES=str(part),
                  BYTEPS_SCHEDULING_CREDIT=str(budget_parts * part)):
        bps.init()
        client, lock = get_state().ps_client, threading.Lock()
        flying, most, push = [0], [0], client.push

        def counted(key, payload, dtype_id, version, cb, **kwargs):
            # from the sender's dequeue to the ack, as the queue counts it
            # (the engine returns the credit inside ``cb``)
            nbytes = len(payload)

            def acked():
                with lock:
                    flying[0] -= nbytes
                cb()

            with lock:
                flying[0] += nbytes
                most[0] = max(most[0], flying[0])
            return push(key, payload, dtype_id, version, cb=acked, **kwargs)

        client.push = counted
        for _ in range(4):
            out = np.array(bps.push_pull(jnp.asarray(x), name="senders.credit", average=False))
            np.testing.assert_array_equal(out, x)
        names = senders()
        gated = sum(hist("stage_idle_seconds", stage=s, why="gated")["count"]
                    for s in ("PUSH", "PUSH.1"))
        bps.shutdown()
    assert names == ["bps-PUSH-0", "bps-PUSH-1"]
    assert part <= most[0] <= budget_parts * part, most
    assert flying[0] == 0
    assert gated > 0  # 24 partitions against a budget of 3: a sender did wait for credit


# --- (e) a fusion group and a compressed job -----------------------------------


@pytest.mark.parametrize(("mode", "env", "declare"), [
    ("fused", {"BYTEPS_FUSION_THRESHOLD": "16384", "BYTEPS_FUSION_CYCLE_MS": "2"}, {}),
    ("codec", {"BYTEPS_MIN_COMPRESS_BYTES": "0"},
     {"byteps_compressor_type": "onebit", "byteps_compressor_onebit_scaling": "True"}),
])
@within(180)
def test_a_fusion_group_and_a_compressed_job_complete_through_two_senders(
        monkeypatch, mode, env, declare):
    """Small leaves packed into fused frames (gate-exempt group tasks that
    either sender may take) beside a partitioned leaf, and a leaf whose
    COMPRESS stripes feed PUSH: bitwise what one sender gives."""
    import jax.numpy as jnp

    import byteps_tpu as bps

    def rounds(n_senders):
        monkeypatch.setattr(ps_client, "PUSH_SENDERS", n_senders)
        rng = np.random.default_rng(5)
        with _Cluster(monkeypatch, BYTEPS_PARTITION_BYTES=str(64 << 10), **env):
            bps.init()
            bps.declare_tensor("senders.leaf", **declare)
            before, got = counters().snapshot(), []
            for _ in range(6):
                big = rng.standard_normal(6 * (64 << 10) // 4).astype(np.float32)
                handles = [bps.push_pull_async(jnp.asarray(big), name="senders.leaf",
                                               average=False)]
                if mode == "fused":
                    handles += [bps.push_pull_async(jnp.asarray(big[:500] * (i + 2)),
                                                    name=f"senders.small{i}", average=False)
                                for i in range(4)]
                got += [np.array(bps.synchronize(h)) for h in handles]
            after, names = counters().snapshot(), senders()
            bps.shutdown()
        return got, {k: after.get(k, 0) - before.get(k, 0) for k in (SECOND, "fused_frames")}, names

    two, grew, names = rounds(2)
    assert names == ["bps-PUSH-0", "bps-PUSH-1"] and grew[SECOND] > 0
    assert (grew["fused_frames"] > 0) == (mode == "fused")
    one, _, _ = rounds(1)
    assert len(two) == len(one)
    for got, want in zip(two, one):
        np.testing.assert_array_equal(got, want)


# --- (f) a thread's account stays one thread's ---------------------------------


def _metric_keys(name: str) -> list:
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        args = json.load(f)["args"]
    share = args.get("share", {})
    return args["keys"] + args.get("account", []) + share.get("of", []) + share.get("in", [])


@within(120)
def test_each_senders_account_is_its_own_wall_clock(monkeypatch):
    import jax.numpy as jnp

    import byteps_tpu as bps
    from byteps_tpu.core.engine import PipelineEngine

    monkeypatch.setattr(PipelineEngine, "_POLL_S", 0.005)  # a window's edges cut at most this
    part, parts, rounds = 64 << 10, 24, 6
    x = np.arange(parts * part // 4, dtype=np.float32)
    names = {"PUSH": ("stage.PUSH", "rpc.send.PUSH"), "PUSH.1": ("stage.PUSH.1", "rpc.send.PUSH.1")}

    def accounts():  # both senders' out of one snapshot, the moment the wall clock is read
        held = metrics().snapshot()["histograms"]
        none = {"count": 0, "sum": 0.0}
        return {stage: {"service": held.get('span_seconds{name="%s"}' % names[stage][0], none),
                        "send": held.get('span_seconds{name="%s"}' % names[stage][1], none),
                        **{why: held.get('stage_idle_seconds{stage="%s",why="%s"}' % (stage, why), none)
                           for why in ("starved", "gated", "dequeue")}}
                for stage in names}

    with _Cluster(monkeypatch, BYTEPS_PARTITION_BYTES=str(part)):
        bps.init()
        for _ in range(2):
            bps.push_pull(jnp.asarray(x), name="senders.account", average=False)
        before, t0 = accounts(), time.perf_counter()
        for _ in range(rounds):
            bps.push_pull(jnp.asarray(x), name="senders.account", average=False)
            time.sleep(0.2)
        wall = time.perf_counter() - t0
        after = accounts()
        families = set(metrics().snapshot()["histograms"])
        bps.shutdown()
    served = 0
    for stage in names:
        grown = {k: after[stage][k]["sum"] - before[stage][k]["sum"] for k in after[stage]}
        # a sender's sends are its own, inside its own service
        assert (after[stage]["send"]["count"] - before[stage]["send"]["count"]
                == after[stage]["service"]["count"] - before[stage]["service"]["count"]), stage
        assert 0 < grown.pop("send") <= grown["service"], stage
        assert sum(grown.values()) == pytest.approx(wall, rel=0.03), (stage, grown, wall)
        assert grown["service"] > 0 and grown["starved"] > 0.5 * wall, (stage, grown)
        served += after[stage]["service"]["count"] - before[stage]["service"]["count"]
    assert served == rounds * parts
    # sender 0 observes (its sends too) under the names the benchmark's
    # eight ps_plane.push_* metrics read; sender 1's are the push1_* files'
    read = {key for m in ("service", "cpu", "starved", "gated", "send", "wait")
            for key in _metric_keys(f"ps_plane.push_{m}_ms")}
    assert read <= families, read - families
    assert not any(".1" in key for key in read)
    second_read = {key for m in ("service", "starved", "send")
                   for key in _metric_keys(f"ps_plane.push1_{m}_ms")}
    assert second_read <= families and all("PUSH.1" in key for key in second_read)
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "ps_plane.second_sender_parts_per_step.json")) as f:
        assert json.load(f)["args"] == {"counters": [SECOND]}
    assert 'stage_wait_seconds{stage="PUSH.1"}' in families


# --- (g) push lane 1 dying mid-frame -------------------------------------------


class _DiesMidFrame:
    """A push lane's socket that, while ``budget`` lasts, sends half of a
    data frame it is given and then is gone; everything else is the
    socket's own."""

    def __init__(self, sock, budget):
        self._sock, self._budget = sock, budget

    def __getattr__(self, name):
        return getattr(self._sock, name)

    def sendmsg(self, bufs):
        if sum(len(b) for b in bufs) < 1024 or self._budget[0] <= 0:
            return self._sock.sendmsg(bufs)
        self._budget[0] -= 1
        whole = b"".join(bytes(b) for b in bufs)
        self._sock.sendall(whole[:len(whole) // 2])
        close_socket(self._sock)
        raise ConnectionError("push lane 1 died mid-frame")


@within(120)
def test_push_lane_one_dying_mid_frame_fails_the_job_once_and_the_next_submit_succeeds(monkeypatch):
    import byteps_tpu as bps
    from byteps_tpu.common.types import DegradedError
    from byteps_tpu.core.state import get_state

    # every attempt of the first job's odd key meets a link whose push lane 1
    # dies under it (the first and its two retries), with the in-place heal
    # and the step's own retry off: the job fails degraded, once
    budget = [3]
    new_conn = ps_client.PSClient._new_conn

    def breaking(self, host, port, dial_timeout=30.0):
        sc = new_conn(self, host, port, dial_timeout)
        if len(sc.stripes) > 1:
            sock, lock = sc.stripes[1]
            sc.stripes[1] = (_DiesMidFrame(sock, budget), lock)
        return sc

    part = 64 << 10
    x = np.arange(2 * part // 4, dtype=np.float32)  # keys 0 and 1: one a push lane
    with _Cluster(monkeypatch, BYTEPS_PARTITION_BYTES=str(part), BYTEPS_RESYNC_DEADLINE_S="0",
                  BYTEPS_DEGRADED_STEP_RETRIES="0", BYTEPS_RPC_BACKOFF_S="0.01"):
        monkeypatch.setattr(ps_client.PSClient, "_new_conn", breaking)
        bps.init()
        before = counters().snapshot()
        with pytest.raises(DegradedError):
            bps.push_pull(x, name="senders.dies", average=False)
        assert "senders.dies" in get_state().engine._reinit_names
        after = counters().snapshot()
        assert budget[0] == 0
        assert after.get("degraded_jobs", 0) - before.get("degraded_jobs", 0) >= 1
        assert after.get("rpc_giveup", 0) - before.get("rpc_giveup", 0) >= 1
        # the next submit runs the init barrier again, over a link dialled
        # whole, and both senders' lanes carry it
        for scale in (1.0, 3.0):
            out = np.array(bps.push_pull(x * scale, name="senders.dies", average=False))
            np.testing.assert_array_equal(out, x * scale)
        assert "senders.dies" not in get_state().engine._reinit_names
        link = _link()
        assert not link.dead and len(link.lanes()) == 4
        assert senders() == ["bps-PUSH-0", "bps-PUSH-1"]
        bps.shutdown()


# --- (h) two threads record into the journal at once ---------------------------


@within(60)
def test_the_journal_holds_a_rounds_every_payload_from_two_threads():
    from byteps_tpu.comm.journal import RoundJournal

    keys, nbytes = 64, 4096
    journal = RoundJournal(2, keys * 3 * nbytes)
    payloads = {(k, v): np.full(nbytes, (k + v) % 251, np.uint8) for k in range(keys)
                for v in (1, 2, 3)}
    for p in payloads.values():
        p.flags.writeable = False
    start = threading.Barrier(2)

    def sender(mine):
        start.wait()
        for version in (1, 2, 3):
            for key in range(mine, keys, 2):
                journal.record(key, version, 0, payloads[key, version].data)

    threads = [threading.Thread(target=sender, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # two rounds a key are kept: every payload of rounds 2 and 3, whole
    for key in range(keys):
        held = {e.version: e for e in journal.entries_after(key, 0)}
        assert sorted(held) == [2, 3], key
        for version, entry in held.items():
            assert bytes(entry.payload) == payloads[key, version].tobytes()
    assert journal.evicted == keys


def test_the_engine_asks_the_links_how_many_senders(monkeypatch):
    """The number of PUSH threads comes from the client's links, not from an
    option: a stub client without the question gets one."""
    from byteps_tpu.common.config import Config
    from byteps_tpu.core.engine import PipelineEngine

    class Client:
        def __init__(self, n):
            self.n = n

        def push_senders(self):
            return self.n

    for client, want in [(Client(2), 2), (Client(1), 1), (object(), 1)]:
        engine = PipelineEngine(Config(), client)
        engine.start()
        try:
            mine = [t.name for t in engine._threads if t.name.startswith("bps-PUSH-")]
            assert mine == [f"bps-PUSH-{i}" for i in range(want)]
            assert isinstance(engine.queues[QueueType.PUSH].pending(), int)  # one queue
        finally:
            engine.stop()
