"""On a TCP link to a server, bulk payload travels one way on a socket
(ISSUE 35): PULL requests and the merged rounds ride pull lanes of their
own, everything else the push lanes.  Counts, identities and bitwise
results — no clock but each case's own time limit."""

import functools
import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.comm.transport import POOL_MIN_BYTES, close_socket
from byteps_tpu.core.telemetry import counters
from byteps_tpu.server.server import PSServer

PART = POOL_MIN_BYTES  # one partition: the smallest frame a pool serves
N = PART // 4  # its f32 elements
LANES = [("push", "tx"), ("push", "rx"), ("pull", "tx"), ("pull", "rx")]


def within(seconds: float):
    """The case's own time limit: its body runs on a thread that is given
    ``seconds``; a case that hangs fails here, not at the suite's limit."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            box = []

            def body():
                try:
                    box.append((True, fn(*args, **kwargs)))
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    box.append((False, e))

            t = threading.Thread(target=body, daemon=True)
            t.start()
            t.join(seconds)
            assert box, f"{fn.__name__} did not finish in {seconds} s"
            ok, value = box[0]
            if not ok:
                raise value
            return value
        return run
    return wrap


def lane_bytes() -> dict:
    got = counters().snapshot_labeled().get("lane_bulk_bytes", {})
    return {(lane, d): got.get((("dir", d), ("lane", lane)), 0) for lane, d in LANES}


def lane_growth(before: dict) -> dict:
    return {k: v - before[k] for k, v in lane_bytes().items() if v != before[k]}


class _Cluster:
    """1 worker / 1 server in-process over the van the environment names."""

    def __init__(self, monkeypatch, **env):
        self.sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
        self.sched.start()
        for name, value in {
            "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(self.sched.port),
            "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
            "BYTEPS_FORCE_DISTRIBUTED": "1", "BYTEPS_PARTITION_BYTES": str(PART),
            **env,
        }.items():
            monkeypatch.setenv(name, value)
        self.srv = PSServer(Config.from_env())
        threading.Thread(target=self.srv.start, daemon=True).start()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        from byteps_tpu.common import config, registry
        from byteps_tpu.core import state

        state.shutdown_state()
        registry.reset_registry()
        config.clear_config()
        self.srv.stop()
        self.sched.stop()


def _link():
    from byteps_tpu.core.state import get_state

    return get_state().ps_client._servers[0]


# --- (a) a round over the split link: where the bytes went, and the sums ----

#: mode → (environment, declare kwargs)
MODES = {
    "sync": ({}, {}),
    "async": ({}, {"byteps_async": "1"}),
    "server_opt": ({}, {"byteps_server_opt": "momentum",
                        "byteps_server_opt_hp": {"lr": 0.01}}),
    "codec": ({"BYTEPS_MIN_COMPRESS_BYTES": "0"},
              {"byteps_compressor_type": "onebit",
               "byteps_compressor_onebit_scaling": "True"}),
    "fused": ({"BYTEPS_FUSION_THRESHOLD": "16384", "BYTEPS_FUSION_CYCLE_MS": "2"}, {}),
}
ROUNDS = 3


def _rounds(monkeypatch, mode: str, **env) -> tuple:
    """``ROUNDS`` rounds of a three-partition leaf (and in ``fused`` mode
    of two small ones, which share a frame).  Returns the results, the
    lanes' growth, the wire counters' growth and the link."""
    import jax.numpy as jnp

    import byteps_tpu as bps

    mode_env, declare = MODES[mode]
    rng = np.random.default_rng(35)
    with _Cluster(monkeypatch, **mode_env, **env):
        bps.init()
        bps.declare_tensor("lane.big", **declare)
        before, wire0 = lane_bytes(), counters().snapshot()
        results = []
        for _ in range(ROUNDS):
            big = rng.standard_normal(3 * N).astype(np.float32)
            handles = [bps.push_pull_async(jnp.asarray(big), name="lane.big", average=False)]
            if mode == "fused":
                handles += [
                    bps.push_pull_async(jnp.asarray(big[:1000] * (i + 2)),
                                        name=f"lane.small{i}", average=False)
                    for i in range(2)
                ]
            # copied at once: on the CPU backend a one-partition result can
            # alias the tensor's lent pull target, which the next round reuses
            results += [np.array(bps.synchronize(h)) for h in handles]
        grown, wire1, link = lane_growth(before), counters().snapshot(), _link()
        bps.shutdown()
    wire = {k: wire1.get(k, 0) - wire0.get(k, 0)
            for k in ("wire_tx_bytes", "wire_rx_bytes", "fused_frames")}
    return results, grown, wire, link


@pytest.mark.parametrize("mode", list(MODES))
@within(120)
def test_a_round_pushes_on_one_lane_and_lands_on_the_other(monkeypatch, mode):
    """Over TCP a round's payload leaves on ``push,tx`` and lands on
    ``pull,rx``; no bulk meets bulk on a socket; and every result is
    bitwise what a one-socket (unix) link gives."""
    results, grown, wire, link = _rounds(monkeypatch, mode)
    # a push lane a PUSH sender (two since ISSUE 39) and as many pull lanes
    assert (len(link.stripes), len(link.pull_stripes)) == (2, 2)
    assert link.pull_stripes is not link.stripes and len(link.lanes()) == 4
    one_results, one_grown, _, one_link = _rounds(monkeypatch, mode, BYTEPS_VAN="uds")
    assert len(one_link.lanes()) == 1
    assert len(results) == len(one_results)
    for got, want in zip(results, one_results):
        np.testing.assert_array_equal(got, want)
    assert ("pull", "tx") not in grown  # a raw PULL request is a bare header
    if mode == "fused":
        # a fused frame wraps its members (a count, a header a member), and
        # the small leaves' merged rounds come back in its reply, on the
        # push lane: small by construction
        framing = wire["fused_frames"] * 128
        assert 0 < grown[("push", "tx")] - wire["wire_tx_bytes"] <= framing
        assert grown[("pull", "rx")] == ROUNDS * 3 * PART
        small = wire["wire_rx_bytes"] - grown[("pull", "rx")]
        assert 0 < grown[("push", "rx")] - small <= framing
    else:
        # what the engine counted as sent is what the push lane carried
        assert grown[("push", "tx")] == wire["wire_tx_bytes"]
        assert ("push", "rx") not in grown
        assert grown[("pull", "rx")] == wire["wire_rx_bytes"]
    if mode != "codec":
        assert grown[("pull", "rx")] == ROUNDS * 3 * PART
    # the one-socket link carried the same bytes, both ways on one lane
    # (how the small leaves pack into frames, and so the framing, is timing's)
    slack = wire["fused_frames"] * 128
    assert abs(one_grown[("push", "tx")] - grown[("push", "tx")]) <= slack
    landed = grown[("pull", "rx")] + grown.get(("push", "rx"), 0)
    assert abs(one_grown[("push", "rx")] - landed) <= slack
    assert not any(lane == "pull" for lane, _ in one_grown)


# --- (b) one lane dying is the link dying -------------------------------------


@pytest.mark.parametrize("killed", ["push", "pull"])
@within(60)
def test_either_lane_dying_fails_pending_once_and_both_come_back(monkeypatch, killed):
    """A pull parked on the server is the pending RPC.  Closing either
    lane fails it once; its retry dials a link with both lanes; the round
    it waited for is pushed, replayed over the new link and summed once."""
    from byteps_tpu.comm.ps_client import PSClient

    with _Cluster(monkeypatch):
        client = PSClient(Config.from_env(), node_uid=f"lane-death-{killed}")
        client.connect()
        try:
            key, x = 7, np.arange(N, dtype=np.float32)
            client.init_tensor(key, N, 0)
            acked, landed, box = threading.Event(), threading.Event(), []
            client.push(key, x.tobytes(), 0, 1, cb=acked.set)
            assert acked.wait(10)
            old = client._servers[0]
            before = counters().snapshot()
            # round 2 is not pushed yet: this pull parks on the server
            client.pull(key, 2, lambda payload: (box.append(bytes(payload)), landed.set()))
            time.sleep(0.2)
            assert not landed.is_set()
            lanes = old.pull_stripes if killed == "pull" else old.stripes
            close_socket(lanes[0][0])
            deadline = time.monotonic() + 10
            while client._servers[0] is old and time.monotonic() < deadline:
                time.sleep(0.02)
            fresh = client._servers[0]
            assert old.dead and fresh is not old and not fresh.dead
            assert len(fresh.lanes()) == 4 and fresh.pull_stripes is not fresh.stripes
            assert all(sock.fileno() == -1 for sock, _, _ in old.lanes())
            # round 2, then the same push again as a lost ack's retry would
            # send it: the ledger is the key's, not a connection's
            for _ in range(2):
                acked.clear()
                client.push(key, (2 * x).tobytes(), 0, 2, cb=acked.set)
                assert acked.wait(10)
            assert landed.wait(10)
            np.testing.assert_array_equal(np.frombuffer(box[0], np.float32), 2 * x)
            after = counters().snapshot()
            grew = {k: after.get(k, 0) - before.get(k, 0)
                    for k in ("rpc_retry", "conn_revive", "push_dedup", "rpc_giveup")}
            assert grew == {"rpc_retry": 1, "conn_revive": 1, "push_dedup": 1, "rpc_giveup": 0}
        finally:
            client.close()


# --- (c) links that keep one socket, (d) lanes a direction --------------------


@pytest.mark.parametrize(("link", "env", "lanes"), [
    ("uds", {"BYTEPS_VAN": "uds"}, 1),
    ("shm", {"BYTEPS_VAN": "shm"}, 1),
    ("shaped", {"BYTEPS_VAN_DELAY_MS": "0.1"}, 1),
    ("shaped_streams2", {"BYTEPS_VAN_DELAY_MS": "0.1", "BYTEPS_TCP_STREAMS": "2"}, 1),
    ("chaos_tcp", {"BYTEPS_VAN": "chaos:tcp"}, 4),
])
@within(120)
def test_which_links_split(monkeypatch, link, env, lanes):
    """A unix or shm van and a shaped link (one wire) keep one socket for
    both directions; a chaos address over TCP splits like plain TCP."""
    if link == "shm":
        import platform

        if platform.machine() not in ("x86_64", "AMD64", "i686"):
            pytest.skip("shm van requires x86-64 (TSO store ordering)")
    results, grown, wire, sc = _rounds(monkeypatch, "sync", **env)
    assert len(sc.lanes()) == lanes and len(sc.stripes) == (2 if lanes == 4 else 1)
    assert (sc.pull_stripes is sc.stripes) == (lanes == 1)
    assert len(results) == ROUNDS
    assert grown[("push", "tx")] == wire["wire_tx_bytes"] == ROUNDS * 3 * PART
    landed_on = "push" if lanes == 1 else "pull"
    assert grown == {("push", "tx"): ROUNDS * 3 * PART, (landed_on, "rx"): ROUNDS * 3 * PART}


@within(60)
def test_a_pull_lane_leaves_the_push_lanes_their_chaos_indices():
    """A seeded chaos schedule is keyed by (seed, connection index): the
    lanes a split link added (the pull lanes, then the second sender's push
    lane) count in a stream of their own, so the first push lanes of the
    servers a worker dials are connections 0, 1, ... as their one sockets
    were, and a schedule aimed at a server's pushes still hits them."""
    from byteps_tpu.comm import chaos
    from byteps_tpu.comm.ps_client import _ServerConn
    from byteps_tpu.comm.van import CHAOS_PREFIX, get_van

    lsock, host, port = get_van("tcp").listen("127.0.0.1")
    chaos.reset_conn_indices()
    links = [_ServerConn(CHAOS_PREFIX + host, port, dial_timeout=5) for _ in range(2)]
    try:
        assert [[sock.conn_index for sock, _, _ in sc.lanes()] for sc in links] == [
            # push lanes 0 and 1, pull lanes 0 and 1: dialled first, last, second, third
            [0, (1 << 17) + 2, 1 << 17, (1 << 17) + 1],
            [1, (1 << 17) + 5, (1 << 17) + 3, (1 << 17) + 4]]
    finally:
        for sc in links:
            sc.close_all()
        lsock.close()
        chaos.reset_conn_indices()


@within(120)
def test_more_streams_are_more_lanes_a_direction(monkeypatch):
    """``BYTEPS_TCP_STREAMS=3``: three push lanes and three pull lanes (one
    or two streams are the two lanes a direction the two PUSH senders
    need), keys striped over each set, the same bytes and the same sums."""
    one, one_grown, _, one_link = _rounds(monkeypatch, "sync")
    three, three_grown, _, link = _rounds(monkeypatch, "sync", BYTEPS_TCP_STREAMS="3")
    assert (len(one_link.stripes), len(one_link.pull_stripes)) == (2, 2)
    assert (len(link.stripes), len(link.pull_stripes), len(link.lanes())) == (3, 3, 6)
    assert [name for _, _, name in link.lanes()] == ["push"] * 3 + ["pull"] * 3
    assert len({id(sock) for sock, _, _ in link.lanes()}) == 6
    for got, want in zip(three, one):
        np.testing.assert_array_equal(got, want)
    assert three_grown == one_grown == {
        ("push", "tx"): ROUNDS * 3 * PART, ("pull", "rx"): ROUNDS * 3 * PART}


def test_a_pull_rides_the_pull_lane_of_its_key():
    """The lane is read off the message: its ``op``, then its key."""
    from byteps_tpu.comm.ps_client import _ServerConn
    from byteps_tpu.comm.transport import Message, Op

    sent = []

    class Sock:
        def __init__(self, name):
            self.name = name

        def sendall(self, data):
            sent.append(self.name)

    sc = _ServerConn.__new__(_ServerConn)
    sc.stripes = [(Sock("push0"), threading.Lock()), (Sock("push1"), threading.Lock())]
    sc.pull_stripes = [(Sock("pull0"), threading.Lock()), (Sock("pull1"), threading.Lock())]
    for op, key in [(Op.PUSH, 4), (Op.PUSH, 5), (Op.PULL, 4), (Op.PULL, 5), (Op.INIT, 5),
                    (Op.FUSED, 2), (Op.REGISTER_COMPRESSOR, 0), (Op.PING, 0),
                    (Op.RESYNC_QUERY, 3)]:
        sc.send_msg(Message(op, key=key, seq=1))
    assert sent == ["push0", "push1", "pull0", "pull1", "push1",
                    "push0", "push0", "push0", "push1"]


# --- (e) the split brings no fresh buffer back --------------------------------


@within(180)
def test_a_steady_step_over_the_split_link_makes_no_fresh_buffer(monkeypatch):
    """PR 34's steady-step counts over the two lanes: every pull target,
    received frame and reply is memory already held, and a step's bytes
    leave on one lane and land on the other."""
    from test_host_buffers import STEADY, _hybrid, grown, site_counts, warm_steps

    import byteps_tpu as bps

    with _Cluster(monkeypatch):
        bps.init()
        hdp, batch = _hybrid()
        start = site_counts()
        losses = warm_steps(monkeypatch, hdp, batch)
        buffers, lanes = site_counts(), lane_bytes()
        losses += [hdp.step(batch) for _ in range(5)]
        got, ever, moved, link = grown(buffers), grown(start), lane_growth(lanes), _link()
        bps.shutdown()
    assert len(link.lanes()) == 4
    assert losses[-1] < losses[0]
    assert got == STEADY
    assert ever[("fresh", "frame")] == 2, ever
    # w1 is two partitions of PART bytes, w2 one of N // 64 * 2 * 8 * 4
    step_bytes = 2 * PART + (2 * N // 64) * 8 * 4
    assert moved == {("push", "tx"): 5 * step_bytes, ("pull", "rx"): 5 * step_bytes}
