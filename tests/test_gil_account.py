"""Whose GIL (ISSUE 71; docs/observability.md "Reading a hop thread by thread",
"One scrape, both ends"): a sampled service splits into the time its thread
held the GIL, the time it waited for it and the time inside calls that let it
go; the server's serve and engine threads keep the account the worker's stage
threads keep; and ``bps.get_metrics()`` in a worker reads both ends of its
sockets — or its own process alone, within a bounded time."""

import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.comm import ps_client
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.comm.transport import Message, Op, send_message
from byteps_tpu.core import tracing
from byteps_tpu.core.engine import PipelineEngine
from byteps_tpu.core.telemetry import (
    MetricsRegistry,
    RobustnessCounters,
    counters,
    metrics,
)
from byteps_tpu.server.server import PSServer

PART = 4096  # bytes a partition
PARTS = 64  # partitions of the one tensor: every lane's threads sample a frame
SERVER = {"role": "server", "rank": "0"}


@pytest.fixture(autouse=True)
def _clean():
    counters().reset()
    metrics().reset()
    yield
    counters().reset()
    metrics().reset()


def key(family, **labels):
    return family + "{" + ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"


def hist(family, **labels):
    return metrics().snapshot()["histograms"].get(key(family, **labels),
                                                  {"count": 0, "sum": 0.0})


# ---------------------------------------------------------------------------
# the split of a sampled service, on injected clocks
# ---------------------------------------------------------------------------


class Clocks:
    """A wall clock and a thread's CPU clock that a test moves by hand, in
    eighths of a millisecond so that every sum is exact in binary."""

    TICK = 2.0 ** -13

    def __init__(self, monkeypatch):
        self.wall = self.cpu = 0.0
        self.reads = 0
        monkeypatch.setattr(tracing, "_wall_clock", lambda: self._read("wall"))
        monkeypatch.setattr(tracing, "_cpu_clock", lambda: self._read("cpu"))

    def _read(self, which):
        self.reads += 1
        return getattr(self, which)

    def run(self, ticks):  # on the CPU
        self.wall += ticks * self.TICK
        self.cpu += ticks * self.TICK

    def off(self, ticks):  # off the CPU
        self.wall += ticks * self.TICK


def _no_bracket(clocks):
    clocks.run(5), clocks.off(3)
    return {"held": 5, "gilwait": 3, "cpu": 5, "wall": 8}


def _one_send(clocks):
    clocks.run(2)
    with tracing.releasing():
        clocks.run(7), clocks.off(4)  # the kernel's copy, and a full socket buffer
    clocks.off(1), clocks.run(1)
    return {"held": 3, "gilwait": 1, "cpu": 10, "wall": 15}


def _nested_and_repeated(clocks):
    for _ in range(3):
        clocks.run(1)
        with tracing.releasing():  # a lane's lock around its sendmsg
            clocks.off(2)
            with tracing.releasing():
                clocks.run(4)
        clocks.off(1)
    return {"held": 3, "gilwait": 3, "cpu": 15, "wall": 24}


def _all_released(clocks):
    with tracing.releasing():
        clocks.off(9)
    return {"held": 0, "gilwait": 0, "cpu": 0, "wall": 9}


def _bracket_that_raises(clocks):
    clocks.run(2)
    with pytest.raises(OSError), tracing.releasing():
        clocks.off(6)
        raise OSError("peer closed")
    clocks.off(2)
    return {"held": 2, "gilwait": 2, "cpu": 2, "wall": 10}


@pytest.mark.parametrize("service", [_no_bracket, _one_send, _nested_and_repeated,
                                     _all_released, _bracket_that_raises],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_sampled_service_splits_into_held_gilwait_and_released(monkeypatch, service):
    clocks = Clocks(monkeypatch)
    sample = tracing.sampled("split." + service.__name__, every=1)
    sample.begin()
    ticks = service(clocks)
    sample.end()
    read = {c: hist("stage_sample_seconds", stage="split." + service.__name__, clock=c)
            for c in tracing.sampled.CLOCKS}
    assert {c: h["count"] for c, h in read.items()} == dict.fromkeys(tracing.sampled.CLOCKS, 1)
    assert {c: h["sum"] for c, h in read.items()} == {c: n * Clocks.TICK for c, n in ticks.items()}
    # held + gilwait + released = wall, to the float: released on the CPU is
    # cpu - held, released off it the rest
    released_wall = read["wall"]["sum"] - read["held"]["sum"] - read["gilwait"]["sum"]
    released_cpu = read["cpu"]["sum"] - read["held"]["sum"]
    assert 0 <= released_cpu <= released_wall
    assert read["held"]["sum"] + read["gilwait"]["sum"] + released_wall == read["wall"]["sum"]
    assert tracing._sampling.acc is None  # the service is over


@pytest.mark.parametrize("every", [2, 16])
def test_an_unsampled_service_reads_no_clock(monkeypatch, every):
    clocks = Clocks(monkeypatch)
    sample = tracing.sampled(f"unsampled.{every}", every=every)
    for _ in range(every - 1):
        sample.begin()
        with tracing.releasing():
            clocks.off(1)
        sample.end()
    assert clocks.reads == 0
    assert hist("stage_sample_seconds", stage=f"unsampled.{every}", clock="wall")["count"] == 0
    with tracing.releasing():  # between two services: no service is open
        clocks.off(1)
    assert clocks.reads == 0
    sample.begin()
    with tracing.releasing():
        clocks.off(1)
    sample.end()
    assert clocks.reads == 8  # two clocks at the service's two ends and the bracket's
    assert hist("stage_sample_seconds", stage=f"unsampled.{every}", clock="wall")["count"] == 1


def test_a_named_bracket_is_a_span_too(monkeypatch):
    Clocks(monkeypatch)
    with tracing.releasing("copyh2d.put"):
        pass
    assert hist("span_seconds", name="copyh2d.put")["count"] == 1


# ---------------------------------------------------------------------------
# a live loopback plane
# ---------------------------------------------------------------------------


@pytest.fixture
def cluster(monkeypatch, tmp_path):
    """1 worker / 1 server in-process over tcp (two push lanes, two pull
    lanes: four serve threads; four engine threads), small partitions, both
    sides' idle threads polling every 5 ms so that a window's edges cut at
    most that much."""
    monkeypatch.setattr(PipelineEngine, "_POLL_S", 0.005)
    monkeypatch.setattr(PSServer, "_POLL_S", 0.005)
    monkeypatch.setattr(tracing.sampled, "EVERY", 16)  # a few rounds sample every thread
    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    for name, value in {
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(sched.port),
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "BYTEPS_FORCE_DISTRIBUTED": "1", "BYTEPS_PARTITION_BYTES": str(PART),
    }.items():
        monkeypatch.setenv(name, value)
    srv = PSServer(Config.from_env())
    threading.Thread(target=srv.start, daemon=True).start()
    yield srv
    import byteps_tpu as bps

    bps.shutdown()
    srv.stop()
    sched.stop()


def raw_rounds(rounds):
    import jax.numpy as jnp

    import byteps_tpu as bps

    rng = np.random.default_rng(71)
    for _ in range(rounds):
        x = rng.standard_normal(PARTS * PART // 4).astype(np.float32)
        out = np.array(bps.push_pull(jnp.asarray(x), name="gil.account", average=False))
        np.testing.assert_array_equal(out, x)  # one worker: the sum is the tensor


def account(kind):
    """(service + idle seconds, threads alive, services) of a kind of the
    server's threads, and the moment they were read."""
    snapshot = metrics().snapshot()
    states = [snapshot["histograms"].get(key("thread_seconds", kind=kind, state=s),
                                         {"count": 0, "sum": 0.0})
              for s in ("service", "idle")]
    return (sum(h["sum"] for h in states), snapshot["gauges"].get(key("threads_alive", kind=kind)),
            states[0]["count"], time.perf_counter())


@pytest.mark.parametrize("kind, threads", [("serve", 4), ("engine", 4)])
def test_a_server_threads_account_adds_up_to_its_wall_clock(cluster, kind, threads):
    import byteps_tpu as bps

    bps.init()
    raw_rounds(2)
    time.sleep(0.02)  # every thread past its last service's end
    accounted0, alive0, served0, t0 = account(kind)
    raw_rounds(3)
    time.sleep(0.15)  # idle counts like service
    raw_rounds(1)
    time.sleep(0.02)
    accounted1, alive1, served1, t1 = account(kind)
    assert alive0 == alive1 == threads
    # every frame of a round is one service of a serve thread and one of an
    # engine thread: a PUSH and a PULL a partition
    assert served1 - served0 == 4 * 2 * PARTS
    # a serve thread's open idle stretch (blocked for the next header) is not
    # observed yet at either edge, an engine thread's is cut by its poll
    assert accounted1 - accounted0 == pytest.approx(threads * (t1 - t0), rel=0.05)
    sampled = {c: hist("stage_sample_seconds", stage=kind, clock=c)
               for c in tracing.sampled.CLOCKS}
    assert sampled["wall"]["count"] >= threads  # one frame in so many, every thread
    assert sampled["held"]["sum"] <= sampled["cpu"]["sum"] <= sampled["wall"]["sum"]
    assert (sampled["held"]["sum"] + sampled["gilwait"]["sum"]
            <= sampled["wall"]["sum"] * (1 + 1e-9))


def test_a_workers_metrics_hold_its_servers_series(cluster):
    import byteps_tpu as bps

    bps.init()
    raw_rounds(1)
    own = metrics().snapshot()
    seen = bps.get_metrics()
    for state in ("service", "idle"):
        for kind in ("serve", "engine"):
            theirs = seen["histograms"][key("thread_seconds", kind=kind, state=state, **SERVER)]
            assert theirs["count"] >= own["histograms"][
                key("thread_seconds", kind=kind, state=state)]["count"] > 0
    assert seen["histograms"][key("server_sum_seconds", **SERVER)]["count"] == PARTS
    assert seen["gauges"][key("threads_alive", kind="serve", **SERVER)] == 4
    # the server's counters stand among the labeled ones; the flat section and
    # get_robustness_counters() stay this process's own
    assert seen["counters_labeled"]["wire_rpc"][key("", **SERVER)] >= 2 * PARTS
    assert seen["counters"] == {k: v for k, v in metrics().snapshot()["counters"].items()
                                if k in seen["counters"]} and "wire_rpc" in seen["counters"]
    assert not any("role=" in k for k in bps.get_robustness_counters())
    # asked again, the series have grown by the request's own frame alone
    again = bps.get_metrics()
    serve = key("thread_seconds", kind="serve", state="service", **SERVER)
    assert again["histograms"][serve]["count"] == seen["histograms"][serve]["count"] + 1


def test_a_labelled_snapshot_leaves_the_heartbeats_delta_as_it_was():
    """What the server answers ``Op.METRICS`` with is a read: the next beat
    ships exactly what it would have shipped."""
    registry = MetricsRegistry(RobustnessCounters())
    registry.observe("server_sum_seconds", 0.001)
    registry.counters.bump("wire_rpc", 3)
    assert registry.delta_snapshot()  # a beat: the baseline moves here
    registry.observe("server_sum_seconds", 0.002)
    registry.counters.bump("wire_rpc", 2)
    labelled = registry.snapshot(labels=SERVER)
    assert labelled["histograms"][key("server_sum_seconds", **SERVER)]["count"] == 2
    assert labelled["counters"] == {} and labelled["counters_labeled"]["wire_rpc"] == {
        key("", **SERVER): 5}
    plain = MetricsRegistry(RobustnessCounters())
    plain.observe("server_sum_seconds", 0.001)
    plain.counters.bump("wire_rpc", 3)
    plain.delta_snapshot()
    plain.observe("server_sum_seconds", 0.002)
    plain.counters.bump("wire_rpc", 2)
    assert registry.delta_snapshot() == plain.delta_snapshot() != {}


def _silent(srv, monkeypatch):
    """A server that does not know the request and says nothing (an older
    Python engine)."""
    serve = PSServer._serve_frame

    def older(self, conn, send_lock, header, pool):
        if header[0] == Op.METRICS:
            return True
        return serve(self, conn, send_lock, header, pool)

    monkeypatch.setattr(PSServer, "_serve_frame", older)


def _rejecting(srv, monkeypatch):
    """A server that rejects the request as the C++ engine rejects an op it
    does not know: the op and seq echoed with status 1."""
    serve = PSServer._serve_frame

    def native(self, conn, send_lock, header, pool):
        if header[0] == Op.METRICS:
            send_message(conn, Message(Op.METRICS, seq=header[3], status=1), send_lock)
            return True
        return serve(self, conn, send_lock, header, pool)

    monkeypatch.setattr(PSServer, "_serve_frame", native)


def _dead(srv, monkeypatch):
    """A server that dies: the next frame finds its connection closed."""
    def gone(self, conn, send_lock, header, pool):
        raise ConnectionError("the server died")

    monkeypatch.setattr(PSServer, "_serve_frame", gone)


@pytest.mark.parametrize("fault, waits", [(_silent, True), (_rejecting, False),
                                          (_dead, False)],
                         ids=lambda f: f.__name__.strip("_") if callable(f) else None)
def test_a_server_that_cannot_answer_costs_a_bounded_wait(cluster, monkeypatch, fault, waits):
    import byteps_tpu as bps

    monkeypatch.setattr(ps_client.PSClient, "METRICS_WAIT_S", 0.3)
    bps.init()
    raw_rounds(1)
    fault(cluster, monkeypatch)
    t0 = time.perf_counter()
    seen = bps.get_metrics()
    took = time.perf_counter() - t0
    assert (0.3 <= took < 1.0) if waits else took < 0.3
    assert not any("role=" in k for section in seen.values() for k in section)
    assert key("span_seconds", name="stage.PUSH") in seen["histograms"]  # the worker's own
    if fault is _dead:
        t0 = time.perf_counter()  # asked again, its dead link answers at once
        assert set(bps.get_metrics()) == set(seen) and time.perf_counter() - t0 < 0.3
    else:
        raw_rounds(1)  # and the link was left as it was: no teardown, no revival
        assert counters().snapshot().get("conn_revive", 0) == 0


@pytest.mark.parametrize("when", ["before_init", "after_shutdown"])
def test_outside_a_plane_the_call_returns_the_local_registry(request, when):
    import byteps_tpu as bps

    if when == "after_shutdown":
        request.getfixturevalue("cluster")
        bps.init()
        raw_rounds(1)
        bps.shutdown()
    t0 = time.perf_counter()
    seen = bps.get_metrics()
    assert time.perf_counter() - t0 < 0.5
    # in-process the server's threads fill this registry too, under their own
    # names; nothing in it was fetched
    assert not any("role=" in k for section in seen.values() for k in section)
    assert set(seen) == set(metrics().snapshot())
