"""A PS hop, thread by thread (docs/observability.md "reading a hop thread by
thread"): a stage thread's service, CPU and idle time by cause close on the
wall clock; an RPC attempt splits into send and reply by op; the receive
threads have spans and names; a wait is no phase; histograms kept at hand
survive a registry reset; the ``slow_step`` trigger says why on stderr."""

import importlib.util
import json
import os
import threading
import time
import types

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.common.types import QueueType, TensorTableEntry
from byteps_tpu.comm import ps_client
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.comm.transport import Message, Op
from byteps_tpu.core import tracing
from byteps_tpu.core.engine import PipelineEngine, _StageIdle
from byteps_tpu.core.flightrec import FlightRecorder
from byteps_tpu.core.scheduler import ScheduledQueue
from byteps_tpu.core.telemetry import (
    MetricsRegistry,
    RobustnessCounters,
    counters,
    metrics,
)
from byteps_tpu.server.server import PSServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ["COPYD2H", "PUSH", "PUSH.1", "PULL", "COPYH2D"]  # PUSH over tcp: two senders (ISSUE 39)
PART = 4096  # bytes a partition
PARTS = 24  # partitions of the one tensor


@pytest.fixture(autouse=True)
def _clean():
    counters().reset()
    metrics().reset()
    tracing.set_process_tracer(None)
    yield
    tracing.set_process_tracer(None)
    counters().reset()
    metrics().reset()


def hist(family, **labels):
    key = family + "{" + ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
    return metrics().snapshot()["histograms"].get(key, {"count": 0, "sum": 0.0})


@pytest.fixture
def cluster(monkeypatch, tmp_path):
    """1 worker / 1 server in-process over tcp (a push lane and a pull
    lane), the tracer on, small partitions; stage threads poll every 5 ms so
    that a window's edges cut at most that much off a wait."""
    monkeypatch.setattr(PipelineEngine, "_POLL_S", 0.005)
    monkeypatch.setattr(tracing.sampled, "EVERY", 16)  # a few rounds sample every thread
    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    for name, value in {
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(sched.port),
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "BYTEPS_FORCE_DISTRIBUTED": "1", "BYTEPS_PARTITION_BYTES": str(PART),
        "BYTEPS_TRACE_ON": "1", "BYTEPS_TRACE_DIR": str(tmp_path),
    }.items():
        monkeypatch.setenv(name, value)
    srv = PSServer(Config.from_env())
    threading.Thread(target=srv.start, daemon=True).start()
    yield tmp_path
    import byteps_tpu as bps

    bps.shutdown()
    srv.stop()
    sched.stop()


def raw_rounds(rounds, pause=0.0):
    """``rounds`` raw jax rounds of one ``PARTS``-partition tensor."""
    import jax.numpy as jnp

    import byteps_tpu as bps

    rng = np.random.default_rng(38)
    for _ in range(rounds):
        x = rng.standard_normal(PARTS * PART // 4).astype(np.float32)
        out = np.array(bps.push_pull(jnp.asarray(x), name="hop.account", average=False))
        np.testing.assert_array_equal(out, x)  # one worker: the sum is the tensor
        time.sleep(pause)
    # a round is done when its last partition is; the thread that served that
    # one closes its span a moment later
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not all(
            spans(name)["count"] == hist("stage_dwell_seconds", stage="PULL")["count"]
            for name in ("stage.PUSH", "stage.PULL", "stage.COPYH2D", "rpc.send.PUSH",
                         "recv.frame.pull")):  # a PULL's send has no span: stage.PULL holds it
        time.sleep(0.005)


def spans(name):
    """``span_seconds`` of ``name`` over both PUSH senders | push lanes (the
    second's are ``<name>.1``; nothing else has one)."""
    both = [hist("span_seconds", name=n) for n in (name, name + ".1")]
    return {k: sum(h[k] for h in both) for k in ("count", "sum")}


def accounts():
    """Every stage thread's account out of ONE snapshot: the moment the
    wall clock beside it is read."""
    held = metrics().snapshot()["histograms"]

    def at(family, **labels):
        key = family + "{" + ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
        return held.get(key, {"count": 0, "sum": 0.0})

    return {stage: {
        "service": at("span_seconds", name=f"stage.{stage}"),
        "cpu": at("stage_sample_seconds", stage=stage, clock="cpu"),
        "wall": at("stage_sample_seconds", stage=stage, clock="wall"),
        "starved": at("stage_idle_seconds", stage=stage, why="starved"),
        "gated": at("stage_idle_seconds", stage=stage, why="gated"),
        "dequeue": at("stage_idle_seconds", stage=stage, why="dequeue"),
    } for stage in STAGES}


# ---------------------------------------------------------------------------
# the stage threads' account
# ---------------------------------------------------------------------------


def test_a_stage_threads_account_closes_on_the_wall_clock(cluster):
    import byteps_tpu as bps

    bps.init()
    raw_rounds(2)  # warm: programs compiled, the tensor declared
    before, t0 = accounts(), time.perf_counter()
    raw_rounds(6, pause=0.25)
    wall = time.perf_counter() - t0
    after = accounts()
    for stage in STAGES:
        grown = {k: after[stage][k]["sum"] - before[stage][k]["sum"] for k in after[stage]}
        served = after[stage]["service"]["count"] - before[stage]["service"]["count"]
        if stage.startswith("PUSH"):  # the two senders share the stage's tasks
            other = "PUSH.1" if stage == "PUSH" else "PUSH"
            served += after[other]["service"]["count"] - before[other]["service"]["count"]
        assert served == 6 * PARTS, stage
        # in service, waiting for a task (starved or gated) or taking one:
        # nothing else a stage thread does, and the account is exact but
        # for the wait or service that a window's edge cuts
        total = grown["service"] + grown["starved"] + grown["gated"] + grown["dequeue"]
        assert total == pytest.approx(wall, rel=0.015), (stage, grown, wall)
        assert 0 <= grown["dequeue"] < 0.1 * wall, (stage, grown)  # small beside the waits
        # one service in so many is read on the CPU clock too: the same ones on
        # both clocks, and on the CPU no longer than on the wall
        every = tracing.sampled.EVERY
        assert (after[stage]["cpu"]["count"] == after[stage]["wall"]["count"]
                == after[stage]["service"]["count"] // every > 0), stage
        assert 0 <= grown["cpu"] <= grown["wall"] <= grown["service"], (stage, grown)
        # the defaults gate nothing: a stage thread with no task is starved
        assert grown["gated"] == 0 and grown["starved"] > 0.5 * wall, (stage, grown)


def test_an_rpc_attempt_splits_into_send_and_reply_by_op(cluster):
    import byteps_tpu as bps

    bps.init()
    raw_rounds(3)
    pushes = spans("stage.PUSH")["count"]
    pulls = hist("span_seconds", name="stage.PULL")["count"]
    assert pushes == pulls == 3 * PARTS
    assert hist("rpc_reply_seconds", op="PUSH", server="0")["count"] == pushes
    assert hist("rpc_reply_seconds", op="PULL", server="0")["count"] == pulls
    # a PULL's request is 50 bytes inside stage.PULL: no span of its own
    assert hist("span_seconds", name="rpc.send.PULL")["count"] == 0
    # a send span is named like the stage thread that made it, and is part of
    # that thread's service; the reply's wait is not in the send
    for sender in ("PUSH", "PUSH.1"):
        sends, served = (hist("span_seconds", name=f"{kind}.{sender}")
                         for kind in ("rpc.send", "stage"))
        assert 0 < sends["count"] == served["count"] and sends["sum"] <= served["sum"], sender
    histograms = metrics().snapshot()["histograms"]
    # the round trip stays one family by server, every attempt in it once
    family = [k for k in histograms if k.startswith("rpc_round_trip_seconds")]
    assert family == ['rpc_round_trip_seconds{server="0"}']
    replies = sum(v["count"] for k, v in histograms.items() if k.startswith("rpc_reply_seconds"))
    assert histograms[family[0]]["count"] == replies  # INIT and the rest under their own op
    for op in ("PUSH", "PULL"):  # send returned → reply is no longer than send → reply
        assert hist("rpc_reply_seconds", op=op, server="0")["sum"] <= histograms[family[0]]["sum"]


def test_the_receive_threads_have_names_and_a_span_a_frame(cluster):
    import byteps_tpu as bps

    bps.init()
    raw_rounds(3)
    pulls = hist("span_seconds", name="stage.PULL")["count"]
    frames = {lane: hist("span_seconds", name=f"recv.frame.{lane}") for lane in ("push", "pull")}
    # a pull lane carries merged rounds and nothing else; every PUSH's ack
    # (and INIT's reply) comes back on the push lane
    assert frames["pull"]["count"] == pulls == 3 * PARTS
    assert frames["push"]["count"] >= spans("stage.PUSH")["count"]
    for lane, served in frames.items():
        # one frame in so many on the stage threads' clocks, the lane kind's two
        # threads under one name (tests/test_gil_account.py has the split)
        sampled = {c: hist("stage_sample_seconds", stage=f"recv.{lane}", clock=c)
                   for c in tracing.sampled.CLOCKS}
        assert 1 <= sampled["wall"]["count"] <= served["count"] // 16 + 1, lane
        assert {h["count"] for h in sampled.values()} == {sampled["wall"]["count"]}, lane
        assert sampled["wall"]["sum"] <= served["sum"], lane
    path = tracing.get_process_tracer().flush()
    with open(path) as f:
        events = [e for e in json.load(f)["traceEvents"] if e["cat"] == "span"]
    for lane in ("push", "pull"):
        tracks = {e["tid"] for e in events if e["name"] == f"recv.frame.{lane}"}
        assert tracks and all(t.startswith(f"bps-recv-{lane}-") for t in tracks), tracks
    names = {t.name for t in threading.enumerate()}
    assert {"bps-recv-push-0", "bps-recv-push-1", "bps-recv-pull-0", "bps-recv-pull-1"} <= names


def test_a_wait_is_in_the_profile_and_no_phase_of_the_benchmarks(cluster, tmp_path):
    from jax.profiler import ProfileData

    import byteps_tpu as bps

    bps.init()
    raw_rounds(1)
    log_dir = tmp_path / "profile"
    with tracing.profile(str(log_dir)):
        raw_rounds(2, pause=0.05)
    paths = list(log_dir.rglob("*.xplane.pb"))
    assert paths, "profiler wrote nothing"
    names = {e.name for plane in ProfileData.from_file(str(paths[-1])).planes
             for line in plane.lines for e in line.events}
    waits = {n for n in names if n.startswith("bpswait.")}
    assert {f"bpswait.stage.{s}.starved" for s in STAGES} <= waits, waits
    assert all(n.rsplit(".", 1)[1] in ("starved", "gated") for n in waits)
    assert {"bps.stage.PUSH", "bps.stage.PUSH.1", "bps.rpc.send.PUSH", "bps.rpc.send.PUSH.1",
            "bps.recv.frame.pull"} <= names
    # benchmark/xplane.py keeps the harness's and the program's phases alone
    spec = importlib.util.spec_from_file_location(
        "bench_xplane", os.path.join(ROOT, "benchmark", "xplane.py"))
    xplane = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(xplane)
    kept = {name for name, _, _ in xplane.load(str(log_dir))["host"]}
    assert "bps.stage.PUSH" in kept and "bps.recv.frame.pull" in kept
    assert not [n for n in kept if not n.startswith(("bps.", "bench."))], kept


# ---------------------------------------------------------------------------
# why a queue's thread is idle
# ---------------------------------------------------------------------------


def _task(key, length):
    return TensorTableEntry(tensor_name=f"t{key}", key=key, length=length)


@pytest.mark.parametrize("held_shut, why, never", [
    (True, "gated", "starved"),   # a task is there, the credit does not cover it
    (False, "starved", "gated"),  # nothing is there
])
def test_an_idle_queue_says_why(held_shut, why, never):
    q = ScheduledQueue(QueueType.PUSH, credit_bytes=64)
    if held_shut:
        q.add_task(_task(1, 1024))  # 4096 bytes against a credit of 64
    idle = _StageIdle("PUSH")
    t0 = time.perf_counter()
    assert q.get_task(timeout=0.05, waiting=idle) is None
    waited = time.perf_counter() - t0
    grown = hist("stage_idle_seconds", stage="PUSH", why=why)
    assert grown["count"] >= 1 and grown["sum"] == pytest.approx(waited, abs=0.02)
    assert hist("stage_idle_seconds", stage="PUSH", why=never)["count"] == 0
    assert q.pending() == int(held_shut)


def test_a_starved_queue_turns_gated_when_an_ineligible_task_arrives():
    q = ScheduledQueue(QueueType.PUSH, credit_bytes=64)
    seen = []

    class Waiting:
        def __call__(self, why):
            seen.append(why)
            return self

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            return False

    threading.Timer(0.05, q.add_task, args=(_task(1, 1024),)).start()
    assert q.get_task(timeout=0.3, waiting=Waiting()) is None
    assert seen[0] == "starved" and seen[-1] == "gated" and set(seen) == {"starved", "gated"}
    small = _task(2, 4)  # 16 bytes: under the credit, eligible at once, no wait
    q.add_task(small)
    assert q.get_task(timeout=0.3, waiting=Waiting()) is small and seen[-1] == "gated"


# ---------------------------------------------------------------------------
# one attempt, one observation each: a bare _AsyncRpc against a stub link
# ---------------------------------------------------------------------------


class _StubLink:
    def __init__(self):
        self.replies, self.sent = [], []

    def alloc_seq(self, cb, sink=None):
        self.replies.append(cb)
        return len(self.replies) - 1

    def send_msg(self, msg):
        self.sent.append(msg)

    def pop_cb(self, seq):
        return None


class _StubClient:
    """What ``_AsyncRpc`` asks of its client; timers fire at once."""

    def __init__(self):
        self.cfg = types.SimpleNamespace(rpc_backoff_s=0.0, rpc_retries=3, job_id=0,
                                         resync_deadline_s=0)
        self._stop = threading.Event()
        self.link = _StubLink()

    def server_for(self, key):
        return 0

    def _conn_for(self, key, revive=False):
        return self.link

    def _deadline_arm(self, sc, sid=None):
        return None

    def _deadline_clear(self, token):
        pass

    def _timer_after(self, delay, fn):
        fn()


@pytest.mark.parametrize("op", [Op.PUSH, Op.PULL])
def test_a_retried_attempt_observes_once_an_attempt_that_was_answered(op):
    client, delivered = _StubClient(), []
    rpc = ps_client._AsyncRpc(
        client, lambda seq: Message(op, key=7, seq=seq, payload=b"x" * 8), 7,
        delivered.append, None, None, None, None, False, True)
    rpc.send_attempt()
    client.link.replies[0](None)  # the connection died: retried, nothing observed
    assert len(client.link.sent) == 2 and not delivered
    # one a send, where a send has a span: a PUSH's
    assert hist("span_seconds", name=f"rpc.send.{op.name}")["count"] == (2 if op is Op.PUSH else 0)
    assert hist("rpc_reply_seconds", op=op.name, server="0")["count"] == 0
    assert hist("rpc_round_trip_seconds", server="0")["count"] == 0
    time.sleep(0.01)
    client.link.replies[1](Message(op, key=7, seq=1))
    assert len(delivered) == 1
    reply = hist("rpc_reply_seconds", op=op.name, server="0")
    trip = hist("rpc_round_trip_seconds", server="0")
    assert reply["count"] == trip["count"] == 1
    assert 0.01 <= reply["sum"] <= trip["sum"]
    assert counters().snapshot_labeled()["rpc_retry"] == {(("server", "0"),): 1}


def test_a_reply_that_beats_the_sends_return_is_timed_from_the_send():
    client, delivered = _StubClient(), []

    def answered_inside_send(msg):  # the receive thread ran first
        client.link.replies[-1](Message(Op.PUSH, key=7, seq=msg.seq))

    client.link.send_msg = answered_inside_send
    ps_client._AsyncRpc(
        client, lambda seq: Message(Op.PUSH, key=7, seq=seq), 7,
        delivered.append, None, None, None, None, False, True).send_attempt()
    reply = hist("rpc_reply_seconds", op="PUSH", server="0")
    assert len(delivered) == 1 and reply["count"] == 1
    assert reply["sum"] == pytest.approx(hist("rpc_round_trip_seconds", server="0")["sum"])


# ---------------------------------------------------------------------------
# histograms kept at hand, and the registry's reset
# ---------------------------------------------------------------------------


def test_a_held_histogram_is_made_again_after_a_reset():
    held = metrics().held("unit_held_seconds", {"stage": "PUSH"})
    assert 'unit_held_seconds{stage="PUSH"}' not in metrics().snapshot()["histograms"]
    held.observe(0.5)
    held.observe(0.25)
    got = hist("unit_held_seconds", stage="PUSH")
    assert got["count"] == 2 and got["sum"] == 0.75
    metrics().reset()
    assert hist("unit_held_seconds", stage="PUSH")["count"] == 0
    held.observe(1.0)  # not into the histogram the registry dropped
    got = hist("unit_held_seconds", stage="PUSH")
    assert got["count"] == 1 and got["sum"] == 1.0
    # and it is the registry's own: observe() by labels lands in the same one
    metrics().observe("unit_held_seconds", 2.0, labels={"stage": "PUSH"})
    assert hist("unit_held_seconds", stage="PUSH")["count"] == 2
    assert held.get() is metrics().histogram("unit_held_seconds", {"stage": "PUSH"})


def test_a_spans_histogram_survives_a_reset_between_two_spans():
    with tracing.span("unit.kept"):
        pass
    metrics().reset()
    with tracing.span("unit.kept"):
        pass
    assert hist("span_seconds", name="unit.kept")["count"] == 1


# ---------------------------------------------------------------------------
# the slow_step trigger says why, on stderr
# ---------------------------------------------------------------------------


def test_a_slow_step_writes_one_line_of_evidence_a_bundle(tmp_path, capsys):
    c = RobustnessCounters()
    reg = MetricsRegistry(counter_store=c)
    rec = FlightRecorder(capacity=64, registry=reg, counter_store=c)
    rec.bundle_dir, rec.bundle_interval_s = str(tmp_path / "bundles"), 3600.0
    for _ in range(10):
        for stage in ("PUSH", "PULL"):
            reg.observe("stage_dwell_seconds", 0.002, labels={"stage": stage})
        rec.record_step(0.01)
    capsys.readouterr()
    reg.observe("stage_dwell_seconds", 0.002, labels={"stage": "PUSH"})
    reg.observe("stage_dwell_seconds", 0.45, labels={"stage": "PULL"})  # where it went
    c.bump("rpc_retry", 2)
    c.bump("rpc_deadline_expired")
    fired = rec.record_step(0.5)  # 50 x the median
    assert "slow_step" in fired["trig"] and len(rec.bundles_written) == 1
    lines = [ln for ln in capsys.readouterr().err.splitlines() if "slow_step" in ln]
    assert len(lines) == 1, lines
    evidence = json.loads(lines[0][lines[0].index("{"):lines[0].rindex("}") + 1])
    assert evidence["dur"] == 0.5 and evidence["median"] == 0.01
    assert evidence["stage"] == "PULL" and evidence["stage_dwell_grew_s"] == pytest.approx(0.448)
    assert evidence["rpc_retry"] == 2 and evidence["rpc_deadline_expired"] == 1
    with open(os.path.join(rec.bundles_written[0], "trigger.json")) as f:
        assert json.load(f)["evidence"] == evidence  # the line is the bundle's own evidence
    rec.record_step(0.5)  # inside the bundle's rate limit: counted, no bundle, no line
    assert len(rec.bundles_written) == 1
    assert not [ln for ln in capsys.readouterr().err.splitlines() if "slow_step" in ln]
