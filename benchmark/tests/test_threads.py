"""The readers ``histogram_per_step`` and ``threads`` on hand-built input
(``python -m pytest benchmark/tests -q``), and the thread-by-thread metrics
in the CPU rehearsal of the two PS cells."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def load(*parts):
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location("bench_" + "_".join(parts)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


threads = load("readers", "threads.py")
per_step = load("readers", "histogram_per_step.py")

#: the 21 metrics the account adds, by what can read them: the program's own
#: histograms (a rehearsal has them) and the TPU's trace (it has not)
FROM_HISTOGRAMS = [
    f"{layer}.{stage}_{what}_ms"
    for what in ("service", "cpu")
    for layer, stage in (("host_engine", "copyd2h"), ("host_engine", "copyh2d"),
                         ("ps_plane", "push"), ("ps_plane", "pull"))
] + ["ps_plane.push_starved_ms", "ps_plane.pull_starved_ms", "host_engine.copyh2d_starved_ms",
     "ps_plane.push_gated_ms", "ps_plane.push_send_ms", "ps_plane.push_reply_ms",
     "ps_plane.pull_reply_ms", "ps_plane.recv_service_ms", "ps_plane.push_wait_ms",
     "ps_plane.pull_wait_ms"]
FROM_THE_TRACE = ["two_level_step.hop_uncovered_ms", "two_level_step.hop_threads_in_service"]
#: what timed the same thing from outside, retired by PR 70 (PERF.md section 3)
RETIRED = ["two_level_step.exposed_exchange_ms", "ps_plane.push_pull_dwell_ms",
           "ps_plane.push_pull_wait_ms", "ps_plane.rpc_round_trip_ms",
           "host_engine.copyd2h_busy_ms", "host_engine.copyd2h_dwell_ms",
           "host_engine.copyh2d_dwell_ms", "two_level_step.step_ms_p50",
           "two_level_step.idle_in_push_service_ms"]


def test_histogram_per_step():
    push, pull = 'span_seconds{name="recv.frame.push"}', 'span_seconds{name="recv.frame.pull"}'
    run = {"steps": 4, "histograms": {
        "before": {push: {"sum": 1.0, "count": 10}},
        "after": {push: {"sum": 3.0, "count": 20}, pull: {"sum": 6.0, "count": 30}}}}
    assert per_step.read(run, keys=[push], scale=1000) == pytest.approx(500.0)  # 2 s over 4 steps
    assert per_step.read(run, keys=[push, pull], scale=1000) == pytest.approx(2000.0)  # summed
    assert per_step.read(run, keys=[push, "absent"]) is None  # a program without the instrument
    assert per_step.read({**run, "steps": 0}, keys=[push]) is None
    # a share of it: the sampled services' CPU seconds over their wall seconds
    cpu, wall = 'stage_sample_seconds{clock="cpu",stage="PUSH"}', 'stage_sample_seconds{clock="wall",stage="PUSH"}'
    run["histograms"]["after"].update({cpu: {"sum": 0.3, "count": 5}, wall: {"sum": 0.4, "count": 5}})
    assert per_step.read(run, keys=[push], scale=1000, share={"of": [cpu], "in": [wall]}) == pytest.approx(375.0)
    assert per_step.read(run, keys=[push], share={"of": ["absent"], "in": [wall]}) is None
    run["histograms"]["before"][wall] = {"sum": 0.4, "count": 5}  # nothing sampled in the window
    assert per_step.read(run, keys=[push], share={"of": [cpu], "in": [wall]}) is None
    # a thread's whole account beyond the window's counted time (the profiler's
    # start and stop, when no step runs) is taken off its wait
    starved, served = 'stage_idle_seconds{stage="PUSH",why="starved"}', 'span_seconds{name="stage.PUSH"}'
    traced = {"steps": 10, "window_s": 4.0, "histograms": {"before": {}, "after": {
        starved: {"sum": 2.5, "count": 9}, served: {"sum": 2.4, "count": 9}}}}
    assert per_step.read(traced, keys=[starved], scale=1000) == pytest.approx(250.0)
    assert per_step.read(traced, keys=[starved], scale=1000, account=[served, starved]) == pytest.approx(160.0)
    assert per_step.read({**traced, "window_s": 5.0}, keys=[starved], scale=1000,
                         account=[served, starved]) == pytest.approx(250.0)  # nothing beyond it
    assert per_step.read(traced, keys=[starved], account=[served, "absent"]) is None
    zero = {"steps": 4, "histograms": {"before": {}, "after": {push: {"sum": 0.0, "count": 0}}}}
    assert per_step.read(zero, keys=[push]) == 0.0  # there and never observed: 0, not nothing


def hand_trace():
    """Two steps of 1.0 s, window [10, 12]; the hop is [t+0.1, t+0.7] of each.
    Device 0 runs [t, t+0.2] and [t+0.8, t+0.9].  In the hop: PUSH serves
    [t+0.15, t+0.45] with its send nested inside, a receive thread
    [t+0.35, t+0.55] (0.1 s beside PUSH), COPYH2D [t+0.55, t+0.6] with the
    finalize nested in it: [t+0.6, t+0.7] nobody serves, nor [t+0.1, t+0.15],
    of which the device idles through [t+0.2, t+0.7] only."""
    by_thread = {"caller": [], "push": [], "recv": [], "h2d": []}
    bench, ops = [], []
    for t in (10.0, 11.0):
        bench += [("bench.step.call", t, t + 0.9), ("bench.step.block", t + 0.9, t + 1.0)]
        by_thread["caller"] += [("bps.hybrid.step", t, t + 0.9), ("bps.hybrid.enqueue", t + 0.05, t + 0.1),
                                ("bps.hybrid.hop_wait", t + 0.1, t + 0.7)]
        by_thread["push"] += [("bps.stage.PUSH", t + 0.15, t + 0.45), ("bps.rpc.send.PUSH", t + 0.2, t + 0.4)]
        by_thread["recv"] += [("bps.recv.frame.pull", t + 0.35, t + 0.55)]
        by_thread["h2d"] += [("bps.stage.COPYH2D", t + 0.55, t + 0.6), ("bps.engine.finalize", t + 0.57, t + 0.6)]
        ops += [("%fusion.1 = fusion()", t, t + 0.2), ("%fusion.9 = fusion()", t + 0.8, t + 0.9)]
    by_thread["push"].append(("bps.stage.PUSH", 9.0, 9.9))  # before the window: not counted
    return {"threads": by_thread, "bench": bench, "ops": ops}


def test_idle_with_no_thread_in_service_and_threads_at_once():
    trace = hand_trace()
    # idle in the hop [0.2, 0.7]; served [0.15, 0.6] by someone: [0.6, 0.7] is nobody's
    assert threads.measure(trace, "hop_uncovered_ms") == pytest.approx(100.0)
    # 0.3 + 0.2 + 0.05 of service (nested spans of one thread once) in a hop of 0.6
    assert threads.measure(trace, "threads_in_service") == pytest.approx(0.55 / 0.6)


def test_two_threads_serving_all_through_the_hop_read_two():
    trace = hand_trace()
    for t in (10.0, 11.0):
        trace["threads"]["push"].append(("bps.stage.PUSH", t + 0.05, t + 0.75))  # past both ends
        trace["threads"]["recv"].append(("bps.recv.frame.pull", t + 0.1, t + 0.7))
        trace["threads"]["h2d"] = []
    assert threads.measure(trace, "threads_in_service") == pytest.approx(2.0)
    assert threads.measure(trace, "hop_uncovered_ms") == pytest.approx(0.0)


def test_a_program_without_receive_spans_or_without_a_hop_reads_nothing():
    trace = hand_trace()
    trace["threads"]["recv"] = []  # the parent: its receive threads open no span
    assert threads.measure(trace, "hop_uncovered_ms") is None
    assert threads.measure(trace, "threads_in_service") is None
    trace = hand_trace()
    trace["threads"]["caller"] = [e for e in trace["threads"]["caller"] if "hop_wait" not in e[0]]
    assert threads.measure(trace, "threads_in_service") is None
    assert threads.measure({"threads": {}, "bench": [], "ops": []}, "hop_uncovered_ms") is None
    assert threads.read({"trace": None}, quantity="hop_uncovered_ms") is None  # a rehearsal
    with pytest.raises(ValueError):
        threads.measure(hand_trace(), "no_such_quantity")


def test_the_account_is_20_entries_over_both_ps_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert len(FROM_HISTOGRAMS + FROM_THE_TRACE) == 20
    assert not [name for name in RETIRED if name in entries]
    for name in FROM_HISTOGRAMS + FROM_THE_TRACE:
        assert entries[name]["workloads"] == ["vgg16_ps", "vgg16_ps_dp4"], name
        assert entries[name]["moves"] == "samples_per_s", name
        assert os.path.exists(os.path.join(HERE, "metrics", f"{name}.json")), name


@pytest.mark.parametrize("cell, devices", [("vgg16_ps", 1), ("vgg16_ps_dp4", 4)])
def test_a_ps_cells_rehearsal_reads_the_threads_account(cell, devices):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    for name in [k for k in env if k.startswith(("DMLC_", "BYTEPS_"))]:
        del env[name]  # a PS test before this one may have left its cluster's addresses
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell, "--seed",
         "2147483659", "--seconds", "2", "--trace", "1", "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])["rehearsal"]
    for name in FROM_HISTOGRAMS:
        assert name in got and got[name]["value"] >= 0, name
    assert not [name for name in FROM_THE_TRACE if name in got]  # no TPU plane on the CPU
    ms = lambda name: got[name]["value"]  # noqa: E731
    for layer, stage in (("host_engine", "copyd2h"), ("host_engine", "copyh2d"),
                         ("ps_plane", "push"), ("ps_plane", "pull")):
        assert 0 < ms(f"{layer}.{stage}_cpu_ms") <= ms(f"{layer}.{stage}_service_ms"), stage
    assert 0 < ms("ps_plane.push_send_ms") <= ms("ps_plane.push_service_ms")
    assert ms("ps_plane.push_gated_ms") == 0 and ms("ps_plane.push_starved_ms") > 0
    assert not [name for name in RETIRED if name in got]
    # one chip cuts nothing over its devices; four put every partition cut
    assert (ms("host_engine.sharded_parts_per_step") > 0) == (devices == 4)
