"""The reader ``gil`` on hand-built input, the five entries that read the two
processes' GIL accounts, and those entries in the CPU rehearsal of the two PS
cells (``python -m pytest benchmark/tests -q``, by hand)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
spec = importlib.util.spec_from_file_location("bench_readers_gil", os.path.join(HERE, "readers", "gil.py"))
gil = importlib.util.module_from_spec(spec)
spec.loader.exec_module(gil)

HOP = 'span_seconds{name="hybrid.hop_wait"}'
ENTRIES = ["two_level_step.hop_gil_held_share", "two_level_step.hop_gil_wait_ms",
           "ps_plane.server_gil_held_share", "ps_plane.server_recv_service_ms",
           "ps_plane.server_engine_service_ms"]


def sample(stage, clock, labels=""):
    return f'stage_sample_seconds{{clock="{clock}"{labels},stage="{stage}"}}'


def hand_run():
    """Four steps, a hop of 2.0 s in all.  PUSH served 2.0 s and held the GIL
    through a quarter of what it sampled; a lane kind's two receive threads
    served 1.0 s together and held it through half."""
    push, recv = 'span_seconds{name="stage.PUSH"}', 'span_seconds{name="recv.frame.pull"}'
    after = {HOP: {"sum": 3.0}, push: {"sum": 2.5}, recv: {"sum": 1.0},
             sample("PUSH", "wall"): {"sum": 0.4}, sample("PUSH", "held"): {"sum": 0.1},
             sample("PUSH", "gilwait"): {"sum": 0.2},
             sample("recv.pull", "wall"): {"sum": 0.08}, sample("recv.pull", "held"): {"sum": 0.04},
             sample("recv.pull", "gilwait"): {"sum": 0.0}}
    before = {HOP: {"sum": 1.0}, push: {"sum": 0.5}}
    threads = [{"service": [push], "stage": "PUSH"}, {"service": [recv], "stage": "recv.pull"}]
    return {"steps": 4, "histograms": {"before": before, "after": after}}, threads


@pytest.mark.parametrize("args, want", [
    ({"clock": "held", "scale": 100, "over": [HOP]}, (2.0 * 0.25 + 1.0 * 0.5) / 2.0 * 100),
    ({"clock": "gilwait", "scale": 1000}, (2.0 * 0.5 + 0.0) / 4 * 1000),
    ({"clock": "held"}, (2.0 * 0.25 + 1.0 * 0.5) / 4),
], ids=["a_share_of_the_hop", "milliseconds_a_step", "seconds_a_step"])
def test_a_threads_sampled_share_is_scaled_to_its_service_and_summed(args, want):
    run, threads = hand_run()
    assert gil.read(run, threads=threads, **args) == pytest.approx(want)


def test_a_servers_series_are_read_under_its_labels():
    labels = ',rank="0",role="server"'
    serve = f'thread_seconds{{kind="serve"{labels},state="service"}}'
    run = {"steps": 2, "histograms": {"before": {}, "after": {
        HOP: {"sum": 1.0}, serve: {"sum": 0.8},
        sample("serve", "wall", labels): {"sum": 0.05}, sample("serve", "held", labels): {"sum": 0.01},
        # the worker's own series of the same stage name are not the server's
        sample("serve", "wall"): {"sum": 1.0}, sample("serve", "held"): {"sum": 1.0}}}}
    threads = [{"service": [serve], "stage": "serve"}]
    assert gil.read(run, threads=threads, clock="held", scale=100, over=[HOP],
                    labels=labels) == pytest.approx(0.8 * 0.2 / 1.0 * 100)


@pytest.mark.parametrize("spoil", [
    lambda run, threads: run["histograms"]["after"].pop(sample("PUSH", "held")),  # the parent: no such clock
    lambda run, threads: run["histograms"]["after"].pop(HOP),
    lambda run, threads: run["histograms"]["after"].pop(threads[1]["service"][0]),
    lambda run, threads: run["histograms"]["before"].update(  # nothing sampled in the window
        {sample("PUSH", "wall"): run["histograms"]["after"][sample("PUSH", "wall")]}),
    lambda run, threads: run["histograms"]["before"].update({HOP: {"sum": 3.0}}),  # no hop in it
], ids=["no_held_clock", "no_hop", "no_service", "nothing_sampled", "no_hop_time"])
def test_a_program_without_the_instrument_reads_nothing(spoil):
    run, threads = hand_run()
    spoil(run, threads)
    assert gil.read(run, threads=threads, clock="held", scale=100, over=[HOP]) is None


def test_no_step_reads_nothing_a_step():
    run, threads = hand_run()
    assert gil.read({**run, "steps": 0}, threads=threads, clock="gilwait") is None


def test_the_five_entries_stand_in_both_ps_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-5:]] == ENTRIES and len(entries) == 126
    for name in ENTRIES:
        m = entries[name]
        assert (m["workloads"], m["moves"], m["source"], m["better"]) == (
            ["vgg16_ps", "vgg16_ps_dp4"], "samples_per_s", "program_span", "lower"), name
        assert m["unit"] == ("%" if name.endswith("_share") else "ms"), name
        with open(os.path.join(HERE, "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == ("histogram_per_step" if "service_ms" in name else "gil"), name
    with open(os.path.join(HERE, "metrics", f"{ENTRIES[0]}.json")) as f:
        threads = json.load(f)["args"]["threads"]
    # nine threads in seven entries: a lane kind's two receive threads share a series
    assert [t["stage"] for t in threads] == ["COPYD2H", "PUSH", "PUSH.1", "PULL", "COPYH2D",
                                             "recv.push", "recv.pull"]


@pytest.mark.parametrize("cell, devices", [("vgg16_ps", 1), ("vgg16_ps_dp4", 4)])
def test_a_ps_cells_rehearsal_reads_both_processes_accounts(cell, devices):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    for name in [k for k in env if k.startswith(("DMLC_", "BYTEPS_"))]:
        del env[name]  # a PS test before this one may have left its cluster's addresses
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell, "--seed",
         "2147483671", "--seconds", "2", "--trace", "1", "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])["rehearsal"]
    value = lambda name: got[name]["value"]  # noqa: E731
    for name in ENTRIES:
        assert name in got and value(name) >= 0, name
    # one GIL a process: no share passes 100 but by the sampling's noise
    assert 0 < value("two_level_step.hop_gil_held_share") <= 105
    assert 0 < value("ps_plane.server_gil_held_share") <= 105
    assert value("ps_plane.server_recv_service_ms") > 0 < value("ps_plane.server_engine_service_ms")
