"""The ``phases`` reader on hand-built traces (``python -m pytest
benchmark/tests -q``): idle time by phase, spans over several threads, self
time by scope, the scope paths out of an xplane file's wire format, and the
CPU rehearsal of the two PS cells."""

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


phases = load("readers", "phases.py")

CALLER = [("grad_dispatch", 0.0, 0.05), ("enqueue", 0.05, 0.1), ("hop_wait", 0.1, 0.7),
          ("reput", 0.7, 0.8), ("apply_dispatch", 0.8, 0.85)]


def hand_trace():
    """Two steps of 1.0 s, window [10, 12].  A step: the caller's phases one
    after another to t+0.85, ``hybrid.step`` to t+0.9, the harness blocked to
    t+1.0; two COPYD2H threads that overlap by 0.1 s; device 0 runs a
    ``while`` (0.2 s, its body 0.15 s of it), a slice copy under no scope
    (0.05 s) and the update (0.1 s): 0.35 s busy, 0.65 s idle."""
    spans, bench, ops = [], [], []
    for t in (10.0, 11.0):
        bench += [("bench.step.call", t, t + 0.9), ("bench.step.block", t + 0.9, t + 1.0)]
        spans.append(("bps.hybrid.step", t, t + 0.9))
        spans += [(f"bps.hybrid.{n}", t + a, t + b) for n, a, b in CALLER]
        spans += [("bps.stage.COPYD2H", t + 0.1, t + 0.3), ("bps.stage.COPYD2H", t + 0.2, t + 0.4)]
        ops += [("%while.1 = while()", t + 0.02, t + 0.22), ("%fusion.7 = fusion()", t + 0.02, t + 0.17),
                ("%slice.1 = slice()", t + 0.3, t + 0.35), ("%fusion.9 = fusion()", t + 0.86, t + 0.96)]
    ops.append(("%fusion.7 = fusion()", 9.0, 9.5))  # before the window: not counted
    spans.append(("bps.hybrid.hop_wait", 9.0, 9.9))
    paths = {"%fusion.7 = fusion()": "jit(grad)/shard_map/jvp(forward)/conv_general_dilated:",
             "%while.1 = while()": "jit(grad)/shard_map/transpose(jvp(forward))/while:",
             "%fusion.9 = fusion()": "jit(hybrid_apply)/optimizer/add:"}
    return {"spans": spans, "bench": bench, "ops": ops, "paths": paths}


def test_idle_time_is_filed_under_the_phase_that_was_open():
    trace = hand_trace()
    idle = {n: phases.measure(trace, "idle_in_ms", f"bps.hybrid.{n}") for n, _, _ in CALLER}
    assert idle["hop_wait"] == pytest.approx(430.0)  # 600 less the while's tail and the slice
    assert idle["reput"] == pytest.approx(100.0)
    assert idle["grad_dispatch"] == pytest.approx(20.0)
    assert idle["enqueue"] == pytest.approx(0.0)  # the device worked all through it
    assert idle["apply_dispatch"] == pytest.approx(50.0)
    # the caller's phases are disjoint: they partition the idle time they cover
    assert sum(idle.values()) == pytest.approx(
        phases.measure(trace, "idle_in_ms", "bps.hybrid.step") - 10.0)
    assert phases.measure(trace, "idle_in_ms", "bps.no.such.span") is None


def test_unattributed_is_what_no_phase_covers():
    # 0.01 s inside hybrid.step after the last phase and 0.04 s while the
    # harness blocks, of 0.65 s idle a step; hybrid.step itself is no phase
    assert phases.measure(hand_trace(), "idle_unattributed_share") == pytest.approx(5 / 65 * 100)
    bare = {**hand_trace(), "spans": []}  # the parent: no bps.* span at all
    assert phases.measure(bare, "idle_unattributed_share") is None


def test_overlapping_stage_threads_are_counted_once():
    covered = phases._covered(hand_trace()["spans"], lambda n: n == "bps.stage.COPYD2H", 10.0, 12.0)
    assert sum(b - a for a, b in covered) == pytest.approx(0.6)  # 300 ms a step, not 2 x 200


def test_self_time_by_scope():
    trace = hand_trace()
    assert phases.measure(trace, "scope_ms", "forward") == pytest.approx(150.0)
    # transpose(jvp(forward)) is the backward pass; the while pays for what its body leaves
    assert phases.measure(trace, "scope_ms", "backward") == pytest.approx(50.0)
    assert phases.measure(trace, "scope_ms", "optimizer") == pytest.approx(100.0)
    fused = {**trace, "paths": {k: v for k, v in trace["paths"].items() if "optimizer" not in v}}
    assert phases.measure(fused, "scope_ms", "optimizer") == 0.0  # scopes, none of them this one
    assert phases.measure({**trace, "paths": {}}, "scope_ms", "forward") is None  # no scopes
    # the program before the scopes: jax names the transpose itself, nothing says forward
    before = {**trace, "paths": {"%while.1 = while()": "jit(grad)/transpose(jvp(loss_fn))/while:"}}
    assert phases.measure(before, "scope_ms", "backward") is None


def test_scope_time_is_the_largest_over_the_devices():
    """Four chips run the same step; device 0's trace lost the update of the
    second step (the profiler drops events of a busy device), device 1's has
    both: the reading is device 1's."""
    trace = hand_trace()
    lost = [op for op in trace["ops"] if not (op[0].startswith("%fusion.9") and op[1] > 11.0)]
    trace = {**trace, "ops": lost, "device_ops": {0: lost, 1: trace["ops"]}}
    assert phases.measure(trace, "scope_ms", "optimizer") == pytest.approx(100.0)
    assert phases.measure(trace, "scope_ms", "forward") == pytest.approx(150.0)
    assert phases.measure({**trace, "device_ops": {0: lost}}, "scope_ms", "optimizer") == pytest.approx(50.0)
    # the idle quantities stay device 0's
    assert phases.measure(trace, "idle_in_ms", "bps.hybrid.reput") == pytest.approx(100.0)


@pytest.mark.parametrize("path, want", [
    ("jit(local_step)/shard_map/jvp(forward)/VGG16/Conv_0/conv_general_dilated:", "forward"),
    ("jit(step)/shard_map/transpose(jvp(forward))/while/body/dot_general:", "backward"),
    ("jit(step)/shard_map/transpose(jvp(forward))/while/body/checkpoint/rematted_computation/tanh:", "backward"),
    ("jit(step)/optimizer/add:", "optimizer"),
    ("jit(local_step)/shard_map/grad_sync/psum:", None),
    ("jit(forward_only)/mul:", None),
    ("", None),
])
def test_classify(path, want):
    assert phases.classify(path) == want


def test_a_run_without_a_trace_reads_nothing():
    assert phases.read({"trace": None}, quantity="scope_ms", match="forward") is None
    with pytest.raises(ValueError, match="no quantity"):
        phases.measure(hand_trace(), "no_such_quantity")
    assert phases.measure({**hand_trace(), "bench": []}, "idle_in_ms", "bps.hybrid.reput") is None


# ---- the scope paths out of the wire format ---------------------------------------


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def msg(field, payload):
    return varint(field << 3 | 2) + varint(len(payload)) + payload


def num(field, n):
    return varint(field << 3) + varint(n)


def entry(key, value):
    return num(1, key) + msg(2, value)


def plane(name, stat_names, events):
    body = num(1, 3) + msg(2, name.encode()) + msg(3, b"\x08\x01" * 40)  # a line: skipped whole
    for sid, sname in stat_names.items():
        body += msg(5, entry(sid, num(1, sid) + msg(2, sname.encode())))
    for eid, (ename, stats) in events.items():
        body += msg(4, entry(eid, num(1, eid) + msg(2, ename.encode()) + b"".join(msg(5, s) for s in stats)))
    return msg(1, body)


def test_scope_paths_from_the_wire_format():
    stat_names = {9: "tf_op", 300: "flops", 11: "jit(f)/optimizer/add:"}
    events = {
        1: ("%fusion.7 = f32[8]{0} fusion(%p)", [num(1, 300) + num(3, 12345),
                                                 num(1, 9) + msg(5, b"jit(f)/jvp(forward)/dot_general:")]),
        2: ("%copy-start = f32[8]{0} copy-start(%p)", [num(1, 300) + num(3, 0)]),  # no tf_op
        700: ("%fusion.9 = f32[8]{0} fusion(%q)", [num(1, 9) + num(7, 11)]),  # by reference
    }
    data = (plane("/device:TPU:0", stat_names, events)
            + plane("/host:CPU", stat_names, {5: ("host_event", [num(1, 9) + msg(5, b"jit(f)/forward/x:")])}))
    assert phases.scope_paths(data) == {
        "%fusion.7 = f32[8]{0} fusion(%p)": "jit(f)/jvp(forward)/dot_general:",
        "%fusion.9 = f32[8]{0} fusion(%q)": "jit(f)/optimizer/add:",
    }
    assert phases.scope_paths(b"") == {}


# ---- the PS cells, rehearsed on virtual CPU devices --------------------------------


@pytest.mark.parametrize("cell, devices", [("vgg16_ps", 1), ("vgg16_ps_dp4", 4)])
def test_a_ps_cell_rehearses_on_virtual_devices(cell, devices):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    for name in [k for k in env if k.startswith(("DMLC_", "BYTEPS_"))]:
        del env[name]  # a PS test before this one may have left its cluster's addresses
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell, "--seed",
         "2147483659", "--seconds", "2", "--trace", "1", "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
    assert line["device"]["count"] == devices and line["failed"] == 0
    faults = [ln for ln in done.stderr.splitlines() if "NOT CORRECT" in ln]
    assert len(faults) == 1 and "rehearsal" in faults[0], faults  # nothing else was wrong
    # every number compared stands beside its limit, last in the line and last on stderr
    assert list(line)[-1] == "compared" and all(c["ok"] for c in line["compared"].values())
    assert {"h2d_bytes_off", "d2h_bytes_off", "wire_tx_bytes_off", "wire_rx_bytes_off"} <= set(line["compared"])
    last = done.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all("] compared " in ln and " limit " in ln for ln in last), last
    assert sum("] steps " in ln and "median" in ln for ln in done.stderr.splitlines()) == 1
    got = line["rehearsal"]
    for name in ("two_level_step.hop_wait_ms", "two_level_step.reput_ms", "two_level_step.enqueue_ms",
                 "host_engine.copyd2h_wait_ms", "host_engine.copyh2d_wait_ms", "host_engine.finalize_ms",
                 "ps_plane.push_wait_ms", "ps_plane.pull_wait_ms",
                 "ps_plane.push_reply_ms", "ps_plane.pull_reply_ms",
                 "ps_plane.wire_mb_per_step", "host_engine.h2d_mb_per_step",
                 "host_engine.prefetched_parts_per_step"):
        assert got[name]["value"] > 0, name
    assert got["ps_plane.wire_mb_per_step"]["value"] == 2 * got["host_engine.h2d_mb_per_step"]["value"]
    assert got["ps_plane.journal_copied_mb_per_step"]["value"] == 0
    assert got["two_level_step.slow_step_share"]["value"] >= 0
    assert not [k for k in got if k.startswith(("train_step.forward", "mesh_collectives"))]  # no TPU plane
