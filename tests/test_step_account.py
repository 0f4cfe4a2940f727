"""The compiled step's own account (ISSUE 54; docs/observability.md "Reading a
slow step"): ``core/tracing.stepped`` keeps what ``jax.jit`` made, puts its
call under ``train.dispatch`` and the host's work on its outputs under
``train.fold``, clocks every step against the one before, and hands the
interval to the flight recorder's light entry — whose ``slow_step`` rule says
on stderr which side of the device a slow step was on, without ever reading
the routing counters of the step in flight."""

import gc
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu import optim
from byteps_tpu.core import flightrec, tracing
from byteps_tpu.core.flightrec import HOST_DELTAS, FlightRecorder
from byteps_tpu.core.telemetry import (
    MetricsRegistry,
    RobustnessCounters,
    counters,
    metrics,
)
from byteps_tpu.models import conv_moe
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.mesh_utils import make_training_mesh

EVEN = 0.02  # seconds between two calls of an even loop


@pytest.fixture(autouse=True)
def recorder(tmp_path):
    """The process's recorder for one test: bundles under ``tmp_path``, the
    rule's own constants, and no rate limit (a step that the machine under
    the tests made slow must not take the one line of the step a test made
    slow: the tests pick theirs by its number)."""
    counters().reset()
    metrics().reset()
    rec = FlightRecorder(capacity=64)
    rec.bundle_dir, rec.bundle_interval_s = str(tmp_path / "bundles"), 0.0
    flightrec.set_process_recorder(rec)
    yield rec
    flightrec.set_process_recorder(None)
    counters().reset()
    metrics().reset()


def hist(family, **labels):
    key = family + ("{" + ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
                    if labels else "")
    return metrics().snapshot()["histograms"].get(key, {"count": 0, "sum": 0.0})


def slow_lines(capsys, step=None):
    """The evidence of every ``slow_step`` line on stderr since the last look
    (``step``: of that step's alone)."""
    lines = [ln for ln in capsys.readouterr().err.splitlines() if "slow_step" in ln]
    found = [json.loads(ln[ln.index("{"):ln.rindex("}") + 1]) for ln in lines]
    return [e for e in found if step in (None, e.get("step"))]


def fired() -> int:
    return sum(n for labels, n in counters().snapshot_labeled().get("flight_trigger", {}).items()
               if dict(labels).get("rule") == "slow_step")


class Standin:
    """What ``jax.jit`` would have made, as far as the seam's clock can tell:
    a call takes ``self.takes`` seconds."""

    takes = 0.0

    def __call__(self, x):
        if self.takes:
            time.sleep(self.takes)
        return x + 1


def even_steps(step, n=10, x=0):
    for _ in range(n):
        x = step(x)
        time.sleep(EVEN)
    return x


# ---------------------------------------------------------------------------
# the seam keeps the compiled function and counts one a call
# ---------------------------------------------------------------------------


def test_the_seam_keeps_what_jit_made_and_counts_one_a_call():
    jitted = jax.jit(lambda x: x * 2.0)
    step = tracing.stepped(jitted)
    x = jnp.ones((4,))
    assert step.lower(x).as_text() == jitted.lower(x).as_text()
    assert step.eval_shape(x).shape == (4,)  # every attribute of the jitted function
    step.optimizer = "kept"  # as build_data_parallel_step sets it
    assert step.optimizer == "kept"
    with pytest.raises(AttributeError):
        step.no_such_attribute
    for _ in range(5):
        x = step(x)
    np.testing.assert_array_equal(x, np.full((4,), 32.0))
    assert hist("span_seconds", name="train.dispatch")["count"] == 5
    assert hist("train_step_interval_seconds")["count"] == 4  # an interval has two ends
    assert hist("span_seconds", name="train.fold")["count"] == 0  # nothing to fold


def test_every_step_builder_of_optim_returns_through_the_seam():
    mesh = make_training_mesh(1, {"dp": 1}, devices=jax.devices()[:1])
    tx = optax.sgd(0.1)
    step = optim.build_data_parallel_step(
        lambda p, b: jnp.mean((b @ p["w"]) ** 2), tx, mesh=mesh, donate=False)
    assert isinstance(step, tracing.stepped) and step.optimizer is tx
    params = {"w": jnp.ones((3, 2))}
    batch = jnp.ones((4, 3))
    assert "stablehlo" in step.lower(params, tx.init(params), batch).as_text()
    state = tx.init(params)
    for _ in range(3):
        params, state, loss = step(params, state, batch)
    assert np.isfinite(float(loss))
    assert hist("span_seconds", name="train.dispatch")["count"] == 3
    _, zero1 = optim.build_zero1_step(
        lambda p, b: jnp.mean((b @ p["w"]) ** 2), tx, mesh=mesh, donate=False)
    assert isinstance(zero1, tracing.stepped)


def test_a_loops_last_step_is_closed_at_shutdown(recorder):
    """A two-step loop has two intervals: the second step has no next entry
    to end it, ``close_steps()`` (``bps.shutdown()``, interpreter exit) does,
    and the recorder that feeds ``slow_step`` holds both."""
    step = tracing.stepped(Standin())
    x = step(step(0))
    assert x == 2 and hist("train_step_interval_seconds")["count"] == 1
    time.sleep(EVEN)
    tracing.close_steps()
    intervals = hist("train_step_interval_seconds")
    assert intervals["count"] == 2 and intervals["sum"] >= EVEN
    assert [r["host"]["step"] for r in recorder.snapshot()] == [1, 2]
    assert [r["host"].get("closed", False) for r in recorder.snapshot()] == [False, True]
    tracing.close_steps()  # closed is closed: nothing is counted twice
    assert hist("train_step_interval_seconds")["count"] == 2
    step(x)  # a call after the close starts a loop of its own: no interval yet
    assert hist("train_step_interval_seconds")["count"] == 2


def test_the_fold_has_its_span_and_gives_the_caller_what_is_left():
    step = tracing.stepped(lambda x: (x, "counts"), fold=lambda out: out[:1])
    for i in range(3):
        assert step(i) == (i,)
    assert hist("span_seconds", name="train.fold")["count"] == 3
    assert hist("span_seconds", name="train.dispatch")["count"] == 3


def test_the_first_calls_arguments_go_through_the_hook_once():
    """``first`` (``build_train_step``'s commit of an uncommitted optimizer
    state) sees the first call's arguments, inside its dispatch span, and what
    it returns is what the jitted function is called with; a later call pays
    one test for it and is handed on as it came."""
    seen = []

    def first(x, y):
        seen.append((x, y))
        return x + 1, y

    step = tracing.stepped(lambda x, y: (x, y), first=first)
    assert step(1, "a") == (2, "a")
    assert [step(1, "b"), step(5, "c")] == [(1, "b"), (5, "c")]
    assert seen == [(1, "a")] and step._first is None
    assert hist("span_seconds", name="train.dispatch")["count"] == 3


def test_the_dispatch_span_ties_the_profilers_clock_to_the_wall_clock():
    seen = []
    real = tracing.TraceAnnotation

    class Seen(real):
        def __init__(self, name, **stats):
            seen.append((name, stats))
            super().__init__(name, **stats)

    tracing.TraceAnnotation = Seen
    try:
        step = tracing.stepped(lambda: None)
        before = time.time_ns()
        step(), step()
    finally:
        tracing.TraceAnnotation = real
    assert [name for name, _ in seen] == ["bps.train.dispatch"] * 2
    assert [stats["step"] for _, stats in seen] == [1, 2]
    assert all(before <= stats["wall_ns"] <= time.time_ns() for _, stats in seen)


# ---------------------------------------------------------------------------
# a slow step says which side of the device it was on
# ---------------------------------------------------------------------------


def test_a_sleep_inside_the_call_reads_where_dispatch(capsys):
    work = Standin()
    step = tracing.stepped(work)
    x = even_steps(step)
    work.takes = 0.25
    x = step(x)  # the slow one
    work.takes = 0.0
    assert not slow_lines(capsys, step=11)  # a step is over when the next one begins
    step(x)
    (evidence,) = slow_lines(capsys, step=11)
    assert evidence["where"] == "dispatch"
    assert 0.25 <= evidence["dispatch_s"] <= evidence["interval_s"]
    assert evidence["caller_s"] < 0.5 * evidence["dispatch_s"] and evidence["fold_s"] == 0
    assert EVEN <= evidence["median_s"] < evidence["interval_s"] / 3
    assert set(HOST_DELTAS) <= set(evidence)
    assert fired() >= 1


def test_a_sleep_between_two_calls_reads_where_caller_on_an_idle_thread(capsys):
    step = tracing.stepped(Standin())
    x = even_steps(step)
    x = step(x)
    time.sleep(0.25)  # the caller's wait: block_until_ready on a late device
    step(x)
    (evidence,) = slow_lines(capsys, step=11)
    assert evidence["where"] == "caller" and evidence["caller_s"] >= 0.25
    assert evidence["dispatch_s"] < 0.1
    # the calling thread slept: it was not on the CPU, no collection, no page fetched back
    assert evidence["cpu_thread_s"] < 0.05 and evidence["gc_s"] == 0 and evidence["gc_n"] == 0
    assert evidence["nvcsw"] >= 1  # it gave the CPU up itself


def test_a_collection_between_two_calls_shows_in_gc_s(capsys):
    step = tracing.stepped(Standin())
    x = even_steps(step)
    x = step(x)
    cycles = []
    for _ in range(200_000):  # a large cycle for the collector to walk
        a, b = [], []
        a.append(b), b.append(a)
        cycles.append(a)
    del cycles, a, b
    gc.collect()
    step(x)
    (evidence,) = slow_lines(capsys, step=11)
    assert evidence["gc_s"] > 0 and evidence["gc_n"] >= 1
    # what the recorder counted, each inside the next on ONE clock: the pause
    # lies in the caller's own time, and that in the interval.  (Not the
    # thread's CPU time against the pause: under six xdist workers the
    # collector is off the CPU for most of its wall time, and the ratio of
    # the two clocks says how loaded the machine is, not where the step went.)
    assert evidence["where"] == "caller"
    assert evidence["gc_s"] <= evidence["caller_s"] <= evidence["interval_s"]
    assert evidence["caller_s"] > max(evidence["dispatch_s"], evidence["fold_s"])
    assert hist("gc_pause_seconds", generation="2")["count"] >= 1
    assert hist("gc_pause_seconds", generation="2")["sum"] > 0


def test_a_second_slow_step_inside_the_rate_limit_is_counted_and_prints_nothing(capsys, recorder):
    recorder.bundle_interval_s = 60.0  # the default: one line a rule a minute
    step = tracing.stepped(Standin())
    x = even_steps(step)
    for _ in range(2):
        x = step(x)
        time.sleep(0.25)
    x = step(x)
    assert len(slow_lines(capsys)) == 1 and fired() >= 2
    assert len(recorder.bundles_written) == 1
    # ... unless it is slower again by the rule's own factor than the one that
    # printed: a step of 3 x the median must not hide one of 50 x behind it
    time.sleep(1.0)
    step(x)
    (worse,) = slow_lines(capsys)
    assert worse["step"] == 13 and worse["interval_s"] >= 1.0 and fired() >= 3
    # the bundle is the ring and the evidence; no registry snapshot (it would
    # wait for the routing statistics of the step in flight)
    bundle = recorder.bundles_written[0]
    assert sorted(os.listdir(bundle)) == ["config.json", "ledger.jsonl", "trigger.json"]
    with open(os.path.join(bundle, "ledger.jsonl")) as f:
        ledger = [json.loads(line) for line in f]
    assert all(r["k"] == "train" and set(HOST_DELTAS) <= set(r["host"]) for r in ledger)
    assert [r["host"]["step"] for r in ledger if r["trig"]][-1:] == [11]  # dumped at the first
    assert len(recorder.bundles_written) == 2


def test_a_capacity_of_zero_turns_the_rule_off_and_leaves_the_instruments(capsys):
    flightrec.set_process_recorder(FlightRecorder(capacity=0))
    step = tracing.stepped(Standin())
    x = even_steps(step)
    x = step(x)
    time.sleep(0.2)
    step(x)
    assert not slow_lines(capsys) and fired() == 0
    assert hist("span_seconds", name="train.dispatch")["count"] == 12
    assert hist("train_step_interval_seconds")["count"] == 11


def test_a_compute_process_gets_a_recorder_and_a_later_ps_plane_makes_its_own():
    flightrec.set_process_recorder(None)
    step = tracing.stepped(Standin())
    step(0), step(1)
    made = flightrec.get_process_recorder()
    assert made is not None and [r["k"] for r in made.snapshot()] == ["train"]
    context = lambda: {"epoch": 7}  # noqa: E731
    planes = flightrec.ensure_process_recorder(context_fn=context)  # from ITS configuration
    assert planes is not made and planes is flightrec.get_process_recorder()
    assert planes.record_step(0.01)["epoch"] == 7
    assert flightrec.ensure_process_recorder(context_fn=lambda: {}) is planes  # as before
    step(2)  # and the step's intervals go where the process's records go
    assert [r["k"] for r in planes.snapshot()] == ["step", "train"]


def test_intervals_and_engine_rounds_keep_their_own_medians(recorder, capsys):
    for _ in range(10):
        recorder.record_step(0.001)  # PS rounds of a millisecond
    step = tracing.stepped(Standin())
    even_steps(step)  # 20 ms: 20 x a round, and no business of the rounds' median
    assert not [e for e in slow_lines(capsys) if e["median_s"] < EVEN]
    assert recorder.record_step(0.0015)["trig"] == []  # nor the intervals of the rounds'


def test_the_rolling_median_is_the_median_of_the_last_sixty_four():
    import statistics

    rng = np.random.default_rng(54)
    rolling, seen = flightrec._Rolling(), []
    for value in rng.integers(0, 50, size=300).tolist():  # ties among them
        rolling.append(float(value))
        seen.append(float(value))
        assert len(rolling) == min(len(seen), 64)
        assert rolling.median() == statistics.median(seen[-64:])


# ---------------------------------------------------------------------------
# the step path never waits for the step in flight
# ---------------------------------------------------------------------------


def test_the_step_path_never_takes_a_snapshot_of_the_routing_counters(monkeypatch, capsys,
                                                                       recorder):
    called = []

    def snapshot(self):
        called.append(True)
        raise AssertionError("a counter snapshot waits for the step in flight")

    monkeypatch.setattr(moe.RoutingCounters, "_snapshot", snapshot)
    cfg = conv_moe.tiny_conv_moe()
    params = conv_moe.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_training_mesh(1, {"dp": 1, "pp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    tx = optax.sgd(1e-3)
    step = tfm.build_train_step(cfg, mesh, tx, donate=False)
    assert isinstance(step, tracing.stepped)
    tokens = jnp.zeros((2, cfg.max_seq), jnp.int32)
    assert "stablehlo" in step.lower(params, tx.init(params), tokens, tokens).as_text()
    state = tx.init(params)
    params, state, loss = jax.block_until_ready(step(params, state, tokens, tokens))  # compiles
    began = time.perf_counter()
    for _ in range(11):
        params, state, loss = jax.block_until_ready(step(params, state, tokens, tokens))
    time.sleep(max(0.5, 6 * (time.perf_counter() - began) / 11))
    out = step(params, state, tokens, tokens)
    assert len(out) == 3 and np.isfinite(float(out[2]))  # the counts stay with the fold
    (evidence,) = slow_lines(capsys, step=12)
    assert evidence["where"] == "caller" and recorder.bundles_written
    # thirteen steps, one of them fired and dumped a bundle: not one snapshot
    assert not called
    monkeypatch.undo()
    assert hist("span_seconds", name="train.fold")["count"] == 13
    # what the fold took off the steps is in the counters all the same
    assert moe.routing_counters()._snapshot()["moe_slots_routed"] > 0


# ---------------------------------------------------------------------------
# the PS path's slow_step asks the host the same question
# ---------------------------------------------------------------------------


def test_a_slow_ps_round_carries_what_the_host_did_meanwhile(tmp_path, capsys):
    c = RobustnessCounters()
    reg = MetricsRegistry(counter_store=c)
    rec = FlightRecorder(capacity=64, registry=reg, counter_store=c)
    rec.bundle_dir = str(tmp_path / "ps_bundles")
    for _ in range(10):
        rec.record_step(0.01)
    cycles = [[] for _ in range(50_000)]
    for a in cycles:
        a.append(a)
    del cycles, a
    gc.collect()
    assert "slow_step" in rec.record_step(0.5)["trig"]
    (evidence,) = slow_lines(capsys)
    assert evidence["dur"] == 0.5 and evidence["median"] == 0.01
    # the process's readings; a round ends on whichever thread took its last reply
    assert set(HOST_DELTAS) - set(evidence) == {"cpu_thread_s"}
    assert evidence["gc_n"] >= 1 and evidence["gc_s"] > 0
    assert evidence["cpu_process_s"] >= evidence["gc_s"] * 0.5
