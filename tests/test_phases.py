"""Phases on the profiler's clock (docs/observability.md): ``tracing.span``
and ``tracing.profile``, the spans of ``HybridDataParallel.step`` and the
engine's stage threads, ``stage_wait_seconds``, and the named scopes inside
the compiled steps."""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from byteps_tpu.common.config import Config
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.core import tracing
from byteps_tpu.core.telemetry import counters, metrics
from byteps_tpu.server.server import PSServer


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
    key = family + "{" + ",".join(f'{k}="{v}"' for k, v in labels.items()) + "}"
    return metrics().snapshot()["histograms"].get(key, {"count": 0, "sum": 0.0})


def span_events(tracer_file):
    with open(tracer_file) as f:
        events = json.load(f)["traceEvents"]
    return sorted((e for e in events if e["cat"] == "span"), key=lambda e: e["ts"])


class TestSpan:
    def test_observes_once_per_exit_and_leaves_a_disabled_tracer_alone(self, tmp_path):
        tracer = tracing.Tracer(enabled=False, trace_dir=str(tmp_path))
        tracing.set_process_tracer(tracer)
        for _ in range(3):
            with tracing.span("unit.outer", k=1):
                assert tracing.current_span() is None  # ids are minted only when traced
                with tracing.span("unit.inner"):
                    pass
        assert hist("span_seconds", name="unit.outer")["count"] == 3
        assert hist("span_seconds", name="unit.inner")["count"] == 3
        assert hist("span_seconds", name="unit.inner")["sum"] <= hist(
            "span_seconds", name="unit.outer")["sum"]
        assert tracer.pending_events() == 0 and tracer.flush() == ""
        assert not list(tmp_path.rglob("*"))

    def test_observes_when_the_block_raises(self):
        with pytest.raises(KeyError):
            with tracing.span("unit.raises"):
                raise KeyError("x")
        assert hist("span_seconds", name="unit.raises")["count"] == 1

    def test_nests_through_the_thread_local_parent(self, tmp_path):
        tracer = tracing.Tracer(enabled=True, trace_dir=str(tmp_path))
        tracing.set_process_tracer(tracer)
        seen = {}

        def other_thread():
            seen["other"] = tracing.current_span()  # the parent is per thread
            with tracing.span("unit.stage", parent=seen["outer"], key=7):
                seen["stage"] = tracing.current_span()

        with tracing.span("unit.outer", step=5):
            seen["outer"] = tracing.current_span()
            with tracing.span("unit.inner"):
                seen["inner"] = tracing.current_span()
            assert tracing.current_span() == seen["outer"]
            t = threading.Thread(target=other_thread)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        assert tracing.current_span() is None and seen["other"] is None
        by_name = {e["name"]: e["args"] for e in span_events(tracer.flush())}
        outer, inner, stage = (by_name[f"unit.{n}"] for n in ("outer", "inner", "stage"))
        assert "parent" not in outer and outer["step"] == 5
        assert inner["parent"] == outer["span"] == stage["parent"] and stage["key"] == 7
        assert inner["trace"] == outer["trace"] == stage["trace"]  # one step, one id
        assert seen["inner"][0] == seen["outer"][0] == seen["stage"][0]
        assert len({outer["span"], inner["span"], stage["span"]}) == 3

    def test_a_span_is_found_in_a_cpu_profile(self, tmp_path):
        """The ``bps.*`` annotation lands in the profiler's own trace, with
        its attributes as stats (was tests/test_data.py's profiler test)."""
        from jax.profiler import ProfileData

        with tracing.profile(str(tmp_path)):
            with tracing.span("demo_region", step=3):
                _ = jnp.sum(jnp.ones(16)).block_until_ready()
        paths = list(tmp_path.rglob("*.xplane.pb"))
        assert paths, "profiler wrote nothing"
        found = [
            dict(e.stats)
            for plane in ProfileData.from_file(str(paths[0])).planes
            for line in plane.lines for e in line.events if e.name == "bps.demo_region"
        ]
        assert len(found) == 1 and int(found[0]["step"]) == 3


# ---------------------------------------------------------------------------
# the two-level step over the in-process PS plane
# ---------------------------------------------------------------------------

PHASES = ["hybrid.grad_dispatch", "hybrid.enqueue", "hybrid.hop_wait", "hybrid.reput",
          "hybrid.apply_dispatch", "hybrid.loss_sync"]
STAGES = ["COPYD2H", "PUSH", "PULL", "COPYH2D"]


@pytest.fixture
def traced_cluster(monkeypatch, tmp_path):
    """1 worker / 1 server in-process, the tracer on, small partitions so
    that one leaf makes several tasks a stage."""
    monkeypatch.setenv("BYTEPS_TRACE_ON", "1")
    monkeypatch.setenv("BYTEPS_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "4096")
    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    srv = PSServer(Config.from_env())
    threading.Thread(target=srv.start, daemon=True).start()
    yield tmp_path
    srv.stop()
    sched.stop()


def run_hybrid(steps):
    import byteps_tpu as bps
    from byteps_tpu.parallel.hybrid import HybridDataParallel

    bps.init()
    rng = np.random.default_rng(3)
    params = {"w1": rng.normal(0, 0.3, (64, 48)).astype(np.float32),
              "w2": rng.normal(0, 0.3, (48, 8)).astype(np.float32)}
    batch = (rng.normal(size=(8, 64)).astype(np.float32),
             rng.normal(size=(8, 8)).astype(np.float32))
    hdp = HybridDataParallel(
        lambda p, b: jnp.mean((jnp.tanh(b[0] @ p["w1"]) @ p["w2"] - b[1]) ** 2),
        params, optax.sgd(0.1), mesh=Mesh(np.array(jax.devices()[:2]), ("dp",)),
        batch_spec=(P("dp"), P("dp")),
    )
    losses = [hdp.step(batch) for _ in range(steps)]
    # a step is done when its last partition is; the sender that pushed that
    # one (two share the stage over tcp) closes its service span a moment later
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and sum(
            hist("span_seconds", name=n)["count"] for n in ("stage.PUSH", "stage.PUSH.1")
    ) < hist("stage_dwell_seconds", stage="PUSH")["count"]:
        time.sleep(0.005)
    tracer = tracing.get_process_tracer()
    path = tracer.flush()
    bps.shutdown()
    assert losses[-1] < losses[0]
    return hdp, path


class TestHybridPhases:
    def test_each_phase_once_a_step_in_order_inside_the_step(self, traced_cluster):
        steps = 3
        _, path = run_hybrid(steps)
        for name in ["hybrid.step"] + PHASES:
            assert hist("span_seconds", name=name)["count"] == steps, name
        spans = span_events(path)
        whole = [e for e in spans if e["name"] == "hybrid.step"]
        assert [e["args"]["step"] for e in whole] == [1, 2, 3]
        assert all(abs(e["args"]["wall_ns"] * 1e-3 - e["ts"]) < 1e6 for e in whole)  # one clock
        assert len({e["args"]["trace"] for e in whole}) == steps
        for step in whole:
            inside = [e for e in spans if e["args"].get("parent") == step["args"]["span"]
                      and e["name"].startswith("hybrid.")]
            assert [e["name"] for e in inside] == PHASES  # sorted by start: in order
            lo, hi = step["ts"], step["ts"] + step["dur"]
            assert all(lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1 for e in inside)
            assert all(e["args"]["trace"] == step["args"]["trace"] for e in inside)
            # submit runs on the caller's thread: every job of the step hangs
            # its stage spans under hybrid.enqueue, in the step's trace
            enqueue = inside[1]["args"]["span"]
            tasks = [e for e in spans if e["args"].get("parent") == enqueue]
            assert {e["name"] for e in tasks} == set(STAGES) | {"engine.finalize"}
            assert all(e["args"]["trace"] == step["args"]["trace"] for e in tasks)
            # and the stage thread's service span hangs under its task's span
            # (by either of the two senders a tcp link has: ISSUE 39)
            served = [e for e in spans if e["name"] in ("stage.PUSH", "stage.PUSH.1")
                      and e["args"]["trace"] == step["args"]["trace"]]
            push = {e["args"]["span"] for e in tasks if e["name"] == "PUSH"}
            assert served and {e["args"]["parent"] for e in served} == push
            assert all(e["tid"].startswith("bps-PUSH") for e in served)

    def test_a_stage_waits_no_longer_than_it_dwells(self, traced_cluster):
        hdp, _ = run_hybrid(2)
        leaves = len(jax.tree.leaves(hdp.params))
        for stage in STAGES:
            # a stage's threads: PUSH's second sender observes under "PUSH.1"
            threads = [stage, stage + ".1"] if stage == "PUSH" else [stage]
            wait, served = (
                {k: sum(h[k] for h in hs) for k in ("count", "sum")}
                for hs in ([hist("stage_wait_seconds", stage=t) for t in threads],
                           [hist("span_seconds", name=f"stage.{t}") for t in threads]))
            dwell = hist("stage_dwell_seconds", stage=stage)
            assert wait["count"] == dwell["count"] == served["count"] > 2 * leaves, stage
            assert 0 <= wait["sum"] <= dwell["sum"], stage
        assert hist("span_seconds", name="engine.finalize")["count"] == 2 * leaves

    @pytest.mark.parametrize("axes", [{"dp": 2}, {"dp": 4}, {"dp": 2, "tp": 2}],
                             ids=["dp2", "dp4", "dp2_tp2"])
    def test_an_averaged_leaf_comes_back_where_the_step_wants_it(
            self, traced_cluster, monkeypatch, axes):
        """ISSUE 67: the engine makes a leaf's average in the sharding its
        gradient was submitted in, so what ``hybrid.reput`` (still a span a
        step) is left with for a replicated leaf is a put of an array onto
        the sharding it has — the same array back, nothing moved.  A
        tensor-parallel leaf's gradient is sharded for real: its average
        comes back on one device and the span's put still places it."""
        import byteps_tpu as bps
        from byteps_tpu.parallel import hybrid

        steps, tp = 2, "tp" in axes
        bps.init()
        rng = np.random.default_rng(3)
        params = {"b": np.zeros(8, np.float32),
                  "w1": rng.normal(0, 0.3, (64, 48)).astype(np.float32),
                  "w2": rng.normal(0, 0.3, (48, 8)).astype(np.float32)}
        batch = (rng.normal(size=(8, 64)).astype(np.float32),
                 rng.normal(size=(8, 8)).astype(np.float32))

        def loss_fn(p, b):
            out = jnp.tanh(b[0] @ p["w1"]) @ p["w2"]  # column- then row-parallel under tp
            return jnp.mean(((jax.lax.psum(out, "tp") if tp else out) + p["b"] - b[1]) ** 2)

        specs = {"b": P(), "w1": P(None, "tp") if tp else P(), "w2": P("tp", None) if tp else P()}
        devices = np.array(jax.devices()[:int(np.prod(list(axes.values())))])
        hdp = hybrid.HybridDataParallel(
            loss_fn, params, optax.sgd(0.1), mesh=Mesh(devices.reshape(*axes.values()), tuple(axes)),
            param_specs=specs, batch_spec=(P("dp"), P("dp")))

        # the step's own puts: the engine's are of host buffers
        reputs, applied = [], []
        real_put, real_apply = jax.device_put, hdp._apply

        def put(x, *args, **kwargs):
            out = real_put(x, *args, **kwargs)
            if isinstance(x, jax.Array):
                reputs.append((x, out))
            return out

        def apply(p, s, g):
            applied.append(g)
            return real_apply(p, s, g)

        monkeypatch.setattr(jax, "device_put", put)
        hdp._apply = apply
        before = counters().snapshot().get("h2d_sharded_parts", 0)
        try:
            losses = [hdp.step(batch) for _ in range(steps)]
        finally:
            bps.shutdown()
        assert losses[-1] < losses[0]
        assert hist("span_seconds", name="hybrid.reput")["count"] == steps

        replicated = [name for name, spec in specs.items() if spec == P()]
        assert len(reputs) == steps * len(params) and len(applied) == steps
        for g in applied:
            for name, leaf in g.items():
                assert leaf.sharding.is_equivalent_to(hdp._shardings[name], leaf.ndim), name
        for i, (came, went) in enumerate(reputs):
            name = sorted(params)[i % len(params)]  # a dict's leaves, in key order
            if name in replicated:
                assert went is came, name  # a put onto the sharding it has: nothing moved
            else:
                assert len(came.sharding.device_set) == 1 and went is not came, name
        # w1's three partitions and w2's | b's one each, cut over the leaf's devices
        parts = {"b": 1, "w1": 3, "w2": 1}
        assert (counters().snapshot().get("h2d_sharded_parts", 0) - before
                == steps * sum(parts[name] for name in replicated))


# ---------------------------------------------------------------------------
# named scopes inside the compiled steps
# ---------------------------------------------------------------------------


def _flax_dp_step():
    from byteps_tpu.optim import build_flax_data_parallel_step

    def apply_fn(variables, x, train=True, mutable=()):
        return x @ variables["params"]["w"], {}

    tx = optax.sgd(0.1)
    step = build_flax_data_parallel_step(
        apply_fn, lambda out, y: jnp.mean((out - y) ** 2), tx,
        mesh=Mesh(np.array(jax.devices()[:2]), ("dp",)), donate=False)
    variables = {"params": {"w": jnp.ones((4, 4))}}
    batch = (jnp.ones((8, 4)), jnp.ones((8, 4)))
    return step.lower(variables, tx.init(variables["params"]), batch)


def _transformer_step():
    from byteps_tpu.models import transformer
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, d_ff=32, n_layers=2, max_seq=8)
    mesh = make_training_mesh(n_devices=2, axis_sizes={"dp": 2, "pp": 1, "sp": 1, "tp": 1})
    tx = optax.adamw(1e-3)
    params = transformer.shard_params(transformer.init_params(cfg), cfg, mesh)
    tokens = jnp.zeros((2, 8), jnp.int32)
    step = transformer.build_train_step(cfg, mesh, tx, donate=False)
    return step.lower(params, tx.init(params), tokens, tokens)


def _hybrid(which):
    from byteps_tpu.parallel.hybrid import HybridDataParallel

    params = {"w": np.ones((4, 4), np.float32)}
    hdp = HybridDataParallel(
        lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2), params, optax.sgd(0.1),
        mesh=Mesh(np.array(jax.devices()[:2]), ("dp",)), batch_spec=(P("dp"), P("dp")))
    if which == "grad":
        return hdp._grad.lower(hdp.params, (jnp.ones((8, 4)), jnp.ones((8, 4))))
    return hdp._apply.lower(hdp.params, hdp.opt_state, hdp.params)


@pytest.mark.parametrize("lowered, scopes", [
    (_flax_dp_step, ["jvp(forward)", "transpose(jvp(forward))", "grad_sync", "optimizer"]),
    # build_train_step writes no gradient psum (shard_map's transpose does it,
    # as in HybridDataParallel._grad): it has no grad_sync of its own to name
    (_transformer_step, ["jvp(forward)", "transpose(jvp(forward))", "optimizer"]),
    (lambda: _hybrid("grad"), ["jvp(forward)", "transpose(jvp(forward))"]),
    (lambda: _hybrid("apply"), ["jit(hybrid_apply)/optimizer"]),
], ids=["flax_data_parallel_step", "transformer_train_step", "hybrid_grad", "hybrid_apply"])
def test_the_compiled_steps_carry_their_scopes(lowered, scopes):
    import byteps_tpu as bps

    bps.init()  # HybridDataParallel declares its tensors
    try:
        text = lowered().as_text(debug_info=True)
    finally:
        bps.shutdown()
    for scope in scopes:
        assert f"/{scope}/" in text or f'"{scope}/' in text, scope
