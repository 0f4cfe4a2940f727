"""The ``step_rest`` reader on a hand-built trace (``python -m pytest
benchmark/tests -q``): every operation of a step is filed once — under
``lm_head`` (the MTP module's too), ``embed``, ``dense_mlp``, by its name (a
``ragged-dot`` with no path), or as unscoped — and the fourth phase holds what
``phases`` files under none of its three; and the ten metric files of ISSUE 54
name readers and cells that are there."""

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_readers_{name}", os.path.join(HERE, "readers", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


step_rest, phases, latent_moe = load("step_rest"), load("phases"), load("latent_moe")

STEP = "jit(train_step)/jit(main)/jit(shmap_body)/"
#: name → (seconds a step, scope path); one operation after another
OPS = {
    "%gather.1 = gather()": (0.010, STEP + "jvp(forward)/embed/gather:"),
    "%fusion.2 = fusion()": (0.100, STEP + "jvp(forward)/while/body/mla_attention/dot_general:"),
    "%fusion.3 = fusion()": (0.050, STEP + "jvp(forward)/while/body/dense_mlp/mul:"),
    "%ragged-dot.1 = custom-call()": (0.060, ""),  # XLA:TPU gives it no path
    "%fusion.4 = fusion()": (0.040, STEP + "jvp(forward)/lm_head/checkpoint/dot_general:"),
    "%fusion.5 = fusion()": (0.030, STEP + "jvp(forward)/mtp/lm_head/checkpoint/dot_general:"),
    "%fusion.6 = fusion()": (0.020, STEP + "jvp(forward)/psum:"),  # under forward alone
    "%fusion.7 = fusion()": (0.080, STEP + "transpose(jvp(forward))/lm_head/checkpoint/transpose:"),
    "%scatter.1 = scatter()": (0.015, STEP + "transpose(jvp(forward))/embed/scatter-add:"),
    "%copy.1 = copy()": (0.025, ""),  # a parameter's copy: no path either
    "%fusion.9 = fusion()": (0.070, "jit(train_step)/optimizer/add:"),
}


def hand_trace(ops=OPS):
    """Two steps of 1.0 s, window [10, 12]; a step runs ``ops`` back to back
    from t+0.1 inside ``bps.train.dispatch`` (t .. t+0.05) and the harness's
    call and block."""
    trace = {"spans": [], "bench": [], "ops": [], "paths": {n: p for n, (_, p) in ops.items() if p}}
    for t in (10.0, 11.0):
        trace["bench"] += [("bench.step.call", t, t + 0.06), ("bench.step.block", t + 0.06, t + 1.0)]
        trace["spans"].append(("bps.train.dispatch", t, t + 0.05))
        at = t + 0.1
        for name, (seconds, _) in ops.items():
            trace["ops"].append((name, at, at + seconds))
            at += seconds
    return trace


def test_every_operation_of_the_step_is_filed_once():
    trace = hand_trace()
    head = step_rest.measure(trace, "scope_ms", "lm_head")
    assert head == pytest.approx(40 + 30 + 80)  # forward, the MTP module's, the backward pass's
    assert step_rest.measure(trace, "scope_ms", "embed") == pytest.approx(10 + 15)
    assert step_rest.measure(trace, "scope_ms", "dense_mlp") == pytest.approx(50)
    assert step_rest.measure(trace, "named_ms", prefix="ragged-dot") == pytest.approx(60)
    # the psum under forward alone and the copy under nothing
    assert step_rest.measure(trace, "unscoped_ms") == pytest.approx(20 + 25)
    attention = latent_moe.measure(trace, "scope_ms", "mla_attention")
    optimizer = phases.measure(trace, "scope_ms", "optimizer")
    assert (attention, optimizer) == (pytest.approx(100), pytest.approx(70))
    busy = sum(seconds for seconds, _ in OPS.values()) * 1e3
    # the identity of ISSUE 54: the family's scopes (mtp is first for its reader:
    # the MTP head is in both, the overlap named in head_loss_ms's file) + the rest
    mtp = latent_moe.measure(trace, "scope_ms", "mtp")
    assert mtp == pytest.approx(30)
    assert attention + mtp + head - mtp + 25 + 50 + 60 + optimizer + 45 == pytest.approx(busy)


def test_the_fourth_phase_is_what_the_three_leave():
    trace = hand_trace()
    three = sum(phases.measure(trace, "scope_ms", p) for p in ("forward", "backward", "optimizer"))
    rest = step_rest.measure(trace, "no_phase_ms")
    assert rest == pytest.approx(60 + 25)  # the grouped product and the copy: no path at all
    assert three + rest == pytest.approx(sum(seconds for seconds, _ in OPS.values()) * 1e3)
    synced = {**OPS, "%all-reduce.1 = all-reduce()": (0.009, "jit(step)/grad_sync/psum:")}
    assert step_rest.measure(hand_trace(synced), "no_phase_ms") == pytest.approx(60 + 25)


def test_a_program_without_the_scopes_reads_nothing_and_does_not_raise():
    bare = {n: (s, p.replace("/lm_head", "").replace("/embed", "").replace("/dense_mlp", ""))
            for n, (s, p) in OPS.items() if not n.startswith("%ragged-dot")}
    trace = hand_trace(bare)
    for match in step_rest.SCOPES:
        assert step_rest.measure(trace, "scope_ms", match) is None
    assert step_rest.measure(trace, "named_ms", prefix="ragged-dot") is None
    # what the parent leaves under no name: the head, the loss, the embedding ...
    assert step_rest.measure(trace, "unscoped_ms") == pytest.approx(10 + 50 + 40 + 20 + 80 + 15 + 25)
    unscoped_step = {n: (s, "") for n, (s, p) in OPS.items()}  # from before the forward scope
    assert step_rest.measure(hand_trace(unscoped_step), "no_phase_ms") is None
    assert step_rest.measure({**trace, "bench": []}, "unscoped_ms") is None
    assert step_rest.read({"trace": None}, "unscoped_ms") is None  # a rehearsal
    with pytest.raises(ValueError):
        step_rest.measure(trace, "no_such_quantity")


def test_idle_time_under_the_dispatch_span_is_filed_there():
    # the device idles t .. t+0.1: half of it inside bps.train.dispatch
    assert phases.measure(hand_trace(), "idle_in_ms", "bps.train.dispatch") == pytest.approx(50.0)


def test_the_named_scopes_are_the_readers_own():
    want = set(step_rest.SCOPES) | {"optimizer", "grad_sync"}
    for name in step_rest.FAMILY_READERS:
        want |= set(load(name).SCOPES)
    # the looped family's reader files what stands under its loop and no scope inside it
    # (``loop_carry``), so the loop's own scope is a name too
    assert step_rest.named_scopes() == want | {"loop_steps"}
    assert load("looped_dense").LOOP == "loop_steps"
    # every family with a reader of its own is known here: nothing of its mixers is "unscoped"
    own = {f[:-3] for f in os.listdir(os.path.join(HERE, "readers"))
           if f.endswith(".py") and hasattr(load(f[:-3]), "SCOPES")} - {"step_rest"}
    assert own == set(step_rest.FAMILY_READERS)
    assert "dense_mlp" in load("conv_moe").SCOPES  # the name the leading SwiGLU already had


ISSUE_54 = ("train_step.head_loss_ms", "train_step.embed_ms", "train_step.dense_mlp_ms",
            "train_step.grouped_products_ms", "train_step.unscoped_ms", "train_step.no_phase_ms",
            "train_step.dispatch_ms", "train_step.fold_ms", "train_step.idle_in_dispatch_ms",
            "moe.rows_walked_per_step")


@pytest.mark.parametrize("name", ISSUE_54)
def test_a_new_metric_names_a_reader_and_cells_that_are_there(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    # a compiled step's metric: no PS cell lists it
    assert all(cells[c]["traffic"] == "local_closed" for c in entry["workloads"])
    with open(os.path.join(HERE, "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reader = load(spec["reader"])
    assert callable(reader.read)
    run = {"trace": None, "steps": 0, "window_s": 1.0, "histograms": {"before": {}, "after": {}},
           "counters": {"before": {}, "after": {}}}
    assert reader.read(run, **spec["args"]) is None  # nothing to read: None, no raise
