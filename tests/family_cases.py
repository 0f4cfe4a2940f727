"""What the model families' test files share: the mesh, the seeded state, the
system's loss and gradients through ``build_train_step``, the distance of two
gradient trees, the ``tiny(variant)`` cache, and the cases that every family
has — written once against a ``Family`` record of what differs.  pytest does
not collect this file; ``tests/test_<family>.py`` holds the record, its
variants and the cases no other family has, and takes the shared ones with
``globals().update(family_cases(FAMILY))`` (``builder_cases`` for the cell's
blocked reference, in the ``_pieces`` file).

**The budget of a family's tests** (tier-1 runs ``-m 'not slow'`` on six
``xdist`` workers, ``--dist load``, cold, inside 1470 s; the cost is
XLA's compile time, which grows with the depth a case compiles and with the
number of distinct programs it makes, not with widths or tokens):

- a new family is a record here and its own cases: at most 150 case-seconds
  on those six workers, and no case over 30 s;
- a variant that is made twice (system and reference ``value_and_grad``)
  compiles two whole models: a few layers, every kind once.  A published
  pattern is walked whole by ``stacks()`` in pure Python
  (``test_the_published_pattern_builds_its_stacks``) and compiled as its
  distinct prefix and one period;
- where a property needs more than that, the heavy form is a ``slow``
  parameter of the same test and a lighter twin that fails for the same
  reason stays in tier-1 (``Family.slow``; CHANGES.md lists each pair and the
  break that turns the twin red);
- cases that read one lowered text or one compiled function share it through
  a module-scoped fixture; a reference walked once runs under ``jax.jit`` (one
  program, not an eager program an operation and shape);
- ``--dist load`` deals the cases one by one, in collection order, to
  whichever worker is free: a dozen heavy cases at the end of the order are
  the run's tail on however few workers take them, so heavy cases go where
  many light ones follow.
"""

import dataclasses
import functools
import importlib.util
import json
import os
import types
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu as bps
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.mesh_utils import make_training_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(dp=1):
    return make_training_mesh(dp, {"dp": dp, "pp": 1, "sp": 1, "tp": 1},
                              devices=jax.devices()[:dp])


def _worst(got, want):
    """(relative L2 distance, leaf) of the leaf that is furthest off."""
    def rel(k):
        scale = float(jnp.linalg.norm(want[k]))
        diff = float(jnp.linalg.norm(got[k] - want[k]))
        return diff / scale if scale else diff
    return max((rel(k), k) for k in got)


def _worst_entry(got, want):
    """(largest entry's distance over the leaf's largest entry, leaf)."""
    return max((float(np.abs(got[k] - want[k]).max() / np.abs(want[k]).max()), k) for k in got)


def _norms(name):
    return "norm" in name


def _state(model, cfg, seed=0, batch=4, bias=None, moved=_norms):
    """Seeded parameters with the leaves ``moved`` names (the norms' scales)
    off their starting values and, where ``bias`` is given, a selection bias
    of that standard deviation; tokens; next-token targets.  Leaf by leaf and
    NOT under ``jax.jit``: a process compiles an operation once a shape, so a
    family's second variant finds every program made (as one program it is
    3–9 s of compiling a variant)."""
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    for i, name in enumerate(params):
        k = jax.random.PRNGKey(seed + 100 + i)
        if bias is not None and name.endswith("router_bias"):
            params[name] = bias * jax.random.normal(k, params[name].shape)
        elif moved(name):
            params[name] = params[name] + 0.1 * jax.random.normal(k, params[name].shape)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, cfg.max_seq), 0, cfg.vocab_size)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


#: the gradient is the update, negated, and the "optimizer's" state: read from
#: the state it is exact (``params − new`` loses ``dt_bias``'s and ``A_log``'s
#: gradients in the subtraction's rounding: 1e-4 of leaves that start at −7 to 3)
_KEPT = optax.GradientTransformation(
    lambda p: jax.tree.map(jnp.zeros_like, p),
    lambda g, state, p=None: (jax.tree.map(jnp.negative, g), g))


@functools.cache
def _step(cfg, dp):
    """One compiled step a (config, mesh): variants that differ by their state
    alone (a skewed bias) share it."""
    return tfm.build_train_step(cfg, _mesh(dp), _KEPT, donate=False)


@functools.cache
def _reference(ref, cfg):
    return jax.jit(jax.value_and_grad(lambda p, *batch: ref.loss(cfg, p, *batch)))


def _system_loss_and_grads(cfg, params, *batch, dp=1, counted=None):
    """Through build_train_step itself; the update reaches the parameters.
    ``counted``: a dict that takes what the step added to the process's counters."""
    step = _step(cfg, dp)
    before = bps.get_robustness_counters()
    new, grads, loss = jax.device_get(step(params, _KEPT.init(params), *batch))
    if counted is not None:
        after = bps.get_robustness_counters()
        counted.update({k: after[k] - before.get(k, 0) for k in after})
    for k in params:  # off the mesh: dp 2 leaves them on two devices
        np.testing.assert_allclose(new[k], np.asarray(params[k]) - grads[k], rtol=1e-6,
                                   atol=1e-6 * float(np.abs(grads[k]).max()), err_msg=k)
    return float(loss), {k: np.asarray(v) for k, v in grads.items()}


def _pallas_calls(jaxpr):
    """(kernel name, operand shapes) of every ``pallas_call`` of a jaxpr,
    nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], [x.aval.shape for x in eqn.invars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def _kernel_names(fn, *args):
    return sorted(name for name, _ in _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr))


@dataclasses.dataclass(frozen=True)
class Family:
    """What differs between the families' copies of the shared cases."""
    name: str
    model: types.ModuleType
    ref: types.ModuleType
    tiny: Callable  # tiny(**overrides) → config
    variants: dict  # name → overrides, or (overrides, the selection bias's deviation)
    state: Callable  # (config, seed=, batch=, bias=) → (params, *the batch's leaves)
    #: variants whose heavy form has a lighter twin among ``variants``
    slow: tuple = ()
    batch: int = 4
    #: the reference's logits, None where the family's own test holds them
    ref_logits: Callable = None
    logits_atol: float = 1e-4
    #: the furthest leaf's gradient off the reference's: measure and limit,
    #: with the reason where it is not the common one
    grad_off: Callable = _worst
    grad_tol: float = 2e-4
    #: (config, leaf) → whether its gradient is non-zero; None: not held
    learns: Callable = lambda cfg, name: None
    #: {suffix: sibling's}: a leaf whose gradient is 0 in the mathematics and
    #: rounding in the reference, under 1e-5 of its sibling's; left out of the distance
    rounding: dict = dataclasses.field(default_factory=dict)
    #: variants whose dp-2 run is held to the reference too
    dp2_to_reference: tuple = ()
    #: (variant, limit) of the dp 2 against dp 1 case
    dp2: tuple = None
    #: {mesh axis: the refusal's words}
    refused_axes: dict = dataclasses.field(default_factory=dict)
    #: [(overrides, the refusal's words)]
    refused: tuple = ()
    #: (overrides, layers, the first layers' kinds, {stack: layers}, more(config))
    published: tuple = None
    #: config → the layers that route; None: no shared counters case
    routing_layers: Callable = None
    #: counters beside the routing statistics that a traced step moves
    also_counts: tuple = ()

    def of(self, variant):
        """(config, its state) of a variant."""
        entry = self.variants[variant]
        overrides, bias = entry if isinstance(entry, tuple) else (entry, None)
        cfg = self.tiny(**overrides)
        kw = {} if bias is None else {"bias": bias}
        deep = getattr(cfg, "n_layers", 0) > 8  # compiled whole: fewer rows
        return cfg, self.state(cfg, batch=min(self.batch, 2 if deep else 4), **kw)

    def params_of(self, names, *more, tag=""):
        """``names`` (each with ``more``) as parameters of a case: ids name
        family and variant, the heavy forms marked slow."""
        return [pytest.param(n, *more, id=f"{self.name}-{n}{tag}",
                             marks=[pytest.mark.slow] if n in self.slow else [])
                for n in names]


def family_cases(fam: Family) -> dict:
    """The ``tiny`` fixture and the shared cases of a family, by name."""
    cases = {}

    def case(fn):
        cases[fn.__name__] = fn
        return fn

    @pytest.fixture(scope="module")
    def tiny():
        """``tiny(variant)`` → that variant's config and state, with the
        system's and the reference's loss and gradients made once and shared
        by the cases: ``.system(dp)`` and ``.reference()`` each return (loss,
        gradients)."""
        made = {}

        def of(variant):
            if variant not in made:
                cfg, state = fam.of(variant)
                runs, counted = {}, {}  # counted: what the newest system run added to the counters

                def system(dp=1):
                    if dp not in runs:
                        runs[dp] = _system_loss_and_grads(cfg, *state, dp=dp, counted=counted)
                    return runs[dp]

                def reference():
                    if "ref" not in runs:
                        runs["ref"] = _reference(fam.ref, cfg)(*state)
                    return runs["ref"]

                made[variant] = types.SimpleNamespace(
                    cfg=cfg, state=state, params=state[0], tokens=state[1], targets=state[2],
                    system=system, reference=reference, counted=counted)
            return made[variant]

        return of

    cases["tiny"] = tiny

    if fam.ref_logits is not None:
        @case
        @pytest.mark.parametrize("variant", fam.params_of(sorted(fam.variants)))
        def test_logits_match_reference(tiny, variant):
            t = tiny(variant)
            got = tfm.build_forward(t.cfg, _mesh())(t.params, t.tokens)[0]
            want = jax.jit(lambda p, x: fam.ref_logits(t.cfg, p, x))(t.params, t.tokens)
            assert got.shape == t.tokens.shape + (t.cfg.vocab_size,)
            np.testing.assert_allclose(got, want,
                                       atol=fam.logits_atol * float(jnp.abs(want).max()))

    @case
    @pytest.mark.parametrize("variant,dp", fam.params_of(sorted(fam.variants), 1)
                             + fam.params_of(fam.dp2_to_reference, 2, tag="-dp2"))
    def test_loss_and_every_leaf_gradient_match_reference(tiny, variant, dp):
        """f32: what is left is the order of sums (a blocked loss, chunks
        against tokens, a grouped product against a loop), a few 1e-5 of a
        leaf's gradient at most."""
        t = tiny(variant)
        loss, grads = t.system(dp)
        want_loss, want = t.reference()
        assert loss == pytest.approx(float(want_loss), rel=1e-5)
        assert set(grads) == set(want) == set(fam.model.layouts(t.cfg))
        for name, g in grads.items():
            learns = fam.learns(t.cfg, name)
            assert learns is None or bool(np.any(g)) == learns, name
        for suffix, sibling in fam.rounding.items():
            for name in [n for n in grads if n.endswith(suffix)]:
                beside = name[:-len(suffix)] + sibling
                assert np.abs(want[name]).max() < 1e-5 * np.abs(want[beside]).max(), name
        held = {k: g for k, g in grads.items() if not k.endswith(tuple(fam.rounding))}
        off, leaf = fam.grad_off(held, want)
        assert off < fam.grad_tol, f"{leaf} is {off:.2e} of its gradient off the reference's"

    if fam.dp2 is not None:
        @case
        @pytest.mark.parametrize("variant", fam.params_of([fam.dp2[0]]))
        def test_same_loss_and_gradients_at_dp2_as_at_dp1(tiny, variant):
            t = tiny(variant)
            loss1, grads1 = t.system(dp=1)
            loss2, grads2 = t.system(dp=2)
            assert loss2 == pytest.approx(loss1, rel=1e-6)
            off, leaf = _worst(grads2, grads1)
            assert off < fam.dp2[1], f"{leaf} differs by {off:.2e} between dp 1 and dp 2"

    if fam.refused_axes:
        @case
        @pytest.mark.parametrize("axis", [pytest.param(a, id=f"{fam.name}-{a}")
                                          for a in fam.refused_axes])
        def test_mesh_axes_that_are_not_built_are_refused(axis):
            sizes = {"dp": 1, "pp": 1, "sp": 1, "tp": 1, axis: 2}
            mesh = make_training_mesh(2, sizes, devices=jax.devices()[:2])
            with pytest.raises(ValueError, match=fam.refused_axes[axis]):
                tfm.build_train_step(fam.tiny(), mesh, optax.sgd(1.0))

    if fam.refused:
        @case
        @pytest.mark.parametrize("overrides, match", [
            pytest.param(o, m, id=f"{fam.name}-{m.replace(' ', '_')}") for o, m in fam.refused])
        def test_patterns_and_shares_that_cannot_be_are_refused(overrides, match):
            with pytest.raises(ValueError, match=match):
                fam.tiny(**overrides)

    if fam.published is not None:
        @case
        def test_the_published_pattern_builds_its_stacks():
            overrides, layers, first_kinds, stacks, more = fam.published
            cfg = fam.tiny(**overrides)
            assert cfg.n_layers == layers
            assert cfg.kinds()[:len(first_kinds)] == first_kinds
            assert {k: n for k, (n, _) in fam.model.stacks(cfg).items()} == stacks
            more(cfg, {k: s for k, (s, _, _) in fam.model.layouts(cfg).items()})

    if fam.routing_layers is not None:
        @case
        def test_routing_counts_reach_the_programs_counters(tiny):
            t = tiny("held_share_of_experts")
            t.system()
            # of the one step through build_train_step; a counter nothing moved is not there
            grown = {k: t.counted.get(k, 0) for k in moe.ROUTING_STATS + fam.also_counts}
            slots = t.tokens.size * t.cfg.top_k * fam.routing_layers(t.cfg)
            assert grown["moe_slots_routed"] == slots
            assert 0 < grown["moe_slots_held"] < slots and grown["moe_slots_dropped"] == 0
            # the chunks that ran
            assert grown["moe_slots_held"] <= grown["moe_rows_walked"] <= slots
            assert 0 < grown["moe_fullest_expert_slots"] <= grown["moe_slots_held"]
            # a traced call of a scan's one form counts itself
            assert all(grown[k] > 0 for k in fam.also_counts)

    return cases


# ---------------------------------------------------------------------------
# the cell's blocked reference (benchmark/builders/*.py) at toy widths
# ---------------------------------------------------------------------------


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def builder_cases(name, ref, state, builder, config, toy, blocks=(), windows=None,
                  never_learns=(), precision=(1e-7, 2e-2)) -> dict:
    """The ``rehearsal`` fixture — ``builder``'s module, ``config``'s rehearsal
    at the ``toy`` widths (the blocking is what is under test, the widths are
    not), its model config, seeded parameters and a batch — and the cases that
    hold the builder's blocked copy to the plain reference ``ref``: with
    ``blocks`` set smaller than the sequence, so that every loop has several
    turns, at each of ``windows`` = (the config's key, the sizes by id).
    ``never_learns``: leaves that pick and take no gradient, left out of the
    distance.  ``precision``: the least and the most that bf16 operands may
    move the loss (rounded somewhere, and not lost)."""
    cases = {}

    def case(fn):
        cases[fn.__name__] = fn
        return fn

    @pytest.fixture(scope="module")
    def rehearsal():
        module = _load(f"benchmark/builders/{builder}.py", f"test_{builder}_builder")
        with open(os.path.join(ROOT, f"benchmark/configs/{config}.json")) as f:
            cfg = json.load(f)
        cfg.update(cfg["rehearsal"])
        cfg.update(toy)
        mcfg = module._model_config(cfg)
        params, tokens, targets = state(mcfg, batch=2)
        return module, cfg, mcfg, params, (tokens, targets)

    cases["rehearsal"] = rehearsal
    key, sizes = windows or (None, {None: None})

    @case
    @pytest.mark.parametrize("window", [
        pytest.param(w, id=f"{name}-{i}" if i else name) for i, w in sizes.items()])
    def test_the_builders_blocked_copy_is_the_reference(rehearsal, monkeypatch, window):
        module, cfg, mcfg, params, batch = rehearsal
        if key is not None:
            cfg = {**cfg, key: window}
            mcfg = module._model_config(cfg)
        for block, size in dict(Q_BLOCK=8, ROW_BLOCK=32, KEY_GROUPS=2, **dict(blocks)).items():
            monkeypatch.setattr(module, block, size)
        got, grads = jax.jit(jax.value_and_grad(module.plain_loss(cfg)))(params, batch)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(mcfg, p, *batch)))(params)
        assert float(got) == pytest.approx(float(want), rel=1e-6)
        for leaf in never_learns:
            grads.pop(leaf), want_grads.pop(leaf)
        off, leaf = _worst(grads, want_grads)
        assert off < 1e-4, f"{leaf}: {off:.2e}"

    @pytest.fixture(scope="module")
    def plain_f32(rehearsal):
        """The f32 loss both precision cases are held to, made once."""
        module, cfg, _, params, batch = rehearsal
        return float(jax.jit(module.plain_loss(cfg))(params, batch))

    cases["plain_f32"] = plain_f32

    @case
    @pytest.mark.parametrize("statistics", [pytest.param(jnp.float32, id=f"{name}-stated"),
                                            pytest.param(jnp.bfloat16, id=f"{name}-below")])
    def test_precision_controls_keep_f32_parameters_and_loss(rehearsal, plain_f32, statistics):
        module, cfg, _, params, batch = rehearsal
        want = plain_f32
        loss, grads = jax.jit(jax.value_and_grad(
            module.plain_loss(cfg, jnp.bfloat16, statistics)))(params, batch)
        assert loss.dtype == jnp.float32
        assert {g.dtype for g in grads.values()} == {jnp.dtype("float32")}
        least, most = precision
        assert least <= abs(float(loss) - want) / want < most

    return cases


def skewed_router_case(tiny, layer, mlp, ref_mlp, by_bias=True, **overrides):
    """A router that sends every token to the two held experts — by its
    selection bias or, where a family has none, by its matrix on positive
    tokens: sixteen times the even load (the first chunk and every tail chunk
    run), none dropped, output = reference."""
    def test_no_slot_is_dropped_under_a_skewed_router():
        cfg = tiny(n_experts=32, experts_held=2, expert_lo=4, top_k=2, **overrides)
        lp = layer(cfg)
        g = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.d_model))
        if by_bias:
            lp["router_bias"] = jnp.zeros_like(lp["router_bias"]).at[4:6].set(10.0)
        else:
            g = jnp.abs(g) + 0.1  # positive tokens: 4 and 5 win
            lp["router"] = jnp.zeros_like(lp["router"]).at[:, 4:6].set(1.0)
        y, stats = jax.jit(lambda g, lp: mlp(cfg, g, lp))(g, lp)
        routed, held, dropped, fullest, walked = (int(v) for v in stats)
        assert routed == held == walked == 128 and dropped == 0 and fullest == 64
        np.testing.assert_allclose(y, ref_mlp(cfg, g, lp), atol=1e-5)

    return test_no_slot_is_dropped_under_a_skewed_router
