"""The pieces the early-routed MoE family brought, each against a hand-written
case on the CPU: a router that reads the attention's input and whose decision
crosses the attention, one sort a layer, the seam in ``held_expert_mlp`` (a
plan from the ids alone, an apply with the gate's activation passed in), the
ReLU gate by hand, the shares of an expert-parallel layer, the hand-over in
``moe_family.walk``, and the cell's blocked reference
(benchmark/builders/smallthinker.py) against
models/early_route_moe_reference.py.  (The model against its reference:
tests/test_early_route_moe.py.  Two files so that ``--dist loadfile`` spreads
them.)
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import early_route_moe as em
from byteps_tpu.models import early_route_moe_reference as ref
from byteps_tpu.models import moe_family as mf
from byteps_tpu.models import transformer as tfm
from byteps_tpu.models import window_moe as wm
from byteps_tpu.parallel import moe

import family_cases as fc
from family_cases import _mesh

_state = functools.partial(fc._state, em)

SLIDING, FULL = "sliding_attention", "full_attention"


def _layer(cfg, stack, seed=3, i=0):
    """Layer ``i`` of ``stack`` of a seeded state."""
    params, _, _ = _state(cfg, seed=seed)
    return {k.split(".", 1)[1]: v[i] for k, v in params.items() if k.startswith(stack + ".")}


# ---------------------------------------------------------------------------
# the router reads the attention's input, and its decision crosses the attention
# ---------------------------------------------------------------------------


def _decisions(cfg, params, tokens, monkeypatch):
    """The stack's output and, layer by layer, the decision that reached the
    experts and the tokens they read (run eagerly, nothing rebuilt)."""
    seen = []
    routed_mlp = mf.routed_mlp

    def record(cfg, g32, g, lp, route, *a, **kw):
        seen.append((route, g32))
        return routed_mlp(cfg, g32, g, lp, route, *a, **kw)

    monkeypatch.setattr(mf, "routed_mlp", record)
    x, _ = em._hidden(cfg, params, tokens)
    return x, seen


def test_the_router_reads_the_attentions_input_and_not_the_mlps(monkeypatch):
    """Changing layer 0's ``W_o`` alone moves what layer 0's experts read and
    leaves every id and weight of layer 0 as it was; changing its
    ``norm_post`` (``moe.norm``) too.  Layer 1, which reads layer 0's output,
    decides otherwise."""
    cfg = em.tiny_early_route_moe(remat=False)
    params, tokens, _ = _state(cfg)
    _, base = _decisions(cfg, params, tokens, monkeypatch)
    assert len(base) == cfg.n_layers and all(isinstance(d, mf.Decision) for d, _ in base)
    moved = dict(params)
    moved["glob.wo"] = params["glob.wo"].at[0].multiply(-1.5)
    moved["moe.norm"] = params["moe.norm"].at[0].add(0.5)
    _, after = _decisions(cfg, moved, tokens, monkeypatch)
    for a, b in zip(jax.tree.leaves(base[0][0]), jax.tree.leaves(after[0][0])):
        np.testing.assert_array_equal(a, b)  # the plan's order and sizes, the weights: bit for bit
    assert float(jnp.max(jnp.abs(base[0][1] - after[0][1]))) > 0.1  # what the experts read moved
    assert float(jnp.max(jnp.abs(base[1][0].weights - after[1][0].weights))) > 1e-3


def test_the_decision_is_the_reference_routers_on_the_normed_input():
    """Layer 0's decision from the mixer's part: the weights are the
    reference's (the six — here two — largest logits, softmax over those) of
    ``norm_in(h)`` and not of ``h``."""
    cfg = em.tiny_early_route_moe()
    lp = _layer(cfg, "glob")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.max_seq, cfg.d_model))
    handed = em._mixer_part(cfg, x, lp, "glob")
    assert isinstance(handed, mf.Handed) and isinstance(handed.value, mf.Decision)
    a = ref._rms(x, lp["norm"], cfg.norm_eps).reshape(-1, cfg.d_model)
    want = ref.route(cfg, a, lp["router"])  # (T, 8), zero off the chosen
    got = np.sort(np.asarray(handed.value.weights), axis=-1)
    np.testing.assert_allclose(got, np.sort(np.asarray(want), axis=-1)[:, -cfg.top_k:], atol=1e-6)
    unnormed = ref.route(cfg, x.reshape(-1, cfg.d_model), lp["router"])
    assert float(jnp.max(jnp.abs(unnormed - want))) > 1e-2
    # and every slot is in the plan once, the held ones counted by expert
    plan = handed.value.plan
    assert sorted(np.asarray(plan.order)) == list(range(a.shape[0] * cfg.top_k))
    assert int(plan.sizes.sum()) == a.shape[0] * cfg.top_k  # all 8 experts held here


def _sort_calls(text: str) -> dict:
    """scope → calls of ``argsort`` in a lowered step (StableHLO with debug
    info) whose scope path has that scope as a segment."""
    paths = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"\(#loc\d+\)\)$', text, re.M))
    counts = {"moe_route": 0, "moe_experts": 0}
    for loc in re.findall(r"^\s*%\S+ = call @argsort\S*\(.* loc\((#loc\d+)\)$", text, re.M):
        for scope in counts:
            counts[scope] += scope in paths.get(loc, "").split("/")
    return counts


@pytest.mark.parametrize("family, sorts", [
    ("early_route_moe", {"moe_route": 4, "moe_experts": 0}),
    ("window_moe", {"moe_route": 0, "moe_experts": 6}),
], ids=["early_route_once_a_layer", "window_moe_twice_an_expert_layer"])
def test_a_layer_sorts_once(family, sorts):
    """The whole step's lowering (forward, both rebuilds, backward): this
    family sorts its slots once a layer, under ``moe_route`` in the mixer's
    forward pass, and nowhere under ``moe_experts``; a family that routes in
    its MLP part sorts in the forward pass and again in the MLP's rebuild
    (three expert layers: six)."""
    module = {"early_route_moe": em, "window_moe": wm}[family]
    cfg = getattr(module, f"tiny_{family}")()
    params = module.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.sgd(1.0)
    tokens = jnp.zeros((2, cfg.max_seq), jnp.int32)
    lowered = tfm.build_train_step(cfg, _mesh(), tx, donate=False).lower(
        params, tx.init(params), tokens, tokens)
    text = lowered.as_text(debug_info=True)
    assert _sort_calls(text) == sorts
    assert text.count("call @argsort") == sum(sorts.values())


def test_a_mixers_part_hands_the_mlps_part_of_its_layer_a_value():
    """``walk``: what a mixer's part returns as ``Handed`` reaches the MLP's
    part of the same layer as its third argument, and no other part."""
    cfg = em.tiny_early_route_moe(layer_types=(FULL, SLIDING, SLIDING), remat=False)
    params = {f"{stack}.w": 10.0 * (i + 1) + jnp.arange(3.0)
              for i, stack in enumerate(("glob", "win", "moe"))}
    got = []

    def mixer(x, lp):
        return mf.Handed(x + 1, {"from": lp["w"]})

    def mlp(x, lp, handed):
        got.append((float(lp["w"]), float(handed["from"])))
        return x + handed["from"], jnp.arange(5, dtype=jnp.int32)

    x, stats = mf.walk(cfg, {"glob": mixer, "win": mixer, "moe": mlp}, ("glob", "win"),
                       params, jnp.zeros(()))
    assert got == [(30.0, 10.0), (31.0, 20.0), (32.0, 21.0)]
    assert float(x) == 3 + 10 + 20 + 21 and list(stats) == [0, 3, 6, 9, 12]
    with pytest.raises(TypeError):  # an MLP's part that is handed nothing it expects
        mf.walk(cfg, {"glob": lambda x, lp: x, "win": mixer, "moe": mlp}, (), params,
                jnp.zeros(()))


# ---------------------------------------------------------------------------
# the seam in held_expert_mlp
# ---------------------------------------------------------------------------


def _routed_case(seed=0, t=40, d=16, f=8, n=8, held=4, lo=2, k=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    g = jax.random.normal(keys[0], (t, d))
    ids, weights = moe.softmax_topk_route(g, jax.random.normal(keys[1], (d, n)), k)
    w_gate, w_up = (jax.random.normal(key, (held, d, f)) * d ** -0.5 for key in keys[2:4])
    w_down = jax.random.normal(keys[4], (held, f, d)) * f ** -0.5
    return g, ids, weights, w_gate, w_up, w_down, lo, n


def _dense(g, ids, weights, w_gate, w_up, w_down, lo, act):
    """The held experts one by one over every token, masked by their weight."""
    y = jnp.zeros_like(g)
    for e in range(w_gate.shape[0]):
        weight = jnp.sum(jnp.where(ids == lo + e, weights, 0.0), axis=-1)
        y = y + weight[:, None] * ((act(g @ w_gate[e]) * (g @ w_up[e])) @ w_down[e])
    return y


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jitted"])
def test_plan_then_apply_at_silu_is_held_expert_mlp_bit_for_bit(jit):
    """The one call the four families make is the plan from the ids alone and
    the apply at ``silu`` (tests/test_family_lowerings.py holds their steps'
    lowerings to what they were before the seam); here the values, and the
    dense computation they stand for."""
    g, ids, weights, w_gate, w_up, w_down, lo, n = _routed_case()

    def whole(g, ids, weights):
        return moe.held_expert_mlp(g, ids, weights, w_gate, w_up, w_down, lo=lo, n_experts=n)

    def two(g, ids, weights):
        plan = moe.held_expert_plan(ids, lo, w_gate.shape[0])
        return moe.held_expert_apply(g, plan, weights, w_gate, w_up, w_down, n, jax.nn.silu)

    if jit:
        whole, two = jax.jit(whole), jax.jit(two)
    (y, stats), (y2, stats2) = whole(g, ids, weights), two(g, ids, weights)
    np.testing.assert_array_equal(y, y2)
    np.testing.assert_array_equal(stats, stats2)
    np.testing.assert_allclose(y, _dense(g, ids, weights, w_gate, w_up, w_down, lo, jax.nn.silu),
                               atol=1e-5)
    assert int(stats[0]) == ids.size and 0 < int(stats[1]) < ids.size and int(stats[2]) == 0


def test_the_plan_touches_no_token_and_orders_the_held_slots_by_expert():
    ids = jnp.asarray([[5, 2], [3, 9], [2, 3], [0, 4]], jnp.int32)  # held: experts 2, 3, 4
    plan = moe.held_expert_plan(ids, lo=2, n_held=3)
    assert list(plan.sizes) == [2, 2, 1]
    # slots (token·2 + choice): expert 2 takes slots 1 and 4, expert 3 slots 2 and 5,
    # expert 4 slot 7; the three that are held elsewhere come after, in order
    assert list(plan.order) == [1, 4, 2, 5, 7, 0, 3, 6]


def test_relu_gated_experts_by_hand():
    """Two tokens, one held expert of width 2: the gate's negative half is
    cut, where silu would let it through."""
    g = jnp.asarray([[1.0, 2.0], [-1.0, 0.5]])
    w_gate = jnp.asarray([[[1.0, -1.0], [0.0, 1.0]]])  # g W_gate = [[1, 1], [-1, 1.5]]
    w_up = jnp.asarray([[[2.0, 0.0], [0.0, 2.0]]])  # g W_up = [[2, 4], [-2, 1]]
    w_down = jnp.asarray([[[1.0, 0.0], [0.0, 1.0]]])
    ids, weights = jnp.asarray([[0], [0]], jnp.int32), jnp.asarray([[0.5], [2.0]])
    plan = moe.held_expert_plan(ids, 0, 1)
    y, _ = moe.held_expert_apply(g, plan, weights, w_gate, w_up, w_down, 1, jax.nn.relu)
    # token 0: relu([1, 1]) * [2, 4] = [2, 4], at weight 0.5; token 1: relu([-1, 1.5]) * [-2, 1]
    # = [0, 1.5], at weight 2
    np.testing.assert_allclose(y, [[1.0, 2.0], [0.0, 3.0]], atol=1e-6)
    y_silu, _ = moe.held_expert_apply(g, plan, weights, w_gate, w_up, w_down, 1, jax.nn.silu)
    assert float(y_silu[1, 0]) == pytest.approx(2.0 * jax.nn.silu(-1.0) * -2.0, rel=1e-6)


def test_4_shares_of_2_add_up_to_the_uncut_layer():
    """An 8-wide router, top-2, in 4 shares of 2 experts: the shares' held
    parts, each by the decision taken on the attention's input, add up to the
    uncut reference's layer, and every slot is held by exactly one share."""
    whole = em.tiny_early_route_moe()
    mixer, lp = _layer(whole, "glob"), _layer(whole, "moe")
    a = jax.random.normal(jax.random.PRNGKey(9), (40, whole.d_model))  # what the router reads
    b = jax.random.normal(jax.random.PRNGKey(10), (40, whole.d_model))  # what the experts read
    want = ref.experts(whole, b, ref.route(whole, a, mixer["router"]), lp)
    total, held = 0.0, 0
    for lo in range(0, 8, 2):
        share = em.tiny_early_route_moe(experts_held=2, expert_lo=lo)
        lp_share = {**lp, **{w: lp[w][lo:lo + 2] for w in ("e_gate", "e_up", "e_down")}}
        decision = mf.decide(share, a, mixer, functools.partial(em._route, share))
        y, stats = mf.routed_mlp(share, b, b, lp_share, decision, act=jax.nn.relu)
        total = total + y
        held += int(stats[1])
        assert int(stats[2]) == 0
        if lo in (0, 6):  # and a share is what the reference gives for that share
            np.testing.assert_allclose(
                y, ref.experts(share, b, ref.route(share, a, mixer["router"]), lp_share), atol=2e-5)
    assert held == 40 * 2
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_no_slot_is_dropped_under_a_skewed_router():
    """A router that sends every token to the two held experts: sixteen times
    the even load (the first chunk and every tail chunk run), none dropped,
    output = reference."""
    cfg = em.tiny_early_route_moe(n_experts=32, experts_held=2, expert_lo=4)
    lp = _layer(cfg, "moe")
    a = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (64, cfg.d_model)))
    router = jnp.zeros((cfg.d_model, 32)).at[:, 4:6].set(1.0)  # positive tokens: 4 and 5 win
    run = jax.jit(lambda a, lp: mf.routed_mlp(
        cfg, a, a, lp, mf.decide(cfg, a, {"router": router}, functools.partial(em._route, cfg)),
        act=jax.nn.relu))
    y, stats = run(a, lp)
    routed, held, dropped, fullest, walked = (int(v) for v in stats)
    assert routed == held == walked == 128 and dropped == 0 and fullest == 64
    np.testing.assert_allclose(y, ref.experts(cfg, a, ref.route(cfg, a, router), lp), atol=1e-5)


# ---------------------------------------------------------------------------
# the cell's blocked reference
# ---------------------------------------------------------------------------


# toy widths: the whole period, a group of three, and a window that is no
# multiple of the query block
globals().update(fc.builder_cases(
    "early_route_moe", ref, _state, builder="smallthinker", config="smallthinker_21b_ep8",
    toy=dict(hidden_size=32, num_attention_heads=6, num_key_value_heads=2, head_dim=8,
             moe_ffn_hidden_size=16, moe_num_primary_experts=4, router_width=16,
             moe_num_active_primary_experts=3, vocab_size=96, max_seq=64,
             sliding_window_size=11),
    windows=("sliding_window_size", {"odd": 11, "two_blocks": 16, "over_the_sequence": 200})))


def test_the_builder_runs_the_first_period_of_the_published_lists(rehearsal):
    builder, cfg, mcfg, _, _ = rehearsal
    assert len(cfg["sliding_window_layout"]) == len(cfg["rope_layout"]) == 52
    assert cfg["first_layer"] == 0 and mcfg.layer_types == (FULL, SLIDING, SLIDING, SLIDING)
    assert (mcfg.n_experts, mcfg.experts_held, mcfg.expert_lo, mcfg.top_k) == (16, 4, 0, 3)
    assert (mcfg.rope_theta, mcfg.norm_eps, mcfg.sliding_window) == (1.5e6, 1e-6, 11)
    for key, other in (("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False),
                       ("tie_word_embeddings", True), ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            builder._model_config({**cfg, key: other})
    with pytest.raises(ValueError, match="rope_layout"):  # rope on a global layer is not built
        builder._model_config({**cfg, "rope_layout": [1] * 52})


# ---------------------------------------------------------------------------
# the embedding's gather, whose transpose sorts its scatter-add by hand
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,n,d", [(11, 40, 6), (40, 11, 3), (5, 64, 8)],
                         ids=["most_rows_hit_twice", "most_rows_not_hit", "every_row_many_times"])
def test_take_rows_and_add_rows_are_the_plain_gather_and_scatter(rows, n, d):
    """``parallel/moe.take_rows`` is ``x[at]`` and ``add_rows`` is
    ``y.at[at].add(rows)`` — repeated and absent rows alike —, and each one's
    gradient is the other: d ``take_rows`` / dx scatters, d ``add_rows`` / d
    rows gathers, d ``add_rows`` / dy passes through."""
    rng = np.random.default_rng(rows * n)
    x = jnp.asarray(rng.normal(size=(rows, d)), jnp.float32)
    at = jnp.asarray(rng.integers(0, rows, size=n), jnp.int32)
    new = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    ct_taken, ct_added = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                          for shape in ((n, d), (rows, d)))
    np.testing.assert_array_equal(moe.take_rows(x, at), x[at])
    np.testing.assert_allclose(moe.add_rows(x, at, new), x.at[at].add(new), rtol=1e-6, atol=1e-6)
    got = jax.grad(lambda x: jnp.sum(moe.take_rows(x, at) * ct_taken))(x)
    want = jax.grad(lambda x: jnp.sum(x[at] * ct_taken))(x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    got = jax.grad(lambda y, r: jnp.sum(moe.add_rows(y, at, r) * ct_added), argnums=(0, 1))(x, new)
    want = jax.grad(lambda y, r: jnp.sum(y.at[at].add(r) * ct_added), argnums=(0, 1))(x, new)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_embeddings_gradient_is_the_scatter_add_of_its_rows(dtype):
    """The step's embedding (``_hidden`` under ``embed``: ``take_rows`` on
    the flattened ids, then the cast): the rows are ``embed[tokens]`` and the
    leaf's gradient is ``zeros.at[tokens].add(cotangent)`` in f32, a token that
    comes twice adding twice."""
    cfg = em.tiny_early_route_moe(compute_dtype=jnp.dtype(dtype), layer_types=(FULL,))
    params, tokens, _ = _state(cfg, seed=5)
    tokens = tokens.at[:, 1].set(tokens[:, 0])  # a row that comes twice a sequence
    seen = {}

    def walk(cfg, run, flash, params, x):  # the stack left out: the embedding alone
        seen["x"] = x
        return x, None

    ct = jnp.asarray(np.random.default_rng(2).normal(size=tokens.shape + (cfg.d_model,)),
                     jnp.float32)

    def through(embed):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(mf, "walk", walk)
            x, _ = em._hidden(cfg, {**params, "embed": embed}, tokens)
        return jnp.sum(x.astype(jnp.float32) * ct)

    got = jax.grad(through)(params["embed"])
    assert seen["x"].dtype == cfg.compute_dtype and seen["x"].shape == ct.shape
    want = jax.grad(lambda e: jnp.sum(e[tokens].astype(cfg.compute_dtype).astype(jnp.float32) * ct))(
        params["embed"])
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_the_embeddings_gradient_is_summed_over_the_ranks():
    """Under ``shard_map`` on two devices, a sequence each: the embedding is
    replicated where the ids vary, and its gradient — each rank's sorted
    scatter-add — comes out summed over the ranks, the unsharded one's."""
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.default_rng(9)
    embed = jnp.asarray(rng.normal(size=(12, 4)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 12, size=(2, 8)), jnp.int32)
    ct = jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.float32)

    def local(embed, tokens, ct):
        def total(embed):
            rows = moe.take_rows(moe.varying(embed, jax.typeof(tokens).vma), tokens.reshape(-1))
            return jnp.sum(rows.reshape(ct.shape) * ct)
        return jax.grad(total)(embed)

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    got = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(), P("dp"), P("dp")),
                                out_specs=P(), check_vma=True))(embed, tokens, ct)
    want = jnp.zeros_like(embed).at[tokens.reshape(-1)].add(ct.reshape(-1, 4))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
