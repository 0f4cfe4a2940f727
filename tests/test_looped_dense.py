"""The looped dense family (models/looped_dense.py) against its plain
reference (models/looped_dense_reference.py): two layers run three times at
hidden 64, seeded random weights, on the CPU mesh — loss, the logits of every
loop step, every leaf's gradient; weight sharing as the sum of the per-pass
gradients of an unrolled, untied copy; the weighted blocked loss against
whole logits; the exit distribution and what the step counts.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import looped_dense as ld
from byteps_tpu.models import looped_dense_reference as ref
from byteps_tpu.models import moe_family as mf
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import moe

import family_cases as fc
from family_cases import _mesh


def _state(cfg, seed=0, batch=4):
    """Parameters with the norms' scales and the exit gate off their starting
    values (a gate at zero would hide a wrong exit distribution), tokens,
    next-token targets with two ignored."""
    params, tokens, targets = fc._state(ld, cfg, seed, batch)
    params["gate_w"] = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 5), (cfg.d_model,))
    params["gate_b"] = jnp.asarray(0.2, jnp.float32)
    return params, tokens, targets.at[0, 3].set(-1).at[-1, -1].set(-1)


FAMILY = fc.Family(
    name="looped_dense", model=ld, ref=ref, tiny=ld.tiny_looped_dense, state=_state,
    variants={
        "two_layers_three_loops": dict(),
        "four_loops_as_published": dict(n_loops=4),
        "one_head_a_key_value_head": dict(n_kv_heads=4),
        "one_layer_looped": dict(n_layers=1, n_loops=4),
        "one_loop_is_a_plain_stack": dict(n_loops=1),
        "no_entropy_term": dict(exit_beta=0.0),
        "no_remat": dict(remat=False),
    },
    # every leaf learns; with one loop step there is no gate to learn
    learns=lambda cfg, name: cfg.n_loops > 1 or name not in ("gate_w", "gate_b"),
    dp2=("two_layers_three_loops", 1e-5),
)
globals().update(fc.family_cases(FAMILY))


@pytest.mark.parametrize("variant", FAMILY.params_of(sorted(FAMILY.variants)))
def test_every_loop_steps_logits_match_reference(tiny, variant):
    t = tiny(variant)
    got, p = jax.jit(lambda q, x: ld.loop_logits(t.cfg, q, x))(t.params, t.tokens)
    want, gates = jax.jit(lambda q, x: ref.forward(t.cfg, q, x))(t.params, t.tokens)
    assert got.shape == (t.cfg.n_loops,) + t.tokens.shape + (t.cfg.vocab_size,)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(p, ref.exit_distribution(gates), atol=2e-6)
    # build_forward gives the last loop step's: the published threshold of 1
    last = tfm.build_forward(t.cfg, _mesh())(t.params, t.tokens)[0]
    np.testing.assert_allclose(last, want[-1], atol=1e-4 * float(jnp.abs(want).max()))


def test_a_shared_weights_gradient_is_the_sum_over_its_passes(tiny):
    """The stack unrolled with a copy of the layers a loop step, untied: each
    copy's gradient is one pass's, and their sum is the gradient the looped
    program gives the shared leaf."""
    t = tiny("two_layers_three_loops")
    cfg, loops = t.cfg, t.cfg.n_loops
    shared = {k: v for k, v in t.params.items() if k.startswith("layer.")}
    rest = {k: v for k, v in t.params.items() if k not in shared}

    def untied_loss(copies):
        """ref.loss with loop step i reading ``copies[i]``."""
        with jax.default_matmul_precision("highest"):
            h = rest["embed"][t.tokens]
            logits, gates = [], []
            for layers in copies:
                h = ref._rms(ref.stack(cfg, layers, h), rest["norm_f"], cfg.norm_eps)
                logits.append(h @ rest["head"].T)
                gates.append(jax.nn.sigmoid(h @ rest["gate_w"] + rest["gate_b"]))
        logits, p = jnp.stack(logits), ref.exit_distribution(jnp.stack(gates))
        valid = t.targets >= 0
        gold = jnp.take_along_axis(logits, jnp.broadcast_to(
            jnp.maximum(t.targets, 0), logits.shape[:-1])[..., None], axis=-1)[..., 0]
        each = jax.nn.logsumexp(logits, axis=-1) - gold
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        return jnp.sum((jnp.sum(p * each, 0) - cfg.exit_beta * entropy) * valid) / jnp.sum(valid)

    want_loss, per_pass = jax.jit(jax.value_and_grad(untied_loss))([shared] * loops)
    loss, grads = t.system()
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    for name in shared:
        passes = [np.asarray(g[name]) for g in per_pass]
        # no pass's gradient is another's, and none is nothing
        assert all(np.any(g) for g in passes)
        assert not np.allclose(passes[0], passes[-1], rtol=1e-2, atol=0)
        total = np.sum(passes, axis=0)
        np.testing.assert_allclose(grads[name], total, rtol=0, atol=2e-4 * np.abs(total).max(),
                                   err_msg=name)


def test_one_loop_without_entropy_is_the_plain_stack_under_xent_sums():
    """``n_loops = 1``: the exit distribution is 1 at the one step, and at
    β = 0 the loss is the mean cross-entropy — ``xent_sums``' own value over
    the stack's normed output."""
    cfg = ld.tiny_looped_dense(n_loops=1, exit_beta=0.0)
    params, tokens, targets = _state(cfg)
    mesh = _mesh()

    def local(params, tokens, targets):
        hs, _ = ld._loop_outputs(cfg, params, tokens)
        total, count = mf.xent_sums(cfg, ld._head_logits, hs[0], targets, params["head"])
        return ld.local_loss(cfg, mesh, params, tokens, targets)[0], (total / count)[None]

    from jax.sharding import PartitionSpec as P
    got, want = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P("dp", "sp"), P("dp", "sp")), out_specs=(P(), P("dp")),
        check_vma=False))(params, tokens, targets)
    assert float(got) == pytest.approx(float(want[0]), rel=1e-6)
    assert float(got) == pytest.approx(float(ref.loss(cfg, params, tokens, targets)), rel=1e-5)


def _whole(cfg, x, targets, weights, head):
    """The weighted loss from whole logits: (Σ w · CE, the rows' CE)."""
    logits = ld._head_logits(cfg, x, head)
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    each = (jax.nn.logsumexp(logits, axis=-1) - gold) * (targets >= 0)
    return jnp.sum(weights * each), each


@pytest.mark.parametrize("cotangent", [1.0, 0.37])
def test_the_weighted_blocked_loss_is_the_one_from_whole_logits(monkeypatch, cotangent):
    """3 x 10 rows in blocks of gcd(30, 4) = 2, some targets ignored, a
    weight a row: the value, dx, d head, and the WEIGHTS' gradient — every
    row's cross-entropy, which is what the exit gate learns from."""
    monkeypatch.setattr(mf, "ROW_BLOCK", 4)
    cfg = ld.tiny_looped_dense()
    rng = np.random.default_rng(57)
    x = jnp.asarray(rng.normal(size=(3, 2, 5, cfg.d_model)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(cfg.vocab_size, cfg.d_model)) * cfg.d_model ** -0.5,
                       jnp.float32)
    targets = rng.integers(0, cfg.vocab_size, size=(3, 2, 5))
    targets[:, 0, [0, 1]] = targets[:, 1, 4] = -1  # a whole block of two among them
    targets = jnp.asarray(targets, jnp.int32)
    weights = jnp.asarray(rng.uniform(0.05, 1.0, size=(3, 2, 5)), jnp.float32)

    def blocked(x, weights, head):
        return cotangent * mf.weighted_xent(cfg, ld._head_logits, x, targets, weights, head)

    def whole(x, weights, head):
        total, each = _whole(cfg, x, targets, weights, head)
        return cotangent * total, jax.lax.stop_gradient(each)

    got_total, got = jax.value_and_grad(blocked, argnums=(0, 1, 2))(x, weights, head)
    (want_total, want_each), want = jax.value_and_grad(whole, argnums=(0, 1, 2), has_aux=True)(
        x, weights, head)
    # differentiated or not, the same value
    np.testing.assert_allclose(blocked(x, weights, head), got_total, rtol=1e-6)
    np.testing.assert_allclose(got_total, want_total, rtol=1e-6)
    assert got[1].shape == targets.shape and got[1].dtype == jnp.float32
    for name, a, b in zip(("x", "weights", "head"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6 * float(jnp.abs(b).max()),
                                   err_msg=name)
    # a weight's gradient is its row's cross-entropy (times the cotangent)
    np.testing.assert_allclose(got[1], cotangent * np.asarray(want_each), rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[0])[:, 0, [0, 1]].any() and not np.asarray(got[1])[:, 1, 4].any()


def test_unit_weights_give_xent_sums_own_value_and_gradients(monkeypatch):
    """One implementation: at weight 1 a row the weighted loss IS
    ``xent_sums`` — value, dx and d head to the bit."""
    monkeypatch.setattr(mf, "ROW_BLOCK", 4)
    cfg = ld.tiny_looped_dense()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 7, cfg.d_model)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(cfg.vocab_size, cfg.d_model)), jnp.float32)
    targets = jnp.asarray(rng.integers(-1, cfg.vocab_size, size=(2, 7)), jnp.int32)
    one = jax.value_and_grad(
        lambda x, h: mf.weighted_xent(cfg, ld._head_logits, x, targets, jnp.ones((2, 7)), h),
        argnums=(0, 1))(x, head)
    two = jax.value_and_grad(
        lambda x, h: mf.xent_sums(cfg, ld._head_logits, x, targets, h)[0], argnums=(0, 1))(x, head)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(two)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("loops", [1, 2, 3, 4])
def test_the_exit_distribution_sums_to_one(loops):
    cfg = ld.tiny_looped_dense(n_loops=loops)
    rng = np.random.default_rng(loops)
    hs = jnp.asarray(rng.normal(size=(loops, 2, 5, cfg.d_model)), jnp.float32)
    gate_w = jnp.asarray(rng.normal(size=cfg.d_model), jnp.float32)
    gate_b = jnp.asarray(-0.4, jnp.float32)
    p, log_p = ld.exit_distribution(hs, gate_w, gate_b)
    assert p.shape == (loops, 2, 5) and bool(jnp.all(p > 0))
    np.testing.assert_allclose(jnp.sum(p, axis=0), np.ones((2, 5)), atol=1e-6)
    np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
    gates = jax.nn.sigmoid(hs @ gate_w + gate_b)
    np.testing.assert_allclose(p, ref.exit_distribution(gates), atol=1e-6)
    # a gate far open exits at once; far shut, at the last step — without a NaN
    for bias, where in ((60.0, 0), (-60.0, loops - 1)):
        p, log_p = ld.exit_distribution(hs, jnp.zeros(cfg.d_model), jnp.asarray(bias))
        assert bool(jnp.all(jnp.isfinite(p))) and not bool(jnp.any(jnp.isnan(p * log_p)))
        np.testing.assert_allclose(p[where], np.ones((2, 5)), atol=1e-6)


@pytest.mark.parametrize("loops,milli", [(3, 1750), (4, 1875)])
def test_the_step_counts_its_layer_passes_and_its_mean_exit_step(loops, milli):
    """At the gate's start (zero: λ = ½) the mean exit step is Σ t pᵗ = 1.75
    at three loops and 1.875 at four; layers × loops passes ran.  Both reach
    the process's counters as the MoE families' routing statistics do."""
    cfg = ld.tiny_looped_dense(n_loops=loops)
    params = ld.init_params(cfg, jax.random.PRNGKey(0))
    assert not np.any(params["gate_w"]) and not np.any(params["gate_b"])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.max_seq), 0, cfg.vocab_size)
    tx = optax.sgd(0.0)
    step = tfm.build_train_step(cfg, _mesh(), tx, donate=False)
    sink = moe.routing_counters()
    before = sink._snapshot()
    step(params, tx.init(params), tokens, jnp.roll(tokens, -1, axis=1))
    step(params, tx.init(params), tokens, jnp.roll(tokens, -1, axis=1))
    after = sink._snapshot()
    grown = {k: after[k] - before.get(k, 0) for k in ld.COUNTS}
    assert grown == {"looped_layer_passes": 2 * cfg.n_layers * loops,
                     "looped_exit_step_milli": 2 * milli}
    # a family without experts counts no slot
    assert all(after[k] == before.get(k, 0) for k in moe.ROUTING_STATS)


def test_a_mesh_beyond_data_parallel_is_refused_in_the_familys_words():
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    cfg = ld.tiny_looped_dense()
    mesh = make_training_mesh(2, {"dp": 1, "pp": 2, "sp": 1, "tp": 1}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="the looped dense family runs data-parallel only.*"
                                         "pp=2.*ring of pipeline stages"):
        tfm.build_train_step(cfg, mesh, optax.sgd(0.0))
    with pytest.raises(ValueError, match="at least one layer once"):
        ld.tiny_looped_dense(n_loops=0)
    with pytest.raises(ValueError, match="multiple of key/value heads"):
        ld.tiny_looped_dense(n_kv_heads=3)


def test_the_family_reads_no_expert_field():
    """``moe_family.Family`` is the protocol alone; the expert fields are
    ``ExpertFamily``'s, which the looped family is not."""
    cfg = ld.tiny_looped_dense()
    assert isinstance(cfg, mf.Family) and not isinstance(cfg, mf.ExpertFamily)
    assert not {"expert_lo", "experts_held", "n_experts"} & {
        f.name for f in dataclasses.fields(cfg)}
    assert set(cfg.layouts()) == set(ld.layouts(cfg))
