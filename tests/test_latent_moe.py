"""The latent-attention MoE family (models/latent_moe.py) against its plain
reference (models/latent_moe_reference.py): tiny widths, seeded random
weights, f32, on the CPU mesh.  Also the pieces it brought: the held-expert
layer (parallel/moe.py), the flash kernel at d_qk != d_v
(ops/flash_attention.py, interpret mode) and the routing counters.
"""

import importlib
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu as bps
from byteps_tpu.models import latent_moe as lm
from byteps_tpu.models import latent_moe_reference as ref
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.mesh_utils import make_training_mesh

fa = importlib.import_module("byteps_tpu.ops.flash_attention")


def _mesh(dp=1):
    return make_training_mesh(dp, {"dp": dp, "pp": 1, "sp": 1, "tp": 1},
                              devices=jax.devices()[:dp])


def _state(cfg, seed=0, batch=4, bias=0.3):
    """Parameters with a NON-ZERO selection bias, tokens, next-token targets."""
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    for name in params:
        if name.endswith("router_bias"):
            params[name] = bias * jax.random.normal(
                jax.random.PRNGKey(seed + 7), params[name].shape)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, cfg.max_seq), 0, cfg.vocab_size)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def _system_loss_and_grads(cfg, params, tokens, targets, dp=1):
    """Through build_train_step itself: sgd at rate 1 turns the update into
    the gradient."""
    tx = optax.sgd(1.0)
    step = tfm.build_train_step(cfg, _mesh(dp), tx, donate=False)
    new, _, loss = step(params, tx.init(params), tokens, targets)
    new = jax.device_get(new)  # off the mesh: dp 2 leaves them on two devices
    return float(loss), {k: np.asarray(params[k]) - new[k] for k in params}


def _worst(got, want):
    """(relative L2 distance, leaf) of the leaf that is furthest off."""
    def rel(k):
        scale = float(jnp.linalg.norm(want[k]))
        diff = float(jnp.linalg.norm(got[k] - want[k]))
        return diff / scale if scale else diff
    return max((rel(k), k) for k in got)


VARIANTS = {
    "dense_layer": dict(n_expert_layers=0, mtp_modules=0),
    "expert_layer_biased_choice": dict(n_dense_layers=0, n_expert_layers=1, mtp_modules=0),
    "two_kinds_no_mtp": dict(mtp_modules=0),
    "mtp_and_both_losses": dict(),
    "held_share_of_experts": dict(experts_held=2, expert_lo=4),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_logits_match_reference(variant):
    cfg = lm.tiny_latent_moe(**VARIANTS[variant])
    params, tokens, _ = _state(cfg)
    got = tfm.build_forward(cfg, _mesh())(params, tokens)[0]
    want, _ = ref.forward(cfg, params, tokens)
    assert got.shape == (4, cfg.max_seq, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_loss_and_every_leaf_gradient_match_reference(variant):
    cfg = lm.tiny_latent_moe(**VARIANTS[variant])
    params, tokens, targets = _state(cfg)
    loss, grads = _system_loss_and_grads(cfg, params, tokens, targets)
    want_loss, want = jax.value_and_grad(
        lambda p: ref.loss(cfg, p, tokens, targets))(params)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert set(grads) == set(want) == set(lm.layouts(cfg))
    off, leaf = _worst(grads, want)
    assert off < 1e-4, f"{leaf} is {off:.2e} of its gradient off the reference's"
    for name in want:  # the selection bias picks and takes no gradient
        if name.endswith("router_bias"):
            assert not np.any(np.asarray(grads[name]))


def test_mtp_loss_is_a_second_term_with_its_weight():
    cfg = lm.tiny_latent_moe()
    params, tokens, targets = _state(cfg)
    with_mtp = ref.loss(cfg, params, tokens, targets)
    main = ref.loss(lm.tiny_latent_moe(mtp_modules=0),
                    {k: v for k, v in params.items() if "mtp" not in k}, tokens, targets)
    double = ref.loss(lm.tiny_latent_moe(mtp_lambda=0.6), params, tokens, targets)
    assert float(with_mtp) > float(main)
    assert float(double - main) == pytest.approx(2 * float(with_mtp - main), rel=1e-5)


def test_same_loss_and_gradients_at_dp2_as_at_dp1():
    cfg = lm.tiny_latent_moe()
    params, tokens, targets = _state(cfg)
    loss1, grads1 = _system_loss_and_grads(cfg, params, tokens, targets, dp=1)
    loss2, grads2 = _system_loss_and_grads(cfg, params, tokens, targets, dp=2)
    assert loss2 == pytest.approx(loss1, rel=1e-6)
    off, leaf = _worst(grads2, grads1)
    assert off < 1e-5, f"{leaf} differs by {off:.2e} between dp 1 and dp 2"


def test_mesh_axes_that_are_not_built_are_refused():
    cfg = lm.tiny_latent_moe()
    mesh = make_training_mesh(2, {"dp": 1, "pp": 1, "sp": 1, "tp": 2},
                              devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="data-parallel only"):
        tfm.build_train_step(cfg, mesh, optax.sgd(1.0))


# ---------------------------------------------------------------------------
# the held-expert layer
# ---------------------------------------------------------------------------


def _layer_params(cfg, seed=3):
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    lp = {k.split(".", 1)[1]: v[0] for k, v in params.items() if k.startswith("moe.")}
    lp["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                                lp["router_bias"].shape)
    return lp


def test_shares_add_up_to_the_uncut_layer():
    """32 experts in 4 shares of 8: the shares' routed parts, and the shared
    expert counted once, give what the reference gives with all 32."""
    whole = lm.tiny_latent_moe(n_experts=32, experts_held=32, top_k=4)
    lp = _layer_params(whole)
    g = jax.random.normal(jax.random.PRNGKey(9), (48, whole.d_model))
    want = ref.expert_mlp(whole, g, lp)
    shared = lm._swiglu(g, lp["s_gate"], lp["s_up"], lp["s_down"])
    total, held = shared, 0
    for lo in range(0, 32, 8):
        share = lm.tiny_latent_moe(n_experts=32, experts_held=8, expert_lo=lo, top_k=4)
        lp_share = {**lp, **{w: lp[w][lo:lo + 8] for w in ("e_gate", "e_up", "e_down")}}
        y, stats = lm.expert_mlp(share, g, lp_share)
        total = total + (y - shared)  # this share's routed part alone
        held += int(stats[1])
        # and each share is what the reference gives for that share
        np.testing.assert_allclose(y, ref.expert_mlp(share, g, lp_share), atol=1e-5)
    assert held == 48 * 4  # every slot is held by exactly one share
    np.testing.assert_allclose(total, want, atol=1e-5)


@pytest.mark.parametrize("favoured, n_experts", [((4,), 8), ((4, 5), 8), ((4, 5), 32)])
def test_no_slot_is_dropped_under_skew(favoured, n_experts):
    """A selection bias that sends every token to the held experts: far more
    slots than the usual chunk holds (2 chunks of 64 rows at 8 experts, 8 of
    16 at 32: the second chunk, then the scanned rest), none dropped,
    output = reference."""
    cfg = lm.tiny_latent_moe(n_experts=n_experts, experts_held=2, expert_lo=4)
    lp = _layer_params(cfg)
    lp["router_bias"] = jnp.zeros(n_experts).at[jnp.asarray(favoured)].set(10.0)
    tokens = 64
    g = jax.random.normal(jax.random.PRNGKey(2), (tokens, cfg.d_model))
    y, stats = jax.jit(lambda g, lp: lm.expert_mlp(cfg, g, lp))(g, lp)
    routed, held, dropped, fullest = (int(v) for v in stats)
    assert routed == tokens * cfg.top_k
    assert held >= tokens * len(favoured)
    if len(favoured) == 2:  # every slot is held: every chunk of the usual size runs
        assert held == routed and routed % (2 * routed * 2 // n_experts) == 0
    assert dropped == 0
    assert fullest == tokens  # a token picks an expert at most once
    np.testing.assert_allclose(y, ref.expert_mlp(cfg, g, lp), atol=1e-5)
    # and the gradient flows through every chunk
    got = jax.grad(lambda lp: jnp.sum(lm.expert_mlp(cfg, g, lp)[0] ** 2))(lp)
    want = jax.grad(lambda lp: jnp.sum(ref.expert_mlp(cfg, g, lp) ** 2))(lp)
    off, leaf = _worst({k: got[k] for k in ("e_gate", "e_down", "router")}, want)
    assert off < 1e-4, f"{leaf}: {off:.2e}"


def test_bias_picks_and_does_not_weigh():
    g = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    bias = jnp.zeros(6).at[5].set(100.0)
    ids, weights = moe.sigmoid_topk_route(g, w, bias, top_k=2, scale=2.5)
    assert np.all(np.any(np.asarray(ids) == 5, axis=1))  # the bias picks
    np.testing.assert_allclose(jnp.sum(weights, axis=1), 2.5, rtol=1e-6)  # and is not in the weights
    scores = jax.nn.sigmoid(g @ w)
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    np.testing.assert_allclose(weights, 2.5 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)


def test_routing_counters_reach_the_programs_counters():
    names = moe.ROUTING_STATS
    before = bps.get_robustness_counters()
    cfg = lm.tiny_latent_moe(experts_held=2, expert_lo=0)
    params, tokens, targets = _state(cfg, bias=0.0)
    tx = optax.sgd(0.1)
    step = tfm.build_train_step(cfg, _mesh(), tx, donate=False)
    for _ in range(2):
        step(params, tx.init(params), tokens, targets)
    after = bps.get_robustness_counters()
    grown = {n: after.get(n, 0) - before.get(n, 0) for n in names}
    layers = cfg.n_expert_layers + cfg.mtp_modules
    assert grown["moe_slots_routed"] == 2 * layers * tokens.size * cfg.top_k
    assert 0 < grown["moe_slots_held"] < grown["moe_slots_routed"]
    assert grown["moe_slots_dropped"] == 0
    assert grown["moe_slots_held"] / 2 <= grown["moe_fullest_expert_slots"] <= grown["moe_slots_held"]


def test_routing_counters_fold_only_what_is_ready():
    class Pending:
        def is_ready(self):
            return False

    sink = moe.RoutingCounters.__new__(moe.RoutingCounters)
    import threading
    sink._lock, sink._pending = threading.Lock(), []
    sink._totals = dict.fromkeys(moe.ROUTING_STATS, 0)
    sink.push(dict(zip(moe.ROUTING_STATS, jnp.asarray([8, 4, 0, 3], jnp.int32))))
    sink.push({"moe_slots_held": Pending()})  # a step still running: push must not wait for it
    sink.push({})  # a family that counts nothing
    assert sink._totals["moe_slots_held"] == 4 and len(sink._pending) == 1


# ---------------------------------------------------------------------------
# the flash kernel at d_qk != d_v (Pallas interpreter)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(128, 128), (128, 256), (256, 128)])
def test_flash_kernel_with_two_head_sizes(causal, blocks):
    b, h, s, d_qk, d_v = 1, 2, 256, 192, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(kk, (b, h, s, d_qk)) for kk in keys[:2])
    v, ct = (jax.random.normal(kk, (b, h, s, d_v)) for kk in keys[2:])
    scale = d_qk ** -0.5

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, block_q=blocks[0],
                                  block_k=blocks[1], interpret=True)

    def dense(q, k, v):
        return fa._dense_reference(q, k, v, causal, scale)

    out = flash(q, k, v)
    assert out.shape == (b, h, s, d_v)
    np.testing.assert_allclose(out, dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * ct), argnums=(0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=5e-5, err_msg=f"d{name}")


def test_flash_kernel_bf16_operands_stay_close_to_f32():
    b, h, s = 1, 1, 256
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k = (jax.random.normal(kk, (b, h, s, 192)).astype(jnp.bfloat16) for kk in keys[:2])
    v = jax.random.normal(keys[2], (b, h, s, 128)).astype(jnp.bfloat16)
    out = fa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    want = fa._dense_reference(*(x.astype(jnp.float32) for x in (q, k, v)), True, 192 ** -0.5)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.abs(out.astype(jnp.float32) - want).max()) < 2e-2 * float(jnp.abs(want).max())


def test_committed_block_table_serves_the_cells_sequence():
    assert fa.tuned_blocks(8192) != (128, 128), "ops/flash_blocks.json lacks the 8192 sweep"
    bq, bk = fa.tuned_blocks(8192)
    assert 8192 % bq == 0 and 8192 % bk == 0


# ---------------------------------------------------------------------------
# the benchmark's blocked copy of the reference, its precision controls, and
# the readings that leg E and tools/latent_moe_precision.py take of a gradient
# ---------------------------------------------------------------------------


def _load(path, name):
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(name, os.path.join(root, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def rehearsal():
    """(the builder, the cell's configuration at its rehearsal cuts, the model
    config, state with a non-zero selection bias)."""
    import json
    import os
    builder = _load("benchmark/builders/joyai_llm_flash.py", "test_joyai_builder")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/joyai_llm_flash_ep32.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    mcfg = builder._model_config(cfg)
    params, tokens, targets = _state(mcfg, batch=2)
    return builder, cfg, mcfg, params, (tokens, targets)


def test_the_builders_blocked_copy_is_the_reference(rehearsal):
    builder, cfg, mcfg, params, batch = rehearsal
    got, grads = jax.value_and_grad(builder.plain_loss(cfg))(params, batch)
    want, want_grads = jax.value_and_grad(lambda p: ref.loss(mcfg, p, *batch))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert _worst(grads, want_grads)[0] < 1e-4


@pytest.mark.parametrize("statistics, least, most", [
    (jnp.float32, 1e-7, 2e-3),   # the precision the configuration states
    (jnp.bfloat16, 1e-7, 2e-3),  # the nearest below: still a sound loss at these widths
])
def test_precision_controls_keep_f32_parameters_and_loss(rehearsal, statistics, least, most):
    builder, cfg, _, params, batch = rehearsal
    want = float(builder.plain_loss(cfg)(params, batch))
    loss, grads = jax.value_and_grad(
        builder.plain_loss(cfg, jnp.bfloat16, statistics))(params, batch)
    assert loss.dtype == jnp.float32 and {g.dtype for g in grads.values()} == {jnp.dtype("float32")}
    assert least < abs(float(loss) - want) / want < most  # rounded somewhere, and not lost


def test_bf16_statistics_move_the_routers_gradient_most(rehearsal):
    builder, cfg, _, params, batch = rehearsal
    want = jax.grad(builder.plain_loss(cfg))(params, batch)
    apart = {}
    for name, statistics in (("stated", jnp.float32), ("below", jnp.bfloat16)):
        got = jax.grad(builder.plain_loss(cfg, jnp.bfloat16, statistics))(params, batch)
        apart[name] = max(float(jnp.linalg.norm(got[k] - want[k]) / jnp.linalg.norm(want[k]))
                          for k in want if k.endswith(".router"))
    assert apart["below"] > 1.5 * apart["stated"]


@pytest.mark.parametrize("fault, reading, least, most", [
    ("none", "projection", 0.0, 1e-6),
    ("halved_expert", "projection", 0.49, 0.51),
    ("lost_dense_leaf", "projection", 0.99, 1.01),
    ("halved_expert", "routed", 0.49, 0.51),
    ("lost_dense_leaf", "rest", 0.99, 1.01),
])
def test_gradient_readings_see_a_planted_fault(rehearsal, fault, reading, least, most):
    smoke = _load("chip_smoke.py", "test_chip_smoke_module")
    builder, cfg, _, params, batch = rehearsal
    want = jax.grad(builder.plain_loss(cfg))(params, batch)
    got = dict(want)
    if fault == "halved_expert":
        got["moe.e_up"] = 0.5 * want["moe.e_up"]
    if fault == "lost_dense_leaf":
        got["dense.wo"] = jnp.zeros_like(want["dense.wo"])
    read = smoke.gradient_readings(got, jax.device_get(want))
    assert least <= read[reading][1] <= most
    assert read["zero"] == ["moe.router_bias", "mtp.router_bias"]
    if fault != "none":
        assert read[reading][0] == ("moe.e_up" if fault == "halved_expert" else "dense.wo")


def test_pinned_choice_sends_every_token_to_the_same_experts(rehearsal):
    smoke = _load("chip_smoke.py", "test_chip_smoke_module")
    _, cfg, mcfg, params, _ = rehearsal
    pinned = smoke.pin_choice(params, cfg)
    assert {k for k in params if pinned[k] is not params[k]} == {"moe.router_bias", "mtp.router_bias"}
    lp = {k.split(".", 1)[1]: v[0] for k, v in pinned.items() if k.startswith("moe.")}
    g = jax.random.normal(jax.random.PRNGKey(3), (40, mcfg.d_model))
    ids, _ = moe.sigmoid_topk_route(g, lp["router"], lp["router_bias"], mcfg.top_k, mcfg.routed_scale)
    assert {tuple(sorted(row)) for row in np.asarray(ids).tolist()} == {(0, 1, 2, 3, 8, 9, 10, 11)}
    _, stats = lm.expert_mlp(mcfg, g, lp)
    routed, held, dropped, fullest = (int(v) for v in stats)
    assert (routed, held, dropped, fullest) == (40 * 8, 40 * 4, 0, 40)  # 16 x a uniform router's share


# ---------------------------------------------------------------------------
# what shares build_train_step with this family stays as it was
# ---------------------------------------------------------------------------


def test_bert_tiny_preset_loss_is_bit_equal_to_before_this_family():
    """Recorded at the parent commit (f12c015) with this very script: two
    adamw steps of ``tiny_test()`` on one device."""
    cfg = tfm.tiny_test()
    mesh = _mesh()
    params = tfm.shard_params(tfm.init_params(cfg, seed=0), cfg, mesh)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, cfg.max_seq)), jnp.int32)
    tx = optax.adamw(1e-3)
    step = tfm.build_train_step(cfg, mesh, tx, donate=False)
    state, losses = tx.init(params), []
    for _ in range(2):
        params, state, loss = step(params, state, tokens, jnp.roll(tokens, -1, 1))
        losses.append(np.float32(loss).tobytes().hex())
    assert losses == ["b3eb9140", "c0a78b40"]
