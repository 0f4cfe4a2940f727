"""The pieces the latent-attention MoE family brought, each against its own
reference on the CPU: the held-expert layer (parallel/moe.py), the routing
counters, the flash kernel at d_qk != d_v (ops/flash_attention.py, Pallas
interpreter), and the pass between the mixer's products and that kernel
(ops/mla_heads.py, Pallas interpreter and XLA's form).  (The model against
its reference: tests/test_latent_moe.py.)
"""

import functools
import importlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu as bps
from byteps_tpu.models import latent_moe as lm
from byteps_tpu.models import latent_moe_reference as ref
from byteps_tpu.models import moe_family as mf
from byteps_tpu.models import transformer as tfm
from byteps_tpu.ops import mla_heads as mh
from byteps_tpu.parallel import moe

import family_cases as fc
from family_cases import _mesh, _worst

_state = functools.partial(fc._state, lm, bias=0.3)

fa = importlib.import_module("byteps_tpu.ops.flash_attention")


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
    shared = mf.swiglu(g, lp["s_gate"], lp["s_up"], lp["s_down"])
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
    slots than the first chunk holds (72 rows of 128 | 256 at 8 experts, 24
    of 256 at 32: the tail chunks run, 16 | 8 rows each), none dropped,
    output = reference."""
    cfg = lm.tiny_latent_moe(n_experts=n_experts, experts_held=2, expert_lo=4)
    lp = _layer_params(cfg)
    lp["router_bias"] = jnp.zeros(n_experts).at[jnp.asarray(favoured)].set(10.0)
    tokens = 64
    g = jax.random.normal(jax.random.PRNGKey(2), (tokens, cfg.d_model))
    y, stats = jax.jit(lambda g, lp: lm.expert_mlp(cfg, g, lp))(g, lp)
    routed, held, dropped, fullest, walked = (int(v) for v in stats)
    assert routed == tokens * cfg.top_k
    assert held >= tokens * len(favoured)
    first_rows, tail_rows = moe.held_walk(routed, 2, n_experts)
    assert held <= walked < held + tail_rows and (walked - first_rows) % tail_rows == 0
    if len(favoured) == 2:  # every slot is held: every chunk runs, and that is every slot
        assert held == routed == walked
    assert dropped == 0
    assert fullest == tokens  # a token picks an expert at most once
    np.testing.assert_allclose(y, ref.expert_mlp(cfg, g, lp), atol=1e-5)
    # and the gradient flows through every chunk
    got = jax.grad(lambda lp: jnp.sum(lm.expert_mlp(cfg, g, lp)[0] ** 2))(lp)
    want = jax.grad(lambda lp: jnp.sum(ref.expert_mlp(cfg, g, lp) ** 2))(lp)
    off, leaf = _worst({k: got[k] for k in ("e_gate", "e_down", "router")}, want)
    assert off < 1e-4, f"{leaf}: {off:.2e}"


def _plain_held(g, ids, weights, w_gate, w_up, w_down, lo, act):
    """Every held expert on every token, what a token did not choose masked."""
    y = jnp.zeros(g.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        out = (act(g @ w_gate[e]) * (g @ w_up[e])) @ w_down[e]
        y = y + jnp.sum(jnp.where(ids == lo + e, weights, 0.0), axis=1)[:, None] * out
    return y


@pytest.mark.parametrize("load", [0.5, 1.0, 1.2, 1.5, 2.5, None],
                         ids=lambda load: f"{load}x" if load else "every_slot")
@pytest.mark.parametrize("n_held, n_experts, act", [(2, 8, jax.nn.silu), (4, 32, jax.nn.relu)],
                         ids=["2of8-silu", "4of32-relu"])
def test_the_walk_follows_the_slots_held(n_held, n_experts, act, load):
    """``held_expert_apply`` on a plan that holds ``load`` times the even
    share of the slots (every slot where ``load`` is None), unevenly over the
    held experts: the plain reference's output and gradients, nothing dropped,
    and rows walked in proportion — at most max(9/8, L + 1/4) of the even
    load (and a tile of 8) up to L = 2, at most 2 L of it beyond."""
    tokens, k, d, f, lo = 256, 4, 16, 24, 3
    every = tokens * k
    even = every * n_held // n_experts
    held = every if load is None else int(load * even)
    keys = jax.random.split(jax.random.PRNGKey(n_experts + held), 8)
    # which slots are held, and by whom: expert lo takes half of them
    local = jnp.maximum(jax.random.randint(keys[0], (every,), -n_held, n_held), 0)
    elsewhere = (lo + n_held + jax.random.randint(keys[1], (every,), 0, n_experts - n_held)) \
        % n_experts
    is_held = jax.random.permutation(keys[2], every) < held
    ids = jnp.where(is_held, lo + local, elsewhere).astype(jnp.int32).reshape(tokens, k)
    weights = jax.random.uniform(keys[3], (tokens, k), minval=0.1)
    g = jax.random.normal(keys[4], (tokens, d))
    w_gate, w_up = (0.3 * jax.random.normal(key, (n_held, d, f)) for key in keys[5:7])
    w_down = 0.3 * jax.random.normal(keys[7], (n_held, f, d))

    def run(g, weights, w_gate, w_up, w_down):
        plan = moe.held_expert_plan(ids, lo, n_held)
        return moe.held_expert_apply(g, plan, weights, w_gate, w_up, w_down, n_experts, act)

    def loss(fn):  # fn → (y, anything): a scalar of y, its gradients, and fn's own result
        def scalar(*a):
            out = fn(*a)
            return jnp.sum(jnp.sin(out[0])), out
        return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2, 3, 4), has_aux=True))

    read = g, weights, w_gate, w_up, w_down
    (_, (y, stats)), got = loss(run)(*read)
    (_, (want_y, _)), want = loss(
        lambda *a: (_plain_held(a[0], ids, *a[1:], lo, act), None))(*read)
    routed, n, dropped, fullest, walked = (int(v) for v in stats)
    assert (routed, n, dropped) == (every, held, 0)
    assert fullest == max(int(jnp.sum(ids == lo + e)) for e in range(n_held))
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    for name, a, b in zip(("g", "weights", "w_gate", "w_up", "w_down"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=5e-5, err_msg=name)
    share = held / even
    assert walked >= held
    assert walked <= (max(9 / 8, share + 1 / 4) * even + 8 if share <= 2 else 2 * share * even)


@pytest.mark.parametrize("every, n_held, n_experts, want", [
    (32768 * 6, 8, 64, (27648, 6144)),  # smallthinker_21b_ep8
    (16384 * 4, 8, 64, (9216, 2048)),  # lfm2_24b_a2b_ep8
    (16384 * 8, 8, 128, (9216, 2048)),  # trinity_mini_ep16
    (16384 * 10, 16, 512, (6144, 1536)),  # qwen3_next_80b_ep32
    (16384 * 8, 8, 256, (4608, 1024)),  # joyai_llm_flash_ep32
    (1024, 2, 8, (288, 64)), (1024, 4, 32, (144, 32)),  # the walk's test above, in tiles of 8
    (64, 8, 8, (64, 16)),  # all experts held: one chunk, every slot
    (6, 1, 16, (6, 8)),  # fewer slots than a tile
])
def test_the_walk_is_sized_from_shapes(every, n_held, n_experts, want):
    """The first chunk 9/8 of the even load and the tail chunks a quarter of
    it, in whole tiles: the five cells' shapes, and the edges."""
    assert moe.held_walk(every, n_held, n_experts) == want


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
    assert grown["moe_slots_held"] <= grown["moe_rows_walked"] <= grown["moe_slots_routed"]
    assert grown["moe_slots_held"] / 2 <= grown["moe_fullest_expert_slots"] <= grown["moe_slots_held"]


def test_routing_counters_fold_only_what_is_ready():
    class Pending:
        def is_ready(self):
            return False

    sink = moe.RoutingCounters.__new__(moe.RoutingCounters)
    sink._lock, sink._pending = threading.Lock(), []
    sink._totals = dict.fromkeys(moe.ROUTING_STATS, 0)
    sink.push(dict(zip(moe.ROUTING_STATS, jnp.asarray([8, 4, 0, 3, 8], jnp.int32))))
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
# from the mixer's products to the flash kernel's operands (ops/mla_heads.py)
# ---------------------------------------------------------------------------

_NOPE, _ROPE, _THETA = 128, 64, 32e6  # the published head: the kernels take no other


def _as_the_parent_wrote_them(q, kv, k_rope):
    """q (B, H, S, 192), kv (B, H, S, 256) as ``bsr,rhk->bhsk`` gave them and
    the rotary key (B, S, 64), columns in the weights' own order → the flash
    kernel's q, k, v by interleaved rope, concatenate and broadcast: what
    ``latent_moe._attention`` did up to PR 49, rounded once as it was."""
    rope = lambda x: ref._rope(x.astype(jnp.float32), _THETA).astype(x.dtype)  # noqa: E731
    b, h, s, _ = q.shape
    key = jnp.broadcast_to(rope(k_rope[:, None]), (b, h, s, _ROPE))
    return (jnp.concatenate([q[..., :_NOPE], rope(q[..., _NOPE:])], axis=-1),
            jnp.concatenate([kv[..., :_NOPE], key], axis=-1), kv[..., _NOPE:])


def _through_the_pass(q, kv, k_rope, interpret):
    """The same three from the same columns, ordered as the mixer's weights
    order them now: token-major, nope | rope | key | value apart, the rotary
    columns even-first."""
    b, h, s, _ = q.shape
    tokens = lambda x: x.transpose(0, 2, 1, 3).reshape(b, s, -1)  # noqa: E731
    return mh.mla_heads(tokens(q[..., :_NOPE]), tokens(mh.even_first(q[..., _NOPE:])),
                        tokens(kv[..., :_NOPE]), tokens(kv[..., _NOPE:]),
                        mh.even_first(k_rope), h, _THETA, interpret=interpret)


def _mixer_arrays(dtype, b=2, h=4, s=64, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, *dims: jax.random.normal(k, dims).astype(dtype)  # noqa: E731
    return ((normal(keys[0], b, h, s, _NOPE + _ROPE), normal(keys[1], b, h, s, 2 * _NOPE),
             normal(keys[2], b, s, _ROPE)),
            (normal(keys[3], b, h, s, s), normal(keys[4], b, h, s, _NOPE)))


def _seen(operands, weights):
    """A scalar of what attention sees of (q, k, v): the scores and v."""
    q, k, v = (x.astype(jnp.float32) for x in operands)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
    return jnp.sum(scores * weights[0]) + jnp.sum(v * weights[1])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("interpret", [True, False], ids=["kernels", "xla"])
def test_the_pass_builds_what_rope_and_concatenate_built(monkeypatch, dtype, interpret):
    """Forward: the parts without positions and v are the parent's bit for
    bit, the rotary parts the parent's in even-first order.  Backward: every
    cotangent — the q product's, the kv product's, and the rotary key's with
    its sum over the heads — is the parent's, taken through the permutation
    back to the weights' own column order."""
    monkeypatch.setattr(mh, "BLOCK_ROWS", 32)  # 2 query blocks x 2 batches x 2 pairs
    assert mh._kernel_path(64, 4, _NOPE, _ROPE, _NOPE, interpret) == interpret
    arrays, weights = _mixer_arrays(dtype)
    want = _as_the_parent_wrote_them(*arrays)
    got = _through_the_pass(*arrays, interpret)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    tol = dict(rtol=0, atol=1e-5) if dtype == jnp.float32 else dict(rtol=2**-7, atol=0)
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape and g.dtype == dtype
        np.testing.assert_array_equal(f32(g[..., :_NOPE]), f32(w[..., :_NOPE]), err_msg=name)
    for name, g, w in zip("qk", got, want):
        np.testing.assert_allclose(f32(g[..., _NOPE:]), f32(mh.even_first(w[..., _NOPE:])),
                                   err_msg=f"{name}'s rotary part", **tol)
    grads = [jax.grad(lambda *a, f=f: _seen(f(*a), weights), argnums=(0, 1, 2))(*arrays)
             for f in (lambda *a: _through_the_pass(*a, interpret), _as_the_parent_wrote_them)]
    for name, g, w in zip(("q", "kv", "the rotary key"), *grads):
        assert g.shape == w.shape and g.dtype == dtype
        # bf16: one rounding of a sum over 4 heads x 64 keys, in another order
        worst = np.abs(f32(w)).max()
        np.testing.assert_allclose(f32(g), f32(w), rtol=0, err_msg=f"d {name}",
                                   atol=(1e-4 if dtype == jnp.float32 else 2**-6) * worst)


def test_the_kernels_are_xlas_form_of_the_pass(monkeypatch):
    """Operands and every cotangent, bf16: the two implementations round the
    same f32 equations once, so they agree to the last place (a fused
    multiply-add here or there), the key's sum over the heads too."""
    monkeypatch.setattr(mh, "BLOCK_ROWS", 32)
    arrays, _ = _mixer_arrays(jnp.bfloat16, seed=1)
    cts = _as_the_parent_wrote_them(*_mixer_arrays(jnp.bfloat16, seed=2)[0])
    outs = []
    for interpret in (True, False):
        out, back = jax.vjp(lambda *a: _through_the_pass(*a, interpret), *arrays)
        outs.append(out + back(cts))
    for g, w in zip(*outs):
        g, w = (np.asarray(x.astype(jnp.float32)) for x in (g, w))
        np.testing.assert_allclose(g, w, rtol=2**-7, atol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("interpret", [True, False], ids=["kernels", "xla"])
def test_the_turn_by_no_angle_is_the_identity(monkeypatch, dtype, interpret):
    """``theta`` None — a configuration without positions — takes the same
    pass with tables of cos 1, sin 0: q is ``[q_nope | q_rope]``, k ``[k_nope |
    the shared key]`` as they came, BIT FOR BIT in either implementation, and
    every cotangent is the plain concatenation's (the shared key's its sum
    over the heads)."""
    monkeypatch.setattr(mh, "BLOCK_ROWS", 32)
    assert mh._kernel_path(64, 4, _NOPE, _ROPE, _NOPE, interpret) == interpret
    (q, kv, key), weights = _mixer_arrays(dtype, seed=4)
    b, h, s, _ = q.shape
    tokens = lambda x: x.transpose(0, 2, 1, 3).reshape(b, s, -1)  # noqa: E731

    def through(q, kv, key):
        return mh.mla_heads(tokens(q[..., :_NOPE]), tokens(q[..., _NOPE:]),
                            tokens(kv[..., :_NOPE]), tokens(kv[..., _NOPE:]), key, h, None,
                            interpret=interpret)

    def plain(q, kv, key):
        shared = jnp.broadcast_to(key[:, None], (b, h, s, _ROPE))
        return q, jnp.concatenate([kv[..., :_NOPE], shared], axis=-1), kv[..., _NOPE:]

    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    for name, g, w in zip("qkv", through(q, kv, key), plain(q, kv, key)):
        assert g.dtype == dtype
        np.testing.assert_array_equal(f32(g), f32(w), err_msg=name)
    grads = [jax.grad(lambda *a, f=f: _seen(f(*a), weights), argnums=(0, 1, 2))(q, kv, key)
             for f in (through, plain)]
    for name, g, w in zip(("q", "kv", "the shared key"), *grads):
        worst = np.abs(f32(w)).max()
        np.testing.assert_allclose(f32(g), f32(w), rtol=0, err_msg=f"d {name}",
                                   atol=(1e-5 if dtype == jnp.float32 else 2**-6) * worst)


def test_a_score_does_not_see_a_common_permutation():
    """The pass turns the rotary columns even-first, the configuration's rope
    turns them interleaved: q·k over a head is the same number either way,
    because q's and k's columns take the same order."""
    arrays, _ = _mixer_arrays(jnp.float32, seed=3)
    scores = lambda q, k, v: jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")  # noqa: E731
    np.testing.assert_allclose(scores(*_through_the_pass(*arrays, False)),
                               scores(*_as_the_parent_wrote_them(*arrays)), rtol=0, atol=2e-4)
    # and a permutation that q and k do NOT share is seen
    q, k, v = _through_the_pass(*arrays, False)
    assert not np.allclose(scores(q, _as_the_parent_wrote_them(*arrays)[1], v),
                           scores(q, k, v), atol=1e-1)


def test_a_sequence_of_no_whole_block_takes_xlas_form(monkeypatch):
    """48 rows under blocks of 32: the chooser answers from the shapes, and
    the result is still the parent's."""
    monkeypatch.setattr(mh, "BLOCK_ROWS", 32)
    assert not mh._kernel_path(48, 4, _NOPE, _ROPE, _NOPE, interpret=True)
    assert not mh._kernel_path(64, 3, _NOPE, _ROPE, _NOPE, interpret=True)  # heads pair up
    assert not mh._kernel_path(64, 4, 8, 4, 8, interpret=True)  # tiny_latent_moe's head
    assert not mh._kernel_path(64, 4, _NOPE, _ROPE, _NOPE, interpret=False)  # no TPU here
    arrays, weights = _mixer_arrays(jnp.float32, s=48, seed=4)
    got = _through_the_pass(*arrays, interpret=True)
    for g, w in zip(got, _as_the_parent_wrote_them(*arrays)):
        np.testing.assert_allclose(g[..., :_NOPE], w[..., :_NOPE], atol=1e-5)
    np.testing.assert_allclose(_seen(got, weights),
                               _seen(_as_the_parent_wrote_them(*arrays), weights), rtol=1e-5)


def test_merge_heads_is_the_output_projection_and_its_transposes():
    """``merge_heads`` writes dO with batch and head as the product's batch
    dimensions; the numbers are ``bhsk,hkd->bsd``'s and its two transposes'."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    o = jax.random.normal(keys[0], (2, 4, 16, 8))
    wo = jax.random.normal(keys[1], (4, 8, 32))
    ct = jax.random.normal(keys[2], (2, 16, 32))
    plain = lambda o, wo: jnp.einsum("bhsk,hkd->bsd", o, wo, precision="highest")  # noqa: E731
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(mh.merge_heads(o, wo), plain(o, wo), atol=1e-5)
        got = jax.grad(lambda *a: jnp.sum(mh.merge_heads(*a) * ct), argnums=(0, 1))(o, wo)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * ct), argnums=(0, 1))(o, wo)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4)
