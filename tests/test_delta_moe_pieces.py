"""The pieces the gated-delta MoE family brought, each against its own
reference on the CPU: the partial rope, the output gate and the convolution
against hand-written cases, the softmax router
(parallel/moe.softmax_topk_route), the held share, the cell's blocked
reference (benchmark/builders/qwen3_next.py) against
models/delta_moe_reference.py, and the two other families' steps, which the
PR that brought this one must not have moved.  (The model against its
reference: tests/test_delta_moe.py; the chunked gated delta rule against the
token-by-token recurrence: tests/test_gated_delta.py.  Three files so that
``--dist loadfile`` spreads them.)
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import delta_moe as dm
from byteps_tpu.models import delta_moe_reference as ref
from byteps_tpu.models import moe_family as mf
from byteps_tpu.ops import causal_conv as cc
from byteps_tpu.ops import gated_delta as gd
from byteps_tpu.parallel import moe

import family_cases as fc
from family_cases import _kernel_names, _worst

_state = functools.partial(fc._state, dm)


# ---------------------------------------------------------------------------
# hand-written cases
# ---------------------------------------------------------------------------


def test_partial_rope_turns_the_first_dims_in_half_rotation_pairs():
    """Head of 6, rotary part 4: dims (0, 2) and (1, 3) are the pairs, dims 4
    and 5 pass; position 0 is left alone."""
    theta = 100.0
    x = jnp.arange(1.0, 19.0).reshape(3, 6)
    got = np.asarray(mf.rope_partial(x, 4, theta))
    np.testing.assert_allclose(got[0], x[0])
    np.testing.assert_allclose(got[:, 4:], x[:, 4:])
    for pos in (1, 2):
        for i, freq in ((0, 1.0), (1, theta ** -0.5)):
            a, b = float(x[pos, i]), float(x[pos, i + 2])
            c, s = np.cos(pos * freq), np.sin(pos * freq)
            np.testing.assert_allclose(got[pos, i], a * c - b * s, rtol=1e-5)
            np.testing.assert_allclose(got[pos, i + 2], b * c + a * s, rtol=1e-5)
    np.testing.assert_allclose(got, ref.rope(x, 4, theta), rtol=1e-5)


def test_output_gate_comes_from_the_query_projection():
    """One token attends to itself alone, so attention's output is its value:
    the mixer gives W_o (v ⊙ sigmoid(gate)), the gate being the second half
    of each head's slice of the query projection."""
    cfg = dm.tiny_delta_moe(full_attention_interval=1, n_layers=1, max_seq=1)
    params, _, _ = _state(cfg)
    lp = {k.split(".", 1)[1]: v[0] for k, v in params.items() if k.startswith("full.")}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 1, cfg.d_model))
    h = ref._rms(x, lp["mixer_norm"], cfg.norm_eps)[0, 0]
    gate = jnp.einsum("d,dhk->hk", h, lp["wq"])[:, cfg.head_dim:]
    v = jnp.repeat(jnp.einsum("d,dhk->hk", h, lp["wv"]), cfg.n_heads // cfg.n_kv_heads, axis=0)
    want = jnp.einsum("hk,hkd->d", v * jax.nn.sigmoid(gate), lp["wo"])
    np.testing.assert_allclose(dm._attention_mixer(cfg, x, lp)[0, 0], want, atol=1e-5)
    np.testing.assert_allclose(ref.attention_mixer(cfg, x, lp)[0, 0], want, atol=1e-5)
    closed = {**lp, "wq": lp["wq"].at[:, :, cfg.head_dim:].set(0.0)}  # gate 0: half open
    np.testing.assert_allclose(dm._attention_mixer(cfg, x, closed)[0, 0],
                               jnp.einsum("hk,hkd->d", 0.5 * v, lp["wo"]), atol=1e-5)


def test_causal_conv_reads_the_past_only():
    x = jnp.arange(1.0, 11.0).reshape(1, 5, 2)
    taps = jnp.array([[1.0, 0.0], [10.0, 0.0], [100.0, 0.0], [1000.0, 1.0]])
    got = np.asarray(mf.causal_conv(x, taps))
    np.testing.assert_allclose(got[0, :, 1], x[0, :, 1])  # the last tap is the present
    np.testing.assert_allclose(got[0, :, 0], [1000, 3100, 5310, 7531, 9753])


@pytest.mark.parametrize("s", [16, 12, 5], ids=["eight_rows", "four_rows", "one_row"])
def test_the_tiles_view_is_the_heads_of_the_token_major_array(s):
    """(B, S, n·d) → (B, S/r, n, r, d) and back: position (b, t, h·d + i)
    lands at (b, t // r, h, t % r, i), r = gcd(S, 8); a statistic over the
    last dim there is the statistic over a head's lanes."""
    b, n, d = 2, 3, 4
    x = jax.random.normal(jax.random.PRNGKey(s), (b, s, n * d))
    tiles = cc.head_tiles(x, n)
    rows = np.gcd(s, 8)
    assert tiles.shape == (b, s // rows, n, rows, d)
    np.testing.assert_array_equal(cc.tokens(tiles), x)
    for t, h in [(0, 0), (s - 1, n - 1), (s // 2, 1)]:
        np.testing.assert_array_equal(tiles[1, t // rows, h, t % rows], x[1, t, h * d:(h + 1) * d])
    np.testing.assert_allclose(
        cc.tokens(jnp.broadcast_to(jnp.sum(tiles ** 2, -1, keepdims=True), tiles.shape)),
        jnp.repeat(jnp.sum(x.reshape(b, s, n, d) ** 2, -1), d, axis=-1), rtol=1e-6)


def test_the_token_major_mixer_is_the_head_major_one():
    """``_delta_scan`` against the same mathematics written head by head on
    (B, H, S, d) arrays with the recurrence for the rule: values and the
    gradients of every input — batch 2, two key heads of two value heads, so
    that a head taken for another would show."""
    cfg = dm.tiny_delta_moe(lin_k_heads=2, lin_v_heads=4, max_seq=16)
    hk, hv, dk, dv = cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim
    b, s = 2, cfg.max_seq
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    qkvz = jax.random.normal(ks[0], (b, s, cfg.lin_channels + hv * dv))
    ba = jax.random.normal(ks[1], (b, s, 2 * hv))
    lp = {"conv": jax.random.normal(ks[2], (cfg.conv_kernel, cfg.lin_channels)),
          "a_log": jax.random.normal(ks[3], (hv,)), "dt_bias": jnp.ones((hv,)),
          "gdn_norm": 1.0 + 0.1 * jax.random.normal(ks[4], (dv,))}

    def head_major(qkvz, ba, lp):
        act = jax.nn.silu(mf.causal_conv(qkvz[..., :cfg.lin_channels], lp["conv"]))
        q, k, v = jnp.split(act, [hk * dk, 2 * hk * dk], axis=-1)
        heads = lambda t, n, d: jnp.moveaxis(t.reshape(b, s, n, d), 2, 1)  # noqa: E731
        q, k = (t * cc.inv_l2(t) for t in (heads(q, hk, dk), heads(k, hk, dk)))
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(ba[..., hv:] + lp["dt_bias"])
        r = hv // hk
        o = gd.gated_delta_recurrence(*(jnp.moveaxis(t, 1, 2) for t in (
            jnp.repeat(q * dk ** -0.5, r, 1), jnp.repeat(k, r, 1), heads(v, hv, dv))), g, beta)
        o = lp["gdn_norm"] * o * jax.lax.rsqrt(jnp.mean(o ** 2, -1, keepdims=True) + cfg.norm_eps)
        z = qkvz[..., cfg.lin_channels:].reshape(b, s, hv, dv)
        return (o * jax.nn.silu(z)).reshape(b, s, hv * dv)

    weigh = jnp.sin(jnp.arange(b * s * hv * dv, dtype=jnp.float32)).reshape(b, s, hv * dv)
    run = lambda fn: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * weigh), argnums=(0, 1, 2)))(qkvz, ba, lp)
    (got, got_g), (want, want_g) = run(lambda *a: dm._delta_scan(cfg, *a)), run(head_major)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_rebuilt_linear_layer_runs_the_inverse_once(monkeypatch, dtype):
    """The gradient through the linear layer as ``_hidden`` rebuilds it
    (``_layer_parts``: a ``jax.checkpoint`` that keeps, by name, what
    ``gated_delta_kernels.SAVED`` lists: the rule's triangular inverse, the
    chunks' entering states and o) holds one ``gdn_chunk_inverse``, one
    ``gdn_scan_fwd`` and one ``gdn_scan_bwd``; with no policy, what the family
    had, both forward kernels twice.  The gradients of x and of every
    parameter are the same bit for bit: the kept arrays are the ones the
    rebuild would have written.  The kernels in the interpreter: two chunks of
    64, a key head of two value heads, the state crossing a grid step."""
    from byteps_tpu.ops import gated_delta_kernels as gk

    monkeypatch.setattr(dm, "chunked_gated_delta_rule", functools.partial(
        gd.chunked_gated_delta_rule, interpret=True, blocks=(2, 1, 1)))
    cfg = dm.tiny_delta_moe(lin_k_heads=1, lin_v_heads=2, lin_k_dim=128, lin_v_dim=128,
                            chunk=64, max_seq=128, compute_dtype=dtype)
    assert cfg.remat
    mixer = ("mixer_norm", "w_qkvz", "w_ba", "conv", "a_log", "dt_bias", "gdn_norm", "w_out")
    ks = jax.random.split(jax.random.PRNGKey(3), len(mixer) + 1)
    lp = {name: 0.3 * jax.random.normal(key, dm.layer_shapes(cfg)["lin"][name])
          for name, key in zip(mixer, ks)}
    x = jax.random.normal(ks[-1], (2, cfg.max_seq, cfg.d_model)).astype(dtype)
    # the family's rebuilt layer, and the same layer under a checkpoint with no policy
    layers = {"family": dm._layer_parts(cfg)[0],
              "none": jax.checkpoint(dm._layer_parts(dataclasses.replace(cfg, remat=False))[0])}
    forward_kernels = {"family": 1, "none": 2}  # the inverse and the walk, each
    assert gk.SAVED == ("gdn_inverse", "gdn_entering", "gdn_out")

    def grad_of(layer):
        return jax.grad(lambda x, lp: jnp.sum(jnp.sin(layer(x, lp).astype(jnp.float32))),
                        argnums=(0, 1))

    grads = {}
    for policy, layer in layers.items():
        assert _kernel_names(grad_of(layer), x, lp) == sorted(
            [gk.INVERSE_KERNEL, gk.FWD_KERNEL] * forward_kernels[policy] + [gk.BWD_KERNEL]), policy
        grads[policy] = jax.jit(grad_of(layer))(x, lp)
    (dx, dlp), (dx_none, dlp_none) = grads["family"], grads["none"]
    np.testing.assert_array_equal(dx, dx_none)
    for name in mixer:
        assert float(jnp.abs(dlp[name]).max()) > 0, name  # every leaf is reached
        np.testing.assert_array_equal(dlp[name], dlp_none[name], err_msg=name)


def _scanned_hidden(cfg, params, tokens):
    """``delta_moe._hidden`` as it ran a period up to PR 59: the linear layers
    the body of a ``lax.scan`` over their stacked leaves, inside the scan over
    the periods.  The same parts (``_layer_parts``), the same order."""
    delta, attention, mlp = dm._layer_parts(cfg)

    def period(x, lps):
        x, each = jax.lax.scan(lambda x, lp: mlp(delta(x, lp), lp), x, lps["lin"])
        x, last = mlp(attention(x, lps["full"]), lps["full"])
        return x, jnp.sum(each, 0) + last

    x = params["embed"][tokens].astype(cfg.compute_dtype)
    x, stats = jax.lax.scan(period, x, {kind: mf.stack_of(params, kind)
                                        for kind in ("lin", "full")})
    return x, jnp.sum(stats, 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_unrolled_period_is_the_scanned_one(monkeypatch, dtype):
    """``_hidden`` runs a period's linear layers as a Python loop, each on its
    slice of the stacked ``lin`` leaves (nothing a layer keeps for its
    backward pass crosses a scan's stack).  Against the same layers run by a
    ``lax.scan``: the loss, the routing statistics and the gradient of every
    leaf — a slice's transpose writes each layer's gradient to its own rows of
    the stack.  Two periods of two linear layers and a full one; the rule's
    kernels in the interpreter (so the rebuild keeps what ``SAVED`` names),
    one sequence of two chunks."""
    monkeypatch.setattr(dm, "chunked_gated_delta_rule", functools.partial(
        gd.chunked_gated_delta_rule, interpret=True, blocks=(2, 1, 1)))
    cfg = dm.tiny_delta_moe(n_layers=6, full_attention_interval=3, lin_k_heads=1, lin_v_heads=2,
                            lin_k_dim=128, lin_v_dim=128, chunk=64, max_seq=128,
                            compute_dtype=dtype)
    assert cfg.remat and cfg.n_periods == 2
    params = dm.init_params(cfg, jax.random.PRNGKey(5))
    assert params["lin.w_qkvz"].shape[:2] == (2, 2)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, cfg.max_seq), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    def loss_of(hidden):
        def loss(params):
            x, stats = hidden(cfg, params, tokens)
            logits = dm._logits(cfg, x, params["norm_f"], params["head"])
            gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
            return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold), stats
        # the same operations in the same order, but XLA fuses a loop's body
        # apart from its surroundings and, left to itself, skips the bfloat16
        # roundings inside a fusion: held to them, the two agree to f32's last bits
        return jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})

    (loss, stats), grads = loss_of(dm._hidden)(params)
    (want, want_stats), want_grads = loss_of(_scanned_hidden)(params)
    tol = 1e-5
    np.testing.assert_allclose(loss, want, rtol=tol)
    np.testing.assert_array_equal(stats, want_stats)
    assert set(grads) == set(params)
    for name, leaf in want_grads.items():  # every leaf is reached, in every layer of its stack
        stacked = {"lin": 2, "full": 1}.get(name.split(".")[0], 0)
        reached = jnp.abs(leaf).reshape(leaf.shape[:stacked] + (-1,)).max(-1)
        assert bool(jnp.all(reached > 0)), name
    off, leaf = _worst(grads, want_grads)
    assert off < tol, (off, leaf)


def test_softmax_router_against_top_k_of_a_dense_softmax_with_planted_ties():
    """Tokens 0 and 1 see exactly equal logits for two experts at the edge of
    the choice: the router picks as ``lax.top_k`` does (the lower index), and
    the weights are the chosen probabilities renormalised."""
    t, d, e, k = 24, 8, 16, 3
    g = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, e))
    w = w.at[:, 7].set(w[:, 3])  # experts 3 and 7 tie for every token
    ids, weights = moe.softmax_topk_route(g, w, k)
    probs = jax.nn.softmax(jnp.dot(g, w, precision="highest"), axis=-1)
    want_p, want_ids = jax.lax.top_k(probs, k)
    np.testing.assert_array_equal(ids, want_ids)
    assert ids.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_allclose(weights, want_p / want_p.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(1), 1.0, rtol=1e-6)
    both = np.any(np.asarray(ids) == 3, axis=1) & np.any(np.asarray(ids) == 7, axis=1)
    only_7 = ~np.any(np.asarray(ids) == 3, axis=1) & np.any(np.asarray(ids) == 7, axis=1)
    assert both.any() and not only_7.any()  # a tie at the edge goes to the lower id


# ---------------------------------------------------------------------------
# the held share
# ---------------------------------------------------------------------------


def _mlp_params(cfg, seed=3):
    params = dm.init_params(cfg, jax.random.PRNGKey(seed))
    return {k.split(".", 1)[1]: v[0] for k, v in params.items() if k.startswith("full.")}


@pytest.mark.parametrize("held", [pytest.param(16, marks=pytest.mark.slow, id="32_shares_of_16"),
                                  pytest.param(64, id="8_shares_of_64")])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """The cell's cut at toy widths: a 512-wide router, top-10, in 32 shares
    of 16 experts.  The shares' routed parts, and the gated shared expert
    counted once, give what the reference gives with all 512.  (A share's
    ``expert_lo`` is a constant of its walk's loop, so every share compiles
    its own: tier-1 holds the same sum over 8 shares of 64, whose first and
    last are held to the reference alike.)"""
    base = dict(n_experts=512, top_k=10, full_attention_interval=1, n_layers=1)
    whole = dm.tiny_delta_moe(experts_held=512, **base)
    lp = _mlp_params(whole)
    g = jax.random.normal(jax.random.PRNGKey(9), (40, whole.d_model))
    want = ref.expert_mlp(whole, g, lp)
    shared = jax.nn.sigmoid(g @ lp["shared_gate"])[:, None] * mf.swiglu(
        g, lp["s_gate"], lp["s_up"], lp["s_down"])
    total, slots = shared, 0
    for lo in range(0, 512, held):
        share = dm.tiny_delta_moe(experts_held=held, expert_lo=lo, **base)
        lp_share = {**lp, **{w: lp[w][lo:lo + held] for w in ("e_gate", "e_up", "e_down")}}
        y, stats = dm.expert_mlp(share, g, lp_share)
        total = total + (y - shared)  # this share's routed part alone
        slots += int(stats[1])
        assert int(stats[2]) == 0
        if lo in (0, 512 - held):  # and a share is what the reference gives for that share
            np.testing.assert_allclose(y, ref.expert_mlp(share, g, lp_share), atol=1e-5)
    assert slots == 40 * 10  # every slot is held by exactly one share
    np.testing.assert_allclose(total, want, atol=2e-5)


test_no_slot_is_dropped_under_a_skewed_router = fc.skewed_router_case(
    dm.tiny_delta_moe, _mlp_params, dm.expert_mlp, ref.expert_mlp, by_bias=False,
    full_attention_interval=1, n_layers=1)


# ---------------------------------------------------------------------------
# the cell's blocked reference, and the programs this PR must not move
# ---------------------------------------------------------------------------


globals().update(fc.builder_cases(
    "delta_moe", ref, _state, builder="qwen3_next", config="qwen3_next_80b_ep32",
    toy=dict(hidden_size=32, head_dim=8, num_attention_heads=4, num_key_value_heads=2,
             linear_key_head_dim=8, linear_value_head_dim=6, linear_num_key_heads=2,
             linear_num_value_heads=4, moe_intermediate_size=16,
             shared_expert_intermediate_size=12, num_experts=4, router_width=16,
             num_experts_per_tok=3, vocab_size=96, max_seq=64, chunk=16),
    blocks=dict(RUN=16, HEAD_GROUPS=2)))
