"""The pieces the state-space MoE family brought, each against a hand-written
case on the CPU: the chunked selective scan (ops/ssd.py) against the
token-by-token recurrence, the convolution's bias, the grouped gated norm, the
ungated experts in ``held_expert_apply`` and the ungated shared expert in
``routed_mlp``, the router's order of operations, the shares of an
expert-parallel layer, one stack a layer in ``moe_family.walk``, and the
cell's blocked reference (benchmark/builders/nemotron_h.py) against
models/ssm_moe_reference.py.  (The model against its reference:
tests/test_ssm_moe.py.  Two files so that ``--dist loadfile`` spreads them.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import moe_family as mf
from byteps_tpu.models import ssm_moe as sm
from byteps_tpu.models import ssm_moe_reference as ref
from byteps_tpu.ops import ssd
from byteps_tpu.parallel import moe

import family_cases as fc
from family_cases import _kernel_names

_state = functools.partial(
    fc._state, sm, moved=lambda name: "norm" in name or name.endswith(("router_bias", "d_skip")))


def _layer(cfg, stack, seed=3, i=0):
    """Layer ``i`` of ``stack`` of a seeded state."""
    params, _, _ = _state(cfg, seed=seed)
    return {k.split(".", 1)[1]: v[i] for k, v in params.items() if k.startswith(stack + ".")}


# ---------------------------------------------------------------------------
# the chunked scan is the recurrence
# ---------------------------------------------------------------------------

H, G, P, N, CHUNK = 4, 2, 6, 5, 8


def _operands(n_chunks, seed=0, dt=(1e-3, 0.1), rate=(1.0, 16.0), batch=2):
    """x, dt, a, b, c at toy sizes: dt log-uniform over ``dt``, the rates
    uniform over ``rate`` (the published start's ranges by default)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    s = n_chunks * CHUNK
    x = jax.random.normal(keys[0], (batch, s, H * P))
    b, c = (jax.random.normal(k, (batch, s, G * N)) for k in keys[1:3])
    step = jnp.exp(jax.random.uniform(keys[3], (batch, s, H), minval=np.log(dt[0]),
                                      maxval=np.log(dt[1])))
    a = -jax.random.uniform(keys[4], (H,), minval=rate[0], maxval=rate[1])
    return x, step, a, b, c


def _chunked(x, dt, a, b, c, cdt=None):
    return ssd.ssd_scan(x, dt, a, b, c, H, G, chunk=CHUNK, compute_dtype=cdt)


def _by_token(x, dt, a, b, c):
    bsz, s, _ = x.shape
    return ssd.ssd_recurrence(x.reshape(bsz, s, H, P), dt, a, b.reshape(bsz, s, G, N),
                              c.reshape(bsz, s, G, N)).reshape(bsz, s, H * P)


#: name → how the operands are drawn: sequences of 1, 2 and 5 chunks (5 is no
#: multiple of a block of chunks: one chunk a block) and of 16 (two blocks of
#: 8), dt at both ends of its range, and a head whose decay over a chunk
#: underflows bf16 and f32 alike (dt·a·chunk = −16 000)
SCANS = {
    "one_chunk": dict(n_chunks=1),
    "two_chunks": dict(n_chunks=2),
    "five_chunks": dict(n_chunks=5),
    "two_blocks_of_chunks": dict(n_chunks=16),
    "smallest_steps": dict(n_chunks=2, dt=(1e-4, 1.0001e-4)),
    "largest_steps": dict(n_chunks=2, dt=(0.0999, 0.1)),
    "a_decay_that_underflows": dict(n_chunks=2, dt=(90.0, 110.0), rate=(10.0, 20.0)),
}


@pytest.mark.parametrize("case", sorted(SCANS))
def test_the_chunked_scan_is_the_recurrence(case):
    """Values and every operand's gradient, f32."""
    operands = _operands(**SCANS[case])
    weight = jax.random.normal(jax.random.PRNGKey(9), operands[0].shape)
    got, want = _chunked(*operands), _by_token(*operands)
    scale = float(jnp.abs(want).max())
    assert np.isfinite(scale) and scale > 0
    np.testing.assert_allclose(got, want, atol=2e-5 * scale)
    grads = [jax.grad(lambda *t: jnp.sum(f(*t) * weight), argnums=(0, 1, 2, 3, 4))(*operands)
             for f in (_chunked, _by_token)]
    for name, g, w in zip("x dt a b c".split(), *grads):
        assert np.all(np.isfinite(g)), name
        # a decay that underflows forgets: the rates' gradient is then 0 = 0
        np.testing.assert_allclose(g, w, atol=5e-5 * max(float(jnp.abs(w).max()), 1e-30),
                                   err_msg=name)


def test_bf16_operands_keep_f32_decay_sums_and_states():
    """bf16 operands of the products, f32 γ, Λ and states: y stays within a
    product's rounding of the f32 recurrence over 16 chunks — a state carried
    in bf16 would lose it chunk by chunk."""
    operands = _operands(16, dt=(0.01, 0.1), rate=(0.05, 0.5))  # a long memory
    want = _by_token(*operands)
    got = _chunked(*(t.astype(jnp.bfloat16) if i in (0, 3, 4) else t
                     for i, t in enumerate(operands)), cdt=jnp.bfloat16)
    assert got.dtype == jnp.float32
    off = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert off < 1e-2, off


def test_a_group_serves_its_heads_in_a_row():
    """Heads 0, 1 read group 0's B and C, heads 2, 3 group 1's: changing group
    1's B moves the last two heads alone."""
    x, dt, a, b, c = _operands(2)
    moved = b.at[..., N:].multiply(-2.0)
    base, after = _chunked(x, dt, a, b, c), _chunked(x, dt, a, moved, c)
    np.testing.assert_array_equal(base[..., :2 * P], after[..., :2 * P])
    assert float(jnp.abs(base[..., 2 * P:] - after[..., 2 * P:]).max()) > 0.1


def test_sequences_and_shapes_that_do_not_divide_are_refused():
    x, dt, a, b, c = _operands(2)
    with pytest.raises(ValueError, match="chunk 5 does not divide sequence 16"):
        ssd.ssd_scan(x, dt, a, b, c, H, G, chunk=5)
    with pytest.raises(ValueError, match="4 heads in 3 groups"):
        ssd.ssd_scan(x, dt, a, b, c, H, 3, chunk=CHUNK)


# ---------------------------------------------------------------------------
# the kernels (ops/ssd_kernels.py) in the Pallas interpreter
# ---------------------------------------------------------------------------


def _kernel_operands(heads, groups, p, n_chunks, batch=1, dt=(1e-3, 0.1), rate=(1.0, 16.0),
                     seed=0):
    """x, dt, a, b, c at the sizes the kernels tile: chunks of 128, a state of
    128, B and C at a product's scale."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    s, n = n_chunks * ssd.CHUNK, 128
    x = jax.random.normal(keys[0], (batch, s, heads * p))
    b, c = (jax.random.normal(k, (batch, s, groups * n)) * n ** -0.5 for k in keys[1:3])
    step = jnp.exp(jax.random.uniform(keys[3], (batch, s, heads), minval=np.log(dt[0]),
                                      maxval=np.log(dt[1])))
    a = -jax.random.uniform(keys[4], (heads,), minval=rate[0], maxval=rate[1])
    return x, step, a, b, c


#: name → the operands' sizes and the chunks a grid step of the forward | the
#: backward kernel: 1 | 2 | 8 heads a group (a head of 128 alone in its lane
#: tile, heads of 64 two a tile), a sequence of one block of chunks and of
#: several (the state crossing a grid step), two batches of two groups (every
#: index map), and a head whose decay over a chunk underflows
KERNEL_SCANS = {
    "one_head_a_group": dict(heads=2, groups=2, p=128, n_chunks=2, blocks=(2, 2)),
    "two_heads_a_group": dict(heads=2, groups=1, p=64, n_chunks=2, blocks=(1, 1)),
    "eight_heads_a_group": dict(heads=8, groups=1, p=64, n_chunks=2, blocks=(2, 1)),
    "several_blocks_of_chunks": dict(heads=4, groups=2, p=64, n_chunks=4, batch=2,
                                     blocks=(2, 2)),
    "a_decay_that_underflows": dict(heads=2, groups=1, p=64, n_chunks=2, blocks=(1, 2),
                                    dt=(90.0, 110.0), rate=(10.0, 20.0)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(KERNEL_SCANS))
def test_the_kernels_are_the_chunked_form_and_the_recurrence(case, dtype):
    """y and the gradients of x, dt, a, B and C through ``ssd_scan_fwd`` |
    ``ssd_scan_bwd``: against XLA's chunked form at the same operand dtype
    (the same casts: a product's rounding apart) and against the f32
    recurrence (at bf16 operands, bf16's rounding apart)."""
    sizes = dict(KERNEL_SCANS[case])
    blocks = sizes.pop("blocks")
    x, dt, a, b, c = _kernel_operands(**sizes)
    heads, groups = sizes["heads"], sizes["groups"]
    assert ssd._kernel_path(ssd.CHUNK, sizes["p"], 128, heads // groups, interpret=True)
    weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def cast(f):
        return lambda x, dt, a, b, c: f(x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype))

    forms = {
        "kernels": cast(lambda *t: ssd.ssd_scan(*t, heads, groups, compute_dtype=dtype,
                                                interpret=True, blocks=blocks)),
        "xla": cast(lambda *t: ssd._chunked_xla(*t, heads, groups, ssd.CHUNK, dtype)),
        "recurrence": lambda x, dt, a, b, c: ssd.ssd_recurrence(
            x.reshape(*x.shape[:2], heads, -1), dt, a, b.reshape(*b.shape[:2], groups, -1),
            c.reshape(*c.shape[:2], groups, -1)).reshape(x.shape),
    }
    out = {name: (f(x, dt, a, b, c), *jax.grad(lambda *t: jnp.sum(f(*t) * weight),
                                               argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c))
           for name, f in forms.items()}
    assert out["kernels"][0].dtype == jnp.float32
    exact = dtype == jnp.float32
    for oracle, tol in (("xla", 1e-4 if exact else 3e-2), ("recurrence", 1e-4 if exact else 3e-2)):
        for name, got, want in zip("y x dt a b c".split(), out["kernels"], out[oracle]):
            assert np.all(np.isfinite(got)), name
            # a decay that underflows forgets: the rates' gradient is then 0 = 0
            np.testing.assert_allclose(
                got, want, atol=tol * max(float(jnp.abs(want).max()), 1e-30),
                err_msg=f"{name} against {oracle}")
    if not exact:  # the same casts as XLA's form: y differs by a sum's order
        off = jnp.linalg.norm(out["kernels"][0] - out["xla"][0]) / jnp.linalg.norm(out["xla"][0])
        assert float(off) < 2e-3, off


@pytest.mark.parametrize("policy, forward_kernels", [("named", 1), ("none", 2)])
def test_a_rebuilt_scan_keeps_what_the_forward_kernel_wrote(policy, forward_kernels):
    """The gradient through a ``jax.checkpoint`` whose policy keeps, by name,
    what ``ssd.SAVED`` lists (the chunks' entering states and y) holds ONE
    ``ssd_scan_fwd`` and one ``ssd_scan_bwd``; with no policy the forward
    kernel twice.  The gradients are the same bit for bit: the kept arrays
    are the ones the rebuild would have written."""
    from byteps_tpu.ops import ssd_kernels as sk

    assert ssd.SAVED == sk.SAVED == ("ssd_entering", "ssd_out")
    operands = _kernel_operands(heads=2, groups=1, p=64, n_chunks=2)

    def scan(*t):
        return jnp.sin(ssd.ssd_scan(*t, 2, 1, interpret=True, blocks=(1, 1)))

    policies = {"named": jax.checkpoint_policies.save_only_these_names(*ssd.SAVED), "none": None}

    def grad_of(rebuilt):
        return jax.grad(lambda *t: jnp.sum(rebuilt(*t)), argnums=(0, 1, 2, 3, 4))

    grads = {name: grad_of(jax.checkpoint(scan, policy=kept)) for name, kept in policies.items()}
    assert _kernel_names(grads[policy], *operands) == sorted(
        [sk.FWD_KERNEL] * forward_kernels + [sk.BWD_KERNEL])
    for got, want in zip(jax.jit(grads[policy])(*operands), jax.jit(grads["none"])(*operands)):
        np.testing.assert_array_equal(got, want)


def _ssd_traces():
    from byteps_tpu.core.telemetry import counters

    snapshot = counters().snapshot()
    return snapshot.get("ssd_kernel_traces", 0), snapshot.get("ssd_xla_traces", 0)


def test_the_scans_path_is_chosen_from_platform_and_shapes(monkeypatch):
    """One function decides: off a TPU and at shapes the kernels do not tile,
    XLA's form; on a TPU at whole tiles, the kernels; each traced call
    counted."""
    from byteps_tpu.ops import _dispatch

    published = dict(chunk=128, head_dim=64, state=128, heads_a_group=8)
    assert not ssd._kernel_path(**published, interpret=False)  # this is a CPU
    assert ssd._kernel_path(**published, interpret=True)
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    assert ssd._kernel_path(**published, interpret=False)
    assert ssd._kernel_path(128, 128, 256, 1, interpret=False)
    assert ssd._kernel_path(128, 32, 128, 4, interpret=False)
    for chunk, p, n, r in [(CHUNK, P, N, H // G), (64, 64, 128, 8), (256, 64, 128, 8),
                           (128, 64, 64, 8), (128, 64, 128, 1), (128, 96, 128, 4),
                           (128, 64, 128, 3)]:
        assert not ssd._kernel_path(chunk, p, n, r, interpret=True), (chunk, p, n, r)

    # a stand-in TPU traces the kernels (nothing is lowered on this CPU) ...
    operands = _kernel_operands(heads=2, groups=1, p=64, n_chunks=2)
    kernels, xla = _ssd_traces()
    text = str(jax.make_jaxpr(lambda *t: ssd.ssd_scan(*t, 2, 1))(*operands))
    assert text.count("pallas_call") == 1 and _ssd_traces() == (kernels + 1, xla)
    # ... and XLA's form where the shapes do not tile, as tiny_ssm_moe's do not
    text = str(jax.make_jaxpr(_chunked)(*_operands(2)))
    assert "pallas_call" not in text and _ssd_traces() == (kernels + 1, xla + 1)


# ---------------------------------------------------------------------------
# the mixer's other parts
# ---------------------------------------------------------------------------


def test_the_convolution_takes_its_bias_before_the_silu():
    """``silu(conv(x) + bias)`` by hand at one channel: the bias is inside
    the activation, and zeros stand before the sequence's start."""
    cfg = sm.tiny_ssm_moe(remat=False)
    lp = _layer(cfg, "ssm")
    u = jax.random.normal(jax.random.PRNGKey(2), (1, cfg.max_seq, cfg.d_model))
    zxbcdt = u @ lp["w_in"]
    di, ch = cfg.d_inner, 3  # a channel of x
    raw = np.asarray(zxbcdt[0, :, di + ch])
    taps, bias = np.asarray(lp["conv"][:, ch]), float(lp["conv_bias"][ch])
    assert abs(bias) > 1e-3  # the rule draws it off zero
    padded = np.concatenate([np.zeros(3), raw])
    want = np.array([bias + sum(taps[j] * padded[t + j] for j in range(4))
                     for t in range(cfg.max_seq)])
    got = ref.conv(zxbcdt[..., di:di + cfg.conv_channels], lp["conv"], lp["conv_bias"])[0, :, ch]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the program's: causal_conv + bias; without the bias it is off by it
    mine = mf.causal_conv(zxbcdt[..., di:di + cfg.conv_channels], lp["conv"]) + lp["conv_bias"]
    np.testing.assert_allclose(mine[0, :, ch], want, rtol=1e-5, atol=1e-6)
    g = sm._ssd_part(cfg, zxbcdt, lp)
    no_bias = sm._ssd_part(cfg, zxbcdt, {**lp, "conv_bias": jnp.zeros_like(lp["conv_bias"])})
    assert float(jnp.abs(g - no_bias).max()) > 1e-3


def test_the_gated_norm_gates_first_and_norms_in_groups():
    """``w · g / rms(g)`` of ``g = y · silu(z)`` over each group's channels on
    its own, by hand; norm-then-gate (``delta_moe``'s order) and one norm over
    all channels both read otherwise."""
    rng = np.random.default_rng(4)
    y, z = (jnp.asarray(rng.normal(size=(2, 3, 12)), jnp.float32) for _ in range(2))
    w = jnp.asarray(1 + 0.2 * rng.normal(size=12), jnp.float32)
    got = sm.grouped_gated_norm(y, z, w, groups=3, eps=1e-5)
    g = np.asarray(y) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    want = np.concatenate(
        [g[..., i:i + 4] / np.sqrt(np.mean(g[..., i:i + 4] ** 2, -1, keepdims=True) + 1e-5)
         for i in (0, 4, 8)], axis=-1) * np.asarray(w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    whole = g / np.sqrt(np.mean(g ** 2, -1, keepdims=True) + 1e-5) * np.asarray(w)
    assert np.abs(whole - want).max() > 1e-2
    y_np = np.asarray(y)
    then_gate = np.concatenate(
        [y_np[..., i:i + 4] / np.sqrt(np.mean(y_np[..., i:i + 4] ** 2, -1, keepdims=True) + 1e-5)
         for i in (0, 4, 8)], axis=-1) * np.asarray(w) * (g / y_np)
    assert np.abs(then_gate - want).max() > 1e-2


@pytest.mark.parametrize("groups, width", [(1, 6), (3, 4), (8, 16)])
def test_a_runs_sum_and_its_spread_are_each_others_transposes(groups, width):
    """``over_runs`` is ``jnp.repeat`` and ``sum_runs`` the sum over a reshape
    — written as selects on a channel's run so that neither is a relayout on a
    TPU —, and each one's gradient is the other."""
    rng = np.random.default_rng(groups)
    stat = jnp.asarray(rng.normal(size=(2, 3, groups)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 3, groups * width)), jnp.float32)
    np.testing.assert_array_equal(sm.over_runs(stat, width), jnp.repeat(stat, width, axis=-1))
    np.testing.assert_allclose(sm.sum_runs(x, width),
                               x.reshape(2, 3, groups, width).sum(-1), rtol=1e-6)
    np.testing.assert_allclose(jax.grad(lambda s: jnp.sum(sm.over_runs(s, width) * x))(stat),
                               sm.sum_runs(x, width), rtol=1e-6)
    np.testing.assert_array_equal(jax.grad(lambda t: jnp.sum(sm.sum_runs(t, width) * stat))(x),
                                  sm.over_runs(stat, width))


def test_the_grouped_gated_norms_gradient_is_the_plain_forms():
    """Through ``over_runs`` | ``sum_runs``' hand-written transposes the
    gradients of y, z and the scale are what autodiff gives the reshape form."""
    rng = np.random.default_rng(6)
    y, z = (jnp.asarray(rng.normal(size=(2, 3, 12)), jnp.float32) for _ in range(2))
    w = jnp.asarray(1 + 0.2 * rng.normal(size=12), jnp.float32)

    def plain(y, z, w):
        g = (y * jax.nn.silu(z)).reshape(2, 3, 3, 4)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + 1e-5)
        return jnp.sum(jnp.sin(g.reshape(2, 3, 12) * w))

    got = jax.grad(lambda *t: jnp.sum(jnp.sin(sm.grouped_gated_norm(*t, groups=3, eps=1e-5))),
                   argnums=(0, 1, 2))(y, z, w)
    for name, g, want in zip("y z w".split(), got, jax.grad(plain, argnums=(0, 1, 2))(y, z, w)):
        np.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-6, err_msg=name)


def test_the_mixer_is_the_references_and_adds_d_x():
    cfg = sm.tiny_ssm_moe(remat=False)
    lp = _layer(cfg, "ssm")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.max_seq, cfg.d_model))
    u = ref._rms(x, lp["norm"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        want = x + ref.mamba2(cfg, u, lp)
        np.testing.assert_allclose(sm._ssm_layer(cfg, x, lp), want, rtol=2e-4, atol=2e-5)
        no_skip = sm._ssm_layer(cfg, x, {**lp, "d_skip": jnp.zeros_like(lp["d_skip"])})
    assert float(jnp.abs(no_skip - want).max()) > 1e-3


def test_the_attention_takes_no_positions():
    """Causal and position-free: the last token's output does not change when
    the tokens before it are reversed (a sum over a set of keys), and token 0
    sees only itself."""
    cfg = sm.tiny_ssm_moe(remat=False)
    lp = _layer(cfg, "attn")
    x = jax.random.normal(jax.random.PRNGKey(6), (1, cfg.max_seq, cfg.d_model))
    turned = jnp.concatenate([x[:, -2::-1], x[:, -1:]], axis=1)
    base, after = (sm._attention_layer(cfg, t, lp) - t for t in (x, turned))
    np.testing.assert_allclose(base[:, -1], after[:, -1], rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(base[:, 0] - after[:, 0]).max()) > 1e-2


# ---------------------------------------------------------------------------
# ungated experts: an absent gate matrix is data
# ---------------------------------------------------------------------------


def _expert_case(t=24, d=8, f=6, held=4, n_experts=8, k=2, seed=7):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    ids = jnp.asarray(np.stack([rng.permutation(n_experts)[:k] for _ in range(t)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(t, k)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(held, d, f)), jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(held, f, d)), jnp.float32)
    return g, ids, weights, w_gate, w_up, w_down, n_experts


def _by_hand(g, ids, weights, w_up, w_down, lo, act, w_gate=None):
    y = np.zeros(g.shape, np.float32)
    for t in range(g.shape[0]):
        for e, w in zip(np.asarray(ids[t]), np.asarray(weights[t])):
            if lo <= e < lo + w_up.shape[0]:
                hidden = act(g[t] @ w_up[e - lo]) if w_gate is None else (
                    act(g[t] @ w_gate[e - lo]) * (g[t] @ w_up[e - lo]))
                y[t] += w * np.asarray(hidden @ w_down[e - lo])
    return y


@pytest.mark.parametrize("gated", [False, True], ids=["two_matrices_relu2", "three_matrices_silu"])
def test_held_experts_with_and_without_a_gate_matrix(gated):
    """``held_expert_apply`` with ``w_gate`` None is ``down(act(up x))``; with
    one it is what it was.  Values and every operand's gradient, experts 2-5
    held of 8."""
    g, ids, weights, w_gate, w_up, w_down, n_experts = _expert_case()
    act = jax.nn.silu if gated else sm.relu2
    gate = w_gate if gated else None
    plan = moe.held_expert_plan(ids, 2, 4)

    def apply(g, weights, w_up, w_down):
        return moe.held_expert_apply(g, plan, weights, gate, w_up, w_down, n_experts, act)[0]

    with jax.default_matmul_precision("highest"):
        got = apply(g, weights, w_up, w_down)
        want = _by_hand(g, ids, weights, w_up, w_down, 2, act, gate)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

        def dense(g, weights, w_up, w_down):
            picked = jnp.zeros((g.shape[0], n_experts)).at[
                jnp.arange(g.shape[0])[:, None], ids].set(weights)
            y = jnp.zeros_like(g)
            for e in range(4):
                hidden = act(g @ w_up[e]) if gate is None else act(g @ gate[e]) * (g @ w_up[e])
                y = y + picked[:, 2 + e, None] * (hidden @ w_down[e])
            return y

        mark = jnp.asarray(np.random.default_rng(1).normal(size=got.shape), jnp.float32)
        grads = [jax.grad(lambda *a: jnp.sum(f(*a) * mark), argnums=(0, 1, 2, 3))(
            g, weights, w_up, w_down) for f in (apply, dense)]
    for a, b in zip(*grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_the_ungated_walk_reaches_its_tail_chunks():
    """Every token picks held experts: the slots overflow the first chunk and
    the tail's loop, with its own backward pass, takes two matrices too."""
    g, _, weights, _, w_up, w_down, n_experts = _expert_case(t=64)
    ids = jnp.tile(jnp.asarray([[2, 3]], jnp.int32), (64, 1))
    plan = moe.held_expert_plan(ids, 2, 4)
    first, _ = moe.held_walk(128, 4, n_experts)
    assert first < 128

    def apply(g, w_up, w_down):
        y, stats = moe.held_expert_apply(g, plan, weights, None, w_up, w_down, n_experts, sm.relu2)
        return jnp.sum(y * y), stats

    with jax.default_matmul_precision("highest"):
        (got, stats), grads = jax.value_and_grad(apply, argnums=(0, 1, 2), has_aux=True)(
            g, w_up, w_down)
        want = _by_hand(g, ids, weights, w_up, w_down, 2, sm.relu2)
    held = dict(zip(moe.ROUTING_STATS, np.asarray(stats)))
    assert held["moe_slots_held"] == 128 and held["moe_slots_dropped"] == 0
    assert held["moe_rows_walked"] > first
    assert float(got) == pytest.approx(float(np.sum(want * want)), rel=1e-4)
    assert all(np.all(np.isfinite(x)) and np.any(x) for x in grads[:1]) and np.any(grads[1][0])
    assert not np.any(grads[1][2])  # experts 4, 5 are held and never chosen


# the six MoE configurations' model and expert widths (benchmark/configs/*.json)
_PUBLISHED_WIDTHS = {
    "joyai_d": (2048, 2048), "joyai_f": (768, 768), "qwen3_next_d": (2048, 2048),
    "qwen3_next_f": (512, 512), "lfm2_d": (2048, 2048), "lfm2_f": (1536, 1536),
    "trinity_d": (2048, 2048), "trinity_f": (1024, 1024), "smallthinker_d": (2560, 2560),
    "smallthinker_f": (768, 768), "nemotron_d": (2688, 3072), "nemotron_f": (1856, 2048)}
# what the CPU tests and the rehearsals' toy cuts use, and the floor's two sides
_SMALL_WIDTHS = {f"small_{n}": (n, n) for n in (6, 8, 16, 48, 64, 128, 200, 1000, 1023)}


@pytest.mark.parametrize("n, want", [
    *_PUBLISHED_WIDTHS.values(), *_SMALL_WIDTHS.values(), (1025, 1536), (1152, 1536),
    (1280, 1280), (3712, 4096)],
    ids=[*_PUBLISHED_WIDTHS, *_SMALL_WIDTHS, "over_the_floor", "nine_lane_tiles",
         "a_multiple_of_256", "twenty_nine_lane_tiles"])
def test_held_tiles_is_a_rule_of_the_width_alone(n, want):
    """A multiple of 256 and every width under the floor go to the grouped
    products as they are; a large width that XLA:TPU would tile by 128 is
    rounded up to whole tiles of 512.  Of the twelve published widths only
    nemotron's two move."""
    assert moe.held_tiles(n) == want
    assert moe.held_tiles(want) == want  # what the rule gives it leaves alone
    assert 512 <= moe.HELD_TILES_FLOOR <= 1024


@pytest.mark.parametrize("gated", [False, True], ids=["two_matrices_relu2", "three_matrices_silu"])
def test_padded_widths_add_exact_zeros(gated, monkeypatch):
    """With the rule's floor lowered the toy widths 8 and 6 are padded to 512
    each: ``y``, the statistics and every operand's gradient are what the
    unpadded call gives — every extra term of every sum is an exact zero, in
    the first chunk and in the tail's loop with its own backward pass — and
    the gradients come back at the operands' own shapes."""
    g, _, weights, w_gate, w_up, w_down, n_experts = _expert_case(t=64)
    ids = jnp.tile(jnp.asarray([[2, 3], [4, 2]], jnp.int32), (32, 1))  # every slot is held
    plan = moe.held_expert_plan(ids, 2, 4)
    assert moe.held_walk(128, 4, n_experts)[0] < 128  # so tail chunks run
    act, gate = (jax.nn.silu, (w_gate,)) if gated else (sm.relu2, ())
    mark = jnp.asarray(np.random.default_rng(2).normal(size=g.shape), jnp.float32)

    def apply(g, weights, w_up, w_down, *gate):
        y, stats = moe.held_expert_apply(g, plan, weights, *(gate or (None,)), w_up, w_down,
                                         n_experts, act)
        return jnp.sum(y * mark), (y, stats)

    def run():
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(apply, argnums=tuple(range(4 + gated)), has_aux=True)(
                g, weights, w_up, w_down, *gate)

    def lowered():  # a new function a call: jax keeps a trace by the function traced
        return jax.jit(lambda *a: apply(*a)).lower(g, weights, w_up, w_down, *gate).as_text()

    (_, (y, stats)), grads = run()
    assert "x512x" not in lowered()
    monkeypatch.setattr(moe, "HELD_TILES_FLOOR", 0)
    assert (moe.held_tiles(8), moe.held_tiles(6)) == (512, 512)
    assert "4x512x512x" in lowered()  # the products do see the padded matrices
    (_, (y_wide, stats_wide)), grads_wide = run()
    held = dict(zip(moe.ROUTING_STATS, np.asarray(stats)))
    assert held["moe_slots_held"] == 128 and held["moe_slots_dropped"] == 0
    assert held["moe_rows_walked"] > moe.held_walk(128, 4, n_experts)[0]
    np.testing.assert_array_equal(stats, stats_wide)
    np.testing.assert_allclose(y_wide, y, rtol=1e-6, atol=1e-6)
    for wide, narrow, operand in zip(grads_wide, grads, (g, weights, w_up, w_down, *gate)):
        assert wide.shape == narrow.shape == operand.shape
        assert np.any(narrow)
        np.testing.assert_allclose(wide, narrow, rtol=1e-5, atol=1e-5)


def test_the_shared_expert_is_ungated_where_the_layer_has_no_gate_matrix():
    """``routed_mlp`` on a layer without ``s_gate``: ``down(relu(up x)²)`` at
    weight 1 beside the routed part; with ``s_gate`` a SwiGLU as before."""
    cfg = sm.tiny_ssm_moe(remat=False)
    lp = _layer(cfg, "moe")
    g = jax.random.normal(jax.random.PRNGKey(8), (20, cfg.d_model))
    route = lambda g32, lp: sm._route(cfg, g32, lp)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        both, _ = mf.routed_mlp(cfg, g, g, lp, route, "shared_expert", act=sm.relu2)
        routed, _ = mf.routed_mlp(cfg, g, g, lp, route, None, act=sm.relu2)
        shared = np.square(np.maximum(np.asarray(g @ lp["s_up"]), 0)) @ np.asarray(lp["s_down"])
        np.testing.assert_allclose(both - routed, shared, rtol=1e-4, atol=1e-4)
        # and the whole layer is the reference's
        np.testing.assert_allclose(both, ref.experts(cfg, g, lp), rtol=1e-4, atol=1e-4)
        gate = jax.random.normal(jax.random.PRNGKey(9), lp["s_up"].shape) * 0.1
        gated, _ = mf.routed_mlp(cfg, g, g, {**lp, "s_gate": gate}, route, "shared_expert")
        plain, _ = mf.routed_mlp(cfg, g, g, lp, route, None)
        np.testing.assert_allclose(gated - plain, mf.swiglu(g, gate, lp["s_up"], lp["s_down"]),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the router's order of operations
# ---------------------------------------------------------------------------


def test_the_bias_picks_and_does_not_weigh_and_the_scale_comes_last():
    """Scores are sigmoids; the ``top_k`` largest of score + bias are chosen;
    the weights are the UNBIASED scores of the chosen, renormalised to 1, then
    times 2.5: by hand on one token whose bias changes the choice."""
    cfg = sm.tiny_ssm_moe(top_k=2, routed_scale=2.5)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, 0.5, -2.0, 0.2, -0.5]])
    router = jnp.eye(8)  # g @ router = g
    bias = jnp.zeros(8).at[3].set(5.0)  # expert 3 is picked whatever its score
    ids, weights = sm._route(cfg, logits, {"router": router, "router_bias": bias})
    assert sorted(np.asarray(ids[0])) == [0, 3]
    s = 1 / (1 + np.exp(-np.asarray(logits[0])))
    want = {0: 2.5 * s[0] / (s[0] + s[3]), 3: 2.5 * s[3] / (s[0] + s[3])}
    for e, w in zip(np.asarray(ids[0]), np.asarray(weights[0])):
        assert w == pytest.approx(want[int(e)], rel=1e-6)
    assert float(weights.sum()) == pytest.approx(2.5, rel=1e-6)
    # the reference's router, written in the published order, agrees
    full = ref.route(cfg, logits, {"router": router, "router_bias": bias})
    assert float(full[0, 0]) == pytest.approx(want[0], rel=1e-6)
    assert float(full[0, 3]) == pytest.approx(want[3], rel=1e-6)
    assert np.count_nonzero(np.asarray(full)) == 2


# ---------------------------------------------------------------------------
# the shares of an expert-parallel layer add up
# ---------------------------------------------------------------------------


def test_sixteen_shares_add_up_to_the_uncut_expert_layer():
    """The 16 shares of a 16-way expert-parallel layer — 2 of 32 experts each,
    the router 32 wide on every one — give routed parts that add up, with the
    shared expert counted ONCE, to the uncut reference's expert layer."""
    whole = sm.tiny_ssm_moe(n_experts=32, experts_held=32, top_k=6, remat=False)
    lp = _layer(whole, "moe")
    g = jax.random.normal(jax.random.PRNGKey(11), (40, whole.d_model))
    route = lambda cfg: (lambda g32, lp: sm._route(cfg, g32, lp))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = ref.experts(whole, g, lp)
        total, held = jnp.zeros_like(g), 0
        for rank in range(16):
            share = sm.tiny_ssm_moe(n_experts=32, experts_held=2, expert_lo=2 * rank, top_k=6,
                                    remat=False)
            mine = {**lp, "e_up": lp["e_up"][2 * rank:2 * rank + 2],
                    "e_down": lp["e_down"][2 * rank:2 * rank + 2]}
            y, stats = mf.routed_mlp(share, g, g, mine, route(share), None, act=sm.relu2)
            total, held = total + y, held + int(stats[1])
            # the reference given the same share leaves out what the others hold, too
            np.testing.assert_allclose(
                y, ref.experts(share, g, mine) - sm.relu2(g @ lp["s_up"]) @ lp["s_down"],
                rtol=2e-4, atol=2e-4)
        shared = sm.relu2(g @ lp["s_up"]) @ lp["s_down"]
    assert held == 40 * 6  # every slot is held by exactly one share
    np.testing.assert_allclose(total + shared, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# one stack a layer
# ---------------------------------------------------------------------------


def test_the_walk_runs_one_part_a_layer_in_the_patterns_order():
    cfg = sm.tiny_ssm_moe(layer_types=tuple("MEMEM*EME"), remat=False)
    params = {f"{stack}.w": 10.0 * (i + 1) + jnp.arange(4.0)
              for i, stack in enumerate(("ssm", "attn", "moe"))}
    ran = []

    def part(stack):
        def run(x, lp):
            ran.append((stack, float(lp["w"])))
            return (x + 1, jnp.arange(5, dtype=jnp.int32)) if stack == "moe" else x + 1
        return run

    x, stats = mf.walk(cfg, {s: part(s) for s in ("ssm", "attn", "moe")}, ("attn",), params,
                       jnp.zeros(()))
    assert ran == [("ssm", 10.0), ("moe", 30.0), ("ssm", 11.0), ("moe", 31.0), ("ssm", 12.0),
                   ("attn", 20.0), ("moe", 32.0), ("ssm", 13.0), ("moe", 33.0)]
    assert float(x) == 9 and list(stats) == [0, 4, 8, 12, 16]


# ---------------------------------------------------------------------------
# the cell's blocked reference is the plain one
# ---------------------------------------------------------------------------


# toy widths: the seven layers the cell runs, two heads a group, three query
# heads a key/value head, four chunks a sequence
globals().update(fc.builder_cases(
    "ssm_moe", ref, _state, builder="nemotron_h", config="nemotron_twotower_30b_ep16",
    toy=dict(first_layer=0, num_hidden_layers=7, hidden_size=32, mamba_num_heads=4,
             mamba_head_dim=6, n_groups=2, ssm_state_size=5, chunk_size=16,
             num_attention_heads=6, num_key_value_heads=2, head_dim=8,
             moe_intermediate_size=16, moe_shared_expert_intermediate_size=32,
             n_routed_experts=4, router_width=16, num_experts_per_tok=3, vocab_size=96,
             max_seq=64),
    never_learns=("moe.router_bias",),  # picks, never learns
    precision=(0.0, 5e-2)))


def test_the_builder_runs_the_patterns_first_layers(rehearsal):
    builder, cfg, mcfg, _, _ = rehearsal
    assert len(cfg["hybrid_override_pattern"]) == 52
    assert mcfg.layer_types == tuple("MEMEM*E") and mcfg.residual_layers == 52
    assert (mcfg.n_experts, mcfg.experts_held, mcfg.expert_lo, mcfg.top_k) == (16, 4, 0, 3)
    assert (mcfg.routed_scale, mcfg.norm_eps, mcfg.chunk) == (2.5, 1e-5, 16)
    assert (mcfg.dt_min, mcfg.dt_max, mcfg.dt_floor) == (1e-3, 0.1, 1e-4)
    for key, other in (("mlp_hidden_act", "silu"), ("use_conv_bias", False), ("n_group", 2),
                       ("norm_topk_prob", False), ("tie_word_embeddings", True),
                       ("sliding_window", 4096), ("time_step_limit", [0, 1.0])):
        with pytest.raises(ValueError, match=key):
            builder._model_config({**cfg, key: other})
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        builder._model_config({**cfg, "first_layer": 50})
