"""Builder for the ``qwen3_next_80b_ep32`` configuration
(benchmark/configs/qwen3_next_80b_ep32.json): Qwen3-Next's block at its
published widths — three gated-delta-rule layers to one gated full-attention
layer, 512-wide softmax routing — one chip's share of a 32-way
expert-parallel deployment.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over a ``DeltaMoEConfig``).

``plain_loss`` is a copy of ``byteps_tpu/models/delta_moe_reference.py``
(float32, ``highest`` matmul precision, the delta rule token by token, dense
causal attention with repeated key/value heads, a loop over the held experts
with a mask), computed in blocks so that three steps at the timed size fit
beside the state that set-up holds: a remat'ed layer at a time and in it a
sequence at a time, the recurrence as a ``lax.scan`` over the positions (in
remat'ed runs of ``RUN`` tokens, so that the backward pass keeps a state a
run and not a token) and a group of heads at a time, attention a block of
queries at a time, the experts and the logits a block of rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
#: rows of queries, and of logits, that the reference holds at a time; in how
#: many runs, each with its own range of keys, the queries are taken; the
#: tokens of the recurrence between two kept states; and in how many groups the
#: rule's key heads are taken
Q_BLOCK, ROW_BLOCK, KEY_GROUPS, RUN, HEAD_GROUPS = 256, 2048, 4, 128, 4


def _layer_counts(cfg: dict) -> tuple:
    """(linear layers, full-attention layers) that are run."""
    full = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return cfg["num_hidden_layers"] - full, full


def _rotary_dim(cfg: dict) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward (3 x forward) of one sequence, recomputation not
    counted, of the mathematics and not of padding or of the chunked form.  A
    token's matrix products: the mixers' projections; in every layer the
    router, the gated shared expert and the slots the held experts expect
    (top_k x held / router width = 0.3125 a token); the head.  Causal
    attention: (S + 1) / 2 keys a query, 2 (d + d) a score, every query
    head.  The delta rule: 6 d_k d_v a token a value head (decay, S^T k, the
    rank-one update, S^T q)."""
    s, d, v = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"]
    linear, full = _layer_counts(cfg)
    hv, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lin_macs = d * (2 * cfg["linear_num_key_heads"] * dk + 2 * hv * dv + 2 * hv) + hv * dv * d
    full_macs = d * (2 * h * hd + 2 * kv * hd) + h * hd * d
    held_slots = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    mlp_macs = (d * cfg["router_width"] + d + 3 * d * cfg["shared_expert_intermediate_size"]
                + held_slots * 3 * d * cfg["moe_intermediate_size"])
    macs = linear * lin_macs + full * full_macs + (linear + full) * mlp_macs + d * v
    attention = full * (s + 1) / 2 * h * 2 * (hd + hd)
    rule = linear * hv * 6 * dk * dv
    return float(3 * s * (2 * macs + attention + rule))


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"qwen3_next builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of byteps_tpu/models/delta_moe_reference.py, blocked)
# ---------------------------------------------------------------------------


def _rms(x, w, eps, st=jnp.float32):
    """RMSNorm, ``1 + w`` scale, with its statistics in ``st``; returns ``st``."""
    x = x.astype(st)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1 + w.astype(st))


def _l2(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, rotary_dim, theta):
    """x (..., S, d): x cos + rotate_half(x) sin on the first ``rotary_dim``
    dims, where rotate_half([a | b]) = [-b | a]; f32 inside."""
    s, half = x.shape[-2], rotary_dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], axis=-1) for f in (jnp.cos, jnp.sin))
    rot, rest = x[..., :rotary_dim].astype(jnp.float32), x[..., rotary_dim:]
    half_turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([(rot * cos + half_turned * sin).astype(x.dtype), rest], axis=-1)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def _kind(params: dict, name: str) -> dict:
    return {k.split(".", 1)[1]: v for k, v in params.items() if k.startswith(name + ".")}


def plain_loss(cfg: dict, compute=jnp.float32, statistics=jnp.float32):
    """Mean next-token cross-entropy over the program's flat parameter dict,
    in float32 whatever ``compute_dtype`` says: the reference is the
    mathematics, and the program's bf16 is held to it by ``reference_rtol``
    and ``reference_update_rtol``.

    The two dtypes are for the controls that those limits are set from
    (``tools/latent_moe_precision.py --config qwen3_next_80b_ep32``; run.py
    passes neither): ``compute`` is what the matrix products' operands and
    the residual stream are rounded to, ``statistics`` what the norms'
    statistics, the router's probabilities, the softmax and the rule's decay
    and state are computed in.  (bfloat16, float32) is the precision the
    configuration states, (bfloat16, bfloat16) the nearest below it.
    Parameters and the loss stay float32 in all of them."""
    eps, theta, rotary = cfg["rms_norm_eps"], float(cfg["rope_theta"]), _rotary_dim(cfg)
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lo, held, top_k = cfg["held_expert_lo"], cfg["num_experts"], cfg["num_experts_per_tok"]
    channels = 2 * hk * dk + hv * dv
    f32 = jnp.float32

    def rms(x, w):
        return _rms(x, w, eps, statistics).astype(compute)

    def w(lp, *names):
        return (lp[n].astype(compute) for n in names)

    # ---- the gated delta rule, token by token --------------------------------

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs  # (H, d), (H,)
        state = jnp.exp(g_t)[:, None, None] * state
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def run_of_tokens(state, xs):
        return lax.scan(token, state, xs)

    def delta_rule(q, k, v, g, beta):
        """One sequence: q, k (S, H, d_k), v (S, H, d_v), g, beta (S, H), all
        in ``statistics``; the state too.  Returns o (S, H, d_v)."""
        s = q.shape[0]
        run = min(RUN, s)
        xs = tuple(x.reshape((s // run, run) + x.shape[1:]) for x in (q, k, v, g, beta))
        _, o = lax.scan(run_of_tokens, jnp.zeros((v.shape[1], dk, dv), statistics), xs)
        return o.reshape(v.shape)

    def delta_mixer(x, lp):
        """x (1, S, D), one sequence.  A group of key heads, with the value
        heads they serve, at a time: heads meet only in the output
        projection's sum, and the temporaries are one group's."""
        s, d = x.shape[1], x.shape[2]
        groups = HEAD_GROUPS if hk % HEAD_GROUPS == 0 else 1
        m, mv = hk // groups, hv // groups
        hn = rms(x, lp["mixer_norm"])[0]

        def by_group(cols, width):  # (..., heads·width) → (G, ..., heads/G·width)
            return jnp.moveaxis(cols.reshape(cols.shape[:-1] + (groups, width)), -2, 0)

        def qkvz(cols):  # the columns [q | k | v | z] of a matrix, each by group
            bounds = [hk * dk, 2 * hk * dk, channels]
            return tuple(by_group(part, width) for part, width in zip(
                jnp.split(cols, bounds, axis=-1), (m * dk, m * dk, mv * dv, mv * dv)))

        wq, wk, wv, wz = qkvz(lp["w_qkvz"])
        tq, tk, tv = qkvz(jnp.pad(lp["conv"], ((0, 0), (0, hv * dv))))[:3]
        wb, wa = (by_group(part, mv) for part in jnp.split(lp["w_ba"], 2, axis=-1))
        per_group = (wq, wk, wv, wz, tq, tk, tv, wb, wa, by_group(lp["a_log"], mv),
                     by_group(lp["dt_bias"], mv),
                     lp["w_out"].reshape(groups, mv * dv, d))

        def conv_silu(cols, taps):
            mixed = (hn @ cols.astype(compute)).astype(f32)
            kernel = taps.shape[0]
            padded = jnp.pad(mixed, ((kernel - 1, 0), (0, 0)))
            conv = sum(padded[j:j + s] * taps[j] for j in range(kernel))
            return jax.nn.silu(conv).astype(statistics)

        @jax.checkpoint
        def group(hn, ws):
            wq, wk, wv, wz, tq, tk, tv, wb, wa, a_log, dt_bias, w_out = ws
            q = _l2(conv_silu(wq, tq).reshape(s, m, dk)) * dk ** -0.5
            k = _l2(conv_silu(wk, tk).reshape(s, m, dk))
            v = conv_silu(wv, tv).reshape(s, mv, dv)
            z = (hn @ wz.astype(compute)).reshape(s, mv, dv)
            beta = jax.nn.sigmoid((hn @ wb.astype(compute)).astype(statistics))
            g = -jnp.exp(a_log).astype(statistics) * jax.nn.softplus(
                (hn @ wa.astype(compute)).astype(statistics) + dt_bias.astype(statistics))
            # each key head serves hv / hk value heads
            o = delta_rule(jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1),
                           v, g, beta)
            o = lp["gdn_norm"].astype(statistics) * o * lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            o = o * jax.nn.silu(z.astype(statistics))
            return o.astype(compute).reshape(s, mv * dv) @ w_out.astype(compute)

        return jnp.sum(lax.map(lambda ws: group(hn, ws), per_group), axis=0)[None]

    # ---- gated softmax attention ----------------------------------------------

    @jax.checkpoint
    def attend(q, k, v, first):
        """One block of queries, whose first row is ``first``, against keys 0.."""
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=f32) / hd ** 0.5
        visible = jnp.arange(k.shape[2])[None, :] <= (first + jnp.arange(q.shape[2]))[:, None]
        p = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf).astype(statistics), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(compute), v)

    def causal_attention(q, k, v):
        """Dense causal attention, never more than Q_BLOCK rows of scores at
        a time: the queries in KEY_GROUPS runs, each against the keys up to
        its end (so the masked half is mostly not computed), a run's blocks
        one after another (``lax.map``)."""
        b, nh, s, _ = q.shape
        run = max(s // KEY_GROUPS, 1)
        block = min(Q_BLOCK, run)
        out = []
        for a in range(0, s, run):
            blocks = q[:, :, a:a + run].reshape(b, nh, run // block, block, -1)
            keys, values = k[:, :, :a + run], v[:, :, :a + run]
            o = lax.map(lambda xs: attend(xs[0], keys, values, xs[1]),
                        (jnp.moveaxis(blocks, 2, 0), a + block * jnp.arange(run // block)))
            out.append(jnp.moveaxis(o, 0, 2).reshape(b, nh, run, -1))
        return jnp.concatenate(out, axis=2)

    def attention_mixer(x, lp):
        wq, wk, wv, wo = w(lp, "wq", "wk", "wv", "wo")
        hn = rms(x, lp["mixer_norm"])
        q_gate = jnp.einsum("bsd,dhk->bhsk", hn, wq)
        q, gate = q_gate[..., :hd], q_gate[..., hd:]
        k = jnp.einsum("bsd,dhk->bhsk", hn, wk)
        v = jnp.einsum("bsd,dhk->bhsk", hn, wv)
        q = _rope(rms(q, lp["q_norm"]), rotary, theta)
        k = _rope(rms(k, lp["k_norm"]), rotary, theta)
        o = causal_attention(q, jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1))
        o = o * jax.nn.sigmoid(gate.astype(statistics)).astype(compute)
        return jnp.einsum("bhsk,hkd->bsd", o, wo)

    # ---- the experts ------------------------------------------------------------

    def expert_mlp(x, lp):
        """A block of rows at a time, each rebuilt in the backward pass: the
        experts' hidden activations are one block's."""
        b, s, d = x.shape
        block = min(ROW_BLOCK, b * s)
        shared_gate = lp["shared_gate"].astype(compute)
        shared_w = tuple(w(lp, "s_gate", "s_up", "s_down"))
        expert_w = tuple(w(lp, "e_gate", "e_up", "e_down"))

        @jax.checkpoint
        def rows(xb):
            g_st = _rms(xb, lp["mlp_norm"], eps, statistics)
            g = g_st.astype(compute)
            probs = jax.nn.softmax(g_st @ lp["router"].astype(statistics), axis=-1)
            _, ids = lax.top_k(probs, top_k)
            chosen = jnp.zeros_like(probs).at[jnp.arange(block)[:, None], ids].set(1.0)
            weights = probs * chosen / jnp.sum(probs * chosen, axis=-1, keepdims=True)
            open_ = jax.nn.sigmoid(jnp.dot(g, shared_gate, preferred_element_type=f32))[:, None]
            shared = open_ * _swiglu(g, *shared_w).astype(f32)

            # the held experts one after another, each over every row, masked by its weight
            def add_expert(y, e):
                w_gate, w_up, w_down, weight = e
                return y + weight[:, None].astype(f32) * _swiglu(g, w_gate, w_up, w_down), None

            y, _ = lax.scan(add_expert, shared, (*expert_w, weights[:, lo:lo + held].T))
            return y.astype(compute)

        return lax.map(rows, x.reshape(-1, block, d)).reshape(b, s, d)

    def layer_of(mixer):
        def layer(x, lp):
            x = x + mixer(x, lp).astype(compute)
            return x + expert_mlp(x, lp)
        return layer

    def xent(x, scale, head, targets):
        """(sum of cross-entropies over targets >= 0, their count), the
        logits a block of rows at a time."""
        d = x.shape[-1]
        block = min(ROW_BLOCK, x.size // d)
        rows, tgt = x.reshape(-1, block, d), targets.reshape(-1, block)

        @jax.checkpoint
        def one(xb, tb):
            logits = jnp.dot(rms(xb, scale), head.astype(compute), preferred_element_type=f32)
            gold = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * (tb >= 0))

        total = jnp.sum(lax.map(lambda xs: one(*xs), (rows, tgt)))
        return total, jnp.sum(tgt >= 0).astype(f32)

    def a_sequence_at_a_time(layer):
        """The layer over a batch, one sequence after another, each rebuilt
        in the backward pass: sequences meet only in the loss's mean, and a
        layer's temporaries are one sequence's."""
        one = jax.checkpoint(lambda row, lp: layer(row[None], lp)[0])

        def batched(x, lp):
            return lax.map(lambda row: one(row, lp), x), None
        return batched

    linear, full = (a_sequence_at_a_time(layer_of(m)) for m in (delta_mixer, attention_mixer))

    def period(x, lps):
        if lps["lin"]:
            x, _ = lax.scan(linear, x, lps["lin"])
        return full(x, lps["full"])

    def loss(params, batch):
        tokens, targets = batch
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(compute)
            x, _ = lax.scan(period, x, {"lin": _kind(params, "lin"),
                                        "full": _kind(params, "full")})
            total, count = xent(x, params["norm_f"], params["head"], targets)
        return total / count

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.delta_moe import DeltaMoEConfig

    return DeltaMoEConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rotary_dim=_rotary_dim(cfg),
        rope_theta=float(cfg["rope_theta"]),
        lin_k_heads=cfg["linear_num_key_heads"], lin_v_heads=cfg["linear_num_value_heads"],
        lin_k_dim=cfg["linear_key_head_dim"], lin_v_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"], chunk=cfg["chunk"],
        d_expert=cfg["moe_intermediate_size"], d_shared=cfg["shared_expert_intermediate_size"],
        n_experts=cfg["router_width"], experts_held=cfg["num_experts"],
        expert_lo=cfg["held_expert_lo"], top_k=cfg["num_experts_per_tok"],
        norm_eps=cfg["rms_norm_eps"], max_seq=cfg["max_seq"],
        compute_dtype=_DTYPES[cfg["compute_dtype"]], remat=cfg["remat"],
    )


def _mesh4(mesh):
    """The program's step wants a (dp, pp, sp, tp) mesh."""
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    return make_training_mesh(
        mesh.size, {"dp": mesh.shape["dp"], "pp": 1, "sp": 1, "tp": 1},
        devices=list(mesh.devices.flat),
    )


def make_state(cfg: dict, key: jax.Array, mesh):
    """Parameters (``delta_moe.init_params``) and one fixed batch of uniform
    token ids over the held rows with next-token targets, made on the device
    from ``key`` in one jitted call."""
    from byteps_tpu.models import delta_moe
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]

    def make(key):
        k_params, k_tokens = jax.random.split(key)
        tokens = jax.random.randint(
            k_tokens, (batch, mcfg.max_seq), 0, mcfg.vocab_size, jnp.int32)
        return delta_moe.init_params(mcfg, k_params), tokens, jnp.roll(tokens, -1, axis=1)

    rows = NamedSharding(mesh, P("dp", "sp"))
    specs = {k: NamedSharding(mesh, s) for k, s in param_specs(mcfg).items()}
    params, tokens, targets = jax.jit(make, out_shardings=(specs, rows, rows))(key)
    return params, (tokens, targets), batch


def build(cfg: dict, traffic: dict, params, batch, mesh):
    """``build_train_step`` with the optimizer state made as the program's
    examples make it (``jax.jit(tx.init)``).  Returns ``step()``, which
    dispatches one training step and returns ``(loss, parameters)``; the
    step donates ``params``."""
    from byteps_tpu.models.transformer import build_train_step

    if traffic["step_path"] != "local":
        raise ValueError(f"qwen3_next builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
