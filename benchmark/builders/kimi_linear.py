"""Builder for the ``kimi_linear_48b_ep32`` configuration
(benchmark/configs/kimi_linear_48b_ep32.json): Kimi Linear's block at its
published widths — Kimi Delta Attention (a delta rule whose decay is a vector
over the key's channels) three layers to one of multi-head latent attention
without positions, 256-wide sigmoid routing beside a shared expert, a leading
dense layer — one chip's share of a 32-way expert-parallel deployment: the
model's own layers 1–5.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over a ``ChannelDeltaMoEConfig``).

``plain_loss`` is a copy of
``byteps_tpu/models/channel_delta_moe_reference.py`` (float32, ``highest``
matmul precision, the delta rule token by token, dense causal attention, a
loop over the held experts with a mask), computed in blocks so that three
steps at the timed size fit beside the state that set-up holds: a remat'ed
layer at a time, the recurrence as a ``lax.scan`` over the positions (in
remat'ed runs of ``RUN`` tokens, so that the backward pass keeps a state a run
and not a token) and a group of heads at a time, attention a block of queries
at a time, the MLPs and the logits a block of rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
#: rows of queries, and of an MLP's tokens and of logits, that the reference
#: holds at a time; in how many runs, each with its own range of keys, the
#: queries are taken; the tokens of the recurrence between two kept states; and
#: in how many groups the rule's heads are taken
Q_BLOCK, ROW_BLOCK, KEY_GROUPS, RUN, HEAD_GROUPS = 128, 2048, 4, 128, 4
MIXERS = {"kda_layers": "channel_delta", "full_attn_layers": "latent_attention"}


def layer_types(cfg: dict) -> tuple:
    """The mixers of the layers that are run, the model's layers 1 to
    ``num_hidden_layers`` by PUBLISHED index: ``linear_attn_config`` lists
    every layer of the model under one of its two kinds."""
    lists = cfg["linear_attn_config"]
    kinds = {i: kind for key, kind in MIXERS.items() for i in lists[key]}
    return tuple(kinds[i] for i in range(1, cfg["num_hidden_layers"] + 1))


def _n_dense(cfg: dict) -> int:
    """The leading layers, of those that are run, whose MLP is dense."""
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def _widths(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    return dict(h=lin["num_heads"], dk=lin["head_dim"], dv=lin["head_dim"], r=lin["head_dim"],
                n=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], dvh=cfg["v_head_dim"], rank=cfg["kv_lora_rank"])


def parameter_count(cfg: dict) -> dict:
    """Parameters by part, as they are held: a delta mixer, the latent mixer,
    an expert layer's MLP, the dense MLP, embedding + head, and the whole."""
    w, d = _widths(cfg), cfg["hidden_size"]
    h, dk, dv, r = w["h"], w["dk"], w["dv"], w["r"]
    delta = (d + d * h * (2 * dk + dv) + d * (2 * r + h)
             + lin_taps(cfg) * h * (2 * dk + dv) + r * h * dk + r * h * dv
             + h + h * dk + dv + h * dv * d)
    latent = (d + d * w["n"] * (w["nope"] + w["rope"]) + d * (w["rank"] + w["rope"]) + w["rank"]
              + w["rank"] * w["n"] * (w["nope"] + w["dvh"]) + w["n"] * w["dvh"] * d)
    f, fs = cfg["moe_intermediate_size"], cfg["moe_intermediate_size"] * cfg["num_shared_experts"]
    moe = (d + d * cfg["router_width"] + cfg["router_width"]
           + cfg["num_experts"] * 3 * d * f + 3 * d * fs)
    dense = d + 3 * d * cfg["intermediate_size"]
    kinds, n_dense = layer_types(cfg), _n_dense(cfg)
    ends = 2 * cfg["vocab_size"] * d + d
    whole = (kinds.count("channel_delta") * delta + kinds.count("latent_attention") * latent
             + n_dense * dense + (len(kinds) - n_dense) * moe + ends)
    return dict(delta_mixer=delta, latent_mixer=latent, expert_mlp=moe, dense_mlp=dense,
                embedding_and_head=ends, whole=whole)


def lin_taps(cfg: dict) -> int:
    return cfg["linear_attn_config"]["short_conv_kernel_size"]


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward (3 x forward) of one sequence, recomputation not
    counted, of the mathematics and not of padding or of the chunked form.  A
    token's matrix products: the mixers' projections (the low-rank gates
    through their 128); the dense MLP or the router, the shared expert and the
    slots the held experts expect (top_k x held / router width = 0.25 a
    token); the head.  Causal attention: (S + 1) / 2 keys a query, 2 (d_qk +
    d_v) a score, every head.  The delta rule: 6 d_k d_v + d_k a token a head
    (S^T k, the rank-one update, S^T q, the decay of the state's entries; the
    channels' exponentials)."""
    s, d, v = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"]
    w, kinds = _widths(cfg), layer_types(cfg)
    h, dk, dv, r = w["h"], w["dk"], w["dv"], w["r"]
    delta_macs = d * h * (2 * dk + dv) + d * (2 * r + h) + r * h * (dk + dv) + h * dv * d
    latent_macs = (d * w["n"] * (w["nope"] + w["rope"]) + d * (w["rank"] + w["rope"])
                   + w["rank"] * w["n"] * (w["nope"] + w["dvh"]) + w["n"] * w["dvh"] * d)
    f = cfg["moe_intermediate_size"]
    held_slots = cfg["num_experts_per_token"] * cfg["num_experts"] / cfg["router_width"]
    moe_macs = d * cfg["router_width"] + 3 * d * f * cfg["num_shared_experts"] + held_slots * 3 * d * f
    n_delta, n_latent = kinds.count("channel_delta"), kinds.count("latent_attention")
    n_dense = _n_dense(cfg)
    macs = (n_delta * delta_macs + n_latent * latent_macs + n_dense * 3 * d * cfg["intermediate_size"]
            + (len(kinds) - n_dense) * moe_macs + d * v)
    attention = n_latent * (s + 1) / 2 * w["n"] * 2 * (w["nope"] + w["rope"] + w["dvh"])
    rule = n_delta * h * (6 * dk * dv + dk)
    return float(3 * s * (2 * macs + attention + rule))


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"kimi_linear builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of
# byteps_tpu/models/channel_delta_moe_reference.py, blocked)
# ---------------------------------------------------------------------------


def _rms(x, w, eps, st=jnp.float32):
    """RMSNorm with its statistics in ``st``; returns ``st``."""
    x = x.astype(st)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(st)


def _l2(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def _stack(params: dict, name: str) -> dict:
    return {k.split(".", 1)[1]: v for k, v in params.items() if k.startswith(name + ".")}


def plain_loss(cfg: dict, compute=jnp.float32, statistics=jnp.float32):
    """Mean next-token cross-entropy over the program's flat parameter dict,
    in float32 whatever ``compute_dtype`` says: the reference is the
    mathematics, and the program's bf16 is held to it by ``reference_rtol``
    and ``reference_update_rtol``.

    The two dtypes are for the controls that those limits are set from
    (``tools/latent_moe_precision.py --config kimi_linear_48b_ep32``; run.py
    passes neither): ``compute`` is what the matrix products' operands and
    the residual stream are rounded to, ``statistics`` what the norms'
    statistics, the router's scores and weights, the softmax and the rule's
    decays (g, exp g) and state are computed in.  (bfloat16, float32) is the
    precision the configuration states, (bfloat16, bfloat16) the nearest below
    it.  Parameters and the loss stay float32 in all of them."""
    eps, w_ = cfg["rms_norm_eps"], _widths(cfg)
    h, dk, dv, r = w_["h"], w_["dk"], w_["dv"], w_["r"]
    nope, rank, qk_dim = w_["nope"], w_["rank"], w_["nope"] + w_["rope"]
    lo, held, top_k = cfg["held_expert_lo"], cfg["num_experts"], cfg["num_experts_per_token"]
    kinds, n_dense = layer_types(cfg), _n_dense(cfg)
    f32 = jnp.float32

    def rms(x, w):
        return _rms(x, w, eps, statistics).astype(compute)

    def w(lp, *names):
        return (lp[n].astype(compute) for n in names)

    # ---- the delta rule with a decay a key channel, token by token ------------

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs  # (H, d_k), (H, d_v), (H,)
        state = jnp.exp(g_t)[:, :, None] * state  # a decay a row of S
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def run_of_tokens(state, xs):
        return lax.scan(token, state, xs)

    def delta_rule(q, k, v, g, beta):
        """One sequence: q, k, g (S, H, d_k), v (S, H, d_v), beta (S, H), all
        in ``statistics``; the state too.  Returns o (S, H, d_v)."""
        s = q.shape[0]
        run = min(RUN, s)
        xs = tuple(x.reshape((s // run, run) + x.shape[1:]) for x in (q, k, v, g, beta))
        _, o = lax.scan(run_of_tokens, jnp.zeros((v.shape[1], dk, dv), statistics), xs)
        return o.reshape(v.shape)

    def delta_mixer(x, lp):
        """x (1, S, D), one sequence.  A group of heads at a time: heads meet
        only in the output projection's sum, and the temporaries are one
        group's."""
        s, d = x.shape[1], x.shape[2]
        groups = HEAD_GROUPS if h % HEAD_GROUPS == 0 else 1
        m = h // groups
        hn = rms(x, lp["norm"])[0]
        low = hn @ lp["w_fgb"].astype(compute)  # [f↓ | g↓ | β]: 2 r + H columns, every group's

        def by_group(cols, width):  # (..., heads·width) → (G, ..., heads/G·width)
            return jnp.moveaxis(cols.reshape(cols.shape[:-1] + (groups, width)), -2, 0)

        def qkv(cols):  # the columns [q | k | v] of a matrix, each by group
            return tuple(by_group(part, width) for part, width in zip(
                jnp.split(cols, [h * dk, 2 * h * dk], axis=-1), (m * dk, m * dk, m * dv)))

        per_group = (*qkv(lp["w_qkv"]), *qkv(lp["conv"]), by_group(lp["w_f"], m * dk),
                     by_group(lp["w_g"], m * dv), by_group(lp["a_log"], m),
                     by_group(lp["dt_bias"], m * dk), by_group(low[:, 2 * r:], m),
                     lp["w_out"].reshape(groups, m * dv, d))

        def conv_silu(cols, taps):
            mixed = (hn @ cols.astype(compute)).astype(f32)
            kernel = taps.shape[0]
            padded = jnp.pad(mixed, ((kernel - 1, 0), (0, 0)))
            conv = sum(padded[j:j + s] * taps[j] for j in range(kernel))
            return jax.nn.silu(conv).astype(statistics)

        @jax.checkpoint
        def group(hn, low, ws):
            wq, wk, wv, tq, tk, tv, w_f, w_g, a_log, dt_bias, beta_in, w_out = ws
            q = _l2(conv_silu(wq, tq).reshape(s, m, dk)) * dk ** -0.5
            k = _l2(conv_silu(wk, tk).reshape(s, m, dk))
            v = conv_silu(wv, tv).reshape(s, m, dv)
            decay_in = (low[:, :r] @ w_f.astype(compute)).astype(statistics)
            g = -jnp.exp(a_log).astype(statistics)[:, None] * jax.nn.softplus(
                decay_in + dt_bias.astype(statistics)).reshape(s, m, dk)
            beta = jax.nn.sigmoid(beta_in.astype(statistics))
            o = delta_rule(q, k, v, g, beta)
            o = lp["o_norm"].astype(statistics) * o * lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            gate = jax.nn.sigmoid((low[:, r:2 * r] @ w_g.astype(compute)).astype(statistics))
            o = o * gate.reshape(s, m, dv)
            return o.astype(compute).reshape(s, m * dv) @ w_out.astype(compute)

        return jnp.sum(lax.map(lambda ws: group(hn, low, ws), per_group), axis=0)[None]

    # ---- latent attention without positions -------------------------------------

    @jax.checkpoint
    def attend(q, k, v, first):
        """One block of queries, whose first row is ``first``, against keys 0.."""
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=f32) / qk_dim ** 0.5
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

    def latent_mixer(x, lp):
        """A group of heads at a time, each rebuilt in the backward pass:
        heads meet only in the output projection's sum (one sequence's q and k
        of all 32 heads are 384 MB each in f32)."""
        n = lp["wq"].shape[1]
        groups = HEAD_GROUPS if n % HEAD_GROUPS == 0 else 1
        hn = rms(x, lp["attn_norm"])
        kv_a = hn @ lp["wkv_a"].astype(compute)
        c_kv, k_pe = rms(kv_a[..., :rank], lp["kv_norm"]), kv_a[:, None, :, rank:]

        def by_group(weight, axis):  # heads on ``axis`` → (G, ..., heads / G, ...)
            shape = weight.shape[:axis] + (groups, n // groups) + weight.shape[axis + 1:]
            return jnp.moveaxis(weight.reshape(shape), axis, 0)

        @jax.checkpoint
        def group(hn, c_kv, k_pe, ws):
            wq, wkv_b, wo = (weight.astype(compute) for weight in ws)
            q = jnp.einsum("bsd,dhk->bhsk", hn, wq)
            kv = jnp.einsum("bsr,rhk->bhsk", c_kv, wkv_b)
            # one key a token that all heads share, as it comes: no positions
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_pe, kv.shape[:3] + k_pe.shape[-1:])], axis=-1)
            return jnp.einsum("bhsk,hkd->bsd", causal_attention(q, k, kv[..., nope:]), wo)

        per_group = (by_group(lp["wq"], 1), by_group(lp["wkv_b"], 1), by_group(lp["wo"], 0))
        return jnp.sum(lax.map(lambda ws: group(hn, c_kv, k_pe, ws), per_group), axis=0)

    # ---- the MLPs, a block of rows at a time --------------------------------------

    def by_rows(x, rows):
        b, s, d = x.shape
        block = min(ROW_BLOCK, b * s)
        return lax.map(jax.checkpoint(rows), x.reshape(-1, block, d)).reshape(b, s, d)

    def dense_mlp(x, lp):
        weights = tuple(w(lp, "w_gate", "w_up", "w_down"))
        return by_rows(x, lambda xb: _swiglu(rms(xb, lp["norm"]), *weights))

    def expert_mlp(x, lp):
        shared_w = tuple(w(lp, "s_gate", "s_up", "s_down"))
        expert_w = tuple(w(lp, "e_gate", "e_up", "e_down"))

        def rows(xb):
            g_st = _rms(xb, lp["norm"], eps, statistics)
            g = g_st.astype(compute)
            scores = jax.nn.sigmoid(g_st @ lp["router"].astype(statistics))
            _, ids = lax.top_k(scores + lp["router_bias"].astype(statistics), top_k)
            chosen = jnp.zeros_like(scores).at[jnp.arange(xb.shape[0])[:, None], ids].set(1.0)
            weights = cfg["routed_scaling_factor"] * scores * chosen / (
                jnp.sum(scores * chosen, axis=-1, keepdims=True) + 1e-20)

            # the held experts one after another, each over every row, masked by its weight
            def add_expert(y, e):
                w_gate, w_up, w_down, weight = e
                return y + weight[:, None].astype(f32) * _swiglu(g, w_gate, w_up, w_down), None

            y, _ = lax.scan(add_expert, _swiglu(g, *shared_w).astype(f32),
                            (*expert_w, weights[:, lo:lo + held].T))
            return y.astype(compute)

        return by_rows(x, rows)

    def xent(x, scale, head, targets):
        """(sum of cross-entropies over targets >= 0, their count), the
        logits a block of rows at a time; the head is (vocabulary, model)."""
        d = x.shape[-1]
        block = min(ROW_BLOCK, x.size // d)
        rows, tgt = x.reshape(-1, block, d), targets.reshape(-1, block)

        @jax.checkpoint
        def one(xb, tb):
            logits = jnp.dot(rms(xb, scale), head.astype(compute).T, preferred_element_type=f32)
            gold = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * (tb >= 0))

        total = jnp.sum(lax.map(lambda xs: one(*xs), (rows, tgt)))
        return total, jnp.sum(tgt >= 0).astype(f32)

    mixer_of = {"channel_delta": ("delta", delta_mixer), "latent_attention": ("latent", latent_mixer)}

    def layer_of(mixer, mlp):
        """A layer over a batch, one sequence after another, each rebuilt in
        the backward pass: sequences meet only in the loss's mean, and a
        layer's temporaries are one sequence's."""
        @jax.checkpoint
        def one(row, mixer_lp, mlp_lp):
            x = row[None]
            x = x + mixer(x, mixer_lp).astype(compute)
            return (x + mlp(x, mlp_lp))[0]

        return lambda x, mixer_lp, mlp_lp: lax.map(lambda row: one(row, mixer_lp, mlp_lp), x)

    def loss(params, batch):
        tokens, targets = batch
        stacks = {s: _stack(params, s) for s in ("delta", "latent", "dense", "moe")}
        seen = dict.fromkeys(stacks, 0)

        def next_of(stack):
            lp = {k: v[seen[stack]] for k, v in stacks[stack].items()}
            seen[stack] += 1
            return lp

        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(compute)
            for i, kind in enumerate(kinds):
                stack, mixer = mixer_of[kind]
                mlp = ("dense", dense_mlp) if i < n_dense else ("moe", expert_mlp)
                x = layer_of(mixer, mlp[1])(x, next_of(stack), next_of(mlp[0]))
            total, count = xent(x, params["norm_f"], params["head"], targets)
        return total / count

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.channel_delta_moe import ChannelDeltaMoEConfig

    w = _widths(cfg)
    return ChannelDeltaMoEConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"], layer_types=layer_types(cfg),
        n_dense_layers=_n_dense(cfg),
        lin_heads=w["h"], lin_k_dim=w["dk"], lin_v_dim=w["dv"], gate_rank=w["r"],
        conv_kernel=lin_taps(cfg), chunk=cfg["chunk"],
        residual_layers=cfg["published"]["num_hidden_layers"],
        n_heads=w["n"], kv_lora_rank=w["rank"], qk_nope_dim=w["nope"], qk_rope_dim=w["rope"],
        v_head_dim=w["dvh"], rope_theta=None if cfg["mla_use_nope"] else float(cfg["rope_theta"]),
        d_ff=cfg["intermediate_size"], d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        n_experts=cfg["router_width"], experts_held=cfg["num_experts"],
        expert_lo=cfg["held_expert_lo"], top_k=cfg["num_experts_per_token"],
        routed_scale=cfg["routed_scaling_factor"], norm_eps=cfg["rms_norm_eps"],
        max_seq=cfg["max_seq"], compute_dtype=_DTYPES[cfg["compute_dtype"]], remat=cfg["remat"],
    )


def _mesh4(mesh):
    """The program's step wants a (dp, pp, sp, tp) mesh."""
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    return make_training_mesh(
        mesh.size, {"dp": mesh.shape["dp"], "pp": 1, "sp": 1, "tp": 1},
        devices=list(mesh.devices.flat),
    )


def make_state(cfg: dict, key: jax.Array, mesh):
    """Parameters (``channel_delta_moe.init_params``) and one fixed batch of
    uniform token ids over the held rows with next-token targets, made on the
    device from ``key`` in one jitted call."""
    from byteps_tpu.models import channel_delta_moe
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]

    def make(key):
        k_params, k_tokens = jax.random.split(key)
        tokens = jax.random.randint(
            k_tokens, (batch, mcfg.max_seq), 0, mcfg.vocab_size, jnp.int32)
        return (channel_delta_moe.init_params(mcfg, k_params), tokens,
                jnp.roll(tokens, -1, axis=1))

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
        raise ValueError(f"kimi_linear builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
