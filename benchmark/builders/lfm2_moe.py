"""Builder for the ``lfm2_24b_a2b_ep8`` configuration
(benchmark/configs/lfm2_24b_a2b_ep8.json): LFM2-24B-A2B's block at its
published widths — double-gated short-convolution mixers three to one with
grouped-query attention at heads of 64, a leading dense layer, 64-wide
sigmoid routing over small experts — one chip's share of an 8-way
expert-parallel deployment.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over a ``ConvMoEConfig``).

``plain_loss`` is a copy of ``byteps_tpu/models/conv_moe_reference.py``
(float32, ``highest`` matmul precision, the convolution as shifted products,
dense causal attention with repeated key/value heads, a loop over the held
experts with a mask), computed in blocks so that three steps at the timed
size fit beside the state that set-up holds: a remat'ed mixer or MLP at a
time and in it a sequence at a time, attention a block of queries at a time,
the dense MLP, the experts and the logits a block of rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
#: rows of queries, and of an MLP's tokens or of logits, that the reference
#: holds at a time; in how many runs, each with its own range of keys, the
#: queries are taken
Q_BLOCK, ROW_BLOCK, KEY_GROUPS = 256, 2048, 4


def _kinds(cfg: dict) -> list:
    """Layer by layer, (mixer, MLP) of the layers that are run: the entries
    ``[first_layer, first_layer + num_hidden_layers)`` of the published
    ``layer_types``; the first ``num_dense_layers`` of them have a dense MLP."""
    lo = cfg["first_layer"]
    types = cfg["layer_types"][lo:lo + cfg["num_hidden_layers"]]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError(f"layer_types has no {cfg['num_hidden_layers']} entries from {lo}")
    return [(t, "dense" if i < cfg["num_dense_layers"] else "moe") for i, t in enumerate(types)]


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _built(cfg: dict) -> None:
    """The switches of the published config that have one position built."""
    for key, want in (("conv_bias", False), ("use_expert_bias", True), ("norm_topk_prob", True)):
        if cfg[key] is not want:
            raise ValueError(f"lfm2_moe builder has {key} = {want} alone, not {cfg[key]!r}")


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward (3 x forward) of one sequence, recomputation not
    counted, of the mathematics and not of padding.  A token's matrix
    products: the mixers' projections; the dense layers' MLP; in every expert
    layer the router and the slots the held experts expect (top_k x held /
    router width = 0.5 a token); the tied head.  Causal attention: (S + 1) /
    2 keys a query, 2 (d + d) a score, every query head.  The short
    convolution: two gates and ``taps`` multiply-adds a channel."""
    s, d, v = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    kinds = _kinds(cfg)
    conv = sum(m == "conv" for m, _ in kinds)
    full = len(kinds) - conv
    dense = sum(m == "dense" for _, m in kinds)
    held_slots = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    macs = (conv * 4 * d * d + full * (2 * d * h * hd + 2 * d * kv * hd)
            + dense * 3 * d * cfg["intermediate_size"]
            + (len(kinds) - dense) * (d * cfg["router_width"]
                                      + held_slots * 3 * d * cfg["moe_intermediate_size"])
            + d * v)
    attention = full * (s + 1) / 2 * h * 2 * (hd + hd)
    gates_and_taps = conv * d * (2 + 2 * cfg["conv_L_cache"])
    return float(3 * s * (2 * macs + attention + gates_and_taps))


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"lfm2_moe builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of byteps_tpu/models/conv_moe_reference.py, blocked)
# ---------------------------------------------------------------------------


def _rms(x, w, eps, st=jnp.float32):
    """RMSNorm ``w x / rms(x)`` with its statistics in ``st``; returns ``st``."""
    x = x.astype(st)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(st)


def _rope(x, theta):
    """x (..., S, d): x cos + rotate_half(x) sin over the whole head, where
    rotate_half([a | b]) = [-b | a]; f32 inside."""
    s, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], axis=-1) for f in (jnp.cos, jnp.sin))
    x32 = x.astype(jnp.float32)
    half_turned = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], axis=-1)
    return (x32 * cos + half_turned * sin).astype(x.dtype)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def plain_loss(cfg: dict, compute=jnp.float32, statistics=jnp.float32):
    """Mean next-token cross-entropy over the program's flat parameter dict,
    in float32 whatever ``compute_dtype`` says: the reference is the
    mathematics, and the program's bf16 is held to it by ``reference_rtol``
    and ``reference_update_rtol``.

    The two dtypes are for the controls that those limits are set from
    (``tools/latent_moe_precision.py --config lfm2_24b_a2b_ep8``; run.py
    passes neither): ``compute`` is what the matrix products' operands, the
    first gate's product and the residual stream are rounded to,
    ``statistics`` what the norms' statistics, the router's scores and
    weights, the softmax, the convolution's products and the second gate are
    computed in.  (bfloat16, float32) is the precision the configuration
    states, (bfloat16, bfloat16) the nearest below it.  Parameters and the
    loss stay float32 in all of them."""
    _built(cfg)
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    lo, held, top_k = cfg["held_expert_lo"], cfg["num_experts"], cfg["num_experts_per_tok"]
    scale, route_eps, taps_n = cfg["routed_scaling_factor"], cfg["route_eps"], cfg["conv_L_cache"]
    f32 = jnp.float32

    def rms(x, w):
        return _rms(x, w, eps, statistics).astype(compute)

    def w(lp, *names):
        return (lp[n].astype(compute) for n in names)

    # ---- the double-gated short convolution -------------------------------------

    def conv_mixer(x, lp):
        """x (1, S, D), one sequence."""
        w_in, w_out = w(lp, "w_in", "w_out")
        s = x.shape[1]
        b_gate, c_gate, inner = jnp.split(rms(x, lp["norm"]) @ w_in, 3, axis=-1)
        u = (b_gate * inner).astype(statistics)
        taps = lp["taps"].astype(statistics)
        conv = jnp.zeros_like(u)
        for back in range(taps_n):  # the last tap weighs the present token
            earlier = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :s]
            conv = conv + taps[taps_n - 1 - back] * earlier
        return (c_gate.astype(statistics) * conv).astype(compute) @ w_out

    # ---- grouped-query softmax attention ---------------------------------------

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
        g = rms(x, lp["norm"])
        q, k, v = (jnp.einsum("bsd,dhk->bhsk", g, m) for m in (wq, wk, wv))
        q, k = _rope(rms(q, lp["q_norm"]), theta), _rope(rms(k, lp["k_norm"]), theta)
        o = causal_attention(q, jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1))
        return jnp.einsum("bhsk,hkd->bsd", o, wo)

    # ---- the MLPs -----------------------------------------------------------------

    def by_rows(rows_fn):
        """An MLP over (1, S, D), a block of rows at a time, each rebuilt in
        the backward pass: the hidden activations are one block's."""
        def mlp(x, lp):
            b, s, d = x.shape
            block = min(ROW_BLOCK, b * s)
            one = jax.checkpoint(lambda xb: rows_fn(xb, lp))
            return lax.map(one, x.reshape(-1, block, d)).reshape(b, s, d)
        return mlp

    def dense_rows(xb, lp):
        return _swiglu(rms(xb, lp["norm"]), *w(lp, "w_gate", "w_up", "w_down"))

    def expert_rows(xb, lp):
        g_st = _rms(xb, lp["norm"], eps, statistics)
        g = g_st.astype(compute)
        scores = jax.nn.sigmoid(g_st @ lp["router"].astype(statistics))
        _, ids = lax.top_k(scores + lp["router_bias"].astype(statistics), top_k)
        chosen = jnp.zeros_like(scores).at[jnp.arange(xb.shape[0])[:, None], ids].set(1.0)
        weights = scale * scores * chosen / (
            jnp.sum(scores * chosen, axis=-1, keepdims=True) + route_eps)

        # the held experts one after another, each over every row, masked by its weight
        def add_expert(y, e):
            w_gate, w_up, w_down, weight = e
            return y + weight[:, None].astype(f32) * _swiglu(g, w_gate, w_up, w_down), None

        y, _ = lax.scan(add_expert, jnp.zeros(xb.shape, f32),
                        (*w(lp, "e_gate", "e_up", "e_down"), weights[:, lo:lo + held].T))
        return y.astype(compute)

    def a_sequence_at_a_time(part):
        """``x + part(x)`` over a batch, one sequence after another, each
        rebuilt in the backward pass: sequences meet only in the loss's mean,
        and a part's temporaries are one sequence's."""
        one = jax.checkpoint(lambda row, lp: row + part(row[None], lp)[0].astype(compute))
        return lambda x, lp: lax.map(lambda row: one(row, lp), x)

    parts = {"conv": a_sequence_at_a_time(conv_mixer),
             "full_attention": a_sequence_at_a_time(attention_mixer),
             "dense": a_sequence_at_a_time(by_rows(dense_rows)),
             "moe": a_sequence_at_a_time(by_rows(expert_rows))}
    stack_of = {"conv": "conv", "full_attention": "attn", "dense": "dense", "moe": "moe"}

    def xent(x, scale, embed, targets):
        """(sum of cross-entropies over targets >= 0, their count), the
        logits a block of rows at a time; the head is the embedding."""
        d = x.shape[-1]
        block = min(ROW_BLOCK, x.size // d)
        rows, tgt = x.reshape(-1, block, d), targets.reshape(-1, block)

        @jax.checkpoint
        def one(xb, tb):
            logits = jnp.dot(rms(xb, scale), embed.astype(compute).T, preferred_element_type=f32)
            gold = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * (tb >= 0))

        total = jnp.sum(lax.map(lambda xs: one(*xs), (rows, tgt)))
        return total, jnp.sum(tgt >= 0).astype(f32)

    def loss(params, batch):
        tokens, targets = batch
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(compute)
            nth = dict.fromkeys(stack_of.values(), 0)
            for pair in _kinds(cfg):
                for kind in pair:
                    stack = stack_of[kind]
                    lp = {k.split(".", 1)[1]: v[nth[stack]] for k, v in params.items()
                          if k.startswith(stack + ".")}
                    nth[stack] += 1
                    x = parts[kind](x, lp)
            total, count = xent(x, params["norm_f"], params["embed"], targets)
        return total / count

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.conv_moe import ConvMoEConfig

    _built(cfg)
    return ConvMoEConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_types=tuple(t for t, _ in _kinds(cfg)), n_dense_layers=cfg["num_dense_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=_head_dim(cfg), rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        conv_kernel=cfg["conv_L_cache"], d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"], n_experts=cfg["router_width"],
        experts_held=cfg["num_experts"], expert_lo=cfg["held_expert_lo"],
        top_k=cfg["num_experts_per_tok"], routed_scale=float(cfg["routed_scaling_factor"]),
        route_eps=cfg["route_eps"], norm_eps=cfg["norm_eps"], max_seq=cfg["max_seq"],
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
    """Parameters (``conv_moe.init_params``) and one fixed batch of uniform
    token ids over the held rows with next-token targets, made on the device
    from ``key`` in one jitted call."""
    from byteps_tpu.models import conv_moe
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]

    def make(key):
        k_params, k_tokens = jax.random.split(key)
        tokens = jax.random.randint(
            k_tokens, (batch, mcfg.max_seq), 0, mcfg.vocab_size, jnp.int32)
        return conv_moe.init_params(mcfg, k_params), tokens, jnp.roll(tokens, -1, axis=1)

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
        raise ValueError(f"lfm2_moe builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
