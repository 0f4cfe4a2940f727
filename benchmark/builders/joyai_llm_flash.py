"""Builder for the ``joyai_llm_flash_ep32`` configuration
(benchmark/configs/joyai_llm_flash_ep32.json): JoyAI-LLM-Flash's block at its
published widths, one chip's share of a 32-way expert-parallel deployment.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over a ``LatentMoEConfig``).

``plain_loss`` is a copy of ``byteps_tpu/models/latent_moe_reference.py``
(float32, ``highest`` matmul precision, dense causal attention, a loop over
the held experts with a mask), computed in blocks so that three steps at the
timed size fit beside the state that set-up holds: a remat'ed layer at a
time and in it a sequence at a time, attention a block of queries at a time,
the logits a block of rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
#: rows of queries, and of logits, that the reference holds at a time; and in
#: how many runs, each with its own range of keys, the queries are taken
Q_BLOCK, ROW_BLOCK, KEY_GROUPS = 256, 2048, 2


def _layer_counts(cfg: dict) -> tuple:
    """(dense layers, expert layers, MTP modules) that are run."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense, cfg["num_nextn_predict_layers"]


def _attention_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * cfg["qk_head_dim"]
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward (3 x forward) of one sequence, recomputation not
    counted, of the mathematics and not of padding.  A token's matrix
    products: every layer's attention projections; the dense layers' SwiGLU;
    in each layer of the expert kind (MTP's too) the router, the shared
    expert and the slots the held experts expect (top_k x held / router
    width = 0.25 a token); the MTP projection; one head a loss.  Causal
    attention: (S + 1) / 2 keys a query, 2 (d_qk + d_v) a score, every head."""
    s, d, v = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"]
    dense, expert, mtp = _layer_counts(cfg)
    swiglu = 3 * d * cfg["moe_intermediate_size"]
    held_slots = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_width"]
    macs = ((dense + expert + mtp) * _attention_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + (expert + mtp) * (d * cfg["router_width"]
                                + swiglu * (cfg["n_shared_experts"] + held_slots))
            + mtp * 2 * d * d + (1 + mtp) * d * v)
    attention = ((dense + expert + mtp) * (s + 1) / 2 * cfg["num_attention_heads"]
                 * 2 * (cfg["qk_head_dim"] + cfg["v_head_dim"]))
    return float(3 * s * (2 * macs + attention))


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"joyai_llm_flash builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of byteps_tpu/models/latent_moe_reference.py, blocked)
# ---------------------------------------------------------------------------


def _rms(x, scale, eps, st=jnp.float32):
    """RMSNorm with its statistics in ``st``; returns ``st``."""
    x = x.astype(st)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(st)


def _rope(x, theta):
    """x (..., S, d): adjacent pairs (2i, 2i+1) rotated by pos * theta^(-2i/d)."""
    s, d = x.shape[-2], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def _stack(params: dict, name: str) -> dict:
    return {k.split(".", 1)[1]: v for k, v in params.items() if k.startswith(name + ".")}


def plain_loss(cfg: dict, compute=jnp.float32, statistics=jnp.float32):
    """``L_main + mtp_lambda L_mtp`` over the program's flat parameter dict,
    in float32 whatever ``compute_dtype`` says: the reference is the
    mathematics, and the program's bf16 is held to it by ``reference_rtol``
    and ``reference_update_rtol``.

    The two dtypes are for the controls that those limits are set from
    (``tools/latent_moe_precision.py``; run.py passes neither): ``compute``
    is what the matrix products' operands and the residual stream are
    rounded to, ``statistics`` what the norms' statistics, the router's
    scores and weights and the softmax are computed in.  (bfloat16, float32)
    is the precision the configuration states, (bfloat16, bfloat16) the
    nearest below it.  Parameters and the loss stay float32 in all of them."""
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    nope, r = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    lo, held, top_k = cfg["held_expert_lo"], cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    f32 = jnp.float32

    def rms(x, scale):
        return _rms(x, scale, eps, statistics).astype(compute)

    def w(lp, *names):
        return (lp[n].astype(compute) for n in names)

    @jax.checkpoint
    def attend(q, k, v, first):
        """One block of queries, whose first row is ``first``, against keys 0.."""
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=f32
                            ) / cfg["qk_head_dim"] ** 0.5
        visible = jnp.arange(k.shape[2])[None, :] <= (first + jnp.arange(q.shape[2]))[:, None]
        p = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf).astype(statistics), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(compute), v)

    def causal_attention(q, k, v):
        """Dense causal attention, never more than Q_BLOCK rows of scores at
        a time: the queries in KEY_GROUPS runs, each against the keys up to
        its end (so the masked half is mostly not computed), a run's blocks
        one after another (``lax.map``)."""
        b, h, s, _ = q.shape
        run = max(s // KEY_GROUPS, 1)
        block = min(Q_BLOCK, run)
        out = []
        for a in range(0, s, run):
            blocks = q[:, :, a:a + run].reshape(b, h, run // block, block, -1)
            keys, values = k[:, :, :a + run], v[:, :, :a + run]
            o = lax.map(lambda xs: attend(xs[0], keys, values, xs[1]),
                        (jnp.moveaxis(blocks, 2, 0), a + block * jnp.arange(run // block)))
            out.append(jnp.moveaxis(o, 0, 2).reshape(b, h, run, -1))
        return jnp.concatenate(out, axis=2)

    def attention(x, lp):
        wq_a, wq_b, wkv_a, wkv_b, wo = w(lp, "wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
        h = rms(x, lp["attn_norm"])
        q = jnp.einsum("bsr,rhk->bhsk", rms(h @ wq_a, lp["q_norm"]), wq_b)
        kv_a = h @ wkv_a
        kv = jnp.einsum("bsr,rhk->bhsk", rms(kv_a[..., :r], lp["kv_norm"]), wkv_b)
        k_rope = _rope(kv_a[:, None, :, r:], theta)  # one a token, shared by all heads
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope, kv.shape[:3] + k_rope.shape[-1:])], axis=-1)
        o = causal_attention(q, k, kv[..., nope:])
        return x + jnp.einsum("bhsk,hkd->bsd", o, wo)

    def dense_layer(x, lp):
        x = attention(x, lp)
        return x + _swiglu(rms(x, lp["mlp_norm"]), *w(lp, "w_gate", "w_up", "w_down"))

    def expert_layer(x, lp):
        x = attention(x, lp)
        b, s, d = x.shape
        g_st = _rms(x, lp["mlp_norm"], eps, statistics).reshape(b * s, d)
        g = g_st.astype(compute)
        scores = jax.nn.sigmoid(g_st @ lp["router"].astype(statistics))
        _, ids = lax.top_k(scores + lp["router_bias"].astype(statistics), top_k)
        chosen = jnp.zeros_like(scores).at[jnp.arange(b * s)[:, None], ids].set(1.0)
        weights = cfg["routed_scaling_factor"] * scores * chosen / (
            jnp.sum(scores * chosen, axis=-1, keepdims=True) + 1e-20)
        # the held experts one after another, each over every token, masked by its weight
        def add_expert(y, e):
            w_gate, w_up, w_down, weight = e
            return y + weight[:, None].astype(f32) * _swiglu(g, w_gate, w_up, w_down), None

        y, _ = lax.scan(add_expert,
                        _swiglu(g, *w(lp, "s_gate", "s_up", "s_down")).astype(f32),
                        (*w(lp, "e_gate", "e_up", "e_down"), weights[:, lo:lo + held].T))
        return x + y.reshape(b, s, d).astype(compute)

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
        in the backward pass: sequences meet only in the loss's means, and a
        layer's temporaries are one sequence's."""
        one = jax.checkpoint(lambda row, lp: layer(row[None], lp)[0])

        def batched(x, lp):
            return lax.map(lambda row: one(row, lp), x), None
        return batched

    def loss(params, batch):
        tokens, targets = batch
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(compute)
            for name, layer in (("dense", dense_layer), ("moe", expert_layer)):
                stack = _stack(params, name)
                if stack:
                    x, _ = lax.scan(a_sequence_at_a_time(layer), x, stack)
            total, count = xent(x, params["norm_f"], params["head"], targets)
            total = total / count
            if cfg["num_nextn_predict_layers"]:
                nxt = rms(params["embed"][targets], params["mtp_norm_e"])
                both = jnp.concatenate([nxt, rms(x, params["mtp_norm_h"])], axis=-1)
                y, _ = lax.scan(a_sequence_at_a_time(expert_layer),
                                both @ params["mtp_proj"].astype(compute), _stack(params, "mtp"))
                after = jnp.concatenate(
                    [targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1)
                mtp_total, mtp_count = xent(y, params["mtp_norm_f"], params["head"], after)
                total = total + cfg["mtp_lambda"] * mtp_total / mtp_count
        return total

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.latent_moe import LatentMoEConfig

    dense, expert, mtp = _layer_counts(cfg)
    return LatentMoEConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], d_expert=cfg["moe_intermediate_size"],
        n_dense_layers=dense, n_expert_layers=expert, n_experts=cfg["router_width"],
        experts_held=cfg["n_routed_experts"], expert_lo=cfg["held_expert_lo"],
        top_k=cfg["num_experts_per_tok"], routed_scale=cfg["routed_scaling_factor"],
        mtp_modules=mtp, mtp_lambda=cfg["mtp_lambda"], rope_theta=float(cfg["rope_theta"]),
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
    """Parameters (``latent_moe.init_params``: N(0, 1/fan_in), 0.02 for the
    embedding, ones for the norms, zero selection bias) and one fixed batch
    of uniform token ids over the held rows with next-token targets, made on
    the device from ``key`` in one jitted call."""
    from byteps_tpu.models import latent_moe
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]

    def make(key):
        k_params, k_tokens = jax.random.split(key)
        tokens = jax.random.randint(
            k_tokens, (batch, mcfg.max_seq), 0, mcfg.vocab_size, jnp.int32)
        return latent_moe.init_params(mcfg, k_params), tokens, jnp.roll(tokens, -1, axis=1)

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
        raise ValueError(f"joyai_llm_flash builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
