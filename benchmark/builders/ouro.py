"""Builder for the ``ouro_2_6b_pp8`` configuration
(benchmark/configs/ouro_2_6b_pp8.json): Ouro-2.6B's block (``model_type:
ouro``, the looped language model of arXiv 2510.25741) at its published
widths — a stack of sandwich-normed dense layers (16 | 16 heads of 128 with
rope over the whole head, a SwiGLU MLP 5632 wide) run ``total_ut_steps`` = 4
times on the same weights, a head and a learned exit gate every loop step,
each token's four cross-entropies weighed by its exit distribution — one
chip of a pipeline of 8 stages of 6 layers.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over a ``LoopedDenseConfig``).

``plain_loss`` is a copy of ``byteps_tpu/models/looped_dense_reference.py``
(float32, ``highest`` matmul precision, the loop steps over the same parameter
dict, dense causal attention over scores, the exit distribution as its
products), computed in blocks so that three steps at the timed size fit beside
the state that set-up holds: the loop steps a ``lax.scan`` (the plain
reference's Python loop, unrolled, holds a copy of the layers' gradient a loop
step), a loop step rebuilt at a time and in it a layer's part at a time, a
sequence at a time; attention a
block of queries at a time against the keys up to its run's end, the MLP and
the logits a block of rows at a time — no loop step's logits stand whole.
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
#: holds at a time; in how many runs, each with its own range of keys, a
#: layer's queries are taken
Q_BLOCK, ROW_BLOCK, KEY_GROUPS = 256, 2048, 4


def _built(cfg: dict) -> None:
    """The switches of the published config that have one position built.
    (``early_exit_threshold`` is not among them: it stops a sequence at
    inference and is read by no training step; ``max_window_layers`` counts
    layers for a sliding window that ``use_sliding_window`` turns off.)"""
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("rope_scaling", None), ("use_sliding_window", False),
                      ("sliding_window", None)):
        if cfg[key] != want or type(cfg[key]) is not type(want):
            raise ValueError(f"ouro builder has {key} = {want!r} alone, not {cfg[key]!r}")
    if set(cfg["layer_types"]) != {"full_attention"}:
        raise ValueError("ouro builder has full_attention layers alone")


def parameters(cfg: dict) -> int:
    """Parameters held: the layers (q, k, v, o; gate, up, down; four norms),
    embedding and untied head, the final norm, the exit gate's vector and
    bias."""
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f + 4 * d
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d + d + 1


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward (3 x forward) of one sequence through ALL
    ``total_ut_steps`` passes of the stack, recomputation not counted (so the
    backward flash kernel's fifth product, the scores again, is not), of the
    mathematics and not of what a block computes above the diagonal.  A
    token's matrix products a pass: every layer's q, k, v, o and SwiGLU, then
    the pass's head over the whole vocabulary and its exit gate.  Attention:
    ``S (S + 1) / 2`` score entries a head, 2 (d + d) a score."""
    s, d, v = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    layers, loops = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    macs = layers * (2 * d * h * hd + 2 * d * kv * hd + 3 * d * cfg["intermediate_size"]) + (
        d * v + d)
    entries = layers * (s * (s + 1) // 2)
    return float(3 * loops * (s * 2 * macs + entries * h * 2 * (hd + hd)))


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"ouro builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of byteps_tpu/models/looped_dense_reference.py, blocked)
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


def exit_distribution(gates):
    """λ (L, ...) → p (L, ...): ``pᵗ = λᵗ ∏_{j<t}(1 − λʲ)``, the last loop
    step taking what is left."""
    p, left = [], jnp.ones_like(gates[0])
    for lam in gates[:-1]:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])


def plain_loss(cfg: dict, compute=jnp.float32, statistics=jnp.float32, stream=None):
    """Mean over the counted tokens of ``Σₜ pᵗ CEᵗ − β H(p)`` over the
    program's flat parameter dict, in float32 whatever ``compute_dtype`` says:
    the reference is the mathematics, and the program's bf16 is held to it by
    ``reference_rtol`` and ``reference_update_rtol``.

    The dtypes are for the controls that those limits are set from
    (``tools/latent_moe_precision.py --config ouro_2_6b_pp8``; run.py passes
    none): ``compute`` is what the matrix products' operands are rounded to,
    ``statistics`` what the norms' statistics, the softmax, the exit gate, its
    distribution and its entropy are computed in, ``stream`` what the residual
    stream — the embedding's rows, every branch's normed output, the loop
    steps' outputs — is held in (``statistics``' dtype where it is not given:
    what the configuration keeps in float32 beside parameters and loss goes
    down together).  (bfloat16, float32) is the precision the configuration
    states, (bfloat16, bfloat16) the nearest below it; (bfloat16, float32,
    bfloat16) and (bfloat16, bfloat16, float32) are the two steps between
    them.  Parameters, the rows' cross-entropies and the loss stay float32 in
    all of them."""
    _built(cfg)
    stream = stream or statistics
    eps, theta, beta = cfg["rms_norm_eps"], float(cfg["rope_theta"]), cfg["exit_beta"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    layers, loops = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    f32 = jnp.float32

    def rms(x, w, to=compute):
        return _rms(x, w, eps, statistics).astype(to)

    def w(lp, *names):
        return (lp[n].astype(compute) for n in names)

    @jax.checkpoint
    def attend(q, k, v, q_pos, k_pos):
        """One block of queries at positions ``q_pos`` against the keys at
        ``k_pos``, every head."""
        scores = jnp.einsum("hqd,hkd->hqk", q, k, preferred_element_type=f32) / hd ** 0.5
        seen = k_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf).astype(statistics), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p.astype(compute), v)

    def causal_attention(q, k, v):
        """Dense causal attention over (heads, S, d), never more than Q_BLOCK
        rows of scores a head at a time: the queries in KEY_GROUPS runs, each
        against the keys up to its end (so the masked half is mostly not
        computed), a run's blocks one after another (``lax.map``)."""
        nh, s, _ = q.shape
        run = max(s // KEY_GROUPS, 1)
        block = min(Q_BLOCK, run)
        out = []
        for a in range(0, s, run):
            blocks = q[:, a:a + run].reshape(nh, run // block, block, -1)
            keys, values, k_pos = k[:, :a + run], v[:, :a + run], jnp.arange(a + run)
            o = lax.map(lambda xs: attend(xs[0], keys, values, xs[1] + jnp.arange(block), k_pos),
                        (jnp.moveaxis(blocks, 1, 0), a + block * jnp.arange(run // block)))
            out.append(jnp.moveaxis(o, 0, 1).reshape(nh, run, -1))
        return jnp.concatenate(out, axis=1)

    @jax.checkpoint
    def attention_part(x, lp):
        """One sequence x (S, D): ``x + norm(attention(norm(x)))``."""
        wq, wk, wv, wo = w(lp, "wq", "wk", "wv", "wo")
        g = rms(x, lp["norm"])
        q, k, v = (jnp.einsum("sd,dhk->hsk", g, m) for m in (wq, wk, wv))
        q, k = _rope(q, theta), _rope(k, theta)
        k, v = jnp.repeat(k, h // kv, axis=0), jnp.repeat(v, h // kv, axis=0)
        y = jnp.einsum("hsk,hkd->sd", causal_attention(q, k, v), wo, preferred_element_type=f32)
        return x + rms(y, lp["post_norm"], stream)

    def mlp_part(x, lp):
        """One sequence x (S, D): ``x + norm(swiglu(norm(x)))``, a block of
        rows at a time, each rebuilt in the backward pass."""
        @jax.checkpoint
        def rows(xb):
            w_gate, w_up, w_down = w(lp, "w_gate", "w_up", "w_down")
            g = rms(xb, lp["mlp_norm"])
            y = (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down
            return xb + rms(y, lp["mlp_post_norm"], stream)

        block = min(ROW_BLOCK, x.shape[0])
        return lax.map(rows, x.reshape(-1, block, x.shape[-1])).reshape(x.shape)

    @jax.checkpoint
    def stack(x, stacked):
        """The layers once over one sequence; rebuilt a loop step at a time
        in the backward pass, and in it a part at a time."""
        return lax.scan(lambda x, lp: (mlp_part(attention_part(x, lp), lp), None), x, stacked)[0]

    def cross_entropies(hs, head, targets):
        """hs (L, S, D) normed, targets (S,) → every loop step's row
        cross-entropies (L, S) f32, the logits a block of rows at a time; the
        head is untied, (V, D)."""
        d = hs.shape[-1]
        block = min(ROW_BLOCK, hs.shape[1])
        tgt = jnp.broadcast_to(targets, hs.shape[:2]).reshape(-1, block)

        @jax.checkpoint
        def one(xb, tb):
            logits = jnp.dot(xb.astype(compute), head.astype(compute).T,
                             preferred_element_type=f32)
            gold = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
            return jax.nn.logsumexp(logits, axis=-1) - gold

        return lax.map(lambda xs: one(*xs), (hs.reshape(-1, block, d), tgt)).reshape(hs.shape[:2])

    def sequence_sums(params, stacked, tokens, targets):
        """One sequence: (Σ over its counted tokens of Σₜ pᵗ CEᵗ − β H(p),
        the tokens counted)."""
        def loop_step(x, _):  # the same layers every time
            x = rms(stack(x, stacked), params["norm_f"], stream)
            return x, x

        # a scan, not the plain reference's Python loop: unrolled, XLA holds
        # one copy of the layers' gradient a loop step (4.2 GiB at this size)
        _, hs = lax.scan(loop_step, params["embed"][tokens].astype(stream), None, length=loops)
        each = cross_entropies(hs, params["head"], targets)
        gates = jax.nn.sigmoid(hs.astype(statistics) @ params["gate_w"].astype(statistics)
                               + params["gate_b"].astype(statistics))
        p = exit_distribution(gates)
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        valid = targets >= 0
        token = jnp.sum(p.astype(f32) * each, axis=0) - beta * entropy.astype(f32)
        return jnp.sum(token * valid), jnp.sum(valid).astype(f32)

    def loss(params, batch):
        tokens, targets = batch
        with jax.default_matmul_precision("highest"):
            stacked = {k.split(".", 1)[1]: v[:layers] for k, v in params.items()
                       if k.startswith("layer.")}
            totals, counts = lax.map(
                lambda row: sequence_sums(params, stacked, *row), (tokens, targets))
        return jnp.sum(totals) / jnp.sum(counts)

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.looped_dense import LoopedDenseConfig

    _built(cfg)
    return LoopedDenseConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_loops=cfg["total_ut_steps"],
        exit_beta=cfg["exit_beta"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
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
    """Parameters (``looped_dense.init_params``) and one fixed batch of
    uniform token ids over the vocabulary with next-token targets, made on
    the device from ``key`` in one jitted call."""
    from byteps_tpu.models import looped_dense
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]

    def make(key):
        k_params, k_tokens = jax.random.split(key)
        tokens = jax.random.randint(
            k_tokens, (batch, mcfg.max_seq), 0, mcfg.vocab_size, jnp.int32)
        return looped_dense.init_params(mcfg, k_params), tokens, jnp.roll(tokens, -1, axis=1)

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
        raise ValueError(f"ouro builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
