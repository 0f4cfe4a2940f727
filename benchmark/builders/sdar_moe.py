"""Builder for the ``sdar_30b_a3b_ep8`` configuration
(benchmark/configs/sdar_30b_a3b_ep8.json): SDAR-30B-A3B's block (``model_type:
sdar_moe``: the Qwen3-MoE layer — pre-norm, 32 | 4 heads of 128 with an RMSNorm
over each head of q and of k before rope, softmax top-8 of 128 renormalised
over 768-wide SwiGLU experts, no shared expert) under BLOCK-DIFFUSION training
(arXiv 2510.06303, whose pass is arXiv 2503.09573's): a noised copy and the
clean copy of every sequence in one pass of 2L rows under a mask that is
neither causal nor banded, a loss over the masked tokens weighed by their
noise — one chip's share of an 8-way expert-parallel deployment.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over a ``BlockDiffusionMoEConfig``).
The batch has THREE leaves, ``(x_t, x_0, weights)``: the noising
(``byteps_tpu/data.block_diffusion_noise``, the input pipeline's) runs once in
``make_state``, one fixed noised batch from ``--seed``.

``plain_loss`` is a copy of ``byteps_tpu/models/block_diffusion_moe_reference.py``
(float32, ``highest`` matmul precision, dense attention over the 2L rows with
the three visibility clauses written as comparisons of positions, repeated
key/value heads, a loop over the held experts with a mask), computed in blocks
so that three steps at the timed size fit beside the state that set-up holds:
a remat'ed part at a time and in it a sequence at a time, attention one
key/value head with its group at a time and in it a block of queries against
only the keys their clauses can see (a noisy block: its own rows of the noisy
half and the clean rows up to its run's end; a clean block: the clean rows up
to its run's end), the experts and the logits a block of rows at a time.  The
layers are ONE ``lax.scan`` over their stacks, every layer over both copies:
unlike the program it computes the last layer's clean half, which the loss
reads nothing of (plain before frugal, and a program a sixth as long to
compile).
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
#: holds at a time; in how many runs, each with its own range of clean keys, a
#: half's queries are taken
Q_BLOCK, ROW_BLOCK, KEY_GROUPS = 256, 2048, 4


def _built(cfg: dict) -> None:
    """The switches of the published config that have one position built."""
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("rope_scaling", None), ("use_sliding_window", False),
                      ("sliding_window", None), ("norm_topk_prob", True),
                      ("attention_bias", False), ("decoder_sparse_step", 1),
                      ("mlp_only_layers", [])):
        if cfg[key] != want or type(cfg[key]) is not type(want):
            raise ValueError(f"sdar_moe builder has {key} = {want!r} alone, not {cfg[key]!r}")
    if cfg["max_seq"] % cfg["block_length"]:
        raise ValueError("block_length tiles max_seq")


def parameters(cfg: dict) -> int:
    """Parameters held: the layers (q, k, v, o, two head norms, two norms, the
    router, the held experts), embedding and untied head, the final norm."""
    d, hd, fe = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = (2 * d * h * hd + 2 * d * kv * hd + 2 * hd + 2 * d + d * cfg["router_width"]
             + cfg["num_experts"] * 3 * d * fe)
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d


def visible_entries(length: int, block: int, queries: str = "both") -> int:
    """Score entries a head that the mask keeps over the 2L rows: a noisy query
    its block (``block``) and the clean tokens before it; a clean query the
    clean tokens up to its block's end.  ``L² + L·block`` for both halves'
    queries, ``(L² + L·block) / 2`` for either half's alone."""
    both = length * length + length * block
    return both if queries == "both" else both // 2


def mean_noise(cfg: dict) -> float:
    """E[t] of the noise law ``U[lo, hi]``: the expected share of masked rows."""
    return (cfg["noise_lo"] + cfg["noise_hi"]) / 2


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward (3 x forward) of one sequence of L tokens, which
    enters the layers as 2L rows; recomputation not counted, of the
    mathematics and not of padding or of what a tile computes outside the
    mask, and ONLY of what the loss depends on, so that MFU can only be
    understated: the last layer's clean half gives keys and values alone (no
    q, no attention output, no W_o, no router, no experts there), and the head
    is counted over the ``L · E[t]`` rows that have a non-zero weight in
    expectation (the program computes all L rows' logits: the rest is work
    without a gradient's worth).  A row's matrix products a layer: q, o, k, v,
    the router, and the slots the held experts expect (top_k x held / router
    width = 1 a row).  Attention: the entries the mask keeps
    (:func:`visible_entries`), 2 (d + d) a score, every query head."""
    s, d, v = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    layers, blk = cfg["num_hidden_layers"], cfg["block_length"]
    held_slots = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    queries = 2 * d * h * hd  # q and o
    keys = 2 * d * kv * hd  # k and v
    mlp = d * cfg["router_width"] + held_slots * 3 * d * cfg["moe_intermediate_size"]
    macs = ((layers - 1) * 2 * s * (queries + keys + mlp)  # both halves
            + s * (queries + mlp) + 2 * s * keys  # the last layer
            + s * mean_noise(cfg) * d * v)
    entries = (layers - 1) * visible_entries(s, blk) + visible_entries(s, blk, "noisy")
    return float(3 * (2 * macs + entries * h * 2 * (hd + hd)))


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"sdar_moe builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of models/block_diffusion_moe_reference.py, blocked)
# ---------------------------------------------------------------------------


def _rms(x, w, eps, st=jnp.float32):
    """RMSNorm ``w x / rms(x)`` with its statistics in ``st``; returns ``st``."""
    x = x.astype(st)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(st)


def _rope(x, theta, positions):
    """x (..., R, d), row r at ``positions[r]``: x cos + rotate_half(x) sin over
    the whole head, where rotate_half([a | b]) = [-b | a]; f32 inside."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], axis=-1) for f in (jnp.cos, jnp.sin))
    x32 = x.astype(jnp.float32)
    half_turned = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], axis=-1)
    return (x32 * cos + half_turned * sin).astype(x.dtype)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def plain_loss(cfg: dict, compute=jnp.float32, statistics=jnp.float32):
    """``(1 / (batch · L)) Σ w · CE`` over the noisy half's rows, over the
    program's flat parameter dict, in float32 whatever ``compute_dtype`` says:
    the reference is the mathematics, and the program's bf16 is held to it by
    ``reference_rtol`` and ``reference_update_rtol``.

    The two dtypes are for the controls that those limits are set from
    (``tools/latent_moe_precision.py --config sdar_30b_a3b_ep8``; run.py passes
    neither): ``compute`` is what the matrix products' operands and the
    residual stream are rounded to, ``statistics`` what the norms' statistics,
    the router's scores and weights and the softmax are computed in.
    (bfloat16, float32) is the precision the configuration states, (bfloat16,
    bfloat16) the nearest below it.  Parameters, the loss weights, the rows'
    cross-entropies and the loss stay float32 in all of them."""
    _built(cfg)
    eps, theta, blk = cfg["rms_norm_eps"], float(cfg["rope_theta"]), cfg["block_length"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lo, held, top_k = cfg["held_expert_lo"], cfg["num_experts"], cfg["num_experts_per_tok"]
    layers = cfg["num_hidden_layers"]
    f32 = jnp.float32

    def rms(x, w):
        return _rms(x, w, eps, statistics).astype(compute)

    def w(lp, *names):
        return (lp[n].astype(compute) for n in names)

    # ---- attention over [x_t | x_0] under the three clauses -------------------------

    @jax.checkpoint
    def attend(q, k, v, q_pos, q_noisy, k_pos, k_noisy):
        """One block of queries of ONE half (``q_noisy``, a traced bool)
        against keys of either (``k_noisy`` (K,)); positions within the half."""
        scores = jnp.einsum("hqd,hkd->hqk", q, k, preferred_element_type=f32) / hd ** 0.5
        bq, bk = (q_pos // blk)[:, None], (k_pos // blk)[None, :]
        seen = jnp.where(k_noisy[None, :], q_noisy & (bq == bk),
                         jnp.where(q_noisy, bq > bk, bq >= bk))
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf).astype(statistics), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p.astype(compute), v)

    def masked_attention(q, k, v, length):
        """q, k, v (heads, 2L, d), rows ``[x_t | x_0]`` → (heads, 2L, d).
        Never more than Q_BLOCK rows of scores a head
        at a time: each half's queries in KEY_GROUPS runs, a run ``[a, a +
        run)`` against the clean keys up to its end and — the noisy half's —
        its own rows of the noisy keys; a run's blocks one after another."""
        nh = q.shape[0]
        run = max(length // KEY_GROUPS, 1)
        block = min(Q_BLOCK, run)
        out = []
        for noisy in (True, False):
            base = 0 if noisy else length
            for a in range(0, length, run):
                blocks = q[:, base + a:base + a + run].reshape(nh, run // block, block, -1)
                own = (a, a + run) if noisy else (a, a)  # its rows of the noisy half
                keys, values = (jnp.concatenate([t[:, own[0]:own[1]],
                                                 t[:, length:length + a + run]], axis=1)
                                for t in (k, v))
                k_pos = jnp.concatenate([jnp.arange(*own), jnp.arange(a + run)])
                k_noisy = jnp.arange(k_pos.shape[0]) < own[1] - own[0]
                o = lax.map(lambda xs: attend(xs[0], keys, values, xs[1] + jnp.arange(block),
                                              jnp.asarray(noisy), k_pos, k_noisy),
                            (jnp.moveaxis(blocks, 1, 0), a + block * jnp.arange(run // block)))
                out.append(jnp.moveaxis(o, 0, 1).reshape(nh, run, -1))
        return jnp.concatenate(out, axis=1)

    def attention_part(x, lp):
        """One sequence's rows x (2L, D) → ``x + attention(norm(x))``; one
        key/value head with its group of query heads at a time, each rebuilt
        in the backward pass and their outputs added in f32."""
        rows, d = x.shape
        length, group = rows // 2, h // kv
        positions = jnp.concatenate([jnp.arange(length)] * 2)
        wq, wk, wv, wo = w(lp, "wq", "wk", "wv", "wo")
        g = rms(x, lp["norm"])
        per_kv = (jnp.moveaxis(wq.reshape(d, kv, group, hd), 1, 0),
                  jnp.moveaxis(wk, 1, 0)[:, :, None], jnp.moveaxis(wv, 1, 0)[:, :, None],
                  wo.reshape(kv, group, hd, d))

        @jax.checkpoint
        def one(y, ws):
            q, k, v = (jnp.einsum("sd,dhk->hsk", g, m) for m in ws[:3])
            q = _rope(rms(q, lp["q_norm"]), theta, positions)
            k = _rope(rms(k, lp["k_norm"]), theta, positions)
            k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
            o = masked_attention(q, k, v, length)
            return y + jnp.einsum("hsk,hkd->sd", o, ws[3], preferred_element_type=f32), None

        y, _ = lax.scan(one, jnp.zeros(x.shape, f32), per_kv)
        return x + y.astype(compute)

    # ---- the routed MLP -------------------------------------------------------------

    def expert_rows(xb, lp):
        g_st = _rms(xb, lp["norm"], eps, statistics)
        g = g_st.astype(compute)
        probs = jax.nn.softmax(g_st @ lp["router"].astype(statistics), axis=-1)
        _, ids = lax.top_k(probs, top_k)
        chosen = jnp.zeros_like(probs).at[jnp.arange(xb.shape[0])[:, None], ids].set(1.0)
        weights = probs * chosen / jnp.sum(probs * chosen, axis=-1, keepdims=True)

        # the held experts one after another, each over every row, masked by its weight
        def add_expert(y, e):
            w_gate, w_up, w_down, weight = e
            return y + weight[:, None].astype(f32) * _swiglu(g, w_gate, w_up, w_down), None

        y, _ = lax.scan(add_expert, jnp.zeros(xb.shape, f32),
                        (*w(lp, "e_gate", "e_up", "e_down"), weights[:, lo:lo + held].T))
        return xb + y.astype(compute)

    def moe_part(x, lp):
        """One sequence's rows (R, D): ``x + moe(norm(x))``, a block of rows at
        a time, each rebuilt in the backward pass."""
        block = min(ROW_BLOCK, x.shape[0])
        one = jax.checkpoint(lambda xb: expert_rows(xb, lp))
        return lax.map(one, x.reshape(-1, block, x.shape[-1])).reshape(x.shape)

    def a_sequence_at_a_time(part):
        """``part`` over a batch, one sequence after another, each rebuilt in
        the backward pass: sequences meet only in the loss's mean."""
        one = jax.checkpoint(part)
        return lambda x, lp: lax.map(lambda rows: one(rows, lp), x)

    attention, moe = a_sequence_at_a_time(attention_part), a_sequence_at_a_time(moe_part)

    @jax.checkpoint
    def layer(x, lps):
        """A layer over the batch; rebuilt a layer at a time in the backward
        pass, and in it a part and a sequence at a time."""
        return moe(attention(x, lps["attn"]), lps["moe"]), None

    def weighted_xent(x, scale_f, head, targets, weights):
        """Σ w · CE over the rows, the logits a block of rows at a time; the
        head is untied, (V, D)."""
        d = x.shape[-1]
        block = min(ROW_BLOCK, x.size // d)

        @jax.checkpoint
        def one(xb, tb, wb):
            logits = jnp.dot(rms(xb, scale_f), head.astype(compute).T, preferred_element_type=f32)
            gold = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * wb)

        return jnp.sum(lax.map(lambda xs: one(*xs), (
            x.reshape(-1, block, d), targets.reshape(-1, block), weights.reshape(-1, block))))

    def loss(params, batch):
        noisy, clean, weights = batch
        with jax.default_matmul_precision("highest"):
            x = params["embed"][jnp.concatenate([noisy, clean], axis=1)].astype(compute)
            # a scan over the stacked layers, every one over both copies: the
            # last layer's clean half is computed and read by nothing (plain
            # before frugal; unrolled, the program's twelve parts compile for
            # minutes and a cut of the stacks copies their gradient)
            stacked = {stack: {k.split(".", 1)[1]: v[:layers] for k, v in params.items()
                               if k.startswith(stack + ".")} for stack in ("attn", "moe")}
            x, _ = lax.scan(layer, x, stacked)
            total = weighted_xent(x[:, :noisy.shape[1]], params["norm_f"], params["head"], clean,
                                  weights.astype(f32))
        return total / weights.size

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.block_diffusion_moe import BlockDiffusionMoEConfig

    _built(cfg)
    return BlockDiffusionMoEConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], block_length=cfg["block_length"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        d_expert=cfg["moe_intermediate_size"], n_experts=cfg["router_width"],
        experts_held=cfg["num_experts"], expert_lo=cfg["held_expert_lo"],
        top_k=cfg["num_experts_per_tok"], norm_eps=cfg["rms_norm_eps"], max_seq=cfg["max_seq"],
        compute_dtype=_DTYPES[cfg["compute_dtype"]], remat=cfg["remat"],
    )


def _mesh4(mesh):
    """The program's step wants a (dp, pp, sp, tp) mesh."""
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    return make_training_mesh(
        mesh.size, {"dp": mesh.shape["dp"], "pp": 1, "sp": 1, "tp": 1},
        devices=list(mesh.devices.flat),
    )


def plain_noise(key: jax.Array, clean: jax.Array, block: int, mask_id: int, lo: float, hi: float):
    """The noising written a second time, plainly, for :func:`make_state` to
    hold the input pipeline's to: block ``b`` of a sequence draws ONE level
    ``t_b ~ U[lo, hi]``, token ``i`` a coin, and where the coin falls under its
    block's level the token becomes the mask token and weighs ``1 / t_b``;
    elsewhere it stays and weighs 0.  The two uniform draws are the pipeline's
    (levels from the first half of ``key``, coins from the second), so the same
    key gives the same batch if, and only if, the law is the same."""
    k_level, k_coin = jax.random.split(key)
    rows, length = clean.shape
    level = jax.random.uniform(k_level, (rows, length // block), jnp.float32, lo, hi)
    coin = jax.random.uniform(k_coin, (rows, length), jnp.float32)
    t = level[:, jnp.arange(length) // block]  # token i's level is its block's
    return jnp.where(coin < t, mask_id, clean), jnp.where(coin < t, 1.0 / t, 0.0)


def make_state(cfg: dict, key: jax.Array, mesh):
    """Parameters (``block_diffusion_moe.init_params``) and one fixed noised
    batch, made on the device from ``key`` in one jitted call: uniform token
    ids over the held rows BUT the last, which is the mask token (never data,
    never a target), noised a block at a time by the input pipeline's
    ``data.block_diffusion_noise`` under the configuration's law — and held to
    :func:`plain_noise` on the same key: a batch under another law (1 / (1 −
    t), a level a token, the mask token among the targets) is refused here,
    before program and reference are both fed it.  Returns ``(params, (x_t,
    x_0, weights), batch)``."""
    from byteps_tpu.data import block_diffusion_noise
    from byteps_tpu.models import block_diffusion_moe
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]
    mask_id = mcfg.vocab_size - 1
    law = (mcfg.block_length, mask_id, cfg["noise_lo"], cfg["noise_hi"])

    def make(key):
        k_params, k_tokens, k_noise = jax.random.split(key, 3)
        clean = jax.random.randint(k_tokens, (batch, mcfg.max_seq), 0, mask_id, jnp.int32)
        noisy, weights = block_diffusion_noise(k_noise, clean, *law)
        own_noisy, own_weights = plain_noise(k_noise, clean, *law)
        same = (jnp.all(noisy == own_noisy) & jnp.all(weights == own_weights)
                & jnp.all(clean != mask_id))
        return block_diffusion_moe.init_params(mcfg, k_params), noisy, clean, weights, same

    rows = NamedSharding(mesh, P("dp", "sp"))
    specs = {k: NamedSharding(mesh, s) for k, s in param_specs(mcfg).items()}
    params, noisy, clean, weights, same = jax.jit(
        make, out_shardings=(specs, rows, rows, rows, NamedSharding(mesh, P())))(key)
    if not bool(same):
        raise ValueError("data.block_diffusion_noise gave another batch than the builder's "
                         "plain_noise on the same key: the noising's law moved")
    return params, (noisy, clean, weights), batch


def build(cfg: dict, traffic: dict, params, batch, mesh):
    """``build_train_step`` with the optimizer state made as the program's
    examples make it (``jax.jit(tx.init)``).  Returns ``step()``, which
    dispatches one training step and returns ``(loss, parameters)``; the
    step donates ``params``."""
    from byteps_tpu.models.transformer import build_train_step

    if traffic["step_path"] != "local":
        raise ValueError(f"sdar_moe builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
