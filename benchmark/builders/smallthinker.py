"""Builder for the ``smallthinker_21b_ep8`` configuration
(benchmark/configs/smallthinker_21b_ep8.json): SmallThinker-21BA3B's block at
its published widths — a router that reads the attention's normed input and
decides before the attention runs, plain grouped-query attention at 28 | 4
heads of 128, one global layer without positions to three sliding-window
layers (4096 keys, rope at theta 1.5e6), top-6 of 64 ReLU-gated experts 768
wide in every layer, no shared expert, untied head — one chip's share of an
8-way expert-parallel deployment.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over an ``EarlyRouteMoEConfig``).

``plain_loss`` is a copy of ``byteps_tpu/models/early_route_moe_reference.py``
(float32, ``highest`` matmul precision, dense attention with both masks
written out as comparisons of positions and repeated key/value heads, a loop
over the held experts with a mask, the router in the published order: the six
largest logits, then a softmax over those), computed in blocks so that three
steps at the timed size fit beside the state that set-up holds: a sequence at
a time through the whole stack and in it a remat'ed mixer or MLP at a time,
attention a block of
queries at a time against only the keys their mask can see (a sliding layer's
block against the ``sliding_window + Q_BLOCK`` keys that end with it, a global
layer's against the keys up to its run's end), the experts and the logits a
block of rows at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
#: rows of queries, and of an MLP's tokens or of logits, that the reference
#: holds at a time; in how many runs, each with its own range of keys, a
#: global layer's queries are taken
Q_BLOCK, ROW_BLOCK, KEY_GROUPS = 256, 2048, 4
SLIDING, FULL = "sliding_attention", "full_attention"


def _kinds(cfg: dict) -> list:
    """Layer by layer, the mixer of the layers that are run: the entries
    ``[first_layer, first_layer + num_hidden_layers)`` of the published
    ``sliding_window_layout`` (1: a window; 0: all keys before).  The
    published ``rope_layout`` is the same list — a layer takes rope iff it
    slides — and that is the one position built."""
    lo, n = cfg["first_layer"], cfg["num_hidden_layers"]
    slides, turns = (cfg[k][lo:lo + n] for k in ("sliding_window_layout", "rope_layout"))
    if len(slides) != n:
        raise ValueError(f"sliding_window_layout has no {n} entries from {lo}")
    if slides != turns:
        raise ValueError("smallthinker builder has rope on the sliding layers alone: "
                         f"rope_layout {turns} is not sliding_window_layout {slides}")
    return [SLIDING if s else FULL for s in slides]


def _built(cfg: dict) -> None:
    """The switches of the published config that have one position built."""
    for key, want in (("moe_primary_router_apply_softmax", True), ("norm_topk_prob", True),
                      ("rope_scaling", None), ("tie_word_embeddings", False)):
        if cfg[key] != want or type(cfg[key]) is not type(want):
            raise ValueError(f"smallthinker builder has {key} = {want!r} alone, not {cfg[key]!r}")


def band_entries(s: int, window: int | None) -> int:
    """Score entries a head that the mask keeps: ``S (S + 1) / 2`` causal, of
    them those fewer than ``window`` back at a window."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * s - window * (window - 1) // 2


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward (3 x forward) of one sequence, recomputation not
    counted, of the mathematics and not of padding or of what a block computes
    outside the band.  A token's matrix products: the mixers' projections (q,
    k, v, out); in every layer the router and the slots the held experts
    expect (active x held / router width = 0.75 a token), three products a
    slot; the untied head.  Attention: the entries the mask keeps
    (:func:`band_entries`), 2 (d + d) a score, every query head."""
    s, d, v = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    kinds = _kinds(cfg)
    held_slots = (cfg["moe_num_active_primary_experts"] * cfg["moe_num_primary_experts"]
                  / cfg["router_width"])
    macs = (len(kinds) * (2 * d * h * hd + 2 * d * kv * hd + d * cfg["router_width"]
                          + held_slots * 3 * d * cfg["moe_ffn_hidden_size"])
            + d * v)
    entries = sum(band_entries(s, cfg["sliding_window_size"] if t == SLIDING else None)
                  for t in kinds)
    return float(3 * (s * 2 * macs + entries * h * 2 * (hd + hd)))


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"smallthinker builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of byteps_tpu/models/early_route_moe_reference.py, blocked)
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


def _reglu(g, w_gate, w_up, w_down):
    gate = g @ w_gate
    return (jnp.where(gate > 0, gate, 0) * (g @ w_up)) @ w_down


def plain_loss(cfg: dict, compute=jnp.float32, statistics=jnp.float32):
    """Mean next-token cross-entropy over the program's flat parameter dict,
    in float32 whatever ``compute_dtype`` says: the reference is the
    mathematics, and the program's bf16 is held to it by ``reference_rtol``
    and ``reference_update_rtol``.

    The two dtypes are for the controls that those limits are set from
    (``tools/latent_moe_precision.py --config smallthinker_21b_ep8``; run.py
    passes neither): ``compute`` is what the matrix products' operands and the
    residual stream are rounded to, ``statistics`` what the norms' statistics,
    the router's logits and weights and the softmax are computed in.
    (bfloat16, float32) is the precision the configuration states,
    (bfloat16, bfloat16) the nearest below it.  Parameters and the loss stay
    float32 in all of them."""
    _built(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    window = cfg["sliding_window_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lo, held = cfg["held_expert_lo"], cfg["moe_num_primary_experts"]
    top_k = cfg["moe_num_active_primary_experts"]
    f32 = jnp.float32

    def rms(x, w):
        return _rms(x, w, eps, statistics).astype(compute)

    def w(lp, *names):
        return (lp[n].astype(compute) for n in names)

    # ---- the router: before the attention, on the attention's normed input -------

    def route(a_st, router):
        """a_st (S, D) in ``statistics`` -> (held, S) weights of the held
        experts, zero where one was not chosen: the ``top_k`` largest logits,
        then a softmax over those alone."""
        logits = a_st @ router.astype(statistics)
        chosen, ids = lax.top_k(logits, top_k)
        weights = jnp.zeros_like(logits).at[jnp.arange(a_st.shape[0])[:, None], ids].set(
            jax.nn.softmax(chosen, axis=-1))
        return weights[:, lo:lo + held].T

    # ---- grouped-query attention under its two masks ------------------------------

    @functools.partial(jax.checkpoint, static_argnums=(5,))
    def attend(q, k, v, q_pos, k_pos, window):
        """One block of queries at positions ``q_pos`` against the keys at
        ``k_pos`` (negative: before the sequence's start, seen by nobody)."""
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=f32) / hd ** 0.5
        seen = (k_pos[None, :] >= 0) & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            seen &= k_pos[None, :] > q_pos[:, None] - window
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf).astype(statistics), axis=-1)
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
            keys, values, k_pos = k[:, :, :a + run], v[:, :, :a + run], jnp.arange(a + run)
            o = lax.map(lambda xs: attend(xs[0], keys, values, xs[1] + jnp.arange(block),
                                          k_pos, None),
                        (jnp.moveaxis(blocks, 2, 0), a + block * jnp.arange(run // block)))
            out.append(jnp.moveaxis(o, 0, 2).reshape(b, nh, run, -1))
        return jnp.concatenate(out, axis=2)

    def window_attention(q, k, v):
        """Dense attention under the window, a block of queries at a time
        against the ``window + block`` keys that end with the block: the keys
        are padded by ``window`` at the front, so every block's slice has one
        length, and the padding's positions are negative."""
        b, nh, s, _ = q.shape
        block = min(Q_BLOCK, s)
        span = min(window, s)  # a window longer than the sequence sees all of it
        pad = ((0, 0), (0, 0), (span, 0), (0, 0))
        keys, values = jnp.pad(k, pad), jnp.pad(v, pad)

        @jax.checkpoint
        def one(xs):
            # the block's keys are cut inside what is rebuilt in the backward
            # pass: kept, they would be gigabytes a tensor over a layer's blocks
            qb, first = xs
            kb = lax.dynamic_slice_in_dim(keys, first, span + block, axis=2)
            vb = lax.dynamic_slice_in_dim(values, first, span + block, axis=2)
            return attend(qb, kb, vb, first + jnp.arange(block),
                          first - span + jnp.arange(span + block), window)

        o = lax.map(one, (jnp.moveaxis(q.reshape(b, nh, s // block, block, -1), 2, 0),
                          block * jnp.arange(s // block)))
        return jnp.moveaxis(o, 0, 2).reshape(b, nh, s, -1)

    def mixer(kind):
        def part(x, lp):
            """(1, S, D) -> the attention's output and the routing weights
            (held, S), both from the one normed input.  One key/value head
            with its group of query heads at a time, each rebuilt in the
            backward pass and their outputs added in f32."""
            d, group = x.shape[-1], h // kv
            wq, wk, wv, wo = w(lp, "wq", "wk", "wv", "wo")
            a_st = _rms(x, lp["norm"], eps, statistics)
            weights = route(a_st[0], lp["router"])  # decided here, before the attention
            a = a_st.astype(compute)
            per_kv = (jnp.moveaxis(wq.reshape(d, kv, group, hd), 1, 0),
                      jnp.moveaxis(wk, 1, 0)[:, :, None], jnp.moveaxis(wv, 1, 0)[:, :, None],
                      wo.reshape(kv, group, hd, d))

            @jax.checkpoint
            def one(y, ws):
                q, k, v = (jnp.einsum("bsd,dhk->bhsk", a, m) for m in ws[:3])
                if kind == SLIDING:  # the full layers take no positional encoding at all
                    q, k = _rope(q, theta), _rope(k, theta)
                k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
                o = (window_attention if kind == SLIDING else causal_attention)(q, k, v)
                return y + jnp.einsum("bhsk,hkd->bsd", o, ws[3], preferred_element_type=f32), None

            y, _ = lax.scan(one, jnp.zeros(x.shape, f32), per_kv)
            return y.astype(compute), weights
        return part

    # ---- the experts ----------------------------------------------------------------

    def experts(x, lp, weights):
        """(1, S, D) and the weights (held, S) decided before the attention
        -> the held experts' part, a block of rows at a time, each rebuilt in
        the backward pass: the hidden activations are one block's.  The held
        experts one after another, each over every row, masked by its weight."""
        _, s, d = x.shape
        block = min(ROW_BLOCK, s)
        e_gate, e_up, e_down = w(lp, "e_gate", "e_up", "e_down")

        @jax.checkpoint
        def one(xs):
            xb, wb = xs
            g = rms(xb, lp["norm"])

            def add_expert(y, e):
                w_gate, w_up, w_down, weight = e
                return y + weight[:, None].astype(f32) * _reglu(g, w_gate, w_up, w_down), None

            y, _ = lax.scan(add_expert, jnp.zeros(xb.shape, f32), (e_gate, e_up, e_down, wb))
            return y.astype(compute)

        rows = x.reshape(s // block, block, d)
        by_block = jnp.moveaxis(weights.reshape(held, s // block, block), 1, 0)
        return lax.map(one, (rows, by_block)).reshape(1, s, d)

    def layer(kind):
        """(1, S, D) -> the layer's output.  The mixer's part and the experts'
        are each rebuilt in the backward pass; the weights cross from the one
        to the other."""
        attend_and_route = jax.checkpoint(mixer(kind))
        mlp = jax.checkpoint(experts)

        def one(x, mixer_lp, moe_lp):
            y, weights = attend_and_route(x, mixer_lp)
            x = x + y
            return x + mlp(x, moe_lp, weights)

        return one

    layers = {SLIDING: layer(SLIDING), FULL: layer(FULL)}
    stack_of = {SLIDING: "win", FULL: "glob"}

    def xent(x, scale_f, head, targets):
        """(sum of cross-entropies over targets >= 0, their count), the
        logits a block of rows at a time; the head is untied, (V, D)."""
        d = x.shape[-1]
        block = min(ROW_BLOCK, x.size // d)
        rows, tgt = x.reshape(-1, block, d), targets.reshape(-1, block)

        @jax.checkpoint
        def one(xb, tb):
            logits = jnp.dot(rms(xb, scale_f), head.astype(compute).T, preferred_element_type=f32)
            gold = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * (tb >= 0))

        total = jnp.sum(lax.map(lambda xs: one(*xs), (rows, tgt)))
        return total, jnp.sum(tgt >= 0).astype(f32)

    def layer_params(params, stack, i):
        return {k.split(".", 1)[1]: v[i] for k, v in params.items() if k.startswith(stack + ".")}

    @jax.checkpoint
    def sequence_sums(params, tokens, targets):
        """One sequence (S,) through the whole stack -> (sum of its
        cross-entropies, their count).  Sequences meet only in the loss's
        mean, so a batch is this function one sequence after another, each
        rebuilt in the backward pass: the activations are one sequence's
        (kept for the batch, XLA held 9.2 GiB of them at 2 x 16384)."""
        x = params["embed"][tokens][None].astype(compute)
        nth = dict.fromkeys(stack_of.values(), 0)
        for i, kind in enumerate(_kinds(cfg)):
            stack = stack_of[kind]
            x = layers[kind](x, layer_params(params, stack, nth[stack]),
                             layer_params(params, "moe", i))
            nth[stack] += 1
        return xent(x, params["norm_f"], params["head"], targets)

    def loss(params, batch):
        with jax.default_matmul_precision("highest"):
            totals, counts = lax.map(lambda xs: sequence_sums(params, *xs), batch)
        return jnp.sum(totals) / jnp.sum(counts)

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.early_route_moe import EarlyRouteMoEConfig

    _built(cfg)
    return EarlyRouteMoEConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_types=tuple(_kinds(cfg)), n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), sliding_window=cfg["sliding_window_size"],
        d_expert=cfg["moe_ffn_hidden_size"], n_experts=cfg["router_width"],
        experts_held=cfg["moe_num_primary_experts"], expert_lo=cfg["held_expert_lo"],
        top_k=cfg["moe_num_active_primary_experts"], norm_eps=cfg["rms_norm_eps"],
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
    """Parameters (``early_route_moe.init_params``) and one fixed batch of
    uniform token ids over the held rows with next-token targets, made on the
    device from ``key`` in one jitted call."""
    from byteps_tpu.models import early_route_moe
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]

    def make(key):
        k_params, k_tokens = jax.random.split(key)
        tokens = jax.random.randint(
            k_tokens, (batch, mcfg.max_seq), 0, mcfg.vocab_size, jnp.int32)
        return early_route_moe.init_params(mcfg, k_params), tokens, jnp.roll(tokens, -1, axis=1)

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
        raise ValueError(f"smallthinker builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
