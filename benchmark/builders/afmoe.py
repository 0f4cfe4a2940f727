"""Builder for the ``trinity_mini_ep16`` configuration
(benchmark/configs/trinity_mini_ep16.json): Trinity-Mini's block
(``model_type: afmoe``) at its published widths — gated grouped-query
attention at heads of 128, three sliding-window layers (2048 keys, rope) to
one global layer (no positions), sandwich norms, a leading dense layer, 128-wide
sigmoid routing over 1024-wide experts beside one shared expert — one chip's
share of a 16-way expert-parallel deployment.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over a ``WindowMoEConfig``).

``plain_loss`` is a copy of ``byteps_tpu/models/window_moe_reference.py``
(float32, ``highest`` matmul precision, dense attention with both masks
written out as comparisons of positions and repeated key/value heads, a loop
over the held experts with a mask), computed in blocks so that three steps at
the timed size fit beside the state that set-up holds: a remat'ed mixer or
MLP at a time and in it a sequence at a time, attention a block of queries at
a time against only the keys their mask can see (a sliding layer's block
against the ``sliding_window + Q_BLOCK`` keys that end with it, a global
layer's against the keys up to its run's end), the dense MLP, the experts and
the logits a block of rows at a time.
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
    """Layer by layer, (mixer, MLP) of the layers that are run: the entries
    ``[first_layer, first_layer + num_hidden_layers)`` of the published
    ``layer_types``; the first ``num_dense_layers`` of them have a dense MLP."""
    lo = cfg["first_layer"]
    types = cfg["layer_types"][lo:lo + cfg["num_hidden_layers"]]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError(f"layer_types has no {cfg['num_hidden_layers']} entries from {lo}")
    return [(t, "dense" if i < cfg["num_dense_layers"] else "moe") for i, t in enumerate(types)]


def _built(cfg: dict) -> None:
    """The switches of the published config that have one position built."""
    for key, want in (("score_func", "sigmoid"), ("route_norm", True), ("n_group", 1),
                      ("topk_group", 1), ("rope_scaling", None), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if cfg[key] != want or type(cfg[key]) is not type(want):
            raise ValueError(f"afmoe builder has {key} = {want!r} alone, not {cfg[key]!r}")


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
    gate, k, v, out); the dense layers' MLP; in every expert layer the router,
    the shared expert and the slots the held experts expect (top_k x held /
    router width = 0.5 a token); the untied head.  Attention: the entries the
    mask keeps (:func:`band_entries`), 2 (d + d) a score, every query head."""
    s, d, v = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    kinds = _kinds(cfg)
    dense = sum(m == "dense" for _, m in kinds)
    held_slots = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    fe = cfg["moe_intermediate_size"]
    macs = (len(kinds) * (3 * d * h * hd + 2 * d * kv * hd)
            + dense * 3 * d * cfg["intermediate_size"]
            + (len(kinds) - dense) * (d * cfg["router_width"]
                                      + 3 * d * fe * cfg["num_shared_experts"]
                                      + held_slots * 3 * d * fe)
            + d * v)
    entries = sum(band_entries(s, cfg["sliding_window"] if t == SLIDING else None)
                  for t, _ in kinds)
    return float(3 * (s * 2 * macs + entries * h * 2 * (hd + hd)))


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"afmoe builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of byteps_tpu/models/window_moe_reference.py, blocked)
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
    (``tools/latent_moe_precision.py --config trinity_mini_ep16``; run.py
    passes neither): ``compute`` is what the matrix products' operands and the
    residual stream are rounded to, ``statistics`` what the norms' statistics,
    the router's scores and weights, the softmax and the gate's sigmoid are
    computed in.  (bfloat16, float32) is the precision the configuration
    states, (bfloat16, bfloat16) the nearest below it.  Parameters and the
    loss stay float32 in all of them."""
    _built(cfg)
    eps, theta, window = cfg["rms_norm_eps"], float(cfg["rope_theta"]), cfg["sliding_window"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lo, held, top_k = cfg["held_expert_lo"], cfg["num_experts"], cfg["num_experts_per_tok"]
    scale, route_eps = cfg["route_scale"], cfg["route_eps"]
    embed_scale = cfg["hidden_size"] ** 0.5 if cfg["mup_enabled"] else 1.0
    f32 = jnp.float32

    def rms(x, w):
        return _rms(x, w, eps, statistics).astype(compute)

    def w(lp, *names):
        return (lp[n].astype(compute) for n in names)

    # ---- gated grouped-query attention under its two masks -----------------------

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
            # pass: kept, they would be 2.25 GB a tensor over a layer's blocks
            qb, first = xs
            kb = lax.dynamic_slice_in_dim(keys, first, span + block, axis=2)
            vb = lax.dynamic_slice_in_dim(values, first, span + block, axis=2)
            return attend(qb, kb, vb, first + jnp.arange(block),
                          first - span + jnp.arange(span + block), window)

        o = lax.map(one, (jnp.moveaxis(q.reshape(b, nh, s // block, block, -1), 2, 0),
                          block * jnp.arange(s // block)))
        return jnp.moveaxis(o, 0, 2).reshape(b, nh, s, -1)

    def attention_mixer(kind):
        def mixer(x, lp):
            """One key/value head with its group of query heads at a time,
            each rebuilt in the backward pass and their outputs added in f32
            (the products over all 32 heads at once hold 256 MB a tensor)."""
            d, group = x.shape[-1], h // kv
            wq, wk, wv, wg, wo = w(lp, "wq", "wk", "wv", "wg", "wo")
            g = rms(x, lp["norm"])
            per_kv = (jnp.moveaxis(wq.reshape(d, kv, group, hd), 1, 0),
                      jnp.moveaxis(wk, 1, 0)[:, :, None], jnp.moveaxis(wv, 1, 0)[:, :, None],
                      jnp.moveaxis(wg.reshape(d, kv, group, hd), 1, 0),
                      wo.reshape(kv, group, hd, d))

            @jax.checkpoint
            def one(y, ws):
                q, k, v, z = (jnp.einsum("bsd,dhk->bhsk", g, m) for m in ws[:4])
                q, k = rms(q, lp["q_norm"]), rms(k, lp["k_norm"])
                if kind == SLIDING:  # the full layers take no positional encoding at all
                    q, k = _rope(q, theta), _rope(k, theta)
                k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
                o = (window_attention if kind == SLIDING else causal_attention)(q, k, v)
                o = (o.astype(statistics) * jax.nn.sigmoid(z.astype(statistics))).astype(compute)
                return y + jnp.einsum("bhsk,hkd->bsd", o, ws[4], preferred_element_type=f32), None

            y, _ = lax.scan(one, jnp.zeros(x.shape, f32), per_kv)
            return rms(y, lp["post_norm"])
        return mixer

    # ---- the MLPs -----------------------------------------------------------------

    def by_rows(rows_fn):
        """An MLP over (1, S, D) between its two norms, a block of rows at a
        time, each rebuilt in the backward pass: the hidden activations are
        one block's."""
        def mlp(x, lp):
            b, s, d = x.shape
            block = min(ROW_BLOCK, b * s)
            one = jax.checkpoint(lambda xb: rms(rows_fn(xb, lp), lp["post_norm"]))
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

        shared = _swiglu(g, *w(lp, "s_gate", "s_up", "s_down")).astype(f32)
        y, _ = lax.scan(add_expert, shared,
                        (*w(lp, "e_gate", "e_up", "e_down"), weights[:, lo:lo + held].T))
        return y.astype(compute)

    def a_sequence_at_a_time(part):
        """``x + part(x)`` over a batch, one sequence after another, each
        rebuilt in the backward pass: sequences meet only in the loss's mean,
        and a part's temporaries are one sequence's."""
        one = jax.checkpoint(lambda row, lp: row + part(row[None], lp)[0].astype(compute))
        return lambda x, lp: lax.map(lambda row: one(row, lp), x)

    parts = {SLIDING: a_sequence_at_a_time(attention_mixer(SLIDING)),
             FULL: a_sequence_at_a_time(attention_mixer(FULL)),
             "dense": a_sequence_at_a_time(by_rows(dense_rows)),
             "moe": a_sequence_at_a_time(by_rows(expert_rows))}
    stack_of = {SLIDING: "win", FULL: "glob", "dense": "dense", "moe": "moe"}

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

    def loss(params, batch):
        tokens, targets = batch
        with jax.default_matmul_precision("highest"):
            x = (params["embed"][tokens] * embed_scale).astype(compute)
            nth = dict.fromkeys(stack_of.values(), 0)
            for pair in _kinds(cfg):
                for kind in pair:
                    stack = stack_of[kind]
                    lp = {k.split(".", 1)[1]: v[nth[stack]] for k, v in params.items()
                          if k.startswith(stack + ".")}
                    nth[stack] += 1
                    x = parts[kind](x, lp)
            total, count = xent(x, params["norm_f"], params["head"], targets)
        return total / count

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.window_moe import WindowMoEConfig

    _built(cfg)
    return WindowMoEConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_types=tuple(t for t, _ in _kinds(cfg)), n_dense_layers=cfg["num_dense_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        sliding_window=cfg["sliding_window"], d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        n_experts=cfg["router_width"], experts_held=cfg["num_experts"],
        expert_lo=cfg["held_expert_lo"], top_k=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["route_scale"]), route_eps=cfg["route_eps"],
        mup=cfg["mup_enabled"], norm_eps=cfg["rms_norm_eps"], max_seq=cfg["max_seq"],
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
    """Parameters (``window_moe.init_params``) and one fixed batch of uniform
    token ids over the held rows with next-token targets, made on the device
    from ``key`` in one jitted call."""
    from byteps_tpu.models import window_moe
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]

    def make(key):
        k_params, k_tokens = jax.random.split(key)
        tokens = jax.random.randint(
            k_tokens, (batch, mcfg.max_seq), 0, mcfg.vocab_size, jnp.int32)
        return window_moe.init_params(mcfg, k_params), tokens, jnp.roll(tokens, -1, axis=1)

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
        raise ValueError(f"afmoe builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
