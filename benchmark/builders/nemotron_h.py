"""Builder for the ``nemotron_twotower_30b_ep16`` configuration
(benchmark/configs/nemotron_twotower_30b_ep16.json): the tower that
Nemotron-Labs-TwoTower-30B-A3B's config gives (``model_type: nemotron_h``) at
its published widths — layers that are a mixer or an MLP alone: Mamba-2
state-space mixers (64 heads of 64, a 64 x 128 state a head, B and C in 8
groups of 128, a biased convolution of 4 taps, a gated grouped norm),
attention at 32 | 2 heads of 128 without positions, top-6 of 128 ungated
relu^2 experts 1856 wide beside a shared one 3712 wide — one chip's share of a
16-way expert-parallel deployment.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over an ``SsmMoEConfig``).

``plain_loss`` is a copy of ``byteps_tpu/models/ssm_moe_reference.py``
(float32, ``highest`` matmul precision, the state-space recurrence token by
token, dense causal attention with repeated key/value heads, a loop over the
held experts with a mask, the router in the published order), computed in
blocks so that three steps at the timed size fit beside the state that set-up
holds: a remat'ed layer at a time and in it a sequence at a time; a Mamba-2
layer one group of heads at a time (its 8 heads, its B
and C, its run of the gated norm: the groups meet only in ``out_proj``'s sum)
and the recurrence a chunk of ``chunk_size`` tokens at a time, each rebuilt in
the backward pass (a state a token kept would be 2 MB x 8192); attention a
block of queries at a time against the keys up to its run's end; the experts
and the logits a block of rows at a time.
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
#: holds at a time; in how many runs, each with its own range of keys, an
#: attention layer's queries are taken
Q_BLOCK, ROW_BLOCK, KEY_GROUPS = 256, 2048, 4
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
_STACK = {MAMBA: "ssm", ATTENTION: "attn", EXPERTS: "moe"}


def _kinds(cfg: dict) -> str:
    """Layer by layer, the part of the layers that are run: the characters
    ``[first_layer, first_layer + num_hidden_layers)`` of the published
    ``hybrid_override_pattern`` (M: a Mamba-2 mixer, *: attention, E: experts)."""
    lo, n = cfg["first_layer"], cfg["num_hidden_layers"]
    kinds = cfg["hybrid_override_pattern"][lo:lo + n]
    if len(kinds) != n or set(kinds) - set(_STACK):
        raise ValueError(f"hybrid_override_pattern has no {n} layers of {sorted(_STACK)} "
                         f"from {lo}: {kinds!r}")
    return kinds


def _built(cfg: dict) -> None:
    """The switches of the published config that have one position built.
    (``expand`` is not among them: the mixer's inner width is
    ``mamba_num_heads x mamba_head_dim`` = 4096, as the published code computes
    it, and 2 x 2688 is read nowhere.)"""
    for key, want in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True), ("n_shared_experts", 1),
                      ("use_conv_bias", True), ("use_bias", False), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("mlp_bias", False), ("sliding_window", None),
                      ("residual_in_fp32", False), ("rescale_prenorm_residual", True),
                      ("tie_word_embeddings", False), ("time_step_limit", [0, None])):
        if cfg[key] != want or type(cfg[key]) is not type(want):
            raise ValueError(f"nemotron_h builder has {key} = {want!r} alone, not {cfg[key]!r}")


def scan_operations(heads: int, head_dim: int, state: int) -> int:
    """Operations a token of one layer's recurrence, forward: the decay of the
    state (one), ``dt x (x) B`` added to it (two) and its read by C (two) an
    entry of the heads' (head_dim x state) states."""
    return 5 * heads * head_dim * state


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward (3 x forward) of one sequence, recomputation not
    counted, of the mathematics and not of padding, of what a block computes
    above the diagonal or of the chunked form's extra products.  A token's
    matrix products: a Mamba-2 layer's ``in_proj`` and ``out_proj``; the
    attention's q, k, v, out; in every expert layer the router, the shared
    expert and the slots the held experts expect (top_k x held / router width
    = 0.375 a token), two products a slot; the untied head.  Beside them the
    recurrence (:func:`scan_operations`), the convolution's taps, and
    attention's entries under the causal mask, 2 (d + d) a score, every query
    head."""
    s, d, v = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    hs, hp, n, g = (cfg[k] for k in ("mamba_num_heads", "mamba_head_dim", "ssm_state_size",
                                     "n_groups"))
    kinds = _kinds(cfg)
    di, conv = hs * hp, hs * hp + 2 * g * n
    held_slots = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_width"]
    macs = (kinds.count(MAMBA) * (d * (di + conv + hs) + di * d + cfg["conv_kernel"] * conv)
            + kinds.count(ATTENTION) * (2 * d * h * hd + 2 * d * kv * hd)
            + kinds.count(EXPERTS) * (
                d * cfg["router_width"]
                + 2 * d * cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
                + held_slots * 2 * d * cfg["moe_intermediate_size"])
            + d * v)
    per_token = 2 * macs + kinds.count(MAMBA) * scan_operations(hs, hp, n)
    entries = kinds.count(ATTENTION) * s * (s + 1) // 2
    return float(3 * (s * per_token + entries * h * 2 * (hd + hd)))


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"nemotron_h builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of byteps_tpu/models/ssm_moe_reference.py, blocked)
# ---------------------------------------------------------------------------


def _rms(x, w, eps, st=jnp.float32):
    """RMSNorm ``w x / rms(x)`` with its statistics in ``st``; returns ``st``."""
    x = x.astype(st)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(st)


def _relu2(h):
    return jnp.where(h > 0, h, 0) ** 2


def plain_loss(cfg: dict, compute=jnp.float32, statistics=jnp.float32):
    """Mean next-token cross-entropy over the program's flat parameter dict,
    in float32 whatever ``compute_dtype`` says: the reference is the
    mathematics, and the program's bf16 is held to it by ``reference_rtol``
    and ``reference_update_rtol``.

    The two dtypes are for the controls that those limits are set from
    (``tools/latent_moe_precision.py --config nemotron_twotower_30b_ep16``;
    run.py passes neither): ``compute`` is what the matrix products' operands
    and the residual stream are rounded to, ``statistics`` what the norms'
    statistics, the router's scores and weights, the softmax, the
    convolution's sum and, of the scan, the step sizes, the decay's sums and
    the states are computed in.  (bfloat16, float32) is the precision the
    configuration states, (bfloat16, bfloat16) the nearest below it.
    Parameters and the loss stay float32 in all of them."""
    _built(cfg)
    eps = cfg["layer_norm_epsilon"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    hs, hp, n, groups = (cfg[k] for k in ("mamba_num_heads", "mamba_head_dim", "ssm_state_size",
                                          "n_groups"))
    chunk, taps = cfg["chunk_size"], cfg["conv_kernel"]
    lo, held, top_k = cfg["held_expert_lo"], cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    scale = cfg["routed_scaling_factor"]
    di, per = hs * hp, hs // groups  # the mixer's inner width; heads a group
    f32 = jnp.float32

    def rms(x, w):
        return _rms(x, w, eps, statistics).astype(compute)

    def w(lp, *names):
        return (lp[name].astype(compute) for name in names)

    # ---- the Mamba-2 mixer, a group of heads at a time ---------------------------

    def recurrence(x, dt, a, b, c):
        """x (S, per, P), dt (S, per), a (per,), b and c (S, N), all in
        ``statistics`` -> y (S, per, P): ``h_t = exp(dt_t a) h_{t-1} + dt_t
        x_t (x) B_t``, ``y_t = h_t C_t``, token by token; a chunk of tokens at
        a time is rebuilt in the backward pass, which then keeps one chunk's
        states."""
        def token(state, xs):
            x_t, dt_t, b_t, c_t = xs
            state = (jnp.exp(dt_t * a)[:, None, None] * state
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
            return state, jnp.einsum("hpn,n->hp", state, c_t)

        @jax.checkpoint
        def a_chunk(state, xs):
            return lax.scan(token, state, xs)

        s = x.shape[0]
        length = min(chunk, s)
        state0 = jnp.zeros((per, hp, n), statistics)
        _, y = lax.scan(a_chunk, state0, tuple(
            t.reshape(s // length, length, *t.shape[1:]) for t in (x, dt, b, c)))
        return y.reshape(s, per, hp)

    def mamba(x, lp):
        """(1, S, D) -> the mixer's output.  ``in_proj``'s columns are
        [z | x | B | C | dt]; group ``i`` takes its 8 heads' columns of z, x
        and dt and its own of B and C, runs conv, silu, softplus, the
        recurrence, ``D x`` and its run of the gated norm, and adds its rows of
        ``out_proj``'s product."""
        s, d = x.shape[1:]
        u = rms(x, lp["norm"])[0]
        w_in, w_out = w(lp, "w_in", "w_out")
        gn, width = groups * n, per * hp
        cols = {"z": (0, width), "x": (di, width), "b": (2 * di, n), "c": (2 * di + gn, n),
                "dt": (2 * di + 2 * gn, per)}
        st = statistics

        def of_group(m, i, name, base=0):
            """Group i's columns ``name`` of m, whose columns start at ``base``
            of ``in_proj``'s."""
            first, size = cols[name]
            return lax.dynamic_slice_in_dim(m, first - base + i * size, size, axis=-1)

        @jax.checkpoint
        def one(y, i):
            def conv(name):
                t = (u @ of_group(w_in, i, name)).astype(st)  # (S, size)
                k = of_group(lp["conv"], i, name, di).astype(st)
                padded = jnp.pad(t, ((taps - 1, 0), (0, 0)))
                total = of_group(lp["conv_bias"], i, name, di).astype(st) + sum(
                    k[j] * padded[j:j + s] for j in range(taps))
                return jax.nn.silu(total).astype(compute).astype(st)

            z = (u @ of_group(w_in, i, "z")).astype(st)
            xs, b, c = conv("x"), conv("b"), conv("c")
            cut = lax.dynamic_slice_in_dim  # a group's heads, channels or rows of a leaf
            dt = jax.nn.softplus((u @ of_group(w_in, i, "dt")).astype(st)
                                 + cut(lp["dt_bias"], i * per, per).astype(st))
            a = -jnp.exp(cut(lp["a_log"], i * per, per).astype(st))
            xh = xs.reshape(s, per, hp)
            yh = recurrence(xh, dt, a, b, c) + cut(lp["d_skip"], i * per, per).astype(st)[
                :, None] * xh
            gated = yh.reshape(s, width) * jax.nn.silu(z)
            normed = gated * lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
            normed = normed * cut(lp["gate_norm"], i * width, width).astype(st)
            rows = cut(w_out, i * width, width, axis=0)
            return y + jnp.dot(normed.astype(compute), rows, preferred_element_type=f32), None

        y, _ = lax.scan(one, jnp.zeros((s, d), f32), jnp.arange(groups))
        return y.astype(compute)[None]

    # ---- grouped-query attention, causal, no positions ---------------------------

    @jax.checkpoint
    def attend(q, k, v, q_pos, k_pos):
        """One block of queries at positions ``q_pos`` against the keys at
        ``k_pos``."""
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=f32) / hd ** 0.5
        seen = k_pos[None, :] <= q_pos[:, None]
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
            o = lax.map(lambda xs: attend(xs[0], keys, values, xs[1] + jnp.arange(block), k_pos),
                        (jnp.moveaxis(blocks, 2, 0), a + block * jnp.arange(run // block)))
            out.append(jnp.moveaxis(o, 0, 2).reshape(b, nh, run, -1))
        return jnp.concatenate(out, axis=2)

    def attention(x, lp):
        """(1, S, D) -> the attention's output.  One key/value head with its
        group of query heads at a time, each rebuilt in the backward pass and
        their outputs added in f32."""
        d, group = x.shape[-1], h // kv
        wq, wk, wv, wo = w(lp, "wq", "wk", "wv", "wo")
        a = rms(x, lp["norm"])
        per_kv = (jnp.moveaxis(wq.reshape(d, kv, group, hd), 1, 0),
                  jnp.moveaxis(wk, 1, 0)[:, :, None], jnp.moveaxis(wv, 1, 0)[:, :, None],
                  wo.reshape(kv, group, hd, d))

        @jax.checkpoint
        def one(y, ws):
            q, k, v = (jnp.einsum("bsd,dhk->bhsk", a, m) for m in ws[:3])
            k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
            o = causal_attention(q, k, v)
            return y + jnp.einsum("bhsk,hkd->bsd", o, ws[3], preferred_element_type=f32), None

        y, _ = lax.scan(one, jnp.zeros(x.shape, f32), per_kv)
        return y.astype(compute)

    # ---- the experts ----------------------------------------------------------------

    def experts(x, lp):
        """(1, S, D) -> the shared expert's and the held experts' part, a block
        of rows at a time, each rebuilt in the backward pass: the hidden
        activations are one block's.  The held experts one after another, each
        over every row, masked by its weight."""
        _, s, d = x.shape
        block = min(ROW_BLOCK, s)
        e_up, e_down, s_up, s_down = w(lp, "e_up", "e_down", "s_up", "s_down")

        @jax.checkpoint
        def one(xb):
            g_st = _rms(xb, lp["norm"], eps, statistics)
            g = g_st.astype(compute)
            scores = jax.nn.sigmoid(g_st @ lp["router"].astype(statistics))
            _, ids = lax.top_k(scores + lp["router_bias"].astype(statistics), top_k)
            chosen = jnp.zeros_like(scores).at[jnp.arange(xb.shape[0])[:, None], ids].set(1.0)
            weights = scale * scores * chosen / (
                jnp.sum(scores * chosen, axis=-1, keepdims=True) + 1e-20)

            def add_expert(y, e):
                w_up, w_down, weight = e
                return y + weight[:, None].astype(f32) * (_relu2(g @ w_up) @ w_down), None

            shared = (_relu2(g @ s_up) @ s_down).astype(f32)
            y, _ = lax.scan(add_expert, shared, (e_up, e_down, weights[:, lo:lo + held].T))
            return y.astype(compute)

        return lax.map(one, x.reshape(s // block, block, d)).reshape(1, s, d)

    def a_sequence_at_a_time(part):
        """``x + part(x)`` over a batch, one sequence after another, each
        rebuilt in the backward pass: sequences meet only in the loss's mean,
        and a part's temporaries are one sequence's.  (The other order — a
        sequence at a time through the whole stack — adds every sequence's
        gradient of ALL parameters to a running sum, a second copy of the
        gradient: 2.5 GiB that set-up does not have.)"""
        one = jax.checkpoint(lambda row, lp: row + part(row[None], lp)[0].astype(compute))
        return lambda x, lp: lax.map(lambda row: one(row, lp), x)

    layers = {MAMBA: a_sequence_at_a_time(mamba), ATTENTION: a_sequence_at_a_time(attention),
              EXPERTS: a_sequence_at_a_time(experts)}

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
            x = params["embed"][tokens].astype(compute)
            nth = dict.fromkeys(_STACK.values(), 0)
            for kind in _kinds(cfg):
                stack = _STACK[kind]
                lp = {k.split(".", 1)[1]: v[nth[stack]] for k, v in params.items()
                      if k.startswith(stack + ".")}
                nth[stack] += 1
                x = layers[kind](x, lp)
            total, count = xent(x, params["norm_f"], params["head"], targets)
        return total / count

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.ssm_moe import SsmMoEConfig

    _built(cfg)
    return SsmMoEConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"], layer_types=tuple(_kinds(cfg)),
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk=min(cfg["chunk_size"], cfg["max_seq"]),
        dt_min=cfg["time_step_min"], dt_max=cfg["time_step_max"], dt_floor=cfg["time_step_floor"],
        residual_layers=cfg["published"]["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"],
        n_experts=cfg["router_width"], experts_held=cfg["n_routed_experts"],
        expert_lo=cfg["held_expert_lo"], top_k=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["routed_scaling_factor"]), norm_eps=cfg["layer_norm_epsilon"],
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
    """Parameters (``ssm_moe.init_params``) and one fixed batch of uniform
    token ids over the held rows with next-token targets, made on the device
    from ``key`` in one jitted call."""
    from byteps_tpu.models import ssm_moe
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]

    def make(key):
        k_params, k_tokens = jax.random.split(key)
        tokens = jax.random.randint(
            k_tokens, (batch, mcfg.max_seq), 0, mcfg.vocab_size, jnp.int32)
        return ssm_moe.init_params(mcfg, k_params), tokens, jnp.roll(tokens, -1, axis=1)

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
        raise ValueError(f"nemotron_h builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
