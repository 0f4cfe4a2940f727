"""Builder for the ``phi4_mini_flash_vp8`` configuration
(benchmark/configs/phi4_mini_flash_vp8.json): layers 15-19 of
Phi-4-mini-flash-reasoning (``model_type: phi4flash``, SambaY) at the published
widths — the seam between the self-decoder and the cross-decoder: differential
attention over a window of 512, the Mamba-1 layer whose scan output is the
memory, the one full differential attention whose keys and values are shared,
a Gated Memory Unit on that memory, differential cross-attention to those keys
and values; LayerNorm with bias, SwiGLU 10240 wide, no positions — with 25 008
rows of the tied embedding: one stage of a pipeline over the 32 layers, the
vocabulary 8-way parallel.

Same names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference: jax alone, nothing of byteps_tpu),
``make_state`` and ``build`` (the program's
``models/transformer.build_train_step`` over a ``CrossDecoderConfig``).

``plain_loss`` is a copy of ``byteps_tpu/models/cross_decoder_reference.py``
(float32, ``highest`` matmul precision, the recurrence token by token, dense
masked softmaxes), computed in blocks so that three steps at the timed size
fit beside the state that set-up holds: a remat'ed part at a time; the Mamba-1
layer a group of channels at a time (twice: once for ``[δ ‖ B ‖ C]``, which
contracts every channel, once for the recurrence, the gate and ``W_out``'s
rows) and the recurrence a chunk of tokens at a time, each rebuilt in the
backward pass; attention a key/value pair with its query pairs at a time and
in it a block of queries at a time, a window layer's against its band of keys
alone; the MLPs, the GMU and the logits a block of rows at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
#: rows of queries, and of an MLP's tokens or of logits, that the reference
#: holds at a time; in how many runs, each with its own range of keys, a full
#: attention layer's queries are taken; channel groups of a Mamba-1 layer
Q_BLOCK, ROW_BLOCK, KEY_GROUPS, CHANNEL_GROUPS = 256, 2048, 4, 8
#: a layer's kind -> the stack that holds its mixer's parameters
_STACK = {"mamba": "mamba", "window": "win", "full": "full", "gmu": "gmu", "cross": "cross"}


def _kind(layer: int, published: int) -> str:
    """The mixer of published layer ``layer`` of ``published``
    (``modeling_phi4flash.py``; ``mb_per_layer`` 2)."""
    half = published // 2
    if layer % 2 == 0:
        return "mamba" if layer <= half else "gmu"
    return "window" if layer < half else "full" if layer == half + 1 else "cross"


def _layers(cfg: dict) -> list:
    """(published index, kind) of the layers that are run."""
    lo, n, published = cfg["first_layer"], cfg["num_hidden_layers"], cfg["published_layers"]
    if not 0 <= lo <= lo + n <= published or not n:
        raise ValueError(f"layers {lo}..{lo + n - 1} of a model of {published}")
    return [(layer, _kind(layer, published)) for layer in range(lo, lo + n)]


def _built(cfg: dict) -> None:
    """The switches of the published config that have one position built."""
    for key, want in (("hidden_act", "silu"), ("mb_per_layer", 2), ("tie_word_embeddings", True),
                      ("mlp_bias", False), ("lm_head_bias", False), ("embd_pdrop", 0),
                      ("resid_pdrop", 0), ("model_type", "phi4flash")):
        if cfg[key] != want or type(cfg[key]) is not type(want):
            raise ValueError(f"phi4flash builder has {key} = {want!r} alone, not {cfg[key]!r}")


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def scan_operations(channels: int, state: int) -> int:
    """Operations a token of one layer's recurrence, forward: the decay of the
    state (one), ``Δ x B`` added to it (two) and its read by C (two) an entry
    of the (channels x state) state."""
    return 5 * channels * state


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward (3 x forward) of one sequence, recomputation not
    counted, of the mathematics and not of what a block computes outside a
    mask.  A token's matrix products: Mamba-1's ``in_proj``, ``x_proj``,
    ``dt_proj`` and ``out_proj``; an attention layer's q, k, v and out (a
    cross layer's q and out); the GMU's two; every layer's SwiGLU, three; the
    tied head over the held rows.  Beside them the recurrence
    (:func:`scan_operations`), the convolution's taps, and attention's score
    entries under its mask — a full or cross layer's S (S + 1) / 2, a window
    layer's W S − W (W − 1) / 2 — each 2 (d_qk + d_v) operations a softmax,
    with d_qk the head size and d_v twice that, two softmaxes a pair of query
    heads."""
    s, d, v, f = cfg["max_seq"], cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    di, n, r, taps = cfg["expand"] * d, cfg["d_state"], cfg["dt_rank"], cfg["d_conv"]
    w = min(cfg["sliding_window"], s)
    kinds = [kind for _, kind in _layers(cfg)]
    own_kv = kinds.count("window") + kinds.count("full")
    macs = (kinds.count("mamba") * (d * 2 * di + di * (r + 2 * n) + r * di + di * d + taps * di)
            + own_kv * (2 * d * h * hd + 2 * d * kv * hd)
            + kinds.count("cross") * 2 * d * h * hd
            + kinds.count("gmu") * 2 * d * di
            + len(kinds) * 3 * d * f
            + d * v)
    per_token = 2 * macs + kinds.count("mamba") * scan_operations(di, n)
    entries = ((kinds.count("full") + kinds.count("cross")) * (s * (s + 1) // 2)
               + kinds.count("window") * (w * s - w * (w - 1) // 2))
    return float(3 * (s * per_token + entries * h * 2 * (hd + 2 * hd)))


# ---------------------------------------------------------------------------
# the tree that is compared
# ---------------------------------------------------------------------------

_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
_HOST = "wq+lambdas"


@jax.jit
def _fold(wq, *lambdas):
    lam = jnp.concatenate(lambdas, axis=-1)[:, None]  # (layers, 1, 4 head_dim)
    return jnp.concatenate([wq.reshape(len(wq), -1, lam.shape[-1]), lam], axis=1)


def compared_params(params: dict) -> dict:
    """The program's parameters as ``make_state`` and ``step()`` hand them to
    run.py: the same leaves, but an attention stack's four λ vectors lie as
    ONE more row under its ``wq`` (rows of 4 x head_dim), in the leaf
    ``<stack>.wq+lambdas``.  adamw steps element by element, so the reference
    trains the same parameters the same way in either tree; only run.py's
    leaf-by-leaf reading changes, and that is the purpose.  A layer's four λ
    vectors take their gradient from ONE scalar, dL/dλ, a sum over every
    token and head that on uniform random tokens comes out near 0 — of
    either sign, its size 1000-fold apart from seed to seed — so on some
    seeds its sign is rounding in any bf16 program, the reference's own
    bf16 control among them, and adamw turns that sign into a whole step of
    all four vectors: as leaves of their own they read 0, 2 or, where the
    reference's step is under adam's eps, 11 (PERF.md section 6, PR 63).
    What this costs: ``correct`` does NOT hold the λ vectors' update on the
    chip (256 of 6.5 M elements of their leaf); their gradient is held in
    float32 by tests/test_cross_decoder.py.  Every other leaf is the
    program's own and reads a lost gradient as 1.0."""
    out = dict(params)
    for stack in sorted({k.rpartition(".")[0] for k in params if k.endswith(".wq")}):
        out[f"{stack}.{_HOST}"] = _fold(
            out.pop(f"{stack}.wq"), *(out.pop(f"{stack}.{name}") for name in _LAMBDAS))
    return out


def program_params(cfg: dict, params: dict) -> dict:
    """:func:`compared_params` undone: the program's own tree."""
    shape, hd = (cfg["hidden_size"], cfg["num_attention_heads"], _head_dim(cfg)), _head_dim(cfg)
    out = dict(params)
    for host in [k for k in params if k.endswith("." + _HOST)]:
        stack, rows = host.rpartition(".")[0], out.pop(host)
        out[f"{stack}.wq"] = rows[:, :-1].reshape(len(rows), *shape)
        for i, name in enumerate(_LAMBDAS):
            out[f"{stack}.{name}"] = rows[:, -1, i * hd:(i + 1) * hd]
    return out


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"phi4flash builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


# ---------------------------------------------------------------------------
# the plain reference (copy of byteps_tpu/models/cross_decoder_reference.py,
# blocked)
# ---------------------------------------------------------------------------


def plain_loss(cfg: dict, compute=jnp.float32, statistics=jnp.float32):
    """Mean next-token cross-entropy over the program's flat parameter dict,
    in float32 whatever ``compute_dtype`` says: the reference is the
    mathematics, and the program's bf16 is held to it by ``reference_rtol``
    and ``reference_update_rtol``.

    The two dtypes are for the controls that those limits are set from
    (``tools/latent_moe_precision.py --config phi4_mini_flash_vp8``; run.py
    passes neither): ``compute`` is what the matrix products' operands and the
    residual stream are rounded to, ``statistics`` what the LayerNorms' and
    the pair norm's statistics, the softmax, λ, the convolution's sum and, of
    the scan, Δ, the decay and the state are computed in.  (bfloat16, float32)
    is the precision the configuration states, (bfloat16, bfloat16) the
    nearest below it.  Parameters and the loss stay float32 in all of them."""
    _built(cfg)
    eps, d = cfg["layer_norm_eps"], cfg["hidden_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    di, n, r, taps = cfg["expand"] * d, cfg["d_state"], cfg["dt_rank"], cfg["d_conv"]
    chunk, window = cfg["scan_chunk"], cfg["sliding_window"]
    groups = math.gcd(CHANNEL_GROUPS, di)
    width = di // groups  # channels a group
    f32, st = jnp.float32, statistics
    cut = lax.dynamic_slice_in_dim

    def ln(x, lp):
        x = x.astype(st)
        x = x - jnp.mean(x, axis=-1, keepdims=True)
        y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return (y * lp["norm"].astype(st) + lp["norm_bias"].astype(st)).astype(compute)

    def w(lp, *names):
        return (lp[name].astype(compute) for name in names)

    def by_rows(one, x, *more):
        """``one`` on blocks of x's rows (S, ·), each rebuilt in the backward
        pass; ``more``: what every block reads whole."""
        s = x.shape[0]
        block = min(ROW_BLOCK, s)
        y = lax.map(lambda xb: jax.checkpoint(one)(xb, *more), x.reshape(s // block, block, -1))
        return y.reshape(s, -1)

    # ---- Mamba-1, a group of channels at a time ------------------------------------

    def recurrence(x, dt, a, b, c):
        """x, dt (S, width), a (width, N), b and c (S, N), all in ``st`` ->
        (S, width): ``h_t = exp(Δ_t (x) A) h_(t-1) + (Δ_t x_t) (x) B_t``,
        ``y_t = h_t C_t``, token by token; a chunk of tokens at a time is
        rebuilt in the backward pass, which then keeps one chunk's states."""
        def token(state, xs):
            x_t, dt_t, b_t, c_t = xs
            state = jnp.exp(dt_t[:, None] * a) * state + (dt_t * x_t)[:, None] * b_t[None, :]
            return state, state @ c_t

        @jax.checkpoint
        def a_chunk(state, xs):
            return lax.scan(token, state, xs)

        s = x.shape[0]
        length = math.gcd(chunk, s)
        _, y = lax.scan(a_chunk, jnp.zeros((width, n), st), tuple(
            t.reshape(s // length, length, *t.shape[1:]) for t in (x, dt, b, c)))
        return y.reshape(s, width)

    def mamba(x, lp):
        """(S, D) -> (the mixer's output (S, D), the memory (S, d_i))."""
        s = x.shape[0]
        u = ln(x, lp)
        w_in, w_x, w_dt, w_out = w(lp, "w_in", "w_x", "w_dt", "w_out")

        def conved(i):
            """Group i's channels of x after the convolution, its bias and silu."""
            t = (u @ cut(w_in, i * width, width, axis=1)).astype(st)
            k = cut(lp["conv"], i * width, width, axis=1).astype(st)
            padded = jnp.pad(t, ((taps - 1, 0), (0, 0)))
            total = cut(lp["conv_bias"], i * width, width).astype(st) + sum(
                k[j] * padded[j:j + s] for j in range(taps))
            return jax.nn.silu(total).astype(compute)

        @jax.checkpoint
        def project(dbc, i):
            rows = cut(w_x, i * width, width, axis=0)
            return dbc + jnp.dot(conved(i), rows, preferred_element_type=f32), None

        dbc, _ = lax.scan(project, jnp.zeros((s, r + 2 * n), f32), jnp.arange(groups))
        dbc = dbc.astype(compute)
        delta, b, c = dbc[:, :r], dbc[:, r:r + n].astype(st), dbc[:, r + n:].astype(st)

        @jax.checkpoint
        def one(y, i):
            xs = conved(i).astype(st)
            z = (u @ cut(w_in, di + i * width, width, axis=1)).astype(st)
            dt = jax.nn.softplus(
                jnp.dot(delta, cut(w_dt, i * width, width, axis=1),
                        preferred_element_type=f32).astype(st)
                + cut(lp["dt_bias"], i * width, width).astype(st))
            a = -jnp.exp(cut(lp["a_log"], i * width, width, axis=0).astype(st))
            m = (recurrence(xs, dt, a, b, c)
                 + cut(lp["d_skip"], i * width, width).astype(st) * xs).astype(compute)
            gated = (m.astype(st) * jax.nn.silu(z)).astype(compute)
            rows = cut(w_out, i * width, width, axis=0)
            return y + jnp.dot(gated, rows, preferred_element_type=f32), m

        y, memory = lax.scan(one, jnp.zeros((s, d), f32), jnp.arange(groups))
        return y.astype(compute), jnp.moveaxis(memory, 0, 1).reshape(s, di)

    # ---- differential attention ---------------------------------------------------

    @jax.checkpoint
    def attend(q, k, v, q_pos, k_pos, band):
        """One block of queries (heads, Q, d) at positions ``q_pos`` against
        the keys (K, d) and values (K, 2 d) at ``k_pos``."""
        scores = jnp.einsum("hqd,kd->hqk", q, k, preferred_element_type=f32) / hd ** 0.5
        seen = k_pos[None, :] <= q_pos[:, None]
        if band is not None:
            seen = seen & (q_pos[:, None] - k_pos[None, :] < band)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf).astype(st), axis=-1)
        return jnp.einsum("hqk,kd->hqd", p.astype(compute), v)

    def masked_attention(q, k, v, band):
        """q (heads, S, d), k (S, d), v (S, 2 d) -> (heads, S, 2 d): dense
        masked attention, never more than Q_BLOCK rows of scores at a time.
        Full: the queries in KEY_GROUPS runs, each against the keys up to its
        end.  Banded: a block of queries against the keys from ``band − 1``
        before its first to its last."""
        nh, s, _ = q.shape
        if band is not None and band < s:
            block = min(Q_BLOCK, s)
            span = block + band - 1  # keys a block can see
            pk, pv = (jnp.pad(t, ((band - 1, 0), (0, 0))) for t in (k, v))

            def one(xs):
                qb, first = xs  # the block's first position; its keys start band − 1 before
                k_pos = first - (band - 1) + jnp.arange(span)
                # a padded key (position < 0) is before every window: masked by its position
                return attend(qb, cut(pk, first, span), cut(pv, first, span),
                              first + jnp.arange(block), jnp.where(k_pos < 0, s, k_pos), band)

            blocks = jnp.moveaxis(q.reshape(nh, s // block, block, -1), 1, 0)
            o = lax.map(one, (blocks, block * jnp.arange(s // block)))
            return jnp.moveaxis(o, 0, 1).reshape(nh, s, -1)
        run = max(s // KEY_GROUPS, 1)
        block = min(Q_BLOCK, run)
        out = []
        for a in range(0, s, run):
            blocks = jnp.moveaxis(q[:, a:a + run].reshape(nh, run // block, block, -1), 1, 0)
            keys, values, k_pos = k[:a + run], v[:a + run], jnp.arange(a + run)
            o = lax.map(lambda xs: attend(xs[0], keys, values, xs[1] + jnp.arange(block), k_pos,
                                          band),
                        (blocks, a + block * jnp.arange(run // block)))
            out.append(jnp.moveaxis(o, 0, 1).reshape(nh, run, -1))
        return jnp.concatenate(out, axis=1)

    def keys_values(u, lp):
        """(k (kv / 2, 2, S, d), V (kv / 2, S, 2 d)): a pair's two key heads,
        and its two value heads side by side.  The one departure from
        cross_decoder_reference.py: a key's bias moves every score of a query
        alike, so its gradient is 0 in the mathematics and rounding in any
        program (1e-8 of ``wk``'s there: tests/test_cross_decoder.py), and
        it is written as the 0 it is — adamw would normalise the rounding
        into a step, and the leaf's comparison would read noise over noise."""
        s = u.shape[0]
        wk, wv = w(lp, "wk", "wv")
        k = (jnp.einsum("sd,dhk->hsk", u, wk)
             + lax.stop_gradient(lp["bk"]).astype(compute)[:, None, :])
        v = jnp.einsum("sd,dhk->hsk", u, wv) + lp["bv"].astype(compute)[:, None, :]
        v = v.reshape(kv // 2, 2, s, hd)
        return k.reshape(kv // 2, 2, s, hd), jnp.concatenate([v[:, 0], v[:, 1]], axis=-1)

    def differential(layer, u, lp, k, v, band):
        """u (S, D) normed -> (S, D).  One key/value pair with its ``group``
        query pairs at a time, each rebuilt in the backward pass and their
        outputs added in f32."""
        s, group = u.shape[0], h // kv
        wq, wo = w(lp, "wq", "wo")
        lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
        lam = (jnp.exp(jnp.sum(lp["lambda_q1"].astype(st) * lp["lambda_k1"].astype(st)))
               - jnp.exp(jnp.sum(lp["lambda_q2"].astype(st) * lp["lambda_k2"].astype(st)))
               + lam_init)
        per_pair = (jnp.moveaxis(wq.reshape(d, kv // 2, group, 2, hd), 1, 0),
                    lp["bq"].astype(compute).reshape(kv // 2, group, 2, hd),
                    wo.reshape(kv // 2, group, 2 * hd, d), k, v)

        @jax.checkpoint
        def one(y, ws):
            wq_, bq_, wo_, k_, v_ = ws
            q = jnp.einsum("sd,dgik->gisk", u, wq_) + bq_[:, :, None, :]  # (group, 2, S, d)
            a1, a2 = (masked_attention(q[:, i], k_[i], v_, band).astype(st) for i in (0, 1))
            a = a1 - lam * a2  # (group, S, 2 d)
            a = a * lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps)
            o = ((1.0 - lam_init) * a * lp["subln"].astype(st)).astype(compute)
            return y + jnp.einsum("gsk,gkd->sd", o, wo_, preferred_element_type=f32), None

        y, _ = lax.scan(one, jnp.zeros((s, d), f32), per_pair)
        return (y + lp["bo"].astype(f32)).astype(compute)

    # ---- the row-wise parts ----------------------------------------------------------

    def gmu(x, lp, memory):
        w_in, w_out = w(lp, "w_in", "w_out")

        def one(rows):
            xb, mb = rows[:, :d], rows[:, d:]
            gate = (ln(xb, lp) @ w_in).astype(st)
            return ((mb.astype(st) * jax.nn.silu(gate)).astype(compute) @ w_out)

        return by_rows(one, jnp.concatenate([x, memory], axis=-1))

    def mlp(x, lp):
        w_gate, w_up, w_down = w(lp, "w_gate", "w_up", "w_down")

        def one(xb):
            g = ln(xb, lp)
            return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down

        return by_rows(one, x)

    def xent(x, scale_f, bias_f, rows, targets):
        """(sum of cross-entropies over targets >= 0, their count), the
        logits a block of rows at a time; the head is the embedding."""
        block = min(ROW_BLOCK, x.shape[0])
        xs, tgt = x.reshape(-1, block, d), targets.reshape(-1, block)
        head = rows.astype(compute).T

        @jax.checkpoint
        def one(xb, tb):
            normed = ln(xb, {"norm": scale_f, "norm_bias": bias_f})
            logits = jnp.dot(normed, head, preferred_element_type=f32)
            gold = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * (tb >= 0))

        total = jnp.sum(lax.map(lambda xt: one(*xt), (xs, tgt)))
        return total, jnp.sum(tgt >= 0).astype(f32)

    def sequence_sums(params, tokens, targets):
        """One sequence through the stack, a remat'ed part at a time."""
        x = params["embed"][tokens].astype(compute)
        nth = dict.fromkeys((*_STACK.values(), "dense"), 0)

        def take(stack):
            """The next layer of a stack."""
            lp = {k.split(".", 1)[1]: v[nth[stack]] for k, v in params.items()
                  if k.startswith(stack + ".")}
            nth[stack] += 1
            return lp

        memory = k = v = None
        for layer, kind in _layers(cfg):
            lp = take(_STACK[kind])
            if kind == "mamba":
                y, memory = jax.checkpoint(mamba)(x, lp)
            elif kind == "gmu":
                y = jax.checkpoint(gmu)(x, lp, memory)
            elif kind == "cross":
                y = jax.checkpoint(lambda x, lp, k, v: differential(
                    layer, ln(x, lp), lp, k, v, None))(x, lp, k, v)
            else:
                band = window if kind == "window" else None

                def own(x, lp, layer=layer, band=band):
                    u = ln(x, lp)
                    k, v = keys_values(u, lp)
                    return differential(layer, u, lp, k, v, band), k, v

                y, k_, v_ = jax.checkpoint(own)(x, lp)
                if kind == "full":
                    k, v = k_, v_
            x = x + y
            x = x + jax.checkpoint(mlp)(x, take("dense")).astype(compute)
        return xent(x, params["norm_f"], params["norm_f_bias"], params["embed"], targets)

    def loss(params, batch):
        tokens, targets = batch
        params = program_params(cfg, params)
        with jax.default_matmul_precision("highest"):
            # sequences meet only in the loss's mean
            totals, counts = lax.map(lambda row: sequence_sums(params, *row), (tokens, targets))
        return jnp.sum(totals) / jnp.sum(counts)

    return loss


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _model_config(cfg: dict):
    from byteps_tpu.models.cross_decoder import CrossDecoderConfig

    _built(cfg)
    _layers(cfg)
    return CrossDecoderConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"], first_layer=cfg["first_layer"],
        held_layers=cfg["num_hidden_layers"], published_layers=cfg["published_layers"],
        d_ff=cfg["intermediate_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=_head_dim(cfg),
        window=cfg["sliding_window"], expand=cfg["expand"], d_state=cfg["d_state"],
        conv_kernel=cfg["d_conv"], dt_rank=cfg["dt_rank"],
        chunk=min(cfg["scan_chunk"], cfg["max_seq"]), dt_min=cfg["time_step_min"],
        dt_max=cfg["time_step_max"], dt_floor=cfg["time_step_floor"],
        norm_eps=cfg["layer_norm_eps"], max_seq=cfg["max_seq"],
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
    """Parameters (``cross_decoder.init_params``, as :func:`compared_params`
    lays them) and one fixed batch of uniform token ids over the held rows
    with next-token targets, made on the device from ``key`` in one jitted
    call."""
    from byteps_tpu.models import cross_decoder
    from byteps_tpu.models.transformer import param_specs

    mcfg, mesh = _model_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]

    def make(key):
        k_params, k_tokens = jax.random.split(key)
        tokens = jax.random.randint(
            k_tokens, (batch, mcfg.max_seq), 0, mcfg.vocab_size, jnp.int32)
        params = compared_params(cross_decoder.init_params(mcfg, k_params))
        return params, tokens, jnp.roll(tokens, -1, axis=1)

    rows = NamedSharding(mesh, P("dp", "sp"))
    specs = {k: NamedSharding(mesh, s) for k, s in param_specs(mcfg).items()}
    specs = {k: specs.get(k, NamedSharding(mesh, P())) for k in jax.eval_shape(make, key)[0]}
    params, tokens, targets = jax.jit(make, out_shardings=(specs, rows, rows))(key)
    return params, (tokens, targets), batch


def build(cfg: dict, traffic: dict, params, batch, mesh):
    """``build_train_step`` with the optimizer state made as the program's
    examples make it (``jax.jit(tx.init)``).  Returns ``step()``, which
    dispatches one training step and returns ``(loss, parameters)``, the
    parameters as :func:`compared_params` lays them; ``params`` is donated."""
    from byteps_tpu.models.transformer import build_train_step

    if traffic["step_path"] != "local":
        raise ValueError(f"phi4flash builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    params = jax.jit(lambda p: program_params(cfg, p), donate_argnums=0)(params)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_model_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, compared_params(state[0])

    return step
