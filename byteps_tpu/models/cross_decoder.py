"""Decoder-hybrid-decoder family (SambaY's block, as Phi-4-mini-flash-reasoning
publishes it, ``model_type: phi4flash``; arXiv 2507.06607, with differential
attention, arXiv 2410.05258) — a contiguous run of the model's layers behind
``build_train_step``.

Every layer is ``x ← x + mixer(LN₁(x)); x ← x + MLP(LN₂(x))``: LayerNorm with
scale and bias, a SwiGLU MLP without bias, no positional encoding anywhere.
The model is NOT periodic: a layer's mixer follows from its PUBLISHED index ℓ
and the published depth n (:func:`kind_of`; half = n / 2):

``mamba``  ℓ even, ℓ ≤ half — Mamba-1: ``[x̃ ‖ z] = W_in u``; ``x̃ ←
           silu(conv(x̃) + b)`` (depthwise, causal, ``conv_kernel`` taps);
           ``[δ ‖ B ‖ C] = W_x x̃``; ``Δ = softplus(W_dt δ + b_dt)``; ``A =
           −exp(A_log)``; the selective scan ``h_t = exp(Δ_t ⊗ A) ⊙ h_{t−1} +
           (Δ_t ⊙ x̃_t) ⊗ B_t``, ``m_t = h_t C_t + D ⊙ x̃_t``
           (``ops/selective_scan.py``); the output ``W_out(m ⊙ silu(z))``.
           **m is the memory**, exported before the gate.
``window`` ℓ odd, ℓ < half — differential attention over a causal window of
           ``window`` keys, the query's own among them.
``full``   ℓ = half + 1 — differential attention, full causal; **exports its
           k and v**.
``gmu``    ℓ even, ℓ > half — a Gated Memory Unit, ``W_out(m ⊙ silu(W_in
           u))`` on the memory of the last ``mamba`` layer: a gate alone, no
           mixing over tokens.
``cross``  ℓ odd, ℓ > half + 1 — differential cross-attention: its own
           queries against the ``full`` layer's k and v, full causal.

Differential attention: ``[q ‖ k ‖ v] = W u + b``, heads of ``head_dim``;
adjacent heads pair — query pair p is heads (2p, 2p + 1) = (q¹, q²), key pair
j is (k¹, k²), the value pair one vector ``V = [v¹ ‖ v²]`` of 2 · head_dim;
query pair p reads key/value pair ⌊p / group⌋; ``a¹ = softmax(q¹k¹ᵀ/√d) V``,
``a² = softmax(q²k²ᵀ/√d) V`` under one mask; ``λ = exp(λ_q1·λ_k1) −
exp(λ_q2·λ_k2) + λ_init``, ``λ_init = 0.8 − 0.6 exp(−0.3 ℓ)``; ``o = (1 −
λ_init) · RMSNorm(a¹ − λ a²)`` over the pair's 2 · head_dim with one learned
scale a layer; the pairs flattened, then ``W_o o + b_o``.  Here: TWO calls of
``ops.flash_attention.flash_attention`` a layer, one a softmax, at (heads/2 |
kv_heads/2) heads of d_qk = head_dim and d_v = 2 · head_dim — q¹ | q² and k¹ |
k² come from the even | odd heads' columns of the weights, so no activation is
cut or stacked, V is never copied, and the kernels sum dV over both calls'
readers as autodiff sums any value read twice.

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is — :class:`moe_family.Patterned` without experts: every layer's MLP
is ``dense``.  The config lists its layers by published index
(``first_layer``, ``held_layers``, ``published_layers``): a device holds one
stage of a pipeline over the model's layers, and the first ``vocab_size`` rows
of the tied embedding.  Parameters are stacked by kind (``mamba``, ``win``,
``full``, ``gmu``, ``cross``, ``dense``), layers unrolled
(``moe_family.walk``), each part rebuilt in the backward pass on its own but
for the flash kernels' outputs and row statistics and the scan's
``selective_scan.SAVED``.  The memory and (k¹, k², V) leave their layer as
``moe_family.Carried`` values: outputs of one rebuilt part and inputs of
later ones, so they are kept, and their cotangents are summed over every
reader.  :func:`run_layers` takes and returns them, so that a stage after the
seam gets them from the stage before it as it gets ``x``.

Compute dtype: the matrix products' operands and the residual stream;
parameters, LayerNorm and pair-norm statistics, the softmax statistics, λ, Δ,
the decay, the state and the loss are f32.  The plain reference is
``models/cross_decoder_reference.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from byteps_tpu.models import moe_family as mf
from byteps_tpu.models.moe_family import Carried, layer_norm
from byteps_tpu.ops.causal_conv import conv_silu
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.selective_scan import CHUNK, SAVED as SCAN_SAVED, selective_scan

#: ``layer_types`` entry → the stack that holds that mixer's parameters
MIXERS = {"mamba": "mamba", "window": "win", "full": "full", "gmu": "gmu", "cross": "cross"}


def kind_of(layer: int, published_layers: int) -> str:
    """The mixer of published layer ``layer`` in a model of
    ``published_layers`` (``modeling_phi4flash.py``: ``mb_per_layer`` 2, the
    cross-decoder from half the depth, its first two layers the last Mamba-1
    and the one full attention)."""
    half = published_layers // 2
    if layer % 2 == 0:
        return "mamba" if layer <= half else "gmu"
    return "window" if layer < half else "full" if layer == half + 1 else "cross"


def lambda_init(layer: int) -> float:
    """λ_init of published layer ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


@dataclasses.dataclass(frozen=True)
class CrossDecoderConfig(mf.Patterned):
    vocab_size: int = 25008  # rows of the tied embedding held here
    d_model: int = 2560
    first_layer: int = 15  # published index of the first layer held
    held_layers: int = 5
    published_layers: int = 32  # the whole model's depth: the kinds follow from it
    d_ff: int = 10240
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    window: int = 512  # keys a ``window`` layer's query sees, its own among them
    # the Mamba-1 mixers
    expand: int = 2
    d_state: int = 16
    conv_kernel: int = 4
    dt_rank: int = 160
    chunk: int = CHUNK
    dt_min: float = 1e-3  # the step sizes the mixers start at: log-uniform between
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    norm_eps: float = 1e-5
    max_seq: int = 16384
    compute_dtype: Any = jnp.float32
    remat: bool = True
    layer_types: Tuple[str, ...] = dataclasses.field(init=False, default=())

    mixers = MIXERS
    family = "cross-decoder"
    lacks = ("hand-over of the memory and the shared keys and values between pipeline stages, "
             "head sharding or hand-over of a scan's state between sequence shards")

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(
            kind_of(layer, self.published_layers) for layer in self.layers))
        super().__post_init__()
        self._check_grouped_heads()
        if self.n_heads % 2 or self.n_kv_heads % 2:
            raise ValueError(f"differential attention pairs adjacent heads: {self.n_heads} | "
                             f"{self.n_kv_heads} heads are not both even")
        if self.published_layers % 4 or self.layers[-1] >= self.published_layers:
            raise ValueError(f"layers {self.layers[0]}..{self.layers[-1]} of a model of "
                             f"{self.published_layers} (a multiple of 4)")

    @property
    def layers(self) -> Tuple[int, ...]:
        """The published indices of the layers held."""
        return tuple(range(self.first_layer, self.first_layer + self.held_layers))

    @property
    def n_dense_layers(self) -> int:
        return self.held_layers  # every layer's MLP is dense

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model


def tiny_cross_decoder(**kw) -> CrossDecoderConfig:
    """The CPU tests' preset: a WHOLE model of 8 layers — mamba, window,
    mamba, window, mamba (exports the memory), full (exports k, v), gmu, cross
    — at toy widths: two query pairs a key/value pair, a window of 5 in 16
    tokens, two chunks a sequence."""
    base = dict(vocab_size=96, d_model=32, first_layer=0, held_layers=8, published_layers=8,
                d_ff=48, n_heads=8, n_kv_heads=4, head_dim=8, window=5, d_state=3, dt_rank=4,
                chunk=8, max_seq=16)
    base.update(kw)
    return CrossDecoderConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; ``<stack>.<name>`` carries the stack's layers as
# leading dim, in the order the layers come
# ---------------------------------------------------------------------------


def stacks(cfg: CrossDecoderConfig) -> Dict[str, Tuple[int, Dict[str, tuple]]]:
    """stack name → (layers, per-layer shapes), the stacks some layer reads.
    The published fused matrices are stored by part: ``w_in``'s columns are
    [x̃ | z], ``w_x``'s [δ | B | C]; ``wq`` | ``wk`` | ``wv`` are ``Wqkv``'s
    column blocks, heads in the published order; ``w_gate`` | ``w_up`` are
    the MLP's ``[g ‖ v]``."""
    d, di, f = cfg.d_model, cfg.d_inner, cfg.d_ff
    h, kv, hd, n, r = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_state, cfg.dt_rank
    ln = {"norm": (d,), "norm_bias": (d,)}
    queries = {**ln, "wq": (d, h, hd), "bq": (h, hd), "wo": (h, hd, d), "bo": (d,),
               "subln": (2 * hd,),
               **dict.fromkeys(("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"), (hd,))}
    attention = {**queries, "wk": (d, kv, hd), "bk": (kv, hd), "wv": (d, kv, hd), "bv": (kv, hd)}
    shapes = {
        "mamba": {**ln, "w_in": (d, 2 * di), "conv": (cfg.conv_kernel, di), "conv_bias": (di,),
                  "w_x": (di, r + 2 * n), "w_dt": (r, di), "dt_bias": (di,), "a_log": (di, n),
                  "d_skip": (di,), "w_out": (di, d)},
        "win": attention, "full": attention, "cross": queries,
        "gmu": {**ln, "w_in": (d, di), "w_out": (di, d)},
        "dense": {**ln, "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)},
    }
    return cfg.stack_sizes(shapes)


def layouts(cfg: CrossDecoderConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``).  The head is the embedding."""
    v, d = cfg.vocab_size, cfg.d_model
    return mf.layouts({"embed": (v, d), "norm_f": (d,), "norm_f_bias": (d,)}, stacks(cfg))


def _init(cfg: CrossDecoderConfig) -> Dict[str, Any]:
    """How the leaves start.  Mamba-1's own (``mamba_ssm``'s module, which the
    published code instantiates): ``A_log = log(1 … d_state)`` a channel, ``D
    = 1``, ``b_dt`` the inverse softplus of a step size log-uniform in
    [dt_min, dt_max] (floor dt_floor), ``W_dt ~ U(±dt_rank^−½)``, the taps
    and their bias as ``nn.Conv1d``'s.  The λ vectors N(0, 0.1²) (the
    published ``lambda_std``); LayerNorms and the pair norm at scale 1, bias
    0; every other bias 0.  Every matrix N(0, 1 / fan-in)
    (``moe_family.fan_in``, the repo's other families' start) and the tied
    embedding N(0, 1 / d_model) — 0.0198 an element at 2560, the published
    ``initializer_range`` 0.02: counted at the published widths on 128 tokens
    (two seeds), the stream leaves the five layers at 0.63, 0.89, 1.09, 1.26,
    1.41 — near 1 —, where ``nn.Linear``'s own start (a third of that
    variance) leaves it at 0.16 to 0.32.  (An embedding at N(0, 1) does not
    go with a TIED head: a token's own row then leads the stream, its logit
    for ITSELF is |e|² = 2560, and the loss starts at 2400.)"""
    def a_log(key, shape):
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)

    def dt_bias(key, shape):
        lo, hi = math.log(cfg.dt_min), math.log(cfg.dt_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key(), shape, jnp.float32, lo, hi)),
                         cfg.dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return {**dict.fromkeys(("norm", "norm_f", "subln", "d_skip"), mf.ones),
            "embed": mf.fan_in(-1), "lambda_*": mf.normal(0.1),
            **dict.fromkeys(("norm_bias", "norm_f_bias", "bq", "bk", "bv", "bo"), mf.zeros),
            **dict.fromkeys(("w_in", "w_x", "w_out"), mf.fan_in(-2)),
            "conv": mf.uniform(cfg.conv_kernel ** -0.5),
            "conv_bias": mf.uniform(cfg.conv_kernel ** -0.5),
            "w_dt": mf.uniform(cfg.dt_rank ** -0.5), "dt_bias": dt_bias, "a_log": a_log}


def init_params(cfg: CrossDecoderConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :func:`_init`."""
    return mf.init_params(layouts(cfg), key, _init(cfg))


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _normed(cfg, x, lp):
    return layer_norm(x, lp["norm"], lp["norm_bias"], cfg.norm_eps).astype(cfg.compute_dtype)


def _mamba_layer(cfg: CrossDecoderConfig, x, lp):
    """x (B, S, D) → ``x + mamba(LN(x))`` and the memory (B, S, d_inner) in
    the compute dtype.  Token-major from end to end."""
    cdt, f32 = cfg.compute_dtype, jnp.float32
    di, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
    with jax.named_scope("mamba_proj"):
        xz = _normed(cfg, x, lp) @ lp["w_in"].astype(cdt)
        z = xz[..., di:]
        xc = conv_silu(xz, lp["conv"], lp["conv_bias"], lo=0, hi=di)
        dbc = xc @ lp["w_x"].astype(cdt)
        b, c = dbc[..., r:r + n], dbc[..., r + n:]
        dt = jnp.einsum("bsr,rc->bsc", dbc[..., :r], lp["w_dt"].astype(cdt),
                        preferred_element_type=f32)
    with jax.named_scope("selective_scan"):
        dt = jax.nn.softplus(dt + lp["dt_bias"])
        m = selective_scan(xc, dt, -jnp.exp(lp["a_log"]), b, c, lp["d_skip"], chunk=cfg.chunk)
    with jax.named_scope("mamba_proj"):
        gated = (m.astype(f32) * jax.nn.silu(z.astype(f32))).astype(cdt)
        y = gated @ lp["w_out"].astype(cdt)
    return Carried(x + y.astype(x.dtype), {"memory": m})


def _pairs(cfg, u, w, bias):
    """The even | odd heads' projections of u (B, S, D), each (B, heads / 2,
    S, head_dim): the first and the second of every adjacent pair."""
    cdt = cfg.compute_dtype
    return tuple(jnp.einsum("bsd,dhk->bhsk", u, w[:, i::2].astype(cdt))
                 + bias[i::2, None, :].astype(cdt) for i in (0, 1))


def _keys_values(cfg, u, lp):
    """(k¹, k², V): the key pairs (B, kv / 2, S, head_dim) each and the value
    pairs' one vector (B, kv / 2, S, 2 · head_dim).  A key's bias moves every
    score of a query alike, under any mask, so no softmax sees it: its
    gradient is 0 in the mathematics and nothing but the products' rounding
    in a program — which adamw would normalise into steps of the full rate —
    so it is taken as the 0 it is."""
    cdt, kv, hd = cfg.compute_dtype, cfg.n_kv_heads, cfg.head_dim
    k1, k2 = _pairs(cfg, u, lp["wk"], jax.lax.stop_gradient(lp["bk"]))
    wv = lp["wv"].reshape(cfg.d_model, kv // 2, 2 * hd).astype(cdt)
    v = jnp.einsum("bsd,dhk->bhsk", u, wv) + lp["bv"].reshape(kv // 2, 1, 2 * hd).astype(cdt)
    return k1, k2, v


def differential_attention(cfg: CrossDecoderConfig, u, lp, kv, window: Optional[int]):
    """u (B, S, D) normed → the mixer's output (B, S, D) in the compute
    dtype: the layer's own queries against ``kv`` = (k¹, k², V), causal (over
    the last ``window`` keys where that is given); λ, the subtraction and the
    pair norm in f32."""
    cdt, f32, hd = cfg.compute_dtype, jnp.float32, cfg.head_dim
    k1, k2, v = kv
    q1, q2 = _pairs(cfg, u, lp["wq"], lp["bq"])
    a1, a2 = (flash_attention(q, k, v, causal=True, scale=hd ** -0.5, window=window)
              for q, k in ((q1, k1), (q2, k2)))
    lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
           - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + lp["lambda_init"])
    o = (1.0 - lp["lambda_init"]) * mf.rms(a1.astype(f32) - lam * a2.astype(f32), lp["subln"],
                                           cfg.norm_eps)
    wo = lp["wo"].reshape(cfg.n_heads // 2, 2 * hd, cfg.d_model).astype(cdt)
    return jnp.einsum("bhsk,hkd->bsd", o.astype(cdt), wo) + lp["bo"].astype(cdt)


def _attention_layer(cfg: CrossDecoderConfig, scope: str, window: Optional[int], export: bool):
    """A ``window`` | ``full`` layer's part: x (B, S, D) → ``x +
    attention(LN(x))``, and (k¹, k², V) beside it where it exports them."""
    def part(x, lp):
        with jax.named_scope(scope):
            u = _normed(cfg, x, lp)
            kv = _keys_values(cfg, u, lp)
            x = x + differential_attention(cfg, u, lp, kv, window).astype(x.dtype)
        return Carried(x, {"kv": kv}) if export else x

    return part


def _cross_layer(cfg: CrossDecoderConfig, x, lp, kv):
    """x (B, S, D) → ``x + attention(LN(x))`` against the ``full`` layer's
    ``kv``."""
    with jax.named_scope("diff_cross_attention"):
        y = differential_attention(cfg, _normed(cfg, x, lp), lp, kv, None)
        return x + y.astype(x.dtype)


def _gmu_layer(cfg: CrossDecoderConfig, x, lp, memory):
    """x (B, S, D) → ``x + W_out(memory ⊙ silu(W_in LN(x)))``."""
    cdt, f32 = cfg.compute_dtype, jnp.float32
    with jax.named_scope("gated_memory"):
        gate = _normed(cfg, x, lp) @ lp["w_in"].astype(cdt)
        gated = (memory.astype(f32) * jax.nn.silu(gate.astype(f32))).astype(cdt)
        return x + (gated @ lp["w_out"].astype(cdt)).astype(x.dtype)


def _dense_layer(cfg: CrossDecoderConfig, x, lp):
    """x (B, S, D) → ``x + SwiGLU(LN(x))``."""
    cdt = cfg.compute_dtype
    with jax.named_scope("dense_mlp"):
        y = mf.swiglu(_normed(cfg, x, lp), *(lp[w].astype(cdt)
                                             for w in ("w_gate", "w_up", "w_down")))
        return x + y.astype(x.dtype)


def run_layers(cfg: CrossDecoderConfig, params, x, carried: Optional[Dict[str, Any]] = None):
    """The held layers on x (B, S, D).  ``carried``: what layers BEFORE these
    exported (``memory``: (B, S, d_inner); ``kv``: (k¹, k², V)) where these
    read it — a stage after the seam gets it with x.  Returns (x, carried
    after these layers)."""
    run = {"mamba": lambda x, lp: _mamba_layer(cfg, x, lp),
           "win": _attention_layer(cfg, "diff_window_attention", cfg.window, False),
           "full": _attention_layer(cfg, "diff_full_attention", None, True),
           "gmu": lambda x, lp, memory: _gmu_layer(cfg, x, lp, memory),
           "cross": lambda x, lp, kv: _cross_layer(cfg, x, lp, kv),
           "dense": lambda x, lp: _dense_layer(cfg, x, lp)}
    kept = {"mamba": SCAN_SAVED, **dict.fromkeys(("win", "full", "cross"), mf.FLASH_SAVED)}
    # λ_init is a layer's own constant: it rides with the layer's leaves
    consts = {}
    for stack in ("win", "full", "cross"):
        held = [lambda_init(layer) for layer, t in zip(cfg.layers, cfg.layer_types)
                if MIXERS[t] == stack]
        if held:
            consts[f"{stack}.lambda_init"] = jnp.asarray(held, jnp.float32)
    carried = dict(carried or {})
    x, _ = mf.walk(cfg, run, kept, {**params, **consts}, x,
                   takes={"gmu": ("memory",), "cross": ("kv",)}, carried=carried)
    return x, carried


def _hidden(cfg: CrossDecoderConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.compute_dtype)
    return run_layers(cfg, params, x)[0]


def row_logits(cfg: CrossDecoderConfig, x, scale, bias, rows):
    """Logits of the final LayerNorm of x with the tied embedding's held
    rows, f32."""
    h = layer_norm(x, scale, bias, cfg.norm_eps).astype(cfg.compute_dtype)
    return jax.lax.dot_general(h, rows.astype(cfg.compute_dtype),
                               (((h.ndim - 1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _head(params):
    return params["norm_f"], params["norm_f_bias"], params["embed"]


def local_logits(cfg: CrossDecoderConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows; the head is the
    embedding."""
    return row_logits(cfg, _hidden(cfg, params, tokens), *_head(params))


def local_loss(cfg: CrossDecoderConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (all zero: nothing routes)."""
    x = _hidden(cfg, params, tokens)
    stats = jnp.zeros((len(mf.ROUTING_STATS),), jnp.int32)
    return mf.mean_loss(*mf.xent_sums(cfg, row_logits, x, targets, *_head(params)), stats)
