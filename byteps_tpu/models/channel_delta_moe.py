"""Channel-decay delta rule / position-free latent attention mixture-of-experts
family (Kimi Linear's block, ``model_type: kimi_linear``, as
Kimi-Linear-48B-A3B-Instruct publishes it) — the layers behind
``build_train_step``.

A layer is ``h ← h + mixer(norm(h))`` then ``h ← h + mlp(norm(h))``, both kinds
given layer by layer as static data.  ``layer_types[i]`` names the mixer:

``"channel_delta"``     Kimi Delta Attention: q, k, v each a projection, a
    depthwise causal convolution of ``conv_kernel`` taps and a silu; q and k
    l2-normed a head, q scaled by d_k^-½; the log-decay
    ``g = −exp(A_log) ⊙ softplus(W_f↑ W_f↓ x + dt_bias)`` a KEY CHANNEL (low
    rank through ``gate_rank``; ``A_log`` a head, ``dt_bias`` a channel);
    ``β = sigmoid(W_β x)`` a head; the delta rule ``S ← (I − β k kᵀ)
    Diag(e^g) S + β k vᵀ``, ``o = Sᵀ q`` (``ops/gated_delta.py``, the channel
    form); ``W_o (sigmoid(W_g↑ W_g↓ x) ⊙ RMSNorm_head(o))``.
``"latent_attention"``  multi-head latent attention with NO positional
    encoding (``mla_use_nope``) and no query bottleneck
    (``moe_family.latent_attention`` with ``theta`` None: the kernels and the
    pass of ``ops/mla_heads.py`` at heads of 128 + 64 | 128, tables of cos 1,
    sin 0).

The first ``n_dense_layers`` layers' MLP is a dense SwiGLU; the others'
``top_k`` of ``n_experts`` sigmoid-routed experts — the largest of ``score +
router_bias``, the unbiased scores of the chosen renormalised and scaled
(``parallel/moe.sigmoid_topk_route``: DeepSeek-V3's ``noaux_tc`` with one
group) — beside one shared expert that every token takes at weight 1.
Bias-free, RMSNorm ``w · x / rms(x)``, untied head, no position table, no
auxiliary loss.

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is (the share of experts and vocabulary this device holds, the
protocol of a family with listed layers, what the families share).
Parameters are stacked by kind (``delta``, ``latent``: the mixers; ``dense``,
``moe``: the MLPs), layer ``i`` takes the next entry of its mixer's stack and of
its MLP's, and every mixer and every MLP is rebuilt in the backward pass on
its own — the latent layer keeping its kernel's output and row statistics, a
delta layer ``DELTA_KEPT`` of what ``gated_delta.CHANNEL_SAVED`` names: the
rule's triangular inverse and its output where the kernels of
``ops/kda_kernels.py`` run (a TPU, shapes that tile), so that a rebuilt layer
does not call ``kda_chunk_inverse`` again (``kda_scan_fwd`` it does: the
chunks' entering states are NOT kept, ``DELTA_KEPT`` says why); the output
alone where XLA's form runs (a block of heads at a time, each block rebuilt in
its own backward pass).
The three input projections are ONE matrix, columns ``[q | k | v]``, and the
three narrow ones another, ``[f↓ | g↓ | β]``: each of q, k, v is convolved from
its own columns (``ops/causal_conv.conv_silu``).  The untied head is laid out
as the embedding is, (vocabulary, model).  The plain reference is
``models/channel_delta_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from byteps_tpu.models import moe_family as mf
from byteps_tpu.models.moe_family import rms, swiglu
from byteps_tpu.ops.causal_conv import conv_silu, per_head
from byteps_tpu.ops.gated_delta import CHANNEL_SAVED, CHUNK, chunked_gated_delta_rule
from byteps_tpu.parallel.moe import sigmoid_topk_route

#: ``layer_types`` entry → the stack that holds that mixer's parameters
MIXERS = {"channel_delta": "delta", "latent_attention": "latent"}
#: what a rebuilt delta layer keeps of the rule: T (134 MB a layer at 32 heads
#: and 16 384 tokens, f32) and o (268 MB, f32).  The chunks' entering states
#: (268 MB in bf16) would save the rebuild's ``kda_scan_fwd`` (6.8 ms a layer),
#: but with them ``kimi_linear_ep32_train16k``'s step is 13.49 GiB by the
#: compiler's count and does not load beside the set-up's reference (12.61
#: without; PERF.md §6 PR 69)
DELTA_KEPT = (CHANNEL_SAVED[0], CHANNEL_SAVED[2])
#: the step sizes the decays start at: log-uniform between these (``_init``; the
#: published configuration has no key for them)
DT_MIN, DT_MAX = 1e-3, 0.1


@dataclasses.dataclass(frozen=True)
class ChannelDeltaMoEConfig(mf.PatternedFamily):
    vocab_size: int = 163840  # rows of the vocabulary held here
    d_model: int = 2304
    layer_types: Tuple[str, ...] = ("channel_delta",) * 3 + ("latent_attention",)
    n_dense_layers: int = 1  # the first so many layers' MLP is dense
    # the delta-rule layers
    lin_heads: int = 32
    lin_k_dim: int = 128
    lin_v_dim: int = 128
    gate_rank: int = 128  # the width the decay's and the output gate's projections pass through
    conv_kernel: int = 4
    chunk: int = CHUNK
    #: the layers whose count the residual branches' start is scaled by (every
    #: branch's last matrix ÷ √(2 · layers)): the whole model's, not the share's
    residual_layers: int = 27
    # the latent-attention layers
    n_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64  # the key all heads share; not turned (rope_theta None)
    v_head_dim: int = 128
    rope_theta: Optional[float] = None  # None: no positions
    # the MLPs
    d_ff: int = 9216  # the dense layers' SwiGLU
    d_expert: int = 1024  # every routed expert's
    d_shared: int = 1024  # the shared expert's
    n_experts: int = 256  # the router's width: the model's routed experts
    experts_held: int = 256  # of them, held here: [expert_lo, expert_lo + held)
    expert_lo: int = 0
    top_k: int = 8
    routed_scale: float = 2.446
    norm_eps: float = 1e-5
    max_seq: int = 16384
    compute_dtype: Any = jnp.float32
    remat: bool = True

    mixers = MIXERS
    family = "channel-delta"
    lacks = ("expert exchange, pipeline split, head sharding or hand-over of a rule's state "
             "and convolution tail between sequence shards")

    def __post_init__(self):
        super().__post_init__()
        if self.qk_rope_dim % 2:
            raise ValueError(f"the shared key needs an even qk_rope_dim, got {self.qk_rope_dim}")

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def lin_channels(self) -> int:
        """What the convolution runs over: q, k and v of the rule."""
        return self.lin_heads * (2 * self.lin_k_dim + self.lin_v_dim)


def tiny_channel_delta_moe(**kw) -> ChannelDeltaMoEConfig:
    """The CPU tests' preset: every kind of layer once (dense delta, expert
    delta, expert latent), toy widths, d_k ≠ d_v, two chunks of two sub-blocks
    a sequence."""
    base = dict(vocab_size=96, d_model=32,
                layer_types=("channel_delta", "channel_delta", "latent_attention"),
                n_dense_layers=1, lin_heads=2, lin_k_dim=8, lin_v_dim=6, gate_rank=4,
                chunk=8, residual_layers=3, n_heads=4, kv_lora_rank=16, qk_nope_dim=8,
                qk_rope_dim=4, v_head_dim=8, d_ff=48, d_expert=16, d_shared=12, n_experts=8,
                experts_held=8, top_k=2, max_seq=16)
    base.update(kw)
    return ChannelDeltaMoEConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; ``<stack>.<name>`` carries the stack's layers as
# leading dim, in the order the layers come
# ---------------------------------------------------------------------------


def stacks(cfg: ChannelDeltaMoEConfig) -> Dict[str, Tuple[int, Dict[str, tuple]]]:
    """stack name → (layers, per-layer shapes), the stacks some layer reads."""
    d, h, dk, dv, r = cfg.d_model, cfg.lin_heads, cfg.lin_k_dim, cfg.lin_v_dim, cfg.gate_rank
    n, f, fe, fs, e = cfg.n_heads, cfg.d_ff, cfg.d_expert, cfg.d_shared, cfg.experts_held
    shapes = {
        "delta": {"norm": (d,), "w_qkv": (d, cfg.lin_channels), "w_fgb": (d, 2 * r + h),
                  "conv": (cfg.conv_kernel, cfg.lin_channels),
                  "w_f": (r, h * dk), "w_g": (r, h * dv),
                  "a_log": (h,), "dt_bias": (h * dk,), "o_norm": (dv,), "w_out": (h * dv, d)},
        "latent": {"attn_norm": (d,), "wq": (d, n, cfg.qk_dim),
                   "wkv_a": (d, cfg.kv_lora_rank + cfg.qk_rope_dim),
                   "kv_norm": (cfg.kv_lora_rank,),
                   "wkv_b": (cfg.kv_lora_rank, n, cfg.qk_nope_dim + cfg.v_head_dim),
                   "wo": (n, cfg.v_head_dim, d)},
        "dense": {"norm": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)},
        # router_bias is e_score_correction_bias: it picks, and takes no gradient
        "moe": {"norm": (d,), "router": (d, cfg.n_experts), "router_bias": (cfg.n_experts,),
                "e_gate": (e, d, fe), "e_up": (e, d, fe), "e_down": (e, fe, d),
                "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d)},
    }
    return cfg.stack_sizes(shapes)


def layouts(cfg: ChannelDeltaMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``).  ``head`` is laid out as the
    embedding is, (vocabulary, model)."""
    v, d = cfg.vocab_size, cfg.d_model
    return mf.layouts({"embed": (v, d), "norm_f": (d,), "head": (v, d)}, stacks(cfg))


def _init(cfg: ChannelDeltaMoEConfig) -> Dict[str, Any]:
    """How the leaves start, beside ``moe_family.INIT_RULES`` (N(0, 1 / fan-in)
    matrices): ones for the norms' scales; ``A_log = log U(1, 16)`` a head;
    ``dt_bias`` a channel the inverse softplus of a step log-uniform in
    [DT_MIN, DT_MAX], so that a token's decay starts between e^-1.6 and
    e^-0.001 a channel; N(0, 1/kernel) convolution taps; zero selection bias
    (where training starts); the embedding at N(0, 1) and **every residual
    branch's last matrix ÷ √(2 · residual_layers)** (``w_out``, ``wo``, the
    MLPs' down projections: the scaling of a pre-norm stack's branches by its
    depth), so that a token's own row leads the stream.  Why: q, k and v come
    out of a silu and are positive on average, so a delta mixer's output has a
    part that all tokens share, which the per-head norm brings to unit size;
    at unit-variance branches the seeded routers see it as an offset an expert
    and the fullest of the 8 held experts takes 30–32 % of the held slots
    (on the chip at 16 384 tokens and on the CPU at 2048, PERF.md §6 PR 68);
    at this scale 17–18 % (CPU, 2048 tokens, two seeds; 12.5 is even, and
    chance alone reads ≈ 14 there): the near-uniform router a deployment's
    balanced one stands for (``ssm_moe._init`` met the same with relu²)."""
    def dt_bias(key, shape):
        lo, hi = math.log(DT_MIN), math.log(DT_MAX)
        dt = jnp.exp(jax.random.uniform(key(), shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))

    def branch_end(*dims):
        rule = mf.fan_in(*dims)
        return lambda key, shape: rule(key, shape) / math.sqrt(2 * cfg.residual_layers)

    return {"*norm*": mf.ones, "embed": mf.normal(1.0), "head": mf.fan_in(-1),
            "router_bias": mf.zeros, "a_log": mf.log_uniform(1.0, 16.0), "dt_bias": dt_bias,
            "wkv_a": mf.fan_in(-2), "wkv_b": mf.fan_in(-3), "wo": branch_end(-3, -2),
            **dict.fromkeys(("w_qkv", "w_fgb", "w_f", "w_g", "conv"), mf.fan_in(-2)),
            **dict.fromkeys(("w_out", "w_down", "e_down", "s_down"), branch_end(-2))}


def init_params(cfg: ChannelDeltaMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :func:`_init`."""
    return mf.init_params(layouts(cfg), key, _init(cfg))


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _delta_scan(cfg: ChannelDeltaMoEConfig, qkv, decay_in, gate_in, beta_in, lp):
    """The delta mixer between its projections: ``qkv`` (B, S, channels) in
    the compute dtype, ``decay_in`` (B, S, H·d_k), ``gate_in`` (B, S, H·d_v)
    and ``beta_in`` (B, S, H) f32 → what ``w_out`` takes, (B, S, H·d_v) in the
    compute dtype.  Token-major from end to end: a head is a run of lanes,
    the rule's (B, S, H, d) a reshape that its own undoes, the statistics a
    head taken on the tiles' view (``causal_conv.per_head``)."""
    cdt = cfg.compute_dtype
    h, dk, dv = cfg.lin_heads, cfg.lin_k_dim, cfg.lin_v_dim
    b, s, _ = qkv.shape

    def convolved(lo, hi, **norm):
        return conv_silu(qkv, lp["conv"][:, lo:hi], lo=lo, hi=hi, **norm)

    q = convolved(0, h * dk, l2_head=dk, scale=dk ** -0.5)
    k = convolved(h * dk, 2 * h * dk, l2_head=dk)
    v = convolved(2 * h * dk, cfg.lin_channels)
    # the log of the decay, ≤ 0: a head's rate on a channel's softplus
    g = -jnp.repeat(jnp.exp(lp["a_log"]), dk) * jax.nn.softplus(decay_in + lp["dt_bias"])
    o = chunked_gated_delta_rule(
        q.reshape(b, s, h, dk), k.reshape(b, s, h, dk), v.reshape(b, s, h, dv),
        g.reshape(b, s, h, dk), jax.nn.sigmoid(beta_in), chunk=cfg.chunk,
        compute_dtype=cdt).reshape(b, s, h * dv)  # f32
    # the gated norm: over each head's values, one scale for all heads
    o = jnp.tile(lp["o_norm"], h) * o * per_head(o, h, lambda head: lax.rsqrt(
        jnp.mean(jnp.square(head), axis=-1, keepdims=True) + cfg.norm_eps))
    return (o * jax.nn.sigmoid(gate_in)).astype(cdt)


def _delta_mixer(cfg: ChannelDeltaMoEConfig, x, lp):
    """x (B, S, D) → Kimi Delta Attention's output (B, S, D), compute dtype."""
    cdt, f32, r = cfg.compute_dtype, jnp.float32, cfg.gate_rank
    with jax.named_scope("kda_proj"):
        a = rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        qkv = a @ lp["w_qkv"].astype(cdt)
        fgb = a @ lp["w_fgb"].astype(cdt)
        decay_in = jnp.dot(fgb[..., :r], lp["w_f"].astype(cdt), preferred_element_type=f32)
        gate_in = jnp.dot(fgb[..., r:2 * r], lp["w_g"].astype(cdt), preferred_element_type=f32)
        beta_in = fgb[..., 2 * r:].astype(f32)
    with jax.named_scope("kda_scan"):
        o = _delta_scan(cfg, qkv, decay_in, gate_in, beta_in, lp)
    with jax.named_scope("kda_proj"):
        return o @ lp["w_out"].astype(cdt)


def _dense_mlp(cfg: ChannelDeltaMoEConfig, x, lp):
    """x (B, S, D) → the dense SwiGLU of its norm (B, S, D), compute dtype."""
    cdt = cfg.compute_dtype
    with jax.named_scope("dense_mlp"):
        g = rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        return swiglu(g, *(lp[w].astype(cdt) for w in ("w_gate", "w_up", "w_down")))


def expert_mlp(cfg: ChannelDeltaMoEConfig, g32, lp):
    """An expert layer's MLP on normed tokens ``g32`` (T, D) f32: the held
    experts' routed part plus the shared expert.  Returns (y (T, D) f32,
    routing stats)."""
    def route(g32, lp):
        return sigmoid_topk_route(
            g32, lp["router"], lp["router_bias"], cfg.top_k, cfg.routed_scale)

    # cast once, before the router: both kinds of expert read this copy
    return mf.routed_mlp(cfg, g32, g32.astype(cfg.compute_dtype), lp, route, "moe_shared")


def _moe_mlp(cfg: ChannelDeltaMoEConfig, x, lp):
    b, s, d = x.shape
    g32 = rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
    y, stats = expert_mlp(cfg, g32, lp)
    return x + y.reshape(b, s, d).astype(x.dtype), stats


def _hidden(cfg: ChannelDeltaMoEConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm, and the
    routing stats summed over the layers."""
    def residual(part):
        return lambda x, lp: x + part(cfg, x, lp).astype(x.dtype)

    run = {"delta": residual(_delta_mixer),
           "latent": lambda x, lp: mf.latent_attention(
               cfg, x, lp, "nope_latent_attention", cfg.rope_theta),
           "dense": residual(_dense_mlp), "moe": lambda x, lp: _moe_mlp(cfg, x, lp)}
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.compute_dtype)
    return mf.walk(cfg, run, {"latent": mf.FLASH_SAVED, "delta": DELTA_KEPT}, params, x)


def local_logits(cfg: ChannelDeltaMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    x, _ = _hidden(cfg, params, tokens)
    return mf.row_logits(cfg, x, params["norm_f"], params["head"])


def local_loss(cfg: ChannelDeltaMoEConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (ROUTING_STATS name → int32) summed over the
    data-parallel ranks."""
    x, stats = _hidden(cfg, params, tokens)
    return mf.mean_loss(
        *mf.xent_sums(cfg, mf.row_logits, x, targets, params["norm_f"], params["head"]), stats)
