"""Gated-delta-rule / gated-attention mixture-of-experts family (Qwen3-Next's
block) — the layers behind ``build_train_step``.

Layers come in periods of ``full_attention_interval``: all but the last of a
period mix tokens with the gated delta rule (linear attention: a short causal
depthwise convolution, then a d_k × d_v state a head carried along the
sequence with a data-dependent decay, ``ops/gated_delta.py``), the last with
gated softmax attention (grouped key/value heads, a per-head RMSNorm on q and
k, rotary embedding on the first part of each head, a sigmoid gate on the
output taken from the query projection; ``ops/flash_attention.py``).  Every
layer's MLP is ``top_k`` of ``n_experts`` softmax-routed experts plus one
shared expert behind a sigmoid gate.  Bias-free, RMSNorm with a ``1 + w``
scale, untied head, no position table.

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is (the share of experts and vocabulary this device holds, the
protocol, what the families share).  The stack is one ``lax.scan`` over the
periods — the program does not grow with the depth — and a period's layers
are unrolled inside it: every mixer and every MLP is rebuilt in the backward
pass but for what a mixer's kernels wrote (``_layer_parts``), and an array a
``lax.scan``'s body keeps for its backward pass is copied into the scan's
stack and out again (2.4 ms each way for a linear layer's 256 MiB on a v5e:
PERF.md §6, PRs 58 and 60), so nothing a linear layer keeps crosses an inner
stack.  It crosses the outer one where a stage holds several periods (one
turn is no loop to XLA).  The plain reference is
``models/delta_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from byteps_tpu.models import moe_family as mf
from byteps_tpu.models.moe_family import rope_partial
from byteps_tpu.ops import gated_delta_kernels
from byteps_tpu.ops.causal_conv import conv_silu, per_head
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.gated_delta import CHUNK, chunked_gated_delta_rule
from byteps_tpu.parallel.moe import ROUTING_STATS, softmax_topk_route


@dataclasses.dataclass(frozen=True)
class DeltaMoEConfig(mf.ExpertFamily):
    vocab_size: int = 151936  # rows of the vocabulary held here
    d_model: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4  # layer i is full attention where (i + 1) % this == 0
    # the gated full-attention layers
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64  # head_dim · partial_rotary_factor
    rope_theta: float = 1e7
    # the gated-delta-rule layers
    lin_k_heads: int = 16
    lin_v_heads: int = 32
    lin_k_dim: int = 128
    lin_v_dim: int = 128
    conv_kernel: int = 4
    chunk: int = CHUNK
    # the experts
    d_expert: int = 512
    d_shared: int = 512
    n_experts: int = 512  # the router's width: the model's routed experts
    experts_held: int = 512  # of them, held here: [expert_lo, expert_lo + held)
    expert_lo: int = 0
    top_k: int = 10
    norm_eps: float = 1e-6
    max_seq: int = 16384
    compute_dtype: Any = jnp.float32
    remat: bool = True

    family = "gated-delta"
    lacks = ("expert exchange, pipeline split, head sharding or state hand-over between "
             "sequence shards")

    def __post_init__(self):
        if self.n_layers % self.full_attention_interval:
            raise ValueError(f"{self.n_layers} layers are no whole number of periods of "
                             f"{self.full_attention_interval}")
        super().__post_init__()
        self._check_grouped_heads()
        if self.lin_v_heads % self.lin_k_heads:
            raise ValueError("the rule's value heads must be a multiple of its key heads")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(
                f"rope needs an even rotary_dim within the head, got {self.rotary_dim}")

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.full_attention_interval

    @property
    def lin_channels(self) -> int:
        """What the convolution runs over: q, k and v of the rule."""
        return 2 * self.lin_k_heads * self.lin_k_dim + self.lin_v_heads * self.lin_v_dim


def tiny_delta_moe(**kw) -> DeltaMoEConfig:
    """The CPU tests' preset: every mechanism, toy widths, two periods,
    H_k != H_v, a rotary part smaller than the head."""
    base = dict(vocab_size=96, d_model=32, n_layers=4, full_attention_interval=2,
                n_heads=4, n_kv_heads=2, head_dim=8, rotary_dim=4,
                lin_k_heads=2, lin_v_heads=4, lin_k_dim=8, lin_v_dim=6, chunk=8,
                d_expert=16, d_shared=12, n_experts=8, experts_held=8, top_k=3, max_seq=16)
    base.update(kw)
    return DeltaMoEConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict.  ``lin.<name>`` carries (periods, linear layers a
# period) as leading dims, ``full.<name>`` (periods,)
# ---------------------------------------------------------------------------


def _mlp_shapes(cfg: DeltaMoEConfig) -> Dict[str, tuple]:
    d, f, fs, e = cfg.d_model, cfg.d_expert, cfg.d_shared, cfg.experts_held
    return {
        "mixer_norm": (d,), "mlp_norm": (d,),
        "router": (d, cfg.n_experts),
        "e_gate": (e, d, f), "e_up": (e, d, f), "e_down": (e, f, d),
        "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d),
        "shared_gate": (d,),
    }


def layer_shapes(cfg: DeltaMoEConfig) -> Dict[str, Dict[str, tuple]]:
    """kind (``lin``, ``full``) → per-layer shapes."""
    d, hv, dv = cfg.d_model, cfg.lin_v_heads, cfg.lin_v_dim
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "lin": {
            "w_qkvz": (d, cfg.lin_channels + hv * dv), "w_ba": (d, 2 * hv),
            "conv": (cfg.conv_kernel, cfg.lin_channels),
            "a_log": (hv,), "dt_bias": (hv,), "gdn_norm": (dv,),
            "w_out": (hv * dv, d), **_mlp_shapes(cfg)},
        "full": {
            "wq": (d, h, 2 * hd), "wk": (d, kv, hd), "wv": (d, kv, hd),
            "q_norm": (hd,), "k_norm": (hd,), "wo": (h, hd, d), **_mlp_shapes(cfg)},
    }


def layouts(cfg: DeltaMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``)."""
    d, v = cfg.d_model, cfg.vocab_size
    lead = {"lin": (cfg.n_periods, cfg.full_attention_interval - 1), "full": (cfg.n_periods,)}
    return mf.layouts({"embed": (v, d), "norm_f": (d,), "head": (d, v)},
                      {kind: (lead[kind], per_layer)
                       for kind, per_layer in layer_shapes(cfg).items() if math.prod(lead[kind])})


#: how the leaves start, beside ``moe_family.INIT_RULES``: 0 for the RMSNorms'
#: ``1 + w`` scales and 1 for the gated norm's, ``A_log = log U(0, 16)`` and
#: ``dt_bias = 1``, N(0, 1/kernel) convolution taps
INIT = {"gdn_norm": mf.ones, "dt_bias": mf.ones, "*norm*": mf.zeros,
        "a_log": mf.log_uniform(1e-3, 16.0), "shared_gate": mf.fan_in(-1),
        **dict.fromkeys(("w_qkvz", "w_ba", "conv", "w_out"), mf.fan_in(-2))}


def init_params(cfg: DeltaMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :data:`INIT`."""
    return mf.init_params(layouts(cfg), key, INIT)


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


#: RMSNorm with a ``1 + w`` scale and f32 statistics; returns f32
_rms = functools.partial(mf.rms, plus_one=True)


def _delta_scan(cfg: DeltaMoEConfig, qkvz, ba, lp):
    """The linear mixer between its projections: ``qkvz`` (B, S, channels +
    H_v·d_v) in the compute dtype and ``ba`` (B, S, 2·H_v) f32 → what
    ``w_out`` takes, (B, S, H_v·d_v) in the compute dtype.  Token-major from
    end to end: a head is a run of lanes, the rule's (B, S, n, d) a reshape
    that its own undoes, and the statistics a head are taken on the tiles'
    view (``causal_conv.per_head``); each of q, k, v is convolved from its own
    columns of ``qkvz`` (``ops/causal_conv.conv_silu``: convolution, silu and
    a key head's l2 norm in one pass, rounded once), so that nothing computed
    is split afterwards."""
    cdt, f32 = cfg.compute_dtype, jnp.float32
    hk, hv, dk, dv = cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim
    b, s, _ = qkvz.shape

    def convolved(lo, hi, **norm):
        return conv_silu(qkvz, lp["conv"][:, lo:hi], lo=lo, hi=hi, **norm)

    q = convolved(0, hk * dk, l2_head=dk, scale=dk ** -0.5)
    k = convolved(hk * dk, 2 * hk * dk, l2_head=dk)
    v = convolved(2 * hk * dk, cfg.lin_channels)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(ba[..., hv:] + lp["dt_bias"])
    # each key head serves lin_v_heads / lin_k_heads value heads
    o = chunked_gated_delta_rule(
        q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk), v.reshape(b, s, hv, dv), g, beta,
        chunk=cfg.chunk, compute_dtype=cdt).reshape(b, s, hv * dv)  # f32
    # the gated norm: over each head's values, one scale for all heads
    o = jnp.tile(lp["gdn_norm"], hv) * o * per_head(o, hv, lambda head: lax.rsqrt(
        jnp.mean(jnp.square(head), axis=-1, keepdims=True) + cfg.norm_eps))
    return (o * jax.nn.silu(qkvz[..., cfg.lin_channels:].astype(f32))).astype(cdt)


def _delta_mixer(cfg: DeltaMoEConfig, x, lp):
    """x (B, S, D) → the gated delta rule's output (B, S, D), compute dtype."""
    cdt = cfg.compute_dtype
    with jax.named_scope("gdn_proj"):
        h = _rms(x, lp["mixer_norm"], cfg.norm_eps).astype(cdt)
        qkvz = h @ lp["w_qkvz"].astype(cdt)
        ba = (h @ lp["w_ba"].astype(cdt)).astype(jnp.float32)
    with jax.named_scope("gdn_scan"):
        o = _delta_scan(cfg, qkvz, ba, lp)
    with jax.named_scope("gdn_proj"):
        return o @ lp["w_out"].astype(cdt)


def _attention_mixer(cfg: DeltaMoEConfig, x, lp):
    """x (B, S, D) → gated softmax attention's output (B, S, D), compute dtype."""
    cdt, hd = cfg.compute_dtype, cfg.head_dim
    with jax.named_scope("gated_attention"):
        h = _rms(x, lp["mixer_norm"], cfg.norm_eps).astype(cdt)
        q_gate = jnp.einsum("bsd,dhk->bhsk", h, lp["wq"].astype(cdt))
        q, gate = q_gate[..., :hd], q_gate[..., hd:]
        k = jnp.einsum("bsd,dhk->bhsk", h, lp["wk"].astype(cdt))
        v = jnp.einsum("bsd,dhk->bhsk", h, lp["wv"].astype(cdt))
        q = rope_partial(_rms(q, lp["q_norm"], cfg.norm_eps).astype(cdt),
                         cfg.rotary_dim, cfg.rope_theta)
        k = rope_partial(_rms(k, lp["k_norm"], cfg.norm_eps).astype(cdt),
                         cfg.rotary_dim, cfg.rope_theta)
        # the kernels take equal head counts: a key/value head is repeated for
        # its group of queries (their gradients add up by the repeat's transpose)
        group = cfg.n_heads // cfg.n_kv_heads
        o = flash_attention(q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
                            causal=True, scale=hd ** -0.5)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(cdt)
        return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(cdt))


def expert_mlp(cfg: DeltaMoEConfig, g32, lp):
    """A layer's MLP on normed tokens ``g32`` (T, D) f32: the held experts'
    routed part plus the gated shared expert.  Returns (y (T, D) f32,
    routing stats)."""
    def route(g32, lp):
        return softmax_topk_route(g32, lp["router"], cfg.top_k)

    # cast once, before the router: both kinds of expert and the gate read this copy
    return mf.routed_mlp(cfg, g32, g32.astype(cfg.compute_dtype), lp, route, "moe_shared")


def _mlp(cfg: DeltaMoEConfig, x, lp):
    b, s, d = x.shape
    g32 = _rms(x, lp["mlp_norm"], cfg.norm_eps).reshape(b * s, d)
    y, stats = expert_mlp(cfg, g32, lp)
    return x + y.reshape(b, s, d).astype(x.dtype), stats


def _layer_parts(cfg: DeltaMoEConfig):
    """(the linear mixer, the attention mixer, the MLP) as a layer runs them:
    the mixers with their residual, ``(x, lp) → x``; the MLP → (x, stats)."""
    def residual(mixer):
        return lambda x, lp: x + mixer(cfg, x, lp).astype(x.dtype)

    delta, attention = residual(_delta_mixer), residual(_attention_mixer)
    mlp = lambda x, lp: _mlp(cfg, x, lp)  # noqa: E731
    if cfg.remat:
        # a layer's mixer and its MLP are each rebuilt in the backward pass,
        # one at a time (a period's four layers at once do not fit beside the
        # state at 16k tokens); of either mixer all but what its costliest
        # kernel wrote, so that it does not run twice: gated attention's
        # output and row statistics, the delta rule's triangular inverse
        # (where the rule takes XLA's form nothing carries that name, and all
        # of the mixer is rebuilt)
        mlp = jax.checkpoint(mlp)
        delta = jax.checkpoint(
            delta, policy=jax.checkpoint_policies.save_only_these_names(
                *gated_delta_kernels.SAVED))
        attention = jax.checkpoint(attention, policy=mf.keep_flash())
    return delta, attention, mlp


def _hidden(cfg: DeltaMoEConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm, and the
    routing stats summed over the layers."""
    delta, attention, mlp = _layer_parts(cfg)

    def period(x, lps):
        stats = jnp.zeros((len(ROUTING_STATS),), jnp.int32)
        # the linear layers unrolled, each on its slice of the stacked leaves:
        # what a layer keeps for its backward pass would be copied into a
        # scan's stack and out again (module docstring)
        for i in range(cfg.full_attention_interval - 1):
            lp = {name: leaf[i] for name, leaf in lps["lin"].items()}
            x, each = mlp(delta(x, lp), lp)
            stats = stats + each
        x, last = mlp(attention(x, lps["full"]), lps["full"])
        return x, stats + last

    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.compute_dtype)
    x, stats = lax.scan(period, x, {kind: mf.stack_of(params, kind) for kind in ("lin", "full")})
    return x, jnp.sum(stats, 0)


def _logits(cfg: DeltaMoEConfig, x, scale, head):
    h = _rms(x, scale, cfg.norm_eps).astype(cfg.compute_dtype)
    return jnp.dot(h, head.astype(cfg.compute_dtype), preferred_element_type=jnp.float32)


def local_logits(cfg: DeltaMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    x, _ = _hidden(cfg, params, tokens)
    return _logits(cfg, x, params["norm_f"], params["head"])


def local_loss(cfg: DeltaMoEConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (ROUTING_STATS name → int32) summed over the
    data-parallel ranks."""
    x, stats = _hidden(cfg, params, tokens)
    return mf.mean_loss(
        *mf.xent_sums(cfg, _logits, x, targets, params["norm_f"], params["head"]), stats)
