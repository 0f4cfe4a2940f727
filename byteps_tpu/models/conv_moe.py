"""Short-convolution / grouped-query-attention mixture-of-experts family
(LFM2-MoE's block, as LFM2-24B-A2B publishes it) — the layers behind
``build_train_step``.

A layer is ``h ← h + mixer(norm(h))`` then ``h ← h + mlp(norm(h))``, and both
kinds are given layer by layer as static data.  ``layer_types[i]`` names the
mixer: ``"conv"`` is LFM2's double-gated short convolution (one projection
split into B, C, x; ``C ⊙ conv(B ⊙ x)`` with a depthwise causal convolution
of ``conv_kernel`` taps, no activation, no state beyond the taps; an output
projection), ``"full_attention"`` is causal softmax attention with grouped
key/value heads, a per-head RMSNorm on q and k, rotary embedding over the
whole head, no gate (``ops/flash_attention.py``).  The first
``n_dense_layers`` layers have a dense SwiGLU MLP; the others ``top_k`` of
``n_experts`` sigmoid-routed experts with a selection bias that picks and
does not weigh, and no shared expert.  Bias-free, RMSNorm ``w · x / rms(x)``,
the head tied to the embedding, no position table.

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is (the share of experts and vocabulary this device holds, the
protocol of a family with listed layers, what the families share).
Parameters are stacked by kind (``conv``, ``attn``: the mixers; ``dense``,
``moe``: the MLPs), and layer ``i`` takes the next entry of its mixer's stack
and of its MLP's; every mixer and every MLP is rebuilt in the backward pass on
its own.  The plain reference is ``models/conv_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from byteps_tpu.models import moe_family as mf
from byteps_tpu.models.moe_family import causal_conv, rms, rope_partial, swiglu
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.parallel.moe import sigmoid_topk_route

#: ``layer_types`` entry → the stack that holds that mixer's parameters
MIXERS = {"conv": "conv", "full_attention": "attn"}


@dataclasses.dataclass(frozen=True)
class ConvMoEConfig(mf.PatternedFamily):
    vocab_size: int = 65536  # rows of the vocabulary held here
    d_model: int = 2048
    layer_types: Tuple[str, ...] = ("conv", "conv", "full_attention", "conv")
    n_dense_layers: int = 2  # the first so many layers' MLP is dense
    # the attention layers
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    # the short-convolution layers
    conv_kernel: int = 3
    # the MLPs
    d_ff: int = 11776  # the dense layers' SwiGLU
    d_expert: int = 1536  # every expert's
    n_experts: int = 64  # the router's width: the model's routed experts
    experts_held: int = 64  # of them, held here: [expert_lo, expert_lo + held)
    expert_lo: int = 0
    top_k: int = 4
    routed_scale: float = 1.0
    route_eps: float = 1e-6  # beside the chosen scores' sum, as the published code has it
    norm_eps: float = 1e-5
    max_seq: int = 8192
    compute_dtype: Any = jnp.float32
    remat: bool = True

    mixers = MIXERS
    family = "short-convolution"
    lacks = ("expert exchange, pipeline split, head sharding or hand-over of the "
             "convolution's last tokens between sequence shards")

    def __post_init__(self):
        super().__post_init__()
        self._check_grouped_heads()
        self._check_even_rope("head_dim")


def tiny_conv_moe(**kw) -> ConvMoEConfig:
    """The CPU tests' preset: every mechanism, toy widths, one leading dense
    layer, both mixers under both MLPs, four query heads a key/value head pair."""
    base = dict(vocab_size=96, d_model=32,
                layer_types=("conv", "full_attention", "conv", "conv"), n_dense_layers=1,
                n_heads=4, n_kv_heads=2, head_dim=8, d_ff=48, d_expert=16,
                n_experts=8, experts_held=8, top_k=2, max_seq=16)
    base.update(kw)
    return ConvMoEConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; ``<stack>.<name>`` carries the stack's layers as
# leading dim, in the order the layers come
# ---------------------------------------------------------------------------


def stacks(cfg: ConvMoEConfig) -> Dict[str, Tuple[int, Dict[str, tuple]]]:
    """stack name → (layers, per-layer shapes), the stacks some layer reads."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f, fe, e = cfg.d_ff, cfg.d_expert, cfg.experts_held
    shapes = {
        "conv": {"norm": (d,), "w_in": (d, 3 * d), "taps": (cfg.conv_kernel, d),
                 "w_out": (d, d)},
        "attn": {"norm": (d,), "wq": (d, h, hd), "wk": (d, kv, hd), "wv": (d, kv, hd),
                 "q_norm": (hd,), "k_norm": (hd,), "wo": (h, hd, d)},
        "dense": {"norm": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)},
        # router_bias is the published expert_bias: it picks, and takes no gradient
        "moe": {"norm": (d,), "router": (d, cfg.n_experts), "router_bias": (cfg.n_experts,),
                "e_gate": (e, d, fe), "e_up": (e, d, fe), "e_down": (e, fe, d)},
    }
    return cfg.stack_sizes(shapes)


def layouts(cfg: ConvMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``).  There is no ``head``: the logits are
    taken with ``embed``."""
    return mf.layouts({"embed": (cfg.vocab_size, cfg.d_model), "norm_f": (cfg.d_model,)},
                      stacks(cfg))


#: how the leaves start, beside ``moe_family.INIT_RULES``: ones for the norms'
#: scales, N(0, 1/kernel) convolution taps, N(0, 0.01²) for the selection bias
#: (a trained balance's size: zeros would hide a bias that weighs)
INIT = {"*norm*": mf.ones, "router_bias": mf.normal(0.01),
        **dict.fromkeys(("w_in", "taps", "w_out"), mf.fan_in(-2))}


def init_params(cfg: ConvMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :data:`INIT`."""
    return mf.init_params(layouts(cfg), key, INIT)


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _conv_mixer(cfg: ConvMoEConfig, x, lp):
    """x (B, S, D) → the double-gated short convolution's output (B, S, D),
    compute dtype."""
    cdt = cfg.compute_dtype
    with jax.named_scope("conv_proj"):
        g = rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        bcx = g @ lp["w_in"].astype(cdt)
    with jax.named_scope("short_conv"):
        b_gate, c_gate, inner = jnp.split(bcx, 3, axis=-1)
        # the taps' products and their sum in f32, and the second gate on them
        conv = causal_conv(b_gate * inner, lp["taps"])
        y = (c_gate.astype(jnp.float32) * conv).astype(cdt)
    with jax.named_scope("conv_proj"):
        return y @ lp["w_out"].astype(cdt)


def _attention_mixer(cfg: ConvMoEConfig, x, lp):
    """x (B, S, D) → grouped-query softmax attention's output (B, S, D),
    compute dtype."""
    cdt, hd = cfg.compute_dtype, cfg.head_dim
    with jax.named_scope("gqa_attention"):
        g = rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        q, k, v = (jnp.einsum("bsd,dhk->bhsk", g, lp[w].astype(cdt)) for w in ("wq", "wk", "wv"))
        q = rope_partial(rms(q, lp["q_norm"], cfg.norm_eps).astype(cdt), hd, cfg.rope_theta)
        k = rope_partial(rms(k, lp["k_norm"], cfg.norm_eps).astype(cdt), hd, cfg.rope_theta)
        # the kernels take equal head counts: a key/value head is repeated for
        # its group of queries (their gradients add up by the repeat's transpose)
        group = cfg.n_heads // cfg.n_kv_heads
        o = flash_attention(q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
                            causal=True, scale=hd ** -0.5)
        return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(cdt))


def _dense_mlp(cfg: ConvMoEConfig, x, lp):
    """x (B, S, D) → the dense SwiGLU of its norm (B, S, D), compute dtype."""
    cdt = cfg.compute_dtype
    with jax.named_scope("dense_mlp"):
        g = rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        return swiglu(g, *(lp[w].astype(cdt) for w in ("w_gate", "w_up", "w_down")))


def expert_mlp(cfg: ConvMoEConfig, g32, lp):
    """An expert layer's MLP on normed tokens ``g32`` (T, D) f32: the held
    experts' routed part, and nothing else (the model has no shared expert).
    Returns (y (T, D) f32, routing stats)."""
    def route(g32, lp):
        return sigmoid_topk_route(g32, lp["router"], lp["router_bias"], cfg.top_k,
                                  cfg.routed_scale, eps=cfg.route_eps)

    return mf.routed_mlp(cfg, g32, g32, lp, route)  # cast where the experts read


def _moe_mlp(cfg: ConvMoEConfig, x, lp):
    b, s, d = x.shape
    g32 = rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
    y, stats = expert_mlp(cfg, g32, lp)
    return x + y.reshape(b, s, d).astype(x.dtype), stats


def _hidden(cfg: ConvMoEConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm, and the
    routing stats summed over the layers."""
    def residual(mixer):
        return lambda x, lp: x + mixer(cfg, x, lp).astype(x.dtype)

    run = {"conv": residual(_conv_mixer), "attn": residual(_attention_mixer),
           "dense": residual(_dense_mlp), "moe": lambda x, lp: _moe_mlp(cfg, x, lp)}
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.compute_dtype)
    return mf.walk(cfg, run, {"attn": mf.FLASH_SAVED}, params, x)


def local_logits(cfg: ConvMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows; the head is the
    embedding."""
    x, _ = _hidden(cfg, params, tokens)
    return mf.row_logits(cfg, x, params["norm_f"], params["embed"])


def local_loss(cfg: ConvMoEConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (ROUTING_STATS name → int32) summed over the
    data-parallel ranks."""
    x, stats = _hidden(cfg, params, tokens)
    return mf.mean_loss(
        *mf.xent_sums(cfg, mf.row_logits, x, targets, params["norm_f"], params["embed"]), stats)
