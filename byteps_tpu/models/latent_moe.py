"""Latent-attention mixture-of-experts family (DeepSeek-V3's block, as
JoyAI-LLM-Flash publishes it) — the layers behind ``build_train_step``.

One model, two kinds of layer: ``n_dense_layers`` leading layers with a dense
SwiGLU MLP, then ``n_expert_layers`` whose MLP is ``top_k`` of ``n_experts``
sigmoid-routed experts plus one shared expert; every layer's attention is
multi-head latent attention (queries and keys/values through low-rank
bottlenecks, a 64-wide rotary key shared by all heads, 192-wide q·k and
128-wide v).  One multi-token-prediction module (depth 1) reuses the
embedding and the head for a second loss.  Bias-free, RMSNorm, untied head,
no position table.

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is (the share of experts and vocabulary this device holds, the
protocol, what the families share).  Each stack is one remat'ed ``lax.scan``.
The plain reference is ``models/latent_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from byteps_tpu.models import moe_family as mf
from byteps_tpu.models.moe_family import rms, swiglu
from byteps_tpu.ops.mla_heads import even_first
from byteps_tpu.parallel.moe import ROUTING_STATS, sigmoid_topk_route


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig(mf.ExpertFamily):
    vocab_size: int = 129280  # rows of the vocabulary held here
    d_model: int = 2048
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 7168  # the dense layers' SwiGLU
    d_expert: int = 768  # every expert's SwiGLU, the shared one's too
    n_dense_layers: int = 1
    n_expert_layers: int = 39
    n_experts: int = 256  # the router's width: the model's routed experts
    experts_held: int = 256  # of them, held here: [expert_lo, expert_lo + held)
    expert_lo: int = 0
    top_k: int = 8
    routed_scale: float = 2.5
    mtp_modules: int = 1  # multi-token-prediction depth: 0 or 1
    mtp_lambda: float = 0.3
    rope_theta: float = 32e6
    norm_eps: float = 1e-6
    max_seq: int = 8192
    compute_dtype: Any = jnp.float32
    remat: bool = True

    family = "latent-attention"
    lacks = "expert exchange, pipeline split or head sharding"

    def __post_init__(self):
        if self.mtp_modules not in (0, 1):
            raise ValueError(f"mtp_modules {self.mtp_modules}: depth 0 or 1 is built")
        super().__post_init__()
        self._check_even_rope("qk_rope_dim")

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


def tiny_latent_moe(**kw) -> LatentMoEConfig:
    """The CPU tests' preset: every mechanism, toy widths."""
    base = dict(vocab_size=96, d_model=32, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
                qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, d_ff=64, d_expert=16,
                n_dense_layers=1, n_expert_layers=2, n_experts=8, experts_held=8,
                top_k=2, max_seq=16)
    base.update(kw)
    return LatentMoEConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; a stack's entries carry its layers as leading dim
# ---------------------------------------------------------------------------


def _attention_shapes(cfg: LatentMoEConfig) -> Dict[str, tuple]:
    d, h = cfg.d_model, cfg.n_heads
    return {
        "attn_norm": (d,),
        "wq_a": (d, cfg.q_lora_rank),
        "q_norm": (cfg.q_lora_rank,),
        "wq_b": (cfg.q_lora_rank, h, cfg.qk_dim),
        "wkv_a": (d, cfg.kv_lora_rank + cfg.qk_rope_dim),
        "kv_norm": (cfg.kv_lora_rank,),
        "wkv_b": (cfg.kv_lora_rank, h, cfg.qk_nope_dim + cfg.v_head_dim),
        "wo": (h, cfg.v_head_dim, d),
        "mlp_norm": (d,),
    }


def _expert_layer_shapes(cfg: LatentMoEConfig) -> Dict[str, tuple]:
    d, f, e = cfg.d_model, cfg.d_expert, cfg.experts_held
    return {
        **_attention_shapes(cfg),
        "router": (d, cfg.n_experts),
        # e_score_correction_bias: picks experts, takes no gradient
        "router_bias": (cfg.n_experts,),
        "e_gate": (e, d, f), "e_up": (e, d, f), "e_down": (e, f, d),
        "s_gate": (d, f), "s_up": (d, f), "s_down": (f, d),
    }


def stacks(cfg: LatentMoEConfig) -> Dict[str, Tuple[int, Dict[str, tuple]]]:
    """stack name → (layers, per-layer shapes); a parameter is ``<stack>.<name>``."""
    d, f = cfg.d_model, cfg.d_ff
    out = {
        "dense": (cfg.n_dense_layers, {
            **_attention_shapes(cfg), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}),
        "moe": (cfg.n_expert_layers, _expert_layer_shapes(cfg)),
    }
    if cfg.mtp_modules:
        out["mtp"] = (cfg.mtp_modules, _expert_layer_shapes(cfg))
    return {k: v for k, v in out.items() if v[0]}


def layouts(cfg: LatentMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``)."""
    d, v = cfg.d_model, cfg.vocab_size
    top = {"embed": (v, d), "norm_f": (d,), "head": (d, v)}
    if cfg.mtp_modules:
        top.update({"mtp_norm_e": (d,), "mtp_norm_h": (d,), "mtp_proj": (2 * d, d),
                    "mtp_norm_f": (d,)})
    return mf.layouts(top, stacks(cfg))


#: how the leaves start, beside ``moe_family.INIT_RULES``: ones for the norms'
#: scales, zero selection bias (where training starts); wq_b and wkv_b
#: contract their first dim
INIT = {"*norm*": mf.ones, "router_bias": mf.zeros, "wq_a": mf.fan_in(-2),
        "wkv_a": mf.fan_in(-2), "wq_b": mf.fan_in(-3), "wkv_b": mf.fan_in(-3),
        "mtp_proj": mf.fan_in(-2)}


def init_params(cfg: LatentMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :data:`INIT`."""
    return mf.init_params(layouts(cfg), key, INIT)


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _attention(cfg: LatentMoEConfig, x, lp):
    """x (B, S, D) → x + latent attention (``moe_family.latent_attention``):
    queries through their bottleneck, the interleaved rope on the rotary
    columns — even columns first in the weights, ``ops/mla_heads.py`` says why
    the scores do not see it."""
    return mf.latent_attention(cfg, x, lp, "mla_attention", cfg.rope_theta, even_first)


def _dense_layer(cfg: LatentMoEConfig, x, lp):
    cdt = cfg.compute_dtype
    x = _attention(cfg, x, lp)
    with jax.named_scope("dense_mlp"):
        g = rms(x, lp["mlp_norm"], cfg.norm_eps).astype(cdt)
        y = swiglu(g, *(lp[w].astype(cdt) for w in ("w_gate", "w_up", "w_down")))
        return x + y.astype(x.dtype)


def expert_mlp(cfg: LatentMoEConfig, g32, lp):
    """The expert layer's MLP on normed tokens ``g32`` (T, D) f32: the held
    experts' routed part plus the shared expert.  Returns (y (T, D) f32,
    routing stats)."""
    def route(g32, lp):
        return sigmoid_topk_route(
            g32, lp["router"], lp["router_bias"], cfg.top_k, cfg.routed_scale)

    # cast once, before the router: both kinds of expert read this copy
    return mf.routed_mlp(cfg, g32, g32.astype(cfg.compute_dtype), lp, route, "moe_shared")


def _expert_layer(cfg: LatentMoEConfig, x, lp):
    x = _attention(cfg, x, lp)
    b, s, d = x.shape
    g32 = rms(x, lp["mlp_norm"], cfg.norm_eps).reshape(b * s, d)
    y, stats = expert_mlp(cfg, g32, lp)
    return x + y.reshape(b, s, d).astype(x.dtype), stats


def _run_stack(cfg: LatentMoEConfig, layer_fn, params, stack: str, x):
    """One remat'ed scan over a stack's layers.  Returns (x, per-layer aux)."""
    lps = mf.stack_of(params, stack)
    if not lps:
        return x, None
    body = lambda carry, lp: layer_fn(cfg, carry, lp)  # noqa: E731
    if cfg.remat:
        # attention's output and row statistics kept: ≈ 0.13 GB a layer at
        # 2 x 8192 tokens
        body = jax.checkpoint(body, policy=mf.keep_flash())
    return lax.scan(body, x, lps)


def _hidden(cfg: LatentMoEConfig, params, tokens, targets=None):
    """tokens (B, S) → the main stack's output before its final norm, the
    MTP module's (None without targets or modules), and the routing stats
    summed over the expert-kind layers."""
    cdt = cfg.compute_dtype
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cdt)
    x, _ = _run_stack(cfg, lambda c, x, lp: (_dense_layer(c, x, lp), None), params, "dense", x)
    x, stats = _run_stack(cfg, _expert_layer, params, "moe", x)
    stats = jnp.zeros((len(ROUTING_STATS),), jnp.int32) if stats is None else jnp.sum(stats, 0)
    x_mtp = None
    if cfg.mtp_modules and targets is not None:
        with jax.named_scope("mtp"):
            # h'_i = W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_i)]: the next
            # token's embedding first, the main stack's state second
            nxt = rms(params["embed"][jnp.maximum(targets, 0)], params["mtp_norm_e"], cfg.norm_eps)
            both = jnp.concatenate([nxt, rms(x, params["mtp_norm_h"], cfg.norm_eps)], axis=-1)
            x_mtp = (both.astype(cdt) @ params["mtp_proj"].astype(cdt)).astype(cdt)
            x_mtp, mtp_stats = _run_stack(cfg, _expert_layer, params, "mtp", x_mtp)
            stats = stats + jnp.sum(mtp_stats, 0)
    return x, x_mtp, stats


def _logits(cfg: LatentMoEConfig, params, x, norm: str):
    h = rms(x, params[norm], cfg.norm_eps).astype(cfg.compute_dtype)
    return (h @ params["head"].astype(cfg.compute_dtype)).astype(jnp.float32)


def local_logits(cfg: LatentMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits of the main model over the held rows."""
    x, _, _ = _hidden(cfg, params, tokens)
    return _logits(cfg, params, x, "norm_f")


def _xent_sums(cfg: LatentMoEConfig, params, x, norm: str, targets):
    """(sum of token cross-entropies, tokens counted); targets < 0 are
    ignored.  Remat'ed: the (B, S, V) logits are rebuilt in the backward
    pass, not kept."""
    def sums(x, scale, head):
        logits = _logits(cfg, {norm: scale, "head": head}, x, norm)
        valid = (targets >= 0).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * valid), jnp.sum(valid)

    if cfg.remat:
        sums = jax.checkpoint(sums)
    with jax.named_scope("lm_head"):
        return sums(x, params[norm], params["head"])


def local_loss(cfg: LatentMoEConfig, mesh: Mesh, params, tokens, targets):
    """``L_main + mtp_lambda · L_mtp``, each a global mean token
    cross-entropy, identical on every rank; and the step's routing stats
    (ROUTING_STATS name → int32) summed over the data-parallel ranks.  The
    MTP module at position i reads t_{i+1} (= targets_i) and predicts
    t_{i+2}; the last position has none and is ignored."""
    x, x_mtp, stats = _hidden(cfg, params, tokens, targets)

    def mean(total, count):
        total, count = mf.over_ranks(total, count)
        return total / count

    loss = mean(*_xent_sums(cfg, params, x, "norm_f", targets))
    if x_mtp is not None:
        with jax.named_scope("mtp"):
            after = jnp.concatenate([targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1)
            loss = loss + cfg.mtp_lambda * mean(
                *_xent_sums(cfg, params, x_mtp, "mtp_norm_f", after))
    (stats,) = mf.over_ranks(stats)
    return loss, dict(zip(ROUTING_STATS, stats))
