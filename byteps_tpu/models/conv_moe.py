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

This device holds the experts ``[expert_lo, expert_lo + experts_held)`` of
every expert layer and the first ``vocab_size`` rows of the vocabulary: its
share of a deployment in which several devices share each layer.  The router
scores all ``n_experts``; what the experts held elsewhere would add is left
out (``parallel/moe.held_expert_mlp``).

``transformer.build_train_step`` / ``build_forward`` take a
:class:`ConvMoEConfig` as they take a ``TransformerConfig``: the config
answers for its family with the parameter table (:func:`layouts`), the mesh
checks, the per-device loss (:func:`local_loss`) and logits.  Parameters are
stacked by kind (``conv``, ``attn``: the mixers; ``dense``, ``moe``: the
MLPs), and layer ``i`` takes the next entry of its mixer's stack and of its
MLP's; every mixer and every MLP is rebuilt in the backward pass on its own.
The plain reference is ``models/conv_moe_reference.py``.

Shared with the other families, by import: the convolution and the rotary
embedding (``models/delta_moe.py``), routing and the held experts
(``parallel/moe.py``), the flash kernels.  ``_rms``, ``_swiglu`` and the
blocked cross-entropy stand here a third time: they differ from
``delta_moe``'s only by the norm's scale (``w`` here, ``1 + w`` there) and by
the tied head; one home for them is ROADMAP D5's own change.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.models.delta_moe import causal_conv, rope_partial
from byteps_tpu.ops.flash_attention import SAVED as FLASH_SAVED
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.parallel.moe import ROUTING_STATS, held_expert_mlp, sigmoid_topk_route

_ALL_AXES = ("dp", "pp", "sp", "tp")
#: ``layer_types`` entry → the stack that holds that mixer's parameters
MIXERS = {"conv": "conv", "full_attention": "attn"}


@dataclasses.dataclass(frozen=True)
class ConvMoEConfig:
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

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = sorted(set(self.layer_types) - set(MIXERS))
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {unknown or 'nothing'}: a layer's mixer is "
                             f"one of {sorted(MIXERS)}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError(f"{self.n_dense_layers} leading dense layers in a model of "
                             f"{len(self.layer_types)}")
        if not 0 <= self.expert_lo <= self.n_experts - self.experts_held:
            raise ValueError(
                f"held experts [{self.expert_lo}, {self.expert_lo + self.experts_held}) "
                f"lie outside the router's {self.n_experts}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("query heads must be a multiple of key/value heads")
        if self.head_dim % 2:
            raise ValueError(f"rope needs an even head_dim, got {self.head_dim}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """Layer by layer, the stacks (mixer's, MLP's) it reads."""
        return tuple((MIXERS[t], "dense" if i < self.n_dense_layers else "moe")
                     for i, t in enumerate(self.layer_types))

    # what transformer.build_train_step / build_forward ask of a family
    def layouts(self) -> Dict[str, Tuple]:
        return layouts(self)

    def validate_mesh(self, mesh: Mesh) -> None:
        validate_mesh(self, mesh)

    def local_loss(self, mesh: Mesh, params, tokens, targets):
        return local_loss(self, mesh, params, tokens, targets)

    def local_logits(self, mesh: Mesh, params, tokens):
        return local_logits(self, params, tokens)[None]  # one microbatch, no pipeline


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
    used = [stack for pair in cfg.kinds() for stack in pair]
    return {k: (used.count(k), v) for k, v in shapes.items() if k in used}


def layouts(cfg: ConvMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes), as
    ``transformer._layouts`` gives them.  Everything is replicated: this
    family runs data-parallel only so far (:func:`validate_mesh`).  There is
    no ``head``: the logits are taken with ``embed``."""
    shapes = {"embed": (cfg.vocab_size, cfg.d_model), "norm_f": (cfg.d_model,)}
    for stack, (n, per_layer) in stacks(cfg).items():
        shapes.update({f"{stack}.{k}": (n,) + s for k, s in per_layer.items()})
    return {k: (s, P(), _ALL_AXES) for k, s in shapes.items()}


def init_params(cfg: ConvMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device): N(0,
    1/fan_in) matrices, 0.02 for the embedding, N(0, 1/kernel) convolution
    taps, ones for the norms' scales, N(0, 0.01²) for the selection bias (a
    trained balance's size: zeros would hide a bias that weighs)."""
    params = {}
    for i, (name, (shape, _, _)) in enumerate(layouts(cfg).items()):
        leaf, k = name.rsplit(".", 1)[-1], jax.random.fold_in(key, i)
        if "norm" in leaf:
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            # the contracted dims: wo its two before the last, wq/wk/wv the model's
            if leaf == "wo":
                fan_in = math.prod(shape[-3:-1])
            else:
                fan_in = shape[-3 if leaf in ("wq", "wk", "wv") else -2]
            std = {"embed": 0.02, "router_bias": 0.01}.get(leaf, fan_in ** -0.5)
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
    return params


def validate_mesh(cfg: ConvMoEConfig, mesh: Mesh) -> None:
    for ax in ("pp", "sp", "tp"):
        if mesh.shape.get(ax, 1) != 1:
            raise ValueError(
                f"the short-convolution MoE family runs data-parallel only: mesh has "
                f"{ax}={mesh.shape[ax]} (no expert exchange, pipeline split, head sharding "
                "or hand-over of the convolution's last tokens between sequence shards is "
                "built for it yet)")


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _rms(x, w, eps: float):
    """RMSNorm ``w · x / rms(x)`` with f32 statistics; returns f32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def _conv_mixer(cfg: ConvMoEConfig, x, lp):
    """x (B, S, D) → the double-gated short convolution's output (B, S, D),
    compute dtype."""
    cdt = cfg.compute_dtype
    with jax.named_scope("conv_proj"):
        g = _rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
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
        g = _rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        q, k, v = (jnp.einsum("bsd,dhk->bhsk", g, lp[w].astype(cdt)) for w in ("wq", "wk", "wv"))
        q = rope_partial(_rms(q, lp["q_norm"], cfg.norm_eps).astype(cdt), hd, cfg.rope_theta)
        k = rope_partial(_rms(k, lp["k_norm"], cfg.norm_eps).astype(cdt), hd, cfg.rope_theta)
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
        g = _rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        return _swiglu(g, *(lp[w].astype(cdt) for w in ("w_gate", "w_up", "w_down")))


def expert_mlp(cfg: ConvMoEConfig, g32, lp):
    """An expert layer's MLP on normed tokens ``g32`` (T, D) f32: the held
    experts' routed part, and nothing else (the model has no shared expert).
    Returns (y (T, D) f32, routing stats)."""
    cdt = cfg.compute_dtype
    with jax.named_scope("moe_route"):
        ids, weights = sigmoid_topk_route(g32, lp["router"], lp["router_bias"], cfg.top_k,
                                          cfg.routed_scale, eps=cfg.route_eps)
    with jax.named_scope("moe_experts"):
        return held_expert_mlp(
            g32.astype(cdt), ids, weights,
            *(lp[w].astype(cdt) for w in ("e_gate", "e_up", "e_down")),
            lo=cfg.expert_lo, n_experts=cfg.n_experts)


def _moe_mlp(cfg: ConvMoEConfig, x, lp):
    b, s, d = x.shape
    g32 = _rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
    y, stats = expert_mlp(cfg, g32, lp)
    return x + y.reshape(b, s, d).astype(x.dtype), stats


def _hidden(cfg: ConvMoEConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm, and the
    routing stats summed over the layers."""
    def residual(mixer):
        return lambda x, lp: x + mixer(cfg, x, lp).astype(x.dtype)

    run = {"conv": residual(_conv_mixer), "attn": residual(_attention_mixer),
           "dense": residual(_dense_mlp), "moe": lambda x, lp: _moe_mlp(cfg, x, lp)}
    if cfg.remat:
        # a layer's mixer and its MLP are each rebuilt in the backward pass,
        # one at a time; of attention all but the kernel's output and row
        # statistics, so that the forward kernel does not run twice
        keep_flash = jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED)
        run = {k: jax.checkpoint(f, policy=keep_flash if k == "attn" else None)
               for k, f in run.items()}

    x = params["embed"][tokens].astype(cfg.compute_dtype)
    stats = jnp.zeros((len(ROUTING_STATS),), jnp.int32)
    stacked = {stack: {k.split(".", 1)[1]: v for k, v in params.items()
                       if k.startswith(stack + ".")} for stack in run}
    seen = dict.fromkeys(run, 0)  # how many layers of each stack have run
    for pair in cfg.kinds():
        for stack in pair:
            lp = {k: v[seen[stack]] for k, v in stacked[stack].items()}
            seen[stack] += 1
            if stack == "moe":
                x, each = run[stack](x, lp)
                stats = stats + each
            else:
                x = run[stack](x, lp)
    return x, stats


def _logits(cfg: ConvMoEConfig, x, scale, embed):
    """The head is the embedding: logits over the held rows, f32."""
    h = _rms(x, scale, cfg.norm_eps).astype(cfg.compute_dtype)
    return lax.dot_general(h, embed.astype(cfg.compute_dtype),
                           (((h.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def local_logits(cfg: ConvMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    x, _ = _hidden(cfg, params, tokens)
    return _logits(cfg, x, params["norm_f"], params["embed"])


#: rows of logits that stand at a time in the loss
ROW_BLOCK = 2048


def _xent_sums(cfg: ConvMoEConfig, params, x, targets):
    """(sum of token cross-entropies, tokens counted); targets < 0 are
    ignored.  A block of rows at a time, each rebuilt in the backward pass:
    the (B·S, V) logits never stand whole."""
    d = x.shape[-1]
    block = math.gcd(x.size // d, ROW_BLOCK)

    def one(xb, tb, scale, embed):
        logits = _logits(cfg, xb, scale, embed)
        gold = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * (tb >= 0))

    if cfg.remat:
        one = jax.checkpoint(one)
    scale, embed = params["norm_f"], params["embed"]
    total = jnp.sum(lax.map(lambda xs: one(*xs, scale, embed),
                            (x.reshape(-1, block, d), targets.reshape(-1, block))))
    return total, jnp.sum(targets >= 0).astype(jnp.float32)


def local_loss(cfg: ConvMoEConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (ROUTING_STATS name → int32) summed over the
    data-parallel ranks."""
    x, stats = _hidden(cfg, params, tokens)
    total, count = _xent_sums(cfg, params, x, targets)
    for ax in ("dp", "sp"):
        total, count, stats = lax.psum(total, ax), lax.psum(count, ax), lax.psum(stats, ax)
    return total / count, dict(zip(ROUTING_STATS, stats))
