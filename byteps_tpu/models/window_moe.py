"""Sliding-window / global-attention mixture-of-experts family (Trinity's
block, ``model_type: afmoe``, as Trinity-Mini publishes it) — the layers
behind ``build_train_step``.

A layer is ``h ← h + norm(mixer(norm(h)))`` then ``h ← h + norm(mlp(norm(h)))``:
sandwich norms, the second inside the residual branch.  Every mixer is gated
grouped-query softmax attention (a per-head RMSNorm on q and k, each
key/value head serving its group of query heads, ``sigmoid(g W_g)`` on the
attention's output before ``W_o``) and ``layer_types[i]`` says which kind:
``"sliding_attention"`` takes rope over the whole head and sees the last
``sliding_window`` keys, itself included (``ops/flash_attention.py``'s banded
kernels); ``"full_attention"`` takes NO positional encoding and is causal.
The first ``n_dense_layers`` layers have a dense SwiGLU MLP; the others
``top_k`` of ``n_experts`` sigmoid-routed experts (weights renormalised and
scaled, a selection bias that picks and does not weigh) beside one shared
expert that every token takes ungated.  The embedding is scaled by
``√d_model`` (``mup``); bias-free, RMSNorm ``w · x / rms(x)``, untied head, no
position table, no auxiliary loss.

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is (the share of experts and vocabulary this device holds — the
shared expert is whole —, the protocol of a family with listed layers, what
the families share).  Parameters are stacked by kind (``win``, ``glob``: the
mixers; ``dense``, ``moe``: the MLPs), layer ``i`` takes the next entry of its
two stacks, and every mixer and every MLP is rebuilt in the backward pass on
its own.  The untied head is laid out as the embedding is, (vocabulary,
model).  The plain reference is ``models/window_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from byteps_tpu.models import moe_family as mf
from byteps_tpu.models.moe_family import rms, swiglu
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.head_norm import head_norm_rope
from byteps_tpu.parallel.moe import sigmoid_topk_route

#: ``layer_types`` entry → the stack that holds that mixer's parameters
MIXERS = {"sliding_attention": "win", "full_attention": "glob"}
#: stack → the scope its mixer's operations are filed under
SCOPES = {"win": "window_attention", "glob": "global_attention"}


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig(mf.PatternedFamily):
    vocab_size: int = 200192  # rows of the vocabulary held here
    d_model: int = 2048
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + ("full_attention",)
    n_dense_layers: int = 2  # the first so many layers' MLP is dense
    # the mixers
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e4  # the sliding layers'; the full layers take no positions
    sliding_window: int = 2048  # keys a sliding layer's query sees, itself included
    # the MLPs
    d_ff: int = 6144  # the dense layers' SwiGLU
    d_expert: int = 1024  # every routed expert's
    d_shared: int = 1024  # the shared expert's: moe_intermediate_size x num_shared_experts
    n_experts: int = 128  # the router's width: the model's routed experts
    experts_held: int = 128  # of them, held here: [expert_lo, expert_lo + held)
    expert_lo: int = 0
    top_k: int = 8
    routed_scale: float = 2.826  # the published route_scale
    route_eps: float = 1e-20  # beside the chosen scores' sum
    mup: bool = True  # the embedding scaled by sqrt(d_model)
    norm_eps: float = 1e-5
    max_seq: int = 16384
    compute_dtype: Any = jnp.float32
    remat: bool = True

    mixers = MIXERS
    family = "sliding-window"
    lacks = ("expert exchange, pipeline split, head sharding or hand-over of a window's "
             "keys between sequence shards")

    def __post_init__(self):
        super().__post_init__()
        self._check_grouped_heads()
        self._check_even_rope("head_dim")
        if self.sliding_window < 1:
            raise ValueError(f"a sliding window holds the query itself at least, got "
                             f"{self.sliding_window}")


def tiny_window_moe(**kw) -> WindowMoEConfig:
    """The CPU tests' preset: every mechanism, toy widths, one leading dense
    layer, both mixers under both MLPs, a window shorter than the sequence,
    two query heads a key/value head."""
    base = dict(vocab_size=96, d_model=32, n_dense_layers=1,
                layer_types=("sliding_attention", "full_attention", "sliding_attention",
                             "sliding_attention"),
                n_heads=4, n_kv_heads=2, head_dim=8, sliding_window=5, d_ff=48, d_expert=16,
                d_shared=16, n_experts=8, experts_held=8, top_k=2, max_seq=16)
    base.update(kw)
    return WindowMoEConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; ``<stack>.<name>`` carries the stack's layers as
# leading dim, in the order the layers come
# ---------------------------------------------------------------------------


def stacks(cfg: WindowMoEConfig) -> Dict[str, Tuple[int, Dict[str, tuple]]]:
    """stack name → (layers, per-layer shapes), the stacks some layer reads.
    ``norm`` stands before a mixer or an MLP, ``post_norm`` after it."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f, fe, fs, e = cfg.d_ff, cfg.d_expert, cfg.d_shared, cfg.experts_held
    mixer = {"norm": (d,), "wq": (d, h, hd), "wk": (d, kv, hd), "wv": (d, kv, hd),
             "wg": (d, h, hd), "q_norm": (hd,), "k_norm": (hd,), "wo": (h, hd, d),
             "post_norm": (d,)}
    shapes = {
        "win": mixer, "glob": mixer,
        "dense": {"norm": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
                  "post_norm": (d,)},
        # router_bias is the published expert_bias: it picks, and takes no gradient
        "moe": {"norm": (d,), "router": (d, cfg.n_experts), "router_bias": (cfg.n_experts,),
                "e_gate": (e, d, fe), "e_up": (e, d, fe), "e_down": (e, fe, d),
                "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d), "post_norm": (d,)},
    }
    return cfg.stack_sizes(shapes)


def layouts(cfg: WindowMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``).  ``head`` is laid out as the
    embedding is, (vocabulary, model)."""
    v, d = cfg.vocab_size, cfg.d_model
    return mf.layouts({"embed": (v, d), "norm_f": (d,), "head": (v, d)}, stacks(cfg))


#: how the leaves start, beside ``moe_family.INIT_RULES``: ones for the norms'
#: scales, N(0, 0.01²) for the selection bias (a trained balance's size: zeros
#: would hide a bias that weighs); the head contracts its last dim
INIT = {"*norm*": mf.ones, "router_bias": mf.normal(0.01), "wg": mf.fan_in(-3),
        "head": mf.fan_in(-1)}


def init_params(cfg: WindowMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :data:`INIT`."""
    return mf.init_params(layouts(cfg), key, INIT)


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _attention_mixer(cfg: WindowMoEConfig, x, lp, stack: str):
    """x (B, S, D) → the gated attention's output under its second norm
    (B, S, D) f32; ``stack`` says which kind: ``win`` turns q and k by their
    positions and sees a window, ``glob`` knows no positions and sees all
    before it."""
    cdt, hd, eps = cfg.compute_dtype, cfg.head_dim, cfg.norm_eps
    with jax.named_scope(SCOPES[stack]):
        g = rms(x, lp["norm"], eps).astype(cdt)
        q, k, v = (jnp.einsum("bsd,dhk->bhsk", g, lp[w].astype(cdt)) for w in ("wq", "wk", "wv"))
        z = jnp.einsum("bsd,dhk->bshk", g, lp["wg"].astype(cdt))  # as W_o's product reads it
        theta = cfg.rope_theta if stack == "win" else None
        q = head_norm_rope(q, lp["q_norm"], eps, theta)
        k = head_norm_rope(k, lp["k_norm"], eps, theta)
        # the kernels find a query head's key/value head themselves: K and V
        # go in at their own head count
        o = flash_attention(q, k, v, causal=True, scale=hd ** -0.5,
                            window=cfg.sliding_window if stack == "win" else None)
        o = o.transpose(0, 2, 1, 3) * jax.nn.sigmoid(z.astype(jnp.float32)).astype(cdt)
        return rms(jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(cdt)), lp["post_norm"], eps)


def _dense_mlp(cfg: WindowMoEConfig, x, lp):
    """x (B, S, D) → the dense SwiGLU between its two norms (B, S, D) f32."""
    cdt = cfg.compute_dtype
    with jax.named_scope("dense_mlp"):
        g = rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        y = swiglu(g, *(lp[w].astype(cdt) for w in ("w_gate", "w_up", "w_down")))
        return rms(y, lp["post_norm"], cfg.norm_eps)


def moe_mlp(cfg: WindowMoEConfig, g32, lp):
    """An expert layer's MLP on normed tokens ``g32`` (T, D) f32: the held
    experts' routed part plus the shared expert, which every token takes at
    weight 1.  Returns (y (T, D) f32, routing stats)."""
    def route(g32, lp):
        return sigmoid_topk_route(g32, lp["router"], lp["router_bias"], cfg.top_k,
                                  cfg.routed_scale, eps=cfg.route_eps)

    # cast where each expert reads
    return mf.routed_mlp(cfg, g32, g32, lp, route, "shared_expert")


def _moe_layer(cfg: WindowMoEConfig, x, lp):
    b, s, d = x.shape
    with jax.named_scope("moe_experts"):  # the MLP's two norms are filed with the experts
        g32 = rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
    y, stats = moe_mlp(cfg, g32, lp)
    with jax.named_scope("moe_experts"):
        y = rms(y.reshape(b, s, d), lp["post_norm"], cfg.norm_eps)
    return x + y.astype(x.dtype), stats


def _hidden(cfg: WindowMoEConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm, and the
    routing stats summed over the layers."""
    def residual(part, *kind):
        return lambda x, lp: x + part(cfg, x, lp, *kind).astype(x.dtype)

    run = {"win": residual(_attention_mixer, "win"), "glob": residual(_attention_mixer, "glob"),
           "dense": residual(_dense_mlp), "moe": lambda x, lp: _moe_layer(cfg, x, lp)}
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        if cfg.mup:
            x = x * math.sqrt(cfg.d_model)
        x = x.astype(cfg.compute_dtype)
    return mf.walk(cfg, run, dict.fromkeys(SCOPES, mf.FLASH_SAVED), params, x)


def local_logits(cfg: WindowMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    x, _ = _hidden(cfg, params, tokens)
    return mf.row_logits(cfg, x, params["norm_f"], params["head"])


def local_loss(cfg: WindowMoEConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (ROUTING_STATS name → int32) summed over the
    data-parallel ranks."""
    x, stats = _hidden(cfg, params, tokens)
    return mf.mean_loss(
        *mf.xent_sums(cfg, mf.row_logits, x, targets, params["norm_f"], params["head"]), stats)
