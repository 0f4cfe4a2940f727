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

This device holds the experts ``[expert_lo, expert_lo + experts_held)`` and
the first ``vocab_size`` rows of embedding and head: its share of a layer
that several devices divide.  The router scores all ``n_experts``; what the
experts held elsewhere would add is left out; the shared expert is whole.

Parameters are stacked by kind (``win``, ``glob``: the mixers; ``dense``,
``moe``: the MLPs), layer ``i`` takes the next entry of its two stacks, and
every mixer and every MLP is rebuilt in the backward pass on its own.  The
plain reference is ``models/window_moe_reference.py``.

By import, not a fifth time: the norm (``w``, where ``delta_moe``'s is
``1 + w``), the SwiGLU, the routed experts' wrapper (scopes ``moe_route``,
``moe_experts``) and the blocked cross-entropy are ``models/conv_moe.py``'s —
its tied head contracts with a (vocabulary, model) matrix, which is how this
family lays out its UNTIED head, so the same function serves once handed
``head`` where it reads ``embed``; rope is ``delta_moe.rope_partial`` at the
whole head.  What differed and stands here: the mixer (gate, two masks, rope
on one kind alone), the sandwich norms, the shared expert, the embedding's scale.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.models.conv_moe import _logits, _rms, _swiglu, _xent_sums, expert_mlp
from byteps_tpu.ops.flash_attention import SAVED as FLASH_SAVED
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.head_norm import head_norm_rope
from byteps_tpu.parallel.moe import ROUTING_STATS

_ALL_AXES = ("dp", "pp", "sp", "tp")
#: ``layer_types`` entry → the stack that holds that mixer's parameters
MIXERS = {"sliding_attention": "win", "full_attention": "glob"}
#: stack → the scope its mixer's operations are filed under
SCOPES = {"win": "window_attention", "glob": "global_attention"}


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
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
        if self.sliding_window < 1:
            raise ValueError(f"a sliding window holds the query itself at least, got "
                             f"{self.sliding_window}")

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
    used = [stack for pair in cfg.kinds() for stack in pair]
    return {k: (used.count(k), v) for k, v in shapes.items() if k in used}


def layouts(cfg: WindowMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes), as
    ``transformer._layouts`` gives them.  Everything is replicated: this
    family runs data-parallel only so far (:func:`validate_mesh`).  ``head``
    is laid out as the embedding is, (vocabulary, model)."""
    v, d = cfg.vocab_size, cfg.d_model
    shapes = {"embed": (v, d), "norm_f": (d,), "head": (v, d)}
    for stack, (n, per_layer) in stacks(cfg).items():
        shapes.update({f"{stack}.{k}": (n,) + s for k, s in per_layer.items()})
    return {k: (s, P(), _ALL_AXES) for k, s in shapes.items()}


def init_params(cfg: WindowMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device): N(0,
    1/fan_in) matrices, 0.02 for the embedding, ones for the norms' scales,
    N(0, 0.01²) for the selection bias (a trained balance's size: zeros would
    hide a bias that weighs)."""
    params = {}
    for i, (name, (shape, _, _)) in enumerate(layouts(cfg).items()):
        leaf, k = name.rsplit(".", 1)[-1], jax.random.fold_in(key, i)
        if "norm" in leaf:
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            # the contracted dims: wo its two before the last, the head its last
            if leaf == "wo":
                fan_in = math.prod(shape[-3:-1])
            else:
                fan_in = shape[{"wq": -3, "wk": -3, "wv": -3, "wg": -3, "head": -1}.get(leaf, -2)]
            std = {"embed": 0.02, "router_bias": 0.01}.get(leaf, fan_in ** -0.5)
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
    return params


def validate_mesh(cfg: WindowMoEConfig, mesh: Mesh) -> None:
    for ax in ("pp", "sp", "tp"):
        if mesh.shape.get(ax, 1) != 1:
            raise ValueError(
                f"the sliding-window MoE family runs data-parallel only: mesh has "
                f"{ax}={mesh.shape[ax]} (no expert exchange, pipeline split, head sharding "
                "or hand-over of a window's keys between sequence shards is built for it yet)")


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
        g = _rms(x, lp["norm"], eps).astype(cdt)
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
        return _rms(jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(cdt)), lp["post_norm"], eps)


def _dense_mlp(cfg: WindowMoEConfig, x, lp):
    """x (B, S, D) → the dense SwiGLU between its two norms (B, S, D) f32."""
    cdt = cfg.compute_dtype
    with jax.named_scope("dense_mlp"):
        g = _rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        y = _swiglu(g, *(lp[w].astype(cdt) for w in ("w_gate", "w_up", "w_down")))
        return _rms(y, lp["post_norm"], cfg.norm_eps)


def moe_mlp(cfg: WindowMoEConfig, g32, lp):
    """An expert layer's MLP on normed tokens ``g32`` (T, D) f32: the held
    experts' routed part (``conv_moe.expert_mlp``: scopes ``moe_route`` and
    ``moe_experts``) plus the shared expert, which every token takes at
    weight 1.  Returns (y (T, D) f32, routing stats)."""
    cdt = cfg.compute_dtype
    y, stats = expert_mlp(cfg, g32, lp)
    with jax.named_scope("shared_expert"):
        shared = _swiglu(g32.astype(cdt), *(lp[w].astype(cdt) for w in ("s_gate", "s_up", "s_down")))
    return y + shared.astype(jnp.float32), stats


def _moe_layer(cfg: WindowMoEConfig, x, lp):
    b, s, d = x.shape
    with jax.named_scope("moe_experts"):  # the MLP's two norms are filed with the experts
        g32 = _rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
    y, stats = moe_mlp(cfg, g32, lp)
    with jax.named_scope("moe_experts"):
        y = _rms(y.reshape(b, s, d), lp["post_norm"], cfg.norm_eps)
    return x + y.astype(x.dtype), stats


def _hidden(cfg: WindowMoEConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm, and the
    routing stats summed over the layers."""
    def residual(part, *kind):
        return lambda x, lp: x + part(cfg, x, lp, *kind).astype(x.dtype)

    run = {"win": residual(_attention_mixer, "win"), "glob": residual(_attention_mixer, "glob"),
           "dense": residual(_dense_mlp), "moe": lambda x, lp: _moe_layer(cfg, x, lp)}
    if cfg.remat:
        # a layer's mixer and its MLP are each rebuilt in the backward pass,
        # one at a time; of attention all but the kernel's output and row
        # statistics, so that the forward kernel does not run twice
        keep_flash = jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED)
        run = {k: jax.checkpoint(f, policy=keep_flash if k in SCOPES else None)
               for k, f in run.items()}

    x = params["embed"][tokens]
    if cfg.mup:
        x = x * math.sqrt(cfg.d_model)
    x = x.astype(cfg.compute_dtype)
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


def local_logits(cfg: WindowMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    x, _ = _hidden(cfg, params, tokens)
    return _logits(cfg, x, params["norm_f"], params["head"])


def local_loss(cfg: WindowMoEConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (ROUTING_STATS name → int32) summed over the
    data-parallel ranks."""
    x, stats = _hidden(cfg, params, tokens)
    # conv_moe's blocked cross-entropy reads its (vocabulary, model) matrix
    # under ``embed``: here that matrix is the untied head
    total, count = _xent_sums(cfg, {"norm_f": params["norm_f"], "embed": params["head"]},
                              x, targets)
    for ax in ("dp", "sp"):
        total, count, stats = lax.psum(total, ax), lax.psum(count, ax), lax.psum(stats, ax)
    return total / count, dict(zip(ROUTING_STATS, stats))
