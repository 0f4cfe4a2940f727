"""Early-routed mixture-of-experts family (SmallThinker's block, ``model_name:
smallthinker_21b_instruct``, as SmallThinker-21BA3B-Instruct publishes it) —
the layers behind ``build_train_step``.

A layer is ``h ← h + attention(norm(h))`` then ``h ← h + experts(norm(h))``,
and the router decides BEFORE the attention: it reads ``a = norm(h)``, the
attention's own normed input, takes the ``top_k`` largest of its
``n_experts`` logits and weighs them by the softmax over those (the same ids
and weights as the full softmax's largest, renormalised:
``parallel/moe.softmax_topk_route``).  That decision — where each slot goes
among the held experts and what it weighs, 8 bytes a slot — crosses the
attention to the MLP part of the same layer (``moe_family.decide`` in the
mixer's part, ``moe_family.Handed`` through ``moe_family.walk``), where the
experts read the tokens the attention left: so a layer sorts its slots once,
in the mixer's forward pass, and neither part's rebuild sorts again.  Every
layer routes (no dense layer, no shared expert) and every expert is a
ReLU-gated MLP ``(relu(b W_gate) ⊙ b W_up) W_down``.

The mixers are plain grouped-query softmax attention, no head norm and no
gate, each key/value head serving its group of query heads (seven at the
published sizes), and ``layer_types[i]`` says which kind:
``"sliding_attention"`` takes rope over the whole head (``ops/head_norm.py``'s
``head_rope``: the q | k products stay token-major and one pass turns them
and writes the kernels' head-major operand) and sees the last
``sliding_window`` keys, itself included (``ops/flash_attention.py``'s banded
kernels); ``"full_attention"`` takes NO positional encoding and is causal.
Bias-free, RMSNorm ``w · x / rms(x)``, untied head, no embedding scale, no
position table, no auxiliary loss.

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is (the share of experts and vocabulary this device holds, the
protocol of a family with listed layers, what the families share).
Parameters are stacked by kind (``win``, ``glob``: the mixers, each with the
layer's router, which reads what they read; ``moe``: the experts), layer
``i`` takes the next entry of its two stacks, and every mixer and every MLP
is rebuilt in the backward pass on its own.  The untied head is laid out as
the embedding is, (vocabulary, model).  The plain reference is
``models/early_route_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from byteps_tpu.models import moe_family as mf
from byteps_tpu.models.moe_family import rms
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.head_norm import head_rope
from byteps_tpu.parallel.moe import softmax_topk_route, take_rows, varying

#: ``layer_types`` entry → the stack that holds that mixer's parameters
MIXERS = {"sliding_attention": "win", "full_attention": "glob"}
#: stack → the scope its mixer's operations are filed under
SCOPES = {"win": "window_attention", "glob": "global_attention"}


@dataclasses.dataclass(frozen=True)
class EarlyRouteMoEConfig(mf.PatternedFamily):
    vocab_size: int = 151936  # rows of the vocabulary held here
    d_model: int = 2560
    layer_types: Tuple[str, ...] = ("full_attention",) + ("sliding_attention",) * 3
    # the mixers
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1.5e6  # the sliding layers'; the full layers take no positions
    sliding_window: int = 4096  # keys a sliding layer's query sees, itself included
    # the experts
    d_expert: int = 768
    n_experts: int = 64  # the router's width: the model's experts
    experts_held: int = 64  # of them, held here: [expert_lo, expert_lo + held)
    expert_lo: int = 0
    top_k: int = 6
    norm_eps: float = 1e-6
    max_seq: int = 16384
    compute_dtype: Any = jnp.float32
    remat: bool = True

    mixers = MIXERS
    n_dense_layers = 0  # every layer routes
    family = "early-routed"
    lacks = ("expert exchange, pipeline split, head sharding or hand-over of a window's "
             "keys between sequence shards")

    def __post_init__(self):
        super().__post_init__()
        self._check_grouped_heads()
        self._check_even_rope("head_dim")
        if self.sliding_window < 1:
            raise ValueError(f"a sliding window holds the query itself at least, got "
                             f"{self.sliding_window}")


def tiny_early_route_moe(**kw) -> EarlyRouteMoEConfig:
    """The CPU tests' preset: every mechanism, toy widths, a global layer and
    three sliding ones, a window shorter than the sequence, seven query heads
    a key/value head, 8 experts top-2."""
    base = dict(vocab_size=96, d_model=32,
                layer_types=("full_attention",) + ("sliding_attention",) * 3,
                n_heads=7, n_kv_heads=1, head_dim=8, sliding_window=5, d_expert=16,
                n_experts=8, experts_held=8, top_k=2, max_seq=16)
    base.update(kw)
    return EarlyRouteMoEConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; ``<stack>.<name>`` carries the stack's layers as
# leading dim, in the order the layers come
# ---------------------------------------------------------------------------


def stacks(cfg: EarlyRouteMoEConfig) -> Dict[str, Tuple[int, Dict[str, tuple]]]:
    """stack name → (layers, per-layer shapes), the stacks some layer reads.
    A layer's router stands with its mixer: both read the mixer's ``norm``."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    fe, e = cfg.d_expert, cfg.experts_held
    mixer = {"norm": (d,), "router": (d, cfg.n_experts), "wq": (d, h, hd), "wk": (d, kv, hd),
             "wv": (d, kv, hd), "wo": (h, hd, d)}
    shapes = {
        "win": mixer, "glob": mixer,
        "moe": {"norm": (d,), "e_gate": (e, d, fe), "e_up": (e, d, fe), "e_down": (e, fe, d)},
    }
    return cfg.stack_sizes(shapes)


def layouts(cfg: EarlyRouteMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``).  ``head`` is laid out as the
    embedding is, (vocabulary, model)."""
    v, d = cfg.vocab_size, cfg.d_model
    return mf.layouts({"embed": (v, d), "norm_f": (d,), "head": (v, d)}, stacks(cfg))


#: how the leaves start, beside ``moe_family.INIT_RULES``: ones for the norms'
#: scales; the head contracts its last dim; the embedding N(0, 1)
#: (``torch.nn.Embedding``'s own start) and not the other families' 0.02.
#: This block has no embedding scale and no norm after a branch, so at 0.02 the
#: stream of seeded weights on uniform tokens is the attention's mean over
#: keys from the second layer on — 76 to 81 % of the router's input common to
#: all tokens by layers 2 and 3, six experts taking half the slots (counted at
#: the published widths and 2048 tokens: PERF.md §6, PR 48; narrower models do
#: not show it) — and a layer's load on the held experts is a draw of which six.  At 1 a token's own row leads and the
#: router spreads its tokens as a trained one does.
INIT = {"*norm*": mf.ones, "head": mf.fan_in(-1), "embed": mf.normal(1.0)}


def init_params(cfg: EarlyRouteMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :data:`INIT`."""
    return mf.init_params(layouts(cfg), key, INIT)


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _route(cfg: EarlyRouteMoEConfig, a32, lp):
    return softmax_topk_route(a32, lp["router"], cfg.top_k)


def _mixer_part(cfg: EarlyRouteMoEConfig, x, lp, stack: str):
    """x (B, S, D) → ``x + attention(norm(x))`` and the layer's routing,
    decided on ``norm(x)``; ``stack`` says which kind of attention: ``win``
    turns q and k by their positions and sees a window, ``glob`` knows no
    positions and sees all before it."""
    cdt, hd = cfg.compute_dtype, cfg.head_dim
    b, s, d = x.shape
    with jax.named_scope(SCOPES[stack]):
        a32 = rms(x, lp["norm"], cfg.norm_eps)
    decision = mf.decide(cfg, a32.reshape(b * s, d), lp, functools.partial(_route, cfg))
    with jax.named_scope(SCOPES[stack]):
        a = a32.astype(cdt)
        if stack == "win":
            # a turned head's product stays token-major, its heads side by
            # side: the rotation's pass writes it head-major
            q, k = (head_rope(jnp.einsum("bsd,df->bsf", a, lp[w].astype(cdt).reshape(d, -1)),
                              hd, cfg.rope_theta) for w in ("wq", "wk"))
        else:
            q, k = (jnp.einsum("bsd,dhk->bhsk", a, lp[w].astype(cdt)) for w in ("wq", "wk"))
        v = jnp.einsum("bsd,dhk->bhsk", a, lp["wv"].astype(cdt))
        # the kernels find a query head's key/value head themselves: K and V
        # go in at their own head count
        o = flash_attention(q, k, v, causal=True, scale=hd ** -0.5,
                            window=cfg.sliding_window if stack == "win" else None)
        y = jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(cdt))
    return mf.Handed(x + y.astype(x.dtype), decision)


def _expert_part(cfg: EarlyRouteMoEConfig, x, lp, decision: mf.Decision):
    """x (B, S, D), the attention's output → ``x + experts(norm(x))`` by the
    decision made before the attention, and the routing stats."""
    b, s, d = x.shape
    with jax.named_scope("moe_experts"):  # the MLP's norm is filed with the experts
        g32 = rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
    # cast where each expert reads
    y, stats = mf.routed_mlp(cfg, g32, g32, lp, decision, act=jax.nn.relu)
    return x + y.reshape(b, s, d).astype(x.dtype), stats


def _hidden(cfg: EarlyRouteMoEConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm, and the
    routing stats summed over the layers."""
    run = {"win": lambda x, lp: _mixer_part(cfg, x, lp, "win"),
           "glob": lambda x, lp: _mixer_part(cfg, x, lp, "glob"),
           "moe": lambda x, lp, decision: _expert_part(cfg, x, lp, decision)}
    with jax.named_scope("embed"):
        # the gather whose transpose sorts its scatter-add by hand: XLA's own
        # takes twice as long at this slice's (rows, model) under 2 x 16 384 tokens
        rows = take_rows(varying(params["embed"], jax.typeof(tokens).vma), tokens.reshape(-1))
        x = rows.reshape(*tokens.shape, -1).astype(cfg.compute_dtype)
    return mf.walk(cfg, run, dict.fromkeys(SCOPES, mf.FLASH_SAVED), params, x)


def local_logits(cfg: EarlyRouteMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    x, _ = _hidden(cfg, params, tokens)
    return mf.row_logits(cfg, x, params["norm_f"], params["head"])


def local_loss(cfg: EarlyRouteMoEConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (ROUTING_STATS name → int32) summed over the
    data-parallel ranks."""
    x, stats = _hidden(cfg, params, tokens)
    return mf.mean_loss(
        *mf.xent_sums(cfg, mf.row_logits, x, targets, params["norm_f"], params["head"]), stats)
