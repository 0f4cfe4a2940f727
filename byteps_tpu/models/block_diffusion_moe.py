"""Block-diffusion mixture-of-experts family (SDAR's block, ``model_type:
sdar_moe``, arXiv 2510.06303; the training pass is that of block diffusion,
arXiv 2503.09573) — the layers behind ``build_train_step``.

The block is Qwen3-MoE's: pre-norm, ``h ← h + attention(norm(h))`` then ``h ←
h + moe(norm(h))``; bias-free grouped-query softmax attention with an RMSNorm
over each head of q and of k before rope over the whole head; in every layer
``top_k`` of ``n_experts`` softmax-routed experts, the chosen weights
renormalised, SwiGLU experts, no shared expert; RMSNorm ``w · x / rms(x)``, an
untied head.  What is new is the TRAINING PASS.  A sequence of ``L`` tokens
enters the layers twice, as rows ``[x_t ‖ x_0]`` of one sequence of ``2L``:
``x_t`` the noised copy (each token of block ``b`` the mask token with
probability ``t_b``: ``byteps_tpu/data.block_diffusion_noise``, the input
pipeline's) and ``x_0`` the clean copy, both embedded by the same table, row
``i`` of either copy at position ``i``.  With ``blk(i) = i // block_length`` a
NOISY query sees the noisy keys of its own block, both directions, and the
clean keys of every earlier block; a CLEAN query the clean keys of its own and
every earlier block; nothing else (``ops/flash_attention.
block_diffusion_attention``: one pair of kernels over the ``2L`` rows that
walks a table of tiles).  The loss reads the noisy half's logits, unshifted —
row ``i`` predicts ``x_0[i]`` — ``(1 / (batch · L)) Σ_i w_i · CE_i`` with
``w_i = 1 / t_blk(i)`` where ``x_t[i]`` is the mask token and 0 elsewhere: the
step's batch has a third leaf, ``weights`` (f32, (batch, L)), which this
family DECLARES (``batch_leaves``) and the compiled step takes after
``targets`` (= ``x_0``; ``tokens`` = ``x_t``).

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is (the share of experts and vocabulary this device holds, what the
families share).  Parameters are stacked by part (``attn``, ``moe``), the
layers unrolled, every part rebuilt in the backward pass on its own, the
attention but for its kernel's output and row statistics (``keep_flash``).
Choices that are this module's:

* positions — the two copies go through norm and rope as ``2 · n_heads`` heads
  of a sequence of ``L`` (a reshape of the head-major operand: ``(B, H, 2L, d)``
  is ``(B, 2H, L, d)`` in memory), so ``ops/head_norm.head_norm_rope`` counts
  ``0 … L − 1`` for each copy as it does for every family, and takes no
  positions;
* the last layer — the loss reads nothing of the clean half there but its keys
  and values: that layer's queries, attention output and MoE run on the noisy
  half's ``L`` rows alone (``_attention_part(..., last=True)``); ``mf.walk``
  gives every layer of a stack one function, so the layer loop is written here
  (twelve lines, as the looped dense family's is);
* clean ``k, v`` are projected once and read by both halves (they are rows of
  the one operand).

What the step counts beside its loss and the routing statistics:
``block_diffusion_masked_tokens`` (rows at a non-zero weight) and
``block_diffusion_weight_milli`` (the mean weight a row in thousandths: which
noise the step saw), both summed over steps by the process's counters.  The
plain reference is ``models/block_diffusion_moe_reference.py``.
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
from byteps_tpu.ops.flash_attention import block_diffusion_attention
from byteps_tpu.ops.head_norm import head_norm_rope
from byteps_tpu.parallel.moe import softmax_topk_route

#: what a step counts beside the routing statistics
COUNTS = ("block_diffusion_masked_tokens", "block_diffusion_weight_milli")
#: the scopes the step's operations are filed under, beside ``moe_family``'s
ATTENTION, ASSEMBLY = "block_diffusion_attention", "copies_assembly"


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMoEConfig(mf.ExpertFamily):
    vocab_size: int = 151936  # rows of the vocabulary held here; the mask token is one
    d_model: int = 2048
    n_layers: int = 48
    block_length: int = 4  # tokens that are denoised together
    # the mixer
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    # the MLP: every layer's is routed
    d_expert: int = 768
    n_experts: int = 128  # the router's width: the model's routed experts
    experts_held: int = 128  # of them, held here: [expert_lo, expert_lo + held)
    expert_lo: int = 0
    top_k: int = 8
    norm_eps: float = 1e-6
    max_seq: int = 8192  # L: the sequence; 2L rows enter the layers
    compute_dtype: Any = jnp.float32
    remat: bool = True

    family = "block-diffusion"
    lacks = ("expert exchange, pipeline split, head sharding or a block mask across "
             "sequence shards")
    #: the batch's leaves after (tokens, targets), each (batch, L), sharded as they are
    batch_leaves = ("weights",)

    def __post_init__(self):
        super().__post_init__()
        self._check_grouped_heads()
        self._check_even_rope("head_dim")
        if self.block_length < 1 or self.max_seq % self.block_length:
            raise ValueError(f"blocks of {self.block_length} do not tile a sequence of "
                             f"{self.max_seq}")

    def local_loss(self, mesh: Mesh, params, tokens, targets, weights):
        return local_loss(self, mesh, params, tokens, targets, weights)

    def local_logits(self, mesh: Mesh, params, tokens):
        raise NotImplementedError(
            "the block-diffusion family's logits read both copies of a sequence "
            "(block_diffusion_moe.local_logits(cfg, params, noisy, clean)); build_forward, "
            "which hands a family tokens alone, is not built for it")


def tiny_block_diffusion_moe(**kw) -> BlockDiffusionMoEConfig:
    """The CPU tests' preset: every mechanism, toy widths — three layers, two
    query heads a key/value head, four blocks of four."""
    base = dict(vocab_size=96, d_model=32, n_layers=3, block_length=4, n_heads=4, n_kv_heads=2,
                head_dim=8, d_expert=16, n_experts=8, experts_held=8, top_k=2, max_seq=16)
    base.update(kw)
    return BlockDiffusionMoEConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; ``<stack>.<name>`` carries the layers as leading dim
# ---------------------------------------------------------------------------


def stacks(cfg: BlockDiffusionMoEConfig) -> Dict[str, Tuple[int, Dict[str, tuple]]]:
    """stack name → (layers, per-layer shapes): every layer reads both."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f, e = cfg.d_expert, cfg.experts_held
    return {
        "attn": (cfg.n_layers, {"norm": (d,), "wq": (d, h, hd), "wk": (d, kv, hd),
                                "wv": (d, kv, hd), "q_norm": (hd,), "k_norm": (hd,),
                                "wo": (h, hd, d)}),
        "moe": (cfg.n_layers, {"norm": (d,), "router": (d, cfg.n_experts),
                               "e_gate": (e, d, f), "e_up": (e, d, f), "e_down": (e, f, d)}),
    }


def layouts(cfg: BlockDiffusionMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``).  ``head`` is laid out as the
    embedding is, (vocabulary, model)."""
    v, d = cfg.vocab_size, cfg.d_model
    return mf.layouts({"embed": (v, d), "norm_f": (d,), "head": (v, d)}, stacks(cfg))


#: How the leaves start, beside ``moe_family.INIT_RULES``: ones for the norms'
#: scales but the per-head q and k norms', the head contracting its last dim,
#: the embedding N(0, 1) (``torch.nn.Embedding``'s own start, and not the
#: next-token families' 0.02 under which a seeded router collapses: PR 48).
#: Two leaves start as no other family's do, for one reason.  A third of the
#: 2L rows are ONE token, the mask.  With its row like any other and unit q/k
#: scales, seeded attention is a mean over thousands of keys at unit logits —
#: the same for every row — so every masked row carries one vector through
#: every layer, all of them choose the same ``top_k`` experts, and a layer's
#: held load is the seed's (0.25–2.1 of even: PERF.md §6 PR 59).  A trained
#: checkpoint's masked rows differ by what they attend to: its attention is
#: peaked, and the mask token is a row its autoregressive training never
#: touched.  So the mask token's row — the LAST of the vocabulary held — starts
#: at :data:`MASK_ROW` of the others' scale (not at zero: a null row has null
#: queries, and its attention is uniform whatever the scales), and the q and k
#: norm scales at :data:`QK_START`: logits of deviation ``QK_START²``, under
#: which a masked row is what a handful of the keys it sees brought it, and
#: the rows differ.  Larger scales even the load further and leave bf16 less
#: able to follow f32 (a masked row is wholly computed; at 2.2–3 not at all):
#: the pair was chosen on the chip between the two (PERF.md §6 PR 59)
MASK_ROW, QK_START = 0.01, 2.0


def _embedding(key, shape):
    """N(0, 1) rows, the last — the mask token's — at :data:`MASK_ROW` of it."""
    return mf.normal(1.0)(key, shape).at[-1].multiply(MASK_ROW)


def _qk_scales(key, shape):
    return jnp.full(shape, QK_START, jnp.float32)


INIT = {"*norm*": mf.ones, "q_norm": _qk_scales, "k_norm": _qk_scales, "head": mf.fan_in(-1),
        "embed": _embedding}


def init_params(cfg: BlockDiffusionMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :data:`INIT`."""
    return mf.init_params(layouts(cfg), key, INIT)


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _attention_part(cfg: BlockDiffusionMoEConfig, x, lp, last: bool = False):
    """x (B, 2L, D), the rows ``[x_t ‖ x_0]`` → ``x + attention(norm(x))``; in
    the ``last`` layer the noisy half's rows alone, (B, L, D): keys and values
    of all ``2L`` rows, queries of the first ``L``."""
    cdt, hd, eps = cfg.compute_dtype, cfg.head_dim, cfg.norm_eps
    b, rows, _ = x.shape
    half = rows // 2
    if last:
        with jax.named_scope(ASSEMBLY):  # the split before the head
            x_q = x[:, :half]
    with jax.named_scope(ATTENTION):
        g = rms(x, lp["norm"], eps).astype(cdt)
        k, v = (jnp.einsum("bsd,dhk->bhsk", g, lp[w].astype(cdt)) for w in ("wk", "wv"))
        q = jnp.einsum("bsd,dhk->bhsk", g[:, :half] if last else g, lp["wq"].astype(cdt))

        def turned(t, w):
            # each copy a sequence of L at positions 0 … L − 1: the copies of a
            # head are neighbours in memory, so they go as heads of their own
            copies = t.reshape(b, -1, half, hd)
            return head_norm_rope(copies, w, eps, cfg.rope_theta).reshape(t.shape)

        o = block_diffusion_attention(turned(q, lp["q_norm"]), turned(k, lp["k_norm"]), v,
                                      cfg.block_length, scale=hd ** -0.5)
        y = jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(cdt))
    return (x_q if last else x) + y.astype(x.dtype)


def moe_mlp(cfg: BlockDiffusionMoEConfig, g32, lp):
    """A layer's MLP on normed tokens ``g32`` (T, D) f32: the held experts'
    routed part and nothing beside it.  Returns (y (T, D) f32, routing stats)."""
    def route(g32, lp):
        return softmax_topk_route(g32, lp["router"], cfg.top_k)

    return mf.routed_mlp(cfg, g32, g32, lp, route)  # cast where the experts read


def _moe_part(cfg: BlockDiffusionMoEConfig, x, lp):
    b, s, d = x.shape
    with jax.named_scope("moe_experts"):  # the MLP's norm is filed with the experts
        g32 = rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
    y, stats = moe_mlp(cfg, g32, lp)
    return x + y.reshape(b, s, d).astype(x.dtype), stats


def _hidden(cfg: BlockDiffusionMoEConfig, params, noisy, clean):
    """noisy, clean (B, L) → the noisy half's rows after the last layer, before
    the final norm, (B, L, D), and the routing stats summed over the layers."""
    parts = {"attn": functools.partial(_attention_part, cfg),
             "last": functools.partial(_attention_part, cfg, last=True),
             "moe": functools.partial(_moe_part, cfg)}
    if cfg.remat:
        keep = mf.keep_flash()
        parts = {k: jax.checkpoint(f, policy=None if k == "moe" else keep)
                 for k, f in parts.items()}
    with jax.named_scope(ASSEMBLY):
        rows = jnp.concatenate([noisy, clean], axis=1)
    with jax.named_scope("embed"):
        x = params["embed"][rows].astype(cfg.compute_dtype)
    attn, moe = mf.stack_of(params, "attn"), mf.stack_of(params, "moe")
    stats = jnp.zeros((len(mf.ROUTING_STATS),), jnp.int32)
    for i in range(cfg.n_layers):
        mixer = parts["last" if i == cfg.n_layers - 1 else "attn"]
        x = mixer(x, {k: v[i] for k, v in attn.items()})
        x, each = parts["moe"](x, {k: v[i] for k, v in moe.items()})
        stats = stats + each
    return x, stats


def local_logits(cfg: BlockDiffusionMoEConfig, params, noisy, clean):
    """(B, L) ×2 → (B, L, V) f32: the noisy half's logits over the held rows,
    row ``i`` for position ``i``."""
    x, _ = _hidden(cfg, params, noisy, clean)
    return mf.row_logits(cfg, x, params["norm_f"], params["head"])


def local_loss(cfg: BlockDiffusionMoEConfig, mesh: Mesh, params, tokens, targets, weights):
    """``(1 / (batch · L)) Σ w · CE`` over the noisy half's rows, identical on
    every rank — ``tokens`` = ``x_t``, ``targets`` = ``x_0``, ``weights`` f32 —
    and what the step counts (``moe_family.ROUTING_STATS`` and :data:`COUNTS` name → int32)."""
    x, stats = _hidden(cfg, params, tokens, targets)
    total = mf.weighted_xent(cfg, mf.row_logits, x, targets, weights, params["norm_f"],
                             params["head"])
    with jax.named_scope("lm_head"):
        w = weights.astype(jnp.float32)
        rows, masked, heavy = jnp.sum(jnp.ones_like(w)), jnp.sum(w > 0), jnp.sum(w)
    total, rows, masked, heavy, stats = mf.over_ranks(total, rows, masked, heavy, stats)
    milli = jnp.round(1000.0 * heavy / rows).astype(jnp.int32)
    return total / rows, {**dict(zip(mf.ROUTING_STATS, stats)),
                          **dict(zip(COUNTS, (masked.astype(jnp.int32), milli)))}
