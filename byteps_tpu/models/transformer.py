"""Flagship transformer family — fully shardable over (dp, pp, sp, tp).

TPU-first design, not a port: the whole train step is ONE compiled SPMD
program under ``shard_map`` over a 4-D mesh:

    dp — batch sharding; gradients psum over ICI (the reference's entire
         data-parallel capability, SURVEY §2.7)
    pp — pipeline stages: layer stack sharded on the leading stage dim,
         GPipe-style microbatch schedule driven by lax.scan with
         lax.ppermute hops between stages
    sp — sequence/context parallelism: ring attention
         (byteps_tpu.parallel.ring_attention) rotating KV blocks on ICI;
         doubles as the expert-parallel axis for MoE (DeepSpeed-MoE
         grouping)
    tp — megatron-style tensor parallelism: attention heads and MLP hidden
         column-sharded, row-parallel matmuls psum'd

Parameters are stored as a flat dict of stacked global arrays with leading
dims (pp, layers_per_stage, ...); sharding specs and gradient-sync axes are
derived per entry (a parameter's grads are psum'd over exactly the axes it
is replicated on).

Flagship configs: BERT-large (the reference's headline benchmark,
BASELINE.md) and GPT-2 medium (BASELINE.json config 5).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byteps_tpu.core.tracing import stepped
from byteps_tpu.parallel.moe import moe_aux_loss, moe_mlp, routing_counters
from byteps_tpu.parallel.ring_attention import ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 1024
    n_heads: int = 16
    # grouped-query attention: number of K/V heads (None = n_heads, i.e.
    # classic MHA).  Query heads share KV groups of n_heads/n_kv_heads;
    # the decode KV cache stores only n_kv_heads (the GQA memory win)
    n_kv_heads: Optional[int] = None
    d_head: int = 64
    d_ff: int = 4096
    n_layers: int = 24
    max_seq: int = 512
    causal: bool = False  # BERT-style bidirectional by default
    moe: bool = False
    n_experts: int = 8
    # experts per token: 2 = GShard-style with renormalized gates (the
    # quality default), 1 = cheaper Switch-style routing
    moe_top_k: int = 2
    capacity_factor: float = 2.0
    # capacity factor for GENERATION prefill.  None (default) = no-drop
    # serving capacity (cf = n_experts, capacity = token count): prompt
    # tokens are never silently dropped from the MLP and generation output
    # is mesh-independent.  Set a finite value (e.g. the training
    # capacity_factor) to bound prefill memory for very long prompts, at
    # the documented cost of GShard-style per-dp-shard overflow drops.
    prefill_capacity_factor: float | None = None
    moe_aux_coef: float = 0.01
    compute_dtype: Any = jnp.float32
    microbatches: int = 0  # 0 → pipeline stages count
    # rematerialize each transformer layer in backward (jax.checkpoint):
    # trades ~30% more FLOPs for O(layers) less activation memory — the
    # HBM-vs-FLOPs dial the reference cannot turn (it owns no compute graph)
    remat: bool = True
    # use the Pallas flash-attention kernel for the per-device attention
    # when sequence parallelism is off (ring attention otherwise).
    # Default off: bert_large_step, the cell that runs this family, is at
    # sequence 128, one kernel block, where nothing was timed.  Measured
    # on one v5e chip with the kernel as it is since PR 29 (bf16 operands
    # on the MXU, masked blocks not fetched; tools/flash_tune.py, batch 16,
    # 16 heads of 64, causal, forward + dQ + dK/dV; PERF.md section 6, PR
    # 29): flash 2.36 ms against dense 2.16 at sequence 512, 6.53 against
    # 8.63 at 1024, 19.68 against 32.09 at 2048 - dense wins at 512, the
    # kernel from 1024 up, and where the S^2 scores do not fit it is the
    # only way (models/latent_moe.py at sequence 8192).  The figures that
    # stood here before (412 vs 291 samples/s at 128) were older than the code.
    use_flash: bool = False
    # sequence-parallel strategy when sp > 1: "ring" (ppermute KV blocks,
    # any head count) or "ulysses" (all-to-all head/seq reshard, needs
    # tp-local heads divisible by sp)
    seq_parallel_impl: str = "ring"

    # qkv/proj bias terms (GPT-2-style checkpoints have them; BERT too)
    attn_bias: bool = False
    # positional encoding: "learned" absolute table (BERT/GPT-2 style) or
    # "rope" rotary embeddings applied to q/k (Llama/GPT-NeoX style —
    # relative, extrapolates past max_seq, composes with ring attention
    # because each key's rotation is baked in before KV blocks travel)
    pos_emb: str = "learned"
    rope_theta: float = 10000.0

    def __post_init__(self):
        if self.seq_parallel_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown seq_parallel_impl {self.seq_parallel_impl!r}; "
                "expected 'ring' or 'ulysses'"
            )
        if self.n_kv_heads is not None and self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads {self.n_heads} not divisible by n_kv_heads "
                f"{self.n_kv_heads} (query heads share KV groups evenly)"
            )
        if self.pos_emb not in ("learned", "rope"):
            raise ValueError(
                f"unknown pos_emb {self.pos_emb!r}; expected 'learned' or 'rope'"
            )
        if self.pos_emb == "rope" and self.d_head % 2:
            raise ValueError(
                f"rope needs an even d_head, got {self.d_head}"
            )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    # What the builders below ask of a model family (models/latent_moe.py's
    # LatentMoEConfig answers the same four): its parameter table, its
    # checks against a mesh, and per device, inside shard_map, its loss with
    # whatever it counts (name -> int32, none here) and its logits.
    def layouts(self) -> Dict[str, Tuple]:
        return _layouts(self)

    def validate_mesh(self, mesh: Mesh) -> None:
        _validate_mesh(self, mesh)

    def local_loss(self, mesh: Mesh, params, tokens, targets):
        return _local_loss(self, mesh, params, tokens, targets), {}

    def local_logits(self, mesh: Mesh, params, tokens):
        return _local_logits(self, mesh, params, tokens)


def bert_large(**kw) -> TransformerConfig:
    """BERT-large: 24L, d1024, 16 heads, ff 4096 — the reference's headline
    scaling benchmark (README.md:38-46, BASELINE.md)."""
    return TransformerConfig(
        vocab_size=30528, d_model=1024, n_heads=16, d_head=64, d_ff=4096,
        n_layers=24, causal=False, **kw,
    )


def gpt2_medium(**kw) -> TransformerConfig:
    """GPT-2 medium: 24L, d1024, causal (BASELINE.json config 5)."""
    return TransformerConfig(
        vocab_size=50257, d_model=1024, n_heads=16, d_head=64, d_ff=4096,
        n_layers=24, causal=True, **kw,
    )


def tiny_test(**kw) -> TransformerConfig:
    kw.setdefault("vocab_size", 64)
    kw.setdefault("d_model", 16)
    kw.setdefault("n_heads", 4)
    kw.setdefault("d_head", 4)
    kw.setdefault("d_ff", 32)
    kw.setdefault("n_layers", 4)
    kw.setdefault("max_seq", 16)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# Parameters: flat dict of stacked global arrays + per-entry layout table
# ---------------------------------------------------------------------------


def _layouts(cfg: TransformerConfig) -> Dict[str, Tuple]:
    """name → (global_shape_fn(pp, tp, sp) irrelevant — shapes are GLOBAL),
    (partition spec), (grad sync axes).  Spec axes reference the 4-D mesh
    (dp, pp, sp, tp)."""
    D, H, dh, F = cfg.d_model, cfg.n_heads, cfg.d_head, cfg.d_ff
    KV = cfg.kv_heads
    L, V, S, E = cfg.n_layers, cfg.vocab_size, cfg.max_seq, cfg.n_experts
    # leading dims of layer params: (pp, layers_per_stage) — pp filled in
    # at init time when the mesh is known
    table = {
        "embed": ((V, D), P(), ("dp", "pp", "sp", "tp")),
    }
    if cfg.pos_emb == "learned":
        table["pos"] = ((S, D), P(), ("dp", "pp", "sp", "tp"))
    table.update({
        "ln_f_s": ((D,), P(), ("dp", "pp", "sp", "tp")),
        "ln_f_b": ((D,), P(), ("dp", "pp", "sp", "tp")),
        "head": ((D, V), P(), ("dp", "pp", "sp", "tp")),
        # layer-stacked (leading (pp, Lps) added at init)
        "ln1_s": ((D,), P("pp"), ("dp", "sp", "tp")),
        "ln1_b": ((D,), P("pp"), ("dp", "sp", "tp")),
        "ln2_s": ((D,), P("pp"), ("dp", "sp", "tp")),
        "ln2_b": ((D,), P("pp"), ("dp", "sp", "tp")),
        "wq": ((D, H, dh), P("pp", None, None, "tp", None), ("dp", "sp")),
        "wk": ((D, KV, dh), P("pp", None, None, "tp", None), ("dp", "sp")),
        "wv": ((D, KV, dh), P("pp", None, None, "tp", None), ("dp", "sp")),
        "wo": ((H, dh, D), P("pp", None, "tp", None, None), ("dp", "sp")),
    })
    if cfg.attn_bias:
        table.update(
            {
                "wq_b": ((H, dh), P("pp", None, "tp", None), ("dp", "sp")),
                "wk_b": ((KV, dh), P("pp", None, "tp", None), ("dp", "sp")),
                "wv_b": ((KV, dh), P("pp", None, "tp", None), ("dp", "sp")),
                # added after the tp psum, like b2
                "wo_b": ((D,), P("pp"), ("dp", "sp", "tp")),
            }
        )
    if cfg.moe:
        table.update(
            {
                "router": ((D, E), P("pp"), ("dp", "sp", "tp")),
                "ew1": ((E, D, F), P("pp", None, "sp", None, None), ("dp", "tp")),
                "eb1": ((E, F), P("pp", None, "sp", None), ("dp", "tp")),
                "ew2": ((E, F, D), P("pp", None, "sp", None, None), ("dp", "tp")),
                "eb2": ((E, D), P("pp", None, "sp", None), ("dp", "tp")),
            }
        )
    else:
        table.update(
            {
                "w1": ((D, F), P("pp", None, None, "tp"), ("dp", "sp")),
                "b1": ((F,), P("pp", None, "tp"), ("dp", "sp")),
                "w2": ((F, D), P("pp", None, "tp", None), ("dp", "sp")),
                "b2": ((D,), P("pp"), ("dp", "sp", "tp")),
            }
        )
    return table


_LAYER_PARAMS_PREFIXES = (
    "ln1_", "ln2_", "wq", "wk", "wv", "wo", "w1", "b1", "w2", "b2",
    "router", "ew1", "eb1", "ew2", "eb2",
)


def _is_layer_param(name: str) -> bool:
    return any(name.startswith(p) for p in _LAYER_PARAMS_PREFIXES)


def param_specs(cfg: TransformerConfig) -> Dict[str, P]:
    return {k: spec for k, (_, spec, _) in cfg.layouts().items()}


def grad_sync_axes(cfg: TransformerConfig) -> Dict[str, Tuple[str, ...]]:
    return {k: axes for k, (_, _, axes) in cfg.layouts().items()}


def init_params(
    cfg: TransformerConfig, seed: int = 0, pp_size: int = 1
) -> Dict[str, np.ndarray]:
    """Host-side init (numpy, float32).  Layer params get leading dims
    (pp, layers_per_stage)."""
    if cfg.n_layers % pp_size:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp {pp_size}")
    lps = cfg.n_layers // pp_size
    rng = np.random.default_rng(seed)
    params: Dict[str, np.ndarray] = {}
    for name, (shape, _, _) in _layouts(cfg).items():
        if _is_layer_param(name):
            full = (pp_size, lps) + shape
        else:
            full = shape
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = 0.02 if name in ("embed", "pos") else 1.0 / math.sqrt(fan_in)
        if name.endswith("_s"):  # layernorm scales → ones
            arr = np.ones(full, dtype=np.float32)
        elif name.endswith("_b") or name.startswith("b") or name.startswith("eb"):
            arr = np.zeros(full, dtype=np.float32)
        else:
            arr = rng.normal(0.0, std, size=full).astype(np.float32)
        params[name] = arr
    return params


# ---------------------------------------------------------------------------
# Forward pieces (run per-device inside shard_map)
# ---------------------------------------------------------------------------


def _vary_all(x, mesh: Mesh):
    """Mark a value as device-varying over the activation axes (VMA mode).

    Activations vary over dp/sp (data) and pp (stage weights) but stay
    *invariant* over tp: every row-parallel matmul ends in a psum over tp,
    so the residual stream is numerically replicated across tp ranks and
    must be typed accordingly (a psum of a replicated-but-varying-typed
    value would silently multiply by the axis size).

    Scan carries must keep a stable varying-axes type; starting them at the
    full activation type avoids carry mismatches once sharded weights mix in.
    """
    all_axes = tuple(ax for ax in mesh.shape.keys() if ax != "tp")
    if not all_axes:
        return x

    def cast(a):
        have = jax.typeof(a).vma
        need = tuple(ax for ax in all_axes if ax not in have)
        return lax.pcast(a, need, to="varying") if need else a

    return jax.tree_util.tree_map(cast, x)


def _ln(x, s, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * s + b


def _rope(x, positions, theta: float):
    """Rotary position embedding (rotate-half convention): x (B, H, s, dh)
    rotated per ABSOLUTE position — sequence-parallel ranks and the cached
    decoder pass their global offsets, so rotations stay consistent when
    KV blocks travel the ring or live in the cache."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (s, half)
    cos = jnp.cos(ang)[None, None]
    sin = jnp.sin(ang)[None, None]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def _qkv_proj(cfg: TransformerConfig, h, lp, positions=None):
    """Shared QKV projection (tp-local heads: wq (D, H_local, dh)) —
    used by the training stage fn AND the cached decoder so the layer
    math can never diverge between paths.  ``positions``: absolute token
    positions (s,), required when cfg.pos_emb == "rope" (q/k rotated
    in-projection; v untouched)."""
    cdt = cfg.compute_dtype
    q = jnp.einsum("bsd,dhk->bhsk", h, lp["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bhsk", h, lp["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bhsk", h, lp["wv"].astype(cdt))
    if cfg.attn_bias:
        q = q + lp["wq_b"].astype(cdt)[None, :, None, :]
        k = k + lp["wk_b"].astype(cdt)[None, :, None, :]
        v = v + lp["wv_b"].astype(cdt)[None, :, None, :]
    if cfg.pos_emb == "rope":
        assert positions is not None, "rope needs absolute positions"
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k, v, n_q_heads: int):
    """Expand grouped K/V heads to the query head count (GQA): each KV
    head serves n_q_heads/kv_heads query heads.  Identity for MHA."""
    rep = n_q_heads // k.shape[1]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)


def _attn_out(cfg: TransformerConfig, attn, lp, x):
    """Shared attention output projection + tp row-parallel combine +
    residual."""
    cdt = cfg.compute_dtype
    o = jnp.einsum("bhsk,hkd->bsd", attn, lp["wo"].astype(cdt))
    o = lax.psum(o, "tp")  # row-parallel combine (free at tp=1)
    if cfg.attn_bias:
        o = o + lp["wo_b"].astype(cdt)
    return x + o.astype(x.dtype)


def _dense_mlp(cfg: TransformerConfig, x, lp):
    """Shared dense MLP block (LN → gelu MLP with tp row-parallel combine
    → residual)."""
    cdt = cfg.compute_dtype
    g = _ln(x, lp["ln2_s"], lp["ln2_b"]).astype(cdt)
    hmid = jax.nn.gelu(
        jnp.einsum("bsd,df->bsf", g, lp["w1"].astype(cdt)) + lp["b1"].astype(cdt)
    )
    y = jnp.einsum("bsf,fd->bsd", hmid, lp["w2"].astype(cdt))
    y = lax.psum(y, "tp")  # row-parallel combine
    y = y + lp["b2"].astype(cdt)
    return x + y.astype(x.dtype)


def _moe_block(cfg: TransformerConfig, x, lp, sp: int,
               capacity_factor: float):
    """Shared MoE MLP block (ln2 → routed expert MLP → residual), used by
    the training layer and the cached decoder so the two cannot drift.
    Returns (new residual stream, router input g) — g feeds the aux loss
    so it always matches exactly what was routed."""
    cdt = cfg.compute_dtype
    g = _ln(x, lp["ln2_s"], lp["ln2_b"]).astype(cdt)
    b_, s_, d_ = g.shape
    y = moe_mlp(
        g.reshape(b_ * s_, d_),
        lp["router"].astype(cdt),
        lp["ew1"].astype(cdt), lp["eb1"].astype(cdt),
        lp["ew2"].astype(cdt), lp["eb2"].astype(cdt),
        axis_name="sp" if sp > 1 else None,
        axis_size=sp,
        capacity_factor=capacity_factor,
        top_k=cfg.moe_top_k,
    ).reshape(b_, s_, d_)
    return x + y.astype(x.dtype), g


def _make_stage_fn(cfg: TransformerConfig, mesh: Mesh):
    sp = mesh.shape.get("sp", 1)
    tp = mesh.shape.get("tp", 1)
    cdt = cfg.compute_dtype

    def layer_fn(x, lp):
        # x: (B, S_local, D)
        h = _ln(x, lp["ln1_s"], lp["ln1_b"]).astype(cdt)
        s_local = x.shape[1]
        positions = (
            lax.axis_index("sp") * s_local + jnp.arange(s_local)
            if cfg.pos_emb == "rope" else None
        )
        q, k, v = _qkv_proj(cfg, h, lp, positions)
        k, v = _repeat_kv(k, v, q.shape[1])  # GQA: groups -> query heads
        if sp == 1 and cfg.use_flash:
            from byteps_tpu.ops.flash_attention import flash_attention

            attn = flash_attention(q, k, v, causal=cfg.causal)
        elif sp > 1 and cfg.seq_parallel_impl == "ulysses":
            from byteps_tpu.parallel.ulysses import ulysses_attention

            attn = ulysses_attention(
                q, k, v, axis_name="sp", axis_size=sp, causal=cfg.causal
            )
        elif sp > 1 and cfg.use_flash:
            # long-context composition: flash-kernel
            # hops inside the ring — O(block) memory per hop instead of
            # the (B, H, S_local, S_local) per-hop score matrix
            from byteps_tpu.parallel.ring_attention import ring_flash_attention

            attn = ring_flash_attention(
                q, k, v, axis_name="sp", axis_size=sp, causal=cfg.causal
            )
        else:
            attn = ring_attention(
                q, k, v, axis_name="sp" if sp > 1 else None, axis_size=sp,
                causal=cfg.causal,
            )
        x = _attn_out(cfg, attn, lp, x)

        if cfg.moe:
            x, g = _moe_block(cfg, x, lp, sp, cfg.capacity_factor)
            b_, s_, d_ = g.shape
            aux = moe_aux_loss(
                g.reshape(b_ * s_, d_), lp["router"].astype(cdt), sp,
                lp["ew1"].shape[0],
            )
        else:
            x = _dense_mlp(cfg, x, lp)
            aux = jnp.zeros((), cdt)
        return x, aux

    def stage_fn(stage_params: Dict[str, jax.Array], x: jax.Array):
        """Run this pp rank's layer stack via scan; stage_params leaves have
        leading dim layers_per_stage."""
        body_fn = layer_fn
        if cfg.remat:
            body_fn = jax.checkpoint(
                layer_fn, policy=jax.checkpoint_policies.nothing_saveable
            )

        def body(carry, lp):
            y, aux = body_fn(carry, lp)
            return y, aux

        x, auxs = lax.scan(body, x, stage_params)
        return x, jnp.sum(auxs)

    return stage_fn


def _pipeline(cfg: TransformerConfig, mesh: Mesh, stage_fn, stage_params, x_mb):
    """GPipe-style pipelined forward under shard_map.

    x_mb: (M, Bmb, S_local, D) embedded microbatches (meaningful on every
    rank; only stage 0 consumes them).  Returns (M, Bmb, S_local, D) final
    activations (meaningful on the last stage) and the masked MoE aux sum.

    The schedule runs M + pp - 1 ticks; each tick every stage processes its
    current microbatch and ppermutes the activation downstream.  Bubble
    ticks compute garbage that is masked out of outputs and aux.
    """
    pp = mesh.shape.get("pp", 1)
    if pp == 1:
        def body(carry, x):
            y, aux = stage_fn(stage_params, x)
            return carry + aux, y
        aux0 = _vary_all(jnp.zeros((), cfg.compute_dtype), mesh)
        aux, ys = lax.scan(body, aux0, x_mb)
        return ys, aux

    idx = lax.axis_index("pp")
    m = x_mb.shape[0]
    ticks = m + pp - 1
    perm = [(i, i + 1) for i in range(pp - 1)]

    def tick(carry, t):
        buf, outputs, aux_acc = carry
        mb = jnp.clip(t - idx, 0, m - 1)
        x_in = jnp.where(idx == 0, lax.dynamic_index_in_dim(x_mb, mb, 0, keepdims=False), buf)
        y, aux = stage_fn(stage_params, x_in)
        valid = jnp.logical_and(t - idx >= 0, t - idx < m)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        is_last = idx == pp - 1
        write = jnp.logical_and(valid, is_last)
        prev = lax.dynamic_index_in_dim(outputs, mb, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, y, prev), mb, 0
        )
        buf_next = lax.ppermute(y, "pp", perm)
        return (buf_next, outputs, aux_acc), None

    buf0 = _vary_all(jnp.zeros_like(x_mb[0]), mesh)
    out0 = _vary_all(jnp.zeros_like(x_mb), mesh)
    aux0 = _vary_all(jnp.zeros((), cfg.compute_dtype), mesh)
    (_, outputs, aux), _ = lax.scan(tick, (buf0, out0, aux0), jnp.arange(ticks))
    return outputs, aux


def _local_forward(cfg: TransformerConfig, mesh: Mesh, params, tokens):
    """Per-device forward body: embed → pipeline → final-LN → logits.

    tokens: (B_local, S_local) int32.  Returns ((M, Bmb, S_local, V) logits,
    aux) — logits meaningful on the last pp stage.
    """
    pp = mesh.shape.get("pp", 1)
    sp = mesh.shape.get("sp", 1)
    stage_fn = _make_stage_fn(cfg, mesh)

    # squeeze the pp-shard dim off layer params: (1, Lps, ...) → (Lps, ...)
    stage_params = {
        k: v[0] for k, v in params.items() if _is_layer_param(k)
    }

    b_local, s_local = tokens.shape
    sp_idx = lax.axis_index("sp")
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        if cfg.pos_emb == "learned":
            positions = sp_idx * s_local + jnp.arange(s_local)
            x = x + params["pos"][positions]
        x = _vary_all(x.astype(cfg.compute_dtype), mesh)

    m = cfg.microbatches or pp
    if b_local % m:
        raise ValueError(f"local batch {b_local} not divisible by {m} microbatches")
    x_mb = x.reshape(m, b_local // m, s_local, cfg.d_model)

    outputs, aux = _pipeline(cfg, mesh, stage_fn, stage_params, x_mb)
    with jax.named_scope("lm_head"):
        h = _ln(outputs, params["ln_f_s"], params["ln_f_b"]).astype(cfg.compute_dtype)
        logits = jnp.einsum("mbsd,dv->mbsv", h, params["head"].astype(cfg.compute_dtype))
    return logits, aux


def _local_loss(cfg: TransformerConfig, mesh: Mesh, params, tokens, targets):
    """Global mean token cross-entropy, identical on every rank after psums.

    Positions with ``target < 0`` are ignored — that one convention covers
    BERT-style masked-LM pretraining (loss only on masked positions; the
    reference's headline benchmark is exactly this workload) and padding.
    """
    pp = mesh.shape.get("pp", 1)
    logits, aux = _local_forward(cfg, mesh, params, tokens)
    m = logits.shape[0]
    tgt = targets.reshape(m, -1, targets.shape[-1])
    valid = (tgt >= 0).astype(jnp.float32)
    safe_tgt = jnp.maximum(tgt, 0)
    with jax.named_scope("lm_head"):
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32), safe_tgt[..., None], axis=-1
        )[..., 0]
        token_loss = (logz - gold) * valid  # (M, Bmb, S_local)
        local_sum = jnp.sum(token_loss)
        local_cnt = jnp.sum(valid)
    # only the last stage holds real logits; the pp-psum picks its value
    # (free no-ops at axis size 1, and they make the loss VMA-invariant
    # over every mesh axis so it is truly replicated)
    is_last = lax.axis_index("pp") == pp - 1
    local_sum = jnp.where(is_last, local_sum, 0.0)
    local_cnt = jnp.where(is_last, local_cnt, 0.0)
    for ax in ("pp", "dp", "sp"):
        local_sum = lax.psum(local_sum, ax)
        local_cnt = lax.psum(local_cnt, ax)
        aux = lax.psum(aux, ax)
    loss = local_sum / local_cnt
    if cfg.moe:
        loss = loss + cfg.moe_aux_coef * aux.astype(jnp.float32)
    return loss


def _local_logits(cfg: TransformerConfig, mesh: Mesh, params, tokens):
    """(M, Bmb, S_local, V) logits, the same on every pipeline stage."""
    logits, _ = _local_forward(cfg, mesh, params, tokens)
    # select the last pipeline stage's logits (garbage elsewhere)
    is_last = lax.axis_index("pp") == mesh.shape.get("pp", 1) - 1
    return lax.psum(jnp.where(is_last, logits, 0.0), "pp")


# ---------------------------------------------------------------------------
# Public builders
# ---------------------------------------------------------------------------


def validate_mesh(cfg, mesh: Mesh) -> None:
    """Config×mesh checks that can only run once the mesh is known: the
    model family's own (``cfg.validate_mesh``)."""
    cfg.validate_mesh(mesh)


def _validate_mesh(cfg: TransformerConfig, mesh: Mesh) -> None:
    """wq is tp-sharded on the query-head dim and wk/wv on the KV-head dim,
    so both head counts must divide tp — otherwise the failure surfaces
    later as an opaque shard_map/NamedSharding error instead of naming
    the bad config (ADVICE r4)."""
    tp = mesh.shape.get("tp", 1)
    if cfg.n_heads % tp:
        raise ValueError(
            f"n_heads {cfg.n_heads} not divisible by tp={tp}: wq is "
            "tp-sharded on the head dim"
        )
    if cfg.kv_heads % tp:
        raise ValueError(
            f"n_kv_heads {cfg.kv_heads} not divisible by tp={tp}: wk/wv "
            "are tp-sharded on the KV-head dim — use more KV heads or a "
            "smaller tp axis (GQA groups cannot span tp shards)"
        )


def shard_params(params: Dict[str, np.ndarray], cfg: TransformerConfig, mesh: Mesh):
    """device_put the host params with their NamedShardings."""
    validate_mesh(cfg, mesh)
    specs = param_specs(cfg)
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }


def build_forward(cfg: TransformerConfig, mesh: Mesh) -> Callable:
    """Jitted SPMD forward: (params, tokens) → logits (M, Bmb, S_local, V).

    Single-chip friendly: with a 1-device mesh all collectives degenerate.
    """
    validate_mesh(cfg, mesh)
    specs = param_specs(cfg)

    shmapped = jax.shard_map(
        functools.partial(cfg.local_logits, mesh),
        mesh=mesh,
        in_specs=(specs, P("dp", "sp")),
        out_specs=P(None, "dp", "sp", None),
        check_vma=True,
    )
    return jax.jit(shmapped)


def build_generate(cfg: TransformerConfig, mesh: Mesh) -> Callable:
    """Greedy decoding: ``generate(params, prompt, n_new) → (B, S0+n_new)``.

    Recompute-based (no KV cache yet): each step runs the cached jitted
    forward on the fixed ``max_seq`` window — causal masking makes the
    right-padding inert.  Requires ``cfg.causal``.
    """
    if not cfg.causal:
        raise ValueError("generation requires a causal config")
    fwd = build_forward(cfg, mesh)

    def generate(params, prompt: np.ndarray, n_new: int) -> np.ndarray:
        prompt = np.asarray(prompt, dtype=np.int32)
        b, s0 = prompt.shape
        if s0 + n_new > cfg.max_seq:
            raise ValueError(f"{s0}+{n_new} exceeds max_seq {cfg.max_seq}")
        dp = mesh.shape.get("dp", 1)
        if b % dp:
            raise ValueError(f"batch {b} not divisible by dp={dp}")
        buf = np.zeros((b, cfg.max_seq), dtype=np.int32)
        buf[:, :s0] = prompt
        for i in range(s0, s0 + n_new):
            logits = fwd(params, jnp.asarray(buf))  # (M, dp*Bmb, S, V)
            arr = np.asarray(logits)
            m, g, s, v = arr.shape
            # Undo the assembly permutation: dim 1 is dp-shard-major while
            # input rows are dp-major with each shard's rows split across
            # the M microbatches — (M, dp, Bmb) must come back together as
            # (dp, M, Bmb) to restore input batch order.
            step_logits = (
                arr.reshape(m, dp, g // dp, s, v)
                .transpose(1, 0, 2, 3, 4)
                .reshape(-1, s, v)
            )
            buf[:, i] = step_logits[:, i - 1, :].argmax(-1)
        return buf[:, : s0 + n_new]

    return generate


def build_generate_cached(cfg: TransformerConfig, mesh: Mesh) -> Callable:
    """KV-cached greedy decoding — the TPU-first generation path.

    Unlike :func:`build_generate` (recompute per token), this keeps per-
    layer K/V caches in HBM and runs the WHOLE decode as one compiled
    ``lax.scan``: prefill writes the prompt's K/V in a single batched
    pass, then each scan step embeds one token, attends against the cache
    (static ``max_seq`` shapes — XLA-friendly), appends its K/V, and emits
    the argmax.  O(S) attention per new token instead of O(S²) recompute.

    Supported mesh axes: dp (batch), tp (heads), pp (layer stages: each
    token's forward hops stage→stage via ppermute, the decode-inherent
    pipeline bubble), and sp (replicated residual stream — sequence
    parallelism has no per-token decode role; for MoE configs sp doubles
    as the EXPERT axis, with the all_to_all dispatch running on the
    replicated tokens).  Requires a causal config.
    """
    if not cfg.causal:
        raise ValueError("generation requires a causal config")

    cdt = cfg.compute_dtype
    S_max = cfg.max_seq
    pp = mesh.shape.get("pp", 1)
    sp = mesh.shape.get("sp", 1)

    def cached_layer(x, lp, kc, vc, offset, cf):
        """x: (B, s, D); kc/vc: (B, H_local, S_max, dh); returns updated
        residual stream and caches with positions [offset, offset+s).
        Projections and MLP are the SAME helpers the training stage uses —
        only the attention core (cache append + masked full-cache attend)
        differs."""
        s = x.shape[1]
        h = _ln(x, lp["ln1_s"], lp["ln1_b"]).astype(cdt)
        positions = (
            offset + jnp.arange(s) if cfg.pos_emb == "rope" else None
        )
        q, k, v = _qkv_proj(cfg, h, lp, positions)
        # the cache holds KV heads only (the GQA decode-memory win); the
        # attend below groups query heads over it without materializing
        # a repeated cache
        kc = lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), offset, axis=2)
        vc = lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), offset, axis=2)
        bq, hq = q.shape[0], q.shape[1]
        hkv = kc.shape[1]
        rep = hq // hkv
        qg = q.reshape(bq, hkv, rep, s, cfg.d_head)
        scores = jnp.einsum("bgrsk,bgtk->bgrst", qg, kc.astype(cdt))
        scores = scores / np.sqrt(cfg.d_head).astype(cdt)
        # query i (absolute offset+i) may see cache positions t <= offset+i
        t_idx = jnp.arange(S_max)
        i_idx = offset + jnp.arange(s)
        mask = t_idx[None, :] <= i_idx[:, None]  # (s, S_max)
        scores = jnp.where(
            mask[None, None, None], scores, jnp.asarray(-1e30, cdt)
        )
        attn = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cdt)
        ctx = jnp.einsum("bgrst,bgtk->bgrsk", attn, vc.astype(cdt))
        ctx = ctx.reshape(bq, hq, s, cfg.d_head)
        x = _attn_out(cfg, ctx, lp, x)
        if cfg.moe:
            # expert-parallel MLP: decode tokens are REPLICATED across the
            # sp (expert) axis, and the all_to_all dispatch/inverse is
            # copy-symmetric — every rank reassembles the full expert
            # output, so the replicated-token result stays identical on
            # all sp members (n redundant capacity copies, trivial at
            # decode token counts).  ``cf`` is the capacity factor:
            # no-drop serving capacity (cf = n_experts ⇒ capacity = t)
            # for the per-token steps AND, by default, for prefill
            # (cfg.prefill_capacity_factor opts back into memory-bounded
            # training semantics for very long prompts).
            y, _ = _moe_block(cfg, x, lp, sp, cf)
            return y, kc, vc
        return _dense_mlp(cfg, x, lp), kc, vc

    def run_layers(stage_params, x, kcs, vcs, offset, cf):
        """scan the layer stack; kcs/vcs leading dim = layers."""

        def body(carry, inp):
            xc = carry
            lp, kc, vc = inp
            xc, kc, vc = cached_layer(xc, lp, kc, vc, offset, cf)
            return xc, (kc, vc)

        x, (kcs, vcs) = lax.scan(body, x, (stage_params, kcs, vcs))
        return x, kcs, vcs

    def full_stack(stage_params, x, kcs, vcs, offset, cf):
        """Run the FULL model depth.  With pp == 1 that is just the local
        stack; otherwise unrolled pp turns: at turn s only stage s runs its
        local layers (lax.cond keeps the others idle — the decode-inherent
        pipeline bubble), then the residual hops to stage s+1 via ppermute.
        The last stage's output is psum-broadcast so every stage computes
        the same logits/token (head params are replicated over pp)."""
        if pp == 1:
            return run_layers(stage_params, x, kcs, vcs, offset, cf)
        pp_idx = lax.axis_index("pp")

        def mine(ops):
            xx, kk, vv = ops
            return run_layers(stage_params, xx, kk, vv, offset, cf)

        for turn in range(pp):
            x, kcs, vcs = lax.cond(
                pp_idx == turn, mine, lambda ops: ops, (x, kcs, vcs)
            )
            if turn != pp - 1:
                x = lax.ppermute(
                    x, "pp", [(j, (j + 1) % pp) for j in range(pp)]
                )
        x = lax.psum(jnp.where(pp_idx == pp - 1, x, jnp.zeros_like(x)), "pp")
        return x, kcs, vcs

    def logits_of(params, x):
        h = _ln(x, params["ln_f_s"], params["ln_f_b"]).astype(cdt)
        return jnp.einsum("bsd,dv->bsv", h, params["head"].astype(cdt))

    def gen_fn(params, tokens, temperature, key, n_new: int,
               sampling: bool = False, top_k: int = 0):
        """tokens: (B_local, s0) EQUAL-LENGTH prompts (no padding support:
        prefill reads the last column's logits and the cache mask is
        position-only); returns (B_local, n_new).

        ``sampling``/``top_k`` are trace-static (they change the program
        structure); ``temperature`` and the PRNG ``key`` are RUNTIME values
        so new seeds/temperatures reuse the compiled program.  Keys fold
        per step AND per dp shard so every row draws independently."""
        stage_params = {k: v[0] for k, v in params.items() if _is_layer_param(k)}
        b, s0 = tokens.shape
        L = stage_params["wq"].shape[0]  # pp-local layer count
        kv_local = stage_params["wk"].shape[2]  # tp-local KV head count
        kcs = jnp.zeros((L, b, kv_local, S_max, cfg.d_head), cdt)
        vcs = jnp.zeros_like(kcs)

        # prefill: one batched pass over the prompt
        base_key = jax.random.fold_in(key, lax.axis_index("dp"))

        def pick(step_logits, step_idx):
            """(B, V) logits → (B,) next tokens."""
            if not sampling:
                return jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
            scaled = step_logits.astype(jnp.float32) / temperature
            if top_k > 0:
                # k-th largest as threshold via partial selection — a full
                # vocab sort per decoded token would dominate the hot path
                kth = lax.top_k(scaled, top_k)[0][:, -1:]
                scaled = jnp.where(scaled >= kth, scaled, -1e30)
            step_key = jax.random.fold_in(base_key, step_idx)
            return jax.random.categorical(step_key, scaled, axis=-1).astype(jnp.int32)

        x = params["embed"][tokens]
        if cfg.pos_emb == "learned":
            x = x + params["pos"][jnp.arange(s0)]
        # prefill: no-drop serving capacity by default (cf = n_experts ⇒
        # capacity = token count — no prompt token ever loses its MLP
        # contribution, and output is mesh-independent); opt into
        # memory-bounded training semantics via prefill_capacity_factor
        prefill_cf = (
            float(cfg.n_experts)
            if cfg.prefill_capacity_factor is None
            else cfg.prefill_capacity_factor
        )
        x, kcs, vcs = full_stack(
            stage_params, x.astype(cdt), kcs, vcs, 0, prefill_cf
        )
        last = pick(logits_of(params, x)[:, -1, :], 0)

        def step(carry, i):
            kcs, vcs, tok, pos = carry
            x = params["embed"][tok]
            if cfg.pos_emb == "learned":
                x = x + params["pos"][pos]
            x = x[:, None, :].astype(cdt)
            # per-token steps: serving capacity (no drops at tiny t)
            x, kcs, vcs = full_stack(
                stage_params, x, kcs, vcs, pos, float(cfg.n_experts)
            )
            nxt = pick(logits_of(params, x)[:, -1, :], i + 1)
            return (kcs, vcs, nxt, pos + 1), tok

        # step k consumes g_k and computes g_{k+1}; emitting the consumed
        # token makes toks exactly [g_1 .. g_n] (the final compute is spare)
        _, toks = lax.scan(
            step, (kcs, vcs, last, jnp.asarray(s0, jnp.int32)),
            jnp.arange(n_new),
        )
        return toks.T  # (B_local, n_new)

    specs = param_specs(cfg)

    import functools

    @functools.lru_cache(maxsize=16)
    def _compiled(n_new: int, sampling: bool, top_k: int):
        # jit handles prompt-shape (s0) caching; only program STRUCTURE
        # (n_new, greedy-vs-sampling, top_k width) keys distinct compiles —
        # seed and temperature are runtime inputs
        return jax.jit(
            jax.shard_map(
                lambda p, t, temp, key: gen_fn(
                    p, t, temp, key, n_new, sampling, top_k
                ),
                mesh=mesh,
                in_specs=(specs, P("dp"), P(), P()),
                out_specs=P("dp"),
                check_vma=False,
            )
        )

    def generate(
        params,
        prompt: np.ndarray,
        n_new: int,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
    ) -> np.ndarray:
        """prompt: (B, s0) EQUAL-LENGTH prompts, B divisible by dp.

        ``temperature == 0`` (default) decodes greedily; ``temperature > 0``
        samples, optionally truncated to the ``top_k`` most likely tokens,
        deterministically for a given ``seed``.  Changing seed or
        temperature reuses the compiled program."""
        prompt = np.asarray(prompt, dtype=np.int32)
        b, s0 = prompt.shape
        if s0 + n_new > S_max:
            raise ValueError(f"{s0}+{n_new} exceeds max_seq {S_max}")
        dp = mesh.shape.get("dp", 1)
        if b % dp:
            raise ValueError(f"batch {b} not divisible by dp={dp}")
        if top_k > cfg.vocab_size:
            raise ValueError(f"top_k={top_k} exceeds vocab_size {cfg.vocab_size}")
        sampling = temperature > 0.0
        new = np.asarray(
            _compiled(n_new, sampling, int(top_k) if sampling else 0)(
                params,
                jnp.asarray(prompt),
                jnp.asarray(max(float(temperature), 1e-9), jnp.float32),
                jax.random.PRNGKey(int(seed)),
            )
        )
        return np.concatenate([prompt, new], axis=1)

    return generate


def build_train_step(
    cfg: TransformerConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    donate: bool = True,
) -> Callable:
    """One compiled SPMD train step:
    (params, opt_state, tokens, targets) → (params, opt_state, loss).

    Gradient sync: per-parameter psum over exactly the mesh axes the
    parameter is replicated on (the DistributedOptimizer semantics of the
    reference, generalized to a 4-D mesh).  The optimizer update runs on
    the sharded views under GSPMD propagation outside the shard_map.

    The loss is the model family's (``cfg.local_loss``), and so is what it
    counts beside it (an MoE family's routing statistics, the looped dense
    family's layer passes and mean exit step): the
    counts leave the compiled step with the loss and reach the process's
    counters (``parallel/moe.RoutingCounters``) without a blocking read.
    A family may DECLARE further leaves of the batch (``cfg.batch_leaves``,
    names; the block-diffusion family's ``("weights",)``, a f32 loss weight a
    token): the step then takes them after ``targets``, each (batch, seq) and
    sharded as the rows are, and hands them to ``cfg.local_loss`` in order.
    A family that declares none lowers to the text it always did.
    The returned step has the compiled function's ``lower``.

    The step compiles ONE program.  It returns the optimizer state on the
    mesh (``NamedSharding``); a state made as the examples make it,
    ``jax.jit(optimizer.init)(params)``, arrives as uncommitted single-device
    arrays, which ``jax.jit`` keys another program by.  So the first call
    commits what arrives uncommitted — a leaf of a parameter's shape on the
    parameter's spec, every other leaf replicated — and runs the program
    every later call runs.  A committed leaf is left where it is; one whose
    spec names a mesh axis of size one is handed on under the spec without it,
    the same placement as jax names it on the way back (``home``).  No leaf is
    copied.
    """
    validate_mesh(cfg, mesh)
    specs = param_specs(cfg)
    leaves = tuple(getattr(cfg, "batch_leaves", ()))

    def loss_and_grad(params, tokens, targets, *more):
        # With VMA checking on, shard_map AD handles gradient sync itself:
        # cotangents of replicated (invariant-typed) params are psum'd over
        # exactly the axes they're replicated on — the DistributedOptimizer
        # allreduce falls out of the type system, no manual collectives.
        def forward(p):
            # the backward pass reads transpose(jvp(forward)) in a trace
            with jax.named_scope("forward"):
                return cfg.local_loss(mesh, p, tokens, targets, *more)

        return jax.value_and_grad(forward, has_aux=True)(params)

    shmapped = jax.shard_map(
        loss_and_grad,
        mesh=mesh,
        in_specs=(specs, P("dp", "sp"), P("dp", "sp")) + (P("dp", "sp"),) * len(leaves),
        out_specs=((P(), P()), specs),
        check_vma=True,
    )

    # the name is the trace's module line (jit_train_step) and part of the
    # compile cache's key, which ignores scopes (see optim.py)
    def train_step(params, opt_state, tokens, targets, *more):
        if len(more) != len(leaves):
            raise TypeError(f"the step's batch is {('tokens', 'targets') + leaves}: "
                            f"{2 + len(more)} leaves given")
        (loss, counts), grads = shmapped(params, tokens, targets, *more)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss, counts

    jitted = jax.jit(train_step, donate_argnums=(0, 1) if donate else ())

    def home(spec):
        # The spec as jax reads it back off a compiled program's results: a
        # mesh axis of size one leaves no trace there, nor does a trailing
        # None.  A leaf that goes in under another name for the same placement
        # (this family's own layouts name pp and tp on any mesh) comes back
        # under this one, and the second call is keyed apart from the first.
        dims = []
        for entry in spec:
            names = tuple(a for a in ((entry,) if isinstance(entry, str) else entry or ())
                          if mesh.shape[a] > 1)
            dims.append(names[0] if len(names) == 1 else names or None)
        while dims and dims[-1] is None:
            dims.pop()
        return NamedSharding(mesh, P(*dims))

    def commit(params, opt_state, *batch):
        # no second copy of the moments: the committed array takes the
        # uncommitted one's buffer where the step may (it donates its state),
        # and shares it where the devices are the same otherwise
        held = {"donate": True} if donate else {"may_alias": True}

        def place(leaf, sharding):
            # a committed leaf stays where the caller put it; only another
            # name for the placement it has is exchanged for jax's own
            if getattr(leaf, "committed", False) and (
                    leaf.sharding == sharding
                    or not sharding.is_equivalent_to(leaf.sharding, leaf.ndim)):
                return leaf
            return jax.device_put(leaf, sharding, **held)

        # which of the state's leaves are parameter-shaped is the optimizer's to say
        opt_state = jax.tree.map(place, opt_state, optax.tree_map_params(
            optimizer, lambda _, spec: home(spec), opt_state, specs,
            transform_non_params=lambda _: home(P())))
        params = jax.tree.map(place, params, {k: home(spec) for k, spec in specs.items()})
        return (params, opt_state) + batch

    def fold(out):
        # a device_get of whatever is ready: a place a host stall can hide
        routing_counters().push(out[3])
        return out[:3]

    return stepped(jitted, fold, first=commit)
