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

This device holds the experts ``[expert_lo, expert_lo + experts_held)`` of
every layer and the first ``vocab_size`` rows of the vocabulary: its share of
a deployment in which several devices share each layer.  The router scores
all ``n_experts``; what the experts held elsewhere would add is left out
(``parallel/moe.held_expert_mlp``).

``transformer.build_train_step`` / ``build_forward`` take a
:class:`DeltaMoEConfig` as they take a ``TransformerConfig``: the config
answers for its family with the parameter table (:func:`layouts`), the mesh
checks, the per-device loss (:func:`local_loss`) and logits.  The stack is
one ``lax.scan`` over the periods, every mixer and every MLP in it rebuilt in
the backward pass.  The plain reference is ``models/delta_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.ops.flash_attention import SAVED as FLASH_SAVED
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.gated_delta import CHUNK, chunked_gated_delta_rule
from byteps_tpu.parallel.moe import ROUTING_STATS, held_expert_mlp, softmax_topk_route

_ALL_AXES = ("dp", "pp", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class DeltaMoEConfig:
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

    def __post_init__(self):
        if self.n_layers % self.full_attention_interval:
            raise ValueError(f"{self.n_layers} layers are no whole number of periods of "
                             f"{self.full_attention_interval}")
        if not 0 <= self.expert_lo <= self.n_experts - self.experts_held:
            raise ValueError(
                f"held experts [{self.expert_lo}, {self.expert_lo + self.experts_held}) "
                f"lie outside the router's {self.n_experts}")
        if self.n_heads % self.n_kv_heads or self.lin_v_heads % self.lin_k_heads:
            raise ValueError("query heads must be a multiple of key/value heads, and the "
                             "rule's value heads of its key heads")
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

    # what transformer.build_train_step / build_forward ask of a family
    def layouts(self) -> Dict[str, Tuple]:
        return layouts(self)

    def validate_mesh(self, mesh: Mesh) -> None:
        validate_mesh(self, mesh)

    def local_loss(self, mesh: Mesh, params, tokens, targets):
        return local_loss(self, mesh, params, tokens, targets)

    def local_logits(self, mesh: Mesh, params, tokens):
        return local_logits(self, params, tokens)[None]  # one microbatch, no pipeline


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
    """name → (global shape, partition spec, gradient sync axes), as
    ``transformer._layouts`` gives them.  Everything is replicated: this
    family runs data-parallel only so far (:func:`validate_mesh`)."""
    d, v = cfg.d_model, cfg.vocab_size
    shapes = {"embed": (v, d), "norm_f": (d,), "head": (d, v)}
    lead = {"lin": (cfg.n_periods, cfg.full_attention_interval - 1), "full": (cfg.n_periods,)}
    for kind, per_layer in layer_shapes(cfg).items():
        if math.prod(lead[kind]):
            shapes.update({f"{kind}.{k}": lead[kind] + s for k, s in per_layer.items()})
    return {k: (s, P(), _ALL_AXES) for k, s in shapes.items()}


def init_params(cfg: DeltaMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device): N(0,
    1/fan_in) matrices, 0.02 for the embedding, N(0, 1/kernel) convolution
    taps, 0 for the RMSNorms' ``1 + w`` scales and 1 for the gated norm's,
    ``A_log = log U(0, 16)`` and ``dt_bias = 1``."""
    params = {}
    for i, (name, (shape, _, _)) in enumerate(layouts(cfg).items()):
        leaf, k = name.rsplit(".", 1)[-1], jax.random.fold_in(key, i)
        if leaf in ("gdn_norm", "dt_bias"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif "norm" in leaf:
            params[name] = jnp.zeros(shape, jnp.float32)
        elif leaf == "a_log":
            params[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1e-3, 16.0))
        else:
            # the contracted dims: wo its two before the last, the taps the kernel
            if leaf == "wo":
                fan_in = math.prod(shape[-3:-1])
            else:
                fan_in = shape[{"wq": -3, "wk": -3, "wv": -3, "shared_gate": -1}.get(leaf, -2)]
            std = 0.02 if name == "embed" else fan_in ** -0.5
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
    return params


def validate_mesh(cfg: DeltaMoEConfig, mesh: Mesh) -> None:
    for ax in ("pp", "sp", "tp"):
        if mesh.shape.get(ax, 1) != 1:
            raise ValueError(
                f"the gated-delta MoE family runs data-parallel only: mesh has "
                f"{ax}={mesh.shape[ax]} (no expert exchange, pipeline split, head sharding "
                "or state hand-over between sequence shards is built for it yet)")


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _rms(x, w, eps: float):
    """RMSNorm with a ``1 + w`` scale and f32 statistics; returns f32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def _inv_l2(x, eps: float = 1e-6):
    """1 / ‖x‖ over the last dim, kept."""
    return lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _head_tiles(x, n: int):
    """x (B, S, n·d) → (B, S/8, n, 8, d): a head's lanes last, eight tokens
    above them (fewer where 8 does not divide S).  That is the (8, 128) tile a
    token-major array already lies in on a TPU, so the view moves nothing and
    a reduction over d stays inside a tile; (B, S, n, d) is another layout
    there and costs a copy each way (PERF.md §6, PR 45)."""
    b, s, c = x.shape
    rows = math.gcd(s, 8)
    return x.reshape(b, s // rows, rows, n, c // n).transpose(0, 1, 3, 2, 4)


def _tokens(t):
    """:func:`_head_tiles` back: (B, S/r, n, r, d) → (B, S, n·d)."""
    b, m, n, rows, d = t.shape
    return t.transpose(0, 1, 3, 2, 4).reshape(b, m * rows, n * d)


def _per_head(x, n: int, stat):
    """``stat`` (over the last dim, kept) of each of the n heads of x
    (B, S, n·d), on every lane of its head: (B, S, n·d).  The one thing in
    the linear mixer that needs the tiles' view."""
    tiles = _head_tiles(x, n)
    return _tokens(jnp.broadcast_to(stat(tiles), tiles.shape))


def rope_partial(x, rotary_dim: int, theta: float):
    """Rotary embedding on the first ``rotary_dim`` of the last dim of x
    (..., S, d), the rest untouched.  Half-rotation pairing: dimension i is
    paired with i + rotary_dim/2, both rotated by ``pos · theta^(-2i/rotary_dim)``."""
    s, half = x.shape[-2], rotary_dim // 2
    freqs = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(half, dtype=jnp.float32) * 2 / rotary_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]  # (S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b, rest = x32[..., :half], x32[..., half:rotary_dim], x32[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1).astype(x.dtype)


def causal_conv(x, taps):
    """Depthwise causal convolution along the sequence: x (B, S, C), taps
    (K, C) f32; ``y_t = Σ_j taps[j] · x_{t-K+1+j}``, zeros before the start
    (the last tap weighs the present token, as ``Conv1d``'s does).  It
    serves two families and both tap counts: this one's 4 taps under a silu,
    and ``models/conv_moe.py``'s 3 taps between two gates
    (tests/test_conv_moe_pieces.py holds the 3-tap case by hand).
    The shifted copies are taken in x's dtype and multiplied in f32 (on the
    chip 6 ms a layer less than shifting an f32 copy: PERF.md §6, PR 36)."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s].astype(jnp.float32) * taps[j] for j in range(k))


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


@jax.custom_vjp
def _conv_rounded(x, taps):
    """:func:`causal_conv` rounded to x's dtype (the taps in f32, their sum
    rounded: what the backward pass keeps of the channels is in the compute
    dtype), with the backward pass written as the convolution is itself:
    ``dx_t = Σ_j taps[j] · dy_{t+K-1-j}``, the shifted copies of ONE padded
    ``dy`` taken in its dtype, multiplied and summed in f32, rounded once.
    (Autodiff pads each tap's rounded product apart and adds the four in x's
    dtype: nine passes over the channels where this makes two.)"""
    return causal_conv(x, taps).astype(x.dtype)


def _conv_fwd(x, taps):
    return _conv_rounded(x, taps), (x, taps)


def _conv_bwd(res, dy):
    x, taps = res
    k, s = taps.shape[0], x.shape[1]
    ahead = jnp.pad(dy, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(ahead[:, k - 1 - j:k - 1 - j + s].astype(jnp.float32) * taps[j] for j in range(k))
    (dtaps,) = jax.vjp(lambda t: causal_conv(x, t), taps)[1](dy.astype(jnp.float32))
    return dx.astype(x.dtype), dtaps


_conv_rounded.defvjp(_conv_fwd, _conv_bwd)


def _delta_scan(cfg: DeltaMoEConfig, qkvz, ba, lp):
    """The linear mixer between its projections: ``qkvz`` (B, S, channels +
    H_v·d_v) in the compute dtype and ``ba`` (B, S, 2·H_v) f32 → what
    ``w_out`` takes, (B, S, H_v·d_v) in the compute dtype.  Token-major from
    end to end: a head is a run of lanes, the rule's (B, S, n, d) a reshape
    that its own undoes, and the statistics a head are taken on the tiles'
    view (:func:`_per_head`); each of q, k, v is convolved from its own
    columns of ``qkvz``, so that nothing computed is split afterwards."""
    cdt, f32 = cfg.compute_dtype, jnp.float32
    hk, hv, dk, dv = cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim
    b, s, _ = qkvz.shape

    def convolved(lo, hi):
        return jax.nn.silu(_conv_rounded(qkvz[..., lo:hi], lp["conv"][:, lo:hi]).astype(f32))

    q, k = convolved(0, hk * dk), convolved(hk * dk, 2 * hk * dk)
    q = (q * _per_head(q, hk, _inv_l2) * dk ** -0.5).astype(cdt)
    k = (k * _per_head(k, hk, _inv_l2)).astype(cdt)
    v = convolved(2 * hk * dk, cfg.lin_channels).astype(cdt)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(ba[..., hv:] + lp["dt_bias"])
    # each key head serves lin_v_heads / lin_k_heads value heads
    o = chunked_gated_delta_rule(
        q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk), v.reshape(b, s, hv, dv), g, beta,
        chunk=cfg.chunk, compute_dtype=cdt).reshape(b, s, hv * dv)  # f32
    # the gated norm: over each head's values, one scale for all heads
    o = jnp.tile(lp["gdn_norm"], hv) * o * _per_head(o, hv, lambda head: lax.rsqrt(
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
    cdt = cfg.compute_dtype
    g = g32.astype(cdt)
    with jax.named_scope("moe_route"):
        ids, weights = softmax_topk_route(g32, lp["router"], cfg.top_k)
    with jax.named_scope("moe_experts"):
        y, stats = held_expert_mlp(
            g, ids, weights, *(lp[w].astype(cdt) for w in ("e_gate", "e_up", "e_down")),
            lo=cfg.expert_lo, n_experts=cfg.n_experts)
    with jax.named_scope("moe_shared"):
        shared = _swiglu(g, *(lp[w].astype(cdt) for w in ("s_gate", "s_up", "s_down")))
        open_ = jax.nn.sigmoid(jnp.dot(g, lp["shared_gate"].astype(cdt),
                                       preferred_element_type=jnp.float32))[:, None]
    return y + open_ * shared.astype(jnp.float32), stats


def _mlp(cfg: DeltaMoEConfig, x, lp):
    b, s, d = x.shape
    g32 = _rms(x, lp["mlp_norm"], cfg.norm_eps).reshape(b * s, d)
    y, stats = expert_mlp(cfg, g32, lp)
    return x + y.reshape(b, s, d).astype(x.dtype), stats


def _hidden(cfg: DeltaMoEConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm, and the
    routing stats summed over the layers."""
    def kind(prefix):
        return {k.split(".", 1)[1]: v for k, v in params.items() if k.startswith(prefix + ".")}

    def residual(mixer):
        return lambda x, lp: x + mixer(cfg, x, lp).astype(x.dtype)

    delta, attention = residual(_delta_mixer), residual(_attention_mixer)
    mlp = lambda x, lp: _mlp(cfg, x, lp)  # noqa: E731
    if cfg.remat:
        # a layer's mixer and its MLP are each rebuilt in the backward pass,
        # one at a time (a period's four layers at once do not fit beside the
        # state at 16k tokens); of gated attention all but the kernel's
        # output and row statistics, so that the forward kernel does not run
        # twice
        delta, mlp = jax.checkpoint(delta), jax.checkpoint(mlp)
        attention = jax.checkpoint(
            attention, policy=jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED))

    def period(x, lps):
        stats = jnp.zeros((len(ROUTING_STATS),), jnp.int32)
        if lps["lin"]:
            x, each = lax.scan(lambda x, lp: mlp(delta(x, lp), lp), x, lps["lin"])
            stats = stats + jnp.sum(each, 0)
        x, last = mlp(attention(x, lps["full"]), lps["full"])
        return x, stats + last

    x = params["embed"][tokens].astype(cfg.compute_dtype)
    x, stats = lax.scan(period, x, {"lin": kind("lin"), "full": kind("full")})
    return x, jnp.sum(stats, 0)


def _logits(cfg: DeltaMoEConfig, x, scale, head):
    h = _rms(x, scale, cfg.norm_eps).astype(cfg.compute_dtype)
    return jnp.dot(h, head.astype(cfg.compute_dtype), preferred_element_type=jnp.float32)


def local_logits(cfg: DeltaMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    x, _ = _hidden(cfg, params, tokens)
    return _logits(cfg, x, params["norm_f"], params["head"])


#: rows of logits that stand at a time in the loss
ROW_BLOCK = 2048


def _xent_sums(cfg: DeltaMoEConfig, params, x, targets):
    """(sum of token cross-entropies, tokens counted); targets < 0 are
    ignored.  A block of rows at a time, each rebuilt in the backward pass:
    the (B·S, V) logits never stand whole."""
    d = x.shape[-1]
    rows = x.size // d
    block = math.gcd(rows, ROW_BLOCK)

    def one(xb, tb, scale, head):
        logits = _logits(cfg, xb, scale, head)
        gold = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * (tb >= 0))

    if cfg.remat:
        one = jax.checkpoint(one)
    scale, head = params["norm_f"], params["head"]
    total = jnp.sum(lax.map(lambda xs: one(*xs, scale, head),
                            (x.reshape(-1, block, d), targets.reshape(-1, block))))
    return total, jnp.sum(targets >= 0).astype(jnp.float32)


def local_loss(cfg: DeltaMoEConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (ROUTING_STATS name → int32) summed over the
    data-parallel ranks."""
    x, stats = _hidden(cfg, params, tokens)
    total, count = _xent_sums(cfg, params, x, targets)
    for ax in ("dp", "sp"):
        total, count, stats = lax.psum(total, ax), lax.psum(count, ax), lax.psum(stats, ax)
    return total / count, dict(zip(ROUTING_STATS, stats))
