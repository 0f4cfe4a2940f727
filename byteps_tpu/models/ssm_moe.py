"""State-space / attention / mixture-of-experts hybrid family (Nemotron-H's
block, ``model_type: nemotron_h``, as Nemotron-Labs-TwoTower-30B-A3B publishes
its tower) — the layers behind ``build_train_step``.

A layer is ONE part behind one norm and one residual add, ``h ← h +
part(norm(h))``, and ``layer_types[i]`` (a character of the published
``hybrid_override_pattern``) says which:

``"M"``  a Mamba-2 mixer: ``in_proj`` to [z | x B C | dt]; a depthwise causal
         convolution of ``conv_kernel`` taps WITH bias over x B C, then silu;
         x as ``ssm_heads`` heads of ``ssm_head_dim``, B and C as
         ``ssm_groups`` groups of ``ssm_state`` (a group serves heads in a
         row); ``dt = softplus(dt + dt_bias)``, ``A = −exp(A_log)`` a head;
         the selective scan ``h_t = exp(dt_t A) h_{t−1} + dt_t B_t ⊗ x_t``,
         ``y_t = h_tᵀ C_t + D x_t`` (``ops/ssd.py``, chunks of ``chunk``);
         ``RMSNorm(y · silu(z))`` in ``ssm_groups`` groups of channels;
         ``out_proj``.
``"*"``  plain grouped-query softmax attention, causal, NO positional
         encoding, no bias, no head norm, each key/value head serving its
         group of query heads (16 at the published sizes;
         ``ops/flash_attention.py``).
``"E"``  ``top_k`` of ``n_experts`` experts by sigmoid scores — the largest of
         ``score + router_bias``, the unbiased scores of the chosen
         renormalised and scaled (``parallel/moe.sigmoid_topk_route``) — each
         an UNGATED MLP ``down(relu(up x)²)``, beside one shared expert of the
         same form that every token takes at weight 1.

Bias-free but for the convolution, RMSNorm ``w · x / rms(x)``, untied head, no
embedding scale, no position table, no auxiliary loss.

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is (the share of experts and vocabulary this device holds, the
protocol of a family with listed layers, what the families share).
Parameters are stacked by kind (``ssm``, ``attn``, ``moe``), layer ``i`` takes
the next entry of its ONE stack, and every layer is rebuilt in the backward
pass on its own (the attention keeping its kernel's output and row
statistics, a Mamba-2 mixer the scan kernel's output and chunk states:
``ops/ssd.SAVED``).  The untied head is laid out as the embedding is, (vocabulary,
model).  The plain reference is ``models/ssm_moe_reference.py``.
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
from byteps_tpu.models.moe_family import rms
from byteps_tpu.ops.causal_conv import conv_silu
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.ssd import CHUNK, SAVED as SSD_SAVED, ssd_scan
from byteps_tpu.parallel.moe import sigmoid_topk_route

#: ``layer_types`` entry (``hybrid_override_pattern``'s characters) → the
#: stack that holds that layer's parameters
PARTS = {"M": "ssm", "*": "attn", "E": "moe"}


def relu2(h):
    """``relu(h)²`` (``mlp_hidden_act: relu2``)."""
    return jnp.square(jax.nn.relu(h))


@dataclasses.dataclass(frozen=True)
class SsmMoEConfig(mf.PatternedFamily):
    vocab_size: int = 131072  # rows of the vocabulary held here
    d_model: int = 2688
    layer_types: Tuple[str, ...] = tuple("MEMEM*EME")
    # the Mamba-2 mixers
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8  # B and C a group; the gated norm's groups too
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = CHUNK
    dt_min: float = 1e-3  # the step sizes the mixers start at: log-uniform between
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    #: the layers whose count the residual branches' start is scaled by
    #: (``rescale_prenorm_residual``: ``out_proj`` ÷ √layers): the whole
    #: model's, not the share's
    residual_layers: int = 52
    # the attention layers
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # the experts
    d_expert: int = 1856
    d_shared: int = 3712
    n_experts: int = 128  # the router's width: the model's routed experts
    experts_held: int = 128  # of them, held here: [expert_lo, expert_lo + held)
    expert_lo: int = 0
    top_k: int = 6
    routed_scale: float = 2.5
    norm_eps: float = 1e-5
    max_seq: int = 8192
    compute_dtype: Any = jnp.float32
    remat: bool = True

    mixers = PARTS
    n_dense_layers = 0  # no layer is a pair
    family = "state-space"
    lacks = ("expert exchange, pipeline split, head sharding or hand-over of a mixer's state "
             "and convolution tail between sequence shards")

    def __post_init__(self):
        super().__post_init__()
        self._check_grouped_heads()
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(f"{self.ssm_heads} state-space heads are no multiple of "
                             f"{self.ssm_groups} groups")

    def kinds(self) -> Tuple[Tuple[str], ...]:
        """Layer by layer, the ONE stack it reads."""
        return tuple((PARTS[t],) for t in self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """What the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


def tiny_ssm_moe(**kw) -> SsmMoEConfig:
    """The CPU tests' preset: every mechanism, toy widths, two chunks a
    sequence, two heads a group, three query heads a key/value head, 8 experts
    top-2 beside a shared expert twice as wide."""
    base = dict(vocab_size=96, d_model=32, layer_types=tuple("MEM*E"),
                ssm_heads=4, ssm_head_dim=6, ssm_groups=2, ssm_state=5, chunk=8,
                residual_layers=5, n_heads=6, n_kv_heads=2, head_dim=8,
                d_expert=16, d_shared=32, n_experts=8, experts_held=8, top_k=2, max_seq=16)
    base.update(kw)
    return SsmMoEConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; ``<stack>.<name>`` carries the stack's layers as
# leading dim, in the order the layers come
# ---------------------------------------------------------------------------


def stacks(cfg: SsmMoEConfig) -> Dict[str, Tuple[int, Dict[str, tuple]]]:
    """stack name → (layers, per-layer shapes), the stacks some layer reads.
    ``w_in``'s columns are [z | x | B | C | dt], the published ``in_proj``'s."""
    d, di, hs = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    fe, fs, e = cfg.d_expert, cfg.d_shared, cfg.experts_held
    shapes = {
        "ssm": {"norm": (d,), "w_in": (d, di + cfg.conv_channels + hs),
                "conv": (cfg.conv_kernel, cfg.conv_channels), "conv_bias": (cfg.conv_channels,),
                "dt_bias": (hs,), "a_log": (hs,), "d_skip": (hs,), "gate_norm": (di,),
                "w_out": (di, d)},
        "attn": {"norm": (d,), "wq": (d, h, hd), "wk": (d, kv, hd), "wv": (d, kv, hd),
                 "wo": (h, hd, d)},
        "moe": {"norm": (d,), "router": (d, cfg.n_experts), "router_bias": (cfg.n_experts,),
                "e_up": (e, d, fe), "e_down": (e, fe, d), "s_up": (d, fs), "s_down": (fs, d)},
    }
    return cfg.stack_sizes(shapes)


def layouts(cfg: SsmMoEConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``).  ``head`` is laid out as the
    embedding is, (vocabulary, model)."""
    v, d = cfg.vocab_size, cfg.d_model
    return mf.layouts({"embed": (v, d), "norm_f": (d,), "head": (v, d)}, stacks(cfg))


def _init(cfg: SsmMoEConfig) -> Dict[str, Any]:
    """How the leaves start — the published initialiser where it says, the
    published modules' own defaults where it does not: ``A_log = log U(1,
    16)``; ``dt_bias`` the inverse softplus of a step size log-uniform in
    [dt_min, dt_max] (floor dt_floor); ``D = 1``; ``out_proj`` ÷
    √``residual_layers`` (``rescale_prenorm_residual``); every matrix as
    ``nn.Linear`` starts (``moe_family.linear``), the convolution's taps N(0,
    1/kernel) and their bias as ``nn.Conv1d``'s (U(±1/√kernel)), the embedding
    as ``nn.Embedding`` does, N(0, 1); the selection bias 0; ones for the
    norms' scales.

    Why not the other families' N(0, 1 / fan-in) matrices: an ungated
    ``relu(u)²`` is never negative, so an expert's output has a part that all
    tokens share (the mean of its hidden units through ``down``), and at unit
    variance the shared expert alone adds 1.2 an element to a stream whose
    embedding is 1: by the third expert layer the seeded router sends the
    held experts 0.43 to 1.47 of the even load and one of them up to 55 % of
    it.  At a third of that variance the common part is a tenth of the
    router's input, every layer holds 0.76 to 1.12 of the even load and the
    fullest expert 14 to 21 % (counted on the CPU at the published widths and
    2 x 2048 tokens, three of the cell's seeds: PERF.md §6, PR 51): the
    near-uniform router a deployment's balanced one stands for.  The
    embedding at N(0, 1), ``early_route_moe``'s choice, serves the same end:
    a token's own row leads the stream."""
    linear = mf.linear

    def dt_bias(key, shape):
        lo, hi = math.log(cfg.dt_min), math.log(cfg.dt_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key(), shape, jnp.float32, lo, hi)),
                         cfg.dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    def w_out(key, shape):
        return linear(-2)(key, shape) / math.sqrt(cfg.residual_layers)

    return {"*norm*": mf.ones, "embed": mf.normal(1.0), "head": linear(-1),
            "w_in": linear(-2), "w_out": w_out, "conv": mf.fan_in(-2),
            # a depthwise channel's fan-in is its taps
            "conv_bias": mf.uniform(cfg.conv_kernel ** -0.5),
            "wq": linear(-3), "wk": linear(-3), "wv": linear(-3), "wo": linear(-3, -2),
            **dict.fromkeys(("router", "e_up", "e_down", "s_up", "s_down"), linear(-2)),
            "dt_bias": dt_bias, "a_log": mf.log_uniform(1.0, 16.0), "d_skip": mf.ones,
            "router_bias": mf.zeros}


def init_params(cfg: SsmMoEConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :func:`_init`."""
    return mf.init_params(layouts(cfg), key, _init(cfg))


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def over_runs(stat, width: int):
    """stat (..., G) → (..., G · width): each value over its run of ``width``
    channels, as a chain of selects on the channel's run — elementwise, so it
    fuses into whoever reads it.  (``jnp.repeat`` is a broadcast to (..., G,
    width) and a reshape, and on a TPU, whose tiles lie over the last two
    dims, that reshape is a copy: 268 MB broadcast and 268 MB copied a call at
    2 x 8192 tokens, 1.23 ms, three times a layer — PERF.md §6, PR 62.)  Its
    transpose is the sum over each run."""
    run = lax.broadcasted_iota(jnp.int32, (stat.shape[-1] * width,), 0) // width
    out = stat[..., :1]
    for i in range(1, stat.shape[-1]):
        out = jnp.where(run == i, stat[..., i:i + 1], out)
    return jnp.broadcast_to(out, (*stat.shape[:-1], run.shape[0]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def sum_runs(x, width: int):
    """x (..., G · width) → (..., G): the sum over each run of ``width``
    channels — :func:`over_runs`' transpose, and it its."""
    run = lax.broadcasted_iota(jnp.int32, (x.shape[-1],), 0) // width
    return jnp.stack([jnp.sum(jnp.where(run == i, x, 0.0), axis=-1)
                      for i in range(x.shape[-1] // width)], axis=-1)


over_runs.defvjp(lambda stat, width: (over_runs(stat, width), None),
                 lambda width, _, ct: (sum_runs(ct, width),))
sum_runs.defvjp(lambda x, width: (sum_runs(x, width), None),
                lambda width, _, ct: (over_runs(ct, width),))


def grouped_gated_norm(y, z, w, groups: int, eps: float):
    """``w · g / rms(g)`` of ``g = y · silu(z)``, the statistics taken over
    each of ``groups`` runs of channels on their own (the gate goes on BEFORE
    the norm; ``delta_moe``'s norms first and gates after).  y, z (..., C);
    f32 inside, returns f32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    width = g.shape[-1] // groups
    mean_square = sum_runs(jnp.square(g), width) / width
    return g * over_runs(lax.rsqrt(mean_square + eps), width) * w


def _ssd_part(cfg: SsmMoEConfig, zxbcdt, lp):
    """The Mamba-2 mixer between its projections: ``zxbcdt`` (B, S, d_inner +
    conv_channels + heads) in the compute dtype → what ``w_out`` takes,
    (B, S, d_inner) in the compute dtype.  Token-major from end to end."""
    cdt, f32 = cfg.compute_dtype, jnp.float32
    di, gn = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    hs, hp = cfg.ssm_heads, cfg.ssm_head_dim
    z, dt = zxbcdt[..., :di], zxbcdt[..., -hs:]

    def conved(lo, hi):
        """Channels lo:hi of x | B | C after the convolution, its bias and silu."""
        return conv_silu(zxbcdt, lp["conv"][:, lo:hi], lp["conv_bias"][lo:hi],
                         lo=di + lo, hi=di + hi)

    # the convolution is a channel's own: x, B and C each from their columns,
    # so that no one array of the three is written to be cut again
    x, b, c = conved(0, di), conved(di, di + gn), conved(di + gn, di + 2 * gn)
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"])
    y = ssd_scan(x, dt, -jnp.exp(lp["a_log"]), b, c, hs, cfg.ssm_groups,
                 chunk=cfg.chunk, compute_dtype=cdt)  # f32
    y = y + jnp.repeat(lp["d_skip"], hp) * x.astype(f32)
    return grouped_gated_norm(y, z, lp["gate_norm"], cfg.ssm_groups, cfg.norm_eps).astype(cdt)


def _ssm_layer(cfg: SsmMoEConfig, x, lp):
    """x (B, S, D) → ``x + mamba2(norm(x))``."""
    cdt = cfg.compute_dtype
    with jax.named_scope("ssm_proj"):
        zxbcdt = rms(x, lp["norm"], cfg.norm_eps).astype(cdt) @ lp["w_in"].astype(cdt)
    with jax.named_scope("ssd_scan"):
        g = _ssd_part(cfg, zxbcdt, lp)
    with jax.named_scope("ssm_proj"):
        return x + (g @ lp["w_out"].astype(cdt)).astype(x.dtype)


def _attention_layer(cfg: SsmMoEConfig, x, lp):
    """x (B, S, D) → ``x + attention(norm(x))``: causal, no positions."""
    cdt, hd = cfg.compute_dtype, cfg.head_dim
    with jax.named_scope("nope16_attention"):
        a = rms(x, lp["norm"], cfg.norm_eps).astype(cdt)
        q, k, v = (jnp.einsum("bsd,dhk->bhsk", a, lp[w].astype(cdt)) for w in ("wq", "wk", "wv"))
        # the kernels find a query head's key/value head themselves: K and V
        # go in at their own head count
        o = flash_attention(q, k, v, causal=True, scale=hd ** -0.5)
        y = jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(cdt))
        return x + y.astype(x.dtype)


def _route(cfg: SsmMoEConfig, g32, lp):
    return sigmoid_topk_route(g32, lp["router"], lp["router_bias"], cfg.top_k, cfg.routed_scale)


def _expert_layer(cfg: SsmMoEConfig, x, lp):
    """x (B, S, D) → ``x + experts(norm(x))`` and the routing stats."""
    b, s, d = x.shape
    with jax.named_scope("moe_experts"):  # the layer's norm is filed with the experts
        g32 = rms(x, lp["norm"], cfg.norm_eps).reshape(b * s, d)
    # cast once: both kinds of expert read this copy
    y, stats = mf.routed_mlp(cfg, g32, g32.astype(cfg.compute_dtype), lp,
                             functools.partial(_route, cfg), "shared_expert", act=relu2)
    return x + y.reshape(b, s, d).astype(x.dtype), stats


def _hidden(cfg: SsmMoEConfig, params, tokens):
    """tokens (B, S) → the stack's output before the final norm, and the
    routing stats summed over the layers."""
    run = {"ssm": lambda x, lp: _ssm_layer(cfg, x, lp),
           "attn": lambda x, lp: _attention_layer(cfg, x, lp),
           "moe": lambda x, lp: _expert_layer(cfg, x, lp)}
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.compute_dtype)
    return mf.walk(cfg, run, {"ssm": SSD_SAVED, "attn": mf.FLASH_SAVED}, params, x)


def local_logits(cfg: SsmMoEConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    x, _ = _hidden(cfg, params, tokens)
    return mf.row_logits(cfg, x, params["norm_f"], params["head"])


def local_loss(cfg: SsmMoEConfig, mesh: Mesh, params, tokens, targets):
    """The global mean next-token cross-entropy, identical on every rank, and
    the step's routing stats (ROUTING_STATS name → int32) summed over the
    data-parallel ranks."""
    x, stats = _hidden(cfg, params, tokens)
    return mf.mean_loss(
        *mf.xent_sums(cfg, mf.row_logits, x, targets, params["norm_f"], params["head"]), stats)
