"""Looped dense family (Ouro's block, ``model_type: ouro``, the looped
language model of arXiv 2510.25741) — the layers behind ``build_train_step``.

A stack of ``n_layers`` dense layers is run ``n_loops`` times on the SAME
weights: ``h⁰ = E[x]`` and ``hᵗ = norm_f(stack(hᵗ⁻¹))`` for t = 1 … n_loops;
every loop step has a head, ``logitsᵗ = hᵗ W_headᵀ``, and an exit gate,
``λᵗ = σ(hᵗ · w_g + b_g)``.  A layer is sandwich-normed, ``a = h +
norm(attention(norm(h)))`` then ``h' = a + norm(mlp(norm(a)))``: bias-free
softmax attention at ``n_heads`` | ``n_kv_heads`` heads of ``head_dim``, rope
over the whole head (half-rotation pairs), causal; a SwiGLU MLP; RMSNorm
``w · x / rms(x)``; an untied head.  A token's exit distribution over the
loop steps is ``pᵗ = λᵗ ∏_{j<t}(1 − λʲ)`` and ``p^L = ∏_{j<L}(1 − λʲ)`` (it
sums to 1; the last step's gate is not read), and the training loss is the
mean over the counted tokens of ``Σₜ pᵗ · CEᵗ − β · H(p)``: every loop step's
cross-entropy weighed by a LEARNED weight, less ``exit_beta`` times the
distribution's entropy.

A family behind ``transformer.build_train_step`` as ``models/moe_family.py``
says one is — its first without experts: no expert field, no routing
statistic; norm, SwiGLU, the blocked loss, ``init_params`` and the remat
policy are that module's.  How it runs: ``lax.scan`` over the loop steps
around ``lax.scan`` over the stacked layers, ONE set of parameters, each
leaf's gradient the sum of ``n_loops`` uses (the outer scan's backward pass
carries it).  The residual stream is float32 whatever ``compute_dtype`` says
— the matrix products' operands are rounded to that; the stream that every
layer pass of every loop step adds to, and every norm reads, is not.  Kept for the
backward pass, a (loop step, layer): the layer's input, f32 — the layer is
rebuilt whole from it — and the flash kernel's output and row statistics;
beside them the ``n_loops`` normed outputs, f32.  The heads are ONE
call of the blocked loss over ``n_loops`` × tokens rows
(``moe_family.weighted_xent``: the weights ``pᵗ`` go in, their gradient —
the gate's — is the rows' cross-entropies, no logits are held, each
block's gradient is taken where its logits stand and the head's gradient is
summed once).  What the step counts beside its loss: ``looped_layer_passes``
(layers × loop steps run) and ``looped_exit_step_milli`` (the mean over the
counted tokens of ``Σₜ t · pᵗ`` in thousandths: 1875 at λ = ½ and four loops),
which reach the process's counters as the MoE families' routing statistics do
— summed over steps, so the second is a sum of per-step means and is read as
growth over a number of steps (docs/observability.md).
``early_exit_threshold`` acts at inference alone and is no part of a training
step; ``local_logits`` gives the last loop step's.  The plain reference is
``models/looped_dense_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from byteps_tpu.models import moe_family as mf
from byteps_tpu.models.moe_family import rms, swiglu
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.head_norm import head_rope
from byteps_tpu.parallel.moe import take_rows, varying

#: what a step counts, in the order :func:`local_loss` sums them over the ranks
COUNTS = ("looped_layer_passes", "looped_exit_step_milli")


@dataclasses.dataclass(frozen=True)
class LoopedDenseConfig(mf.Family):
    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48  # the stack that is looped
    n_loops: int = 4  # total_ut_steps: times the stack runs
    exit_beta: float = 0.1  # the entropy term's weight in the loss
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    d_ff: int = 5632
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq: int = 8192
    compute_dtype: Any = jnp.float32
    remat: bool = True

    family = "looped dense"
    lacks = ("ring of pipeline stages that a microbatch crosses once a loop step, head "
             "sharding or sequence split")

    def __post_init__(self):
        self._check_grouped_heads()
        self._check_even_rope("head_dim")
        if self.n_loops < 1 or self.n_layers < 1:
            raise ValueError(f"a looped stack runs at least one layer once, got "
                             f"{self.n_layers} layers x {self.n_loops} loops")


def tiny_looped_dense(**kw) -> LoopedDenseConfig:
    """The CPU tests' preset: every mechanism, toy widths — two layers run
    three times, two query heads a key/value head."""
    base = dict(vocab_size=96, d_model=64, n_layers=2, n_loops=3, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=96, max_seq=16)
    base.update(kw)
    return LoopedDenseConfig(**base)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; ``layer.<name>`` carries the layers as leading dim
# ---------------------------------------------------------------------------


def layouts(cfg: LoopedDenseConfig) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes): every leaf
    replicated (``moe_family.layouts``).  ``norm`` | ``post_norm`` stand
    before | after the attention, ``mlp_norm`` | ``mlp_post_norm`` around the
    MLP; ``head`` is laid out as the embedding is, (vocabulary, model)."""
    v, d, h, kv, hd, f = (cfg.vocab_size, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, cfg.d_ff)
    layer = {"norm": (d,), "wq": (d, h, hd), "wk": (d, kv, hd), "wv": (d, kv, hd),
             "wo": (h, hd, d), "post_norm": (d,), "mlp_norm": (d,), "w_gate": (d, f),
             "w_up": (d, f), "w_down": (f, d), "mlp_post_norm": (d,)}
    return mf.layouts({"embed": (v, d), "norm_f": (d,), "head": (v, d), "gate_w": (d,),
                       "gate_b": ()}, {"layer": (cfg.n_layers, layer)})


#: how the leaves start, beside ``moe_family.INIT_RULES``: ones for the norms'
#: scales; the head contracts its last dim; the exit gate at zero, λ = ½ at
#: every loop step; the embedding N(0, 1) (``torch.nn.Embedding``'s own start)
#: and not the MoE families' 0.02 — every branch leaves its second norm at
#: unit size, so at 0.02 a token's own row would be a fiftieth of the stream
#: after the first attention, whose mean over the keys is common to all tokens
INIT = {"*norm*": mf.ones, "head": mf.fan_in(-1), "gate_w": mf.zeros, "gate_b": mf.zeros,
        "embed": mf.normal(1.0)}


def init_params(cfg: LoopedDenseConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), by
    :data:`INIT`."""
    return mf.init_params(layouts(cfg), key, INIT)


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def _attention_part(cfg: LoopedDenseConfig, x, lp):
    """x (B, S, D) → ``x + norm(attention(norm(x)))``."""
    cdt, hd, eps = cfg.compute_dtype, cfg.head_dim, cfg.norm_eps
    d = x.shape[-1]
    with jax.named_scope("loop_attention"):
        g = rms(x, lp["norm"], eps).astype(cdt)
        # a turned head's product stays token-major, its heads side by side:
        # the rotation's pass writes it head-major
        q, k = (head_rope(jnp.einsum("bsd,df->bsf", g, lp[w].astype(cdt).reshape(d, -1)),
                          hd, cfg.rope_theta) for w in ("wq", "wk"))
        v = jnp.einsum("bsd,dhk->bhsk", g, lp["wv"].astype(cdt))
        o = flash_attention(q, k, v, causal=True, scale=hd ** -0.5)
        y = jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(cdt))
        return x + rms(y, lp["post_norm"], eps).astype(x.dtype)


def _mlp_part(cfg: LoopedDenseConfig, x, lp):
    """x (B, S, D) → ``x + norm(swiglu(norm(x)))``."""
    cdt, eps = cfg.compute_dtype, cfg.norm_eps
    with jax.named_scope("loop_mlp"):
        g = rms(x, lp["mlp_norm"], eps).astype(cdt)
        y = swiglu(g, *(lp[w].astype(cdt) for w in ("w_gate", "w_up", "w_down")))
        return x + rms(y, lp["mlp_post_norm"], eps).astype(x.dtype)


def _loop_outputs(cfg: LoopedDenseConfig, params, tokens):
    """tokens (B, S) → every loop step's normed output (n_loops, B, S, D) f32
    — what its head and its gate read and the next loop step starts from — and
    the layer passes run."""
    layers = mf.stack_of(params, "layer")
    attention, mlp = functools.partial(_attention_part, cfg), functools.partial(_mlp_part, cfg)

    def final_norm(x, w):
        with jax.named_scope("loop_heads"):
            return rms(x, w, cfg.norm_eps)

    def layer(x, lp):
        return mlp(attention(x, lp), lp)

    if cfg.remat:
        # a layer rebuilt whole in the backward pass from its f32 input; kept
        # of it are the flash kernel's output and row statistics, of the final
        # norm its input
        layer = jax.checkpoint(layer, policy=mf.keep_flash())
        final_norm = jax.checkpoint(final_norm)

    def loop_step(carry, _):
        x, passes = carry
        x, _ = lax.scan(lambda x, lp: (layer(x, lp), None), x, layers)
        x = final_norm(x, params["norm_f"])
        return (x, passes + cfg.n_layers), x

    with jax.named_scope("embed"):
        rows = take_rows(varying(params["embed"], jax.typeof(tokens).vma), tokens.reshape(-1))
        x = rows.reshape(*tokens.shape, -1)
    with jax.named_scope("loop_steps"):
        passes = varying(jnp.zeros((), jnp.int32), jax.typeof(x).vma)
        (_, passes), hs = lax.scan(loop_step, (x, passes), None, length=cfg.n_loops)
    return hs, passes


def _head_logits(cfg: LoopedDenseConfig, h, head):
    """Logits of normed rows h with the (vocabulary, model) head, f32."""
    return lax.dot_general(h.astype(cfg.compute_dtype), head.astype(cfg.compute_dtype),
                           (((h.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def exit_distribution(hs, gate_w, gate_b):
    """hs (n_loops, ...rows, D), the loop steps' normed outputs → (p, log p),
    each (n_loops, ...rows) f32: ``pᵗ = λᵗ ∏_{j<t}(1 − λʲ)``, the last step
    taking what is left, from ``λᵗ = σ(hᵗ · w_g + b_g)``; built from
    ``log σ(±g)``, so no product underflows."""
    g = jnp.einsum("l...d,d->l...", hs, gate_w,
                   precision=lax.Precision.HIGHEST) + gate_b
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)  # log ∏_{j<=t} (1 − λʲ)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    log_p = jnp.concatenate([jax.nn.log_sigmoid(g[:-1]) + before[:-1], before[-1:]], axis=0)
    return jnp.exp(log_p), log_p


def loop_logits(cfg: LoopedDenseConfig, params, tokens):
    """(B, S) → every loop step's logits (n_loops, B, S, V) f32 and the exit
    distribution (n_loops, B, S)."""
    hs, _ = _loop_outputs(cfg, params, tokens)
    p, _ = exit_distribution(hs, params["gate_w"], params["gate_b"])
    return _head_logits(cfg, hs, params["head"]), p


def local_logits(cfg: LoopedDenseConfig, params, tokens):
    """(B, S) → (B, S, V) f32 logits of the last loop step: what the model
    gives at the published ``early_exit_threshold`` of 1."""
    hs, _ = _loop_outputs(cfg, params, tokens)
    return _head_logits(cfg, hs[-1], params["head"])


def local_loss(cfg: LoopedDenseConfig, mesh: Mesh, params, tokens, targets):
    """The global mean over the counted tokens of ``Σₜ pᵗ CEᵗ − β H(p)``,
    identical on every rank, and what the step counts (:data:`COUNTS` name →
    int32)."""
    hs, passes = _loop_outputs(cfg, params, tokens)
    with jax.named_scope("exit_gate"):
        # rebuilt in the backward pass from the kept outputs
        distribution = jax.checkpoint(exit_distribution) if cfg.remat else exit_distribution
        p, log_p = distribution(hs, params["gate_w"], params["gate_b"])
    with jax.named_scope("loop_heads"):
        # every loop step's rows in one blocked loss: the weights go in, their
        # gradient is the rows' cross-entropies
        all_targets = jnp.broadcast_to(targets, p.shape)
        weighed = mf.weighted_xent(cfg, _head_logits, hs, all_targets, p, params["head"])
    with jax.named_scope("exit_gate"):
        counted = (targets >= 0).astype(jnp.float32)
        entropy = -jnp.sum(p * log_p, axis=0)
        steps = jnp.arange(1, cfg.n_loops + 1, dtype=jnp.float32).reshape(-1, *[1] * targets.ndim)
        total = weighed - cfg.exit_beta * jnp.sum(entropy * counted)
        exit_steps = jnp.sum(jnp.sum(steps * p, axis=0) * counted)
        total, count, exit_steps = mf.over_ranks(total, jnp.sum(counted), exit_steps)
        passes = lax.pmax(lax.pmax(passes, "dp"), "sp")
        milli = jnp.round(1000.0 * exit_steps / count).astype(jnp.int32)
    return total / count, dict(zip(COUNTS, (passes, milli)))
