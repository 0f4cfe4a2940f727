"""What the families behind ``build_train_step`` that are no
``TransformerConfig`` share — the six mixture-of-experts families
``latent_moe``, ``delta_moe``, ``conv_moe``, ``window_moe``,
``early_route_moe``, ``ssm_moe``, and ``looped_dense``, which has no experts —
each decision written once.

A device of an expert family (:class:`ExpertFamily`) holds the experts
``[expert_lo, expert_lo + experts_held)`` of every expert layer and the first
``vocab_size`` rows of the vocabulary: its share of a deployment in which
several devices share each layer.  The router scores all ``n_experts``; what
the experts held elsewhere would add is left out
(``parallel/moe.held_expert_mlp``).

A family is a module.  ``transformer.build_train_step`` / ``build_forward``
take its config as they take a ``TransformerConfig``: a frozen dataclass that
says ``class XConfig(Family)`` (:class:`ExpertFamily` where it holds experts,
or :class:`PatternedFamily`, where ``layer_types`` lists the layers one by
one) and names itself (``family``, ``lacks``); beside it the module holds
``layouts(cfg)``, ``local_loss(cfg, mesh, params, tokens, targets)`` — the
loss and what the step counts, by name — and ``local_logits(cfg, params,
tokens)``, and the base answers with them.  What a family writes itself is
its fields, its shapes, its mixers, how its layers are stacked and run, and
one table of rules for :func:`init_params`; what it takes from here is the
norm, the SwiGLU, the short convolution and the rotary embedding, the held
experts' MLP with its scopes, the blocked cross-entropy (with a weight a row
where the family's loss has one), the sums over the ranks, and the walk over
listed layers.

Nothing here branches on which family calls: what differs between them comes
in as data (``1 + w`` or ``w``, a router, a scope's name, a logits function
and what it reads, a weight a row or none, a table).  No family's module
imports another; all import this one.
"""

from __future__ import annotations

import fnmatch
import functools
import math
import sys
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.ops.flash_attention import SAVED as FLASH_SAVED, flash_attention
from byteps_tpu.ops.mla_heads import merge_heads, mla_heads
from byteps_tpu.parallel.moe import (ROUTING_STATS, HeldPlan, held_expert_apply,
                                     held_expert_plan, varying)

_ALL_AXES = ("dp", "pp", "sp", "tp")
#: rows of logits that stand at a time in the blocked loss
ROW_BLOCK = 2048


# ---------------------------------------------------------------------------
# The protocol: what transformer.build_train_step / build_forward ask of a family
# ---------------------------------------------------------------------------


class Family:
    """Base of a family's config: it reads the family's module (see the
    module's docstring) and no field but those its checks are asked about —
    a family without experts is a family (``models/looped_dense.py``)."""

    #: the words of :meth:`validate_mesh`'s refusal: the family's name, what
    #: kind of family it is, and what beside data parallelism is not built
    #: for it
    family = lacks = ""
    kind = "family"

    def _check_grouped_heads(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("query heads must be a multiple of key/value heads")

    def _check_even_rope(self, field: str):
        if getattr(self, field) % 2:
            raise ValueError(f"rope needs an even {field}, got {getattr(self, field)}")

    def _module(self):
        return sys.modules[type(self).__module__]

    def layouts(self) -> Dict[str, Tuple]:
        return self._module().layouts(self)

    def validate_mesh(self, mesh: Mesh) -> None:
        for ax in ("pp", "sp", "tp"):
            if mesh.shape.get(ax, 1) != 1:
                raise ValueError(
                    f"the {self.family} {self.kind} runs data-parallel only: mesh has "
                    f"{ax}={mesh.shape[ax]} (no {self.lacks} is built for it yet)")

    def local_loss(self, mesh: Mesh, params, tokens, targets):
        return self._module().local_loss(self, mesh, params, tokens, targets)

    def local_logits(self, mesh: Mesh, params, tokens):
        # one microbatch, no pipeline
        return self._module().local_logits(self, params, tokens)[None]


class ExpertFamily(Family):
    """Base of a family with expert layers: it reads the fields each of them
    has (``expert_lo``, ``experts_held``, ``n_experts``)."""

    kind = "MoE family"

    def __post_init__(self):
        if not 0 <= self.expert_lo <= self.n_experts - self.experts_held:
            raise ValueError(
                f"held experts [{self.expert_lo}, {self.expert_lo + self.experts_held}) "
                f"lie outside the router's {self.n_experts}")


class Patterned(Family):
    """A family whose layers are static data: ``layer_types[i]`` names layer
    i's mixer, and the first ``n_dense_layers`` layers' MLP is dense, the
    others' routed (stacks ``dense``, ``moe``) — with every layer dense, a
    family without experts (``cross_decoder``).  A family whose layer is ONE
    part — a mixer or an MLP alone — lists every part's type in ``mixers``
    and gives :meth:`kinds` itself, one stack a layer (``ssm_moe``)."""

    #: ``layer_types`` entry → the stack that holds that mixer's parameters
    mixers = {}

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = sorted(set(self.layer_types) - set(self.mixers))
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {unknown or 'nothing'}: a layer's mixer is "
                             f"one of {sorted(self.mixers)}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError(f"{self.n_dense_layers} leading dense layers in a model of "
                             f"{len(self.layer_types)}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def kinds(self) -> Tuple[Tuple[str, ...], ...]:
        """Layer by layer, the stacks it reads, in order: (mixer's, MLP's)."""
        return tuple((self.mixers[t], "dense" if i < self.n_dense_layers else "moe")
                     for i, t in enumerate(self.layer_types))

    def stack_sizes(self, shapes: Dict[str, Dict[str, tuple]]):
        """``shapes`` (stack → per-layer shapes) → stack → (layers, per-layer
        shapes), the stacks some layer reads."""
        used = [stack for pair in self.kinds() for stack in pair]
        return {k: (used.count(k), v) for k, v in shapes.items() if k in used}


class PatternedFamily(Patterned, ExpertFamily):
    """:class:`Patterned` with expert layers (``conv_moe``, ``window_moe``,
    ``early_route_moe``, ``ssm_moe``)."""

    def __post_init__(self):
        Patterned.__post_init__(self)
        ExpertFamily.__post_init__(self)


# ---------------------------------------------------------------------------
# Parameters: a flat dict; ``<stack>.<name>`` carries the stack's leading dims
# ---------------------------------------------------------------------------


def layouts(top: Dict[str, tuple], stacks: Dict[str, Tuple]) -> Dict[str, Tuple]:
    """name → (global shape, partition spec, gradient sync axes), as
    ``transformer._layouts`` gives them: the top-level leaves, then
    ``<stack>.<name>`` for every ``stack → (leading dims, per-layer shapes)``
    (a count of layers, or a tuple where a stack has two leading dims).
    Everything is replicated: these families run data-parallel only so far
    (:meth:`Family.validate_mesh`)."""
    shapes = dict(top)
    for stack, (lead, per_layer) in stacks.items():
        lead = lead if isinstance(lead, tuple) else (lead,)
        shapes.update({f"{stack}.{k}": lead + s for k, s in per_layer.items()})
    return {k: (s, P(), _ALL_AXES) for k, s in shapes.items()}


def stack_of(params, stack: str):
    """The entries ``<stack>.<name>`` of ``params``, by ``<name>``."""
    return {k.split(".", 1)[1]: v for k, v in params.items() if k.startswith(stack + ".")}


# A rule makes one leaf: (key: () -> the leaf's PRNG key, shape) -> f32 array.


def ones(key, shape):
    return jnp.ones(shape, jnp.float32)


def zeros(key, shape):
    return jnp.zeros(shape, jnp.float32)


def normal(std: float):
    """N(0, std²)."""
    return lambda key, shape: std * jax.random.normal(key(), shape, jnp.float32)


def fan_in(*dims: int):
    """N(0, 1 / fan-in), the fan-in the product of the dims a product with
    this leaf contracts."""
    return lambda key, shape: normal(math.prod(shape[d] for d in dims) ** -0.5)(key, shape)


def uniform(bound: float):
    """U(−bound, bound)."""
    return lambda key, shape: jax.random.uniform(key(), shape, jnp.float32, -bound, bound)


def linear(*dims: int):
    """``torch.nn.Linear``'s own start, U(−1/√fan-in, 1/√fan-in) (variance
    1 / (3 fan-in)), the fan-in as :func:`fan_in`'s."""
    return lambda key, shape: uniform(math.prod(shape[d] for d in dims) ** -0.5)(key, shape)


def log_uniform(lo: float, hi: float):
    """log U(lo, hi)."""
    return lambda key, shape: jnp.log(jax.random.uniform(key(), shape, jnp.float32, lo, hi))


#: leaf (or a pattern of ``fnmatch``) → rule, where the families agree: 0.02
#: for the embedding, N(0, 1 / fan-in) matrices that contract the dim before
#: their last (a head of (model, vocabulary) too), projections to heads that
#: contract the model dim, the one back from heads that contracts both of its
INIT_RULES = {
    "embed": normal(0.02),
    "wq": fan_in(-3), "wk": fan_in(-3), "wv": fan_in(-3), "wo": fan_in(-3, -2),
    **dict.fromkeys(("head", "router", "w_gate", "w_up", "w_down", "e_gate", "e_up", "e_down",
                     "s_gate", "s_up", "s_down"), fan_in(-2)),
}


def init_params(layout: Dict[str, Tuple], key: jax.Array,
                rules: Dict[str, Callable]) -> Dict[str, jax.Array]:
    """f32 parameters from ``key``, jittable (made on the device), in
    ``layout``'s order.  Leaf ``i`` is drawn with ``fold_in(key, i)`` by the
    rule its name has (the part after the stack's) in ``rules``, the family's
    table, or else in :data:`INIT_RULES`; an exact name comes before a
    pattern.  A leaf without a rule is refused: a new leaf's scale is a
    decision."""
    table = {**INIT_RULES, **rules}
    params = {}
    for i, (name, (shape, _, _)) in enumerate(layout.items()):
        leaf = name.rsplit(".", 1)[-1]
        rule = table.get(leaf) or next(
            (r for pattern, r in table.items() if fnmatch.fnmatchcase(leaf, pattern)), None)
        if rule is None:
            raise ValueError(f"init_params has no rule for the leaf {name!r}: the family's "
                             "table says how each of its leaves starts")
        params[name] = rule(lambda: jax.random.fold_in(key, i), shape)
    return params


# ---------------------------------------------------------------------------
# Forward pieces (per device, inside shard_map)
# ---------------------------------------------------------------------------


def rms(x, w, eps: float, plus_one: bool = False):
    """RMSNorm ``w · x / rms(x)`` — ``(1 + w) · x / rms(x)`` with
    ``plus_one`` — with f32 statistics; returns f32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (
        1.0 + w if plus_one else w)


def layer_norm(x, w, b, eps: float):
    """LayerNorm ``w · (x − mean) / std + b`` with f32 statistics; returns f32."""
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w + b


def swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


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
    serves two families and both tap counts: ``delta_moe``'s 4 taps under a
    silu, and ``conv_moe``'s 3 taps between two gates
    (tests/test_conv_moe_pieces.py holds the 3-tap case by hand).
    The shifted copies are taken in x's dtype and multiplied in f32 (on the
    chip 6 ms a layer less than shifting an f32 copy: PERF.md §6, PR 36)."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s].astype(jnp.float32) * taps[j] for j in range(k))


class Decision(NamedTuple):
    """An expert layer's routing, made: where each slot goes among the held
    experts (``parallel/moe.HeldPlan``) and what each choice weighs, (T, k)
    f32.  8 bytes a slot: small enough to keep where tokens are rebuilt."""

    plan: HeldPlan
    weights: jax.Array


def decide(cfg, g32, lp, route: Callable) -> Decision:
    """An expert layer's routing decided on the tokens ``g32`` (T, D) f32,
    which need not be the tokens the experts will read: ``route(g32, lp)`` →
    (ids, weights), the family's router, and the held experts' plan from the
    ids, all under ``moe_route``.  :func:`routed_mlp` takes it in a router's
    place."""
    with jax.named_scope("moe_route"):
        ids, weights = route(g32, lp)
        return Decision(held_expert_plan(ids, cfg.expert_lo, cfg.experts_held), weights)


def routed_mlp(cfg, g32, g, lp, route: Union[Callable, Decision],
               shared_scope: Optional[str] = None, act: Callable = jax.nn.silu):
    """An expert layer's MLP on normed tokens ``g32`` (T, D) f32: the held
    experts' routed part (``parallel/moe.held_expert_apply``: what the experts
    held elsewhere would add is left out; the rows it walks follow the slots
    held, a first chunk of 9/8 of the even load and tail chunks of a quarter;
    ``act`` is their gate's activation)
    plus, under ``shared_scope`` where
    the family has one, the shared expert that every token takes — behind
    ``sigmoid(g · shared_gate)`` where the layer has that leaf, at weight 1
    where not.  Both kinds of expert are gated MLPs ``(act(g W_gate) ⊙ g W_up)
    W_down`` where the layer has the gate's matrix (``e_gate``, ``s_gate``),
    and ``act(g W_up) W_down`` where it has none.
    ``route(g32, lp)`` → (ids, weights) is the family's router, or
    ``route`` is the :class:`Decision` made earlier (:func:`decide`).
    ``g`` is what the experts read: the tokens in the compute dtype where the
    family has them, or ``g32`` again — then they are cast where each expert
    reads them.  The three scopes are what the benchmark's readers file a
    step's operations by.  Returns (y (T, D) f32, routing stats)."""
    cdt = cfg.compute_dtype
    made = not callable(route)
    if not made:
        with jax.named_scope("moe_route"):
            ids, weights = route(g32, lp)
    with jax.named_scope("moe_experts"):
        # cast before the plan: the order the four older families' steps lower in
        tokens = g.astype(cdt)
        e_gate = lp["e_gate"].astype(cdt) if "e_gate" in lp else None
        e_up, e_down = lp["e_up"].astype(cdt), lp["e_down"].astype(cdt)
        plan, weights = route if made else (
            held_expert_plan(ids, cfg.expert_lo, cfg.experts_held), weights)
        y, stats = held_expert_apply(tokens, plan, weights, e_gate, e_up, e_down,
                                     n_experts=cfg.n_experts, act=act)
    if shared_scope is None:
        return y, stats
    with jax.named_scope(shared_scope):
        g = g.astype(cdt)
        if "s_gate" in lp:
            shared = swiglu(g, *(lp[w].astype(cdt) for w in ("s_gate", "s_up", "s_down")))
        else:
            shared = act(g @ lp["s_up"].astype(cdt)) @ lp["s_down"].astype(cdt)
        open_ = None
        if "shared_gate" in lp:
            open_ = jax.nn.sigmoid(jnp.dot(g, lp["shared_gate"].astype(cdt),
                                           preferred_element_type=jnp.float32))[:, None]
    shared = shared.astype(jnp.float32)
    return y + (shared if open_ is None else open_ * shared), stats


def keep_flash():
    """The remat policy of an attention part: all of it is rebuilt in the
    backward pass but the kernel's output and row statistics, so that the
    forward kernel, a layer's costliest part, does not run twice.  A new
    object each call, and jax keeps traced functions apart by it: a caller
    makes one for the parts it wants lowered as one."""
    return jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED)


def latent_attention(cfg, x, lp, scope: str, theta: Optional[float],
                     order: Callable = lambda w: w):
    """x (B, S, D) → x + multi-head latent attention, under ``scope``: keys
    and values through a low-rank bottleneck (``wkv_a`` to ``kv_lora_rank`` +
    one ``qk_rope_dim``-wide key a token that every head shares, ``wkv_b`` to
    ``n_heads`` of ``qk_nope_dim`` + ``v_head_dim``), queries through one too
    where the layer has its leaves (``wq_a``, ``q_norm``, ``wq_b``) and
    straight from the normed stream where it has ``wq`` alone; causal softmax
    at scale ``qk_dim``^-½.  ``theta`` is the rope's base for the shared key
    and the queries' last ``qk_rope_dim`` columns — None: no positions, those
    columns are keys and queries as they come (``ops/mla_heads.py``: tables
    of cos 1, sin 0) — and ``order`` the order the caller's rope wants those
    columns' WEIGHTS in (``mla_heads.even_first`` for an interleaved rope).

    Every product is written token-major, (B, S, heads · width), from weight
    columns in the order ``ops/mla_heads.py`` reads — all heads' parts without
    positions, all heads' rotary parts, keys, values — and one pass builds the
    flash kernels' operands from them; no activation is sliced, concatenated
    or turned outside it."""
    cdt, nope, r, heads = cfg.compute_dtype, cfg.qk_nope_dim, cfg.kv_lora_rank, cfg.n_heads
    with jax.named_scope(scope):
        def columns(w):  # (rank, heads, width) → (rank, heads · width)
            return w.reshape(w.shape[0], -1)

        h = rms(x, lp["attn_norm"], cfg.norm_eps).astype(cdt)
        if "wq_a" in lp:
            c_q = rms(h @ lp["wq_a"].astype(cdt), lp["q_norm"], cfg.norm_eps).astype(cdt)
            wq = lp["wq_b"].astype(cdt)
        else:
            c_q, wq = h, lp["wq"].astype(cdt)
        q_nope = c_q @ columns(wq[..., :nope])
        q_rope = c_q @ columns(order(wq[..., nope:]))
        wkv_a = lp["wkv_a"].astype(cdt)
        kv_a = h @ jnp.concatenate([wkv_a[:, :r], order(wkv_a[:, r:])], axis=-1)
        c_kv = rms(kv_a[..., :r], lp["kv_norm"], cfg.norm_eps).astype(cdt)
        wkv_b = lp["wkv_b"].astype(cdt)
        k_nope = c_kv @ columns(wkv_b[..., :nope])
        v = c_kv @ columns(wkv_b[..., nope:])
        # one rotary key a token, shared by every head
        q, k, v = mla_heads(q_nope, q_rope, k_nope, v, kv_a[..., r:], heads, theta)
        # the Pallas kernels on a TPU (the only way at 8k: one sequence's
        # scores are 4.3 GB a layer); off a TPU this takes the dense path
        o = flash_attention(q, k, v, causal=True, scale=cfg.qk_dim ** -0.5)
        return x + merge_heads(o, lp["wo"].astype(cdt)).astype(x.dtype)


class Handed(NamedTuple):
    """What a layer's mixer part returns to :func:`walk` when the MLP part of
    the same layer is to get a value beside ``x``."""

    x: jax.Array
    value: Any


class Carried(NamedTuple):
    """What a part returns to :func:`walk` when LATER layers are to get values
    beside ``x``: ``values`` (name → array or tuple of arrays) travel on with
    the residual stream to every part that ``takes`` them."""

    x: jax.Array
    values: Dict[str, Any]


def walk(cfg, run: Dict[str, Callable], kept: Dict[str, Tuple[str, ...]], params, x,
         takes: Optional[Dict[str, Tuple[str, ...]]] = None,
         carried: Optional[Dict[str, Any]] = None):
    """The layers of a :class:`PatternedFamily`, unrolled: layer by layer of
    ``cfg.kinds()``, the mixer's part and the MLP's (or the one part of a
    layer that is either alone), each on the next entry of its stack.
    ``run[stack](x, lp)`` → x, or (x, routing stats) from a part that routes;
    each is rebuilt in the backward pass on its own, one at a time, but for
    what ``kept[stack]`` names: the ``checkpoint_name``s of its kernels'
    outputs (``FLASH_SAVED`` for a stack of attention parts, ``ops/ssd.SAVED``
    for Mamba-2 mixers), so that a forward kernel does not run twice; a stack
    that ``kept`` leaves out is rebuilt whole.  The layers are a Python loop:
    a kept array crosses no ``lax.scan``'s stack.  A mixer's
    part may return :class:`Handed`: its value goes to the MLP's part of the
    same layer as a third argument — an output of the one rebuilt part and an
    input of the other, so it is kept, and what only it needs (a sort, say) is
    in neither rebuild.  A part may return :class:`Carried`: its values are
    written to ``carried`` (the caller's dict, name → value; what earlier
    layers held ELSEWHERE exported comes in through it and what these layers
    export is read from it afterwards), and every later part whose stack
    ``takes`` names them gets them as further arguments, in that order.  A
    carried value is an output of one rebuilt part and an input of others: it
    is kept, not rebuilt, and autodiff sums its cotangent over its readers.
    Returns (x, the routing stats summed over the layers)."""
    if cfg.remat:
        # one policy object a set of names (see keep_flash): the stacks that
        # keep the same lower as one
        policy = {names: jax.checkpoint_policies.save_only_these_names(*names)
                  for names in set(kept.values())}
        run = {k: jax.checkpoint(f, policy=policy[kept[k]] if k in kept else None)
               for k, f in run.items()}
    stats = jnp.zeros((len(ROUTING_STATS),), jnp.int32)
    stacked = {stack: stack_of(params, stack) for stack in run}
    seen = dict.fromkeys(run, 0)  # how many layers of each stack have run
    takes, carried = takes or {}, {} if carried is None else carried
    for pair in cfg.kinds():
        handed = ()
        for stack in pair:
            lp = {k: v[seen[stack]] for k, v in stacked[stack].items()}
            seen[stack] += 1
            x, handed = run[stack](x, lp, *handed, *(carried[n] for n in takes.get(stack, ()))), ()
            if isinstance(x, Carried):
                carried.update(x.values)
                x = x.x
            elif isinstance(x, Handed):
                x, handed = x.x, (x.value,)
            elif isinstance(x, tuple):
                x, each = x
                stats = stats + each
    return x, stats


def row_logits(cfg, x, scale, rows):
    """Logits of the final norm of x with a (vocabulary, model) matrix — a
    tied embedding, or a head laid out as one — over the held rows, f32."""
    h = rms(x, scale, cfg.norm_eps).astype(cfg.compute_dtype)
    return lax.dot_general(h, rows.astype(cfg.compute_dtype),
                           (((h.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _varying_as(tree, *like):
    """``tree``'s leaves typed as varying over every axis one of ``like``
    varies over (under ``shard_map``; themselves elsewhere)."""
    axes = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.tree.map(lambda leaf: varying(leaf, axes), tree)


def _block_loss(rows, tb, wb=None):
    """One block's masked sum of token cross-entropies from its f32 logits —
    each row's weighed by ``wb`` where there is one —, the logsumexp it took,
    and the rows' cross-entropies (0 where the target is ignored)."""
    lse = jax.nn.logsumexp(rows, axis=-1)
    gold = jnp.take_along_axis(rows, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
    each = (lse - gold) * (tb >= 0)
    return jnp.sum(each if wb is None else each * wb), lse, each


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _blocked_xent(cfg, logits: Callable, xs, ts, ws, reads):
    """The sum of token cross-entropies over blocks of rows ``xs`` (n, block,
    D), ``ts`` (n, block) — each row's weighed by ``ws`` (n, block) f32 where
    that is not None; ``reads`` is what ``logits`` reads beside a block.
    Undifferentiated, the plain blocked sum."""
    return jnp.sum(lax.map(
        lambda xtw: _block_loss(logits(cfg, xtw[0], *reads), *xtw[1:])[0], (xs, ts, ws)))


def _blocked_xent_up(cfg, logits: Callable, xs, ts, ws, reads):
    """Differentiated, a block's gradient is taken where its logits stand:
    ``softmax − onehot`` over the counted rows — times the row's weight —,
    f32, pulled back through the family's ``logits`` at once: dx of the block
    written, the gradients of what ``logits`` reads (a final norm's scale, the
    head) summed in f32 across the blocks.  Three products a block; nothing
    of a block is rebuilt on the way down.  A weight's gradient is its row's
    cross-entropy, kept for the way down."""

    def one(sums, xtw):
        xb, tb, wb = xtw
        rows, pull = jax.vjp(functools.partial(logits, cfg), xb, *reads)
        loss, lse, each = _block_loss(rows, tb, wb)
        counted = (tb >= 0)[:, None]
        hot = jnp.arange(rows.shape[-1], dtype=tb.dtype) == tb[:, None]
        slope = jnp.where(counted, jnp.exp(rows - lse[:, None]) - hot, 0.0)
        dxb, *into = pull(slope if wb is None else slope * wb[:, None])
        return (tuple(s + d.astype(jnp.float32) for s, d in zip(sums, into)),
                (loss, dxb, None if wb is None else each))

    sums = tuple(jnp.zeros_like(w, jnp.float32) for w in reads)
    sums, (losses, dxs, each) = lax.scan(one, _varying_as(sums, xs, *reads), (xs, ts, ws))
    return jnp.sum(losses), (dxs, each, *(s.astype(w.dtype) for s, w in zip(sums, reads)))


def _blocked_xent_down(cfg, logits, kept, ct):
    dxs, each, *into = kept
    dxs, *into = ((ct * g).astype(g.dtype) for g in (dxs, *into))
    return dxs, None, None if each is None else ct * each, tuple(into)


_blocked_xent.defvjp(_blocked_xent_up, _blocked_xent_down)


def _xent_blocks(cfg, logits: Callable, x, targets, weights, reads):
    """:func:`_blocked_xent` on x (..., D) cut into blocks of rows, under
    ``lm_head``."""
    d = x.shape[-1]
    block = math.gcd(x.size // d, ROW_BLOCK)
    with jax.named_scope("lm_head"):
        # under shard_map what the logits read is replicated and x varies: the
        # cotangents are summed over x's axes by this cast's transpose
        reads = _varying_as(reads, x)
        return _blocked_xent(cfg, logits, x.reshape(-1, block, d), targets.reshape(-1, block),
                             None if weights is None else weights.reshape(-1, block), reads)


def xent_sums(cfg, logits: Callable, x, targets, *reads):
    """(sum of token cross-entropies, tokens counted); targets < 0 are
    ignored.  ``logits(cfg, x, *reads)`` is the family's (``reads``: a final
    norm's scale and the head).  A block of rows at a time: the (B·S, V)
    logits never stand whole, and under differentiation each block's gradient
    is taken while its logits stand (:func:`_blocked_xent_up`), so no block
    is built twice."""
    total = _xent_blocks(cfg, logits, x, targets, None, reads)
    with jax.named_scope("lm_head"):
        return total, jnp.sum(targets >= 0).astype(jnp.float32)


def weighted_xent(cfg, logits: Callable, x, targets, weights, *reads):
    """:func:`xent_sums` with a weight a row: ``Σ weight · cross-entropy``
    over the counted rows.  The one blocked implementation: the weights go
    in, so a block's gradient is still taken while its logits stand, and they
    may be learned — a weight's gradient is its row's cross-entropy (0 where
    the target is ignored)."""
    return _xent_blocks(cfg, logits, x, targets, weights.astype(jnp.float32), reads)


def over_ranks(*sums):
    """Each of ``sums`` summed over the data-parallel and sequence ranks."""
    for ax in ("dp", "sp"):
        sums = tuple(lax.psum(s, ax) for s in sums)
    return sums


def mean_loss(total, count, stats):
    """The global mean token cross-entropy, identical on every rank, and the
    step's routing stats (ROUTING_STATS name → int32) summed over the ranks."""
    total, count, stats = over_ranks(total, count, stats)
    return total / count, dict(zip(ROUTING_STATS, stats))
