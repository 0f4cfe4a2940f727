"""Expert parallelism: dense top-k MoE with all-to-all dispatch.

New scope beyond reference parity (SURVEY §2.7).  GShard-style dense
formulation — routing is expressed as einsums with one-hot dispatch masks
so everything is static-shaped for XLA, and tokens travel to their expert's
rank via ``lax.all_to_all`` over the expert axis.  Top-2 (the GShard /
Switch-paper default for quality) routes each token to its two best
experts with renormalized gates; top-1 keeps the cheaper Switch behavior.

Expert grouping follows DeepSpeed-MoE: the expert axis can be any mesh
axis (we reuse ``sp`` in the default training mesh) — each rank in the
group owns ``n_experts / group_size`` experts.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax


def moe_mlp(
    x: jax.Array,
    router_w: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    b2: jax.Array,
    axis_name: Optional[str],
    axis_size: int,
    capacity_factor: float = 2.0,
    top_k: int = 1,
) -> jax.Array:
    """Top-k routed expert MLP (k=1 Switch-style, k=2 GShard-style).

    x:        (T, D) local tokens (flattened batch*seq)
    router_w: (D, E) global router
    w1:       (E_local, D, F), b1: (E_local, F)
    w2:       (E_local, F, D), b2: (E_local, D)
    where E = axis_size * E_local.

    Returns (T, D).
    """
    t, d = x.shape
    e_local = w1.shape[0]
    e_total = e_local * max(1, axis_size)
    top_k = max(1, min(top_k, e_total))

    logits = x @ router_w  # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)

    # per-expert queue slots scale with k (each token occupies k queues),
    # but never beyond t: a token picks each expert at most once, so the
    # no-drop bound stays t even for top-2 (prefill sizing relies on this)
    capacity = max(1, min(int(capacity_factor * top_k * t / e_total), t))

    # iterated argmax: choice i masks out choices < i (static unroll — k
    # is a compile-time constant, so XLA sees straight-line einsum code).
    # Bookkeeping masks/positions are float32 regardless of compute dtype:
    # a bfloat16 cumsum is only exact to 256, and positions past that
    # would collide queue slots and silently blend tokens.
    masks, gate_vals = [], []
    remaining = gates
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)  # (T,)
        oh = jax.nn.one_hot(idx, e_total, dtype=jnp.float32)
        masks.append(oh)
        gate_vals.append(jnp.sum(gates * oh.astype(gates.dtype), axis=-1))
        remaining = remaining * (1.0 - oh.astype(remaining.dtype))

    if top_k > 1:
        # GShard renormalization: the k selected gates sum to 1 per token
        denom = sum(gate_vals) + 1e-9
        weights = [gv / denom for gv in gate_vals]
    else:
        weights = gate_vals

    # positions: choice-i tokens queue AFTER all choice-<i assignments of
    # the same expert (GShard's locations2 = cumsum(mask2) + sum(mask1))
    dispatch = jnp.zeros((t, e_total, capacity), x.dtype)
    combine = jnp.zeros((t, e_total, capacity), x.dtype)
    prev_counts = jnp.zeros((e_total,), jnp.float32)
    for oh, wv in zip(masks, weights):
        pos = (jnp.cumsum(oh, axis=0) - 1.0) * oh + prev_counts[None, :] * oh
        keep = (pos < capacity) * oh  # drop overflow
        pos_oh = jax.nn.one_hot(
            jnp.sum(pos, axis=-1).astype(jnp.int32), capacity, dtype=jnp.float32
        )
        d_i = (keep[:, :, None] * pos_oh[:, None, :]).astype(x.dtype)  # (T, E, C)
        dispatch = dispatch + d_i
        # wv cast to x.dtype: a float32 weight would silently promote the
        # whole (T, E, C) combine tensor (gates need no exact bookkeeping)
        combine = combine + d_i * wv.astype(x.dtype)[:, None, None]
        prev_counts = prev_counts + jnp.sum(oh, axis=0)

    # gather tokens per expert slot: (E_total, C, D); global expert
    # e = rank*e_local + local_idx, so contiguous dim-0 chunks map to ranks
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    if axis_name is not None and axis_size > 1:
        # scatter expert chunks to their owning rank, gathering every
        # peer's slots for OUR experts along the capacity dim:
        # (E_total, C, D) → (E_local, n·C, D)
        expert_in = lax.all_to_all(
            expert_in, axis_name, split_axis=0, concat_axis=1, tiled=True
        )

    h = jnp.einsum("ecd,edf->ecf", expert_in, w1) + b1[:, None, :]
    h = jax.nn.gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]

    if axis_name is not None and axis_size > 1:
        # inverse route: (E_local, n·C, D) → (E_total, C, D)
        out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0, tiled=True)
    # return tokens to their source positions, weighted by gate
    y = jnp.einsum("tec,ecd->td", combine, out)
    return y


def moe_aux_loss(x: jax.Array, router_w: jax.Array, axis_size: int, e_local: int) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style): mean(gates)·mean(mask)·E."""
    e_total = e_local * max(1, axis_size)
    gates = jax.nn.softmax(x @ router_w, axis=-1)
    mask = jax.nn.one_hot(jnp.argmax(gates, axis=-1), e_total, dtype=x.dtype)
    return e_total * jnp.mean(jnp.mean(gates, axis=0) * jnp.mean(mask, axis=0))


# ---------------------------------------------------------------------------
# Held experts: top-k routing (sigmoid or softmax scores) over the whole
# model's experts, grouped products over the experts this device holds, no
# capacity and no drop
# ---------------------------------------------------------------------------

#: what one compiled step reports of its routing, in this order
ROUTING_STATS = ("moe_slots_routed", "moe_slots_held", "moe_slots_dropped",
                 "moe_fullest_expert_slots", "moe_rows_walked")


def sigmoid_topk_route(g: jax.Array, router_w: jax.Array, select_bias: jax.Array,
                       top_k: int, scale: float, eps: float = 1e-20) -> tuple:
    """DeepSeek-V3's ``noaux_tc`` routing with one group.  ``g`` (T, D) and
    ``router_w`` (D, E) in f32 — near-ties among E sigmoid scores decide which
    experts run, so the scores are full f32 products.  The ``top_k`` largest
    of ``score + select_bias`` are chosen; the bias picks and does not weigh:
    ``w_i = scale · s_i / (Σ_chosen s_j + eps)`` (``eps`` is DeepSeek-V3's by
    default; LFM2-MoE publishes 1e-6).  Returns the chosen ids (T, k) int32
    and their weights (T, k) f32."""
    scores = jax.nn.sigmoid(jnp.dot(
        g.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32))
    _, ids = lax.top_k(scores + lax.stop_gradient(select_bias), top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)
    return ids.astype(jnp.int32), weights


def softmax_topk_route(g: jax.Array, router_w: jax.Array, top_k: int) -> tuple:
    """Softmax routing with the chosen weights renormalised (Qwen3-Next's
    ``norm_topk_prob``): ``p = softmax(g W)`` over all E experts, the
    ``top_k`` largest chosen, ``w_i = p_i / Σ_chosen p_j``.  f32 at full
    precision for :func:`sigmoid_topk_route`'s reason: near-ties decide which
    experts run.  Returns the chosen ids (T, k) int32 and weights (T, k) f32."""
    probs = jax.nn.softmax(jnp.dot(
        g.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32), axis=-1)
    chosen, ids = lax.top_k(probs, top_k)
    return ids.astype(jnp.int32), chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


class HeldPlan(NamedTuple):
    """What :func:`held_expert_plan` decides from the chosen ids alone, for
    :func:`held_expert_apply`: which slots (token, choice) go to experts held
    here, in which order, and how many each expert takes."""

    order: jax.Array  # (T·k,) int32: the slots, held ones first, by expert
    sizes: jax.Array  # (n_held,) int32: slots of each held expert


def held_expert_plan(ids: jax.Array, lo: int, n_held: int) -> HeldPlan:
    """The part of a held-expert layer that depends on the routing ids (T, k)
    alone: the slots whose expert is one of ``[lo, lo + n_held)`` ordered by
    expert (one sort) and counted.  It touches no token, so a caller may make
    it before the tokens the experts read exist, keep it, and put an exchange
    between it and :func:`held_expert_apply`."""
    local = ids.reshape(-1) - lo
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)  # (T·k,)
    sizes = jnp.sum(key[:, None] == jnp.arange(n_held, dtype=key.dtype)[None, :],
                    axis=0, dtype=jnp.int32)  # slots of each held expert
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # held slots first, by expert
    return HeldPlan(order, sizes)


def held_expert_mlp(g: jax.Array, ids: jax.Array, weights: jax.Array,
                    w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                    lo: int, n_experts: int) -> tuple:
    """:func:`held_expert_plan` and :func:`held_expert_apply` in one call, the
    experts bias-free SwiGLUs: ``ids`` (T, k) as ``weights``, ``lo`` the first
    held expert."""
    plan = held_expert_plan(ids, lo, w_gate.shape[0])
    return held_expert_apply(g, plan, weights, w_gate, w_up, w_down, n_experts, jax.nn.silu)


def varying(x: jax.Array, axes: frozenset) -> jax.Array:
    """x typed as varying over ``axes`` too (under ``shard_map``; x elsewhere)."""
    need = tuple(axes - jax.typeof(x).vma)
    return lax.pcast(x, need, to="varying") if need else x


@jax.custom_vjp
def add_rows(y: jax.Array, at: jax.Array, rows: jax.Array) -> jax.Array:
    """``y.at[at].add(rows)``, the sort made by hand.  XLA:TPU sorts a
    scatter's indices itself and gathers the rows in that order; at some
    shapes it fuses that gather into the scatter, which then takes over twice
    as long whatever the rows (into (32 768, 2560) f32: 17.4 ms for 27 648
    rows and 15.6 for 6144, where 49 152 rows take 9.3; PERF.md §6 PR 49).
    Sorted here and the gather kept behind a barrier, the same sum takes 7.3
    and 4.8 ms, and no shape tried takes 0.3 ms more than XLA's own."""
    at, order = lax.sort_key_val(at, jnp.arange(at.shape[0], dtype=at.dtype))
    return y.at[at].add(lax.optimization_barrier(rows[order]), indices_are_sorted=True)


@jax.custom_vjp
def take_rows(x: jax.Array, at: jax.Array) -> jax.Array:
    """``x[at]``, whose backward pass is :func:`add_rows` (as that one's is
    this): each the other's transpose, so both ways a scatter is sorted by hand."""
    return x[at]


add_rows.defvjp(lambda y, at, rows: (add_rows(y, at, rows), at),
                lambda at, d: (d, None, take_rows(d, at)))
take_rows.defvjp(lambda x, at: (x[at], (x, at)),
                 lambda kept, d: (add_rows(jnp.zeros_like(kept[0]), kept[1], d), None))


def held_walk(every: int, n_held: int, n_experts: int) -> tuple:
    """The geometry of :func:`held_expert_apply`'s walk over ``every`` = T·k
    ordered slots, from shapes alone: the rows of the first chunk — 9/8 of
    the even load ``every · n_held / n_experts``, what a uniform router sends
    here and an eighth — and of each tail chunk, a quarter of it; both in
    whole tiles of 512 rows (of 8 below 4096 slots)."""
    unit = 8 if every < 4096 else 512
    first = min(every, _round_up(-(-9 * every * n_held // (8 * n_experts)), unit))
    return first, _round_up(-(-every * n_held // (4 * n_experts)), unit)


#: a width under this is handed to the grouped products as it is
HELD_TILES_FLOOR = 1024


def held_tiles(n: int) -> int:
    """The width :func:`held_expert_apply`'s grouped products see for a model
    or expert width ``n``, from the number alone.  XLA:TPU's ragged-dot kernel
    tiles the contraction and the output width each by the largest of 512 |
    256 | 128 that divides it, and on 128-tiles it pays its per-step cost, not
    the MXU's (2688 × 1856: ``512,128,128``, a tenth of the peak; PERF.md §6
    PR 53).  So a large width that is no multiple of 256 is padded with zeros
    up to whole tiles of 512 (26 % more multiply-adds at 3072 × 2048 cost less
    than the 256-tiles of 2816 × 2048: the same probe); a multiple of 256 is
    served well as it is, and the widths under the floor — every CPU test's —
    lower as they always did."""
    if n < HELD_TILES_FLOOR or n % 256 == 0:
        return n
    return _round_up(n, 512)


def held_expert_apply(g: jax.Array, plan: HeldPlan, weights: jax.Array,
                      w_gate: Optional[jax.Array], w_up: jax.Array, w_down: jax.Array,
                      n_experts: int, act: Callable) -> tuple:
    """The routed experts' part of a layer's output that THIS device's
    experts give: ``Σ_{i chosen and held} w_i E_i(g)``, every ``E`` a
    bias-free gated MLP ``(act(g W_gate) ⊙ g W_up) W_down`` — or, where the
    experts have no gate matrix (``w_gate`` None), ``act(g W_up) W_down``.

    g:        (T, D) tokens, compute dtype
    plan:     :func:`held_expert_plan` of the chosen ids (T, k)
    weights:  (T, k) from a ``*_topk_route`` above, over all ``n_experts``
              of the model
    w_gate, w_up: (n_held, D, F), w_down: (n_held, F, D) — the experts
              ``[lo, lo + n_held)``, which this device holds
    act:      the gate's activation (``jax.nn.silu``: SwiGLU), the hidden
              units' own where there is no gate

    The slots (token, choice) whose expert is held come ordered by expert
    and are multiplied in grouped products (``lax.ragged_dot``: each
    expert takes exactly its rows, however many).  No capacity, no drop: the
    ordered slots are walked in chunks, and the rows walked follow the slots
    held (:func:`held_walk`): a first chunk of 9/8 of what a uniform router
    sends here, which the usual step ends with, and behind one ``cond`` tail
    chunks of a quarter of that load for as long as slots are left (all T·k
    slots at most) — a layer that holds L times the even load walks at most
    max(9/8, L + 1/4) times it.  So imbalance costs time in proportion and
    never a token, and the buffers stay one chunk large.  What the experts
    held elsewhere would add is left out: with expert parallelism their
    devices add it, and on one device nothing stands in for them.

    Returns ``(y (T, D) f32, stats (5,) int32 in ROUTING_STATS order)``."""
    t, k = weights.shape
    order, sizes = plan
    every = t * k
    # the experts' matrices as the products take them: (gate,) up, down
    ws = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    d, f = g.shape[1], w_up.shape[2]
    d_wide, f_wide = held_tiles(d), held_tiles(f)  # what the products see
    if (d_wide, f_wide) != (d, f):
        def widen(w, rows, cols):
            return jnp.pad(w, ((0, 0), (0, rows - w.shape[1]), (0, cols - w.shape[2])))

        ws = (*(widen(w, d_wide, f_wide) for w in ws[:-1]), widen(w_down, f_wide, d_wide))
    first_rows, tail_rows = held_walk(every, w_up.shape[0], n_experts)
    n_tail = -(-(every - first_rows) // tail_rows)
    order = jnp.pad(order, (0, first_rows + n_tail * tail_rows - every))

    def rows_of(g, flat_w, ws, order, ends, sizes, first, rows):
        """The slots [first, first + rows) of the ordered list: what they add
        to y (rows, D) f32, and beside it the tokens they add it to and how
        many slots the grouped products took."""
        slot = lax.dynamic_slice_in_dim(order, first, rows)
        tok = slot // k
        # this chunk's rows of each expert: its span cut to the chunk
        mine = jnp.clip(jnp.minimum(ends, first + rows) - jnp.maximum(ends - sizes, first),
                        0, rows)
        # rows past the last held slot belong to no expert: a grouped
        # product leaves them unwritten (garbage on the TPU), so every
        # product's operand and result is cleared there by selection
        live = (first + jnp.arange(rows) < ends[-1])[:, None]
        xs = jnp.where(live, take_rows(g, tok), 0)  # (rows, D)
        if d_wide != d:
            xs = jnp.pad(xs, ((0, 0), (0, d_wide - d)))

        def grouped(lhs, rhs):
            return jnp.where(live, lax.ragged_dot(lhs, rhs, mine), 0)

        *w_in, w_down = ws  # (gate,) up
        hidden = act(grouped(xs, w_in[0]))
        if len(w_in) == 2:
            hidden = hidden * grouped(xs, w_in[1])
        out = grouped(hidden, w_down)
        if d_wide != d:
            out = out[:, :d]
        return out.astype(jnp.float32) * flat_w[slot][:, None], (tok, jnp.sum(mine))

    # The tail: a loop that is as long as the slots ask, which jax cannot turn
    # round by itself, so it brings its own backward pass — the same chunks
    # walked again.  (A scan over all n_tail chunks can be turned round, but a
    # step of it that is skipped still adds a zero cotangent of the three
    # weight arrays to the scan's carry.)

    @jax.custom_vjp
    def tail(y, *read):
        """y with the tail chunks added, one after the other while slots are
        left; the slots they took; how many ran."""
        def chunk(carry):
            i, y, took = carry
            add, (tok, n) = rows_of(*read, first_rows + i * tail_rows, tail_rows)
            return i + 1, add_rows(y, tok, add), took + n

        n_slots = read[-2][-1]
        none = jnp.zeros_like(n_slots)  # typed as the counts are, also under shard_map
        ran, y, took = lax.while_loop(lambda c: first_rows + c[0] * tail_rows < n_slots,
                                      chunk, (none, y, none))
        return y, took, ran

    def tail_forth(y, *read):
        out = tail(y, *read)
        return out, (read, out[2])

    def tail_back(kept, cotangents):
        """Each tail chunk that ran, rebuilt and pulled back on its own: y's
        cotangent reaches every chunk as it is (a chunk only adds to y), and a
        chunk that did not run adds nothing, not even zeros."""
        (*inputs, order, ends, sizes), ran = kept
        dy = cotangents[0]

        def chunk(i, sums):
            _, pull, (tok, _) = jax.vjp(
                lambda *a: rows_of(*a, order, ends, sizes, first_rows + i * tail_rows, tail_rows),
                *inputs, has_aux=True)
            return jax.tree.map(jnp.add, sums, pull(take_rows(dy, tok)))

        sums = lax.fori_loop(jnp.zeros_like(ran), ran, chunk,
                             jax.tree.map(jnp.zeros_like, tuple(inputs)))
        return (dy, *sums, None, None, None)  # the plan is integers

    tail.defvjp(tail_forth, tail_back)

    ends = jnp.cumsum(sizes)
    n_slots = ends[-1]
    axes = jax.typeof(g).vma  # under shard_map the walk's arrays vary as g does
    y, *read = jax.tree.map(lambda x: varying(x, axes), (
        jnp.zeros((t, g.shape[1]), jnp.float32), g, weights.reshape(-1), ws,
        order, ends, sizes))
    add, (tok, taken) = rows_of(*read, 0, first_rows)
    y, walked = add_rows(y, tok, add), jnp.asarray(first_rows, jnp.int32)
    if n_tail:
        # behind ONE cond: the usual step ends with the first chunk and pays
        # for no branch it does not take (a cond a chunk cost 16 zero-filled
        # cotangents a layer in the backward pass)
        none = jnp.zeros_like(n_slots)
        y, after, ran = lax.cond(n_slots > first_rows, lambda y: tail(y, *read),
                                 lambda y: (y, none, none), y)
        taken, walked = taken + after, walked + ran * tail_rows
    stats = jnp.stack([jnp.asarray(every, jnp.int32), n_slots, n_slots - taken,
                       jnp.max(sizes), walked])
    return y, stats


class RoutingCounters:
    """What compiled steps count (name → int32 scalar: the ROUTING_STATS of
    the expert layers, or whatever a family without experts counts — any
    name a step's ``local_loss`` returns), added to the process's counters
    (``bps.get_robustness_counters()``) without a blocking read:
    :meth:`push` keeps a step's device arrays and folds in only what is
    ready; a snapshot of the counters waits for the rest."""

    def __init__(self) -> None:
        import threading

        from byteps_tpu.core.telemetry import counters

        self._lock = threading.Lock()
        self._pending: list = []
        self._totals = dict.fromkeys(ROUTING_STATS, 0)
        counters().register_provider(self._snapshot)

    def push(self, counts: dict) -> None:
        if not counts:  # a family that counts nothing
            return
        with self._lock:
            self._pending.append(counts)
            self._fold(wait=False)

    def _fold(self, wait: bool) -> None:
        while self._pending and (
                wait or all(v.is_ready() for v in self._pending[0].values())):
            for name, v in jax.device_get(self._pending.pop(0)).items():
                self._totals[name] = self._totals.get(name, 0) + int(v)

    def _snapshot(self) -> dict:
        with self._lock:
            self._fold(wait=True)
            return dict(self._totals)


_routing_counters: Optional[RoutingCounters] = None


def routing_counters() -> RoutingCounters:
    """The process's one sink for routing statistics."""
    global _routing_counters
    if _routing_counters is None:
        _routing_counters = RoutingCounters()
    return _routing_counters
