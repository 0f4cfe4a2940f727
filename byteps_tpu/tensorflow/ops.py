"""TF-graph-level push_pull / broadcast ops for the TensorFlow plugin.

Re-design of byteps/tensorflow/ops.py (the reference registers C++ custom
ops ``BytepsPushPull``/``BytepsBroadcast`` with TF gradients,
ops.py:110-207, ops.cc).  The TPU build routes the cross-worker hop through
the shared byteps_tpu core (host PS path over DCN) via ``tf.py_function``
— a host callback is exactly what the data plane is — and registers the
gradient with ``tf.custom_gradient``: the gradient of a sum-over-workers
is the sum-over-workers of the gradient (ops.py:136-146).

Works in eager mode and inside ``tf.function`` (Keras 3 wraps train steps
in tf.function; py_function stays a host roundtrip either way).
"""

from __future__ import annotations

import re
import threading
from typing import List, Optional, Sequence

import numpy as np
import tensorflow as tf

from byteps_tpu.api import push_pull_async as _core_push_pull_async
from byteps_tpu.api import synchronize as _core_synchronize


def _normalize_name(name: str) -> str:
    """TF-rule normalization, matching the reference (ops.py:100-102)."""
    return re.sub("[^a-zA-Z0-9_]", "_", name)


_anon_lock = threading.Lock()
_anon_counter = 0


def _auto_name(tensor, scope: str) -> str:
    """Deterministic fallback name.

    Graph mode: derived from the op name (stable across workers running the
    same graph — the reference's scheme).  Eager mode: a per-process counter;
    identical call order across workers yields identical names (the same
    assumption the reference makes for graph node names).
    """
    global _anon_counter
    if hasattr(tensor, "name") and not tf.executing_eagerly():
        return scope + "BytePSPushPull_" + _normalize_name(tensor.name)
    with _anon_lock:
        _anon_counter += 1
        return f"{scope}BytePSPushPull_auto_{_anon_counter}"


def _host_push_pull_group(
    tensors: Sequence[tf.Tensor],
    names: Sequence[str],
    average: bool,
) -> List[tf.Tensor]:
    """Group push_pull: one host callback launches every tensor async
    (priority = −index, the declaration-order priority of the reference's
    DistributedOptimizer) then synchronizes — all round-trips overlap,
    like torch's ``push_pull_group_sync_inplace`` (parallel/distributed.py).
    """
    names = list(names)
    dtypes = [t.dtype for t in tensors]

    def host_fn(*ts):
        handles = [
            _core_push_pull_async(
                np.asarray(t), name=n, average=average, priority=-i
            )
            for i, (t, n) in enumerate(zip(ts, names))
        ]
        return [np.asarray(_core_synchronize(h)) for h in handles]

    outs = tf.py_function(host_fn, [tf.convert_to_tensor(t) for t in tensors], Tout=dtypes)
    if len(tensors) == 1 and not isinstance(outs, (list, tuple)):
        outs = [outs]
    for o, t in zip(outs, tensors):
        o.set_shape(t.shape)
    return list(outs)


def _push_pull(tensor, scope: str = "", name: Optional[str] = None, average: bool = False):
    """Sum ``tensor`` over all workers; gradient is also summed over
    workers (RegisterGradient('BytepsPushPull'), ops.py:136-146)."""
    if name is None:
        name = _auto_name(tensor, scope)

    @tf.custom_gradient
    def op(x):
        y = _host_push_pull_group([x], [name], average)[0]

        def grad(dy):
            return _push_pull(dy, name=name + ".grad", average=average)

        return y, grad

    return op(tensor)


def push_pull_group(tensors, names, average: bool = True):
    """Differentiable grouped push_pull (overlapped round-trips)."""

    @tf.custom_gradient
    def op(*xs):
        ys = _host_push_pull_group(xs, names, average)

        def grad(*dys):
            return push_pull_group(dys, [n + ".grad" for n in names], average)

        return ys, grad

    return op(*tensors)


def _fused_name(names: Sequence[str]) -> str:
    """Stable bucket key: every worker builds the same gradient list in
    the same order, so hashing the ordered member names yields identical
    keys without any coordination (the same assumption per-tensor naming
    already makes)."""
    import hashlib

    h = hashlib.sha1("\x00".join(names).encode()).hexdigest()[:12]
    return f"Fused.{len(names)}.{h}"


def push_pull_group_fused(tensors, names, average: bool = True):
    """Differentiable grouped push_pull with IN-GRAPH fusion.

    The plain group path pays the py_function marshalling and one engine
    submit per tensor.  Here the tensors are concatenated per dtype
    by TF's own C++ runtime, so the host hop marshals and submits ONE
    flat tensor per dtype, and the outputs are split/reshaped back
    in-graph.  Composes with the level-1 compressors (an fp16-compressed
    gradient list simply fuses into an fp16 bucket).

    Requires fully-defined static shapes (the split sizes); falls back
    to the per-tensor group path when any shape is dynamic.  Per-tensor
    priority ordering is coarsened to per-bucket (buckets ride ONE host
    hop, launched async with earlier-declared dtypes first) — the DCN
    hop this plugin feeds is a single host pipeline either way.
    """
    tensors = list(tensors)
    names = list(names)
    if any(not t.shape.is_fully_defined() for t in map(tf.convert_to_tensor, tensors)):
        return push_pull_group(tensors, names, average)

    @tf.custom_gradient
    def op(*xs):
        buckets: dict = {}  # dtype -> member indices, declaration order
        for i, x in enumerate(xs):
            buckets.setdefault(x.dtype, []).append(i)
        # ONE host hop for every bucket: the flats ride a single
        # py_function whose host_fn launches them all async (bucket
        # round-trips overlap; earlier-declared dtypes get priority)
        flats, fnames = [], []
        for dtype, idxs in buckets.items():
            flats.append(tf.concat([tf.reshape(xs[i], [-1]) for i in idxs], 0))
            fnames.append(_fused_name([names[i] for i in idxs]))
        outs = _host_push_pull_group(flats, fnames, average)
        ys = [None] * len(xs)
        for (dtype, idxs), out in zip(buckets.items(), outs):
            sizes = [int(np.prod(xs[i].shape.as_list() or [1])) for i in idxs]
            for i, part in zip(idxs, tf.split(out, sizes)):
                ys[i] = tf.reshape(part, xs[i].shape)

        def grad(*dys):
            return push_pull_group_fused(
                dys, [n + ".grad" for n in names], average
            )

        return ys, grad

    return op(*tensors)


def broadcast(tensor, root_rank: int, scope: str = "", name: Optional[str] = None):
    """Root's value everywhere: non-root contributes zeros to an unaveraged
    sum (the reference's broadcast trick, ops.py:149-190)."""
    from byteps_tpu.api import rank

    if name is None:
        name = _auto_name(tensor, scope).replace("PushPull", "Broadcast")

    @tf.custom_gradient
    def op(x):
        src = x if rank() == root_rank else tf.zeros_like(x)
        y = _host_push_pull_group([src], [name], average=False)[0]

        def grad(dy):
            g = _push_pull(dy, name=name + ".grad", average=False)
            if rank() != root_rank:
                g = tf.zeros_like(g)
            return g

        return y, grad

    return op(tensor)
