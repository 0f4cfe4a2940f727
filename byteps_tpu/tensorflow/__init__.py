"""TensorFlow plugin — Horovod-compatible adapter for TF2/Keras-3 models.

Parity surface with the reference's byteps/tensorflow plugin
(tensorflow/__init__.py:40-81 push_pull, 141-173 broadcast hook, 186-268
DistributedOptimizer, 343-417 DistributedGradientTape; ops.py:110-207):
``init``, ``shutdown``, ``push_pull``, ``broadcast(_variables)``,
``DistributedOptimizer``, ``DistributedGradientTape``,
``BroadcastGlobalVariablesHook``, level-1 ``Compression``.

The data plane is the shared byteps_tpu core: identity in single-worker
mode, PS-over-DCN when distributed.  The TF graph reaches it through
``tf.py_function`` host callbacks (byteps_tpu.tensorflow.ops) — the
reference reaches its core through C++ custom ops; on the TPU build the
cross-worker hop is a host-side PS roundtrip either way, and the TPU
compute path remains JAX.

This image carries TF 2.21 + Keras 3: the Keras optimizer wrap overrides
``apply_gradients`` (Keras 3 removed the ``get_gradients`` /
``_aggregate_gradients`` hooks the reference patched,
_keras/__init__.py:33-45).
"""

from __future__ import annotations

import os
from typing import Optional

import tensorflow as tf

from byteps_tpu.api import (  # noqa: F401  (re-exported parity surface)
    declare_tensor,
    get_pushpull_speed,
    init,
    local_rank,
    local_size,
    rank,
    resume,
    shutdown,
    size,
    suspend,
)
from byteps_tpu.tensorflow.compression import Compression  # noqa: F401
from byteps_tpu.tensorflow.ops import (  # noqa: F401
    _push_pull,
    broadcast,
    push_pull_group,
)

Average = "Average"
Sum = "Sum"


def push_pull(
    tensor,
    scope: str = "",
    average: Optional[bool] = None,
    compression=Compression.none,
    op: Optional[str] = None,
    name: Optional[str] = None,
    enable_async: bool = False,
):
    """Cross-worker reduction of a tf.Tensor (tensorflow/__init__.py:40-81):
    compress → summed _push_pull → decompress → divide by size unless Sum
    or async mode."""
    if op is None:
        op = Sum if average is False else Average
    compressed, ctx = compression.compress(tensor)
    summed = _push_pull(compressed, scope=scope, name=name, average=False)
    out = compression.decompress(summed, ctx)
    if op == Average and not enable_async:
        out = out / tf.cast(size(), out.dtype)
    return out


def _param_name(var, idx: int) -> str:
    """Unique cross-worker key for a variable.  Keras 3 ``Variable.name``
    is the SHORT name ('kernel', 'bias' — identical across layers); only
    ``.path`` ('sequential/dense_1/kernel') is unique, so prefer it."""
    from byteps_tpu.tensorflow.ops import _normalize_name

    name = getattr(var, "path", None) or getattr(var, "name", None)
    return _normalize_name(name) if name else f"param_{idx}"


def broadcast_variables(variables, root_rank: int = 0, scope: str = "") -> None:
    """Assign root's values into every worker's variables
    (tensorflow/__init__.py:113-121)."""
    for i, var in enumerate(variables):
        var.assign(
            broadcast(
                tf.convert_to_tensor(var), root_rank, scope=scope,
                name=f"Broadcast.{_param_name(var, i)}",
            )
        )


def _sync_grads(grads, sources, compression, op: str, scope: str):
    """Shared gradient cross-worker sync: filter live grads, name them by
    their source variable, compress → grouped push_pull (overlapped) →
    decompress → average.  Used by DistributedGradientTape and the Keras
    optimizer wrap."""
    flat = list(grads)
    live = [(i, g) for i, g in enumerate(flat) if g is not None]
    if not live or size() <= 1:
        return flat
    names, comp, ctxs = [], [], []
    for i, g in live:
        names.append(f"Gradient.{scope}.{_param_name(sources[i], i)}")
        c, ctx = compression.compress(tf.convert_to_tensor(g))
        comp.append(c)
        ctxs.append(ctx)
    fusion = os.environ.get("BYTEPS_TF_FUSION", "auto")
    # in-graph dtype-bucket fusion: one host hop + one engine submit per
    # dtype instead of per tensor.  Worth it exactly when the concat/
    # split compile into a graph (tf.function — the Keras train-step
    # case); in eager mode the ~60 extra op dispatches of a 30-tensor
    # list cost MORE than the marshalling saved, so "auto" fuses only
    # while tracing.  1/0 force it on/off (all workers must agree: fusion
    # changes the wire keys).
    use_fused = (
        fusion == "1"
        or (fusion not in ("0", "1") and not tf.executing_eagerly())
    )
    if use_fused:
        from byteps_tpu.tensorflow.ops import push_pull_group_fused

        summed = push_pull_group_fused(comp, names, average=False)
    else:
        summed = push_pull_group(comp, names, average=False)
    for (i, _), s, ctx in zip(live, summed, ctxs):
        out = compression.decompress(s, ctx)
        if op == Average:
            out = out / tf.cast(size(), out.dtype)
        flat[i] = out
    return flat


def __getattr__(name):
    # The broadcast-at-first-batch callback lives in the keras plugin
    # (variables don't exist until the model/optimizer are built, so
    # on_train_begin would be a silent no-op — _keras/callbacks.py:31-49);
    # expose it here lazily to avoid an import cycle and a second variant.
    if name in ("BroadcastGlobalVariablesCallback", "BroadcastGlobalVariablesHook"):
        from byteps_tpu.keras.callbacks import BroadcastGlobalVariablesCallback

        return BroadcastGlobalVariablesCallback
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


class DistributedGradientTape:
    """Wraps tf.GradientTape; ``gradient()`` push_pulls the grads
    (tensorflow/__init__.py:343-417).

    Composition, not inheritance: every non-overridden method (reset,
    stop_recording, jacobian, watched_variables, …) is forwarded to the
    WRAPPED tape, which owns all recording state.
    """

    def __init__(
        self,
        tape: tf.GradientTape,
        compression=Compression.none,
        op: str = Average,
        scope: str = "tape",
    ) -> None:
        self._tape = tape
        self._compression = compression
        self._op = op
        self._scope = scope

    def __enter__(self):
        self._tape.__enter__()
        return self

    def __exit__(self, *exc):
        return self._tape.__exit__(*exc)

    def __getattr__(self, name):
        return getattr(self._tape, name)

    def watch(self, tensor):
        self._tape.watch(tensor)

    def gradient(self, target, sources, output_gradients=None):
        grads = self._tape.gradient(target, sources, output_gradients)
        if size() <= 1:
            return grads
        flat = _sync_grads(
            tf.nest.flatten(grads), tf.nest.flatten(sources),
            self._compression, self._op, self._scope,
        )
        return tf.nest.pack_sequence_as(grads, flat)


def _wrap_keras_optimizer_class(base_cls, compression, op, scope, enable_async):
    """Dynamic subclass of a Keras-3 optimizer whose ``apply_gradients``
    push_pulls the gradients first.  Same class NAME as the wrapped
    optimizer so a saved model restores without byteps installed
    (_keras/__init__.py:77-83)."""

    def apply_gradients(self, grads_and_vars, *args, **kwargs):
        pairs = [(g, v) for g, v in grads_and_vars]
        if size() > 1 and not enable_async and pairs:
            grads, vars_ = zip(*pairs)
            pairs = list(zip(_sync_grads(grads, vars_, compression, op, scope), vars_))
        result = base_cls.apply_gradients(self, pairs, *args, **kwargs)
        if enable_async and size() > 1:
            _async_param_sync(self, pairs, scope)
        return result

    return type(
        base_cls.__name__,
        (base_cls,),
        {"apply_gradients": apply_gradients, "_byteps_wrapped": True},
    )


def _async_param_sync(opt, pairs, scope) -> None:
    """Async-mode parameter-store sync: push weight DELTAS, pull back the
    server's latest parameters (torch/__init__.py:195-218,
    tensorflow/__init__.py:244-268 translated to eager assignment)."""
    for i, (_, var) in enumerate(pairs):
        name = f"AsyncParam.{scope}.{_param_name(var, i)}"
        cur = tf.convert_to_tensor(var)
        prev = getattr(var, "_byteps_prev", None)
        delta = cur - prev if prev is not None else cur
        new = _push_pull(delta, name=name, average=False)
        var.assign(new)
        var._byteps_prev = tf.identity(new)


def DistributedOptimizer(
    optimizer,
    name: Optional[str] = None,
    compression=Compression.none,
    op: str = Average,
    scope: str = "opt",
    backward_passes_per_step: int = 1,
):
    """Wrap a Keras optimizer so gradients are push_pulled before being
    applied (tensorflow/__init__.py:282-340 routed through the Keras path,
    since TF 2.21 ships Keras 3 only)."""
    if backward_passes_per_step > 1:
        raise ValueError(
            "backward_passes_per_step > 1 is not supported with Keras "
            "(matching the reference, tensorflow/__init__.py:300-302)"
        )
    if not isinstance(optimizer, tf.keras.optimizers.Optimizer):
        raise ValueError(
            f"expected a keras optimizer, got {type(optimizer).__name__}"
        )
    enable_async = int(os.getenv("BYTEPS_ENABLE_ASYNC", "0")) != 0
    cls = _wrap_keras_optimizer_class(
        type(optimizer), compression, op, scope, enable_async
    )
    return cls.from_config(optimizer.get_config())
