"""Test-only mxnet-compatible shim (NOT shipped; lives under tests/).

The image has no mxnet wheel, but byteps_tpu.mxnet's logic must be
EXECUTED, not just imported.  This module implements
the exact API subset the plugin touches — numpy-backed NDArray,
``mx.nd.array``, ``mx.optimizer.Optimizer`` (+ a concrete SGD),
``mx.gluon.Trainer``/``Parameter`` with real gluon step semantics
(lazy ``_init_params``, ``rescale_grad = _scale / batch_size``) — so the
plugin's DistributedOptimizer/DistributedTrainer/broadcast_parameters
run their real code paths against a live PS cluster.

Faithfulness notes (vs real mxnet/gluon):
- ``Trainer.step`` runs ``_init_params`` (when params are pending),
  ``_allreduce_grads``, then the optimizer update loop with
  ``rescale_grad = self._scale / batch_size`` — the contract the
  plugin's ``step``/``_allreduce_grads`` override relies on.
- ``Parameter`` exposes ``_deferred_init``, ``_check_and_get``,
  ``list_grad``, ``grad_req`` exactly as the plugin consumes them.
- NDArray is synchronous (wait_to_read is a no-op), matching the
  plugin's in-place write-back semantics.
"""

from __future__ import annotations

import numpy as _np

np = _np  # the plugin's compression.py probes mx.np for dtype constants


class Context:
    def __init__(self, kind: str = "cpu", index: int = 0) -> None:
        self.kind = kind
        self.index = index

    def __repr__(self) -> str:
        return f"{self.kind}({self.index})"


_CPU = Context()


def cpu(index: int = 0) -> Context:
    return _CPU


class NDArray:
    def __init__(self, data, dtype=None, ctx: Context = None) -> None:
        self._a = _np.array(data, dtype=dtype or _np.float32)
        self._ctx = ctx or _CPU

    # --- surface the plugin touches -----------------------------------
    @property
    def dtype(self):
        return self._a.dtype

    @property
    def context(self) -> Context:
        return self._ctx

    @property
    def shape(self):
        return self._a.shape

    def asnumpy(self) -> _np.ndarray:
        return self._a.copy()

    def copy(self) -> "NDArray":
        return NDArray(self._a.copy(), dtype=self._a.dtype, ctx=self._ctx)

    def astype(self, dtype) -> "NDArray":
        return NDArray(self._a.astype(dtype), dtype=dtype, ctx=self._ctx)

    def wait_to_read(self) -> None:
        pass  # synchronous backend

    def __setitem__(self, key, value) -> None:
        self._a[key] = value._a if isinstance(value, NDArray) else value

    def __getitem__(self, key):
        return NDArray(self._a[key], dtype=self._a.dtype, ctx=self._ctx)

    def __imul__(self, other) -> "NDArray":
        self._a *= other._a if isinstance(other, NDArray) else other
        return self

    def __isub__(self, other) -> "NDArray":
        self._a -= other._a if isinstance(other, NDArray) else other
        return self

    def __iadd__(self, other) -> "NDArray":
        self._a += other._a if isinstance(other, NDArray) else other
        return self

    def __repr__(self) -> str:
        return f"NDArray({self._a!r})"


class _NdModule:
    @staticmethod
    def array(data, dtype=None, ctx: Context = None) -> NDArray:
        return NDArray(data, dtype=dtype, ctx=ctx)

    @staticmethod
    def zeros(shape, dtype=_np.float32, ctx: Context = None) -> NDArray:
        return NDArray(_np.zeros(shape, dtype), dtype=dtype, ctx=ctx)


nd = _NdModule()


class Optimizer:
    """mx.optimizer.Optimizer subset: state creation + learning rate."""

    def __init__(self, learning_rate: float = 0.01, rescale_grad: float = 1.0,
                 **kwargs) -> None:
        self.learning_rate = learning_rate
        self.rescale_grad = rescale_grad
        for k, v in kwargs.items():
            setattr(self, k, v)

    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        self.update(index, weight, grad, state)

    def set_learning_rate(self, lr: float) -> None:
        self.learning_rate = lr

    def set_lr_mult(self, args_lr_mult) -> None:
        pass

    def set_wd_mult(self, args_wd_mult) -> None:
        pass


class SGD(Optimizer):
    def update(self, index, weight, grad, state):
        if isinstance(index, (tuple, list)):
            for i, w, g in zip(index, weight, grad):
                self.update(i, w, g, state)
            return
        weight._a -= self.learning_rate * self.rescale_grad * (
            grad._a.astype(weight._a.dtype)
        )


_OPTIMIZERS = {"sgd": SGD}


def create(name: str, **kwargs) -> Optimizer:
    return _OPTIMIZERS[name.lower()](**kwargs)


class _OptimizerModule:
    Optimizer = Optimizer
    SGD = SGD
    create = staticmethod(create)


optimizer = _OptimizerModule()


class Parameter:
    def __init__(self, name: str, data, grad_req: str = "write") -> None:
        self.name = name
        arr = _np.asarray(data, dtype=_np.float32)
        self._data = [NDArray(arr)]
        self._grad = [NDArray(_np.zeros_like(arr))]
        self.grad_req = grad_req
        self._deferred_init = False

    def data(self) -> NDArray:
        return self._data[0]

    def grad(self) -> NDArray:
        return self._grad[0]

    def list_grad(self):
        return self._grad

    def _check_and_get(self, arr_list, _t):
        return arr_list


class Trainer:
    """mx.gluon.Trainer subset with the step() contract the plugin's
    overrides depend on."""

    def __init__(self, params, optimizer, optimizer_params=None, kvstore=None):
        self._params = list(params)
        self._param2idx = {p.name: i for i, p in enumerate(self._params)}
        self._params_to_init = list(self._params)
        if isinstance(optimizer, str):
            optimizer = create(optimizer, **(optimizer_params or {}))
        elif optimizer_params:
            for k, v in optimizer_params.items():
                setattr(optimizer, k, v)
        self._optimizer = optimizer
        self._scale = 1.0
        self._states = [None] * len(self._params)

    @property
    def learning_rate(self) -> float:
        return self._optimizer.learning_rate

    def _init_params(self) -> None:
        self._params_to_init = []

    def _allreduce_grads(self) -> None:
        pass

    def step(self, batch_size, ignore_stale_grad=False) -> None:
        if self._params_to_init:
            self._init_params()
        # real gluon: rescale by _scale/batch_size (the plugin sets
        # _scale = batch_size so its own normalization is not repeated)
        self._optimizer.rescale_grad = self._scale / batch_size
        self._allreduce_grads()
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False) -> None:
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            if self._states[i] is None:
                self._states[i] = self._optimizer.create_state_multi_precision(
                    i, p.data()
                )
            self._optimizer.update_multi_precision(
                i, p.data(), p.list_grad()[0], self._states[i]
            )


class _GluonModule:
    Trainer = Trainer
    Parameter = Parameter


gluon = _GluonModule()
