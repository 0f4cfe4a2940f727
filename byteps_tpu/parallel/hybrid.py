"""Hybrid two-level data parallelism: ICI mesh reduce + PS push_pull.

The reference's defining topology (docs/architecture.md:26-44): gradients
are first reduced INSIDE the machine over the fast local interconnect
(NCCL there), and only the machine-level sum crosses the slow inter-host
network through the PS push/pull plane.  The TPU translation:

- level 1: a jitted ``shard_map`` training-gradient step over this
  host's ``Mesh`` — per-device gradients pmean'd over the data axis with
  XLA collectives riding ICI; tensor-parallel parameters keep their
  sharding (their gradients are per-shard by construction).
- level 2: the host hop — each gradient crosses the DCN through the real
  PS plane (``push_pull_async``, priority = −declaration order, so the
  OSDI scheduling applies to the inter-host leg exactly as in the
  reference), averaged across workers.
- the optimizer applies the globally-averaged gradients — a replicated
  leaf's comes back from the engine in the sharding it was submitted in,
  a tensor-parallel leaf's is put back on its ``NamedSharding`` by the
  step — and parameters stay on the device for the next step.

This is the composition in one loop: the mesh plane and the PS plane
are not alternatives, they are the two levels of one step.

    mesh = Mesh(devices.reshape(2, 2), ("dp", "tp"))
    hdp = HybridDataParallel(loss_fn, params, optax.sgd(0.1), mesh=mesh,
                             param_specs=specs, batch_spec=P("dp"))
    for batch in loader:
        loss = hdp.step(batch)      # ICI pmean -> PS push_pull -> update
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import byteps_tpu as bps
from byteps_tpu.comm.mesh import get_global_mesh
from byteps_tpu.core.tracing import span


class HybridDataParallel:
    """Two-level DDP: mesh collectives inside the host, PS across hosts.

    ``loss_fn(params, batch) -> scalar`` runs per-device inside
    shard_map with the mesh axes bound (use ``lax.psum(..., "tp")`` etc.
    for tensor-parallel partials).  ``param_specs``/``batch_spec`` are
    PartitionSpec pytrees (defaults: replicated params, batch sharded on
    ``dp_axis``).
    """

    _instances = 0

    def __init__(
        self,
        loss_fn: Callable,
        params: Dict[str, Any],
        optimizer: optax.GradientTransformation,
        mesh: Optional[Mesh] = None,
        dp_axis: str = "dp",
        param_specs: Optional[Dict[str, P]] = None,
        batch_spec: Any = None,
        name_prefix: str = "Hybrid",
    ) -> None:
        self.mesh = mesh or get_global_mesh()
        if self.mesh is None:
            raise RuntimeError("no mesh: call byteps_tpu.init() or pass mesh=")
        self.optimizer = optimizer
        self.dp_axis = dp_axis
        self._iid = HybridDataParallel._instances
        HybridDataParallel._instances += 1
        self._prefix = f"{name_prefix}.{self._iid}"

        leaves = jax.tree_util.tree_leaves_with_path(params)
        self._names = [jax.tree_util.keystr(path) for path, _ in leaves]
        for name in self._names:
            bps.declare_tensor(f"{self._prefix}{name}")
        self._specs = (
            param_specs
            if param_specs is not None
            else jax.tree.map(lambda _: P(), params)
        )
        self._shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        # dtypes are the caller's choice (bf16 params are standard on TPU)
        self.params = jax.tree.map(
            lambda v, sh: jax.device_put(jnp.asarray(v), sh),
            params, self._shardings,
        )
        self.opt_state = optimizer.init(self.params)
        batch_spec = P(dp_axis) if batch_spec is None else batch_spec

        dp_size = self.mesh.shape[dp_axis]

        def forward(p, batch):
            # the scope names every operation of the loss; the backward
            # pass then reads transpose(jvp(forward)) in the trace
            with jax.named_scope("forward"):
                return loss_fn(p, batch)

        def hybrid_grad(p, batch):
            loss, grads = jax.value_and_grad(forward)(p, batch)
            loss = lax.pmean(loss, dp_axis)
            # level 1, the ICI reduce: under VMA-checked shard_map AD the
            # cotangent of every parameter is ALREADY psum'd over the
            # axes the parameter is unvarying on (dp for all params —
            # that psum is the ICI all-reduce); an explicit pmean here
            # would double-count.  Only the sum→mean division remains.
            grads = jax.tree.map(lambda g: g / dp_size, grads)
            return loss, grads

        self._grad = jax.jit(
            jax.shard_map(
                hybrid_grad,
                mesh=self.mesh,
                in_specs=(self._specs, batch_spec),
                out_specs=(P(), self._specs),
                check_vma=True,
            )
        )

        def hybrid_apply(p, s, g):
            with jax.named_scope("optimizer"):
                updates, s = optimizer.update(g, s, p)
                return optax.apply_updates(p, updates), s

        self._apply = jax.jit(hybrid_apply)
        self._steps = 0

    def step(self, batch) -> float:
        """One full two-level step; returns the (host-level) loss.  Its
        phases are ``tracing.span``s (docs/observability.md "Phases on the
        profiler's clock")."""
        self._steps += 1
        with span("hybrid.step", step=self._steps, wall_ns=time.time_ns()):
            with span("hybrid.grad_dispatch"):
                loss, grads = self._grad(self.params, batch)
            # level 2: the DCN hop — every gradient through the PS plane,
            # front layers first (priority = −declaration order)
            with span("hybrid.enqueue"):
                flat, treedef = jax.tree_util.tree_flatten(grads)
                # hand the engine the LIVE jax.Array: COPYD2H stages each
                # partition asynchronously on its own thread (overlapping
                # the remaining gathers) and the priority queue has real
                # work to reorder — np.asarray here would serialize every
                # gather on this thread before the first byte hit the wire
                handles = [
                    bps.push_pull_async(
                        g,
                        name=f"{self._prefix}{self._names[i]}",
                        average=True,
                        priority=-i,
                    )
                    for i, g in enumerate(flat)
                ]
            with span("hybrid.hop_wait"):
                averaged = [bps.synchronize(h) for h in handles]
            with span("hybrid.reput"):
                # the engine made a replicated leaf's average in the
                # sharding its gradient went in (COPYH2D), so this put is
                # of an array onto the sharding it has: the same array
                # back.  A leaf sharded for real (tensor-parallel) comes
                # back on one device and is placed here
                g_global = jax.tree_util.tree_unflatten(treedef, averaged)
                g_global = jax.tree.map(
                    lambda g, sh: jax.device_put(jnp.asarray(g), sh),
                    g_global, self._shardings,
                )
            with span("hybrid.apply_dispatch"):
                self.params, self.opt_state = self._apply(
                    self.params, self.opt_state, g_global
                )
            with span("hybrid.loss_sync"):
                return float(loss)
