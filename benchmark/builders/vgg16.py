"""Builder for the ``vgg16`` configuration (benchmark/configs/vgg16.json).

``make_state`` makes parameters and the fixed batch on the device from the
seed; ``build`` wraps the program's own entry points
(``byteps_tpu.models.vgg.VGG16`` through ``build_flax_data_parallel_step``
or ``HybridDataParallel``) into one ``step()``; ``plain_loss`` is the
configuration's plain reference, written against jax alone;
``flops_per_sample`` counts what a sample's forward and backward passes
require.  The harness (benchmark/run.py) runs the reference on the state,
then times ``step()``, and never looks inside.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward of one image: 3 x 2 x multiply-accumulates of the
    convolutions and the three dense layers (pooling, relu and the loss are
    not counted)."""
    hw, cin, macs = cfg["image_size"], cfg["in_channels"], 0
    for v in cfg["conv_channels"]:
        if v == "M":
            hw //= 2
        else:
            macs += 9 * cin * v * hw * hw
            cin = v
    flat = cin * hw * hw
    macs += flat * cfg["hidden"] + cfg["hidden"] ** 2 + cfg["hidden"] * cfg["num_classes"]
    return 6.0 * macs


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"vgg16 builder knows sgd, not {opt['name']!r}")
    return optax.sgd(opt["learning_rate"], momentum=opt["momentum"])


def plain_loss(cfg: dict):
    """The reference: VGG configuration D and softmax cross-entropy in plain
    jax — no flax, no byteps_tpu.  Same parameter tree as the flax module
    (Conv_i / Dense_i with kernel, bias), compute in ``compute_dtype`` with
    an f32 classifier, as the configuration states."""
    cdt = _DTYPES[cfg["compute_dtype"]]

    def loss(params, batch):
        x, y = batch
        x = x.astype(cdt)
        conv_i = 0
        for v in cfg["conv_channels"]:
            if v == "M":
                x = lax.reduce_window(
                    x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
                )
                continue
            p = params[f"Conv_{conv_i}"]
            conv_i += 1
            x = lax.conv_general_dilated(
                x, p["kernel"].astype(cdt), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ) + p["bias"].astype(cdt)
            x = jnp.maximum(x, 0)
        x = x.reshape(x.shape[0], -1)
        for i in (0, 1):
            p = params[f"Dense_{i}"]
            x = jnp.maximum(x @ p["kernel"].astype(cdt) + p["bias"].astype(cdt), 0)
        p = params["Dense_2"]
        logits = x.astype(jnp.float32) @ p["kernel"] + p["bias"]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    return loss


def make_state(cfg: dict, key: jax.Array, mesh):
    """Parameters and the one fixed batch, made on the device from ``key``
    in one jitted call.  Returns ``(params, batch, global_batch)``."""
    model = _model(cfg)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]
    hw = cfg["image_size"]
    rows = NamedSharding(mesh, P("dp"))

    def make(key):
        kp, kx, ky = jax.random.split(key, 3)
        x = jax.random.normal(kx, (batch, hw, hw, cfg["in_channels"]), jnp.float32)
        y = jax.random.randint(ky, (batch,), 0, cfg["num_classes"], jnp.int32)
        return model.init(kp, x[:1])["params"], x, y

    out = (NamedSharding(mesh, P()), rows, rows)
    params, x, y = jax.jit(make, out_shardings=out)(key)
    return params, (x, y), batch


def _model(cfg: dict):
    from byteps_tpu.models.vgg import VGG16

    return VGG16(
        dtype=_DTYPES[cfg["compute_dtype"]], hidden=cfg["hidden"],
        num_classes=cfg["num_classes"],
    )


def build(cfg: dict, traffic: dict, params, batch, mesh):
    """The step path the traffic names, over the program's own entry points.
    Returns ``step()``: it dispatches one whole training step and returns
    ``(loss, parameters)`` for the harness to block on, the parameters as the
    tree ``make_state`` made.  ``params`` belongs
    to the step from here on (the local step donates it)."""
    model, tx = _model(cfg), make_optimizer(cfg)

    def loss_from_logits(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()

    if traffic["step_path"] == "ps":
        from byteps_tpu.parallel.hybrid import HybridDataParallel

        hdp = HybridDataParallel(
            lambda p, xy: loss_from_logits(model.apply({"params": p}, xy[0]), xy[1]),
            params, tx, mesh=mesh, batch_spec=(P("dp"), P("dp")),
        )

        def step():
            loss = hdp.step(batch)
            return loss, hdp.params

        return step
    if traffic["step_path"] == "local":
        from byteps_tpu.optim import build_flax_data_parallel_step

        state = [{"params": params}, jax.jit(tx.init)(params)]
        step_fn = build_flax_data_parallel_step(
            model.apply, loss_from_logits, tx, mesh=mesh
        )

        def step():
            state[0], state[1], loss = step_fn(state[0], state[1], batch)
            return loss, state[0]["params"]

        return step
    raise ValueError(f"vgg16 builder has no step path {traffic['step_path']!r}")
