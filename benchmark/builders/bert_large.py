"""Builder for the ``bert_large`` configuration (benchmark/configs/bert_large.json).

Same four names as every builder: ``flops_per_sample``, ``make_optimizer``,
``plain_loss`` (the plain reference, jax alone), ``make_state`` (parameters
and the fixed batch on the device from the seed) and ``build`` (the program's
``models/transformer.build_train_step`` wrapped into one ``step()``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
_SIZES = ("vocab_size", "d_model", "n_heads", "d_head", "d_ff", "n_layers",
          "max_seq", "causal", "remat", "use_flash")


def flops_per_sample(cfg: dict) -> float:
    """Forward + backward of one sequence, recomputation not counted:
    6 S (12 L D^2 + D V) for the matrix multiplications of the blocks and the
    output head, 12 L S^2 D for the attention scores and their use."""
    s, layers, d, v = cfg["max_seq"], cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]
    return float(6 * s * (12 * layers * d * d + d * v) + 12 * layers * s * s * d)


def make_optimizer(cfg: dict) -> optax.GradientTransformation:
    opt = cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"bert_large builder knows adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"])


def _ln(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def plain_loss(cfg: dict):
    """The reference: a pre-LN transformer encoder and mean token
    cross-entropy in plain jax — no byteps_tpu.  Parameters are the program's
    flat dict (layer entries stacked under leading dims (1, layers)); compute
    in ``compute_dtype`` as the configuration states, softmax statistics and
    the loss in f32, each layer recomputed in the backward pass so that the
    reference's memory stays below the system's."""
    cdt = _DTYPES[cfg["compute_dtype"]]
    scale = cfg["d_head"] ** -0.5
    top = ("embed", "pos", "ln_f_s", "ln_f_b", "head")

    def layer(x, lp):
        h = _ln(x, lp["ln1_s"], lp["ln1_b"]).astype(cdt)
        q, k, v = (jnp.einsum("bsd,dhk->bhsk", h, lp[w].astype(cdt))
                   for w in ("wq", "wk", "wv"))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k)
        if cfg["causal"]:
            s = scores.shape[-1]
            scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
        attn = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
        x = x + jnp.einsum("bhsk,hkd->bsd", attn, lp["wo"].astype(cdt)).astype(x.dtype)
        g = _ln(x, lp["ln2_s"], lp["ln2_b"]).astype(cdt)
        mid = jax.nn.gelu(g @ lp["w1"].astype(cdt) + lp["b1"].astype(cdt))
        y = mid @ lp["w2"].astype(cdt) + lp["b2"].astype(cdt)
        return x + y.astype(x.dtype), None

    def loss(params, batch):
        tokens, targets = batch
        x = params["embed"][tokens] + params["pos"][jnp.arange(tokens.shape[1])]
        stack = {k: v[0] for k, v in params.items() if k not in top}
        x, _ = lax.scan(jax.checkpoint(layer), x.astype(cdt), stack)
        h = _ln(x, params["ln_f_s"], params["ln_f_b"]).astype(cdt)
        logits = (h @ params["head"].astype(cdt)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return loss


def _transformer_config(cfg: dict):
    from byteps_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        compute_dtype=_DTYPES[cfg["compute_dtype"]], **{k: cfg[k] for k in _SIZES}
    )


def _mesh4(mesh):
    """The program's transformer wants a (dp, pp, sp, tp) mesh."""
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    return make_training_mesh(
        mesh.size, {"dp": mesh.shape["dp"], "pp": 1, "sp": 1, "tp": 1},
        devices=list(mesh.devices.flat),
    )


def make_state(cfg: dict, key: jax.Array, mesh):
    """Parameters (the rule of ``transformer.init_params``: N(0, 1/fan_in),
    0.02 for the tables, ones and zeros for scales and biases) and one fixed
    batch of tokens with next-token targets, made on the device from ``key``
    in one jitted call.  ``init_params`` itself draws on the host."""
    from byteps_tpu.models import transformer as tfm

    tcfg, mesh = _transformer_config(cfg), _mesh4(mesh)
    batch = cfg["batch_per_chip"] * mesh.shape["dp"]
    layouts = tfm._layouts(tcfg)

    def make(key):
        params = {}
        for i, (name, (shape, _, _)) in enumerate(layouts.items()):
            full = (1, tcfg.n_layers) + shape if tfm._is_layer_param(name) else shape
            if name.endswith("_s"):
                params[name] = jnp.ones(full, jnp.float32)
            elif name.endswith("_b") or name.startswith("b"):
                params[name] = jnp.zeros(full, jnp.float32)
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                std = 0.02 if name in ("embed", "pos") else 1.0 / math.sqrt(fan_in)
                params[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), full, jnp.float32
                )
        tokens = jax.random.randint(
            jax.random.fold_in(key, len(layouts)), (batch, tcfg.max_seq), 0,
            tcfg.vocab_size, jnp.int32,
        )
        return params, tokens, jnp.roll(tokens, -1, axis=1)

    rows = NamedSharding(mesh, P("dp", "sp"))
    specs = {k: NamedSharding(mesh, s) for k, s in tfm.param_specs(tcfg).items()}
    params, tokens, targets = jax.jit(make, out_shardings=(specs, rows, rows))(key)
    return params, (tokens, targets), batch


def build(cfg: dict, traffic: dict, params, batch, mesh):
    """``build_train_step`` with the optimizer state made as the program's
    examples make it (``jax.jit(tx.init)``).  Returns ``step()``, which
    dispatches one training step and returns ``(loss, parameters)``; the
    step donates ``params``."""
    from byteps_tpu.models.transformer import build_train_step

    if traffic["step_path"] != "local":
        raise ValueError(f"bert_large builder has no step path {traffic['step_path']!r}")
    tx = make_optimizer(cfg)
    state = [params, jax.jit(tx.init)(params)]
    step_fn = build_train_step(_transformer_config(cfg), _mesh4(mesh), tx)

    def step():
        state[0], state[1], loss = step_fn(state[0], state[1], *batch)
        return loss, state[0]

    return step
