#!/usr/bin/env python3
"""Where the limits on the precision of the families behind ``build_train_step``
come from: the six MoE families', and the looped dense and cross-decoder
families' (no experts: no routed leaf among the gradient's readings, no
``--pin``).

    python tools/latent_moe_precision.py --seeds 2900002001 2900002011 ...
    python tools/latent_moe_precision.py --config qwen3_next_80b_ep32 --seeds ...
    python tools/latent_moe_precision.py --config lfm2_24b_a2b_ep8 --seeds ...
    python tools/latent_moe_precision.py --config trinity_mini_ep16 --seeds ...
    python tools/latent_moe_precision.py --config smallthinker_21b_ep8 --seeds ...
    python tools/latent_moe_precision.py --config nemotron_twotower_30b_ep16 --seeds ...
    python tools/latent_moe_precision.py --config ouro_2_6b_pp8 --seeds ...
    python tools/latent_moe_precision.py --config sdar_30b_a3b_ep8 --seeds ...
    python tools/latent_moe_precision.py --config sdar_30b_a3b_ep8 --fault unit_weights --seeds ...
    python tools/latent_moe_precision.py --config phi4_mini_flash_vp8 --seeds ...
    python tools/latent_moe_precision.py --config kimi_linear_48b_ep32 --seeds ...

For each seed, at the size of benchmark/configs/<config>.json (by default
joyai_llm_flash_ep32.json) and with the benchmark's own state (``make_state`` from the seed as run.py folds
it), on the TPU:

  f32       the plain reference (the builder's blocked ``plain_loss``): three
            adamw steps, the first step's gradient kept
  program   ``build_train_step`` over the builder's model config: one step's
            gradient (as chip_smoke.py's leg E takes it) and three adamw steps
  stated    the plain reference at the precision the configuration states:
            bf16 operands, f32 norm statistics, router and softmax — a model
            of the program, to show that the control reads like it
  below     the same with the norms' statistics, the router's scores and
            weights and the softmax (and, where the configuration has a
            state-space scan, its step sizes, decay sums and states — a
            Mamba-1 scan's Δ, decay and state; where it has a delta rule, its
            log-decay (a head's or a key channel's), decay and state; where it has differential
            attention, λ and the pair norm's statistics; where it
            has an exit gate, the gate, its distribution and entropy; where
            it states a float32 residual stream, that) in bf16: the nearest
            precision below, which ``correct`` has to refuse
  below_stream | below_statistics   where the builder's ``plain_loss`` takes a
            ``stream`` dtype: the two steps between ``stated`` and ``below``,
            the stream alone and the statistics alone in bf16

and against f32, as ``benchmark/run.py`` and leg E read them: the largest
relative distance of the three losses (``reference_rtol``); the parameters
after three steps as a share of the reference's own update, over all leaves
and in the worst (``reference_update_rtol``: value, leaf_value); the first
gradient by leaf (leg E's limits).  One JSON line a seed on stdout, all of
them in ``chiprun_out/<config>_precision.json``.  ``--rehearse`` runs the
configuration's rehearsal cuts on the CPU: control flow only, never a reading.

``--fault`` (the block-diffusion family's configuration alone) runs, in place
of the program and the controls, the program with a fault PLANTED — what the
cell's limits have to refuse: ``unit_weights``, the loss weights ignored (every
masked token at weight 1, where the batch says 1 / t); ``causal_noisy``, the
noised copy given the causal mask among its own rows (a noisy query sees every
earlier noisy key, where it should see its own block's, both directions; the
clean keys as they should be) — :func:`causal_noisy_attention` in the mixer's
place, XLA's dense form a block of queries at a time.
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import inspect
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _builder(name: str):
    path = os.path.join(ROOT, "benchmark", "builders", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"precision_{name}_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def causal_noisy_attention(q, k, v, block_length, scale=None, **_):
    """``ops/flash_attention.block_diffusion_attention``'s signature with the
    PLANTED fault: noisy query ``i`` sees noisy key ``j`` iff ``j <= i``.
    Dense, 256 queries at a time, each block rebuilt in the backward pass."""
    import jax
    import jax.numpy as jnp

    b, h, sq, d = q.shape
    half, group = k.shape[2] // 2, h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    rows = min(256, sq)
    cols = jnp.arange(2 * half)
    c_noisy, c_pos = cols < half, cols % half

    @jax.checkpoint
    def one(xs):
        qb, first = xs
        r = first + jnp.arange(rows)
        r_noisy, r_pos = (r < half)[:, None], (r % half)[:, None]
        rb, cb = r_pos // block_length, c_pos // block_length
        seen = jnp.where(c_noisy, r_noisy & (c_pos <= r_pos),
                         jnp.where(r_noisy, rb > cb, rb >= cb))
        scores = jnp.einsum("bhqd,bhkd->bhqk", qb, k,
                            preferred_element_type=jnp.float32) * (scale or d ** -0.5)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)

    blocks = jnp.moveaxis(q.reshape(b, h, sq // rows, rows, d), 2, 0)
    out = jax.lax.map(one, (blocks, rows * jnp.arange(sq // rows)))
    return jnp.moveaxis(out, 0, 2).reshape(b, h, sq, -1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--config", default="joyai_llm_flash_ep32",
                    help="a configuration of benchmark/configs whose builder's plain_loss "
                         "takes (compute, statistics)")
    ap.add_argument("--pin", action="store_true",
                    help="with chip_smoke.pin_choice: every token picks the same experts")
    ap.add_argument("--fault", choices=("unit_weights", "causal_noisy"),
                    help="the block-diffusion configuration's program with this fault planted, "
                         "in place of the program and the controls")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from chip_smoke import gradient_readings, keep_gradient, pin_choice

    from byteps_tpu.models.transformer import build_train_step

    dev = jax.devices()[0]
    if (dev.platform == "tpu") == args.rehearse:
        raise SystemExit(f"readings come from the TPU (and --rehearse stays off it); "
                         f"jax found {dev.platform!r}")
    with open(os.path.join(ROOT, "benchmark", "configs", f"{args.config}.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearsal"])
    builder, steps = _builder(cfg["builder"]), 3
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    tx = builder.make_optimizer(cfg)
    def plain_steps(loss_fn):
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(p, s, b):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            updates, s = tx.update(grads, s, p)
            return optax.apply_updates(p, updates), s, loss, grads

        def run(p, batch):
            """(losses, the first gradient, the parameters after the steps);
            ``p`` is donated."""
            s, losses, first = jax.jit(tx.init)(p), [], None
            for i in range(steps):
                p, s, loss, grads = step(p, s, batch)
                losses.append(float(loss))
                # off the device at once: the steps need the room
                first = jax.device_get(grads) if i == 0 else first
                del grads
            return losses, first, p
        return run

    keep = keep_gradient()
    model, mesh4 = builder._model_config(cfg), builder._mesh4(mesh)
    grad_step = build_train_step(model, mesh4, keep, donate=False)
    train_step = build_train_step(model, mesh4, tx)  # as the builder's ``build`` makes it

    # a builder may hand run.py its parameters in a tree of its own (phi4flash's)
    own = jax.jit(functools.partial(getattr(builder, "program_params", lambda _, p: p), cfg),
                  donate_argnums=0)
    compared = getattr(builder, "compared_params", lambda p: p)

    def program_steps(p, batch):
        p = own(p)
        first = jax.device_get(compared(grad_step(p, keep.init(p), *batch)[1]))
        s, losses = jax.jit(tx.init)(p), []
        for _ in range(steps):
            p, s, loss = train_step(p, s, *batch)
            losses.append(float(loss))
        return losses, first, compared(p)

    runs = {"program": program_steps,
            "stated": plain_steps(builder.plain_loss(cfg, jnp.bfloat16, jnp.float32)),
            "below": plain_steps(builder.plain_loss(cfg, jnp.bfloat16, jnp.bfloat16))}
    if "stream" in inspect.signature(builder.plain_loss).parameters:
        # a family that states a float32 residual stream: "below" has stream
        # and statistics in bf16 together; these are the two steps between
        for name, dtypes in (("below_stream", (jnp.float32, jnp.bfloat16)),
                             ("below_statistics", (jnp.bfloat16, jnp.float32))):
            runs[name] = plain_steps(builder.plain_loss(cfg, jnp.bfloat16, *dtypes))
    if args.fault == "unit_weights":
        runs = {"fault": lambda p, batch: program_steps(
            p, (*batch[:2], (batch[2] > 0).astype(batch[2].dtype)))}
    elif args.fault:
        import byteps_tpu.models.block_diffusion_moe as family

        family.block_diffusion_attention = causal_noisy_attention  # before any step is traced
        runs = {"fault": program_steps}
    reference = plain_steps(builder.plain_loss(cfg))

    @jax.jit
    def dist(x, y):
        return jnp.linalg.norm(x - y)

    lines = []
    for seed in args.seeds:
        key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)  # as run.py
        params, batch, _ = builder.make_state(cfg, key, mesh)
        if args.pin:
            params = pin_choice(params, cfg)
        # the starting point, and what the reference makes of it, wait on the
        # host: the device holds one run's state at a time (the f32 step
        # alone takes 14 of its 15.75 GiB)
        placed = jax.tree.map(lambda x: x.sharding, params)
        start = jax.device_get(params)
        want_losses, want_grads, want_params = reference(params, batch)
        del params
        want_params = jax.device_get(want_params)
        moved = {k: float(np.linalg.norm(want_params[k] - start[k])) for k in start}
        line = {"seed": seed, "pinned": args.pin, "f32_losses": want_losses}
        for name, run in runs.items():
            losses, grads, after = run(jax.device_put(start, placed), batch)
            off = {k: float(dist(after[k], want_params[k])) for k in after}
            del after
            apart = {k: off[k] / moved[k] if moved[k] else (math.inf if off[k] else 0.0)
                     for k in off}
            worst = max(apart, key=apart.get)
            line[name] = {
                "losses": losses,
                "loss_off": max(abs(g - w) / abs(w) for g, w in zip(losses, want_losses)),
                "update": math.hypot(*off.values()) / math.hypot(*moved.values()),
                "update_leaf": [worst, apart[worst]],
                "update_median": sorted(apart.values())[len(apart) // 2],
                "update_by_leaf": apart,
                "gradient": gradient_readings(grads, want_grads),
            }
            del grads
        print(json.dumps(line), flush=True)
        lines.append(line)
        del start, want_grads, want_params
    if not args.rehearse:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        name = (f"{args.config}_precision{'_pinned' if args.pin else ''}"
                f"{'_' + args.fault if args.fault else ''}.json")
        with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
