"""Benchmark: BERT-large training throughput on one TPU chip.

The reference's headline benchmark is BERT-large pretraining throughput
(README.md:38-46, BASELINE.md); with one real chip available the honest
single-chip metric is train samples/sec (fwd+bwd+adam, bf16 compute,
seq 128 — GluonNLP phase-1 geometry, batch 64/device like the reference's
per-GPU batch).

``vs_baseline`` normalizes against a 40%-MFU target on the chip's peak
bf16 throughput — i.e. vs_baseline >= 1.0 means the compiled step reaches
the efficiency class the reference claims for its GPU stack (~90% scaling
of a well-fed device).  Prints ONE JSON line.

Measures the TPU or fails: no TPU, ``JAX_PLATFORMS=cpu``, a device kind
missing from ``PEAKS``, or any exception other than out-of-memory (which
only moves on to the next, smaller candidate) ends in a non-zero exit.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

#: device_kind → (peak bf16 FLOP/s, HBM bytes/s) of one chip.  Source:
#: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s).
#: A kind that is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}


def _require_tpu():
    """The device every number below is measured on; exits non-zero
    unless it is a TPU whose peaks are known."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU; jax found platform {dev.platform!r} "
            f"({dev.device_kind}) — no result"
        )
    if dev.device_kind not in PEAKS:
        raise SystemExit(
            f"bench.py has no peak figures for device_kind {dev.device_kind!r} "
            f"(known: {sorted(PEAKS)}); add them with their source"
        )
    return dev


def _is_oom(e: Exception) -> bool:
    return "RESOURCE_EXHAUSTED" in repr(e) or "out of memory" in repr(e).lower()


def _first_that_fits(batches, time_fn):
    """``(batch, time_fn(batch))`` for the first batch that does not run
    out of device memory.  Out-of-memory is the ONLY error that moves on
    to the next candidate; anything else — and running out of candidates —
    propagates."""
    for batch in batches:
        try:
            return batch, time_fn(batch)
        except Exception as e:  # noqa: BLE001 — re-raised unless OOM
            if not _is_oom(e):
                raise
    raise RuntimeError(f"every candidate batch ran out of memory: {batches}")


def _time_transformer_step(cfg, batch: int, seq: int, steps: int, warmup: int):
    """Build + compile + time one transformer train-step config.  All
    allocations live in THIS frame, so an OOM unwinds them before any
    retry at a smaller batch allocates its own copy.  Raises on failure."""
    import jax
    import jax.numpy as jnp
    import optax

    from byteps_tpu.models.transformer import (
        build_train_step,
        init_params,
        shard_params,
    )
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    mesh = make_training_mesh(1, {"dp": 1, "pp": 1, "sp": 1, "tp": 1})
    params = shard_params(init_params(cfg, seed=0, pp_size=1), cfg, mesh)
    tx = optax.adamw(1e-4)
    opt_state = jax.jit(tx.init)(params)
    step = build_train_step(cfg, mesh, tx, donate=True)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    )
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, axis=1))

    for _ in range(warmup):  # warmup / compile
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    return batch * steps / dt, float(loss)


def _run_config(batch: int, seq: int, steps: int, remat: bool):
    """Compile + time one train-step config.  Returns (samples/s, loss,
    cfg), or None when it does not fit in device memory."""
    import jax.numpy as jnp

    from byteps_tpu.models.transformer import bert_large

    cfg = bert_large(max_seq=seq, compute_dtype=jnp.bfloat16, remat=remat)
    try:
        sps, loss = _time_transformer_step(cfg, batch, seq, steps, warmup=3)
    except Exception as e:  # noqa: BLE001 — re-raised unless OOM
        if not _is_oom(e):
            raise
        return None
    return sps, loss, cfg


def _run_transformer_extra(cfg_fn, batches, seq: int, steps: int, peak_bf16: float):
    """Secondary transformer config (seq-512 flash etc.): returns a dict
    for extra.models, trying batches largest-first until one fits.  The
    timed body lives in _time_transformer_step so a failed attempt's
    device buffers unwind before the smaller batch allocates."""
    cfg = cfg_fn()
    batch, (sps, _loss) = _first_that_fits(
        batches, lambda b: _time_transformer_step(cfg, b, seq, steps, warmup=2)
    )
    D, L, V = cfg.d_model, cfg.n_layers, cfg.vocab_size
    flops = 6 * seq * (12 * L * D * D + D * V) + 12 * L * seq * seq * D
    return {
        "samples_per_sec": round(sps, 2),
        "mfu": round(sps * flops / peak_bf16, 4),
        "batch": batch,
        "seq": seq,
    }


def _time_conv_step(model, batch: int, steps: int, hw: int):
    """Build + time one conv train-step config; allocations confined to
    this frame (see _time_transformer_step).  Raises on failure."""
    import jax
    import jax.numpy as jnp
    import optax

    from byteps_tpu.optim import build_flax_data_parallel_step
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    mesh = make_training_mesh(1, {"dp": 1, "pp": 1, "sp": 1, "tp": 1})
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, hw, hw, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 1000, size=(batch,)).astype(np.int32))
    variables = model.init(jax.random.PRNGKey(0), x[:1], train=True)
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = jax.jit(tx.init)(variables["params"])
    step = build_flax_data_parallel_step(
        model.apply,
        lambda lg, lb: optax.softmax_cross_entropy_with_integer_labels(lg, lb).mean(),
        tx,
        mesh=mesh,
    )
    for _ in range(2):
        variables, opt_state, loss = step(variables, opt_state, (x, y))
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        variables, opt_state, loss = step(variables, opt_state, (x, y))
    jax.block_until_ready(loss)
    return batch * steps / (time.perf_counter() - t0)


def _run_conv_extra(model_name: str, batches, steps: int, hw: int = 224):
    """ResNet-50 / VGG-16 data-parallel train throughput (the reference's
    own benchmark models, docs/performance.md:3-12) on one chip."""
    import jax.numpy as jnp

    if model_name == "resnet50":
        from byteps_tpu.models.resnet import ResNet50

        model = ResNet50(dtype=jnp.bfloat16)
    else:
        from byteps_tpu.models.vgg import VGG16

        model = VGG16(dtype=jnp.bfloat16)

    batch, sps = _first_that_fits(
        batches, lambda b: _time_conv_step(model, b, steps, hw)
    )
    return {"samples_per_sec": round(sps, 2), "batch": batch, "hw": hw}


def _bench_extra_models(steps: int, peak_bf16: float) -> dict:
    """The reference benchmarks ResNet-50 and VGG-16 alongside BERT
    (docs/performance.md:3-12, BASELINE.json configs 2/4/5); seq-512
    configs exercise the Pallas flash path where attention dominates."""
    import jax.numpy as jnp

    from byteps_tpu.models.transformer import bert_large, gpt2_medium

    return {
        "resnet50": _run_conv_extra("resnet50", (128, 64), steps),
        "vgg16": _run_conv_extra("vgg16", (64, 32), steps),
        "bert_large_seq512_flash": _run_transformer_extra(
            lambda: bert_large(
                max_seq=512, compute_dtype=jnp.bfloat16, remat=True, use_flash=True
            ),
            (32, 16), 512, steps, peak_bf16,
        ),
        "gpt2_medium_seq512_flash": _run_transformer_extra(
            lambda: gpt2_medium(
                max_seq=512, compute_dtype=jnp.bfloat16, remat=True, use_flash=True
            ),
            (32, 16), 512, steps, peak_bf16,
        ),
    }


def main() -> None:
    import jax

    import byteps_tpu as bps

    bps.init()  # places the compile cache (core/state.py) and the mesh
    dev = _require_tpu()
    peak_bf16, _peak_hbm = PEAKS[dev.device_kind]

    seq = int(os.environ.get("BENCH_SEQ", "128"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    if os.environ.get("BENCH_BATCH"):
        configs = [
            (int(os.environ["BENCH_BATCH"]), os.environ.get("BENCH_REMAT", "0") == "1")
        ]
    else:
        # try the measured-best configs plus the no-remat candidate (skips
        # the ~30% recompute FLOPs if activations fit); dense attention —
        # see TransformerConfig.use_flash.  Report the fastest that fits.
        configs = [(128, False), (128, True), (64, True)]

    tried = {}
    best = None
    for batch, remat in configs:
        res = _run_config(batch, seq, steps, remat)
        key = f"b{batch}_remat{int(remat)}"
        if res is None:
            tried[key] = "OOM"
            continue
        sps, loss, mcfg = res
        tried[key] = round(sps, 2)
        if best is None or sps > best[0]:
            best = (sps, loss, batch, remat, mcfg)
    if best is None:
        raise SystemExit(f"no benchmark config fit in device memory: {tried}")
    samples_per_sec, loss, batch, remat, mcfg = best

    # model FLOPs per sample (fwd+bwd = 3x fwd): matmul params + attention
    D, L, V, S = mcfg.d_model, mcfg.n_layers, mcfg.vocab_size, seq
    flops_per_sample = 6 * S * (12 * L * D * D + D * V) + 12 * L * S * S * D
    mfu = samples_per_sec * flops_per_sample / peak_bf16
    baseline_samples_per_sec = 0.40 * peak_bf16 / flops_per_sample

    payload = {
        "metric": "bert_large_train_samples_per_sec_per_chip",
        "value": round(samples_per_sec, 2),
        "unit": "samples/s",
        "vs_baseline": round(samples_per_sec / baseline_samples_per_sec, 4),
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
        "extra": {
            "mfu": round(mfu, 4),
            "batch": batch,
            "remat": remat,
            "seq": seq,
            "steps": steps,
            "loss": float(loss),
            "configs_tried": tried,
            "vs_baseline_definition": (
                "fraction of a 40%-MFU target on this chip's peak "
                "bf16 FLOPs (single-chip; self-chosen target). The "
                "reference's own headline metric is multi-worker "
                "scaling efficiency — see tools/scaling_bench.py "
                "for that harness (>=85% north star)."
            ),
        },
    }

    # breadth: the reference's other benchmark models (ResNet-50, VGG-16)
    # plus seq-512 flash-attention configs; secondary metrics only, the
    # headline stays BERT seq-128 for cross-round comparability
    if os.environ.get("BENCH_EXTRA_MODELS", "1") != "0":
        payload["extra"]["models"] = _bench_extra_models(
            int(os.environ.get("BENCH_EXTRA_STEPS", "8")), peak_bf16
        )
    print(json.dumps(payload))
    bps.shutdown()


if __name__ == "__main__":
    main()
