"""Data input utilities: worker sharding + device prefetch.

The reference delegates input pipelines to the frameworks; for the TPU
build the two pieces worth owning are:

- :func:`shard_for_worker` / :class:`ShardedDataset` — deterministic
  per-worker (and per-epoch shuffled) sharding of an index space, the
  cross-host analogue of the reference's per-GPU samplers.
- :func:`prefetch_to_device` — a double-buffered host→device pipeline so
  the next batch's H2D transfer overlaps the current step (the D2H/H2D
  overlap the reference builds with CUDA copy streams, done here with
  jax async dispatch).
- :func:`block_diffusion_noise` — the noising of block-diffusion training
  (``models/block_diffusion_moe.py``): a noise level a block, a mask token
  where a coin at that level says so, and the loss weight ``1 / t`` that
  goes with it.  Jittable: a job runs it in its input pipeline, on the
  device, once a step.
"""

from __future__ import annotations

import collections
from typing import Any, Iterable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np


def shard_for_worker(
    num_examples: int,
    worker_rank: Optional[int] = None,
    num_workers: Optional[int] = None,
    seed: int = 0,
    shuffle: bool = True,
    drop_remainder: bool = True,
) -> np.ndarray:
    """Indices owned by this worker: shuffle globally (same seed on every
    worker), then stride-partition so shards are disjoint and balanced."""
    import byteps_tpu as bps

    rank = bps.rank() if worker_rank is None else worker_rank
    world = bps.size() if num_workers is None else num_workers
    idx = np.arange(num_examples)
    if shuffle:
        np.random.default_rng(seed).shuffle(idx)
    if drop_remainder:
        per = num_examples // world
        idx = idx[: per * world]
    return idx[rank::world]


class ShardedDataset:
    """Minimal epoch iterator over (x, y, ...) arrays, sharded per worker.

    Reshuffles every epoch with seed = base_seed + epoch (identical
    permutation on every worker, disjoint shards)."""

    def __init__(
        self,
        arrays,
        batch_size: int,
        seed: int = 0,
        worker_rank: Optional[int] = None,
        num_workers: Optional[int] = None,
    ) -> None:
        self.arrays = tuple(np.asarray(a) for a in arrays)
        n = {len(a) for a in self.arrays}
        if len(n) != 1:
            raise ValueError(f"arrays disagree on length: {n}")
        self.num_examples = n.pop()
        self.batch_size = batch_size
        self.seed = seed
        self.worker_rank = worker_rank
        self.num_workers = num_workers

    def epoch(self, epoch: int = 0) -> Iterator[tuple]:
        idx = shard_for_worker(
            self.num_examples, self.worker_rank, self.num_workers,
            seed=self.seed + epoch,
        )
        for i in range(0, len(idx) - self.batch_size + 1, self.batch_size):
            sel = idx[i : i + self.batch_size]
            yield tuple(a[sel] for a in self.arrays)


def prefetch_to_device(
    iterator: Iterable,
    size: int = 2,
    sharding: Optional[Any] = None,
) -> Iterator:
    """Keep ``size`` batches in flight on device.

    ``jax.device_put`` is async; holding a small deque of already-
    transferred batches lets the H2D DMA of batch N+1 overlap step N's
    compute — the role the reference's dedicated CUDA copy streams play
    (global.cc:253-268)."""

    put = (
        (lambda b: jax.device_put(b, sharding))
        if sharding is not None
        else jax.device_put
    )
    it = iter(iterator)
    if size <= 0:  # prefetch disabled: plain pass-through transfer
        for b in it:
            yield put(b)
        return
    queue: collections.deque = collections.deque()
    try:
        for _ in range(size):
            queue.append(put(next(it)))
    except StopIteration:
        pass
    while queue:
        out = queue.popleft()
        try:
            queue.append(put(next(it)))
        except StopIteration:
            pass
        yield out


def block_diffusion_noise(key: jax.Array, tokens: jax.Array, block_length: int, mask_id: int,
                          lo: float, hi: float) -> tuple:
    """Block diffusion's forward process under the linear schedule ``α_t = 1 −
    t``: for each sequence of ``tokens`` (..., L) and each block of
    ``block_length`` tokens draw ``t_b ~ U[lo, hi]``; each token of the block
    becomes ``mask_id`` independently with probability ``t_b``.  Returns
    ``(x_t, weights)``: the noised copy (``tokens``' dtype) and the f32 loss
    weights, ``1 / t_b`` where the token was masked and 0 elsewhere — the
    weight of the objective's ``−α'_t / (1 − α_t) = 1 / t``.  ``U(0, 1]`` is
    the unclipped objective; a clipped range (``lo`` > 0) bounds the weights
    at ``1 / lo``, which is what small blocks are trained under.  ``tokens``
    are the targets as they are: ``mask_id`` is a row of the vocabulary that
    data never holds."""
    if not 0.0 < lo <= hi <= 1.0:
        raise ValueError(f"noise levels U[{lo}, {hi}] lie outside (0, 1]")
    length = tokens.shape[-1]
    if length % block_length:
        raise ValueError(f"blocks of {block_length} do not tile a sequence of {length}")
    k_level, k_coin = jax.random.split(key)
    blocks = tokens.shape[:-1] + (length // block_length,)
    t = jnp.repeat(jax.random.uniform(k_level, blocks, jnp.float32, lo, hi), block_length, axis=-1)
    masked = jax.random.uniform(k_coin, tokens.shape, jnp.float32) < t
    return (jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens),
            jnp.where(masked, 1.0 / t, 0.0))
