"""Data sharding / prefetch utilities."""

import numpy as np
import pytest

import byteps_tpu as bps
from byteps_tpu.data import ShardedDataset, prefetch_to_device, shard_for_worker


class TestSharding:
    def test_disjoint_and_complete(self):
        shards = [
            shard_for_worker(100, worker_rank=r, num_workers=4, seed=1)
            for r in range(4)
        ]
        allidx = np.concatenate(shards)
        assert len(allidx) == 100
        assert len(set(allidx.tolist())) == 100  # disjoint cover

    def test_same_seed_same_permutation(self):
        a = shard_for_worker(50, 0, 2, seed=7)
        b = shard_for_worker(50, 0, 2, seed=7)
        np.testing.assert_array_equal(a, b)

    def test_drop_remainder_balances(self):
        shards = [shard_for_worker(103, r, 4, seed=0) for r in range(4)]
        assert all(len(s) == 25 for s in shards)

    def test_dataset_epochs_reshuffle(self):
        bps.init()
        x = np.arange(64, dtype=np.float32)
        ds = ShardedDataset([x, x * 2], batch_size=8, seed=3)
        b0 = [bx for bx, _ in ds.epoch(0)]
        b1 = [bx for bx, _ in ds.epoch(1)]
        assert len(b0) == 8
        assert not all(np.array_equal(a, b) for a, b in zip(b0, b1))
        # pairing preserved
        for bx, by in ds.epoch(0):
            np.testing.assert_allclose(by, bx * 2)
        bps.shutdown()


class TestPrefetch:
    def test_order_and_completeness(self):
        batches = [np.full((2,), i, np.float32) for i in range(7)]
        out = list(prefetch_to_device(batches, size=3))
        assert len(out) == 7
        for i, b in enumerate(out):
            np.testing.assert_allclose(np.asarray(b), i)

    def test_short_iterator(self):
        out = list(prefetch_to_device([np.ones(2)], size=4))
        assert len(out) == 1
