"""VGG family (flax) — the reference's communication-bound benchmark model
(BASELINE.md: VGG-16, +100% over Horovod there because its huge
dense layers stress the gradient path — exactly what the PS/compression
pipeline accelerates)."""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax.numpy as jnp

_CFG16 = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512, "M", 512, 512, 512, "M"]
_CFG11 = [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"]


class VGG(nn.Module):
    cfg: Sequence
    num_classes: int = 1000
    hidden: int = 4096
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.dtype)
        for v in self.cfg:
            if v == "M":
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
            else:
                x = nn.Conv(v, (3, 3), padding="SAME", dtype=self.dtype)(x)
                x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(self.hidden, dtype=self.dtype)(x))
        x = nn.relu(nn.Dense(self.hidden, dtype=self.dtype)(x))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


def VGG16(**kw) -> VGG:
    return VGG(cfg=_CFG16, **kw)


def VGG11(**kw) -> VGG:
    return VGG(cfg=_CFG11, **kw)


def VGGTiny(**kw) -> VGG:
    kw.setdefault("num_classes", 10)
    kw.setdefault("hidden", 64)
    return VGG(cfg=[8, "M", 16, "M"], **kw)
