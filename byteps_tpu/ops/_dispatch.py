"""What every kernel module under ``byteps_tpu/ops/`` knows in common, once:
which platform the default device is, whether a module's Pallas kernels run
there, the lane width, how a ``pallas_call``'s outputs vary under
``shard_map``, and how a tuned table is read.  What a kernel TILES — the
shape test — is its module's knowledge and stays there.
"""

from __future__ import annotations

import functools
import json

import jax

#: TPU vector lanes: the trailing dim of a tile
LANES = 128


def platform() -> str:
    """Platform of the default device — the one probe under ``ops/``, and a
    function so that a test, or a compile for a described chip, can stand a
    TPU in."""
    return jax.devices()[0].platform


def kernels_run(fits: bool, interpret: bool) -> bool:
    """THE decision between a module's Pallas kernels (True) and XLA's form
    of the same equations (False), given the module's own shape test: on a
    TPU the kernels run wherever they fit; off a TPU (Mosaic cannot compile
    there) only where the caller asked for the Pallas interpreter."""
    return fits and (interpret or platform() == "tpu")


def vma_union(*xs):
    """Union of the inputs' varying-manual-axes sets: under
    ``shard_map(check_vma=True)`` a ``pallas_call``'s out_shapes must declare
    how they vary across the manual mesh axes, and an output varies over
    exactly the axes any of its inputs varies over."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


@functools.cache
def tuned(path: str, sections) -> dict:
    """An on-chip sweep's artifact, read once a path: ``sections`` (the
    module's own parsing) of its JSON document, ``{}`` where the file is
    absent or does not read as ``sections`` expects."""
    try:
        with open(path) as f:
            return sections(json.load(f))
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        return {}
