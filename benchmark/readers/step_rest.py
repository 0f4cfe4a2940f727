"""Reader ``step_rest``: the rest of a compiled step — what a trace holds of
it beside the layers' own scopes, so that every operation of a step is under
a name (ISSUE 54).  ``models/transformer.build_train_step``'s families open
``lm_head`` around the final norm, the head's product, logsumexp, the gold
logit and the masked sum (the rebuilt blocks of the backward pass included),
``embed`` around the embedding's gather with its cast or scale (its transpose
is the embedding's scatter-add) and, in ``models/latent_moe.py``,
``dense_mlp`` around the leading layers' norm and SwiGLU.  A program without
these scopes (the parent of the PR that brought them), and a run without a
TPU trace, read None where nothing is filed.

All quantities are self time (a ``while`` charged for what its body leaves)
a traced step of device 0's operations inside the traced window, through
``readers/phases.py``'s loader and window; in a compute cell only the step's
own program runs there.

``scope_ms``: the operations filed under ``match``: the FIRST of ``SCOPES``
that their scope path has as a segment, as the family readers file theirs —
so ``lm_head`` takes the MTP module's head too (``…/mtp/…/lm_head``), which
readers/latent_moe.py files under ``mtp``.

``named_ms``: the operations whose name starts with ``prefix``, whatever
their path (XLA:TPU's grouped products, ``ragged-dot-…``, carry none).

``unscoped_ms``: the operations under none of this reader's ``SCOPES``, none
of ``optimizer`` | ``grad_sync``, none of the ``SCOPES`` of the family readers
(``FAMILY_READERS``, read from their files; a looped family's ``LOOP`` with
them: what stands under ``loop_steps`` and no scope inside it is that reader's
``loop_carry``) and whose name is no ``ragged-dot``: what is still under no
name — parameter slices, copies, the loss's last sums.  It reads the whole
stack in a step whose layers open no scope (the dense transformer, the flax
step).

``no_phase_ms``: the operations that ``readers/phases.py``'s own rule files
under none of ``forward``, ``backward``, ``optimizer`` and that are not under
``grad_sync`` either: with it the phases add up to the busy time.  None for a
program without the ``forward`` scope, as ``phases`` reads.
"""

from __future__ import annotations

import functools
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ("lm_head", "embed", "dense_mlp")
#: the readers whose SCOPES name the layers' own parts of a step
FAMILY_READERS = ("latent_moe", "delta_moe", "conv_moe", "window_moe", "ssm_moe",
                  "looped_dense", "block_diffusion_moe", "cross_decoder", "channel_delta_moe")
#: as the grouped products' operations are named in a trace; they carry no scope path
RAGGED_DOT = "ragged-dot"
#: scopes of the step itself, outside the differentiated loss
STEP_SCOPES = ("optimizer", "grad_sync")


@functools.cache
def _reader(name: str):
    """benchmark/readers/<name>.py by file."""
    spec = importlib.util.spec_from_file_location(
        f"bench_readers_{name}", os.path.join(HERE, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def named_scopes() -> frozenset:
    """Every scope some metric files an operation by."""
    readers = [_reader(name) for name in FAMILY_READERS]
    family = {s for reader in readers for s in reader.SCOPES}
    loops = {reader.LOOP for reader in readers if hasattr(reader, "LOOP")}
    return frozenset(family | loops | set(SCOPES) | set(STEP_SCOPES))


def scope_of(path: str) -> str | None:
    parts = path.split("/")
    return next((s for s in SCOPES if s in parts), None)


def is_named(name: str, prefix: str) -> bool:
    return name.lstrip("%").startswith(prefix)


def unscoped(path: str, name: str) -> bool:
    return not (named_scopes().intersection(path.split("/")) or is_named(name, RAGGED_DOT))


def measure(trace: dict, quantity: str, match: str = "", prefix: str = ""):
    ph = _reader("phases")
    lo, hi, steps = ph.window(trace["bench"])
    if not steps:
        return None
    own = ph._xplane().self_seconds(trace["ops"], lo, hi)
    paths = trace["paths"]
    if quantity == "scope_ms":
        filed = [t for name, t in own.items() if scope_of(paths.get(name, "")) == match]
    elif quantity == "named_ms":
        filed = [t for name, t in own.items() if is_named(name, prefix)]
    elif quantity == "unscoped_ms":
        filed = [t for name, t in own.items() if unscoped(paths.get(name, ""), name)]
    elif quantity == "no_phase_ms":
        phase = {name: ph.classify(paths.get(name, "")) for name in own}
        if "forward" not in phase.values():
            return None
        filed = [t for name, t in own.items() if phase[name] is None
                 and "grad_sync" not in paths.get(name, "").split("/")]
        return sum(filed) / steps * 1e3  # 0 where the phases hold everything
    else:
        raise ValueError(f"step_rest reader has no quantity {quantity!r}")
    return sum(filed) / steps * 1e3 if filed else None


def read(run: dict, quantity: str, match: str = "", prefix: str = ""):
    if not run.get("trace"):  # a rehearsal's trace holds no TPU plane
        return None
    trace = _reader("phases").newest_trace()
    return measure(trace, quantity, match, prefix) if trace else None
