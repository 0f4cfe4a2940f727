"""Reader ``block_diffusion_moe``: what the block-diffusion MoE step adds to a
trace and to the counters (byteps_tpu/models/block_diffusion_moe.py, the
``flash_fwd_bd`` | ``flash_bwd_bd`` kernels of ops/flash_attention.py).  A
program without these scopes, kernels or counters (the parent of the PR that
brought them), and a run without a TPU trace, read None everywhere.

``scope_ms``: self time a traced step of device 0's operations filed under
the scope ``match`` — forward, recomputation and backward together.  An
operation is filed under the FIRST of ``SCOPES`` that its scope path has as
a segment; the scopes' times are disjoint and can be added.  ``SCOPES`` are
the two this family's own metrics read: the router's and the experts' scopes
are read through readers/delta_moe.py by the accepted metrics that list the
cell.

``flash_roofline_share``: the least time the chip could take for the traced
block-diffusion kernel calls (``flash_fwd_bd`` and the one backward kernel
``flash_bwd_bd``: both kinds are charged), as % of the time they took.  The
least time of a call is the larger of its operations over the peak bf16 rate
and its bytes over the peak HBM rate (``peaks.json``); operations and bytes
are of the mathematics (:func:`flash_cost`) — the entries the mask keeps,
whatever tiles compute them — from the shapes in the operation's own HLO line
and the ``block_length`` of the configuration the metric's file names
(``config``: ``benchmark/configs/<config>.json``, the file the cell runs).

``counter_per_step``: growth of ``counter`` over the window a completed step;
a counter the program does not keep reads None.  (The family's three routing
counters are read by ``latent_moe``'s reader, as the other families' are.)
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ("block_diffusion_attention", "copies_assembly")
#: ops/flash_attention.py's block-diffusion kernels, as a trace's operation names start
KERNELS = ("flash_fwd_bd", "flash_bwd_bd")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}


@functools.cache
def _phases():
    """benchmark/readers/phases.py by file: the trace loader and its window."""
    spec = importlib.util.spec_from_file_location(
        "bench_readers_phases", os.path.join(HERE, "phases.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def scope_of(path: str) -> str | None:
    """The scope an operation is filed under, from its scope path."""
    parts = path.split("/")
    return next((s for s in SCOPES if s in parts), None)


def flash_cost(kernel: str, bh: int, bh_kv: int, sq: int, sk: int, d_qk: int, d_v: int,
               item: int, block: int) -> tuple:
    """(operations, bytes) that one block-diffusion call needs: ``bh`` query
    heads of ``sq`` rows against ``bh_kv`` key/value heads of ``sk`` = 2L rows,
    the noised copy's then the clean copy's.  Score entries a head: ``L² +
    L·block`` where both copies' queries are there (``sq`` = 2L), half of it
    for the noised copy's alone (``sq`` = L).  ``flash_fwd_bd``: QK^T and PV,
    two products an entry, 2 (d_qk + d_v).  ``flash_bwd_bd``: the one backward
    kernel's five — the scores again, dV, dP, dQ, dK: 2 (3 d_qk + 2 d_v).
    Bytes: q and the output (backward: q, dO, dQ) once a query head, the f32
    row statistics one value a row (forward the logsumexp; backward it and Δ);
    k and v once a KEY/VALUE head, the clean copy's rows a second time where
    both halves' queries read them (backward: and dK, dV written once)."""
    length = sk // 2
    both = sq == sk
    per_head = (length * length + length * block) // (1 if both else 2)
    entries = bh * per_head
    kv_rows = bh_kv * (sk + (length if both else 0))
    if kernel == "flash_fwd_bd":
        return (entries * 2 * (d_qk + d_v),
                bh * sq * (item * (d_qk + d_v) + 4) + kv_rows * item * (d_qk + d_v))
    if kernel == "flash_bwd_bd":
        return (entries * 2 * (3 * d_qk + 2 * d_v),
                bh * sq * (item * (2 * d_qk + d_v) + 8)
                + (kv_rows + bh_kv * sk) * item * (d_qk + d_v))
    raise ValueError(f"no block-diffusion flash kernel {kernel!r}")


def _flash_call(name: str) -> tuple | None:
    """(kernel, bh, bh_kv, sq, sk, d_qk, d_v, item) from an operation's HLO
    line, or None.  After the tables (1-D ``s32``) every kernel's first
    floating operand is q ``[bh, sq, d_qk]``, then k ``[bh_kv, sk, d_qk]`` and
    v ``[bh_kv, sk, d_v]``."""
    head = name.lstrip("%")
    kernel = next((k for k in KERNELS if head.startswith(k)), None)
    if kernel is None or "custom-call(" not in name:
        return None
    shapes = re.findall(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]",
                        name.split("custom-call(", 1)[1])
    if len(shapes) < 3:
        return None
    (dtype, bh, sq, d_qk), (_, bh_kv, sk, _), (_, _, _, d_v) = shapes[:3]
    return kernel, int(bh), int(bh_kv), int(sq), int(sk), int(d_qk), int(d_v), _ITEM[dtype]


def measure(trace: dict, quantity: str, match: str = "", peaks: dict | None = None,
            block: int = 0):
    ph = _phases()
    lo, hi, steps = ph.window(trace["bench"])
    if not steps:
        return None
    if quantity == "scope_ms":
        own = ph._xplane().self_seconds(trace["ops"], lo, hi)
        filed = [t for name, t in own.items()
                 if scope_of(trace["paths"].get(name, "")) == match]
        return sum(filed) / steps * 1e3 if filed else None
    if quantity == "flash_roofline_share":
        least = took = 0.0
        for name, a, b in trace["ops"]:
            call = _flash_call(name) if lo <= a and b <= hi else None
            if call:
                ops, nbytes = flash_cost(*call, block=block)
                least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
                took += b - a
        return least / took * 100.0 if took else None
    raise ValueError(f"block_diffusion_moe reader has no quantity {quantity!r}")


def read(run: dict, quantity: str, match: str = "", counter: str = "", config: str = ""):
    if quantity == "counter_per_step":
        before, after = run["counters"]["before"], run["counters"]["after"]
        if counter not in after or not run["steps"]:
            return None
        return (after[counter] - before.get(counter, 0)) / run["steps"]
    if not run.get("trace"):  # a rehearsal's trace holds no TPU plane
        return None
    trace = _phases().newest_trace()
    if not trace:
        return None
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        kinds = json.load(f)["kinds"]
    # the one kind whose peak the harness used for this run
    peaks = next((p for p in kinds.values()
                  if p["bf16_flops_per_s"] == run.get("peak_flops_per_s")), None)
    if quantity != "flash_roofline_share":
        return measure(trace, quantity, match)
    if peaks is None:
        return None
    with open(os.path.join(os.path.dirname(HERE), "configs", f"{config}.json")) as f:
        block = json.load(f)["block_length"]
    return measure(trace, quantity, match, peaks, block)
