"""Reader ``looped_dense``: what the looped dense step adds to a trace and to
the counters (byteps_tpu/models/looped_dense.py).  A program without these
scopes or counters (the parent of the PR that brought them), and a run without
a TPU trace, read None everywhere.

``scope_ms``: self time a traced step of device 0's operations filed under
the scope ``match`` — forward, recomputation and backward together.  An
operation is filed under the FIRST of ``SCOPES`` that its scope path has as
a segment.  ``loop_carry`` is no scope of the program's: it is what stands
under ``loop_steps`` — the scan over the loop steps around the scan over the
layers — and under NONE of the scopes inside it: what looping itself costs,
the residuals written in the forward pass and read in the backward pass, a
layer's parameters sliced from their stack, the gradients of the shared
weights added across the passes, the scans' own bookkeeping.  The five times
are disjoint and can be added.

``flash_roofline_share``: the least time the chip could take for the traced
full causal flash-attention kernel calls (``flash_fwd``, and the one backward
kernel ``flash_bwd``: both kinds are charged), as % of the time they took.
The least time of a call is the larger of its operations over the peak bf16
rate and its bytes over the peak HBM rate (``peaks.json``); operations and
bytes are of the mathematics (:func:`flash_cost`) — the entries the causal
mask keeps, whatever blocks compute them — from the shapes in the operation's
own HLO line.  The metric's file states the shape it is listed for.

``counter_per_step``: growth of ``counter`` over the window a completed step;
a counter the program does not keep reads None.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ("exit_gate", "loop_heads", "loop_attention", "loop_mlp")
#: the scope around the loop steps, and the name its own time is read under
LOOP, CARRY = "loop_steps", "loop_carry"
#: ops/flash_attention.py's full causal kernels, as a trace's operation names
#: start; a banded call (``flash_fwd_win``) is none of this family's
KERNELS = ("flash_fwd", "flash_bwd")
_NOT = ("flash_fwd_win", "flash_bwd_win")
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
    """The name an operation's time is read under: the first of ``SCOPES`` in
    its scope path, or ``loop_carry`` under ``loop_steps`` and none of them."""
    parts = path.split("/")
    scope = next((s for s in SCOPES if s in parts), None)
    return CARRY if scope is None and LOOP in parts else scope


def flash_cost(kernel: str, bh: int, s: int, d_qk: int, d_v: int, item: int) -> tuple:
    """(operations, bytes) that one full causal call needs.  ``S (S + 1) / 2``
    score entries a head.  ``flash_fwd``: QK^T and PV, two products an entry,
    2 (d_qk + d_v).  ``flash_bwd``: the ONE backward kernel's five — the
    scores again (they cannot be kept), dV, dP, dQ, dK: 2 (3 d_qk + 2 d_v).
    Bytes: each operand and result once (forward q, k, v, out; backward q, k,
    v, dO read and dQ, dK, dV written), the f32 row statistics one value a row
    (forward the logsumexp; backward it and Δ)."""
    entries, rows = bh * (s * (s + 1) // 2), bh * s
    if kernel == "flash_fwd":
        return entries * 2 * (d_qk + d_v), rows * (item * (2 * d_qk + 2 * d_v) + 4)
    if kernel == "flash_bwd":
        return entries * 2 * (3 * d_qk + 2 * d_v), rows * (item * (4 * d_qk + 3 * d_v) + 8)
    raise ValueError(f"no full causal flash kernel {kernel!r}")


def _flash_call(name: str) -> tuple | None:
    """(kernel, bh, s, d_qk, d_v, item) from an operation's HLO line, or None.
    Every kernel's first operand is q ``[bh, s, d_qk]`` and third v
    ``[bh, s, d_v]``."""
    head = name.lstrip("%")
    kernel = next((k for k in KERNELS if head.startswith(k)), None)
    if kernel is None or head.startswith(_NOT) or "custom-call(" not in name:
        return None
    shapes = re.findall(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]",
                        name.split("custom-call(", 1)[1])
    if len(shapes) < 3:
        return None
    (dtype, bh, s, d_qk), (_, _, _, d_v) = shapes[0], shapes[2]
    return kernel, int(bh), int(s), int(d_qk), int(d_v), _ITEM[dtype]


def measure(trace: dict, quantity: str, match: str = "", peaks: dict | None = None):
    ph = _phases()
    lo, hi, steps = ph.window(trace["bench"])
    if not steps:
        return None
    if quantity == "scope_ms":
        own = ph._xplane().self_seconds(trace["ops"], lo, hi)
        filed = [t for name, t in own.items() if scope_of(trace["paths"].get(name, "")) == match]
        return sum(filed) / steps * 1e3 if filed else None
    if quantity == "flash_roofline_share":
        least = took = 0.0
        for name, a, b in trace["ops"]:
            call = _flash_call(name) if lo <= a and b <= hi else None
            if call:
                ops, nbytes = flash_cost(*call)
                least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
                took += b - a
        return least / took * 100.0 if took else None
    raise ValueError(f"looped_dense reader has no quantity {quantity!r}")


def read(run: dict, quantity: str, match: str = "", counter: str = ""):
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
    if quantity == "flash_roofline_share" and peaks is None:
        return None
    return measure(trace, quantity, match, peaks)
