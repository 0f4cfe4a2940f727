"""Reader ``window_moe``: what the sliding-window / global-attention MoE step
adds to a trace (byteps_tpu/models/window_moe.py, the banded kernels of
ops/flash_attention.py).  A program without these scopes or kernels (the
parent of the PR that brought them), and a run without a TPU trace, read None
everywhere.

``scope_ms``: self time a traced step of device 0's operations filed under
the scope ``match`` — forward, recomputation and backward together.  An
operation is filed under the FIRST of ``SCOPES`` that its scope path has as
a segment; the scopes' times are disjoint and can be added.  XLA:TPU's
grouped-product custom call (``ragged-dot-…``) comes out of the compiler with
no scope path at all; every one in this family's step is the held experts',
so it is filed under ``moe_experts`` by its name (as readers/conv_moe.py
does).

``flash_roofline_share``: the least time the chip could take for the traced
flash-attention kernel calls of one ``kind``, as % of the time they took.
``kind`` ``"window"`` takes the banded calls (``flash_fwd_win``,
``flash_bwd_win``), ``"global"`` the others (``flash_fwd``, ``flash_bwd``);
a call is told by the start of its operation's name, the longest kernel name
first.  The least time of a call is the larger of its operations over the
peak bf16 rate and its bytes over the peak HBM rate (``peaks.json``);
operations and bytes are of the mathematics (:func:`flash_cost`) — the
entries the mask keeps, whatever blocks compute them — from the shapes in the
operation's own HLO line and the window in the metric's file.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ("window_attention", "global_attention", "dense_mlp", "moe_route", "shared_expert",
          "moe_experts")
#: as the grouped products' operations are named in a trace; they carry no scope path
RAGGED_DOT = "ragged-dot"
#: ops/flash_attention.py's kernel names, as a trace's operation names start;
#: the longest first, so that a banded call is not taken for a full one
KERNELS = ("flash_fwd_win", "flash_bwd_win", "flash_fwd", "flash_bwd")
KINDS = {"window": ("flash_fwd_win", "flash_bwd_win"), "global": ("flash_fwd", "flash_bwd")}
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}


@functools.cache
def _phases():
    """benchmark/readers/phases.py by file: the trace loader and its window."""
    spec = importlib.util.spec_from_file_location(
        "bench_readers_phases", os.path.join(HERE, "phases.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def scope_of(path: str, name: str = "") -> str | None:
    """The scope an operation is filed under, from its scope path and, for
    the grouped products alone, its name."""
    parts = path.split("/")
    scope = next((s for s in SCOPES if s in parts), None)
    if scope is None and name.lstrip("%").startswith(RAGGED_DOT):
        return "moe_experts"
    return scope


def flash_cost(kernel: str, bh: int, s: int, d_qk: int, d_v: int, item: int,
               window: int | None = None) -> tuple:
    """(operations, bytes) that one causal call needs.  Score entries a head:
    ``S (S + 1) / 2``, or ``W S - W (W - 1) / 2`` at a window ``W < S`` (every
    query its last ``W`` keys, the first ``W`` queries fewer).  ``flash_fwd*``:
    QK^T and PV, two products an entry, 2 (d_qk + d_v).  ``flash_bwd*``: the
    ONE backward kernel's five — the scores again, dV, dP, dQ, dK: 2 (3 d_qk
    + 2 d_v).  Bytes: each operand and result once (forward q, k, v, out;
    backward q, k, v, dO read and dQ, dK, dV written), the f32 row statistics
    one value a row (forward the logsumexp; backward it and Δ)."""
    per_head = s * (s + 1) // 2 if window is None or window >= s else (
        window * s - window * (window - 1) // 2)
    entries, rows = bh * per_head, bh * s
    if kernel.startswith("flash_fwd"):
        return (entries * 2 * (d_qk + d_v), rows * (item * (2 * d_qk + 2 * d_v) + 4))
    if kernel.startswith("flash_bwd"):
        return (entries * 2 * (3 * d_qk + 2 * d_v),
                rows * (item * (4 * d_qk + 3 * d_v) + 8))
    raise ValueError(f"no flash kernel {kernel!r}")


def _flash_call(name: str) -> tuple | None:
    """(kernel, bh, s, d_qk, d_v, item) from an operation's HLO line, or None.
    Every kernel's first operand is q ``[bh, s, d_qk]`` and third v
    ``[bh, s, d_v]``."""
    head = name.lstrip("%")
    kernel = next((k for k in KERNELS if head.startswith(k)), None)
    if kernel is None or "custom-call(" not in name:
        return None
    shapes = re.findall(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]",
                        name.split("custom-call(", 1)[1])
    if len(shapes) < 3:
        return None
    (dtype, bh, s, d_qk), (_, _, _, d_v) = shapes[0], shapes[2]
    return kernel, int(bh), int(s), int(d_qk), int(d_v), _ITEM[dtype]


def measure(trace: dict, quantity: str, match: str = "", peaks: dict | None = None,
            kind: str = "", window: int | None = None):
    ph = _phases()
    lo, hi, steps = ph.window(trace["bench"])
    if not steps:
        return None
    if quantity == "scope_ms":
        own = ph._xplane().self_seconds(trace["ops"], lo, hi)
        filed = [t for name, t in own.items()
                 if scope_of(trace["paths"].get(name, ""), name) == match]
        return sum(filed) / steps * 1e3 if filed else None
    if quantity == "flash_roofline_share":
        least = took = 0.0
        for name, a, b in trace["ops"]:
            call = _flash_call(name) if lo <= a and b <= hi else None
            if call and call[0] in KINDS[kind]:
                ops, nbytes = flash_cost(*call, window=window if kind == "window" else None)
                least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
                took += b - a
        return least / took * 100.0 if took else None
    raise ValueError(f"window_moe reader has no quantity {quantity!r}")


def read(run: dict, quantity: str, match: str = "", kind: str = "", window: int | None = None):
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
    return measure(trace, quantity, match, peaks, kind, window)
