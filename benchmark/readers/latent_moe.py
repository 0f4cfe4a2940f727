"""Reader ``latent_moe``: what the latent-attention MoE step adds to a trace
and to the counters (byteps_tpu/models/latent_moe.py, parallel/moe.py,
ops/flash_attention.py).  A program without these scopes, kernels or counters
(the parent of the PR that brought them), and a run without a TPU trace, read
None everywhere.

``scope_ms``: self time a traced step of device 0's operations filed under
the scope ``match`` — forward, recomputation and backward together.  An
operation is filed under the FIRST of ``SCOPES`` that its scope path has as
a segment, so ``mtp`` takes the whole multi-token-prediction module (its
attention and experts too) and the other scopes are the main stack's; the
scopes' times are disjoint and can be added.

``counter_per_step``: growth of ``counter`` over the window per completed
step; ``counter_share``: growth of ``counter`` as % of the growth of ``of``.
A routing counter that never grew is absent from a snapshot and reads 0 where
``moe_slots_routed`` is there.

``flash_roofline_share``: the least time the chip could take for the traced
flash-attention kernel calls, as % of the time they took.  The least time of
a call is the larger of its operations over the peak bf16 rate and its bytes
over the peak HBM rate (``peaks.json``); operations and bytes are of the
mathematics (:func:`flash_call_cost`), from the shapes in the operation's
own HLO line.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ("mtp", "mla_attention", "moe_route", "moe_experts", "moe_shared")
#: ops/flash_attention.py's kernel names, as a trace's operation names start
FLASH_FWD, FLASH_DQ, FLASH_DKV = "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"
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
    parts = path.split("/")
    return next((s for s in SCOPES if s in parts), None)


def flash_call_cost(kind: str, bh: int, s: int, d_qk: int, d_v: int, item: int) -> tuple:
    """(operations, bytes) that one causal call needs.  ``S(S+1)/2`` score
    entries a head.  Forward: QK^T and PV, 2 (d_qk + d_v) an entry.  The two
    backward kernels share the five products the backward pass needs — the
    scores again (they cannot be kept), dV, dP, dQ, dK: 2 (3 d_qk + 2 d_v) an
    entry — charged to the dQ kernel (scores, dP, dQ) and the dK/dV kernel
    (dV, dK) as each cannot do without; what the split recomputes beyond that
    (scores and dP a second time) is not counted.  Bytes: each operand and
    result once, the f32 row statistics as one value a row."""
    entries = bh * s * (s + 1) // 2
    rows = bh * s
    if kind == FLASH_FWD:
        return (entries * 2 * (d_qk + d_v),
                rows * (item * (2 * d_qk + 2 * d_v) + 4))
    if kind == FLASH_DQ:
        return (entries * 2 * (2 * d_qk + d_v),
                rows * (item * (3 * d_qk + 2 * d_v) + 8))
    if kind == FLASH_DKV:
        return (entries * 2 * (d_qk + d_v),
                rows * (item * (3 * d_qk + 3 * d_v) + 8))
    raise ValueError(f"no flash kernel {kind!r}")


def _flash_call(name: str) -> tuple | None:
    """(kind, bh, s, d_qk, d_v, item) from an operation's HLO line, or None.
    Every kernel's first operand is q ``[bh, s, d_qk]`` and third v
    ``[bh, s, d_v]``."""
    head = name.lstrip("%")
    kind = next((k for k in (FLASH_DQ, FLASH_DKV, FLASH_FWD) if head.startswith(k)), None)
    if kind is None or "custom-call(" not in name:
        return None
    shapes = re.findall(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]",
                        name.split("custom-call(", 1)[1])
    if len(shapes) < 3:
        return None
    (dtype, bh, s, d_qk), (_, _, _, d_v) = shapes[0], shapes[2]
    return kind, int(bh), int(s), int(d_qk), int(d_v), _ITEM[dtype]


def measure(trace: dict, quantity: str, match: str = "", peaks: dict | None = None):
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
                ops, nbytes = flash_call_cost(*call)
                least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
                took += b - a
        return least / took * 100.0 if took else None
    raise ValueError(f"latent_moe reader has no quantity {quantity!r}")


def _grown(run: dict, counter: str):
    before, after = run["counters"]["before"], run["counters"]["after"]
    if "moe_slots_routed" not in after:
        return None
    return after.get(counter, 0) - before.get(counter, 0)


def read(run: dict, quantity: str, match: str = "", counter: str = "", of: str = ""):
    if quantity == "counter_per_step":
        grown = _grown(run, counter)
        return None if grown is None or not run["steps"] else grown / run["steps"]
    if quantity == "counter_share":
        grown, whole = _grown(run, counter), _grown(run, of)
        return grown / whole * 100.0 if whole else None
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
