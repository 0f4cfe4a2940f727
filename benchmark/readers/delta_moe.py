"""Reader ``delta_moe``: what the gated-delta MoE step adds to a trace
(byteps_tpu/models/delta_moe.py, ops/gated_delta.py).  A program without
these scopes (the parent of the PR that brought them), and a run without a
TPU trace, read None everywhere.

``scope_ms``: self time a traced step of device 0's operations filed under
the scope ``match`` — forward, recomputation and backward together.  An
operation is filed under the FIRST of ``SCOPES`` that its scope path has as
a segment; the scopes' times are disjoint and can be added.

``gdn_scan_roofline_share``: the least time the chip could take for the
gated delta rule of the traced steps, as % of the time of the operations
under ``gdn_scan`` — so it reads the same work whatever implements the rule,
XLA's chunked form or a kernel.  The work is the mathematics of the
recurrence (:func:`delta_rule_cost`), not of the chunked form; the least time
is the larger of operations over the peak bf16 rate and bytes over the peak
HBM rate (``peaks.json``).  The shape (``layers``, heads, head sizes, tokens
a sample, bytes an element) stands in the metric's file.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ("gdn_scan", "gdn_proj", "gated_attention", "moe_route", "moe_experts", "moe_shared")


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


def delta_rule_cost(tokens: int, key_heads: int, value_heads: int, d_k: int, d_v: int,
                    item: int) -> tuple:
    """(operations, bytes) that one layer's rule needs for ``tokens`` tokens,
    forward and backward, recomputation not counted.  A token a value head:
    decay, S^T k, the rank-one update and S^T q are 6 d_k d_v operations
    forward, and twice that backward.  Bytes: q and k (a key head each), v
    and the gate z read and o written once forward in ``item`` bytes, the
    decay's log g and the writing strength beta in f32; they and their
    cotangents once backward."""
    ops = 3 * tokens * value_heads * 6 * d_k * d_v
    forward = tokens * (item * (2 * key_heads * d_k + 3 * value_heads * d_v) + 4 * 2 * value_heads)
    return ops, 3 * forward


def measure(trace: dict, quantity: str, match: str = "", least_s: float = 0.0):
    """``least_s``: the least seconds a step for ``gdn_scan_roofline_share``."""
    ph = _phases()
    lo, hi, steps = ph.window(trace["bench"])
    if not steps:
        return None
    if quantity == "gdn_scan_roofline_share":
        match = "gdn_scan"
    elif quantity != "scope_ms":
        raise ValueError(f"delta_moe reader has no quantity {quantity!r}")
    own = ph._xplane().self_seconds(trace["ops"], lo, hi)
    filed = sum(t for name, t in own.items() if scope_of(trace["paths"].get(name, "")) == match)
    if not filed:
        return None
    return filed / steps * 1e3 if quantity == "scope_ms" else least_s * steps / filed * 100.0


def read(run: dict, quantity: str, match: str = "", **shape):
    if not run.get("trace"):  # a rehearsal's trace holds no TPU plane
        return None
    trace = _phases().newest_trace()
    if not trace:
        return None
    least_s = 0.0
    if quantity == "gdn_scan_roofline_share":
        with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
            kinds = json.load(f)["kinds"]
        # the one kind whose peak the harness used for this run
        peaks = next((p for p in kinds.values()
                      if p["bf16_flops_per_s"] == run.get("peak_flops_per_s")), None)
        if peaks is None:
            return None
        layers, per_sample = shape.pop("layers"), shape.pop("tokens_per_sample")
        ops, nbytes = delta_rule_cost(run["global_batch"] * per_sample, **shape)
        least_s = layers * max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return measure(trace, quantity, match, least_s)
