"""Reader ``ssm_moe``: what the state-space MoE step adds to a trace
(byteps_tpu/models/ssm_moe.py, ops/ssd.py).  A program without these scopes
(the parent of the PR that brought them), and a run without a TPU trace, read
None everywhere.

``scope_ms``: self time a traced step of device 0's operations filed under
the scope ``match`` — forward, recomputation and backward together.  An
operation is filed under the FIRST of ``SCOPES`` that its scope path has as
a segment; the scopes' times are disjoint and can be added.  XLA:TPU's
grouped-product custom call (``ragged-dot-…``) comes out of the compiler with
no scope path at all; every one in this family's step is the held experts',
so it is filed under ``moe_experts`` by its name (as readers/conv_moe.py
does).

``ssd_scan_roofline_share``: the least time the chip could take for the
selective state-space scan of the traced steps, as % of the time of ALL the
operations under ``ssd_scan`` (the convolution, its bias and silu, the
softplus, the scan, ``D x`` and the gated grouped norm) — so it reads the same
work whatever implements the scan, XLA's chunked form or a kernel.  The work
is the mathematics of the recurrence (:func:`ssd_cost`), not of the chunked
form; the least time is the larger of operations over the peak bf16 rate and
bytes over the peak HBM rate (``peaks.json``).  The shape (``layers``, heads,
head size, state size, groups, tokens a sample, bytes an element) stands in
the metric's file.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ("ssd_scan", "ssm_proj", "nope16_attention", "moe_route", "shared_expert",
          "moe_experts")
#: as the grouped products' operations are named in a trace; they carry no scope path
RAGGED_DOT = "ragged-dot"


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


def ssd_cost(tokens: int, heads: int, head_dim: int, state: int, groups: int,
             item: int) -> tuple:
    """(operations, bytes) that one layer's scan needs for ``tokens`` tokens,
    forward and backward, recomputation not counted.  A token a head: the
    decay of the (head_dim x state) state, ``dt x (x) B`` added to it and its
    read by C are 5 head_dim state operations forward, and twice that
    backward.  Bytes: x, the gate z and y (a head each) and B and C (a group
    each) once forward in ``item`` bytes, the step size dt in f32; they and
    their cotangents once backward."""
    ops = 3 * tokens * heads * 5 * head_dim * state
    forward = tokens * (item * (3 * heads * head_dim + 2 * groups * state) + 4 * heads)
    return ops, 3 * forward


def measure(trace: dict, quantity: str, match: str = "", least_s: float = 0.0):
    """``least_s``: the least seconds a step for ``ssd_scan_roofline_share``."""
    ph = _phases()
    lo, hi, steps = ph.window(trace["bench"])
    if not steps:
        return None
    if quantity == "ssd_scan_roofline_share":
        match = "ssd_scan"
    elif quantity != "scope_ms":
        raise ValueError(f"ssm_moe reader has no quantity {quantity!r}")
    own = ph._xplane().self_seconds(trace["ops"], lo, hi)
    filed = sum(t for name, t in own.items()
                if scope_of(trace["paths"].get(name, ""), name) == match)
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
    if quantity == "ssd_scan_roofline_share":
        with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
            kinds = json.load(f)["kinds"]
        # the one kind whose peak the harness used for this run
        peaks = next((p for p in kinds.values()
                      if p["bf16_flops_per_s"] == run.get("peak_flops_per_s")), None)
        if peaks is None:
            return None
        layers, per_sample = shape.pop("layers"), shape.pop("tokens_per_sample")
        ops, nbytes = ssd_cost(run["global_batch"] * per_sample, **shape)
        least_s = layers * max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return measure(trace, quantity, match, least_s)
