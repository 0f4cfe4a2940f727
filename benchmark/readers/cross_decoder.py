"""Reader ``cross_decoder``: what the decoder-hybrid-decoder step adds to a
trace (byteps_tpu/models/cross_decoder.py, ops/selective_scan.py).  A program
without these scopes (the parent of the PR that brought them), and a run
without a TPU trace, read None everywhere.

``scope_ms``: self time a traced step of device 0's operations filed under
the scope ``match`` — forward, recomputation and backward together.  An
operation is filed under the FIRST of ``SCOPES`` that its scope path has as a
segment; the scopes' times are disjoint and can be added.

``selective_scan_roofline_share``: the least time the chip could take for the
Mamba-1 scans of the traced steps, as % of the time of ALL the operations
under ``selective_scan`` (Δ's softplus, the recurrence, ``D x``) — so it reads
the same work whatever implements the scan, XLA's form or a kernel.  The work
is the recurrence's mathematics (:func:`scan_cost`); the least time is the
larger of operations over the peak bf16 rate and bytes over the peak HBM rate
(``peaks.json``).  The shape (``layers``, channels, state size, tokens a
sample, bytes an element) stands in the metric's file.

``diff_flash_roofline_share``: as readers/window_moe.py's
``flash_roofline_share`` (its ``flash_cost`` and its parsing of a call's HLO
line, read from that file) over differential attention's flash calls — d_qk
64, d_v 128, a key/value pair serving two query pairs — the banded ones
(``kind`` ``"window"``) or the others (``"full"``: the full layer's and the
cross layer's, the same kernel at the same shape).
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ("selective_scan", "mamba_proj", "diff_window_attention", "diff_full_attention",
          "diff_cross_attention", "gated_memory", "dense_mlp", "lm_head", "embed")
KINDS = {"window": ("flash_fwd_win", "flash_bwd_win"), "full": ("flash_fwd", "flash_bwd")}


@functools.cache
def _reader(name: str):
    """benchmark/readers/<name>.py by file."""
    spec = importlib.util.spec_from_file_location(
        f"bench_readers_{name}", os.path.join(HERE, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def scope_of(path: str, name: str = "") -> str | None:
    """The scope an operation is filed under, from its scope path."""
    parts = path.split("/")
    return next((s for s in SCOPES if s in parts), None)


def scan_cost(tokens: int, channels: int, state: int, item: int) -> tuple:
    """(operations, bytes) that one layer's scan needs for ``tokens`` tokens,
    forward and backward, recomputation not counted.  A token a (channel,
    state entry): the decay, ``Δ x B`` added and the read by C are 5
    operations forward, and twice that backward.  Bytes: x and y (a channel
    each) in ``item`` bytes, Δ in f32, B and C (``state`` each) in ``item``
    once forward; they and their cotangents once backward."""
    ops = 3 * tokens * 5 * channels * state
    forward = tokens * (item * (2 * channels + 2 * state) + 4 * channels)
    return ops, 3 * forward


def measure(trace: dict, quantity: str, match: str = "", peaks: dict | None = None,
            least_s: float = 0.0, kind: str = "", window: int | None = None):
    """``least_s``: the least seconds a step for ``selective_scan_roofline_share``."""
    ph = _reader("phases")
    lo, hi, steps = ph.window(trace["bench"])
    if not steps:
        return None
    if quantity == "diff_flash_roofline_share":
        flash = _reader("window_moe")
        least = took = 0.0
        for name, a, b in trace["ops"]:
            call = flash._flash_call(name) if lo <= a and b <= hi else None
            if call and call[0] in KINDS[kind]:
                ops, nbytes = flash.flash_cost(*call, window=window if kind == "window" else None)
                least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
                took += b - a
        return least / took * 100.0 if took else None
    if quantity == "selective_scan_roofline_share":
        match = "selective_scan"
    elif quantity != "scope_ms":
        raise ValueError(f"cross_decoder reader has no quantity {quantity!r}")
    own = ph._xplane().self_seconds(trace["ops"], lo, hi)
    filed = sum(t for name, t in own.items()
                if scope_of(trace["paths"].get(name, ""), name) == match)
    if not filed:
        return None
    return filed / steps * 1e3 if quantity == "scope_ms" else least_s * steps / filed * 100.0


def read(run: dict, quantity: str, match: str = "", kind: str = "", window: int | None = None,
         **shape):
    if not run.get("trace"):  # a rehearsal's trace holds no TPU plane
        return None
    trace = _reader("phases").newest_trace()
    if not trace:
        return None
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        kinds = json.load(f)["kinds"]
    # the one kind whose peak the harness used for this run
    peaks = next((p for p in kinds.values()
                  if p["bf16_flops_per_s"] == run.get("peak_flops_per_s")), None)
    least_s = 0.0
    if quantity != "scope_ms" and peaks is None:
        return None
    if quantity == "selective_scan_roofline_share":
        layers, per_sample = shape.pop("layers"), shape.pop("tokens_per_sample")
        ops, nbytes = scan_cost(run["global_batch"] * per_sample, **shape)
        least_s = layers * max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return measure(trace, quantity, match, peaks, least_s, kind, window)
