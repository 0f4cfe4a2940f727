"""Reader ``channel_delta_moe``: what the channel-delta MoE step adds to a
trace (byteps_tpu/models/channel_delta_moe.py, ops/gated_delta.py's channel
form, moe_family.latent_attention without positions).  A program without
these scopes (the parent of the PR that brought them), and a run without a TPU
trace, read None everywhere.  (The family's three routing counters are read
by ``latent_moe``'s reader, by data alone, as the other families' are.)

``scope_ms``: self time a traced step of device 0's operations filed under
the scope ``match`` — forward, recomputation and backward together.  An
operation is filed under the FIRST of ``SCOPES`` that its scope path has as a
segment — the grouped products, which carry no scope path on this compiler,
under ``moe_experts`` by name —; the scopes' times are disjoint and can be
added.

``kda_scan_roofline_share``: the least time the chip could take for the delta
rule of the traced steps, as % of the time of ALL the operations under
``kda_scan`` (the convolutions, the gates and the gated norm too) — so it
reads the same work whatever implements the rule, XLA's chunked form or a
kernel.  The work is the mathematics of the recurrence
(:func:`channel_delta_rule_cost`), not of the chunked form; the least time is
the larger of operations over the peak bf16 rate and bytes over the peak HBM
rate (``peaks.json``).  The shape (``layers``, heads, head sizes, tokens a
sample, bytes an element) stands in the metric's file.

``nope_mla_flash_roofline_share``: as readers/window_moe.py's
``flash_roofline_share`` (its ``flash_cost`` and its parsing of a call's HLO
line, read from that file) over the latent layer's causal flash calls,
``flash_fwd`` | ``flash_bwd`` at d_qk 192, d_v 128.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ("kda_scan", "kda_proj", "nope_latent_attention", "dense_mlp", "moe_route",
          "moe_experts", "moe_shared")
#: as the grouped products' operations are named in a trace; they carry no scope path
RAGGED_DOT = "ragged-dot"
KERNELS = ("flash_fwd", "flash_bwd")


@functools.cache
def _reader(name: str):
    """benchmark/readers/<name>.py by file."""
    spec = importlib.util.spec_from_file_location(
        f"bench_readers_{name}", os.path.join(HERE, f"{name}.py"))
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


def channel_delta_rule_cost(tokens: int, heads: int, d_k: int, d_v: int, item: int) -> tuple:
    """(operations, bytes) that one layer's rule needs for ``tokens`` tokens,
    forward and backward, recomputation not counted.  A token a head: Sᵀk,
    the rank-one update and Sᵀq are 6 d_k d_v operations forward as
    ``delta_moe.delta_rule_cost`` counts them (the decay of S's d_k d_v
    entries among them), plus d_k for the channels' exponentials; twice that
    backward.  Bytes: q, k (d_k each), v and o (d_v each) in ``item`` bytes,
    the decay's log g in f32 A CHANNEL (d_k) and the writing strength beta in
    f32, each once forward; they and their cotangents once backward."""
    ops = 3 * tokens * heads * (6 * d_k * d_v + d_k)
    forward = tokens * heads * (item * (2 * d_k + 2 * d_v) + 4 * d_k + 4)
    return ops, 3 * forward


def measure(trace: dict, quantity: str, match: str = "", peaks: dict | None = None,
            least_s: float = 0.0):
    """``least_s``: the least seconds a step for ``kda_scan_roofline_share``."""
    ph = _reader("phases")
    lo, hi, steps = ph.window(trace["bench"])
    if not steps:
        return None
    if quantity == "nope_mla_flash_roofline_share":
        flash = _reader("window_moe")
        least = took = 0.0
        for name, a, b in trace["ops"]:
            call = flash._flash_call(name) if lo <= a and b <= hi else None
            if call and call[0] in KERNELS:
                ops, nbytes = flash.flash_cost(*call)
                least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
                took += b - a
        return least / took * 100.0 if took else None
    if quantity == "kda_scan_roofline_share":
        match = "kda_scan"
    elif quantity != "scope_ms":
        raise ValueError(f"channel_delta_moe reader has no quantity {quantity!r}")
    own = ph._xplane().self_seconds(trace["ops"], lo, hi)
    filed = sum(t for name, t in own.items()
                if scope_of(trace["paths"].get(name, ""), name) == match)
    if not filed:
        return None
    if quantity == "kda_scan_roofline_share":
        return least_s * steps / filed * 100.0
    return filed / steps * 1e3


def read(run: dict, quantity: str, match: str = "", **shape):
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
    if quantity.endswith("roofline_share") and peaks is None:
        return None
    least_s = 0.0
    if quantity == "kda_scan_roofline_share":
        layers, per_sample = shape.pop("layers"), shape.pop("tokens_per_sample")
        ops, nbytes = channel_delta_rule_cost(run["global_batch"] * per_sample, **shape)
        least_s = layers * max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return measure(trace, quantity, match, peaks, least_s)
