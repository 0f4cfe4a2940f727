"""Reader ``harness``: what the harness itself recorded about the run.

``quantity``: ``mfu`` (% of the chips' peak bf16 rate that the samples
completed in the window account for: model FLOP per sample x samples / window
seconds / (chips x peak); forward and backward only, recomputation not
counted), ``peak_hbm_gib`` (the largest footprint read in the window on the
fullest device: ``bytes_in_use`` + ``bytes_reserved`` of one reading),
``peak_in_use_gib`` (the fullest device's ``peak_bytes_in_use`` after the
window) or ``slow_step_share`` (% of the window spent in steps slower than
1.25 x the median of the window's step times, dispatch to the
``block_until_ready`` of loss and parameters, by the host's clock: stalls
inside a run, where the median is the run's own level)."""

import statistics

_BYTES = {"peak_hbm_gib": "peak_hbm_bytes", "peak_in_use_gib": "peak_in_use_bytes"}
SLOW = 1.25  # a step this many medians long is a stalled one


def step_record(step_s: list, window_s: float) -> dict | None:
    """The shape of one window's step times (seconds in, milliseconds and
    shares of ``window_s`` out); None for a window without a whole step."""
    if not step_s:
        return None
    p50 = statistics.median(step_s)
    tenths = [p50] * 9  # one step: every percentile is that step
    if len(step_s) > 1:
        tenths = statistics.quantiles(step_s, n=10, method="inclusive")
    slowest = max(range(len(step_s)), key=step_s.__getitem__)
    return {
        "steps": len(step_s),
        "p50_ms": p50 * 1e3, "p10_ms": tenths[0] * 1e3, "p90_ms": tenths[-1] * 1e3,
        "slowest_ms": step_s[slowest] * 1e3, "slowest_index": slowest,
        "slow_share": sum(t for t in step_s if t > SLOW * p50) / window_s,
        "in_steps_share": sum(step_s) / window_s,
    }


def read(run: dict, quantity: str):
    if quantity == "mfu":
        if not (run["steps"] and run["peak_flops_per_s"]):
            return None
        rate = run["steps"] * run["global_batch"] / run["window_s"]
        return rate * run["flops_per_sample"] / (run["chips"] * run["peak_flops_per_s"]) * 100.0
    if quantity in _BYTES:
        peak = run.get(_BYTES[quantity])
        return peak / 2**30 if peak else None
    if quantity == "slow_step_share":
        record = step_record(run.get("step_s") or [], run["window_s"])
        return record["slow_share"] * 100.0 if record else None
    raise ValueError(f"harness reader has no quantity {quantity!r}")
