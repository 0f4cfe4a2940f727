"""Reader ``harness``: what the harness itself recorded about the run.

``quantity``: ``mfu`` (% of the chips' peak bf16 rate that the samples
completed in the window account for: model FLOP per sample x samples / window
seconds / (chips x peak); forward and backward only, recomputation not
counted), ``peak_hbm_gib`` (the largest footprint read in the window on the
fullest device: ``bytes_in_use`` + ``bytes_reserved`` of one reading) or
``peak_in_use_gib`` (the fullest device's ``peak_bytes_in_use`` after the
window)."""

_BYTES = {"peak_hbm_gib": "peak_hbm_bytes", "peak_in_use_gib": "peak_in_use_bytes"}


def read(run: dict, quantity: str):
    if quantity == "mfu":
        if not (run["steps"] and run["peak_flops_per_s"]):
            return None
        rate = run["steps"] * run["global_batch"] / run["window_s"]
        return rate * run["flops_per_sample"] / (run["chips"] * run["peak_flops_per_s"]) * 100.0
    if quantity in _BYTES:
        peak = run.get(_BYTES[quantity])
        return peak / 2**30 if peak else None
    raise ValueError(f"harness reader has no quantity {quantity!r}")
