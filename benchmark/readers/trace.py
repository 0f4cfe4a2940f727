"""Reader ``trace``: figures of the traced steps (benchmark/xplane.py's
``reduce``), device 0.

``quantity``: ``busy_ms`` (device-op union per step), ``idle_share`` (% of
the traced window with no operation running), ``op_ms`` (summed time of the
operations whose name contains ``match``, per step)."""


def read(run: dict, quantity: str, match: str = ""):
    t = run.get("trace")
    if not t or not t["steps"]:
        return None
    if quantity == "busy_ms":
        return t["busy0_s"] / t["steps"] * 1e3
    if quantity == "idle_share":
        return (1.0 - t["busy0_s"] / t["window_s"]) * 100.0
    if quantity == "op_ms":
        hit = [v for k, v in t["op_seconds"].items() if match in k]
        return sum(hit) / t["steps"] * 1e3 if hit else None
    raise ValueError(f"trace reader has no quantity {quantity!r}")
