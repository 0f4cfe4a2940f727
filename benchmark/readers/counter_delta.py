"""Reader ``counter_delta``: growth of the program's counters
(``bps.get_robustness_counters()``) over the window, summed over
``counters``, per completed step, x ``scale``."""


def read(run: dict, counters: list, scale: float = 1.0):
    before, after = run["counters"]["before"], run["counters"]["after"]
    if not run["steps"] or not all(c in after for c in counters):
        return None
    grown = sum(after[c] - before.get(c, 0) for c in counters)
    return grown / run["steps"] * scale
