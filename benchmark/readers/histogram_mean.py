"""Reader ``histogram_mean``: the mean of the program's own histogram
observations made inside the window, (delta sum) / (delta count) x ``scale``,
over one or more keys of ``bps.get_metrics()["histograms"]`` taken together."""


def read(run: dict, keys: list, scale: float = 1.0):
    before, after = run["histograms"]["before"], run["histograms"]["after"]
    dsum = dcount = 0.0
    for key in keys:
        if key not in after:
            return None
        start = before.get(key, {"sum": 0.0, "count": 0})
        dsum += after[key]["sum"] - start["sum"]
        dcount += after[key]["count"] - start["count"]
    return dsum / dcount * scale if dcount else None
