"""Reader ``histogram_per_step``: what the program's own histograms gathered
inside the window, a completed step: the sum over ``keys`` of ``bps.get_metrics()
["histograms"]`` of (delta sum), / ``run["steps"]`` x ``scale``.  Where the
observations are durations of one thread's work (a stage thread's services,
its waits by cause), that is the thread's time in them a step.

``share`` = ``{"of": [keys], "in": [keys]}`` takes that share of it: (delta sum
over ``of``) / (delta sum over ``in``), two histograms the program fills from
the same sampled observations (one service in so many on the thread's CPU
clock and on the wall clock: the part of a step's service that was on the CPU).

``account`` lists the keys of a thread's WHOLE account (its service and every
cause of its idle time: together they are its wall clock between the two
snapshots).  The harness takes the profiler's start and stop out of
``window_s`` (5 s on four chips), while the program's clock runs on; no step
runs then, so whatever the account holds beyond ``window_s`` the thread spent
waiting for work, and it is taken off: a wait reads what it was inside the
window's counted time, traced run or not.

A key missing after the window reads nothing: a program without the instrument."""


def read(run: dict, keys: list, scale: float = 1.0, share: dict | None = None,
         account: list | None = None):
    before, after = run["histograms"]["before"], run["histograms"]["after"]
    share, account = share or {"of": [], "in": []}, account or []
    if not run["steps"] or any(k not in after for k in keys + share["of"] + share["in"] + account):
        return None

    def grown(names):
        return sum(after[k]["sum"] - before.get(k, {"sum": 0.0})["sum"] for k in names)

    seconds = grown(keys)
    if account:
        seconds = max(0.0, seconds - max(0.0, grown(account) - run["window_s"]))
    if share["of"]:
        whole = grown(share["in"])
        if not whole:
            return None
        seconds *= grown(share["of"]) / whole
    return seconds / run["steps"] * scale
