"""Reader ``gil``: how long a process's threads HELD its GIL, or waited for it,
out of the program's own sampled services (``core/tracing.sampled``).

``threads`` lists the threads of one process, each as ``{"service": [keys],
"stage": "<stage>"}``: ``service`` the histograms whose (delta) sum is the
thread's time in service inside the window (a stage thread's
``span_seconds{name="stage.<STAGE>"}``, a server thread kind's
``thread_seconds{...state="service"}``), ``stage`` the label under which one
service in so many (61) was split on two clocks —
``stage_sample_seconds{clock=...,stage=...<labels>}``, ``labels`` the further
labels a server's series carry in the worker's snapshot
(``,rank="0",role="server"``).  A thread gives its service x (delta sum of
``clock``) / (delta sum of ``wall``): the sampled share scaled to the thread's
whole service, as ``histogram_per_step``'s ``share`` does; the threads are
summed.  Threads of one kind that share their series (a link's two receive
threads a lane kind, the server's four serve threads) are one entry.

``over`` = [keys]: the sum is divided by those histograms' delta sum (the
caller's ``span_seconds{name="hybrid.hop_wait"}``: the share of the hop during
which one of these threads held the GIL; one GIL cannot be held by two, so
100 x that cannot pass 100 but by the sampling's noise and by what the
threads hold outside the hop).  Without ``over`` it is divided by the steps.

A key missing after the window, or a thread that sampled nothing in it, reads
nothing: a program without the instrument."""


def read(run: dict, threads: list, clock: str, scale: float = 1.0,
         over: list | None = None, labels: str = ""):
    before, after = run["histograms"]["before"], run["histograms"]["after"]

    def grown(names):
        return sum(after[k]["sum"] - before.get(k, {"sum": 0.0})["sum"] for k in names)

    def sampled(stage, which):
        return f'stage_sample_seconds{{clock="{which}"{labels},stage="{stage}"}}'

    total = 0.0
    for thread in threads:
        of, whole = sampled(thread["stage"], clock), sampled(thread["stage"], "wall")
        if any(k not in after for k in thread["service"] + [of, whole]):
            return None
        wall = grown([whole])
        if not wall:
            return None
        total += grown(thread["service"]) * grown([of]) / wall
    if over is not None:
        if any(k not in after for k in over) or not grown(over):
            return None
        return total / grown(over) * scale
    return total / run["steps"] * scale if run["steps"] else None
