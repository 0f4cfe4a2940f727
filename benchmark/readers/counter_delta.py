"""Reader ``counter_delta``: growth of the program's counters
(``bps.get_robustness_counters()``) over the window, summed over
``counters``, per completed step, x ``scale``.

The program's snapshot holds a counter only once it has been raised.  A
counter that counts what should not happen is therefore absent in a sound run:
``beside`` names counters raised on the same code path (``journal_ref_bytes``
for ``journal_copy_bytes``), and where all of those are there a missing
counter reads 0.  A program without the path at all has neither, and reads
nothing."""


def read(run: dict, counters: list, scale: float = 1.0, beside: tuple = ()):
    before, after = run["counters"]["before"], run["counters"]["after"]
    proven = bool(beside) and all(c in after for c in beside)
    if not run["steps"] or not (proven or all(c in after for c in counters)):
        return None
    grown = sum(after.get(c, 0) - before.get(c, 0) for c in counters)
    return grown / run["steps"] * scale
