"""The one timing loop of the tools that time a function on the chip
(flash_tune.py, gdn_tune.py, held_slots_probe.py)."""

import contextlib
import time


def timed(fn, *xs, steps: int, warmup: int = 1, trace_dir: str = "") -> float:
    """Milliseconds a call of ``fn(*xs)`` — a jitted or compiled function — on
    the host's clock: ``warmup`` calls first, each waited for (the first
    compiles), then ``steps`` calls dispatched back to back and ONE wait, for
    the last call's result, so the device runs them without a gap and the
    clock stops when it is done.  With ``trace_dir`` the timed calls run under
    jax's profiler, which writes there: what a caller reads out of that
    profile (a kernel's share, the device's busy time) stands beside this
    reading.  On the CPU (a rehearsal) the number is XLA:CPU's or the Pallas
    interpreter's and never a device metric."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*xs))
    with jax.profiler.trace(trace_dir) if trace_dir else contextlib.nullcontext():
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*xs)
        jax.block_until_ready(out)
        elapsed = time.perf_counter() - t0
    return elapsed / steps * 1e3
