"""tools/gdn_tune.py, the gated delta rule's sizing tool, at a length the CPU
runs in seconds through the Pallas interpreter: control flow and the shape of
its one JSON line only — its milliseconds mean something on the chip."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "gdn_tune.py")
SHAPE = [1, 1, 2, 256, 128, 128]


def _run(*flags):
    return subprocess.run(
        [sys.executable, TOOL, "--shape", ",".join(map(str, SHAPE)), "--blocks", "2,4", *flags],
        cwd=REPO, capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.fixture(scope="module")
def line():
    out = _run("--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert len(lines) == 1, out.stdout
    return json.loads(lines[0])


def test_one_json_line_says_what_ran_where(line):
    assert line["rehearsal"] is True and line["device"].startswith("cpu")  # no device metric
    assert (line["shape"], line["chunk"]) == (SHAPE, 64)
    assert line["xla_ms"] > 0 and line["kernels_ms"] > 0
    # the mixer's whole gdn_scan part beside the rule alone
    assert line["mixer_ms"] > 0
    assert line["around_kernels_ms"] == pytest.approx(line["mixer_ms"] - line["kernels_ms"],
                                                       abs=2e-3)


def test_every_kernel_is_timed_at_every_block_and_the_best_are_named(line):
    from byteps_tpu.ops import gated_delta_kernels as gk

    assert list(line["by_kernel"]) == [gk.INVERSE_KERNEL, gk.FWD_KERNEL, gk.BWD_KERNEL]
    for name, best in zip(line["by_kernel"], line["blocks"]):
        times = line["by_kernel"][name]
        assert set(times) == {"2", "4"} and all(t > 0 for t in times.values())
        assert times[str(best)] == min(times.values())


def test_it_refuses_to_time_a_cpu_and_a_rehearsal_writes_nothing(line):
    from byteps_tpu.ops import gated_delta as gd

    before = os.path.getmtime(gd._TUNED_PATH) if os.path.exists(gd._TUNED_PATH) else None
    out = _run()
    assert out.returncode == 2 and not out.stdout.strip() and "refusing" in out.stderr
    after = os.path.getmtime(gd._TUNED_PATH) if os.path.exists(gd._TUNED_PATH) else None
    assert before == after
