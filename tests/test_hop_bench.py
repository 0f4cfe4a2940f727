"""tools/hop_bench.py, the host-path probe, at a size the CPU runs in seconds:
control flow and counts only — its timings mean something on the chip's host."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FRAMES, NBYTES = 8, 64 << 10


@pytest.fixture(scope="module")
def reading():
    """One run a mode, made when a test first asks for it."""
    made = {}

    def run(mode):
        if mode not in made:
            out = subprocess.run(
                [sys.executable, os.path.join(REPO, "tools", "hop_bench.py"), "--mode", mode,
                 "--frames", str(FRAMES), "--bytes", str(NBYTES)],
                cwd=REPO, capture_output=True, text=True, timeout=240,
            )
            assert out.returncode == 0, out.stderr[-3000:]
            lines = out.stdout.strip().splitlines()
            assert len(lines) == 1, out.stdout
            made[mode] = json.loads(lines[0])
        return made[mode]

    return run


@pytest.mark.parametrize("mode", ["frame", "echo"])
def test_one_json_line_with_positive_medians(reading, mode):
    got = reading(mode)
    assert (got["mode"], got["frames"], got["bytes"]) == (mode, FRAMES, NBYTES)
    assert got["plane"] == "host" and got["host_cores"] == os.cpu_count()
    sides = [got["journal_on"], got["journal_off"]] if mode == "frame" else [got]
    for side in sides:
        assert 0 < side["p10_ms"] <= side["median_ms"] <= side["p90_ms"]


def test_the_journal_keeps_references_to_read_only_frames(reading):
    got = reading("frame")
    passes = got["timed_passes"] + 1  # the warm-up pass journals too
    assert got["journal_ref_bytes"] == passes * FRAMES * NBYTES
    assert got["journal_copy_bytes"] == 0
