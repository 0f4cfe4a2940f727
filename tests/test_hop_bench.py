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


@pytest.mark.parametrize("mode", ["frame", "echo", "d2h"])
def test_one_json_line_with_positive_medians(reading, mode):
    got = reading(mode)
    assert (got["mode"], got["frames"], got["bytes"]) == (mode, FRAMES, NBYTES)
    assert got["plane"] == "host" and got["host_cores"] == os.cpu_count()
    sides = {"frame": ["journal_on", "journal_off"], "echo": ["fresh", "held", "split"],
             "d2h": ["one_at_a_time", "issued_first", "issued_first_freed"]}[mode]
    for side in [got[name] for name in sides]:
        assert 0 < side["p10_ms"] <= side["median_ms"] <= side["p90_ms"]


def test_brackets_size_the_split_of_a_sampled_service(reading):
    """A bracket outside a sampled service reads no clock, so it is the
    cheaper; the three sending passes differ in their sampling alone."""
    got = reading("brackets")
    assert 0 < got["bracket_unsampled_us"] < got["bracket_sampled_us"]
    assert 0 < got["service_unsampled_us"] < got["service_sampled_us"]
    assert got["thread_time_pair_us"] > 0
    for name in ("as_the_program", "every_one", "none"):
        side = got["sent_sampling_" + name]
        assert 0 < side["p10_ms"] <= side["median_ms"] <= side["p90_ms"]


def test_echo_reads_one_connection_and_one_a_direction(reading):
    """``split`` is ``held`` with the pulls and their replies on a second
    connection to the same child (a TCP link's push lane and pull lane),
    ``two_senders`` is ``split`` with two pushing threads, a push connection
    each; every reading brings its last partition back unchanged, or the
    tool exits non-zero."""
    got = reading("echo")
    assert [k for k in got if isinstance(got[k], dict)] == [
        "fresh", "held", "split", "two_senders", "two_each"]
    for side in ("held", "split", "two_senders", "two_each"):
        assert set(got[side]) == {"median_ms", "p10_ms", "p90_ms"}


def test_d2h_reads_one_array_both_ways_and_says_where(reading):
    """Both loops read every slice back unchanged (the tool exits non-zero
    otherwise); the line names the device, so a CPU reading cannot pass for
    the chip's, and on a mesh it also reads a replicated array's slices."""
    got = reading("d2h")
    assert got["device"] == "cpu" and got["devices"] >= 1
    assert ("one_at_a_time_replicated" in got) == (got["devices"] > 1)
    first = got["issued_first"]
    assert 0 < first["issue_ms"] <= first["p90_ms"]
    for side in (got["one_at_a_time"], first):
        assert side["gb_per_s"] == pytest.approx(NBYTES / side["median_ms"] / 1e6)


def test_the_journal_keeps_references_to_read_only_frames(reading):
    got = reading("frame")
    passes = got["timed_passes"] + 1  # the warm-up pass journals too
    assert got["journal_ref_bytes"] == passes * FRAMES * NBYTES
    assert got["journal_copy_bytes"] == 0
