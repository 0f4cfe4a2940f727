"""``BENCHMARK.json``'s listing, held by the suite the driver runs (ROADMAP D22
(i)): the data-only checks of ``benchmark/tests/test_benchmark.py``
(``test_the_listing_holds_together``, ``test_each_per_layer_entry_has_its_
metric_file_and_reader``) — files read, nothing under ``benchmark/`` imported
or run.  The yardstick's other checks stay by hand (``python -m pytest
benchmark/tests -q``)."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = load_json(ROOT, "BENCHMARK.json")


def metric_files():
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "metrics")))


def test_each_per_layer_entry_has_its_metric_file_and_reader():
    """BENCHMARK.json alone says what a metric is (name, layer, unit, better,
    source, moves); its file says only how it is read, by a reader that is
    there and has a ``read``."""
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert metric_files() == sorted(m["name"] for m in BENCH["per_layer"])
    for m in BENCH["per_layer"]:
        spec = load_json(BENCH_DIR, "metrics", f"{m['name']}.json")
        assert set(spec) == {"reader", "args", "what"}, m["name"]
        assert m["moves"] in end_to_end, m["name"]
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        with open(os.path.join(BENCH_DIR, "readers", f"{spec['reader']}.py")) as f:
            assert re.search(r"^def read\(run", f.read(), re.M), (m["name"], spec["reader"])


def test_the_listing_holds_together():
    """``per_layer`` under its cap, one entry a metric file — no two files with
    the same reader and arguments, so a quantity that several cells report is
    ONE entry listing them — and nothing waiting outside the list."""
    assert len(BENCH["per_layer"]) <= 128
    assert len({m["name"] for m in BENCH["per_layer"]}) == len(BENCH["per_layer"])
    read_by = {}
    for name in metric_files():
        spec = load_json(BENCH_DIR, "metrics", f"{name}.json")
        read_by.setdefault((spec["reader"], json.dumps(spec["args"], sort_keys=True)),
                           []).append(name)
    assert [names for names in read_by.values() if len(names) > 1] == []
    assert not os.path.exists(os.path.join(BENCH_DIR, "unlisted"))


def test_a_listed_cell_reports_what_its_metric_moves():
    """A ``workloads`` list names cells that exist, once each, and each reports
    the end-to-end metric the entry moves; every cell has a per-layer entry."""
    cells = {w["name"] for w in BENCH["workloads"]}
    end_to_end = {m["name"]: set(m.get("workloads", cells)) for m in BENCH["end_to_end"]}
    covered = set()
    for m in BENCH["per_layer"]:
        listed = m.get("workloads")
        covered |= set(listed or cells)
        if listed is not None:
            assert listed and len(set(listed)) == len(listed), m["name"]
            assert set(listed) <= end_to_end[m["moves"]], m["name"]
    assert covered == cells
