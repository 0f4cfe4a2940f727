#!/usr/bin/env python
"""CI guard: every metric the code emits must be in the documented catalog.

Scans ``byteps_tpu/`` for metric registrations/bumps —

    counters().bump("name" ...)        # counters (incl. chaos _bump sites)
    counters().set_floor("name" ...)
    metrics().observe("name" ...)      # histograms
    metrics().histogram("name" ...)
    metrics().held("name" ...)         # a histogram kept at hand
    metrics().gauge_set("name" ...) / gauge_fn("name" ...)

— and fails (exit 1) listing any name absent from the metric catalog in
``docs/observability.md``.  f-string names (``f"fusion_flush_{reason}"``)
are matched by their literal prefix: at least one documented name must
start with it.

The native C++ plane is covered too: every ``"native_*"`` string
literal in ``byteps_tpu/native/*.cc`` (counter names in ps_server.cc's
``kCounterNames``, histogram names at their registration sites) must
appear in the catalog — the GIL-free engines' metric names rot exactly
like the Python ones.  Wired into tier-1 as
``tests/test_observability.py::test_metrics_catalog_complete`` so the
catalog cannot rot.

Usage: ``python tools/check_metrics_doc.py [--repo ROOT]``
"""

from __future__ import annotations

import argparse
import os
import re
import sys

#: call sites that mint a metric name; the first string literal argument
#: is the name.  ``_bump`` covers the chaos van's counter helper.
_CALL_RE = re.compile(
    r"\.(?:bump|_bump|set_floor|observe|histogram|held|gauge_set|gauge_fn)\(\s*"
    r"(f?)\"([A-Za-z0-9_{}]+)\"",
)

#: metric names in the docs catalog: any backticked word-ish token
_DOC_NAME_RE = re.compile(r"`([a-z][a-z0-9_]*)`")

#: a native metric name minted in C++ — any native_* string literal in
#: the engine sources (counter name tables, histogram registration
#: sites).  The native_ prefix is the naming contract
#: (docs/observability.md), so the literal scan IS the registration scan.
_NATIVE_NAME_RE = re.compile(r"\"(native_[a-z0-9_]+)\"")


def discover_emitted(repo: str) -> dict:
    """{name_or_prefix: [file:line, ...]}; prefixes end with '*'."""
    found: dict = {}
    pkg = os.path.join(repo, "byteps_tpu")
    for root, _dirs, files in os.walk(pkg):
        if "__pycache__" in root:
            continue
        for fn in files:
            path = os.path.join(root, fn)
            if fn.endswith(".cc"):
                # native plane: scan the C++ sources' string literals for
                # native_* metric names (counters + histograms)
                with open(path) as f:
                    text = f.read()
                for m in _NATIVE_NAME_RE.finditer(text):
                    line = text[: m.start()].count("\n") + 1
                    rel = os.path.relpath(path, repo)
                    found.setdefault(m.group(1), []).append(f"{rel}:{line}")
                continue
            if not fn.endswith(".py"):
                continue
            with open(path) as f:
                text = f.read()
            for m in _CALL_RE.finditer(text):
                is_f, name = m.group(1), m.group(2)
                if is_f or "{" in name:
                    # f-string: enforce the literal prefix
                    name = name.split("{", 1)[0]
                    if not name:
                        continue  # fully dynamic: nothing checkable
                    name += "*"
                line = text[: m.start()].count("\n") + 1
                rel = os.path.relpath(path, repo)
                found.setdefault(name, []).append(f"{rel}:{line}")
    return found


def documented_names(repo: str) -> set:
    doc = os.path.join(repo, "docs", "observability.md")
    if not os.path.exists(doc):
        return set()
    with open(doc) as f:
        return set(_DOC_NAME_RE.findall(f.read()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--repo",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    args = ap.parse_args(argv)
    emitted = discover_emitted(args.repo)
    docs = documented_names(args.repo)
    if not docs:
        print("docs/observability.md missing or has no catalog entries",
              file=sys.stderr)
        return 1
    missing = []
    for name, sites in sorted(emitted.items()):
        if name.endswith("*"):
            prefix = name[:-1]
            ok = any(d.startswith(prefix) for d in docs)
        else:
            ok = name in docs
        if not ok:
            missing.append((name, sites))
    if missing:
        print("metrics emitted but not documented in docs/observability.md:",
              file=sys.stderr)
        for name, sites in missing:
            print(f"  {name}  ({'; '.join(sites[:3])})", file=sys.stderr)
        return 1
    print(f"metrics catalog OK: {len(emitted)} emitted name(s), "
          f"{len(docs)} documented")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
