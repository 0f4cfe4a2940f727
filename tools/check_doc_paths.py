#!/usr/bin/env python
"""CI guard: the documents name files that exist, the sources cite none that do not.

Beside tools/check_env_doc.py and check_metrics_doc.py, for the repo's
account of itself.  Two checks, each a way the record rotted before PR 31:

1. Every repo path or artifact that ``README.md``, ``PERF.md``,
   ``docs/*.md`` and ``examples/README.md`` name inside backticks or as a
   link target exists, and every upper-case ``*.json`` artifact they name
   anywhere.  A name counts when it ends in a source, document
   or data suffix and has no placeholder in it (``<cell>``, ``*``, ``…``);
   it is looked up from the repo's root, from ``byteps_tpu/`` (the
   documents say ``core/engine.py``), from ``benchmark/`` and from the
   document's own directory, and a bare file name anywhere in the tree.
   What a run writes and what belongs to the reference or to jax is in
   ``NOT_OURS``, each with its reason.
2. No file under ``byteps_tpu/``, ``tools/``, ``tests/`` cites the deleted
   verdict file by round and item, or a root ``*_rNN.json`` artifact of
   the CPU harness: a reason stays, a citation of a file that is gone
   does not.

Wired into tier-1 as the two cases of
``tests/test_observability.py::test_the_record_names_only_what_exists``
(``missing_paths`` and ``stale_citations``, one each).

Usage: ``python tools/check_doc_paths.py [--repo ROOT]``
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import sys

_SUFFIXES = "py|md|json|jsonl|sh|cc|h|toml|yml|yaml|so"
#: a path-like name: optional directories, a file name, a known suffix,
#: then optionally ``:12``, ``:12-34`` or ``::test_name``
_NAME_RE = re.compile(
    rf"^(?P<path>[\w.][\w./-]*\.(?:{_SUFFIXES}))(?::\d+(?:-\d+)?|::[\w:\[\]-]+)?$"
)
_QUOTED_RE = re.compile(r"`([^`\n]+)`|\]\(([^)#\s]+)(?:#[^)]*)?\)")
#: a root artifact (``FUSION_BENCH.json``), counted wherever it stands
_ARTIFACT_RE = re.compile(r"\b[A-Z][A-Z0-9_]*(?:_r\d\d?)?\.jsonl?\b")

#: named in the documents, and rightly not in the checkout
NOT_OURS = {
    # written by a run, under a directory the user names
    "decision.json", "trigger.json", "ledger.jsonl", "metrics.json", "attrib.json",
    "comm.json", "merged.json",
    # the driver's, beside the checkout
    "TESTS_LAST_RUN.json",
    # the reference's own build file; a published model's configuration
    "setup.py", "config.json",
}

#: the deleted records, spelt so that this file does not cite them
_CITATION_RE = re.compile("VER" + r"DICT|\b[A-Z][A-Z_]*_r\d\d?\.json\b")


def _documents(repo: str) -> list:
    docs = [os.path.join(repo, n) for n in ("README.md", "PERF.md", "examples/README.md")]
    return [p for p in docs + sorted(glob.glob(os.path.join(repo, "docs", "*.md")))
            if os.path.exists(p)]


def _basenames(repo: str) -> set:
    names = set()
    for root, dirs, files in os.walk(repo):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in (
            "__pycache__", "chiprun_out", "chiprun_checkout")]
        names.update(files)
    return names


def missing_paths(repo: str) -> list:
    """``doc:line: name`` for every named path that resolves to nothing."""
    known, out = _basenames(repo), []
    for doc in _documents(repo):
        roots = (repo, os.path.join(repo, "byteps_tpu"),
                 os.path.join(repo, "benchmark"), os.path.dirname(doc))
        with open(doc) as f:
            lines = f.read().splitlines()
        for lineno, line in enumerate(lines, 1):
            named = [q or link for q, link in _QUOTED_RE.findall(line)]
            for name in named + _ARTIFACT_RE.findall(_QUOTED_RE.sub(" ", line)):
                for word in name.split():
                    m = _NAME_RE.match(word.strip(",;()"))
                    if m is None or "://" in word:
                        continue
                    path = m.group("path")
                    if path in NOT_OURS or os.path.basename(path) in NOT_OURS:
                        continue
                    if any(os.path.exists(os.path.join(r, path)) for r in roots):
                        continue
                    if "/" not in path and path in known:
                        continue
                    out.append(f"{os.path.relpath(doc, repo)}:{lineno}: {path}")
    return out


def stale_citations(repo: str) -> list:
    """``file:line: text`` for every citation of a deleted record."""
    out = []
    for sub in ("byteps_tpu", "tools", "tests"):
        for root, dirs, files in os.walk(os.path.join(repo, sub)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for fn in files:
                # this guard spells the pattern; scanning itself would match
                if not fn.endswith((".py", ".cc", ".h", ".sh")) or fn == "check_doc_paths.py":
                    continue
                path = os.path.join(root, fn)
                with open(path, errors="replace") as f:
                    for lineno, line in enumerate(f, 1):
                        if _CITATION_RE.search(line):
                            out.append(f"{os.path.relpath(path, repo)}:{lineno}: {line.strip()}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--repo",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    args = ap.parse_args(argv)
    failures = [f"names a file that does not exist — {m}" for m in missing_paths(args.repo)]
    failures += [f"cites a deleted record — {m}" for m in stale_citations(args.repo)]
    for line in failures:
        print(line, file=sys.stderr)
    if failures:
        print(f"check_doc_paths: {len(failures)} finding(s)", file=sys.stderr)
        return 1
    print("check_doc_paths: documents and sources name only what exists")
    return 0


if __name__ == "__main__":
    sys.exit(main())
