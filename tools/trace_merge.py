#!/usr/bin/env python
"""Stitch per-process byteps trace files into ONE cross-process timeline.

Each worker writes ``<trace_dir>/<local_rank>/comm.json`` and each server
(Python engine directly, native C++ engine via the span-ring drain in
NativePSServer) ``<trace_dir>/server<rank>/comm.json`` (core/tracing.py).
Span events carry wire-propagated trace/span ids (docs/observability.md),
so a worker's PUSH span and the server's recv→sum→publish→reply children
share a trace id — but they live in separate files.  This tool:

1. collects every ``comm.json`` under the given directories (or explicit
   file paths),
2. keeps per-process identity: span events already carry a ``pid`` like
   ``worker0`` / ``server1``; per-tensor stage envelopes (whose pid is
   the tensor name) are namespaced per source file so two workers' rows
   don't collide,
3. emits Chrome trace FLOW events (``ph: s/f``) linking every
   parent→child span pair found across processes, so Perfetto draws
   arrows from the worker RPC span into the server's child spans,
4. counts ORPHANED children (parent id never seen — a missing server or
   worker file) instead of silently dropping the arrow: a clean-looking
   merge that actually lost a process now says so,
5. writes one merged Perfetto-loadable JSON.

Usage:

    python tools/trace_merge.py -o merged.json TRACE_DIR [TRACE_DIR ...]

``--critical-path ATTRIB.json`` additionally walks the merged flow graph
and attributes where the time of one training step went — engine-queue
wait vs wire vs sum vs publish vs reply, split per engine (``python`` /
``native``; native server children are tagged ``engine: "native"`` by
the drain).  Reducer-lane spans (the drain
puts each stripe on its own ``stripe<N>`` Perfetto track) additionally
get a per-stripe **occupancy** split — stripe identity comes from the
span's ``stripe`` arg or, failing that, its ``stripe<N>`` tid — and the
occupancy is fed straight into the SAME ``hot_stripe`` trigger rule the
on-node flight recorder runs (core/flightrec.py), so a skewed key hash
found in an offline trace and one caught live by the flight recorder
are judged by one rule, not two drifting reimplementations.

Demo recipe (2 workers / 1 server, fused + chaos): docs/observability.md.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple


def find_trace_files(paths: List[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for root, _dirs, files in os.walk(p):
            for f in files:
                if f.endswith(".json") and f.startswith("comm"):
                    out.append(os.path.join(root, f))
    return sorted(set(out))


def _source_tag(path: str) -> str:
    """A short per-file namespace: the containing directory name
    (``0``, ``1``, ``server0``, …)."""
    return os.path.basename(os.path.dirname(os.path.abspath(path))) or "trace"


def merge(files: List[str]) -> dict:
    events: List[dict] = []
    #: span id (hex) → (pid, tid, ts_us, dur_us) of the span that OWNS it
    by_span: Dict[str, Tuple[str, str, float, float]] = {}
    #: (child span ref) parent id (hex) → list of child event tuples
    child_refs: List[Tuple[str, str, str, float]] = []

    for path in files:
        tag = _source_tag(path)
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            print(f"warning: skipping {path}: {e}", file=sys.stderr)
            continue
        for ev in payload.get("traceEvents", []):
            ev = dict(ev)
            args = ev.get("args") or {}
            if ev.get("cat") == "span":
                # cross-process identity is already in pid (worker0 …)
                span = args.get("span")
                if span and ev.get("ph") == "X":
                    prev = by_span.get(span)
                    # keep the EARLIEST event as the span's anchor (a
                    # task's first stage), so flow arrows start where
                    # the work did
                    if prev is None or ev["ts"] < prev[2]:
                        by_span[span] = (
                            ev["pid"], ev["tid"], ev["ts"], ev.get("dur", 0)
                        )
                parent = args.get("parent")
                if parent:
                    child_refs.append(
                        (parent, ev["pid"], ev["tid"], ev["ts"])
                    )
            else:
                # per-tensor stage envelope: namespace the tensor-name pid
                # per source process so two ranks' rows stay separate
                ev["pid"] = f"{tag}:{ev.get('pid', '')}"
            events.append(ev)

    # flow events: arrow from the parent span (worker RPC) to each child
    # (server-side stage).  One flow id per parent span.  A child whose
    # parent was never merged in (missing worker/server file, dropped
    # window) is an ORPHAN — counted, not silently armless.
    flow_id = 0
    seen_parent_flow: Dict[str, int] = {}
    orphan_parents: Dict[str, int] = {}
    flows: List[dict] = []
    for parent, cpid, ctid, cts in child_refs:
        anchor = by_span.get(parent)
        if anchor is None:
            orphan_parents[parent] = orphan_parents.get(parent, 0) + 1
            continue  # parent span's process wasn't merged in
        ppid, ptid, pts, pdur = anchor
        fid = seen_parent_flow.get(parent)
        if fid is None:
            flow_id += 1
            fid = seen_parent_flow[parent] = flow_id
            flows.append({
                "name": "rpc", "cat": "flow", "ph": "s", "id": fid,
                "ts": pts + max(0.0, pdur) / 2, "pid": ppid, "tid": ptid,
            })
        flows.append({
            "name": "rpc", "cat": "flow", "ph": "f", "bp": "e", "id": fid,
            "ts": cts, "pid": cpid, "tid": ctid,
        })
    events.extend(flows)
    events.sort(key=lambda e: e.get("ts", 0))
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "merged_from": files,
            "linked_spans": len(seen_parent_flow),
            "cross_process_children": len(child_refs),
            # children whose parent id never appeared in any merged file
            # — usually a process whose trace file is missing entirely
            "orphaned_spans": sum(orphan_parents.values()),
            "orphaned_parent_ids": len(orphan_parents),
        },
    }


# --- critical-path attribution (docs/observability.md) ---------------------
#
# Walk the merged flow graph: every server child span names its stage
# (recv = engine-queue wait, sum, publish, reply, resync) and parents
# onto the worker span that caused it.  The worker side of the same RPC
# is the PUSH / PULL / FUSE stage event carrying that span id.  Whatever
# part of the worker-observed RPC the server stages don't cover is wire
# + client overhead.  Aggregated per engine (the native server's drained
# children carry ``engine: "native"``), per stage, and per trace (one
# push_pull invocation = one trace = one step's worth of one tensor).

#: worker pipeline stages that bound one wire RPC (engine.py stage names)
_RPC_STAGES = {"PUSH", "PULL", "FUSE", "RESYNC", "INIT"}
_SERVER_STAGES = ("recv", "sum", "publish", "reply", "resync")

#: reducer-lane track names the span drain emits (server.py
#: ``_drain_spans_once``): one Perfetto thread per stripe
_STRIPE_TID = re.compile(r"^stripe(\d+)$")


def _span_stripe(args: dict, tid) -> Optional[int]:
    """Which reducer stripe executed a server child span: the explicit
    ``stripe`` arg when the drain stamped one, else derived from the
    ``stripe<N>`` track (tid) the drain files every reducer-lane span
    under.  None = a serve/control-thread span (``key<K>`` tracks)."""
    s = (args or {}).get("stripe")
    if s is not None:
        try:
            return int(s)
        except (TypeError, ValueError):
            return None
    m = _STRIPE_TID.match(str(tid or ""))
    return int(m.group(1)) if m else None


def _eval_hot_stripe(busy_us: Dict[str, float],
                     busy_n: Dict[str, int]) -> Optional[dict]:
    """Feed the per-stripe occupancy into the hot-stripe trigger rule
    the on-node flight recorder runs, verbatim: build the same record
    shape (``{"stripes": {stripe: {"n", "s"}}}``) and call
    ``flightrec._rule_hot_stripe`` with the same
    ``BYTEPS_FLIGHT_SLOW_FACTOR`` threshold.  Returns the rule's
    evidence dict (a confirmed hot stripe) or None — also None when the
    byteps package isn't importable (this tool stays runnable on a box
    that only has the trace files)."""
    try:
        from byteps_tpu.core.flightrec import _rule_hot_stripe
    except ImportError:
        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        try:
            from byteps_tpu.core.flightrec import _rule_hot_stripe
        except ImportError:
            return None
    try:
        factor = float(os.environ.get("BYTEPS_FLIGHT_SLOW_FACTOR") or 3.0)
    except ValueError:
        factor = 3.0
    shim = type("_Rec", (), {"slow_factor": factor})()
    record = {
        "stripes": {
            s: {"n": busy_n.get(s, 0), "s": us / 1e6}
            for s, us in busy_us.items()
        }
    }
    return _rule_hot_stripe(shim, record)


def critical_path(merged: dict) -> dict:
    #: parent span id → {"extent": [min_ts, max_end] of worker RPC-stage
    #: events, "any": [min_ts, max_end] of ANY owning event}
    parents: Dict[str, dict] = {}
    #: parent span id → list of child dicts
    children: Dict[str, List[dict]] = {}
    traces = set()
    for ev in merged.get("traceEvents", []):
        if ev.get("cat") != "span" or ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        span, parent = args.get("span"), args.get("parent")
        if args.get("trace"):
            traces.add(args["trace"])
        # a server stage under a worker RPC.  A WORKER span can have a
        # parent too (a task submitted inside a tracing.span hangs under
        # the step's phase; a stage thread's service span under its
        # task): those stay RPC owners below, never someone's server time
        if parent and ev.get("name") in _SERVER_STAGES:
            children.setdefault(parent, []).append({
                "name": ev.get("name", ""),
                "ts": float(ev.get("ts", 0.0)),
                "dur": float(ev.get("dur", 0.0)),
                "engine": args.get("engine", "python"),
                # reducer lane (native key-striped engine): which stripe
                # thread executed this stage — explicit arg or the
                # stripe<N> track the drain filed it under; None = a
                # serve/control thread
                "stripe": _span_stripe(args, ev.get("tid")),
            })
            continue
        if span:
            p = parents.setdefault(span, {"extent": None, "any": None})
            t0 = float(ev.get("ts", 0.0))
            t1 = t0 + float(ev.get("dur", 0.0))
            which = "extent" if ev.get("name") in _RPC_STAGES else "any"
            cur = p[which]
            if cur is None:
                p[which] = [t0, t1]
            else:
                cur[0] = min(cur[0], t0)
                cur[1] = max(cur[1], t1)

    engines: Dict[str, dict] = {}
    for parent, kids in children.items():
        engine = kids[0]["engine"]
        agg = engines.setdefault(engine, {
            "rpcs": 0,
            "stages_us": {s: 0.0 for s in _SERVER_STAGES},
            "wire_us": 0.0,
            "wire_rpcs": 0,
            "stripe_sum_us": {},
            "stripe_busy_us": {},
            "stripe_busy_n": {},
        })
        agg["rpcs"] += 1
        srv0, srv1 = None, None
        for k in kids:
            if k["name"] in agg["stages_us"]:
                agg["stages_us"][k["name"]] += k["dur"]
                # per-reducer sum time (native striped engine): split by
                # the stripe lane that executed it, so a bad key hash
                # shows up as one runaway reducer in the attribution
                if k["name"] == "sum" and k.get("stripe") is not None:
                    per = agg["stripe_sum_us"]
                    per[str(k["stripe"])] = (
                        per.get(str(k["stripe"]), 0.0) + k["dur"]
                    )
            # lane OCCUPANCY: every stage a stripe thread executed, not
            # just sum — a reducer drowning in publish fan-out is just as
            # hot as one drowning in summation, and this is the feed the
            # hot-stripe trigger rule judges
            if k.get("stripe") is not None:
                lane = str(k["stripe"])
                agg["stripe_busy_us"][lane] = (
                    agg["stripe_busy_us"].get(lane, 0.0) + k["dur"]
                )
                agg["stripe_busy_n"][lane] = (
                    agg["stripe_busy_n"].get(lane, 0) + 1
                )
            t0, t1 = k["ts"], k["ts"] + k["dur"]
            srv0 = t0 if srv0 is None else min(srv0, t0)
            srv1 = t1 if srv1 is None else max(srv1, t1)
        # wire + client overhead: the worker-observed RPC extent minus
        # the server-side extent.  Same-host clocks (the demo recipe)
        # make this meaningful; cross-host skew shows up as negative
        # and is floored.
        anchor = parents.get(parent)
        extent = anchor and (anchor["extent"] or anchor["any"])
        if extent is not None and srv0 is not None:
            wire = max(0.0, (extent[1] - extent[0]) - (srv1 - srv0))
            agg["wire_us"] += wire
            agg["wire_rpcs"] += 1

    out: Dict[str, dict] = {}
    for engine, agg in engines.items():
        total = sum(agg["stages_us"].values()) + agg["wire_us"]
        stages = {}
        for s in _SERVER_STAGES:
            us = agg["stages_us"][s]
            stages["queue_wait" if s == "recv" else s] = {
                "total_s": us / 1e6,
                "mean_s": us / 1e6 / agg["rpcs"] if agg["rpcs"] else 0.0,
                "share": us / total if total else 0.0,
            }
        stages["wire"] = {
            "total_s": agg["wire_us"] / 1e6,
            "mean_s": (agg["wire_us"] / 1e6 / agg["wire_rpcs"]
                       if agg["wire_rpcs"] else 0.0),
            "share": agg["wire_us"] / total if total else 0.0,
        }
        out[engine] = {"rpcs": agg["rpcs"], "stages": stages}
        lanes = sorted(
            set(agg["stripe_sum_us"]) | set(agg["stripe_busy_us"]),
            key=int,
        )
        if lanes:
            sum_total = sum(agg["stripe_sum_us"].values())
            busy_total = sum(agg["stripe_busy_us"].values())
            out[engine]["reducers"] = {}
            for stripe in lanes:
                sum_us = agg["stripe_sum_us"].get(stripe, 0.0)
                busy = agg["stripe_busy_us"].get(stripe, 0.0)
                out[engine]["reducers"][stripe] = {
                    "sum_total_s": sum_us / 1e6,
                    "share_of_sum": sum_us / sum_total if sum_total else 0.0,
                    "busy_total_s": busy / 1e6,
                    # this lane's share of all reducer busy time — the
                    # tid-occupancy view a hot stripe dominates
                    "occupancy": busy / busy_total if busy_total else 0.0,
                }
            hot = _eval_hot_stripe(agg["stripe_busy_us"],
                                   agg["stripe_busy_n"])
            if hot is not None:
                out[engine]["hot_stripe"] = hot
    return {
        "traces": len(traces),
        "linked_rpcs": sum(e["rpcs"] for e in out.values()),
        "orphaned_spans": merged.get("otherData", {}).get("orphaned_spans", 0),
        "engines": out,
    }


def _print_attribution(attrib: dict) -> None:
    print(
        f"critical path: {attrib['linked_rpcs']} linked RPC(s) across "
        f"{attrib['traces']} trace(s)"
    )
    for engine, agg in sorted(attrib["engines"].items()):
        print(f"  [{engine}] {agg['rpcs']} rpcs")
        for stage, d in agg["stages"].items():
            if d["total_s"] == 0.0:
                continue
            print(
                f"    {stage:<11s} {d['total_s'] * 1e3:9.3f} ms total  "
                f"{d['mean_s'] * 1e6:9.1f} µs/rpc  {d['share'] * 100:5.1f}%"
            )
        for stripe, d in agg.get("reducers", {}).items():
            print(
                f"    reducer {stripe:<3s} {d['sum_total_s'] * 1e3:9.3f} ms "
                f"sum   {d['share_of_sum'] * 100:5.1f}% of sum  "
                f"{d['occupancy'] * 100:5.1f}% occupancy"
            )
        hot = agg.get("hot_stripe")
        if hot:
            print(
                f"    HOT STRIPE: reducer {hot['stripe']} holds "
                f"{hot['share'] * 100:.0f}% of lane time "
                f"({hot['sum_seconds'] * 1e3:.3f} ms vs sibling median "
                f"{hot['sibling_median'] * 1e3:.3f} ms) — the flight "
                "recorder's hot_stripe rule fires on this trace; see "
                "docs/fusion.md (BYTEPS_SERVER_STRIPES / key hash)"
            )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+",
                    help="trace dirs (searched recursively) or comm.json files")
    ap.add_argument("-o", "--output", default="merged_trace.json")
    ap.add_argument(
        "--critical-path", metavar="ATTRIB_JSON", default=None,
        help="also walk the merged flow graph and write a per-engine, "
        "per-stage step-time attribution (queue wait / sum / publish / "
        "reply / wire) to this path",
    )
    args = ap.parse_args(argv)
    files = find_trace_files(args.paths)
    if not files:
        print("no comm*.json trace files found", file=sys.stderr)
        return 1
    merged = merge(files)
    with open(args.output, "w") as f:
        json.dump(merged, f)
    meta = merged["otherData"]
    orphan_note = ""
    if meta["orphaned_spans"]:
        orphan_note = (
            f", {meta['orphaned_spans']} ORPHANED span(s) across "
            f"{meta['orphaned_parent_ids']} missing parent id(s) — a "
            "process's trace file is probably missing"
        )
    print(
        f"merged {len(files)} file(s) → {args.output}: "
        f"{len(merged['traceEvents'])} events, "
        f"{meta['linked_spans']} linked spans, "
        f"{meta['cross_process_children']} cross-process children"
        f"{orphan_note}"
    )
    if args.critical_path:
        attrib = critical_path(merged)
        with open(args.critical_path, "w") as f:
            json.dump(attrib, f, indent=2, sort_keys=True)
        _print_attribution(attrib)
        print(f"attribution → {args.critical_path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
