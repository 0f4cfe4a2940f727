#!/usr/bin/env python
"""bps_top — live terminal view of the byteps metrics plane.

Polls one or more Prometheus exposition endpoints (``BYTEPS_METRICS_PORT``
per process, or the scheduler's cluster aggregate) and renders the
signals docs/observability.md says to read first: RPC round-trip
percentiles, per-stage dwell, retry/dedupe/chaos counters (with per-server
breakdown when present), fusion pack quality, server sum/publish latency,
and push/pull throughput.  Counter RATES are computed between polls.

Usage:

    python tools/bps_top.py http://127.0.0.1:9102            # one endpoint
    python tools/bps_top.py http://w0:9102 http://sched:9102 # several
    python tools/bps_top.py --once http://127.0.0.1:9102     # single frame

No dependencies beyond the stdlib; parses the text exposition directly.
"""

from __future__ import annotations

import argparse
import re
import sys
import time
import urllib.request
from typing import Dict, Tuple

Sample = Dict[Tuple[str, str], float]  # (metric, label-string) → value


def scrape(url: str, timeout: float = 2.0) -> Sample:
    if "://" not in url:
        url = "http://" + url
    body = urllib.request.urlopen(url, timeout=timeout).read().decode()
    out: Sample = {}
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        try:
            series, value = line.rsplit(" ", 1)
            name, _, labels = series.partition("{")
            out[(name, "{" + labels if labels else "")] = float(value)
        except ValueError:
            continue
    return out


def _fmt_s(v: float) -> str:
    if v >= 1.0:
        return f"{v:7.2f}s "
    if v >= 1e-3:
        return f"{v * 1e3:7.2f}ms"
    return f"{v * 1e6:7.1f}µs"


def _histo_rows(s: Sample) -> list:
    """Latency rows with percentiles.  Native-engine families
    (``byteps_native_*``, fed through the histogram-provider seam) sort
    NEXT TO their Python twins — ``native_rpc_round_trip_seconds``
    lands beside ``rpc_round_trip_seconds`` tagged ``[native]`` — so a
    mixed-engine cluster reads in one screen."""
    rows = []
    fams = sorted(
        {n[: -len("_p50")] for (n, _lbl) in s if n.endswith("_p50")},
        # group by the engine-stripped name, python row first
        key=lambda f: (f.replace("byteps_native_", "byteps_"),
                       "native_" in f),
    )
    for fam in fams:
        disp = fam.replace("byteps_", "")
        if disp.startswith("native_"):
            disp = disp[len("native_"):] + " [native]"
        for lbl in sorted({l for (n, l) in s if n == fam + "_p50"}):
            count = s.get((fam + "_count", lbl), 0)
            rows.append((
                disp + (lbl or ""),
                int(count),
                s.get((fam + "_p50", lbl), 0.0),
                s.get((fam + "_p90", lbl), 0.0),
                s.get((fam + "_p99", lbl), 0.0),
            ))
    return rows


_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(vals: list) -> str:
    """Tiny unicode sparkline, scaled to the row's own max."""
    if not vals:
        return ""
    top = max(vals) or 1.0
    return "".join(
        _SPARK[min(len(_SPARK) - 1, int(v / top * (len(_SPARK) - 1)))]
        for v in vals
    )


def render(url: str, cur: Sample, prev: Sample, dt: float,
           hist: dict = None) -> str:
    lines = [f"── {url} " + "─" * max(0, 60 - len(url))]
    # gauges
    for (name, lbl), v in sorted(cur.items()):
        if name == "byteps_pushpull_mbps":
            lines.append(f"  push/pull throughput : {v:10.2f} MB/s")
    # flight-recorder steps row (docs/observability.md "Flight recorder
    # & doctor"): last-N step-time sparkline per node from the
    # node_step_seconds gauge history across polls, the scheduler-marked
    # straggler rank starred, and flight trigger counts per rule.  On a
    # node endpoint the gauge is unlabeled; on the scheduler aggregate
    # each node's series carries {role, rank}.
    straggler = cur.get(("byteps_cluster_straggler_rank", ""), -1.0)
    step_rows = []
    for (name, lbl), v in cur.items():
        if name != "byteps_node_step_seconds":
            continue
        series = None
        if hist is not None:
            series = hist.setdefault((name, lbl), [])
            series.append(v)
            del series[:-24]
        rm = re.search(r'rank="(-?\d+)"', lbl)
        role_m = re.search(r'role="([^"]*)"', lbl)
        who = (
            f"{role_m.group(1) if role_m else 'node'}"
            f"{rm.group(1) if rm else ''}"
        )
        star = (
            "*" if rm and (role_m is None or role_m.group(1) == "worker")
            and float(rm.group(1)) == straggler else " "
        )
        step_rows.append((who, star, v, list(series or [v])))
    if step_rows:
        lines.append(f"  {'steps (sparkline = last polls)':42s} {'last':>9s}")
        for who, star, v, series in sorted(step_rows):
            lines.append(
                f"  {who + star:10s} {_sparkline(series):24s}"
                f" {_fmt_s(v):>12s}"
            )
        trig = {}
        for (name, lbl), v in cur.items():
            if name == "byteps_flight_trigger_labeled_total":
                tm = re.search(r'rule="([^"]*)"', lbl)
                if tm:
                    trig[tm.group(1)] = trig.get(tm.group(1), 0) + int(v)
        if trig:
            cells = " ".join(f"{r}={n}" for r, n in sorted(trig.items()))
            lines.append(f"  flight triggers      : {cells}")
    # per-tenant row (docs/async.md): one line per JOB sharing the fleet
    # — last-N step-time sparkline (job_step_last_seconds gauge history
    # across polls) plus quota utilization, the delta rate of the job's
    # served bytes against its configured server_job_quota_mbps ceiling.
    tenant_rows = {}
    for (name, lbl), v in cur.items():
        if name != "byteps_job_step_last_seconds":
            continue
        jm = re.search(r'job="([^"]*)"', lbl)
        if not jm:
            continue
        series = None
        if hist is not None:
            series = hist.setdefault((name, lbl), [])
            series.append(v)
            del series[:-24]
        row = tenant_rows.setdefault(
            jm.group(1), {"last": 0.0, "series": [], "util": None}
        )
        row["last"] = max(row["last"], v)
        row["series"] = list(series or [v])
    quotas, rates = {}, {}
    for (name, lbl), v in cur.items():
        jm = re.search(r'job="([^"]*)"', lbl)
        if not jm:
            continue
        if name == "byteps_server_job_quota_mbps":
            # quotas are enforced PER SERVER (ROADMAP note), and the
            # aggregate carries one series per server rank — the fleet
            # ceiling the summed byte rate compares against is the SUM
            quotas[jm.group(1)] = quotas.get(jm.group(1), 0.0) + v
        elif name == "byteps_server_job_bytes_labeled_total" and dt > 0:
            d = v - prev.get((name, lbl), 0.0)
            rates[jm.group(1)] = rates.get(jm.group(1), 0.0) + max(0.0, d) / dt
    for job, mbps in quotas.items():
        row = tenant_rows.setdefault(
            job, {"last": 0.0, "series": [], "util": None}
        )
        rate = rates.get(job, 0.0) / 1e6  # bytes/s → MB/s
        row["util"] = (rate, mbps)
    if tenant_rows:
        lines.append(
            f"  {'tenants (job: steps | quota use)':42s} {'last':>9s}"
        )
        for job in sorted(tenant_rows, key=lambda j: int(j) if j.isdigit() else 0):
            row = tenant_rows[job]
            cell = f"  job {job:<6s} {_sparkline(row['series']):24s}"
            if row["last"]:
                cell += f" {_fmt_s(row['last']):>12s}"
            if row["util"] is not None:
                rate, mbps = row["util"]
                pct = 100.0 * rate / mbps if mbps > 0 else 0.0
                cell += f"  quota {rate:6.2f}/{mbps:g} MB/s ({pct:3.0f}%)"
            lines.append(cell)
    # reducer backlog of the key-striped native engine, one cell per
    # stripe — a persistently deep cell while its siblings sit at 0 is
    # the hot-stripe signature (docs/fusion.md).  Sorted numerically (s2
    # before s10); the series also carry a `server` instance label, so
    # cells are prefixed with it when more than one server shares the
    # endpoint (scaling_bench threads mode).
    depths = []
    for (name, lbl), v in cur.items():
        if name != "byteps_native_stripe_queue_depth":
            continue
        sm = re.search(r'stripe="(\d+)"', lbl)
        srv = re.search(r'server="([^"]*)"', lbl)
        depths.append((srv.group(1) if srv else "",
                       int(sm.group(1)) if sm else -1, v))
    if depths:
        many = len({s for s, _, _ in depths}) > 1
        cells = " ".join(
            (f"{srv}:" if many else "") + f"s{i}={int(v)}"
            for srv, i, v in sorted(depths)
        )
        lines.append(f"  stripe queue depth   : {cells}")
    # control plane (docs/robustness.md "Control-plane recovery"): the
    # scheduler incarnation the aggregate belongs to, how many expected
    # nodes have not yet re-registered with it (nonzero only during a
    # rebirth's rejoin window), and how many nodes report themselves in
    # control_plane_degraded mode (scheduler link down, data plane
    # still training on the last book)
    inc = rejoining = None
    degraded = 0
    for (name, lbl), v in cur.items():
        if name == "byteps_cluster_sched_incarnation":
            inc = int(v)
        elif name == "byteps_cluster_rejoining_nodes":
            rejoining = int(v)
        elif name == "byteps_control_plane_degraded" and v:
            degraded += 1
    if inc is not None or rejoining or degraded:
        lines.append(
            "  control plane        : "
            + (f"incarnation {inc}" if inc is not None else "incarnation ?")
            + f" | rejoining {rejoining or 0} | degraded {degraded}"
        )
    # elastic resharding ownership (docs/robustness.md "migration flow"):
    # the scheduler aggregate carries the cluster map epoch plus each
    # server's heartbeat-shipped owned-key count and adopted epoch, so a
    # migration is watchable as keys draining from one rank's cell into
    # another's; a rank still on an older epoch is marked with '*'.
    map_epoch = None
    owned: Dict[int, float] = {}
    srv_epoch: Dict[int, float] = {}
    for (name, lbl), v in cur.items():
        if name == "byteps_cluster_map_epoch":
            map_epoch = int(v)
        elif name in ("byteps_server_owned_keys", "byteps_server_map_epoch"):
            rm = re.search(r'rank="(-?\d+)"', lbl)
            if rm is None:
                continue
            dst = owned if name.endswith("owned_keys") else srv_epoch
            dst[int(rm.group(1))] = v
    if map_epoch is not None or owned:
        cells = " ".join(
            f"r{r}={int(v)}"
            + ("*" if map_epoch is not None
               and srv_epoch.get(r, map_epoch) < map_epoch else "")
            for r, v in sorted(owned.items())
        )
        head = f"epoch {map_epoch}" if map_epoch is not None else "epoch ?"
        lines.append(
            f"  ownership map        : {head}"
            + (f" | owned keys {cells}" if cells else "")
        )
    # adaptive control plane (docs/autotune.md): the tuning epoch the
    # fleet runs under, per-rule action/rollback totals, and how many
    # keys the fleet codec consensus turned off on this node.  Only the
    # scheduler aggregate carries the epoch + tune counters; a node
    # endpoint may still show its tune_codec_off slice.
    tune_epoch = None
    tune_acts: Dict[str, int] = {}
    tune_rbs: Dict[str, int] = {}
    codec_off_keys = 0
    for (name, lbl), v in cur.items():
        if name == "byteps_cluster_tuning_epoch":
            tune_epoch = int(v)
        elif name == "byteps_tune_action_labeled_total":
            rm = re.search(r'rule="([^"]*)"', lbl)
            if rm:
                tune_acts[rm.group(1)] = tune_acts.get(rm.group(1), 0) + int(v)
        elif name == "byteps_tune_rollback_labeled_total":
            rm = re.search(r'rule="([^"]*)"', lbl)
            if rm:
                tune_rbs[rm.group(1)] = tune_rbs.get(rm.group(1), 0) + int(v)
        elif name == "byteps_tune_codec_off_total":
            codec_off_keys += int(v)
    if tune_epoch is not None or tune_acts or tune_rbs:
        cells = " ".join(
            f"{r}={n}" for r, n in sorted(tune_acts.items())
        ) or "none"
        rb_total = sum(tune_rbs.values())
        line = (
            "  autotune             : "
            + (f"epoch {tune_epoch}" if tune_epoch is not None else "epoch ?")
            + f" | actions {cells} | rollbacks {rb_total}"
        )
        if codec_off_keys:
            line += f" | fleet codec-off keys {codec_off_keys}"
        lines.append(line)
    # compressed wire path (docs/gradient-compression.md): cumulative
    # wire bytes the codecs removed vs shipped, and how many keys the
    # adaptive policy (BYTEPS_COMPRESSION_AUTO) turned OFF because their
    # observed ratio made compression a loss
    saved = tx = auto_off = 0
    for (name, lbl), v in cur.items():
        if lbl:
            continue  # flat totals only (labeled twins double-count)
        if name == "byteps_wire_bytes_saved_total":
            saved = int(v)
        elif name == "byteps_wire_tx_bytes_total":
            tx = int(v)
        elif name == "byteps_compression_auto_off_total":
            auto_off = int(v)
    if saved or auto_off:
        pct = 100.0 * saved / max(1, saved + tx)
        lines.append(
            f"  compression          : saved {saved / 1e6:.1f} MB on wire"
            f" ({pct:.0f}% of push bytes) | auto-disabled keys {auto_off}"
        )
    # latency families
    rows = _histo_rows(cur)
    if rows:
        lines.append(f"  {'latency':42s} {'count':>8s} {'p50':>9s} {'p90':>9s} {'p99':>9s}")
        for fam, count, p50, p90, p99 in rows:
            lines.append(
                f"  {fam:42s} {count:8d} {_fmt_s(p50)} {_fmt_s(p90)} {_fmt_s(p99)}"
            )
    # counters + rates (totals only; labeled series shown when nonzero)
    counter_rows = []
    for (name, lbl), v in sorted(cur.items()):
        if not name.endswith("_total"):
            continue
        rate = ""
        if dt > 0 and (name, lbl) in prev:
            r = (v - prev[(name, lbl)]) / dt
            if r:
                rate = f"{r:9.1f}/s"
        if v or rate:
            counter_rows.append(
                f"  {name.replace('byteps_', '')[: -len('_total')] + (lbl or ''):42s}"
                f" {int(v):10d} {rate}"
            )
    if counter_rows:
        lines.append(f"  {'counter':42s} {'total':>10s}   rate")
        lines.extend(counter_rows)
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("urls", nargs="+", help="metrics endpoints to poll")
    ap.add_argument("-i", "--interval", type=float, default=2.0)
    ap.add_argument("--once", action="store_true",
                    help="print one frame and exit (no screen clearing)")
    args = ap.parse_args(argv)
    prev: Dict[str, Sample] = {}
    hist: Dict[str, dict] = {}
    t_prev = time.monotonic()
    while True:
        frames = []
        now = time.monotonic()
        dt = now - t_prev
        for url in args.urls:
            try:
                cur = scrape(url)
            except Exception as e:  # noqa: BLE001 — a dead peer is a display fact
                frames.append(f"── {url}\n  unreachable: {e}")
                continue
            frames.append(render(
                url, cur, prev.get(url, {}), dt,
                hist=hist.setdefault(url, {}),
            ))
            prev[url] = cur
        t_prev = now
        out = "\n\n".join(frames)
        if args.once:
            print(out)
            return 0
        sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
        print(f"bps_top — {time.strftime('%H:%M:%S')} "
              f"(every {args.interval:g}s, ctrl-c to quit)\n")
        print(out)
        try:
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


if __name__ == "__main__":
    raise SystemExit(main())
