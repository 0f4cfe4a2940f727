"""Observability plane: distributed tracing + metrics registry
(docs/observability.md).

Layers under test:

- histogram bucket/percentile math, labeled counters (flat back-compat),
  concurrent bump/observe vs. snapshot/render
- Prometheus text exposition format + the HTTP scrape endpoint
- tracer multi-window flush (the old one-shot latch dropped window 2)
- wire propagation of span ids: optional-on-decode header field, a
  retried frame keeps its span, fused frames carry pack + member spans,
  server dedupe annotation lands on the right span
- scheduler-side cluster aggregate fed by heartbeat-piggybacked deltas
- cross-process trace merge (tools/trace_merge.py) on a fake cluster
  with fusion + chaos-injected retries
- the metrics catalog guard (tools/check_metrics_doc.py)
- native-engine interop: traced and untraced frames on one uds/shm
  stream stay framed (old↔new frame interop)
- native observability parity (ISSUE 6): the C++ engine's child spans
  (recv→sum→publish→reply, dedupe-annotated, fused members parented on
  trailer ids) drained into the process tracer; the histogram-provider
  seam merging native_* histograms into snapshot/Prometheus/deltas;
  trace_merge orphan accounting + --critical-path attribution
"""

import json
import os
import struct
import sys
import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.common.types import (
    DataType,
    RequestType,
    get_command_type,
)
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.comm.transport import (
    Message,
    Op,
    connect,
    decode_fused_push,
    decode_fused_spans,
    encode_fused_push,
    recv_message,
    send_message,
)
from byteps_tpu.core.telemetry import (
    COUNT_BUCKETS,
    Histogram,
    MetricsRegistry,
    RobustnessCounters,
    counters,
    metrics,
    serve_metrics,
)
from byteps_tpu.core.tracing import Tracer
from byteps_tpu.server.server import PSServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_metrics():
    counters().reset()
    metrics().reset()
    yield
    counters().reset()
    metrics().reset()


class TestHistogram:
    def test_bucket_placement_le_semantics(self):
        h = Histogram("t", buckets=(0.001, 0.01, 0.1))
        for v in (0.0005, 0.001, 0.005, 0.05, 5.0):
            h.observe(v)
        snap = h.snapshot()
        # cumulative: le=0.001 counts 0.0005 AND the exact 0.001
        assert snap["buckets"][0] == (0.001, 2)
        assert snap["buckets"][1] == (0.01, 3)
        assert snap["buckets"][2] == (0.1, 4)
        assert snap["buckets"][3] == (float("inf"), 5)
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(0.0005 + 0.001 + 0.005 + 0.05 + 5.0)

    def test_percentiles_interpolate_and_clamp(self):
        h = Histogram("t", buckets=(0.001, 0.01, 0.1, 1.0))
        for _ in range(90):
            h.observe(0.005)  # lands in (0.001, 0.01]
        for _ in range(10):
            h.observe(0.5)    # lands in (0.1, 1.0]
        p50 = h.percentile(0.50)
        assert 0.001 < p50 <= 0.01
        p99 = h.percentile(0.99)
        assert 0.1 < p99 <= 1.0
        # monotone in q
        assert h.percentile(0.1) <= p50 <= h.percentile(0.95) <= 1.0

    def test_empty_and_overflow(self):
        h = Histogram("t", buckets=(0.001, 0.01))
        assert h.percentile(0.99) == 0.0
        h.observe(100.0)  # +Inf bucket
        # past the last finite bound: report that bound (honest limit)
        assert h.percentile(0.99) == 0.01
        assert h.snapshot()["buckets"][-1] == (float("inf"), 1)

    def test_merge_counts(self):
        a = Histogram("t", buckets=(1.0, 2.0))
        b = Histogram("t", buckets=(1.0, 2.0))
        a.observe(0.5)
        b.observe(1.5)
        b.observe(9.0)
        snap_b = b.snapshot()
        a.merge_counts(b.raw_counts(), snap_b["sum"], snap_b["count"])
        merged = a.snapshot()
        assert merged["count"] == 3
        assert merged["buckets"][0] == (1.0, 1)
        assert merged["buckets"][1] == (2.0, 2)


class TestLabeledCounters:
    def test_flat_totals_include_labeled_bumps(self):
        c = RobustnessCounters()
        c.bump("rpc_retry", 2, labels={"server": "0"})
        c.bump("rpc_retry", 3, labels={"server": "1"})
        c.bump("rpc_retry")  # unlabeled
        assert c.snapshot() == {"rpc_retry": 6}  # back-compat: flat ints
        labeled = c.snapshot_labeled()["rpc_retry"]
        assert labeled[(("server", "0"),)] == 2
        assert labeled[(("server", "1"),)] == 3

    def test_get_robustness_counters_stays_flat(self):
        import byteps_tpu as bps

        counters().bump("conn_revive", labels={"server": "2"})
        snap = bps.get_robustness_counters()
        assert snap["conn_revive"] == 1
        assert all(isinstance(v, int) for v in snap.values())
        # the dimension is reachable through the metrics surface
        m = bps.get_metrics()
        assert m["counters_labeled"]["conn_revive"] == {'{server="2"}': 1}

    def test_reset_clears_labels(self):
        c = RobustnessCounters()
        c.bump("x", labels={"a": "b"})
        c.reset()
        assert c.snapshot() == {}
        assert c.snapshot_labeled() == {}


class TestConcurrency:
    def test_concurrent_bump_observe_snapshot(self):
        """N writer threads race the snapshot/render readers; totals must
        come out exact and no render may throw mid-mutation."""
        reg = MetricsRegistry()
        N_THREADS, N_OPS = 8, 500
        stop = threading.Event()
        render_errors = []

        def writer(tid):
            for i in range(N_OPS):
                reg.counters.bump("wire_rpc", labels={"server": str(tid % 3)})
                reg.observe("rpc_round_trip_seconds", 0.001 * (i % 7 + 1))

        def reader():
            while not stop.is_set():
                try:
                    reg.snapshot()
                    reg.render_prometheus()
                    reg.counters.snapshot()
                except Exception as e:  # noqa: BLE001
                    render_errors.append(e)
                    return

        readers = [threading.Thread(target=reader) for _ in range(2)]
        writers = [
            threading.Thread(target=writer, args=(t,)) for t in range(N_THREADS)
        ]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join()
        stop.set()
        for t in readers:
            t.join()
        assert not render_errors
        assert reg.counters.get("wire_rpc") == N_THREADS * N_OPS
        h = reg.histogram("rpc_round_trip_seconds")
        assert h.snapshot()["count"] == N_THREADS * N_OPS


class TestPrometheusExposition:
    def _registry(self):
        reg = MetricsRegistry()
        reg.counters.bump("rpc_retry", 4, labels={"server": "1"})
        reg.counters.bump("push_dedup")
        reg.gauge_set("pushpull_mbps", 42.0)
        for v in (0.002, 0.004, 0.03):
            reg.observe("rpc_round_trip_seconds", v)
        reg.observe("stage_dwell_seconds", 0.01, labels={"stage": "PUSH"})
        return reg

    def test_labeled_gauges_family_and_remove(self):
        """Gauges accept a label set (one TYPE line per family, one
        series per label combination) and gauge_remove drops exactly
        one series — the surface the per-stripe backlog feed uses."""
        reg = self._registry()
        reg.gauge_set("native_stripe_queue_depth", 3, labels={"stripe": "0"})
        reg.gauge_fn("native_stripe_queue_depth", lambda: 7.0,
                     labels={"stripe": "1"})
        text = reg.render_prometheus()
        assert text.count(
            "# TYPE byteps_native_stripe_queue_depth gauge") == 1
        assert 'byteps_native_stripe_queue_depth{stripe="0"} 3.0' in text
        assert 'byteps_native_stripe_queue_depth{stripe="1"} 7.0' in text
        gauges = reg.snapshot()["gauges"]
        assert gauges['native_stripe_queue_depth{stripe="1"}'] == 7.0
        assert gauges["pushpull_mbps"] == 42.0  # unlabeled keys unchanged
        reg.gauge_remove("native_stripe_queue_depth", labels={"stripe": "1"})
        text = reg.render_prometheus()
        assert 'byteps_native_stripe_queue_depth{stripe="0"} 3.0' in text
        assert 'stripe="1"' not in text

    def test_text_format_valid(self):
        import re

        text = self._registry().render_prometheus()
        line_re = re.compile(
            r"^(# (TYPE|HELP) .*|[a-zA-Z_:][a-zA-Z0-9_:]*"
            r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
            r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? [0-9.+-einfEINF]+)$"
        )
        for line in text.strip().splitlines():
            assert line_re.match(line), f"invalid exposition line: {line!r}"
        assert "byteps_rpc_retry_total 4" in text
        # labeled breakdown is a SEPARATE family: the flat total already
        # includes labeled bumps, so one family would double-count in
        # sum() queries
        assert 'byteps_rpc_retry_labeled_total{server="1"} 4' in text
        assert 'byteps_rpc_retry_total{server="1"}' not in text
        assert "# TYPE byteps_rpc_round_trip_seconds histogram" in text
        assert 'byteps_rpc_round_trip_seconds_bucket{le="+Inf"} 3' in text
        assert "byteps_rpc_round_trip_seconds_count 3" in text
        assert "byteps_rpc_round_trip_seconds_p99" in text
        assert 'byteps_stage_dwell_seconds_count{stage="PUSH"} 1' in text

    def test_bucket_counts_monotone(self):
        text = self._registry().render_prometheus()
        cums = [
            float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("byteps_rpc_round_trip_seconds_bucket")
        ]
        assert cums == sorted(cums) and cums[-1] == 3

    def test_http_endpoint_scrapes(self):
        import urllib.request

        reg = self._registry()
        srv = serve_metrics(0, reg.render_prometheus, host="127.0.0.1")
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5
            )
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            body = resp.read().decode()
            assert "byteps_rpc_round_trip_seconds_p50" in body
        finally:
            srv.close()

    def test_port_conflict_falls_back_ephemeral(self):
        reg = self._registry()
        first = serve_metrics(0, reg.render_prometheus, host="127.0.0.1")
        try:
            second = serve_metrics(
                first.port, reg.render_prometheus, host="127.0.0.1"
            )
            try:
                assert second.port != first.port and second.port > 0
            finally:
                second.close()
        finally:
            first.close()


class TestSchedulerAggregate:
    def test_delta_merge_preserves_totals_and_attribution(self):
        node = MetricsRegistry()
        agg = MetricsRegistry()
        node.counters.bump("rpc_retry", 2, labels={"server": "0"})
        node.observe("rpc_round_trip_seconds", 0.005)
        agg.merge_delta(node.delta_snapshot(),
                        labels={"role": "worker", "rank": "1"})
        # second delta: only the increment travels
        node.counters.bump("rpc_retry")
        d2 = node.delta_snapshot()
        assert d2["c"] == {"rpc_retry": 1}
        agg.merge_delta(d2, labels={"role": "worker", "rank": "1"})
        assert agg.counters.get("rpc_retry") == 3  # no double count
        assert agg.histogram("rpc_round_trip_seconds").snapshot()["count"] == 1
        labeled = agg.counters.snapshot_labeled()["rpc_retry"]
        assert labeled[(("rank", "1"), ("role", "worker"))] == 3

    def test_empty_delta_is_empty(self):
        node = MetricsRegistry()
        node.counters.bump("x")
        node.delta_snapshot()
        assert node.delta_snapshot() == {}

    def test_malformed_delta_ignored(self):
        agg = MetricsRegistry()
        agg.merge_delta({"c": {"ok": 1}, "h": [{"bogus": True}]})
        assert agg.counters.get("ok") == 1

    def test_requeued_delta_rides_next_beat(self):
        """A delta whose heartbeat send failed must not lose increments:
        requeue_delta folds it into the next snapshot."""
        node = MetricsRegistry()
        node.counters.bump("rpc_retry", 2, labels={"server": "0"})
        node.observe("rpc_round_trip_seconds", 0.01)
        d1 = node.delta_snapshot()
        node.requeue_delta(d1)  # the send "failed"
        node.counters.bump("rpc_retry")  # fresh increment meanwhile
        d2 = node.delta_snapshot()
        assert d2["c"]["rpc_retry"] == 3  # requeued 2 + fresh 1
        assert sum(r["n"] for r in d2["h"]) == 1
        agg = MetricsRegistry()
        agg.merge_delta(d2)
        assert agg.counters.get("rpc_retry") == 3
        assert node.delta_snapshot() == {}  # nothing left behind


class TestTracerWindows:
    def test_multiple_flush_windows(self, tmp_path):
        """The one-shot ``_flushed`` latch is gone: each flush writes the
        CURRENT window and clears the buffer, so profiler.trace() can
        capture more than one window per process."""
        tr = Tracer(enabled=True, start_step=0, end_step=99,
                    trace_dir=str(tmp_path / "w1"), local_rank=0)
        tr.record("t", "PUSH", 1.0, 0.5, step=1)
        p1 = tr.flush()
        assert p1 and os.path.exists(p1)
        # window 2 into a different dir (profiler.trace sets trace_dir)
        tr.trace_dir = str(tmp_path / "w2")
        tr.record("t", "PULL", 2.0, 0.5, step=2)
        p2 = tr.flush()
        assert p2 and os.path.exists(p2) and p2 != p1
        ev2 = json.load(open(p2))["traceEvents"]
        assert [e["name"] for e in ev2] == ["PULL"]  # window 2 only
        # empty window: no write, previous file untouched
        assert tr.flush() == ""
        assert json.load(open(p2))["traceEvents"]

    def test_flush_never_clobbers_earlier_window_in_same_dir(self, tmp_path):
        """A shutdown flush landing in a directory a profiler window
        already used must write comm.<n>.json, not overwrite the
        captured window (trace_merge globs comm*.json, so both merge)."""
        tr = Tracer(enabled=True, trace_dir=str(tmp_path), local_rank=0)
        tr.record_span("trk", "PUSH", 1.0, 0.1, {"span": "a"})
        p1 = tr.flush()
        tr.record_span("trk", "PULL", 2.0, 0.1, {"span": "b"})
        p2 = tr.flush()
        assert p1.endswith("comm.json") and p2.endswith("comm.2.json")
        assert [e["name"] for e in json.load(open(p1))["traceEvents"]] == ["PUSH"]
        assert [e["name"] for e in json.load(open(p2))["traceEvents"]] == ["PULL"]
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import trace_merge

            files = trace_merge.find_trace_files([str(tmp_path)])
            assert set(files) == {p1, p2}
        finally:
            sys.path.remove(os.path.join(REPO, "tools"))

    def test_event_buffer_capped(self, tmp_path):
        tr = Tracer(enabled=True, trace_dir=str(tmp_path), local_rank=0)
        tr.MAX_EVENTS = 10
        for i in range(25):
            tr.record_span("trk", f"s{i}", 1.0, 0.1)
        assert tr.pending_events() == 10
        path = tr.flush()
        payload = json.load(open(path))
        assert len(payload["traceEvents"]) == 10
        assert payload["otherData"]["dropped_events"] == 15

    def test_spans_gated_separately(self, tmp_path):
        tr = Tracer(enabled=True, trace_dir=str(tmp_path), local_rank=0,
                    spans_enabled=False)
        tr.record_span("trk", "PUSH", 1.0, 0.1, {"span": "ab"})
        tr.record_instant("trk", "chaos_drop")
        assert tr.pending_events() == 0
        tr.spans_enabled = True
        tr.record_span("trk", "PUSH", 1.0, 0.1, {"span": "ab"})
        assert tr.pending_events() == 1


class TestWirePropagation:
    def test_trace_context_optional_on_decode(self):
        """New frames (with context) and old frames (without) cross one
        stream back-to-back; both decode, status comes back clean."""
        import socket

        a, b = socket.socketpair()
        try:
            send_message(a, Message(Op.PUSH, key=5, payload=b"pp", seq=1,
                                    flags=3, trace=(0x1234, 0x5678)))
            send_message(a, Message(Op.PUSH, key=6, payload=b"qq", seq=2))
            m1 = recv_message(b)
            m2 = recv_message(b)
            assert m1.trace == (0x1234, 0x5678)
            assert m1.status == 0 and m1.flags == 3 and m1.payload == b"pp"
            assert m2.trace is None and m2.payload == b"qq"
        finally:
            a.close()
            b.close()

    def test_retried_frame_keeps_its_span(self):
        """Client-level: the first send attempt dies, the retry re-sends
        — and BOTH wire frames carry the identical (trace, span) pair."""
        from byteps_tpu.comm.ps_client import PSClient

        cfg = Config(num_worker=1, num_server=1, rpc_retries=2,
                     rpc_backoff_s=0.01)
        client = PSClient(cfg)
        client.rank = 0
        sent = []
        done = threading.Event()

        class FakeConn:
            dead = False

            def __init__(self):
                self._cbs = {}
                self._seq = 0
                self.fail_next = True

            def alloc_seq(self, cb, sink=None):
                seq = self._seq
                self._seq += 1
                self._cbs[seq] = cb
                return seq

            def send_msg(self, msg):
                sent.append(msg)
                if self.fail_next:
                    self.fail_next = False
                    raise ConnectionError("injected")
                # answer asynchronously like a real recv lane
                cb = self._cbs.pop(msg.seq)
                threading.Thread(
                    target=cb, args=(Message(Op.PUSH, key=msg.key,
                                             seq=msg.seq),),
                    daemon=True,
                ).start()

            def pop_cb(self, seq):
                return self._cbs.pop(seq, None)

            def close_all(self):
                pass

        conn = FakeConn()
        client._servers = [conn]
        client._server_addrs = [("x", 0)]
        try:
            client.push(
                key=0, payload=b"\x00" * 8, dtype_id=0, version=1,
                cb=done.set, trace=(777, 888),
            )
            assert done.wait(5.0), "push never completed through the retry"
            assert len(sent) == 2, [m.seq for m in sent]
            assert sent[0].trace == (777, 888)
            assert sent[1].trace == (777, 888)
            assert counters().get("rpc_retry") == 1
            labeled = counters().snapshot_labeled()
            assert labeled["rpc_retry"][(("server", "0"),)] == 1
        finally:
            client.close()

    def test_fused_frame_carries_pack_and_member_spans(self):
        members = [(1, 7, 1, b"aaaa"), (2, 7, 1, b"bb")]
        body = encode_fused_push(members, span_ids=[0xA1, 0xB2])
        assert decode_fused_push(body) == members  # old decoder: unchanged
        assert decode_fused_spans(body) == [0xA1, 0xB2]
        assert decode_fused_spans(encode_fused_push(members)) is None
        with pytest.raises(ValueError, match="match members"):
            encode_fused_push(members, span_ids=[0xA1])


class TestServerChildSpans:
    def _server(self, tmp_path, num_worker=1):
        cfg = Config(num_worker=num_worker, trace_on=True,
                     trace_dir=str(tmp_path))
        return PSServer(cfg)

    def _init_key(self, srv, conn, lock, key, n=4, flags=1):
        srv._handle_init(
            Message(Op.INIT, key=key, seq=0, flags=flags,
                    payload=struct.pack("!QI", n, int(DataType.FLOAT32))),
            conn, lock,
        )

    def test_push_children_join_worker_span_and_dedupe_annotates(self, tmp_path):
        """recv→sum→publish→reply children share the worker's trace id
        with parent = the wire span id; a REPLAYED push (same version)
        yields a sum span annotated dedupe=True on the same parent."""
        import socket

        srv = self._server(tmp_path)
        a, b = socket.socketpair()
        lock = threading.Lock()
        try:
            self._init_key(srv, a, lock, key=9)
            assert recv_message(b).op == Op.INIT
            cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                   int(DataType.FLOAT32))
            payload = np.ones(4, dtype=np.float32).tobytes()
            msg = Message(Op.PUSH, key=9, seq=1, flags=1, cmd=cmd,
                          version=1, payload=payload,
                          trace=(0xCAFE, 0xD00D))
            srv._handle_push(msg, a, lock, t_enq=time.time())
            assert recv_message(b).op == Op.PUSH
            # replay (retry after lost ack): ack-only + dedupe annotation
            srv._handle_push(
                Message(Op.PUSH, key=9, seq=2, flags=1, cmd=cmd, version=1,
                        payload=payload, trace=(0xCAFE, 0xD00D)),
                a, lock, t_enq=time.time(),
            )
            assert recv_message(b).op == Op.PUSH
            events = [e for e in srv.tracer._events if e.get("cat") == "span"]
            assert {e["name"] for e in events} >= {"recv", "sum", "publish",
                                                  "reply"}
            sums = [e for e in events if e["name"] == "sum"]
            assert len(sums) == 2
            for e in sums:
                assert e["args"]["trace"] == format(0xCAFE, "x")
                assert e["args"]["parent"] == format(0xD00D, "x")
            assert [e["args"]["dedupe"] for e in sums] == [False, True]
            assert counters().get("push_dedup") == 1
            assert metrics().histogram("server_sum_seconds").snapshot()["count"] == 2
            assert metrics().histogram("server_publish_seconds").snapshot()["count"] == 1
        finally:
            a.close()
            b.close()
            srv.stop()

    def test_fused_members_parent_on_member_spans(self, tmp_path):
        import socket

        srv = self._server(tmp_path)
        a, b = socket.socketpair()
        lock = threading.Lock()
        KEY_A, KEY_B = 41, 42
        cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                               int(DataType.FLOAT32))
        try:
            for key in (KEY_A, KEY_B):
                self._init_key(srv, a, lock, key=key)
                assert recv_message(b).op == Op.INIT
            frame = encode_fused_push(
                [(KEY_A, cmd, 1, np.ones(4, np.float32).tobytes()),
                 (KEY_B, cmd, 1, np.full(4, 2.0, np.float32).tobytes())],
                span_ids=[0x111, 0x222],
            )
            msg = Message(Op.FUSED, key=KEY_A, seq=5, flags=1, cmd=2,
                          payload=frame, trace=(0xFACE, 0xF00))
            srv._handle_fused(msg, a, lock, t_enq=time.time())
            reply = recv_message(b)
            assert reply.op == Op.FUSED
            events = [e for e in srv.tracer._events if e.get("cat") == "span"]
            sums = [e for e in events if e["name"] == "sum"]
            assert {e["args"]["parent"] for e in sums} == {
                format(0x111, "x"), format(0x222, "x")
            }
            assert all(e["args"]["fused"] for e in sums)
            assert all(e["args"]["trace"] == format(0xFACE, "x") for e in sums)
            recvs = [e for e in events if e["name"] == "recv"]
            assert recvs and recvs[0]["args"]["parent"] == format(0xF00, "x")
        finally:
            a.close()
            b.close()
            srv.stop()


class TestMetricsCatalog:
    def test_metrics_catalog_complete(self):
        """tools/check_metrics_doc.py: every emitted metric name must be
        in the docs/observability.md catalog — the tier-1 rot guard."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import check_metrics_doc

            assert check_metrics_doc.main(["--repo", REPO]) == 0
        finally:
            sys.path.remove(os.path.join(REPO, "tools"))

    def test_env_catalog_complete(self):
        """tools/check_env_doc.py: every BYTEPS_* env knob the code
        reads must be documented in docs/env.md — same rot guard, for
        the configuration surface."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import check_env_doc

            assert check_env_doc.main(["--repo", REPO]) == 0
        finally:
            sys.path.remove(os.path.join(REPO, "tools"))

    def test_doctor_rules_complete(self):
        """tools/check_doctor_rules.py: every bps_doctor rule names a
        real docs/troubleshooting.md anchor and is cited by the field
        guide, and every field-guide row names a rule (or carries an
        explicit no-rule waiver) — the doc/rule rot guard for the
        diagnosis engine (docs/observability.md "Flight recorder &
        doctor")."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import check_doctor_rules

            assert check_doctor_rules.main(["--repo", REPO]) == 0
        finally:
            sys.path.remove(os.path.join(REPO, "tools"))

    @pytest.mark.parametrize("check", ["missing_paths", "stale_citations"])
    def test_the_record_names_only_what_exists(self, check):
        """tools/check_doc_paths.py: every repo path or artifact that
        README.md, PERF.md, docs/*.md and examples/README.md name exists
        (``missing_paths``), and no file under byteps_tpu/, tools/, tests/
        cites a record that was deleted (``stale_citations``)."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import check_doc_paths

            assert getattr(check_doc_paths, check)(REPO) == []
        finally:
            sys.path.remove(os.path.join(REPO, "tools"))


@pytest.fixture
def observed_cluster(monkeypatch, tmp_path):
    """1 worker / 1 server, tracing + fusion + seeded chaos drops +
    fast heartbeats: the in-process version of the docs/observability.md
    demo recipe."""
    monkeypatch.setenv("BYTEPS_TRACE_ON", "1")
    monkeypatch.setenv("BYTEPS_TRACE_START_STEP", "0")
    monkeypatch.setenv("BYTEPS_TRACE_END_STEP", "999")
    monkeypatch.setenv("BYTEPS_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("BYTEPS_FUSION_THRESHOLD", "16384")
    monkeypatch.setenv("BYTEPS_FUSION_CYCLE_MS", "2")
    monkeypatch.setenv("BYTEPS_VAN", "chaos:tcp")
    monkeypatch.setenv("BYTEPS_CHAOS_SEED", "4242")
    monkeypatch.setenv("BYTEPS_CHAOS_DROP", "0.05")
    monkeypatch.setenv("BYTEPS_RPC_DEADLINE_S", "0.3")
    monkeypatch.setenv("BYTEPS_INIT_DEADLINE_S", "0.5")
    monkeypatch.setenv("BYTEPS_RPC_RETRIES", "6")
    monkeypatch.setenv("BYTEPS_RPC_BACKOFF_S", "0.05")
    monkeypatch.setenv("BYTEPS_CONNECT_RETRY_S", "0.2")
    monkeypatch.setenv("BYTEPS_DEGRADED_STEP_RETRIES", "3")
    monkeypatch.setenv("BYTEPS_HEARTBEAT_INTERVAL", "0.2")
    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    srv = PSServer(Config.from_env())
    threading.Thread(target=srv.start, daemon=True).start()
    yield {"scheduler": sched, "server": srv, "trace_dir": tmp_path}
    srv.stop()
    sched.stop()


class TestClusterObservability:
    def test_merged_trace_joins_fused_and_retried_spans(self, observed_cluster, monkeypatch):
        """The acceptance shape, in-process: run fused traffic under
        seeded chaos, merge worker + server trace files, and assert (a)
        server child spans share worker trace ids, (b) at least one
        Op.FUSED pack span exists, (c) at least one chaos fault was
        tagged on an owning span of a frame that was then retried."""
        import byteps_tpu as bps
        from byteps_tpu.core import tracing

        # The schedule is seeded per connection, but WHICH frame a roll
        # lands on moves with the fuser's timing, and every connection of
        # this process is a chaos socket: replies and heartbeats carry no
        # trace context, so on a loaded machine every drop of 12 steps
        # can fall on those.  Run until one has been tagged with the span
        # of its frame (12 steps at least, as when the machine is quiet).
        tags_with_span = []
        record_instant = tracing.Tracer.record_instant

        def spy(self, track, name, args=None, ts=None):
            if track == "chaos":
                tags_with_span.append("span" in (args or {}))
            record_instant(self, track, name, args, ts)

        monkeypatch.setattr(tracing.Tracer, "record_instant", spy)
        bps.init()
        rng = np.random.default_rng(1)
        names = [f"obs.{k}" for k in range(6)]
        for step in range(240):
            if step >= 12 and any(tags_with_span) and counters().get("rpc_retry"):
                break
            xs = {n: rng.standard_normal(211 + 13 * i).astype(np.float32)
                  for i, n in enumerate(names)}
            hs = {n: bps.push_pull_async(x, name=n, average=False)
                  for n, x in xs.items()}
            for n, h in hs.items():
                np.testing.assert_array_equal(
                    np.asarray(bps.synchronize(h)), xs[n]
                )
        snap = counters().snapshot()
        assert snap.get("fused_frames", 0) >= 1, snap
        assert snap.get("chaos_drop", 0) >= 1, snap  # schedule fired
        assert snap.get("rpc_retry", 0) >= 1, snap   # and was healed
        # per-peer dimension: the one server carries the retries
        assert counters().snapshot_labeled()["rpc_retry"], "no peer labels"
        time.sleep(0.6)  # a heartbeat carries deltas to the scheduler
        agg = observed_cluster["scheduler"].metrics_agg.counters.snapshot()
        assert agg.get("wire_rpc", 0) >= 1, agg
        bps.shutdown()
        observed_cluster["server"].stop()

        # --- merge the per-process files into one timeline ------------
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import trace_merge

            td = str(observed_cluster["trace_dir"])
            out = os.path.join(td, "merged.json")
            assert trace_merge.main([td, "-o", out]) == 0
        finally:
            sys.path.remove(os.path.join(REPO, "tools"))
        merged = json.load(open(out))
        events = merged["traceEvents"]
        spans = [e for e in events if e.get("cat") == "span"]
        worker_spans = {
            e["args"]["span"] for e in spans
            if str(e.get("pid", "")).startswith("worker") and "args" in e
            and "span" in e["args"]
        }
        server_children = [
            e for e in spans
            if str(e.get("pid", "")).startswith("server")
            and e.get("args", {}).get("parent")
        ]
        assert server_children, "server emitted no child spans"
        joined = [
            e for e in server_children
            if e["args"]["parent"] in worker_spans
        ]
        assert joined, "no server child joined a worker span"
        # same trace id across the process boundary
        worker_traces = {
            e["args"]["trace"] for e in spans
            if str(e.get("pid", "")).startswith("worker")
            and "trace" in e.get("args", {})
        }
        assert any(
            e["args"]["trace"] in worker_traces for e in joined
        ), "joined child spans carry foreign trace ids"
        # at least one fused pack span made the timeline
        assert any(e["name"] == "FUSED_RPC" for e in spans), "no pack span"
        # chaos faults tagged with owning spans, and at least one such
        # span retried (rpc_retry >= 1 asserted above, spans match)
        chaos_tags = [
            e for e in events
            if e.get("ph") == "i" and e.get("args", {}).get("injected")
        ]
        assert chaos_tags, "no chaos fault tagged on the timeline"
        assert any("span" in e["args"] for e in chaos_tags), (
            "chaos faults lost their owning spans"
        )
        # flow links were emitted for the merged view
        assert merged["otherData"]["linked_spans"] >= 1


def _have_native() -> bool:
    from byteps_tpu.native import get_lib

    lib = get_lib()
    return lib is not None and hasattr(lib, "bps_native_server_start_unix")


@pytest.mark.skipif(not _have_native(), reason="native lib not built")
class TestNativeTraceInterop:
    """The C++ engine must IGNORE trace-context bytes: a tracing Python
    worker and the native server interoperate on one stream, old and new
    frames mixed (conftest's native timeout guards apply)."""

    @pytest.mark.parametrize("van", ["uds", "shm"])
    def test_native_server_skips_trace_context(self, van, monkeypatch):
        if van == "shm":
            import platform

            if platform.machine() not in ("x86_64", "AMD64", "i686"):
                pytest.skip("shm van needs x86-64 TSO")
        from byteps_tpu.server.server import NativePSServer

        monkeypatch.setenv("BYTEPS_VAN", van)
        cfg = Config(num_worker=1, num_server=1)
        srv = NativePSServer(cfg)
        try:
            sock = connect(srv.host, srv.port)
            cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                   int(DataType.FLOAT32))
            x = np.arange(16, dtype=np.float32)
            # NEW frame: init WITH trace context
            send_message(sock, Message(
                Op.INIT, key=3, seq=1, flags=1,
                payload=struct.pack("!QI", 16, int(DataType.FLOAT32)),
                trace=(0xAB, 0xCD),
            ))
            assert recv_message(sock).op == Op.INIT
            # NEW frame: traced push — the 16 extra bytes must be skipped
            send_message(sock, Message(
                Op.PUSH, key=3, seq=2, flags=1, cmd=cmd, version=1,
                payload=x.tobytes(), trace=(0xAB, 0xCE),
            ))
            ack = recv_message(sock)
            assert ack.op == Op.PUSH and ack.seq == 2
            # OLD frame on the SAME stream: untraced pull still framed
            send_message(sock, Message(Op.PULL, key=3, seq=3, cmd=cmd,
                                       version=1))
            reply = recv_message(sock)
            assert reply.op == Op.PULL and reply.seq == 3
            got = np.frombuffer(reply.payload, dtype=np.float32)
            np.testing.assert_array_equal(got, x)  # stream never desynced
            # and once more traced, proving steady-state interop
            send_message(sock, Message(Op.PULL, key=3, seq=4, cmd=cmd,
                                       version=1, trace=(0xAB, 0xCF)))
            reply = recv_message(sock)
            assert reply.op == Op.PULL and reply.seq == 4
            np.testing.assert_array_equal(
                np.frombuffer(reply.payload, dtype=np.float32), x
            )
            from byteps_tpu.comm.transport import close_socket

            close_socket(sock)
        finally:
            srv.stop()


class TestHistProviderSeam:
    """The histogram twin of the counter-provider seam: external raw-
    bucket records (the native engines' feed) must merge into EVERY read
    surface and survive absorb/reset (pure-Python — no native lib)."""

    REC = {
        "name": "native_server_sum_seconds",
        "labels": {"key": "7"},
        "le": [0.001, 0.01],
        "b": [2, 1, 1],  # raw counts incl. +Inf
        "sum": 0.5,
        "count": 4,
    }

    def _registry(self):
        return MetricsRegistry()

    def test_snapshot_and_prometheus_include_provider(self):
        reg = self._registry()
        reg.register_hist_provider(lambda: [dict(self.REC)])
        snap = reg.snapshot()["histograms"]
        assert snap['native_server_sum_seconds{key="7"}']["count"] == 4
        text = reg.render_prometheus()
        assert 'native_server_sum_seconds_bucket{key="7",le="0.001"} 2' in text
        assert 'native_server_sum_seconds_count{key="7"} 4' in text
        assert "native_server_sum_seconds_p50" in text

    def test_provider_merges_into_local_family(self):
        """A local histogram with the same (name, labels, bounds) and a
        provider feed sum bucket-wise — one combined family."""
        reg = self._registry()
        h = reg.histogram("native_server_sum_seconds", labels={"key": "7"},
                          buckets=(0.001, 0.01))
        h.observe(0.0005)
        reg.register_hist_provider(lambda: [dict(self.REC)])
        snap = reg.snapshot()["histograms"]
        assert snap['native_server_sum_seconds{key="7"}']["count"] == 5

    def test_delta_ships_provider_increments_once(self):
        reg = self._registry()
        state = {"count": 4}
        def provider():
            rec = dict(self.REC)
            rec["count"] = state["count"]
            rec["b"] = [2, 1, state["count"] - 3]
            return [rec]
        reg.register_hist_provider(provider)
        d1 = reg.delta_snapshot()
        assert any(r["name"] == "native_server_sum_seconds" and r["n"] == 4
                   for r in d1["h"])
        assert not reg.delta_snapshot().get("h")  # nothing new
        state["count"] = 6
        d3 = reg.delta_snapshot()
        assert any(r["n"] == 2 for r in d3["h"])

    def test_absorb_preserves_totals_and_delta_continuity(self):
        reg = self._registry()
        fn = lambda: [dict(self.REC)]  # noqa: E731
        reg.register_hist_provider(fn)
        reg.delta_snapshot()  # baseline shipped
        reg.absorb_hist_provider(fn)
        snap = reg.snapshot()["histograms"]
        assert snap['native_server_sum_seconds{key="7"}']["count"] == 4
        # absorbed totals are unchanged → no spurious delta
        assert not reg.delta_snapshot().get("h")

    def test_reset_rebaselines_provider(self):
        reg = self._registry()
        reg.register_hist_provider(lambda: [dict(self.REC)])
        assert reg.snapshot()["histograms"]
        reg.reset()
        # native source never clears, but post-reset view starts at zero
        assert 'native_server_sum_seconds{key="7"}' not in (
            reg.snapshot()["histograms"]
        )

    def test_malformed_records_dropped(self):
        reg = self._registry()
        reg.register_hist_provider(lambda: [
            {"name": "x"},                              # missing fields
            {"name": "y", "labels": {}, "le": [1.0],
             "b": [1], "sum": 0, "count": 1},           # b too short
            "not-a-dict",
        ])
        assert reg.snapshot()["histograms"] == {}


@pytest.mark.skipif(not _have_native(), reason="native lib not built")
class TestNativeServerChildSpans:
    """Tentpole: the C++ engine stamps the same child-span model the
    Python server does — drained through the span ring into the process
    tracer (conftest's native timeout guards apply)."""

    def _server(self, tmp_path, monkeypatch, num_worker=1):
        from byteps_tpu.server.server import NativePSServer

        monkeypatch.setenv("BYTEPS_VAN", "tcp")
        cfg = Config(num_worker=num_worker, num_server=1, trace_on=True,
                     trace_dir=str(tmp_path))
        return NativePSServer(cfg)

    def _wait_spans(self, srv, n, timeout=5.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            with srv.tracer._lock:
                events = [e for e in srv.tracer._events
                          if e.get("cat") == "span"]
            if len(events) >= n:
                return events
            time.sleep(0.05)
        raise AssertionError(
            f"native span drain produced {len(events)} events, wanted {n}"
        )

    def test_native_push_children_join_worker_span_and_dedupe(
            self, tmp_path, monkeypatch):
        srv = self._server(tmp_path, monkeypatch)
        try:
            sock = connect(srv.host, srv.port)
            cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                   int(DataType.FLOAT32))
            x = np.arange(8, dtype=np.float32)
            send_message(sock, Message(
                Op.INIT, key=3, seq=1, flags=1,
                payload=struct.pack("!QI", 8, int(DataType.FLOAT32)),
            ))
            assert recv_message(sock).op == Op.INIT
            send_message(sock, Message(
                Op.PUSH, key=3, seq=2, flags=1, cmd=cmd, version=1,
                payload=x.tobytes(), trace=(0xCAFE, 0xD00D),
            ))
            assert recv_message(sock).op == Op.PUSH
            # replay (retry after a lost ack): dedupe-annotated sum span
            send_message(sock, Message(
                Op.PUSH, key=3, seq=3, flags=1, cmd=cmd, version=1,
                payload=x.tobytes(), trace=(0xCAFE, 0xD00D),
            ))
            assert recv_message(sock).op == Op.PUSH
            events = self._wait_spans(srv, 7)
            assert {e["name"] for e in events} >= {"recv", "sum", "publish",
                                                  "reply"}
            for e in events:
                assert e["args"]["trace"] == format(0xCAFE, "x")
                assert e["args"]["parent"] == format(0xD00D, "x")
                assert e["args"]["engine"] == "native"
            sums = [e for e in events if e["name"] == "sum"]
            assert [e["args"]["dedupe"] for e in sums] == [False, True]
            assert srv.native_counters()["native_push_dedup"] == 1
            from byteps_tpu.comm.transport import close_socket

            close_socket(sock)
        finally:
            srv.stop()
        # stop() flushed the drained spans to server<rank>/comm.json for
        # the merge tool (rank unset → "server" subdir)
        out = tmp_path / "server" / "comm.json"
        assert out.exists()
        written = json.load(open(out))["traceEvents"]
        assert any(e.get("cat") == "span" for e in written)

    def test_native_spans_land_on_per_stripe_lanes(self, tmp_path,
                                                   monkeypatch):
        """Key-striped engine: reducer-executed spans carry their stripe
        and the drain maps each stripe to its own Perfetto thread lane
        (``tid: stripeN``) so the merged timeline shows per-reducer
        occupancy."""
        from byteps_tpu.native import key_stripe

        monkeypatch.setenv("BYTEPS_SERVER_STRIPES", "2")
        srv = self._server(tmp_path, monkeypatch)
        expect = key_stripe(3, 2)
        try:
            sock = connect(srv.host, srv.port)
            cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                   int(DataType.FLOAT32))
            send_message(sock, Message(
                Op.INIT, key=3, seq=1, flags=1,
                payload=struct.pack("!QI", 8, int(DataType.FLOAT32)),
            ))
            assert recv_message(sock).op == Op.INIT
            send_message(sock, Message(
                Op.PUSH, key=3, seq=2, flags=1, cmd=cmd, version=1,
                payload=np.ones(8, np.float32).tobytes(),
                trace=(0xBEEF, 0xF00D),
            ))
            assert recv_message(sock).op == Op.PUSH
            events = self._wait_spans(srv, 4)
            for e in events:
                assert e["tid"] == f"stripe{expect}", e
                assert e["args"]["stripe"] == expect
                assert e["args"]["key"] == 3
            from byteps_tpu.comm.transport import close_socket

            close_socket(sock)
        finally:
            srv.stop()

    def test_native_fused_members_parent_on_trailer_ids(self, tmp_path, monkeypatch):
        srv = self._server(tmp_path, monkeypatch)
        try:
            sock = connect(srv.host, srv.port)
            cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                   int(DataType.FLOAT32))
            for key, seq in ((11, 1), (12, 2)):
                send_message(sock, Message(
                    Op.INIT, key=key, seq=seq, flags=1,
                    payload=struct.pack("!QI", 4, int(DataType.FLOAT32)),
                ))
                assert recv_message(sock).op == Op.INIT
            frame = encode_fused_push(
                [(11, cmd, 1, np.ones(4, np.float32).tobytes()),
                 (12, cmd, 1, np.full(4, 2.0, np.float32).tobytes())],
                span_ids=[0x111, 0x222],
            )
            send_message(sock, Message(
                Op.FUSED, key=11, seq=3, flags=1, cmd=2, payload=frame,
                trace=(0xFACE, 0xF00),
            ))
            reply = recv_message(sock)
            assert reply.op == Op.FUSED
            events = self._wait_spans(srv, 3)
            sums = [e for e in events if e["name"] == "sum"]
            assert {e["args"]["parent"] for e in sums} == {
                format(0x111, "x"), format(0x222, "x")
            }
            assert all(e["args"]["fused"] for e in sums)
            assert all(e["args"]["trace"] == format(0xFACE, "x")
                       for e in sums)
            recvs = [e for e in events if e["name"] == "recv"]
            assert recvs and recvs[0]["args"]["parent"] == format(0xF00, "x")
            from byteps_tpu.comm.transport import close_socket

            close_socket(sock)
        finally:
            srv.stop()

    def test_native_spans_off_is_silent(self, tmp_path, monkeypatch):
        """BYTEPS_TRACE_SPANS=0 semantics: trace-flagged frames are
        consumed but the ring never sees a write."""
        from byteps_tpu.server.server import NativePSServer

        monkeypatch.setenv("BYTEPS_VAN", "tcp")
        cfg = Config(num_worker=1, num_server=1, trace_on=True,
                     trace_spans=False, trace_dir=str(tmp_path))
        srv = NativePSServer(cfg)
        try:
            from byteps_tpu.native import native_server_drain_spans

            sock = connect(srv.host, srv.port)
            cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                   int(DataType.FLOAT32))
            send_message(sock, Message(
                Op.INIT, key=5, seq=1, flags=1,
                payload=struct.pack("!QI", 4, int(DataType.FLOAT32)),
                trace=(0xAB, 0xCD),
            ))
            assert recv_message(sock).op == Op.INIT
            send_message(sock, Message(
                Op.PUSH, key=5, seq=2, flags=1, cmd=cmd, version=1,
                payload=np.ones(4, np.float32).tobytes(), trace=(0xAB, 0xCE),
            ))
            assert recv_message(sock).op == Op.PUSH
            assert len(native_server_drain_spans(srv._id)) == 0
            from byteps_tpu.comm.transport import close_socket

            close_socket(sock)
        finally:
            srv.stop()


@pytest.mark.skipif(not _have_native(), reason="native lib not built")
class TestNativeHistogramSeam:
    """Native server + client histograms reach get_metrics_text() and
    survive source stop (conftest's native timeout guards apply)."""

    def test_native_server_histograms_merge_and_survive_stop(
            self, tmp_path, monkeypatch):
        from byteps_tpu.server.server import NativePSServer

        monkeypatch.setenv("BYTEPS_VAN", "tcp")
        cfg = Config(num_worker=1, num_server=1)
        srv = NativePSServer(cfg)
        try:
            sock = connect(srv.host, srv.port)
            cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                   int(DataType.FLOAT32))
            send_message(sock, Message(
                Op.INIT, key=9, seq=1, flags=1,
                payload=struct.pack("!QI", 8, int(DataType.FLOAT32)),
            ))
            assert recv_message(sock).op == Op.INIT
            send_message(sock, Message(
                Op.PUSH, key=9, seq=2, flags=1, cmd=cmd, version=1,
                payload=np.ones(8, np.float32).tobytes(),
            ))
            assert recv_message(sock).op == Op.PUSH
            text = metrics().render_prometheus()
            assert 'native_server_sum_seconds_count{key="9"} 1' in text
            assert 'native_request_bytes_count{key="9"} 1' in text
            snap = metrics().snapshot()["histograms"]
            assert snap['native_server_sum_seconds{key="9"}']["count"] == 1
            from byteps_tpu.comm.transport import close_socket

            close_socket(sock)
        finally:
            srv.stop()
        # absorbed at stop: totals survive the instance
        text = metrics().render_prometheus()
        assert 'native_server_sum_seconds_count{key="9"} 1' in text

    def test_native_stripe_depth_gauges_appear_and_leave(self, monkeypatch):
        """The key-striped engine exports one backlog gauge series per
        reducer (labeled ``stripe`` + the owning ``server`` instance, so
        two servers in one process can't collide); the series leave the
        scrape surface when the instance stops (no dead callables) —
        and only THAT instance's series leave."""
        from byteps_tpu.server.server import NativePSServer

        monkeypatch.setenv("BYTEPS_VAN", "tcp")
        monkeypatch.setenv("BYTEPS_SERVER_STRIPES", "2")
        cfg = Config(num_worker=1, num_server=1)
        srv = NativePSServer(cfg)
        srv2 = NativePSServer(cfg)
        sid, sid2 = srv._id, srv2._id
        try:
            text = metrics().render_prometheus()
            for inst in (sid, sid2):
                for s in ("0", "1"):
                    assert (
                        f'byteps_native_stripe_queue_depth'
                        f'{{server="{inst}",stripe="{s}"}}' in text
                    ), text
            gauges = metrics().snapshot()["gauges"]
            key0 = f'native_stripe_queue_depth{{server="{sid}",stripe="0"}}'
            assert gauges[key0] == 0.0
        finally:
            srv.stop()
        # the sibling's series survive the first instance's stop
        text = metrics().render_prometheus()
        assert f'server="{sid}"' not in text
        assert (
            f'byteps_native_stripe_queue_depth{{server="{sid2}",stripe="0"}}'
            in text
        )
        srv2.stop()
        assert "native_stripe_queue_depth" not in metrics().render_prometheus()

    def test_native_client_rtt_histogram(self, monkeypatch):
        from byteps_tpu.comm.ps_client import _NativeServerConn
        from byteps_tpu.native import get_lib

        lib = get_lib()
        port = lib.bps_native_server_start(0, 1, 0)
        assert port > 0
        conn = None
        try:
            conn = _NativeServerConn("127.0.0.1", port)
            done = threading.Event()
            box = []

            def cb(msg):
                box.append(msg)
                done.set()

            seq = conn.alloc_seq(cb)
            assert seq >= 0
            conn.send_msg(Message(Op.PING, seq=seq, trace=(0x77, 0x88)))
            assert done.wait(5.0) and box[0] is not None
            text = metrics().render_prometheus()
            assert "native_rpc_round_trip_seconds_count 1" in text
        finally:
            if conn is not None:
                conn.close_all()
            lib.bps_native_server_stop(port)
        # absorbed at close: the attempt's latency survives
        assert "native_rpc_round_trip_seconds_count 1" in (
            metrics().render_prometheus()
        )


class TestTraceMergeAttribution:
    """trace_merge satellites: orphaned-span accounting + the
    --critical-path per-engine attribution pass (synthetic trace files —
    no cluster needed)."""

    def _write(self, d, name, events):
        sub = d / name
        sub.mkdir(parents=True, exist_ok=True)
        with open(sub / "comm.json", "w") as f:
            json.dump({"traceEvents": events}, f)

    def _span(self, pid, tid, name, ts_us, dur_us, trace, span=None,
              parent=None, **extra):
        args = {"trace": format(trace, "x")}
        if span is not None:
            args["span"] = format(span, "x")
        if parent is not None:
            args["parent"] = format(parent, "x")
        args.update(extra)
        return {"name": name, "cat": "span", "ph": "X", "ts": ts_us,
                "dur": dur_us, "pid": pid, "tid": tid, "args": args}

    def _merge_tool(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import trace_merge
        finally:
            sys.path.remove(os.path.join(REPO, "tools"))
        return trace_merge

    def test_orphans_counted_not_dropped_silently(self, tmp_path):
        tm = self._merge_tool()
        self._write(tmp_path, "server0", [
            # child whose parent (a worker span) was never merged in —
            # the worker file is "missing"
            self._span("server0", "key1", "sum", 100, 10, trace=0xA1,
                       span=0x10, parent=0xDEAD),
        ])
        merged = tm.merge(tm.find_trace_files([str(tmp_path)]))
        assert merged["otherData"]["orphaned_spans"] == 1
        assert merged["otherData"]["orphaned_parent_ids"] == 1
        assert merged["otherData"]["linked_spans"] == 0

    def test_critical_path_attributes_per_engine_and_stage(self, tmp_path):
        tm = self._merge_tool()
        T = 0xAA
        # worker: one PUSH RPC-stage span (span 0x5), 0..1000µs
        self._write(tmp_path, "0", [
            self._span("worker0", "k", "PUSH", 0, 1000, trace=T, span=0x5),
        ])
        # python server: children covering 200..800µs
        self._write(tmp_path, "server0", [
            self._span("server0", "key1", "recv", 200, 100, trace=T,
                       span=0x20, parent=0x5),
            self._span("server0", "key1", "sum", 300, 300, trace=T,
                       span=0x21, parent=0x5),
            self._span("server0", "key1", "publish", 600, 100, trace=T,
                       span=0x22, parent=0x5),
            self._span("server0", "key1", "reply", 700, 100, trace=T,
                       span=0x23, parent=0x5),
        ])
        # native server: a second worker RPC + engine-tagged children
        self._write(tmp_path, "1", [
            self._span("worker1", "k", "PUSH", 0, 500, trace=T, span=0x6),
        ])
        self._write(tmp_path, "server1", [
            self._span("server1", "key1", "recv", 100, 50, trace=T,
                       span=0x30, parent=0x6, engine="native"),
            self._span("server1", "key1", "sum", 150, 200, trace=T,
                       span=0x31, parent=0x6, engine="native"),
        ])
        merged = tm.merge(tm.find_trace_files([str(tmp_path)]))
        attrib = tm.critical_path(merged)
        assert set(attrib["engines"]) == {"python", "native"}
        py = attrib["engines"]["python"]["stages"]
        assert py["queue_wait"]["total_s"] == pytest.approx(100e-6)
        assert py["sum"]["total_s"] == pytest.approx(300e-6)
        assert py["publish"]["total_s"] == pytest.approx(100e-6)
        assert py["reply"]["total_s"] == pytest.approx(100e-6)
        # wire = worker extent (1000) - server extent (200..800 = 600)
        assert py["wire"]["total_s"] == pytest.approx(400e-6)
        nat = attrib["engines"]["native"]["stages"]
        assert nat["sum"]["total_s"] == pytest.approx(200e-6)
        # wire = 500 - (100..350 = 250)
        assert nat["wire"]["total_s"] == pytest.approx(250e-6)
        assert attrib["linked_rpcs"] == 2
        shares = [d["share"] for d in py.values()]
        assert sum(shares) == pytest.approx(1.0)

    def test_critical_path_splits_sum_by_reducer_stripe(self, tmp_path):
        """Native sum spans carry their reducer stripe; the attribution
        pass reports per-reducer occupancy (`reducers`) so a hot stripe
        is visible in TRACE_ATTRIB artifacts, not just the live gauges."""
        tm = self._merge_tool()
        T = 0xCC
        self._write(tmp_path, "0", [
            self._span("worker0", "k", "PUSH", 0, 1000, trace=T, span=0x7),
        ])
        self._write(tmp_path, "server0", [
            self._span("server0", "stripe0", "sum", 100, 300, trace=T,
                       span=0x50, parent=0x7, engine="native", stripe=0),
            self._span("server0", "stripe1", "sum", 100, 100, trace=T,
                       span=0x51, parent=0x7, engine="native", stripe=1),
            # control-thread span (no stripe): counted in the stage
            # totals but never in a reducer lane
            self._span("server0", "key9", "resync", 500, 50, trace=T,
                       span=0x52, parent=0x7, engine="native"),
        ])
        attrib = tm.critical_path(
            tm.merge(tm.find_trace_files([str(tmp_path)])))
        nat = attrib["engines"]["native"]
        assert nat["stages"]["sum"]["total_s"] == pytest.approx(400e-6)
        red = nat["reducers"]
        assert set(red) == {"0", "1"}
        assert red["0"]["sum_total_s"] == pytest.approx(300e-6)
        assert red["0"]["share_of_sum"] == pytest.approx(0.75)
        assert red["1"]["share_of_sum"] == pytest.approx(0.25)

    def test_stripe_identity_derived_from_tid_occupancy(self, tmp_path):
        """Reducer-lane spans whose args lack a ``stripe`` field still
        land in the per-stripe occupancy: identity falls back to the
        ``stripe<N>`` track (tid) the drain files every lane span under,
        and occupancy counts EVERY stage on the lane, not just sum."""
        tm = self._merge_tool()
        T = 0xD1
        self._write(tmp_path, "0", [
            self._span("worker0", "k", "PUSH", 0, 2000, trace=T, span=0x8),
        ])
        self._write(tmp_path, "server0", [
            # no stripe arg anywhere — tid carries the lane identity
            self._span("server0", "stripe0", "sum", 100, 300, trace=T,
                       span=0x60, parent=0x8, engine="native"),
            self._span("server0", "stripe0", "publish", 400, 100, trace=T,
                       span=0x61, parent=0x8, engine="native"),
            self._span("server0", "stripe1", "sum", 100, 100, trace=T,
                       span=0x62, parent=0x8, engine="native"),
            # control-thread span on a key track: never a lane
            self._span("server0", "key3", "reply", 600, 50, trace=T,
                       span=0x63, parent=0x8, engine="native"),
        ])
        attrib = tm.critical_path(
            tm.merge(tm.find_trace_files([str(tmp_path)])))
        red = attrib["engines"]["native"]["reducers"]
        assert set(red) == {"0", "1"}
        # sum split still only counts sum stages
        assert red["0"]["sum_total_s"] == pytest.approx(300e-6)
        # occupancy counts sum + publish on the lane
        assert red["0"]["busy_total_s"] == pytest.approx(400e-6)
        assert red["0"]["occupancy"] == pytest.approx(0.8)
        assert red["1"]["occupancy"] == pytest.approx(0.2)

    def test_skewed_occupancy_feeds_hot_stripe_trigger(self, tmp_path):
        """The attribution pass runs the flight recorder's OWN
        hot_stripe rule on the per-lane occupancy: a skewed key hash
        found offline and one caught live are judged identically."""
        tm = self._merge_tool()
        T = 0xD2
        self._write(tmp_path, "0", [
            self._span("worker0", "k", "PUSH", 0, 20000, trace=T, span=0x9),
        ])
        # stripe0 is hot: 10 ms busy vs 2 ms siblings (past the
        # rule's 3× median bar and its 1 ms absolute floor)
        self._write(tmp_path, "server0", [
            self._span("server0", "stripe0", "sum", 0, 10000, trace=T,
                       span=0x70, parent=0x9, engine="native"),
            self._span("server0", "stripe1", "sum", 0, 2000, trace=T,
                       span=0x71, parent=0x9, engine="native"),
            self._span("server0", "stripe2", "sum", 0, 2000, trace=T,
                       span=0x72, parent=0x9, engine="native"),
        ])
        attrib = tm.critical_path(
            tm.merge(tm.find_trace_files([str(tmp_path)])))
        hot = attrib["engines"]["native"]["hot_stripe"]
        assert hot["stripe"] == "0"
        assert hot["sum_seconds"] == pytest.approx(0.01)
        assert hot["sibling_median"] == pytest.approx(0.002)
        assert hot["share"] == pytest.approx(10.0 / 14.0, rel=1e-3)
        # balanced lanes: same pipeline, no verdict
        bal = tmp_path / "balanced"
        self._write(bal, "0", [
            self._span("worker0", "k", "PUSH", 0, 20000, trace=T, span=0xA),
        ])
        self._write(bal, "server0", [
            self._span("server0", "stripe0", "sum", 0, 2000, trace=T,
                       span=0x80, parent=0xA, engine="native"),
            self._span("server0", "stripe1", "sum", 0, 2100, trace=T,
                       span=0x81, parent=0xA, engine="native"),
        ])
        attrib = tm.critical_path(
            tm.merge(tm.find_trace_files([str(bal)])))
        assert "hot_stripe" not in attrib["engines"]["native"]

    def test_cli_writes_attribution_artifact(self, tmp_path):
        tm = self._merge_tool()
        T = 0xBB
        self._write(tmp_path, "0", [
            self._span("worker0", "k", "PUSH", 0, 100, trace=T, span=0x9),
        ])
        self._write(tmp_path, "server0", [
            self._span("server0", "key1", "sum", 10, 50, trace=T,
                       span=0x40, parent=0x9),
        ])
        out = tmp_path / "merged.json"
        attrib = tmp_path / "attrib.json"
        rc = tm.main([str(tmp_path), "-o", str(out),
                      "--critical-path", str(attrib)])
        assert rc == 0
        doc = json.load(open(attrib))
        assert doc["engines"]["python"]["rpcs"] == 1
