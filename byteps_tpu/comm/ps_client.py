"""Worker-side PS client — the KVWorker replacement.

ps-lite surface the core consumes (SURVEY §2.4): zero-copy ``ZPush``/
``ZPull`` with completion callbacks (core_loops.cc:571,609), key→server
routing (EncodeDefaultKey, global.cc:628-677), scheduler rendezvous +
global barrier (global.cc:289-294).

One link per server — over TCP a push lane and a pull lane, so bulk travels
one way on a socket (``_ServerConn``; docs/transports.md); a receiver thread
per connection demuxes responses by ``seq`` and fires callbacks — the callback thread then drives
the next pipeline stage, exactly like ps-lite's callback threads drive
FinishOrProceed.

Self-healing (docs/robustness.md): every data-plane RPC is retried with
exponential backoff + jitter when its connection dies (``BYTEPS_RPC_
RETRIES`` attempts after the first), transparently re-dialing a dead
server connection first (revival) — so an injected disconnect, a dropped
frame, or a server restart costs a retry, not a failed training step.
With ``BYTEPS_RPC_DEADLINE_S`` set, a per-attempt deadline additionally
catches HUNG servers: expiry tears the suspect connection down (so no
late response can race a retry into a caller's zero-copy sink) and the
normal dead-connection retry path heals it.  Pushes carry the worker's
rank in the header ``flags`` byte so the server dedupes replays —
retried summation stays exactly-once (see server.py).
"""

from __future__ import annotations

import functools
import itertools
import json
import socket
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from byteps_tpu.core.telemetry import counters, metrics
from byteps_tpu.core.tracing import sampled, span, thread_tag

from byteps_tpu.common.config import Config
from byteps_tpu.common.hashing import assign_server
from byteps_tpu.common.types import RequestType, get_command_type
from byteps_tpu.comm.rendezvous import GROUP_ALL, GROUP_WORKERS, RESIZE_SEQ
from byteps_tpu.comm.transport import (
    Message,
    Op,
    close_socket,
    connect,
    FramePool,
    recv_message,
    recv_payload,
    release_frame,
    send_message,
)

#: sentinel payload marking a response whose bytes were received directly
#: into the caller's registered sink buffer (zero-copy pull)
_ZERO_COPIED = object()


#: threads that send PUSH frames at once over one split TCP link (the engine's
#: PUSH stage runs as many: ``core/engine.py`` ``PipelineEngine.start``), and
#: with them the least lanes such a link has A DIRECTION: a sender a push
#: lane, and as many pull lanes, each with its receive thread.  One 4 MB
#: ``sendmsg`` (and one ``recv_into``) is the kernel's copy made BY the
#: calling thread, and one loopback stream carries about 3 GB/s: on the
#: chip's host neither a second sender alone nor second sockets behind one
#: sender moved a step, the two together did (PERF.md §6 PR 39)
PUSH_SENDERS = 2


class _ServerConn:
    def __init__(self, host: str, port: int, streams: int = 1,
                 dial_timeout: float = 30.0) -> None:
        from byteps_tpu.comm.shaping import (
            maybe_shape,
            shaping_enabled,
            warn_native_bypass_once,
        )

        from byteps_tpu.comm.van import SHM_PREFIX, UNIX_PREFIX, strip_chaos

        shaped = shaping_enabled()
        if streams > 1 and shaped:
            # each stripe would get its OWN virtual wire, silently scaling
            # the emulated link to N x BYTEPS_VAN_RATE_MBYTES_S — a shaped
            # link models one wire, so striping is forced off
            warn_native_bypass_once(
                "ignoring BYTEPS_TCP_STREAMS>1 (a shaped link is one wire)"
            )
        tcp = not shaped and not strip_chaos(host).startswith(
            (UNIX_PREFIX, SHM_PREFIX)
        )
        # striped lanes (BYTEPS_TCP_STREAMS, tcp only): extra parallel
        # connections to the same server, each framed message riding ONE
        # lane chosen by key, so distinct partitions fan out over
        # independent kernel streams (the RDMA/UCX multi-lane van analogue,
        # reference setup.py:312-330).
        streams = max(1, streams) if tcp else 1
        #: how many threads may usefully send PUSH frames on this link at
        #: once: a split TCP link gives each a push lane; a link of one
        #: socket has one send lock, which two senders would take in turns
        self.push_senders = PUSH_SENDERS if tcp else 1
        each = max(streams, self.push_senders)  # lanes a direction
        # On TCP, bulk travels ONE WAY on a socket: PULL requests go out on
        # lanes of their own and the merged rounds come back on them, so a
        # 4 MB reply's recv_into and another partition's 4 MB push sendmsg
        # never take turns on one kernel socket's lock.  Everything else
        # (PUSH, FUSED, INIT, control frames) rides the push lanes, whose
        # replies are header-only acks; push lane 0 doubles as the control
        # lane.  A key's PULL leaves only after its PUSH was acked, and its
        # next PUSH only after that PULL was answered (the engine's round
        # gate): the ack orders them, not a socket's FIFO.  A unix, shm or
        # shaped link keeps one socket, which then carries both directions.
        # Dial order: the ``streams`` push lanes a one-socket link had, the
        # pull lanes, then the push lanes the second sender adds — what the
        # split added dials ``added_lane``, so the first lanes keep their
        # chaos connection indices.
        lanes = []
        try:
            for i in range(2 * each if tcp else 1):
                # data-plane link: shaped when BYTEPS_VAN_DELAY_MS /
                # BYTEPS_VAN_RATE_MBYTES_S emulate a DCN link (shaping.py)
                lanes.append(
                    (maybe_shape(connect(host, port, timeout=dial_timeout,
                                         added_lane=i >= streams)),
                     threading.Lock())
                )
        except (ConnectionError, OSError):
            for sock, _ in lanes:
                close_socket(sock)
            raise
        #: push lanes (tcp: one a sender at least), then (tcp) as many pull lanes
        self.stripes = lanes[:streams] + lanes[streams + each:]
        self.pull_stripes = lanes[streams:streams + each] or self.stripes
        self.cb_lock = threading.Lock()
        self.callbacks: Dict[int, Callable[[Message], None]] = {}
        #: seq → caller-owned buffer the response payload is received INTO
        #: (zero-copy pull; ps-lite ZPull-into-SArray parity)
        self.sinks: Dict[int, memoryview] = {}
        self.next_seq = 0
        self.recv_thread: Optional[threading.Thread] = None
        self.dead = False  # set once the LAST recv loop exits; cb_lock-guarded
        # receiver loops still running; the last one to exit runs the
        # mark_dead drain (see lane_exited)
        self._live_lanes = len(lanes)
        #: per-server label value for counter slices (the book index the
        #: conn was built for; "?" for stubs) — set by the caller
        self.server_label = "?"
        #: CRC mismatches across the whole striped connection (the
        #: BYTEPS_CHECKSUM_CONN_LIMIT escalation tally)
        self._ck_fails = 0

    def note_checksum_fail(self) -> int:
        """Account one checksum-rejected reply; returns the connection's
        running mismatch total (cb_lock-guarded: lanes race)."""
        with self.cb_lock:
            self._ck_fails += 1
            return self._ck_fails

    def lane_exited(self) -> bool:
        """Account one receiver loop's exit; True when it was the last.
        Only the LAST lane may drain callbacks: a sibling lane can still be
        mid-recv_into, writing a response payload into a caller's
        zero-copy sink — draining early would hand the caller a 'failed'
        buffer another thread is still filling."""
        with self.cb_lock:
            self._live_lanes -= 1
            return self._live_lanes <= 0

    def lanes(self) -> list:
        """``(sock, send_lock, name)`` of every connection of the link."""
        named = [(sock, lock, "push") for sock, lock in self.stripes]
        if self.pull_stripes is not self.stripes:
            named += [(sock, lock, "pull") for sock, lock in self.pull_stripes]
        return named

    def close_all(self) -> None:
        """Close every lane: one lane dying poisons the whole connection
        (a partially-striped server link would strand keyed requests)."""
        for sock, _, _ in self.lanes():
            close_socket(sock)

    def alloc_seq(
        self,
        cb: Callable[[Message], None],
        sink: Optional[memoryview] = None,
    ) -> int:
        """Register a response callback; returns -1 (after firing
        ``cb(None)``) if the connection already died — a request enqueued
        AFTER the recv loop drained pending callbacks would otherwise
        never fire and its caller would hang in synchronize()."""
        with self.cb_lock:
            if not self.dead:
                seq = self.next_seq
                self.next_seq += 1
                self.callbacks[seq] = cb
                if sink is not None:
                    self.sinks[seq] = sink
                return seq
        cb(None)  # outside the lock: callbacks run user code
        return -1

    def pop_cb(self, seq: int) -> Optional[Callable[[Message], None]]:
        with self.cb_lock:
            self.sinks.pop(seq, None)
            return self.callbacks.pop(seq, None)

    def peek_sink(self, seq: int) -> Optional[memoryview]:
        """The registered receive buffer for a response seq, WITHOUT
        popping the callback: the entry must stay registered until the
        payload is fully received, so a connection dying mid-payload still
        drains the callback with None (mark_dead) instead of losing it."""
        with self.cb_lock:
            return self.sinks.get(seq)

    def mark_dead(self):
        """Flag the connection dead and drain pending callbacks (fired
        with None by the caller).  New alloc_seq calls fail immediately."""
        with self.cb_lock:
            self.dead = True
            cbs = list(self.callbacks.values())
            self.callbacks.clear()
            self.sinks.clear()
            return cbs

    def send_msg(self, msg: Message) -> None:
        """Frame + send on the message's lane: chosen by its ``op`` (a PULL
        rides a pull lane) and striped by its key, both stable, so a key's
        pushes share one stream and its pulls another.  Two PUSH senders
        that meet on one lane take turns on its lock, and part again at
        their next keys."""
        pull = msg.op == Op.PULL
        lanes = self.pull_stripes if pull else self.stripes
        sock, lock = lanes[msg.key % len(lanes)]
        _count_bulk(pull and lanes is not self.stripes, "tx", msg.op,
                    len(msg.payload))
        send_message(sock, msg, lock)


_LANE_LABELS = {
    (pull_lane, direction): {"lane": "pull" if pull_lane else "push",
                             "dir": direction}
    for pull_lane in (False, True) for direction in ("tx", "rx")
}


def _count_bulk(pull_lane: bool, direction: str, op, nbytes: int) -> None:
    """``lane_bulk_bytes{lane, dir}``: data-plane (PUSH, PULL, FUSED) payload
    bytes a lane sent | received.  On a split link ``push,tx`` and
    ``pull,rx`` carry a round, ``pull,tx`` a row-sparse request's indices
    and ``push,rx`` a fused frame's small reply: bulk met no bulk.  A
    one-socket link files both directions under ``push``."""
    if nbytes and op in (Op.PUSH, Op.PULL, Op.FUSED):
        counters().bump("lane_bulk_bytes", nbytes,
                        labels=_LANE_LABELS[pull_lane, direction])


class _NativeServerConn:
    """C++ data-plane lanes behind the same surface as ``_ServerConn``.

    Framing, striping, seq demux, and payload receive — including
    zero-copy pull-into-caller-buffer — run on GIL-free native threads
    (native/ps_client.cc; the worker-plane split of core_loops.cc:
    538-618).  Python runs only per-completion callbacks.  Selected by
    ``BYTEPS_NATIVE_CLIENT=1`` for tcp/uds links; the shm van keeps the
    Python client (its bulk path is already syscall-free mmap memcpy).

    Locking: ``alloc_seq`` registers the Python callback under
    ``_lock`` in the same critical section as the native alloc, and the
    completion hook pops under the same lock — a drain racing a fresh
    alloc blocks until the callback is registered, so no completion can
    ever miss its callback."""

    def __init__(self, host: str, port: int, streams: int = 1,
                 on_zero_copy=None) -> None:
        import ctypes

        from byteps_tpu.comm.van import UNIX_PREFIX
        from byteps_tpu.native import BPSC_CALLBACK, get_lib

        lib = get_lib()
        if lib is None or not hasattr(lib, "bpsc_drain"):
            raise ConnectionError("native client library unavailable")
        kind = 1 if host.startswith(UNIX_PREFIX) else 0
        addr = host[len(UNIX_PREFIX):] if kind else host
        self._lib = lib
        self._ct = ctypes
        self._lock = threading.Lock()
        self._cbs: Dict[int, tuple] = {}  # seq → (cb, sink keep-alive)
        self.dead = False
        #: per-server label for counter slices (set by the caller)
        self.server_label = "?"
        # mirror of the C++ lanes' mismatch tally (fed by op=-3
        # notifications) so the conn-limit escalation is counted here
        # too — the lanes themselves read the same env at bpsc_create
        from byteps_tpu.comm.transport import checksum_conn_limit

        self._ck_fails = 0
        self._ck_limit = checksum_conn_limit()
        self._on_zero_copy = on_zero_copy
        h = lib.bpsc_create(addr.encode(), port, kind, streams)
        if h < 0:
            raise ConnectionError(
                f"native client connect failed: {host}:{port}"
            )
        self._h: Optional[int] = h
        #: trace-context-aware send (None on a stale .so: trace context
        #: is then silently dropped, the pre-parity behavior)
        self._send2 = getattr(lib, "bpsc_send2", None)
        # the lanes' per-attempt round-trip histogram
        # (native_rpc_round_trip_seconds, measured send syscall →
        # completion enqueue with no ctypes/drain batching in the
        # number) merges into the process registry through the
        # histogram-provider seam (docs/observability.md)
        self._hist_provider = None
        if self._send2 is not None:
            from byteps_tpu.core.telemetry import metrics
            from byteps_tpu.native import native_client_histograms

            self._hist_provider = lambda: native_client_histograms(h)
            metrics().register_hist_provider(self._hist_provider)
        # batched-delivery buffers (bpsc_drain): a record array + payload
        # arena reused across drains; the doorbell handler is serialized
        # by _drain_lock so concurrent lane doorbells can't share them
        from byteps_tpu.native import DRAIN_REC_DTYPE

        self._drain_lock = threading.Lock()
        self._recs = np.zeros(512, dtype=DRAIN_REC_DTYPE)
        self._arena = np.zeros(1 << 20, dtype=np.uint8)
        # the CFUNCTYPE object must outlive the native lanes or the
        # trampoline is freed under a live C thread
        self._c_cb = BPSC_CALLBACK(self._on_doorbell)
        lib.bpsc_set_cb(h, self._c_cb, None)

    def _on_doorbell(self, _ctx, op, status, flags, seq, key, cmd,
                     version, payload, length, zero_copied) -> None:
        """op=-2 doorbell: the C++ completion queue went non-empty —
        drain in bulk (one trampoline per BURST instead of per message;
        the ~10-30µs ctypes marshalling cost made per-message delivery
        measurably slower on many-small-message rounds, VAN_BENCH
        r4/r5).  Any other op is bpsc_close's final per-record flush
        (the handle is out of the registry by then, so drain cannot
        deliver) — dispatch it directly."""
        if op != -2:
            try:
                if op >= 0 and not zero_copied and length:
                    body = self._ct.string_at(payload, length)
                else:
                    body = b""
                self._dispatch(op, seq, length, zero_copied, 0, key, cmd,
                               version, status, flags, None, direct=body)
            except Exception:  # noqa: BLE001 — never unwind into C
                pass
            return
        try:
            with self._drain_lock:
                while self._drain_once():
                    pass
        except Exception:  # noqa: BLE001 — never unwind into the C lane
            # a failed drain (e.g. MemoryError growing the arena) cannot
            # retry: the doorbell only fires on empty→non-empty, so the
            # queue would strand every future completion.  The connection
            # is unusable — fail every pending request loudly instead of
            # hanging its waiters.
            self._fail_pending()

    def _fail_pending(self) -> None:
        with self._lock:
            self.dead = True
            entries = list(self._cbs.values())
            self._cbs.clear()
        for entry in entries:
            try:
                entry[0](None)
            except Exception:  # noqa: BLE001
                pass

    def _drain_once(self) -> bool:
        ct = self._ct
        n = self._lib.bpsc_drain(
            self._h,
            self._recs.ctypes.data_as(ct.c_void_p),
            len(self._recs),
            self._arena.ctypes.data_as(ct.c_void_p),
            self._arena.nbytes,
        )
        if n == 0:
            return False
        if n < 0:  # first payload exceeds the arena: grow and retry
            self._arena = np.zeros(
                max(-int(n), 2 * self._arena.nbytes), dtype=np.uint8
            )
            return True
        # bulk field extraction: one vectorized .tolist() per column
        # instead of per-record numpy void indexing (~1µs per field
        # access adds up fast on small-message bursts)
        r = self._recs
        ops = r["op"][:n].tolist()
        seqs = r["seq"][:n].tolist()
        lens = r["len"][:n].tolist()
        zcs = r["zc"][:n].tolist()
        offs = r["off"][:n].tolist()
        keys = r["key"][:n].tolist()
        cmds = r["cmd"][:n].tolist()
        vers = r["version"][:n].tolist()
        stats = r["status"][:n].tolist()
        flags = r["flags"][:n].tolist()
        arena = self._arena
        for i in range(n):
            try:
                self._dispatch(
                    ops[i], seqs[i], lens[i], zcs[i], offs[i], keys[i],
                    cmds[i], vers[i], stats[i], flags[i], arena,
                )
            except Exception:  # noqa: BLE001
                # one bad callback must not strand the rest of the batch:
                # the doorbell only fires on empty→non-empty, so an
                # aborted drain would leave queued messages waiting
                # forever
                pass
        return True

    def _dispatch(self, op, seq, length, zc, off, key, cmd, version,
                  status, flags, arena, direct: Optional[bytes] = None) -> None:
        if op == -3:
            # corrupt-frame notification from the native recv lanes
            # (docs/robustness.md "Wire integrity"): the corrupt reply
            # was dropped IN C++ before the demux and the pending entry
            # stays registered (deadline/retry re-fetches) — this record
            # only carries the count to the telemetry plane.  The
            # corrupt frame's op rides in ``cmd``; ``status`` says which
            # validator rejected it (0 = CRC32C, 1 = lossless decode).
            try:
                opname = Op(cmd).name if cmd else "?"
            except ValueError:
                opname = str(cmd)
            counters().bump(
                "wire_lossless_fail" if status == 1 else "wire_checksum_fail",
                labels={
                    "side": "client", "op": opname,
                    "server": self.server_label,
                })
            self._ck_fails += 1
            if self._ck_limit and self._ck_fails == self._ck_limit:
                # the C++ lane breaks at exactly this count: record the
                # quarantine once, like the Python recv lanes do
                counters().bump("wire_checksum_conn_drop")
            return
        with self._lock:
            if op < 0:  # the connection died with this seq pending
                self.dead = True
            entry = self._cbs.pop(seq, None)
        if entry is None:
            return
        cb = entry[0]
        if op < 0:
            cb(None)
            return
        if zc:
            body = _ZERO_COPIED
            if self._on_zero_copy is not None:
                self._on_zero_copy()
        elif direct is not None:  # close-flush path: bytes already copied
            body = direct
        elif length:
            body = arena[off : off + length].tobytes()
        else:
            body = b""
        cb(Message(Op(op), key=key, payload=body, seq=seq, cmd=cmd,
                   version=version, status=status, flags=flags))

    def alloc_seq(self, cb, sink: Optional[memoryview] = None) -> int:
        sink_ptr, sink_len, keep = None, 0, None
        if sink is not None:
            # export the caller's writable buffer; the native lane
            # receives the response payload straight into it
            keep = (self._ct.c_ubyte * len(sink)).from_buffer(sink)
            sink_ptr = self._ct.addressof(keep)
            sink_len = len(sink)
        with self._lock:
            if not self.dead and self._h is not None:
                seq = self._lib.bpsc_alloc_seq(self._h, sink_ptr, sink_len)
                if seq >= 0:
                    self._cbs[seq] = (cb, keep)
                    return seq
        cb(None)  # outside the lock: callbacks run user code
        return -1

    #: the C++ lanes send on native threads: one Python sender feeds them
    push_senders = 1

    def send_msg(self, msg: Message) -> None:
        payload = msg.payload or b""
        n = len(payload)
        ptr = None
        if n:
            # no-copy pointer for bytes / bytearray / memoryview /
            # ndarray payloads alike; arr keeps the buffer alive for the
            # duration of the (synchronous) native send
            arr = np.frombuffer(payload, dtype=np.uint8)
            ptr = arr.ctypes.data
        with self._lock:
            h = self._h
        if h is None:
            raise ConnectionError("native connection closed")
        if msg.trace is not None and self._send2 is not None:
            # the (trace_id, span_id) context rides the TRACE_FLAG wire
            # block exactly as the Python transport emits it, so server
            # child spans join worker spans over the native client too
            rc = self._send2(
                h, int(msg.op), msg.seq, msg.key, msg.cmd, msg.version,
                msg.flags, ptr, n, msg.trace[0], msg.trace[1],
            )
        else:
            rc = self._lib.bpsc_send(
                h, int(msg.op), msg.seq, msg.key, msg.cmd, msg.version,
                msg.flags, ptr, n,
            )
        if rc != 0:
            raise ConnectionError("server connection lost (native send)")

    def pop_cb(self, seq: int):
        with self._lock:
            entry = self._cbs.pop(seq, None)
        return entry[0] if entry is not None else None

    def close_all(self) -> None:
        if self._hist_provider is not None:
            # fold the lanes' final latency totals into the registry
            # WHILE the handle still resolves (bpsc_close erases it)
            from byteps_tpu.core.telemetry import metrics

            metrics().absorb_hist_provider(self._hist_provider)
            self._hist_provider = None
        with self._lock:
            h, self._h = self._h, None
        if h is not None:
            # joins the native lanes; their drain fires pending callbacks
            # (cb(None)) before the join returns
            self._lib.bpsc_close(h)
        with self._lock:
            self.dead = True


#: (server rank, op, job) → the two histograms an attempt's reply observes,
#: kept at hand: ``rpc_round_trip_seconds{server[, job]}`` and
#: ``rpc_reply_seconds{op, server}``
_RPC_HISTS: Dict[tuple, tuple] = {}


def _rpc_hists(sid: str, op: str, job_id: int) -> tuple:
    held = _RPC_HISTS.get((sid, op, job_id))
    if held is None:
        labels = {"server": sid}
        if job_id:
            # per-tenant slice (docs/async.md); job 0 keeps the
            # pre-tenancy series shape
            labels["job"] = str(job_id)
        held = _RPC_HISTS[sid, op, job_id] = (
            metrics().held("rpc_round_trip_seconds", labels),
            metrics().held("rpc_reply_seconds", {"op": op, "server": sid}),
        )
    return held


class _AsyncRpc:
    """One async RPC's deadline + retry + revival state
    (:meth:`PSClient._async_rpc` has the contract).

    An object with methods, not a nest of closures: closures that name
    each other are a reference cycle, and that cycle held the RPC's
    payload, its sink and the engine's task and job until a collection.
    Whoever waits on this RPC — the connection's callback table, the
    timer wheel, the retry pool — holds a bound method of it, and it
    holds none of them back: the last one to let go frees everything."""

    __slots__ = (
        "client", "make_msg", "key", "deliver", "on_error", "sink",
        "abort_check", "precheck", "heal", "chase", "attempt", "healed",
        "chases", "backoff", "sid", "op", "t_sent_done",
    )

    def __init__(self, client, make_msg, key, deliver, on_error, sink,
                 abort_check, precheck, heal, chase) -> None:
        from byteps_tpu.comm.retry import Backoff

        self.client = client
        self.make_msg = make_msg
        self.key = key
        self.deliver = deliver
        self.on_error = on_error
        self.sink = sink
        self.abort_check = abort_check
        self.precheck = precheck
        self.heal = heal
        self.chase = chase
        self.attempt = 0
        self.healed = False
        self.chases = 0
        self.backoff = Backoff(base=client.cfg.rpc_backoff_s, cap=2.0)
        #: the newest attempt's request op, and the moment its send_msg
        #: returned (0.0 while it has not: a reply can beat the return)
        self.op = "?"
        self.t_sent_done = 0.0
        # server-rank label for the robustness counters: a single sick
        # server must be visible in the per-peer dimension, not just as
        # an anonymous bump of the flat total (docs/observability.md)
        try:
            self.sid = str(client.server_for(key))
        except (ValueError, ZeroDivisionError, IndexError, ConnectionError):
            self.sid = "?"

    def aborted_cleanup(self) -> bool:
        """True (and routes to on_error) when the op is abandoned."""
        if self.abort_check is not None and self.abort_check():
            if self.on_error is not None:
                self.on_error()
            return True
        return False

    def finish_fail(self) -> None:
        counters().bump("rpc_giveup", labels={"server": self.sid})
        if self.on_error is not None:
            self.on_error()

    def fail(self) -> None:
        # retries exhausted: before surfacing the error, try the
        # in-place heal ONCE — resync the server's authoritative
        # ledger, replay journaled pushes it never absorbed, then
        # re-attempt this RPC (docs/robustness.md "healing flow").
        # Off this thread: the heal blocks in dials and recovery
        # RPCs, and fail() can fire from a recv-loop drain.
        client = self.client
        if (self.heal and not self.healed and not client._stop.is_set()
                and client.cfg.resync_deadline_s > 0):
            self.healed = True
            client._dispatch_retry(self.heal_and_resend)
            return
        self.finish_fail()

    def heal_and_resend(self) -> None:
        if self.aborted_cleanup():
            return
        if self.client._heal_in_place(self.key, self.sid):
            self.attempt = 0
            self.send_attempt()
        else:
            self.finish_fail()

    def retry_later(self) -> None:
        client = self.client
        if self.aborted_cleanup():
            return  # abandoned: no resend, cleanup via on_error
        if client._stop.is_set() or self.attempt >= client.cfg.rpc_retries:
            self.fail()
            return
        self.attempt += 1
        counters().bump("rpc_retry", labels={"server": self.sid})
        # timer wheel, not threading.Timer: no per-retry thread churn
        client._timer_after(self.backoff.next_delay(), self.send_attempt)

    def chase_redirect(self, msg: Message) -> None:
        # Op.WRONG_OWNER: the server holds a NEWER ownership map —
        # this key migrated (docs/robustness.md "migration flow").
        # Wait (bounded) for the book that map rode in on, then
        # resend: routing re-runs per attempt, so the resend lands on
        # the new owner, whose migrated per-(worker, key) ledger
        # dedupes anything the old owner already summed.  A chase
        # does not consume the retry budget (the server answered;
        # nothing failed) but is capped so a pathological ping-pong
        # still surfaces an error instead of looping forever.
        client = self.client
        counters().bump("wrong_owner_redirect", labels={"server": self.sid})
        if self.aborted_cleanup():
            return
        if not self.chase:
            # fused frames never chase: the new map may scatter the
            # pack's members across servers, so resending the intact
            # frame just ping-pongs — the caller's error path (engine
            # unfuse fallback) regroups into per-key RPCs that each
            # chase on their own
            self.fail()
            return
        self.chases += 1
        if client._stop.is_set() or self.chases > client._max_chases:
            self.fail()
            return
        # off the recv thread: the map-epoch wait blocks
        client._dispatch_retry(functools.partial(self.rechase, msg.version))

    def rechase(self, target: int) -> None:
        if self.aborted_cleanup():
            return
        self.client._wait_map_epoch(
            target, timeout=min(2.0, 0.25 * self.chases)
        )
        self.send_attempt()

    def send_attempt(self) -> None:
        client = self.client
        if self.aborted_cleanup():
            return
        if client._stop.is_set() or (
            self.precheck is not None and not self.precheck()
        ):
            self.fail()
            return
        try:
            sc = client._conn_for(self.key, revive=self.attempt > 0)
        except (ConnectionError, OSError):
            self.retry_later()
            return
        # arm BEFORE alloc: alloc_seq on a dead connection fires
        # on_reply(None) synchronously, which must find the token
        token = client._deadline_arm(sc, self.sid)
        on_reply = functools.partial(self.on_reply, token, time.monotonic())
        seq = sc.alloc_seq(on_reply, sink=self.sink)
        if seq < 0:
            return  # on_reply(None) already fired → retry scheduled
        msg = self.make_msg(seq)
        self.op = msg.op.name
        self.t_sent_done = 0.0
        try:
            # lane lock + frame + sendmsg: the part of this attempt that is
            # the sender's (under bps.stage.PUSH on a stage thread), named
            # like that thread's account (PUSH's second: ".1").  A PULL is
            # a 50-byte request under bps.stage.PULL: no span of its own
            if self.op == "PULL":
                sc.send_msg(msg)
            else:
                with span("rpc.send." + self.op + thread_tag()):
                    sc.send_msg(msg)
            self.t_sent_done = time.monotonic()
            # every frame that actually hit the wire (incl. retries):
            # what fusion lowers (tests/test_fusion.py compares it)
            counters().bump("wire_rpc")
        except (ConnectionError, OSError):
            # died between alloc and send: claim the callback — if the
            # drain beat us to it, on_reply(None) already retried
            if sc.pop_cb(seq) is not None:
                client._deadline_clear(token)
                self.retry_later()

    def on_reply(self, token, t_sent: float, msg: Optional[Message]) -> None:
        """One attempt's reply (``None``: its connection died)."""
        client = self.client
        client._deadline_clear(token)
        if msg is None:
            self.retry_later()
        elif msg.op == Op.WRONG_OWNER:
            self.chase_redirect(msg)
        elif self.aborted_cleanup():
            pass  # late success on an abandoned op: cleanup only
        else:
            # per-ATTEMPT round trip (retries each time their own
            # attempt; the retry cost itself shows up in
            # retry_backoff_seconds + the rpc_retry counter).
            # Labeled per server RANK like the rpc_* counters:
            # the flight recorder's straggler rule needs "whose
            # p99 ran away THIS step", which a flat family can
            # never answer (docs/observability.md).  Beside it the
            # attempt's second half by op: send returned → reply, the
            # server's and the wire's part (a PUSH's 4 MB sendmsg is in
            # the round trip and not in this)
            now = time.monotonic()
            round_trip, reply = _rpc_hists(
                self.sid, self.op, client.cfg.job_id)
            round_trip.observe(now - t_sent)
            reply.observe(now - (self.t_sent_done or t_sent))
            self.deliver(msg)


class PSClient:
    # class-level defaults for the elastic resharding surface: stub
    # clients (tests build them with ``__new__``) and pre-resharding
    # pickles route legacy without tripping AttributeError
    reshard = False
    map_epoch = 0
    _ownership = None
    _routing: tuple = ((), (), None)
    _max_chases = 8
    #: highest scheduler incarnation seen in a book (zombie fence;
    #: docs/robustness.md "Control-plane recovery")
    sched_incarnation = 0
    _sched_reconnecting = False
    _sched_terminal = False
    _seen_map_epoch = 0
    _seen_ring_overrides: dict = {}
    _reconnect_token = 0
    #: adaptive control plane (docs/autotune.md): the newest adopted
    #: ``tuning`` section + its epoch; class-level defaults keep
    #: __new__-built test stubs and pre-tuner pickles safe
    tuning: Optional[dict] = None
    _tuning_epoch = 0
    _tuning_listeners: tuple = ()

    def __init__(self, cfg: Config, node_uid: Optional[str] = None) -> None:
        self.cfg = cfg
        from byteps_tpu.common.config import resolve_node_uid

        self.node_uid = resolve_node_uid(node_uid)
        self.rank: Optional[int] = None
        self.num_workers = cfg.num_worker
        self.num_servers = cfg.num_server
        self._sched: Optional[socket.socket] = None
        self._sched_lock = threading.Lock()
        self._sched_cbs: Dict[int, threading.Event] = {}
        self._sched_cb_lock = threading.Lock()
        self._sched_seq = 0
        self._sched_dead = False  # set when the scheduler recv loop exits
        # --- control-plane recovery (docs/robustness.md) ---
        # scheduler-link loss no longer latches this node dead: the recv
        # loop's exit hands off to a reconnect state machine that redials
        # the scheduler address with bounded backoff and re-REGISTERs
        # (uid + last-known rank + epochs), while the DATA plane keeps
        # training on the last-adopted book — control_plane_degraded
        # mode.  _sched_up is set while the link is healthy; _sched_
        # terminal marks a reconnect give-up (the legacy latch) so
        # waiters (barrier retries) fail instead of parking forever.
        self.sched_incarnation = 0
        self._sched_up = threading.Event()
        self._sched_terminal = False
        self._sched_reconnecting = False
        #: ownership generation of the ACTIVE reconnect machine: under
        #: repeated link chaos a machine's cleanup can race the next
        #: machine spawned by the recv loop it itself started — only the
        #: holder of the current token may clear flags or latch terminal
        self._reconnect_token = 0
        self._seen_map_epoch = 0
        self._seen_ring_overrides = {}
        self._servers: List[_ServerConn] = []
        self._server_addrs: List[tuple] = []
        #: bumped whenever the server list is rebuilt (elastic server
        #: resize): the engine re-runs each key's init-push barrier — and
        #: re-ships compressor configs — against the new owners before the
        #: key's next use
        self.server_generation = 0
        self._stop = threading.Event()
        self._rebuild_lock = threading.Lock()  # serializes live server swaps
        self._book_token = 0     # RESIZE_SEQ arrival counter (sched thread)
        self._applied_token = 0  # newest book actually applied
        self.is_recovery = False
        #: responses whose payloads landed directly in caller buffers
        self.zero_copy_pulls = 0
        #: newest membership epoch seen in a scheduler book (eviction /
        #: adoption / resize broadcasts bump it; docs/robustness.md)
        self.membership_epoch = 0
        # --- elastic resharding (docs/robustness.md "migration flow") ---
        # ownership = epoch-stamped consistent-hash ring over server
        # RANKS, adopted from books atomically with the connection list
        # (one _routing snapshot: a key routes against the count/list/map
        # it was hashed under, never a mixed pair).  A reply of
        # Op.WRONG_OWNER means the server knows a newer map: the RPC
        # waits (bounded) for its book, re-routes, and resends — the
        # chase; journal replay and init retries chase the same way.
        self.reshard = cfg.elastic_reshard
        #: newest adopted ownership-map epoch; _map_cv is notified on
        #: every adoption so redirect chases can wait for their book
        self.map_epoch = 0
        self._map_cv = threading.Condition()
        self._ownership = None  # OwnershipMap or None (legacy routing)
        #: (servers, ranks, ownership) swapped as ONE atomic snapshot
        self._routing: tuple = ([], [], None)
        #: WRONG_OWNER chases per RPC before surfacing the error
        self._max_chases = 8
        # --- per-RPC deadline machinery (BYTEPS_RPC_DEADLINE_S) ---
        # token → (conn, expire_at); a scanner thread tears down the
        # connection of any RPC that blows its deadline — the drain then
        # fires every pending callback with None and the retry layer takes
        # over.  Lazy: the thread starts on the first armed deadline.
        #
        # The same thread doubles as the retry TIMER WHEEL: backoff-delayed
        # resend callbacks park in a heap and FIRE from the scanner loop,
        # replacing one short-lived threading.Timer thread per retry (at
        # chaos-test retry rates that churn was hundreds of thread spawns
        # per second).  Due callbacks EXECUTE on a small persistent
        # executor pool (bps-rpc-retry-*, grown on backlog to a fixed
        # cap), never the scanner itself: a resend can block — revival
        # dial, or send_msg into the full socket buffer of a hung server —
        # and the ONLY thing that unblocks a wedged send is the scanner
        # expiring that connection's deadline and tearing it down, so the
        # scanner must never be the thread doing the sending.  Bounded
        # thread count, zero per-retry churn.
        self._rpc_tokens = itertools.count()
        self._outstanding: Dict[int, tuple] = {}
        self._outstanding_lock = threading.Lock()
        self._scan_cv = threading.Condition(self._outstanding_lock)
        self._timers: list = []  # heap of (fire_at, tiebreak, fn)
        self._deadline_thread: Optional[threading.Thread] = None
        import queue as _queue

        self._retry_q: "_queue.Queue" = _queue.Queue()
        # executor POOL, grown lazily to a small cap: resends serialize
        # per thread, and one resend can block in a revival dial to a
        # black-holed server — a healthy server's 0.1s-backoff retry must
        # not queue behind it for the dial timeout.  Threads persist
        # (zero per-retry churn); the cap bounds the footprint.
        self._retry_threads: List[threading.Thread] = []
        self._retry_pool_cap = 4
        # --- recovery plane (docs/robustness.md "healing flow") ---
        # per-server heal serialization: concurrent give-ups against one
        # server collapse into a single resync (the generation counter
        # lets late arrivals ride a heal that completed while they waited)
        self._heal_meta_lock = threading.Lock()
        self._heal_locks: Dict[str, threading.Lock] = {}
        self._heal_gen: Dict[str, int] = {}
        # init-idempotency tokens: per-key init sequence, salted per
        # client instance so a restarted process (or a post-shutdown
        # re-init) can never collide with a previous generation's
        # completed-barrier record on the server
        import random as _random

        self._init_seq_lock = threading.Lock()
        self._init_seqs: Dict[int, int] = {}
        self._init_salt = _random.SystemRandom().getrandbits(16)
        # --- adaptive control plane (docs/autotune.md) ---
        # listeners (the engine) run on every NEWER tuning adoption;
        # registration replays the current section so an engine built
        # after connect() (the normal init order) still sees it
        self.tuning = None
        self._tuning_epoch = 0
        self._tuning_listeners: list = []

    # --- rendezvous ------------------------------------------------------

    def connect(self) -> None:
        """Register with the scheduler and connect to every server
        (GetOrInitPS, global.cc:283-297)."""
        from byteps_tpu.comm.transport import connect_control

        self._sched = connect_control(
            self.cfg.ps_root_uri, self.cfg.ps_root_port
        )
        send_message(
            self._sched,
            Message(
                Op.REGISTER,
                payload=json.dumps(
                    {
                        "role": "worker",
                        "host": "",
                        "port": 0,
                        "uid": self.node_uid,
                        # a re-register after resume(num_workers=±k) carries
                        # the NEW expected topology — the scheduler adopts it
                        # (elastic world-size change, operations.cc:96-119)
                        "num_workers": self.cfg.num_worker,
                        "num_servers": self.cfg.num_server,
                        # multi-tenant identity + QoS (docs/async.md): the
                        # scheduler builds the per-job membership map and
                        # the servers' service weights / admission quotas
                        # from these
                        "job": self.cfg.job_id,
                        "job_priority": self.cfg.job_priority,
                        "job_quota_mbps": self.cfg.job_quota_mbps,
                    }
                ).encode(),
            ),
        )
        resp = recv_message(self._sched)
        if resp.status != 0:
            err = json.loads(resp.payload.decode()).get("error", "register refused")
            raise RuntimeError(f"scheduler refused registration: {err}")
        book = json.loads(resp.payload.decode())
        self.rank = book["rank"]
        self.num_workers = self._book_num_workers(book)
        self.num_servers = book["num_servers"]
        self.is_recovery = book.get("is_recovery", False)
        self._fence_book(book)  # learn the scheduler's incarnation
        self._note_membership(book)
        self._sched_up.set()
        # the degraded-state gauge exists from bring-up so bps_top can
        # count healthy (0) vs degraded (1) nodes in the aggregate
        metrics().gauge_set("control_plane_degraded", 0)
        self._server_addrs = [tuple(s) for s in book["servers"]]
        for host, port in self._server_addrs:
            sc = self._new_conn(host, port)
            sc.server_label = str(len(self._servers))
            self._servers.append(sc)
        self._install_routing(
            self._servers, book.get("server_ranks"),
            self._ownership_from_book(book),
        )
        # scheduler receiver for barrier responses
        t = threading.Thread(target=self._sched_recv_loop, daemon=True)
        t.start()
        # periodic heartbeat to the scheduler (ps-lite heartbeat parity;
        # knob: BYTEPS_HEARTBEAT_INTERVAL via Config)
        if self.cfg.heartbeat_interval > 0:
            threading.Thread(
                target=self._heartbeat_loop,
                args=(self.cfg.heartbeat_interval,),
                daemon=True,
            ).start()
        # global barrier mirrors Postoffice::Barrier at init
        # (global.cc:289-294).  On elastic rejoin the scheduler releases
        # the recovering node's barrier immediately (the rest of the
        # cluster is mid-training, not waiting at a barrier).
        self.barrier(GROUP_ALL)

    def close(self) -> None:
        self._stop.set()
        with self._outstanding_lock:
            # wake the deadline/timer scanner so it exits (and drains any
            # parked retry timers through their stop-check fail path)
            self._scan_cv.notify_all()
        for sc in self._servers:
            sc.close_all()
        close_socket(self._sched)
        self._servers = []

    def _sched_request(self, msg: Message,
                       timeout: Optional[float] = None) -> Message:
        """Send a scheduler request and wait for its seq-matched response.
        Raises ConnectionError if the scheduler link is dead or dies while
        waiting — or, with ``timeout``, when no response arrives in time
        (a chaos-dropped control frame would otherwise park the caller
        forever on a healthy connection; heartbeats pass one)."""
        with self._sched_cb_lock:
            if self._sched_dead:
                raise ConnectionError("scheduler connection lost")
            seq = self._sched_seq
            self._sched_seq += 1
            ev = threading.Event()
            box: list = []
            self._sched_cbs[seq] = (ev, box)
        msg.seq = seq
        send_message(self._sched, msg, self._sched_lock)
        if not ev.wait(timeout):
            with self._sched_cb_lock:
                self._sched_cbs.pop(seq, None)
            raise ConnectionError("scheduler request timed out")
        if not box:
            raise ConnectionError("scheduler connection lost")
        return box[0]

    def _fence_book(self, book: dict) -> bool:
        """Incarnation fence (docs/robustness.md "Control-plane
        recovery"): refuse a book stamped with an OLDER scheduler
        incarnation than one this node already acted on — a zombie
        scheduler racing its restarted successor must not roll the
        topology back (the control-plane twin of the zombie-worker
        fence).  Adopts a newer incarnation on accept.  Books without
        the stamp (older schedulers) always pass."""
        inc = int(book.get("sched_incarnation", 0) or 0)
        if inc and self.sched_incarnation and inc < self.sched_incarnation:
            counters().bump("sched_stale_book")
            return False
        if inc > self.sched_incarnation:
            if self.sched_incarnation:
                # scheduler REBIRTH: the successor's tuner numbering
                # restarts, so the monotone adoption fence must re-arm
                # or its decisions would be refused while the dead
                # incarnation's tuning stayed live forever.  -1 (not 0)
                # so even an epoch-0 initial section adopts.  The
                # successor normally RE-ADOPTS the fleet's live state
                # from the survivors' rejoin reports (_tuning_report →
                # AutoTuner.adopt_rejoin_report), so its first book
                # confirms the running decisions; only a tunerless
                # successor (BYTEPS_AUTOTUNE off) ships an empty
                # section, deliberately reverting the fleet to launch
                # values.
                self._tuning_epoch = -1
            self.sched_incarnation = inc
        return True

    def _note_membership(self, book: dict) -> None:
        """Track the scheduler's membership epoch + cumulative eviction
        totals from an address book (observability; docs/robustness.md)."""
        epoch = book.get("epoch")
        if epoch is not None and epoch > self.membership_epoch:
            self.membership_epoch = epoch
        # newest map epoch SEEN in any book — tracked independently of
        # the resharding feature (which only adopts maps when on), so a
        # rejoin re-REGISTER always reports what this node observed and
        # a reborn scheduler fences above it
        me = book.get("map_epoch")
        if me is not None and int(me) >= self._seen_map_epoch:
            self._seen_map_epoch = int(me)
            # newest placement overrides seen in any book: they ride the
            # rejoin report (_tuning_report) so a reborn scheduler can
            # re-adopt placement instead of migrating every overridden
            # key home on its first book
            self._seen_ring_overrides = dict(
                book.get("ring_overrides") or {}
            )
        ev = book.get("evictions") or {}
        for role, name in (("worker", "worker_evicted"),
                           ("server", "server_evicted")):
            if ev.get(role):
                counters().set_floor(name, int(ev[role]))
        self._adopt_tuning(book)

    def _adopt_tuning(self, book: dict) -> None:
        """Adopt a book's ``tuning`` section (docs/autotune.md) when it
        is NEWER than the one already applied — monotone by tuning
        epoch, so a re-broadcast or a racing stale book can never roll
        a fleet decision back.  Listeners (the engine's _apply_tuning)
        run outside any routing lock; a listener error must never
        poison book adoption."""
        t = book.get("tuning")
        if not isinstance(t, dict):
            if self.tuning is not None:
                # the control plane no longer runs a tuner (toggled off,
                # or a reborn scheduler without BYTEPS_AUTOTUNE): revert
                # to legacy — an empty section makes the engine restore
                # its launch fusion threshold and re-enable fleet-
                # disabled codecs.  Once, not per book.
                self.tuning = None
                self._tuning_epoch = 0
                for cb in tuple(self._tuning_listeners):
                    try:
                        cb({})
                    except Exception as e:  # noqa: BLE001
                        from byteps_tpu.common import logging as bpslog

                        bpslog.warning("tuning listener failed: %r", e)
            return
        try:
            epoch = int(t.get("epoch", 0) or 0)
        except (TypeError, ValueError):
            return
        if self.tuning is not None and epoch <= self._tuning_epoch:
            return
        self._tuning_epoch = epoch
        self.tuning = dict(t)
        for cb in tuple(self._tuning_listeners):
            try:
                cb(self.tuning)
            except Exception as e:  # noqa: BLE001
                from byteps_tpu.common import logging as bpslog

                bpslog.warning("tuning listener failed: %r", e)

    def _tuning_report(self) -> Optional[dict]:
        """The fleet-tuning state this node last adopted — the rejoin
        REGISTER carries it so a RESTARTED scheduler's tuner re-adopts
        the live decisions (docs/autotune.md "Rollback flow") instead
        of reverting them with its empty epoch-0 state.  None when no
        tuner ever armed (the report field stays absent and the legacy
        wire is byte-identical)."""
        if self.tuning is None:
            return None
        rep = dict(self.tuning)
        if self._seen_ring_overrides:
            rep["ring_overrides"] = dict(self._seen_ring_overrides)
        return rep

    def add_tuning_listener(self, cb) -> None:
        """Register a fleet-tuning consumer; replays the current
        section immediately (the initial book lands in connect(),
        BEFORE the engine exists to listen)."""
        if not isinstance(self._tuning_listeners, list):
            self._tuning_listeners = []  # stub built via __new__
        self._tuning_listeners.append(cb)
        if self.tuning is not None:
            try:
                cb(self.tuning)
            except Exception as e:  # noqa: BLE001
                from byteps_tpu.common import logging as bpslog

                bpslog.warning("tuning listener failed: %r", e)

    def _book_num_workers(self, book: dict) -> int:
        """The worker count THIS client aggregates over.  Multi-tenant
        books (docs/async.md) carry a per-job membership map — a tenant
        job's rounds involve only ITS workers, so averaging and the
        round-completion expectation use the job's population, not the
        fleet's.  Single-tenant books (no ``jobs`` field, or job 0 not
        split out) fall back to the fleet total, the pre-tenancy
        behavior."""
        jobs = book.get("jobs")
        if jobs:
            # job 0 included: in a MIXED fleet (tenant workers present)
            # the default-namespace job's rounds also complete against
            # only ITS workers, so averaging over the fleet total would
            # divide by the wrong population.  Single-job books yield
            # len == num_workers, the pre-tenancy value.
            mine = jobs.get(str(self.cfg.job_id))
            if mine and mine.get("workers"):
                return len(mine["workers"])
        return book["num_workers"]

    def _ownership_from_book(self, book: Optional[dict]):
        """Build the book's OwnershipMap, or None (resharding off, or an
        older scheduler whose books carry no map)."""
        if not self.reshard or not book:
            return None
        ranks = book.get("server_ranks")
        epoch = book.get("map_epoch")
        if not ranks or epoch is None:
            return None
        from byteps_tpu.common.hashing import OwnershipMap

        return OwnershipMap(
            ranks, epoch=int(epoch), vnodes=self.cfg.ring_vnodes,
            # autotuner hot-key rebalance (docs/autotune.md): per-key
            # placement overrides ride beside the map epoch as one
            # versioned placement
            overrides=book.get("ring_overrides"),
        )

    def _install_routing(self, servers, ranks, omap) -> None:
        """Swap the (connections, ranks, ownership) routing snapshot as
        one atomic reference, and wake redirect chases waiting for the
        map epoch the new book carries."""
        self._routing = (servers, list(ranks or []), omap)
        with self._map_cv:
            self._ownership = omap
            if omap is not None and omap.epoch > self.map_epoch:
                self.map_epoch = omap.epoch
            self._map_cv.notify_all()

    def _wait_map_epoch(self, epoch: int, timeout: float) -> bool:
        """Block until this client's adopted map epoch reaches ``epoch``
        (the epoch a WRONG_OWNER redirect carried) or ``timeout`` —
        chasing before the book lands would just re-route with the same
        stale map."""
        with self._map_cv:
            return self._map_cv.wait_for(
                lambda: self.map_epoch >= epoch or self._stop.is_set(),
                timeout,
            )

    def request_resize(self, num_workers: Optional[int] = None,
                       num_servers: Optional[int] = None) -> dict:
        """Ask the scheduler to adopt a new expected topology from THIS
        live worker — the wire shape of elastic ``resume(num_servers=±k)``
        (a re-REGISTER carrying the new expectation) without tearing the
        runtime down.  Blocks until the scheduler can answer (a scale-up
        reply parks until the new server registers), adopts the returned
        book, and returns it.  With BYTEPS_ELASTIC_RESHARD the resize is
        a live migration: servers ship re-homed keys to the new owners
        and no re-init barrier fires (docs/robustness.md "migration
        flow")."""
        payload = json.dumps({
            "role": "worker", "host": "", "port": 0, "uid": self.node_uid,
            "num_workers": int(num_workers or self.num_workers),
            "num_servers": int(num_servers or self.num_servers),
        }).encode()
        resp = self._sched_request(Message(Op.REGISTER, payload=payload))
        if resp.status != 0:
            err = json.loads(resp.payload.decode()).get("error", "refused")
            raise RuntimeError(f"scheduler refused resize: {err}")
        book = json.loads(resp.payload.decode())
        if not self._fence_book(book):
            raise ConnectionError("resize book from a stale scheduler incarnation")
        self.num_workers = self._book_num_workers(book)
        self._note_membership(book)
        with self._sched_cb_lock:
            self._book_token += 1
            token = self._book_token
        self._rebuild_servers(
            book["num_servers"], [tuple(s) for s in book["servers"]],
            token, book=book,
        )
        return book

    def barrier(self, group: int = GROUP_WORKERS) -> None:
        """Scheduler barrier.  Rides through a scheduler crash: a wait
        broken by link loss re-arms against the successor once the
        reconnect machine rejoins (the restarted scheduler's barrier
        table starts empty, and every surviving participant re-sends, so
        pairing stays correct).  Raises ConnectionError only once the
        reconnect machine has terminally given up."""
        while True:
            try:
                self._sched_request(Message(Op.BARRIER, flags=group))
                return
            except ConnectionError:
                if self._stop.is_set() or not self._await_control_plane():
                    raise

    def _await_control_plane(self, poll: float = 0.25) -> bool:
        """Block until the scheduler link is healthy again (True) or the
        reconnect machine gave up / the client closed (False).  The wait
        is bounded by the reconnect machine itself: it either rejoins or
        sets the terminal latch within its retry budget."""
        while not self._stop.is_set():
            if self._sched_up.wait(poll):
                return True
            with self._sched_cb_lock:
                if self._sched_terminal and not self._sched_reconnecting:
                    return False
        return False

    def query_cluster(self) -> dict:
        """Heartbeat ages per node from the scheduler (failure detection,
        SURVEY §5.3)."""
        from byteps_tpu.comm.transport import decode_liveness

        return decode_liveness(self._sched_request(Message(Op.QUERY)).payload)

    def _heartbeat_loop(self, interval: float) -> None:
        beat_incarnation = None
        while not self._stop.is_set():
            if self._stop.wait(interval):
                return
            with self._sched_cb_lock:
                if self._sched_dead:
                    # control_plane_degraded: the reconnect machine owns
                    # the link — keep ticking (a single send failure must
                    # never permanently end all future beats; the fix for
                    # the terminal-return latch, docs/robustness.md)
                    continue
            inc = self.sched_incarnation
            if inc != beat_incarnation:
                # first beat to a NEW scheduler incarnation ships the
                # FULL metric history, not a delta against baselines the
                # dead scheduler took to its grave — the successor's
                # aggregate starts empty.  reship_for is idempotent per
                # incarnation (in-process fleets share one registry).
                metrics().reship_for(inc)
                beat_incarnation = inc
            # piggyback this process's metric DELTAS on the beat: the
            # scheduler folds them into its cluster-wide aggregate
            # registry (served on its own BYTEPS_METRICS_PORT), so one
            # scrape of the scheduler sees the whole job without the
            # scraper having to discover every worker's endpoint
            delta = metrics().delta_snapshot()
            # flight-recorder ledger tail (docs/observability.md "Flight
            # recorder & doctor"): a compact window of recent per-step
            # records rides every beat so the scheduler holds a
            # cluster-wide step matrix.  Idempotent — the window is
            # re-shipped and the scheduler dedupes by step index, so a
            # lost beat costs nothing and the requeue path (which only
            # folds metric increments) never needs to know about it.
            from byteps_tpu.core.flightrec import get_process_recorder

            rec = get_process_recorder()
            ups = None
            if rec is not None and rec.enabled:
                tail = rec.ledger_tail()
                if tail:
                    delta["fr"] = tail
                # fleet-central bundle upload (BYTEPS_FLIGHT_UPLOAD):
                # compact trigger bundles ride the beat to the
                # scheduler's BYTEPS_FLIGHT_DIR.  Taken (not re-shipped
                # like the tail) — a failed beat gives them back below.
                ups = rec.take_uploads()
                if ups:
                    delta["fb"] = ups
            try:
                payload = json.dumps(delta).encode() if delta else b""
                # bounded wait: a chaos-dropped PING on a healthy link
                # must cost one beat, not park this thread forever
                self._sched_request(
                    Message(Op.PING, payload=payload),
                    timeout=max(2.0, 4 * interval),
                )
            except (ConnectionError, OSError):
                # the delta was consumed from the shipped baselines but
                # may never have been delivered — give it back for the
                # next beat (or a successor control plane).  Delivery
                # toward the aggregate is AT-LEAST-ONCE by design
                # (docs/observability.md): a timed-out beat whose
                # request actually landed re-ships its increments, a
                # deliberate over-count bias — losing increments would
                # silently understate degradation, which is worse.
                metrics().requeue_delta(delta)
                if ups and rec is not None:
                    rec.requeue_uploads(ups)
                continue

    def _sched_recv_loop(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    msg = recv_message(self._sched)
                except (ConnectionError, OSError):
                    return
                if msg.op == Op.ADDRBOOK and msg.seq == RESIZE_SEQ:
                    # another worker resized the cluster: adopt the worker
                    # count (averaging reads it live) and, on a SERVER
                    # resize, rebuild the connection set — key→server
                    # routing follows num_servers automatically and the
                    # engine re-inits keys on their new owners
                    # (server_generation bump)
                    book = json.loads(msg.payload.decode())
                    if not self._fence_book(book):
                        # zombie scheduler racing its restarted
                        # successor: refuse the stale-incarnation book
                        continue
                    self.num_workers = self._book_num_workers(book)
                    self._note_membership(book)
                    new_addrs = [tuple(s) for s in book["servers"]]
                    # token = book arrival order on THIS (single) thread:
                    # rebuild threads acquire the lock in arbitrary order,
                    # so staleness is decided by token, not address
                    # equality.  EVERY book spawns a rebuild — even one
                    # matching the live set (a rollback can race a failed
                    # rebuild's delayed retry; the no-op case is detected
                    # under the rebuild lock, where it is atomic with any
                    # in-flight apply).  Rebuild OFF this thread: connects
                    # can block/fail and must neither stall scheduler
                    # callback delivery nor kill this loop (→ _sched_dead)
                    with self._sched_cb_lock:
                        self._book_token += 1
                        token = self._book_token
                    threading.Thread(
                        target=self._rebuild_servers,
                        args=(book["num_servers"], new_addrs, token),
                        kwargs={"book": book},
                        daemon=True,
                    ).start()
                    continue
                with self._sched_cb_lock:
                    entry = self._sched_cbs.pop(msg.seq, None)
                if entry is not None:
                    ev, box = entry
                    box.append(msg)
                    ev.set()
        finally:
            # wake every pending waiter with an empty box → they raise
            # ConnectionError instead of hanging on a dead scheduler; flag
            # the link dead so LATER _sched_request calls fail fast instead
            # of registering callbacks nobody will ever drain
            with self._sched_cb_lock:
                self._sched_dead = True
                self._sched_up.clear()
                pending = list(self._sched_cbs.values())
                self._sched_cbs.clear()
                spawn_reconnect = (
                    not self._stop.is_set()
                    and not self._sched_reconnecting
                )
                latch_terminal = False
                token = 0
                if spawn_reconnect:
                    if self.cfg.sched_reconnect_retries > 0:
                        self._sched_reconnecting = True
                        self._reconnect_token += 1
                        token = self._reconnect_token
                    else:
                        # legacy terminal latch (BYTEPS_SCHED_RECONNECT_
                        # RETRIES=0): degraded forever, waiters fail fast
                        self._sched_terminal = True
                        latch_terminal = True
                        spawn_reconnect = False
            for ev, _ in pending:
                ev.set()
            if latch_terminal:
                # the gauge must still report the outage even though no
                # reconnect machine will run
                metrics().gauge_set("control_plane_degraded", 1)
            if spawn_reconnect:
                # hand off to the reconnect state machine instead of
                # latching dead: the data plane keeps training on the
                # last-adopted book while this node redials the
                # scheduler address (control_plane_degraded mode,
                # docs/robustness.md "Control-plane recovery")
                metrics().gauge_set("control_plane_degraded", 1)
                threading.Thread(
                    target=self._sched_reconnect_loop, args=(token,),
                    name="bps-sched-reconnect", daemon=True,
                ).start()

    # --- control-plane reconnect state machine ---------------------------
    #
    # docs/robustness.md "Control-plane recovery".  Scheduler-link loss
    # used to latch `_sched_dead` terminally: one `kill -9` of the
    # scheduler and the job could never resize, evict, reshard, or
    # aggregate metrics again — even though the worker↔server data plane
    # was perfectly healthy.  Instead the node enters control_plane_
    # degraded mode (data plane trains on the last-adopted book) while
    # this machine redials the scheduler address with bounded backoff
    # and re-REGISTERs carrying its uid, last-known rank, and the
    # membership/map epochs it acted under — a restarted scheduler
    # rebuilds its registration table from exactly these reports.

    def _sched_reconnect_loop(self, token: int = 0) -> None:
        from byteps_tpu.comm.retry import Backoff

        from byteps_tpu.common import logging as bpslog

        backoff = Backoff(
            base=max(0.05, self.cfg.sched_reconnect_backoff_s), cap=10.0
        )
        attempts = 0
        try:
            while not self._stop.is_set():
                if attempts >= self.cfg.sched_reconnect_retries:
                    bpslog.warning(
                        "scheduler reconnect gave up after %d attempts — "
                        "control plane is down for good (data plane "
                        "continues on the last book)", attempts,
                    )
                    with self._sched_cb_lock:
                        if self._reconnect_token == token:
                            self._sched_terminal = True
                    return
                attempts += 1
                counters().bump("sched_reconnect")
                sock = None
                try:
                    sock, book = self._sched_re_register()
                except (ConnectionError, OSError, RuntimeError, ValueError):
                    if sock is not None:
                        close_socket(sock)
                    if self._stop.wait(backoff.next_delay()):
                        return
                    continue
                if book is None:
                    # register answered by a STALE incarnation (zombie
                    # scheduler still bound to the address): refuse and
                    # redial — the successor will win the port
                    close_socket(sock)
                    if self._stop.wait(backoff.next_delay()):
                        return
                    continue
                self._adopt_rejoin(sock, book)
                return
        finally:
            latch = False
            with self._sched_cb_lock:
                if self._reconnect_token == token and self._sched_reconnecting:
                    # loop exiting WITHOUT a successful adopt (give-up,
                    # stop, or an unexpected error unwinding this
                    # thread): latch terminal so barrier retries fail
                    # instead of polling a machine that no longer
                    # exists.  The token gate matters: a successful
                    # _adopt_rejoin hands ownership to the recv loop it
                    # spawns, and if THAT loop already died and spawned
                    # the next machine (token advanced), this exiting
                    # one must not clear the successor's flag or latch
                    # terminal over its live retry budget.
                    self._sched_reconnecting = False
                    if self._sched_dead:
                        self._sched_terminal = True
                        latch = True
            if latch:
                metrics().gauge_set("control_plane_degraded", 1)

    def _sched_re_register(self):
        """One redial + re-REGISTER attempt → (socket, book).  The book
        is None when a zombie (stale-incarnation) scheduler answered.
        Blocks in recv until the scheduler replies — a RESTARTED
        scheduler parks the reply until its population completes or its
        rejoin grace window expires, and this thread is the right place
        to wait that out."""
        from byteps_tpu.comm.transport import connect_control

        sock = connect_control(self.cfg.ps_root_uri, self.cfg.ps_root_port)
        try:
            payload = json.dumps({
                "role": "worker", "host": "", "port": 0,
                "uid": self.node_uid,
                # LIVE topology expectation, not the launch-time config:
                # the cluster may have been resized since
                "num_workers": self.num_workers,
                "num_servers": self.num_servers,
                # state-reconstruction report for a reborn scheduler
                "last_rank": self.rank,
                "epoch": self.membership_epoch,
                "map_epoch": max(self.map_epoch, self._seen_map_epoch),
                # control-plane reconnect, NOT a process restart: the
                # runtime is live and connect()'s re-init barrier will
                # not run, so the scheduler must not arm the
                # recovered-conn barrier bypass for this conn
                "reconnect": True,
                "job": self.cfg.job_id,
                "job_priority": self.cfg.job_priority,
                "job_quota_mbps": self.cfg.job_quota_mbps,
                # last-adopted fleet tuning + placement overrides: a
                # reborn scheduler re-adopts these before its first
                # books (AutoTuner.adopt_rejoin_report) so live
                # decisions survive the restart
                "tuning": self._tuning_report(),
            }).encode()
            send_message(sock, Message(Op.REGISTER, payload=payload))
            resp = recv_message(sock)
            if resp.status != 0:
                err = json.loads(resp.payload.decode()).get(
                    "error", "register refused"
                )
                raise RuntimeError(f"scheduler refused rejoin: {err}")
            book = json.loads(resp.payload.decode())
            if not self._fence_book(book):
                return sock, None
            return sock, book
        except BaseException:
            close_socket(sock)
            raise

    def _adopt_rejoin(self, sock, book: dict) -> None:
        """Install a successful rejoin: swap the control socket in, adopt
        the book (rank is stable — the scheduler honored the uid/rank
        report), restart the receiver, and wake barrier retries."""
        self.rank = book["rank"]
        self.num_workers = self._book_num_workers(book)
        self.is_recovery = True
        self._note_membership(book)
        counters().bump("sched_rejoin")
        with self._sched_cb_lock:
            old, self._sched = self._sched, sock
            self._sched_dead = False
            # hand the NEXT reconnect cycle to the recv loop we are about
            # to spawn: if the rejoined link dies again (likely under
            # scheduler-link chaos), its finally must see reconnecting
            # False and start a fresh machine rather than assume this
            # (exiting) one still owns the link
            self._sched_reconnecting = False
            self._book_token += 1
            token = self._book_token
        close_socket(old)  # the dead link's fd must not outlive the rejoin
        threading.Thread(target=self._sched_recv_loop, daemon=True).start()
        # adopt the book's server set/ownership map like a RESIZE_SEQ
        # broadcast — when nothing changed (the common crash-restart
        # case) this is the no-op path: no reconnect churn, no
        # generation bump, the version sequence continues bitwise
        self._rebuild_servers(
            book["num_servers"], [tuple(s) for s in book["servers"]],
            token, book=book,
        )
        with self._sched_cb_lock:
            # only mark the link up if it is STILL up: under repeated
            # chaos the fresh socket can die during the rebuild above,
            # and re-setting the event then would make barrier retries
            # busy-spin against a dead link until the next rejoin
            alive = not self._sched_dead
            if alive:
                self._sched_up.set()
        if alive:
            metrics().gauge_set("control_plane_degraded", 0)

    def _rebuild_servers(
        self,
        num_servers: int,
        new_addrs: List[tuple],
        token: int = 1 << 62,
        retry_delay: float = 2.0,
        book: Optional[dict] = None,
    ) -> None:
        """Adopt a resized server book live: connect to the new set, swap,
        then fail the old connections' in-flight requests (same path as a
        server death — the handle errors instead of hanging).  Requests
        racing the swap may still land on an old connection and fail; the
        caller's next round routes and re-inits against the new owners.

        Runs on its own thread (a connect may block or fail during elastic
        churn); rebuilds are serialized, and a stale book — one that
        ARRIVED before the currently-applied one, regardless of which
        thread wins the lock — is skipped by its monotonic ``token``."""
        with self._rebuild_lock:
            if token <= self._applied_token or self._stop.is_set():
                return  # superseded by a newer book, or shutting down
            if token < self._book_token:
                # a newer book exists and ITS rebuild was spawned
                # unconditionally — let it establish the truth; applying
                # this older one would override the correct topology
                return
            if new_addrs == self._server_addrs:
                # live set already matches this newest book (rollback
                # racing a failed rebuild's retry): mark applied so older
                # pending retries cancel, no reconnect churn.  The book's
                # ownership map still installs — rank identities can
                # change under identical addresses (dead-slot adoption)
                self.num_servers = num_servers
                omap = self._ownership_from_book(book)
                if omap is not None:
                    self._install_routing(
                        self._servers, (book or {}).get("server_ranks"),
                        omap,
                    )
                self._applied_token = token
                return
            fresh: List[_ServerConn] = []
            for attempt in range(3):
                if token < self._book_token:
                    # superseded mid-rebuild: stop holding the lock through
                    # further connect timeouts; the newer book's rebuild is
                    # blocked on us and owns the truth
                    for sc in fresh:
                        sc.close_all()
                    return
                try:
                    for host, port in new_addrs[len(fresh):]:
                        sc = self._new_conn(host, port)
                        sc.server_label = str(len(fresh))
                        fresh.append(sc)
                    break
                except OSError as e:
                    if attempt == 2:
                        # persistent: keep the current (stale) server set for
                        # now (the control plane stays alive, in-flight
                        # failures surface per-request), but don't stay
                        # desynced forever — RESIZE_SEQ books are broadcast
                        # once, so schedule a delayed re-attempt of this same
                        # book; a newer book supersedes it via the token check
                        from byteps_tpu.common import logging as bpslog

                        bpslog.warning(
                            "server-resize rebuild failed after retries: %r "
                            "— retrying in %.0fs", e, retry_delay
                        )
                        for sc in fresh:
                            sc.close_all()

                        def _retry():
                            if self._stop.wait(retry_delay):
                                return
                            self._rebuild_servers(
                                num_servers, new_addrs, token,
                                min(retry_delay * 2, 30.0), book=book,
                            )

                        threading.Thread(target=_retry, daemon=True).start()
                        return
                    self._stop.wait(0.3 * (attempt + 1))
            if token < self._book_token:
                # a newer book arrived while we were blocked in connects;
                # its unconditionally-spawned rebuild owns the truth
                for sc in fresh:
                    sc.close_all()
                return
            old, self._servers = self._servers, fresh
            self._server_addrs = list(new_addrs)
            self.num_servers = num_servers
            omap = self._ownership_from_book(book)
            if self.reshard:
                self._install_routing(
                    fresh, (book or {}).get("server_ranks"), omap
                )
            else:
                # legacy clients (and __new__-built test stubs) have no
                # map condition variable; keep the snapshot coherent so
                # _conn_for's identity check sees the fresh list
                self._routing = (fresh, [], None)
            if omap is None:
                # legacy resize: keys re-home via the hash fns onto
                # fresh stores — the engine re-runs every key's
                # init-push barrier against the new owners
                self.server_generation += 1
            # else: live resharding — the servers migrate each re-homed
            # key's state (store + ledger + init tokens) to its new
            # owner, so the version sequence continues in place and NO
            # re-init barrier fires (docs/robustness.md "migration flow")
            self._applied_token = token
        for sc in old:
            sc.close_all()  # recv loops exit → mark_dead fails pendings

    def _new_conn(self, host: str, port: int, dial_timeout: float = 30.0):
        """Build a server connection: the C++ data plane when
        BYTEPS_NATIVE_CLIENT=1 and the lib speaks it (tcp/uds only —
        the shm van's Python client is already zero-copy), else the
        Python lanes + recv threads.  ``dial_timeout`` bounds the connect
        (revival dials pass a deadline-scaled bound; the native client
        keeps its own fixed 30s)."""
        from byteps_tpu.comm.shaping import shaping_enabled
        from byteps_tpu.comm.van import CHAOS_PREFIX, SHM_PREFIX

        if shaping_enabled() and self.cfg.native_client:
            from byteps_tpu.comm.shaping import warn_native_bypass_once

            warn_native_bypass_once("ignoring BYTEPS_NATIVE_CLIENT=1")
        elif self.cfg.native_client and not host.startswith(
            (SHM_PREFIX, CHAOS_PREFIX)  # chaos needs the Python fault layer
        ):
            from byteps_tpu.native import get_lib

            lib = get_lib()
            if lib is not None and hasattr(lib, "bpsc_drain"):
                return _NativeServerConn(
                    host, port, streams=self.cfg.tcp_streams,
                    on_zero_copy=self._count_zero_copy,
                )
        sc = _ServerConn(host, port, streams=self.cfg.tcp_streams,
                         dial_timeout=dial_timeout)
        self._start_recv_loops(sc)
        return sc

    def push_senders(self) -> int:
        """How many PUSH stage threads the server links can feed at once:
        the least over the links, decided by their kind (a split TCP link
        has a push lane a sender; a unix, shm, shaped or native link one)."""
        return min((sc.push_senders for sc in self._servers), default=1)

    def _count_zero_copy(self) -> None:
        self.zero_copy_pulls += 1

    # --- per-RPC deadlines + retry (docs/robustness.md) ------------------

    def _worker_flag(self) -> int:
        """Worker identity for the header ``flags`` byte: rank+1, so the
        server can dedupe replayed pushes on (worker, key, version).  0 =
        no identity (rank unknown, or ≥255 workers — the u8 runs out) and
        the server skips dedupe for that push."""
        r = self.rank
        return r + 1 if r is not None and 0 <= r < 255 else 0

    def _init_token(self, key: int) -> int:
        """Init-idempotency token carried in the INIT frame's ``version``
        field (docs/robustness.md): low 16 bits = this client's per-key
        init sequence, high 16 bits = the membership epoch folded with a
        per-client random salt.  Every RETRY of one logical init reuses
        the same token, so a replayed INIT whose barrier already released
        is acked from the server's completed-barrier record instead of
        re-parked (the dropped-ack strand).  Epoch-scoping + the salt
        make elastic rejoin and post-shutdown re-init mint FRESH tokens,
        so a genuine new barrier always parks."""
        with self._init_seq_lock:
            seq = self._init_seqs.get(key, 0) + 1
            self._init_seqs[key] = seq
        high = (self._init_salt ^ (self.membership_epoch & 0xFFFF)) & 0xFFFF
        return (high << 16) | (seq & 0xFFFF)

    def _ensure_scanner_locked(self) -> None:
        """Start (or wake) the shared deadline/timer scanner thread.
        Caller holds ``_outstanding_lock``."""
        if self._deadline_thread is None:
            self._deadline_thread = threading.Thread(
                target=self._deadline_loop, name="bps-rpc-deadline",
                daemon=True,
            )
            self._deadline_thread.start()
        else:
            self._scan_cv.notify()

    def _deadline_arm(self, sc, sid: Optional[str] = None) -> Optional[int]:
        """Register one in-flight RPC attempt with the deadline scanner;
        returns a token for :meth:`_deadline_clear`, or None when
        deadlines are disabled.  ``sid`` (server-rank string) labels the
        expiry counter so one hung server stands out of the total."""
        if self.cfg.rpc_deadline_s <= 0:
            return None
        token = next(self._rpc_tokens)
        expire = time.monotonic() + self.cfg.rpc_deadline_s
        with self._outstanding_lock:
            self._outstanding[token] = (sc, expire, sid)
            self._ensure_scanner_locked()
        return token

    def _deadline_clear(self, token: Optional[int]) -> None:
        if token is None:
            return
        with self._outstanding_lock:
            self._outstanding.pop(token, None)

    def _timer_after(self, delay: float, fn) -> None:
        """Timer wheel: fire ``fn`` after ``delay`` seconds (timed by the
        ``bps-rpc-deadline`` scanner, executed on the bounded
        ``bps-rpc-retry-*`` pool).  Replaces per-retry ``threading.Timer``
        spawning with a handful of persistent threads.  After close(),
        ``fn`` runs inline so its stop-check resolves the caller (fail →
        on_error) instead of parking forever."""
        import heapq

        with self._outstanding_lock:
            if not self._stop.is_set():
                heapq.heappush(
                    self._timers,
                    (time.monotonic() + delay, next(self._rpc_tokens), fn),
                )
                self._ensure_scanner_locked()
                return
        fn()

    def _dispatch_retry(self, fn) -> None:
        """Queue a due retry callback onto the persistent executor pool.
        An executor may block in a resend (revival dial, wedged send);
        the scanner stays free to expire deadlines — including the one
        whose teardown unblocks a wedged send — and a visible backlog
        grows the pool (to the cap) so one blocked dial doesn't
        head-of-line-block other servers' retries."""
        self._retry_q.put(fn)
        threads = self._retry_threads
        if not threads or (
            self._retry_q.qsize() > 0 and len(threads) < self._retry_pool_cap
        ):
            t = threading.Thread(
                target=self._retry_loop,
                name=f"bps-rpc-retry-{len(threads)}", daemon=True,
            )
            threads.append(t)
            t.start()

    def _retry_loop(self) -> None:
        import queue as _queue

        while True:
            try:
                fn = self._retry_q.get(timeout=0.5)
            except _queue.Empty:
                if self._stop.is_set():
                    return
                continue
            try:
                # after close(): still run — fn's stop-check fails it out
                # through on_error instead of stranding its waiter
                fn()
            except Exception:  # noqa: BLE001 — executor must survive
                pass

    def _deadline_loop(self) -> None:
        """Deadline scanner + retry timer wheel (one timing thread).

        Deadlines: an RPC past its deadline means its server is hung (a
        dead one would have closed the connection).  Tear the suspect
        connection down — the recv-loop drain fires every pending callback
        with None, so ALL of that connection's RPCs funnel into the one
        retry path, and no late response can race a retried pull into a
        caller's zero-copy sink (the old lanes are fully dead first).

        Timers: backoff-delayed resends parked by :meth:`_timer_after`
        become DUE here and are handed to the executor thread (see
        :meth:`_dispatch_retry` for why they must not run on this one).
        The condition wait sleeps exactly until the next timer or the
        next deadline scan tick, whichever is sooner, and is notified on
        every new arm/park so an earlier event never waits behind a
        longer sleep."""
        import heapq

        tick = (
            max(0.01, min(0.25, self.cfg.rpc_deadline_s / 4))
            if self.cfg.rpc_deadline_s > 0 else 0.25
        )
        try:
            while True:
                due, doomed = [], []
                with self._outstanding_lock:
                    if self._stop.is_set():
                        return
                    now = time.monotonic()
                    while self._timers and self._timers[0][0] <= now:
                        due.append(heapq.heappop(self._timers)[2])
                    for t in [
                        t for t, (_, at, _sid) in self._outstanding.items()
                        if at <= now
                    ]:
                        sc, _, sid = self._outstanding.pop(t)
                        doomed.append((sc, sid))
                    if not due and not doomed:
                        timeout = (
                            self._timers[0][0] - now if self._timers else None
                        )
                        if self._outstanding:
                            timeout = (
                                tick if timeout is None else min(timeout, tick)
                            )
                        self._scan_cv.wait(timeout)
                        continue
                # teardowns on THIS thread (close_all never blocks), due
                # retries handed to the executor thread (a resend can
                # block — and the teardown side must stay live to unblock
                # it; see __init__)
                if doomed:
                    for sc, sid in doomed:
                        counters().bump(
                            "rpc_deadline_expired",
                            labels={"server": sid} if sid is not None else None,
                        )
                    for sc in {id(s): s for s, _ in doomed}.values():
                        try:
                            sc.close_all()
                        except Exception:  # noqa: BLE001
                            pass
                for fn in due:
                    self._dispatch_retry(fn)
        finally:
            # shutdown drain: every parked retry must still resolve (its
            # stop-check fails it through on_error) — parking it forever
            # would strand a synchronize() waiter
            with self._outstanding_lock:
                leftovers = [fn for _, _, fn in self._timers]
                self._timers.clear()
            for fn in leftovers:
                try:
                    fn()
                except Exception:  # noqa: BLE001
                    pass

    def _async_rpc(
        self,
        make_msg: Callable[[int], Message],
        key: int,
        deliver: Callable[[Message], None],
        on_error: Optional[Callable[[], None]],
        sink: Optional[memoryview] = None,
        abort_check: Optional[Callable[[], bool]] = None,
        precheck: Optional[Callable[[], bool]] = None,
        heal: bool = True,
        chase: bool = True,
    ) -> None:
        """Send one async RPC with deadline + retry + revival.

        ``make_msg(seq)`` builds the wire message per attempt;
        ``deliver(msg)`` fires once on success; ``on_error`` fires once
        when ``BYTEPS_RPC_RETRIES`` attempts are exhausted (or
        immediately with retries disabled — the legacy fail-fast path).

        ``abort_check``: returns True once the caller has abandoned this
        RPC's whole operation (engine job failed) — pending retries stop
        resending and route to ``on_error`` instead (the caller's error
        path is idempotent and still owes per-task cleanup: queue
        accounting, round-gate re-arm).  Without the fence, a retry
        timer armed before the abandonment could replay an
        old-generation push AFTER the re-init barrier cleared the
        server's dedupe ledger, double-summing that worker.

        ``precheck``: evaluated before EVERY attempt (first and retries);
        returning False fails the RPC straight to ``on_error`` without
        sending.  Used by fused frames to bail out the moment the server
        set resizes — a pre-resize pack's members may no longer share a
        destination, and the caller's error path knows how to regroup
        (engine unfuse fallback), while blind resends would just burn the
        retry budget shipping mis-homed keys.

        ``heal``: with retries exhausted, route ONCE through the in-place
        resync heal (docs/robustness.md "healing flow") before surfacing
        the error — the give-up may be one-sided (every frame to a LIVE
        server lost) and a successful server resync + journal replay
        earns the RPC one fresh attempt.  Fused frames pass ``False``:
        their error path is the unfuse fallback, and the per-key unfused
        RPCs it spawns carry their own heal.
        """
        _AsyncRpc(self, make_msg, key, deliver, on_error, sink,
                  abort_check, precheck, heal, chase).send_attempt()

    # --- recovery plane: in-place heal via server-driven resync ----------
    #
    # docs/robustness.md "healing flow".  A worker that exhausted its RPC
    # retries against a LIVE server (one-sided degradation: chaos drops,
    # a flapping link, a deadline storm) used to have only the global
    # re-init barrier — which waits for peers that never come, stranding
    # the whole job.  Instead: ask the server for its authoritative
    # per-key round/ledger state (Op.RESYNC_QUERY), replay exactly the
    # journaled pushes it never absorbed, and resume in place.  Peers
    # never block, no barrier, no scheduler involvement.

    def resync_in_place(self, key: int) -> bool:
        """Public entry to the heal state machine (engine / api layer):
        resync ``key``'s owning server and replay whatever journaled
        rounds it is missing.  True = the server's ledger now agrees
        with this worker's emission history."""
        try:
            sid = str(self.server_for(key))
        except (ValueError, ZeroDivisionError, IndexError, ConnectionError):
            return False
        return self._heal_in_place(key, sid)

    def _heal_in_place(self, key: int, sid: str) -> bool:
        """One heal attempt, serialized per server: query → replay →
        resume, bounded by ``BYTEPS_RESYNC_DEADLINE_S`` wall-clock.
        Counters: ``resync_attempt`` / ``resync_replayed_rounds`` /
        ``resync_giveup`` (flat + per-server labels); the attempt also
        lands as a ``RESYNC`` span on the process timeline, and the wire
        query carries its trace context so the server's ``resync`` child
        span joins it on the merged Perfetto view."""
        if (self.cfg.resync_deadline_s <= 0 or self._stop.is_set()
                or not self._worker_flag()):
            # anonymous workers (no rank identity) have no ledger slot on
            # the server — there is nothing to resync against
            return False
        with self._heal_meta_lock:
            lock = self._heal_locks.setdefault(sid, threading.Lock())
            entry_gen = self._heal_gen.get(sid, 0)
        trace = None
        tracer = None
        from byteps_tpu.core.tracing import (
            get_process_tracer,
            new_trace_id,
            span_args,
        )

        tracer = get_process_tracer()
        if tracer is not None and tracer.enabled and tracer.spans_enabled:
            trace = (new_trace_id(), new_trace_id())
        t0 = time.time()
        with lock:
            with self._heal_meta_lock:
                if self._heal_gen.get(sid, 0) != entry_gen:
                    # a concurrent give-up healed this server while we
                    # waited for the lock — ride its work
                    return True
            counters().bump("resync_attempt", labels={"server": sid})
            ok, replayed = False, 0
            try:
                ok, replayed = self._run_resync(key, sid, trace)
            except Exception:  # noqa: BLE001 — a heal must never leak
                ok = False
            if ok:
                with self._heal_meta_lock:
                    self._heal_gen[sid] = entry_gen + 1
            else:
                counters().bump("resync_giveup", labels={"server": sid})
        if trace is not None:
            tracer.record_span(
                "resync", "RESYNC", t0, time.time() - t0,
                span_args(trace[0], trace[1], server=sid,
                          replayed=replayed, healed=ok),
            )
        return ok

    def _run_resync(self, route_key: int, sid: str, trace) -> tuple:
        """The heal body → (ok, rounds_replayed).  Caller holds the
        server's heal lock.

        1. (Re-)dial the server; a server that cannot be dialed is DOWN,
           not one-sided — that case belongs to eviction/rebuild, so the
           heal fails fast instead of burning the budget.
        2. Op.RESYNC_QUERY for every key this worker journals toward the
           server (plus the triggering key): the reply's per-key ``seen``
           is the newest version of OUR pushes its exactly-once ledger
           absorbed.
        3. Replay, oldest-first, exactly the journaled rounds above each
           ``seen`` watermark through the NORMAL push path (ledger
           dedupe, zombie fence, round publish all apply) — fused-pack
           members replay as plain per-key pushes, which the server sums
           identically.
        """
        from byteps_tpu.comm.journal import get_journal
        from byteps_tpu.comm.retry import Backoff
        from byteps_tpu.comm.transport import (
            decode_resync_state,
            encode_resync_query,
        )

        deadline_at = time.monotonic() + self.cfg.resync_deadline_s
        j = get_journal()
        wid = self._worker_flag()

        def owned(k: int) -> bool:
            try:
                return str(self.server_for(k)) == sid
            except (ValueError, ZeroDivisionError, IndexError, ConnectionError):
                return False

        keys = sorted(
            {route_key} | {k for k in (j.keys() if j else []) if owned(k)}
        )
        backoff = Backoff(base=max(0.01, self.cfg.rpc_backoff_s), cap=1.0)

        def recovery_rpc(k: int, make_msg, errmsg: str):
            """One blocking recovery RPC, re-dialed and re-sent within
            the heal budget; None once the budget (or the server) dies."""
            while True:
                remaining = deadline_at - time.monotonic()
                if remaining <= 0 or self._stop.is_set():
                    return None
                per_try = (
                    min(remaining, max(0.2, self.cfg.rpc_deadline_s))
                    if self.cfg.rpc_deadline_s > 0 else remaining
                )
                try:
                    sc = self._conn_for(k, revive=True)
                except (ConnectionError, OSError):
                    return None  # server not dialable: not the one-sided case
                try:
                    return self._blocking_request(sc, make_msg, errmsg, per_try)
                except ConnectionError:
                    # frames still being lost (the chaos that caused the
                    # give-up): back off and re-dial within the budget
                    if self._stop.wait(min(
                        backoff.next_delay(),
                        max(0.0, deadline_at - time.monotonic()),
                    )):
                        return None

        resp = recovery_rpc(
            route_key,
            lambda seq: Message(
                Op.RESYNC_QUERY, key=route_key, seq=seq, flags=wid,
                payload=encode_resync_query(wid, keys), trace=trace,
            ),
            "resync query failed",
        )
        if resp is None:
            return False, 0
        if resp.op != Op.RESYNC_STATE or resp.status != 0:
            # the server doesn't speak the recovery plane (a pre-parity
            # native binary rejects with nonzero status; current engines
            # — Python AND C++ — both serve it) — fall back to re-init
            return False, 0
        state = decode_resync_state(resp.payload)
        replayed = 0
        for k in keys:
            info = state.get(k)
            if info is None:
                if j is not None and j.entries_after(k, 0):
                    # we journaled pushes for a key the server no longer
                    # holds: its store was lost (restart) — only the init
                    # barrier can rebuild allocation, resync cannot
                    return False, replayed
                continue
            entries = (
                j.entries_after(k, int(info.get("seen", 0))) if j else []
            )
            for e in entries:
                ack = recovery_rpc(
                    k,
                    lambda seq, _k=k, _e=e: Message(
                        Op.PUSH, key=_k, seq=seq, cmd=_e.cmd,
                        version=_e.version, flags=wid, payload=_e.payload,
                        trace=trace,
                    ),
                    f"resync replay failed for key {k}",
                )
                if ack is None or ack.status != 0 or ack.op == Op.WRONG_OWNER:
                    # a redirect mid-replay means the key moved AGAIN
                    # (double migration race): fail this heal — the
                    # give-up path re-runs once the new book lands
                    return False, replayed
                counters().bump(
                    "resync_replayed_rounds", labels={"server": sid}
                )
                replayed += 1
        return True, replayed

    def _blocking_request_retrying(
        self, key: int, make_msg, errmsg: str, use_deadline: bool = True
    ) -> Message:
        """Retrying wrapper for the blocking control RPCs (init-push,
        compressor registration).  Safe to replay: the server keys init
        waiters and compressor registration idempotently (server.py).

        ``use_deadline=False`` for RPCs whose latency depends on PEER
        workers (the init barrier: the server withholds the ack until
        every worker arrives) — the ordinary per-RPC deadline would make
        on-time workers tear down healthy connections whenever one peer
        straggles.  Such RPCs use the separate ``BYTEPS_INIT_DEADLINE_S``
        budget instead (default 0 = none; set it ABOVE worst-case worker
        skew — chaos tests set it small to heal dropped init acks).
        Connection death still fails the wait immediately (cb(None)
        drain) either way, so retries remain live; a hung server during
        a deadline-free init is the scheduler eviction policy's job."""
        from byteps_tpu.comm.retry import Backoff

        backoff = Backoff(base=self.cfg.rpc_backoff_s, cap=2.0)
        deadline = (
            (self.cfg.rpc_deadline_s or None) if use_deadline
            else (self.cfg.init_deadline_s or None)
        )
        try:
            sid = str(self.server_for(key))
        except (ValueError, ZeroDivisionError, IndexError, ConnectionError):
            sid = "?"
        last: Optional[BaseException] = None
        attempt = 0
        redirects = 0
        while attempt <= self.cfg.rpc_retries:
            if attempt:
                counters().bump("rpc_retry", labels={"server": sid})
                if self._stop.wait(backoff.next_delay()):
                    break
            try:
                sc = self._conn_for(key, revive=attempt > 0)
            except (ConnectionError, OSError) as e:
                last = e
                attempt += 1
                continue
            try:
                resp = self._blocking_request(sc, make_msg, errmsg, deadline)
            except ConnectionError as e:
                last = e
                attempt += 1
                continue
            if resp.op == Op.WRONG_OWNER:
                # the key migrated (docs/robustness.md "migration flow"):
                # wait for the redirect's book, re-route, resend.  Chases
                # don't consume the retry budget (the server answered)
                # but are capped against a pathological ping-pong.
                if redirects >= self._max_chases:
                    last = ConnectionError("wrong-owner chase exhausted")
                    break
                redirects += 1
                counters().bump(
                    "wrong_owner_redirect", labels={"server": sid}
                )
                self._wait_map_epoch(
                    resp.version, min(2.0, 0.25 * redirects)
                )
                continue
            return resp
        counters().bump("rpc_giveup")
        raise ConnectionError(errmsg) from last

    @staticmethod
    def _blocking_request(
        sc, make_msg, errmsg: str, timeout: Optional[float] = None
    ) -> Message:
        """Send one server request and block for its ack; raises
        ConnectionError if the connection is dead or dies while waiting
        (the alloc_seq dead-path fires the callback with None).  With a
        ``timeout``, expiry tears the (presumed hung) connection down —
        same policy as the async deadline scanner."""
        done = threading.Event()
        box: list = []
        seq = sc.alloc_seq(lambda msg: (box.append(msg), done.set()))
        if seq >= 0:
            try:
                sc.send_msg(make_msg(seq))
            except OSError:
                # connection died between alloc_seq and send: callers see
                # the same ConnectionError as the dead-connection path
                sc.pop_cb(seq)
                raise ConnectionError(errmsg) from None
        if not done.wait(timeout):
            counters().bump("rpc_deadline_expired")
            sc.close_all()
            done.wait(5.0)  # the drain fires promptly once lanes close
        if not box or box[0] is None:
            raise ConnectionError(errmsg)
        return box[0]

    #: how long get_metrics() waits for the servers' registries, all together
    METRICS_WAIT_S = 2.0

    def server_metrics(self) -> List[dict]:
        """Every linked server's registry now, as ``MetricsRegistry.snapshot``
        under the server's own ``{role="server", rank}`` labels: one
        ``Op.METRICS`` a server, all sent before any is waited for, all
        waited for ``METRICS_WAIT_S`` together.  A server that is dead, does
        not answer in time or does not know the request (the C++ engine
        rejects it with status 1) is left out — nothing is retried and no
        connection is torn down: the caller is reading numbers, not healing
        a plane."""
        waiting = []
        for sc in list(self._servers):
            done, box = threading.Event(), []
            try:
                seq = sc.alloc_seq(
                    lambda msg, box=box, done=done: (box.append(msg), done.set()))
                if seq >= 0:
                    sc.send_msg(Message(Op.METRICS, seq=seq))
            except (ConnectionError, OSError):
                continue
            waiting.append((sc, seq, done, box))
        snapshots, deadline = [], time.monotonic() + self.METRICS_WAIT_S
        for sc, seq, done, box in waiting:
            if not done.wait(max(0.0, deadline - time.monotonic())):
                sc.pop_cb(seq)  # a late reply finds nobody and is dropped
                continue
            msg = box[0]
            if msg is None or msg.status or msg.op != Op.METRICS:
                continue
            try:
                snapshots.append(json.loads(bytes(msg.payload)))
            except ValueError:
                pass
            release_frame(msg.payload)
        return snapshots

    def _start_recv_loops(self, sc: _ServerConn) -> None:
        """One receiver per lane; all lanes demux into the shared seq-keyed
        callback table (responses come back on the lane that carried the
        request — the server answers per-connection)."""
        index = {"push": itertools.count(), "pull": itertools.count()}
        threads = [
            threading.Thread(target=self._recv_loop,
                             args=(sc, sock, name == "pull"), daemon=True,
                             name=f"bps-recv-{name}-{next(index[name])}")
            for sock, _, name in sc.lanes()
        ]
        sc.recv_thread = threads[0]
        for t in threads:
            t.start()

    def _recv_loop(self, sc: _ServerConn, sock, pull_lane: bool = False) -> None:
        from byteps_tpu.comm.transport import checksum_conn_limit, recv_header_ex

        ck_limit = checksum_conn_limit()
        # this lane's receive buffers, for replies no sink takes (a codec's
        # merged round, a fused reply): whoever consumes one releases it
        pool = FramePool()
        # one frame's service on this thread, parsed header → the callback's
        # return (payload receive, integrity, the engine's _proceed); one
        # frame in sampled.EVERY on the stage threads' two clocks, the payload's receive
        # its releasing call (transport.recv_into), under stage="recv.<lane>"
        # for the lane kind's threads together.  A one-socket link files
        # under "push", as lane_bulk_bytes does
        lane = "pull" if pull_lane else "push"
        span_name = "recv.frame." + lane
        sample = sampled("recv." + lane)
        try:
            while not self._stop.is_set():
                try:
                    header = recv_header_ex(sock)
                except (ConnectionError, OSError):
                    return
                with span(span_name):
                    sample.begin()
                    try:
                        alive = self._recv_frame(sc, sock, pull_lane, header,
                                                 pool, ck_limit)
                    finally:
                        sample.end()
                if not alive:
                    return
        finally:
            # one lane dying poisons the whole striped connection: close
            # every lane (wakes the sibling receivers).  The DRAIN — fail
            # every pending request with cb(None) so callers never hang in
            # synchronize() — runs only on the LAST lane to exit: sibling
            # receivers may still be writing into callers' zero-copy sinks
            sc.close_all()
            if sc.lane_exited():
                for cb in sc.mark_dead():
                    try:
                        cb(None)
                    except Exception:  # noqa: BLE001
                        pass

    def _recv_frame(self, sc: _ServerConn, sock, pull_lane: bool, header,
                    pool: FramePool, ck_limit: int) -> bool:
        """Receive one reply frame's payload and hand it to its callback;
        False when the lane must close (its connection died, or it passed
        the checksum-mismatch limit)."""
        from byteps_tpu.comm.transport import (
            LosslessError,
            frame_checksum,
            recv_into,
        )
        from byteps_tpu.compression.lossless import decompress_frame

        (op, status, flags, seq, key, cmd, version, length,
         trace, crc, lossless) = header
        try:
            # the callback is popped only AFTER the payload is
            # fully received: dying mid-payload must leave it for
            # mark_dead's cb(None) drain, never lose it
            sink = sc.peek_sink(seq)
            _count_bulk(pull_lane, "rx", op, length)
            # a lossless frame's `length` is the container size,
            # never the caller's raw-sized sink — decode lands in
            # an owned payload (no zero-copy for compressed frames)
            zero_copied = (not lossless and sink is not None
                           and length == len(sink))
            if zero_copied:
                # zero-copy: the aggregated payload lands directly
                # in the caller's result buffer — no intermediate
                # bytes object, no frombuffer+slice copy
                recv_into(sock, sink)
                payload = _ZERO_COPIED
            else:
                payload = (
                    recv_payload(sock, length, pool)
                    if length else b""
                )
            if crc is not None and frame_checksum(
                trace, sink if zero_copied else payload
            ) != crc:
                # end-to-end wire integrity (docs/robustness.md):
                # a corrupted reply is DROPPED before the seq
                # demux — the callback stays registered so the
                # deadline/retry machinery re-fetches (a zero-
                # copy sink holding garbage is harmless: the
                # retried response overwrites it before the
                # caller ever wakes).  Repeated mismatches
                # poison the connection → revival re-dials.
                release_frame(payload)  # dropped unread
                fails = sc.note_checksum_fail()
                counters().bump("wire_checksum_fail", labels={
                    "side": "client",
                    "op": getattr(op, "name", str(op)),
                    "server": getattr(sc, "server_label", "?"),
                })
                if ck_limit and fails >= ck_limit:
                    counters().bump("wire_checksum_conn_drop")
                    return False
                return True
            if lossless:
                # decompress AFTER integrity passes; a corrupt
                # container is dropped exactly like a CRC
                # mismatch — the callback stays registered, the
                # deadline/retry machinery re-fetches, and
                # repeated failures poison the connection
                container = payload
                try:
                    payload = decompress_frame(container, op=op)
                except LosslessError:
                    fails = sc.note_checksum_fail()
                    counters().bump("wire_lossless_fail", labels={
                        "side": "client",
                        "op": getattr(op, "name", str(op)),
                        "server": getattr(sc, "server_label", "?"),
                    })
                    if ck_limit and fails >= ck_limit:
                        counters().bump("wire_checksum_conn_drop")
                        return False
                    return True
                finally:
                    release_frame(container)  # decoded or dropped
            if zero_copied:
                self.zero_copy_pulls += 1
        except (ConnectionError, OSError):
            return False
        cb = sc.pop_cb(seq)
        if cb is not None:
            cb(
                Message(
                    op, key=key, payload=payload, seq=seq, cmd=cmd,
                    version=version, status=status, flags=flags,
                )
            )
        return True

    # --- key routing -----------------------------------------------------

    def server_for(self, key: int) -> int:
        """The key's owning server RANK.  Under live resharding this is
        the adopted ownership map's owner; legacy routing hashes over the
        server count (where rank == list index)."""
        omap = self._ownership
        if omap is not None:
            return omap.owner(key)
        if self.num_servers <= 0:
            # transiently-empty book (eviction burst): retryable, unlike
            # the hash fn's ValueError
            raise ConnectionError("no servers in current book")
        return assign_server(
            key,
            self.num_servers,
            fn=self.cfg.key_hash_fn,
            coef=self.cfg.built_in_hash_coef,
            mixed_mode=self.cfg.enable_mixed_mode,
            mixed_bound=self.cfg.mixed_mode_bound,
            num_workers=self.num_workers,
            ring_vnodes=self.cfg.ring_vnodes,
        )

    def _conn_for(self, key: int, revive: bool = False) -> _ServerConn:
        """Route a key from ONE atomic snapshot of the server list.
        During a live resize the list reference swaps under us; hashing
        with ``len(snapshot)`` keeps count and list consistent (reading
        self.num_servers separately could pair the new count with the old
        list → IndexError instead of the designed dead-connection path).

        ``revive=True`` (retry attempts): a dead connection is re-dialed
        in place first — a transient disconnect (chaos van, server
        restart, deadline teardown) heals without scheduler involvement.
        """
        servers = self._servers
        if not servers:
            # a burst of evictions can transiently empty the book;
            # ConnectionError (not the hash fn's ValueError) keeps this
            # on the retry path so the next book heals it
            raise ConnectionError("no servers in current book")
        routing = self._routing
        # the ownership map routes only when its snapshot matches the
        # live list (the two swap together; a mismatch means a rebuild is
        # mid-swap or the client was built without a book — fall back to
        # legacy count-hash routing, which the redirect chase corrects)
        ranks, omap = (
            (routing[1], routing[2]) if routing[0] is servers else ([], None)
        )
        if omap is not None and ranks and len(ranks) == len(servers):
            owner = omap.owner(key)
            try:
                idx = ranks.index(owner)
            except ValueError:
                raise ConnectionError(
                    f"owner rank {owner} not in current book"
                ) from None
        else:
            idx = assign_server(
                key,
                len(servers),
                fn=self.cfg.key_hash_fn,
                coef=self.cfg.built_in_hash_coef,
                mixed_mode=self.cfg.enable_mixed_mode,
                mixed_bound=self.cfg.mixed_mode_bound,
                num_workers=self.num_workers,
                ring_vnodes=self.cfg.ring_vnodes,
            )
        sc = servers[idx]
        if revive and getattr(sc, "dead", False):
            sc = self._revive_conn(idx, sc)
        return sc

    def _revive_conn(self, idx: int, dead_sc) -> _ServerConn:
        """Replace a dead server connection with a fresh dial to the same
        address (server state is per-key, not per-connection, so a revived
        link resumes exactly where the dead one left off — retried pushes
        dedupe server-side).  Raises on dial failure.

        The dial happens OUTSIDE the rebuild lock: a black-holed server
        (no RST, dial blocks until its timeout) must not stall elastic
        RESIZE rebuilds or other keys' revives behind it.  Both lock
        sections re-validate, so a rebuild landing mid-dial wins and the
        late revival is discarded."""
        with self._rebuild_lock:
            if self._stop.is_set():
                raise ConnectionError("client closed")
            servers = self._servers  # re-read: a rebuild may have swapped it
            if idx >= len(servers):
                raise ConnectionError("server set resized")
            cur = servers[idx]
            if cur is not dead_sc and not getattr(cur, "dead", False):
                return cur  # another retry already revived this slot
            host, port = self._server_addrs[idx]
        # revival dials get a deadline-scaled bound: with per-RPC
        # deadlines armed the operator opted into bounded-latency failure
        # handling, and a black-holed server (SYN dropped, no RST) must
        # not pin a retry-executor thread for the full 30s van timeout
        dial_timeout = (
            min(30.0, max(2.0, 4 * self.cfg.rpc_deadline_s))
            if self.cfg.rpc_deadline_s > 0 else 30.0
        )
        fresh = self._new_conn(host, port, dial_timeout)  # lock NOT held
        fresh.server_label = str(idx)
        with self._rebuild_lock:
            servers = self._servers
            if (self._stop.is_set() or idx >= len(servers)
                    or self._server_addrs[idx] != (host, port)):
                fresh.close_all()  # superseded by a rebuild/shutdown
                raise ConnectionError("server set changed during revive")
            cur = servers[idx]
            if cur is not dead_sc and not getattr(cur, "dead", False):
                fresh.close_all()  # another reviver won the race
                return cur
            servers[idx] = fresh
        counters().bump("conn_revive", labels={"server": str(idx)})
        cur.close_all()  # idempotent; frees the old lanes' fds
        return fresh

    # --- data plane ------------------------------------------------------

    def init_tensor(self, key: int, num_elements: int, dtype_id: int,
                    trace: Optional[tuple] = None,
                    async_profile: bool = False,
                    staleness: int = -1,
                    server_opt: Optional[str] = None,
                    server_opt_hp: Optional[dict] = None) -> None:
        """Blocking init-push; doubles as the cross-worker barrier for this
        key (InitTensor blocking ZPush, operations.cc:283-414).

        Wire payload is language-neutral (u64 nelems + u32 dtype, network
        order) so the native C++ server parses it directly.  Carries the
        worker flag so a replayed init REPLACES this worker's barrier
        waiter instead of double-counting it (server.py).  ``trace``
        rides the optional trace-context header field; a retried init
        keeps its span.

        The ``version`` field carries the init-idempotency token
        (:meth:`_init_token`), fixed across this init's retries: a retry
        arriving AFTER the barrier released is acked from the server's
        completed-barrier record instead of re-parked — without it, the
        retrier's released peers never re-init the key and the short
        barrier strands the retry until its budget dies.

        ``async_profile`` (docs/async.md): the key is declared ASYNC —
        the server applies its pushes immediately and serves pulls from
        current state, bounded by ``staleness`` (-1 = unbounded).  The
        profile rides a 5-byte payload extension (u8 profile + i32
        staleness) that sync keys never send, so pre-tenancy servers
        keep seeing the exact 12-byte INIT they always parsed — and the
        native C++ engine, which has no async plane, rejects the
        extended form with a clean ``status=1`` echo (the Python-engine
        fallback rule, docs/async.md).

        ``server_opt`` (docs/architecture.md "Server-side optimizer"):
        the key declares a server-side update rule — bit 1 of the same
        profile byte, followed by the rule block (name + canonical-JSON
        ``server_opt_hp``), so the server runs the optimizer and this
        worker pulls updated parameters.  Engines without the update
        plane reject with the same clean status echo."""
        import struct

        token = self._init_token(key)
        payload = struct.pack("!QI", num_elements, dtype_id)
        profile = (1 if async_profile else 0) | (2 if server_opt else 0)
        if profile:
            payload += struct.pack("!Bi", profile, int(staleness))
        if server_opt:
            from byteps_tpu.comm.transport import encode_server_opt_block
            from byteps_tpu.server.update_rules import canonical_hp

            payload += encode_server_opt_block(
                server_opt, canonical_hp(server_opt_hp or {})
            )
        resp = self._blocking_request_retrying(
            key,
            lambda seq: Message(
                Op.INIT,
                key=key,
                seq=seq,
                flags=self._worker_flag(),
                version=token,
                payload=payload,
                trace=trace,
            ),
            f"server connection lost during init of key {key}",
            # the init ack legitimately waits for PEER workers — a
            # per-attempt deadline would punish stragglers' peers
            use_deadline=False,
        )
        if resp is not None and resp.status != 0:
            # the server REFUSED this init with a clean status echo —
            # the native C++ engine rejecting an async profile or a
            # job-namespaced key (docs/async.md), or a genuinely
            # incompatible server.  Failing fast here is the whole
            # point of the clean rejection: training on would leave
            # every later push/pull status-echoed too, and the job
            # would silently run on uninitialized state.
            from byteps_tpu.common.tenancy import job_of_key

            if server_opt:
                why = (f"the server-side optimizer plane (rule "
                       f"{server_opt!r}) needs Python-engine servers — "
                       "see docs/architecture.md")
            elif async_profile:
                why = ("async push_pull needs Python-engine servers "
                       "— see docs/async.md")
            elif job_of_key(key):
                why = (f"job {job_of_key(key)} keys need Python-engine "
                       "servers (multi-tenant namespaces are rejected "
                       "by the C++ engine) — see docs/async.md")
            else:
                why = "server refused the init"
            raise RuntimeError(
                f"server refused init for key {key} (status "
                f"{resp.status}): {why}"
            )

    def push(
        self,
        key: int,
        payload: bytes,
        dtype_id: int,
        version: int,
        cb: Callable[[], None],
        request_type: RequestType = RequestType.DEFAULT_PUSH_PULL,
        on_error: Optional[Callable[[], None]] = None,
        abort_check: Optional[Callable[[], bool]] = None,
        trace: Optional[tuple] = None,
        lossless: Optional[bool] = None,
    ) -> None:
        """Async push; ``cb`` fires on server ack (ZPush,
        core_loops.cc:538-582); ``on_error`` fires once retries are
        exhausted after connection failures (BYTEPS_RPC_RETRIES);
        ``abort_check`` fences pending retries once the caller abandons
        the operation.

        Replay-safe: the worker flag + version lets the server suppress a
        retransmitted push whose original WAS summed (ack lost), so
        summation stays exactly-once under retry.  ``trace`` is the
        (trace_id, span_id) context propagated on the wire — built ONCE
        into the closure, so every retry attempt re-sends the SAME span
        (the server's dedupe annotation then lands on the right one).

        ``lossless=True`` asks the transport for the lossless frame
        transform on this push (the tuner's per-key lossless arm for
        keys whose lossy codec lost) — the frame ships compressed only
        when the container actually wins; Python wire only (the native
        client's send path doesn't stamp the flag)."""
        cmd = get_command_type(request_type, dtype_id)
        flags = self._worker_flag()
        self._async_rpc(
            lambda seq: Message(
                Op.PUSH, key=key, seq=seq, payload=payload, cmd=cmd,
                version=version, flags=flags, trace=trace,
                lossless=lossless,
            ),
            key,
            deliver=lambda msg: cb(),
            on_error=on_error,
            abort_check=abort_check,
        )

    def push_fused(
        self,
        members: List[tuple],
        cb: Callable[[list], None],
        on_error: Optional[Callable[[], None]] = None,
        abort_check: Optional[Callable[[], bool]] = None,
        trace: Optional[tuple] = None,
        member_spans: Optional[List[int]] = None,
    ) -> None:
        """One multi-key fused push+pull RPC (Op.FUSED; docs/fusion.md).

        ``members`` is ``[(key, cmd, version, payload), ...]`` — small
        same-server partitions packed by the engine's FUSE stage.  The
        whole frame shares ONE seq, ONE deadline token, and ONE retry
        state (vs. 2 × len(members) for unfused push+pull pairs), and is
        routed by its first member's key.  ``cb`` receives the decoded
        reply ``[(key, version, merged_bytes), ...]``.

        Replay-safe like :meth:`push`: the frame carries the worker flag,
        and the server runs every sub-push through the per-(worker, key)
        exactly-once ledger — a retransmitted frame re-sums nothing that
        already landed, atomically per member key.

        Tracing: ``trace`` is the PACK's span (outer header field);
        ``member_spans`` (one id per member, same order) ride the fused
        body's optional trailer so the server can stamp per-member child
        spans.  Both are fixed per frame — retries keep their spans."""
        import struct as _struct

        from byteps_tpu.comm.transport import (
            decode_fused_reply,
            encode_fused_push,
        )

        frame = encode_fused_push(members, span_ids=member_spans)
        route_key = members[0][0]
        flags = self._worker_flag()
        # generation fence: the pack was grouped under the CURRENT server
        # set; if a resize lands before any attempt (first or retry), the
        # members may no longer share a server — fail fast to on_error
        # (the engine regroups via its unfuse fallback) instead of
        # re-shipping mis-homed keys until retries exhaust
        gen0 = self.server_generation

        def deliver(msg: Message) -> None:
            # decode INSIDE the delivery path: a corrupted reply (chaos
            # corrupt fault surviving framing, buggy server) must route to
            # the caller's error handler — raising here would unwind into
            # the recv lane AFTER the callback was popped and the deadline
            # cleared, stranding every member with no retry
            try:
                reply = decode_fused_reply(msg.payload)
            except (ValueError, _struct.error):
                counters().bump("fused_reply_malformed")
                if on_error is not None:
                    on_error()
                return
            finally:
                release_frame(msg.payload)  # every slot is a copy out of it
            cb(reply)

        self._async_rpc(
            lambda seq: Message(
                Op.FUSED, key=route_key, seq=seq, payload=frame,
                cmd=len(members), flags=flags, trace=trace,
            ),
            route_key,
            deliver=deliver,
            on_error=on_error,
            abort_check=abort_check,
            precheck=lambda: self.server_generation == gen0,
            # no frame-level heal and no redirect chase: the fused error
            # path is the unfuse fallback, whose per-key RPCs each carry
            # their own heal (and chase WRONG_OWNER individually)
            heal=False,
            chase=False,
        )

    def pull(
        self,
        key: int,
        version: int,
        cb: Callable[[bytes], None],
        dtype_id: int = 0,
        request_type: RequestType = RequestType.DEFAULT_PUSH_PULL,
        on_error: Optional[Callable[[], None]] = None,
        payload: bytes = b"",
        sink: Optional[memoryview] = None,
        abort_check: Optional[Callable[[], bool]] = None,
        trace: Optional[tuple] = None,
    ) -> None:
        """Async pull; ``cb`` receives the aggregated payload (ZPull,
        core_loops.cc:584-618); ``on_error`` fires if the server connection
        dies before the response.  ``payload`` carries the request body for
        row-sparse pulls (the row indices to gather).

        ``sink``: caller-owned writable buffer; when the response length
        matches, the payload is received INTO it (zero payload copies) and
        ``cb`` gets the ``_ZERO_COPIED`` sentinel instead of bytes.

        Pulls are read-only, hence idempotent — retried freely.  A retried
        sink pull never races a late writer: retry only happens after the
        previous attempt's connection is fully dead (all lanes exited)."""
        cmd = get_command_type(request_type, dtype_id)
        self._async_rpc(
            lambda seq: Message(
                Op.PULL, key=key, seq=seq, payload=payload, cmd=cmd,
                version=version, trace=trace,
            ),
            key,
            deliver=lambda msg: cb(msg.payload),
            on_error=on_error,
            sink=sink,
            abort_check=abort_check,
        )

    def register_compressor(self, key: int, kwargs: Dict[str, str]) -> None:
        """Ship compressor config to the owning server
        (kCompressedPushPull init push, operations.cc:396-408).

        Payload is newline-separated ``key=value`` text — parseable by the
        Python and native C++ servers alike.  Replay-idempotent (the
        server overwrites the key's chain), so the retrying path applies."""
        payload = "\n".join(f"{k}={v}" for k, v in sorted(kwargs.items())).encode()
        self._blocking_request_retrying(
            key,
            lambda seq: Message(
                Op.REGISTER_COMPRESSOR, key=key, seq=seq, payload=payload
            ),
            f"server connection lost registering compressor for key {key}",
        )

    def set_compression_lr(self, lr: float) -> None:
        """Broadcast the optimizer lr to every server's EF chains (flag
        bit 0 on REGISTER_COMPRESSOR, payload = big-endian f64 — the
        wire replacement for the reference's lr.s mmap,
        vanilla_error_feedback.h:44-58).  Fire-and-forget: EF lr scaling
        is a numerical refinement, not a correctness barrier."""
        import struct as _struct

        payload = _struct.pack("!d", float(lr))
        for sc in self._servers:
            try:
                seq = sc.alloc_seq(lambda msg: None)
                if seq < 0:
                    continue  # dead server already handled by the data path
                sc.send_msg(
                    Message(Op.REGISTER_COMPRESSOR, seq=seq, payload=payload, flags=1)
                )
            except (ConnectionError, OSError):
                continue  # dead server already handled by the data path
