"""Chaos van: deterministic fault injection on the PS data plane.

Production BytePS assumes nodes die mid-training (ps-lite heartbeats +
elastic suspend/resume, SURVEY §5.3); this van lets one machine rehearse
those failures.  ``BYTEPS_VAN=chaos:<inner>`` wraps any fd-stream van
(``chaos:tcp``, ``chaos:uds``, ``chaos:shm``) and injects faults on
every data-plane connection — both directions, because the listener
wraps accepted sockets and the published address carries a ``chaos+``
prefix so dialing clients wrap theirs too (the same address-encoded
dispatch the shm van uses).

Faults are decided per FRAME — transport.py sends one framed message per
``sendall``/``sendmsg`` call — so a "drop" loses exactly one message
while the connection stays healthy, which is the case per-RPC deadlines
and retries exist for.  Classes:

- **drop**:       the frame never leaves; silence until a deadline fires.
- **delay**:      the frame is held up to ``BYTEPS_CHAOS_DELAY_MS``.
- **disconnect**: the connection is torn down (peer sees EOF/RST) — the
                  client's revive-and-retry path must heal it.
- **truncate**:   a prefix of the frame is sent, then the connection is
                  torn down — a crash mid-send; the peer must detect the
                  short frame, not parse garbage.
- **corrupt**:    the frame's magic byte is flipped before sending — the
                  peer's framing check rejects it and drops the
                  connection.  (This models link corruption that survives
                  to the app layer as frame desync.)
- **payload corrupt**: ONE seeded byte past the fixed 32-byte header
                  gets one bit flipped and the frame ships otherwise
                  intact — the most common real-DCN silent failure (bad
                  NIC/DRAM flipping bits that TCP's 16-bit checksum
                  misses).  Historically this module refused to inject
                  it because nothing could detect it; with the
                  end-to-end integrity plane (``BYTEPS_WIRE_CHECKSUM``,
                  docs/robustness.md "Wire integrity") a receiver
                  verifies the frame's CRC32C before any sum core or
                  demux sees it, so payload corruption is now an
                  injectable, testable fault class.  With checksums OFF
                  the flip passes silently — exactly the A/B that
                  proves detection is the checksum's doing, not luck.

Determinism: ``BYTEPS_CHAOS_SEED`` seeds a per-connection
``random.Random`` derived from ``(seed, connection_index)``, where the
index is a process-global counter — with a fixed seed and a fixed
connect order, the fault schedule replays exactly.  A link's pull lanes
(and the scheduler link) count in streams of their own, so the push
lanes' indices are those of the servers dialed, in order.

Knobs (probabilities in [0,1], applied per frame in the order drop →
disconnect → truncate → corrupt → payload corrupt; delay is rolled
independently):

    BYTEPS_CHAOS_SEED            int,   default 0
    BYTEPS_CHAOS_DROP            float, default 0
    BYTEPS_CHAOS_DISCONNECT      float, default 0
    BYTEPS_CHAOS_TRUNCATE        float, default 0
    BYTEPS_CHAOS_CORRUPT         float, default 0
    BYTEPS_CHAOS_PAYLOAD_CORRUPT float, default 0
    BYTEPS_CHAOS_DELAY           float, default 0
    BYTEPS_CHAOS_DELAY_MS        float, default 20 (max; uniform 0..max)

Targeting (one-sided failure rehearsal — docs/robustness.md "healing
flow"; all three compose):

    BYTEPS_CHAOS_OPS          comma-separated op codes (transport.Op
                              ints) or Op member names ("MIGRATE_STATE",
                              case-insensitive); only frames whose
                              header op matches are faulted (RESYNC and
                              migration frames are ordinary frames: name
                              23/24 or MIGRATE_STATE/WRONG_OWNER here to
                              fault the recovery or resharding plane
                              itself).  Empty = all ops.
    BYTEPS_CHAOS_TARGET_PORT  fault only connections dialed to — or
                              accepted by a listener bound at — this TCP
                              port (one server out of the fleet).  0 =
                              every connection.
    BYTEPS_CHAOS_FAULT_BUDGET process-global cap on TOTAL injected
                              faults; once spent, chaos passes through.
                              With DROP=1.0 this makes "exactly the
                              first N targeted frames die" a
                              deterministic schedule — how the resync
                              tests kill one worker's retry budget on
                              cue.  -1 (default) = unlimited.

Non-targeted frames consume no RNG rolls, so the schedule for targeted
frames stays reproducible per (seed, connection index) regardless of
surrounding traffic.  Every injected fault bumps a ``chaos_*``
robustness counter (core/telemetry.py), so tests can assert the
schedule actually fired.

Scheduler link (docs/robustness.md "Control-plane recovery"): with
``BYTEPS_CHAOS_SCHED=1`` under a chaos van, the CONTROL plane is
faulted too — node→scheduler dials wrap their socket
(:func:`wrap_control`) and the scheduler wraps accepted connections,
so ``BYTEPS_CHAOS_TARGET_PORT=<scheduler port>`` plus symbolic
``BYTEPS_CHAOS_OPS`` names (``REGISTER``/``PING``/``ADDRBOOK``/
``BARRIER``) make scheduler-link faults deterministically injectable.
Control connections draw from a SEPARATE connection-index counter, so
arming the flag never shifts the data plane's per-connection RNG
streams (existing seeded schedules replay unchanged).  Off (default):
the scheduler link is never faulted and control wire behavior is
byte-identical to a chaos-less run.
"""

from __future__ import annotations

import itertools
import os
import random
import socket
import threading
import time
from dataclasses import dataclass

from byteps_tpu.comm.van import CHAOS_PREFIX  # single source of the prefix

#: process-global connection index — (seed, index) keys each socket's RNG
_conn_counter = itertools.count()
_conn_counter_lock = threading.Lock()

#: SEPARATE index stream for control-plane (scheduler) connections:
#: arming BYTEPS_CHAOS_SCHED must not shift the data-plane sockets'
#: (seed, index)-keyed RNG streams, or every existing seeded schedule
#: would silently change.  Offset keeps the two streams' derived seeds
#: disjoint.
_ctrl_conn_counter = itertools.count(1 << 16)

#: and a third for the lanes a split tcp link to a server ADDED to its one
#: socket (the pull lanes, then the second sender's push lane:
#: ps_client._ServerConn): a link's first push lanes keep the indices its
#: one socket had, so a seeded schedule aimed at a server's pushes still
#: hits them (those of the keys that stayed on lane 0) whatever else the
#: link dials.
_added_conn_counter = itertools.count(1 << 17)


def _next_conn_index() -> int:
    with _conn_counter_lock:
        return next(_conn_counter)


def _next_ctrl_conn_index() -> int:
    with _conn_counter_lock:
        return next(_ctrl_conn_counter)


def _next_added_conn_index() -> int:
    with _conn_counter_lock:
        return next(_added_conn_counter)


def reset_conn_indices() -> None:
    """Restart the connection-index streams from their origins.

    The per-socket fault RNG is keyed by (seed, connection index), and
    the index is process-global — a seeded chaos schedule therefore
    depends on how many chaos connections EARLIER tests in the same
    process happened to open.  Deterministic chaos tests call this at
    setup so their schedule is canonical (indices from 0) no matter
    which sub-suite combination runs them — the order-dependence that
    made test_fusion's ``[native-s4]`` lane flake across pytest
    selections.  Test-harness only: live jobs never reset mid-run."""
    global _conn_counter, _ctrl_conn_counter, _added_conn_counter
    with _conn_counter_lock:
        _conn_counter = itertools.count()
        _ctrl_conn_counter = itertools.count(1 << 16)
        _added_conn_counter = itertools.count(1 << 17)


def control_chaos_enabled() -> bool:
    """True when the process opted the scheduler link into fault
    injection: a chaos van is selected AND ``BYTEPS_CHAOS_SCHED=1``."""
    return (
        os.environ.get("BYTEPS_VAN", "").startswith("chaos:")
        and os.environ.get("BYTEPS_CHAOS_SCHED", "0").lower()
        not in ("", "0", "false", "no", "off")
    )


def wrap_control(sock, peer_port: int):
    """Chaos-wrap one control-plane (node→scheduler) socket when
    :func:`control_chaos_enabled`; pass-through otherwise.  Targeting
    composes: ``BYTEPS_CHAOS_TARGET_PORT=<scheduler port>`` faults only
    the scheduler link, and ``BYTEPS_CHAOS_OPS`` can name the control
    ops (REGISTER/PING/ADDRBOOK/BARRIER)."""
    if not control_chaos_enabled():
        return sock
    return ChaosSocket(
        sock, ChaosParams.from_env(), _next_ctrl_conn_index(),
        peer_port=peer_port,
    )


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v not in (None, "") else default


def _parse_op(tok: str) -> int:
    """One BYTEPS_CHAOS_OPS token → wire op code.  Accepts the raw int
    ("25") or the transport.Op member name ("MIGRATE_STATE",
    case-insensitive) — deterministic tests naming the migration plane
    shouldn't have to hardcode its op numbers."""
    tok = tok.strip()
    try:
        return int(tok)
    except ValueError:
        from byteps_tpu.comm.transport import Op

        try:
            return int(Op[tok.upper()])
        except KeyError:
            raise ValueError(
                f"BYTEPS_CHAOS_OPS token {tok!r} is neither an op code "
                "nor a transport.Op name"
            ) from None


@dataclass(frozen=True)
class ChaosParams:
    seed: int = 0
    drop: float = 0.0
    disconnect: float = 0.0
    truncate: float = 0.0
    corrupt: float = 0.0
    #: seeded single-bit flip past the fixed 32-byte header (frame ships
    #: otherwise intact) — detectable ONLY by the CHECKSUM_FLAG integrity
    #: plane (docs/robustness.md "Wire integrity")
    payload_corrupt: float = 0.0
    delay: float = 0.0
    delay_ms: float = 20.0
    #: fault only frames with these header op codes (empty = all)
    ops: frozenset = frozenset()
    #: fault only connections to/from this TCP port (0 = all)
    target_port: int = 0

    @staticmethod
    def from_env() -> "ChaosParams":
        ops = frozenset(
            _parse_op(tok) for tok in
            os.environ.get("BYTEPS_CHAOS_OPS", "").split(",") if tok.strip()
        )
        return ChaosParams(
            seed=int(os.environ.get("BYTEPS_CHAOS_SEED", "0") or 0),
            drop=_env_float("BYTEPS_CHAOS_DROP", 0.0),
            disconnect=_env_float("BYTEPS_CHAOS_DISCONNECT", 0.0),
            truncate=_env_float("BYTEPS_CHAOS_TRUNCATE", 0.0),
            corrupt=_env_float("BYTEPS_CHAOS_CORRUPT", 0.0),
            payload_corrupt=_env_float("BYTEPS_CHAOS_PAYLOAD_CORRUPT", 0.0),
            delay=_env_float("BYTEPS_CHAOS_DELAY", 0.0),
            delay_ms=_env_float("BYTEPS_CHAOS_DELAY_MS", 20.0),
            ops=ops,
            target_port=int(
                os.environ.get("BYTEPS_CHAOS_TARGET_PORT", "0") or 0
            ),
        )


# --- process-global fault budget (BYTEPS_CHAOS_FAULT_BUDGET) --------------
#
# Counts TOTAL injected faults across every chaos connection in the
# process; once spent the chaos layer passes frames through untouched.
# Latched from env on first use; tests reset it explicitly.

_budget_lock = threading.Lock()
_budget_left: list = [None]  # [None] = unread; [-1] = unlimited


def reset_fault_budget(n=None) -> None:
    """Re-arm the process fault budget: ``n`` faults, or re-read
    ``BYTEPS_CHAOS_FAULT_BUDGET`` lazily when ``n`` is None."""
    with _budget_lock:
        _budget_left[0] = None if n is None else int(n)


def _budget_allows() -> bool:
    """Consume one unit of the fault budget; False = budget spent (the
    frame must pass through un-faulted)."""
    with _budget_lock:
        left = _budget_left[0]
        if left is None:
            left = int(
                os.environ.get("BYTEPS_CHAOS_FAULT_BUDGET", "-1") or -1
            )
        if left < 0:
            _budget_left[0] = left
            return True
        if left == 0:
            _budget_left[0] = 0
            return False
        _budget_left[0] = left - 1
        return True


class ChaosSocket:
    """Socket proxy injecting send-side faults at frame granularity.

    Exposes ``sendmsg`` so transport._send delivers header+payload as ONE
    call (the scatter-gather path) — a fault then hits a whole frame, not
    half of one.  Header-only messages arrive via ``sendall``, also one
    frame.  Receives and teardown pass straight through.
    """

    def __init__(self, sock, params: ChaosParams, conn_index: int,
                 peer_port: int = 0) -> None:
        self._sock = sock
        self._p = params
        self.conn_index = conn_index
        # independent stream per connection, reproducible per (seed, index)
        self._rng = random.Random((params.seed << 20) ^ conn_index)
        self._send_lock = threading.Lock()  # fault decisions are ordered
        # one-sided targeting: with target_port set, only the connection
        # dialed to (or accepted at) that port is ever faulted
        self._targeted = (
            not params.target_port or peer_port == params.target_port
        )

    # --- fault engine -----------------------------------------------------
    def _bump(self, name: str, frame: bytes = b"") -> None:
        from byteps_tpu.core.telemetry import counters

        counters().bump(name)
        self._tag_span(name, frame)

    @staticmethod
    def _tag_span(name: str, frame: bytes) -> None:
        """Stamp the injected fault on the OWNING span (the trace context
        of the frame being faulted), so a rehearsed fault is
        distinguishable from an organic one on the merged timeline: the
        instant event shares the victim RPC's trace/span ids and carries
        ``injected: true`` (docs/observability.md)."""
        from byteps_tpu.core.tracing import get_process_tracer

        tracer = get_process_tracer()
        if tracer is None or not tracer.enabled:
            return
        args = {"fault": name, "injected": True}
        if len(frame) >= 48 and frame[2] & 0x80:  # status TRACE_FLAG
            import struct as _struct

            trace_id, span_id = _struct.unpack_from("!QQ", frame, 32)
            args["trace"] = format(trace_id, "x")
            args["span"] = format(span_id, "x")
        tracer.record_instant("chaos", name, args)

    def _die(self, reason: str) -> None:
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        raise ConnectionError(f"chaos: injected {reason}")

    def _send_frame(self, data: bytes) -> None:
        p = self._p
        with self._send_lock:
            # targeting: an untargeted connection, or a frame whose
            # header op is outside the BYTEPS_CHAOS_OPS filter, passes
            # through WITHOUT consuming an RNG roll — the targeted
            # schedule stays reproducible regardless of other traffic
            if not self._targeted or (
                p.ops and (len(data) < 2 or data[1] not in p.ops)
            ):
                self._sock.sendall(data)
                return
            roll = self._rng.random()
            if roll < p.drop:
                if not _budget_allows():
                    self._sock.sendall(data)
                    return
                self._bump("chaos_drop", data)
                return
            roll -= p.drop
            if roll < p.disconnect:
                if not _budget_allows():
                    self._sock.sendall(data)
                    return
                self._bump("chaos_disconnect", data)
                self._die("disconnect")
            roll -= p.disconnect
            if roll < p.truncate:
                if not _budget_allows():
                    self._sock.sendall(data)
                    return
                self._bump("chaos_truncate", data)
                k = self._rng.randrange(0, max(1, len(data)))
                try:
                    self._sock.sendall(data[:k])
                except OSError:
                    pass
                self._die("truncated frame")
            roll -= p.truncate
            if roll < p.corrupt:
                if not _budget_allows():
                    self._sock.sendall(data)
                    return
                self._bump("chaos_corrupt", data)
                mangled = bytearray(data)
                if mangled:
                    mangled[0] ^= 0xFF  # flip the magic → framing rejects it
                self._sock.sendall(bytes(mangled))
                return
            roll -= p.corrupt
            if roll < p.payload_corrupt:
                # single-bit flip past the fixed 32-byte header (trace
                # block / checksum field / payload — all covered by the
                # CHECKSUM_FLAG CRC); a header-only frame has nothing to
                # flip and passes through untouched without spending
                # budget
                if len(data) <= 32 or not _budget_allows():
                    self._sock.sendall(data)
                    return
                self._bump("chaos_payload_corrupt", data)
                mangled = bytearray(data)
                idx = self._rng.randrange(32, len(mangled))
                mangled[idx] ^= 1 << self._rng.randrange(8)
                self._sock.sendall(bytes(mangled))
                return
            if (p.delay > 0 and self._rng.random() < p.delay
                    and _budget_allows()):
                self._bump("chaos_delay", data)
                time.sleep(self._rng.random() * p.delay_ms / 1e3)
            self._sock.sendall(data)

    # --- socket surface used by transport.py ------------------------------
    def sendall(self, data) -> None:
        self._send_frame(bytes(data))

    def sendmsg(self, bufs) -> int:
        # one frame: transport._send passes [header, payload]; joining keeps
        # the fault decision atomic per message (the copy is the chaos tax)
        frame = b"".join(bytes(b) for b in bufs)
        self._send_frame(frame)
        return len(frame)

    @property
    def family(self):
        return self._sock.family

    def recv(self, n: int) -> bytes:
        return self._sock.recv(n)

    def recv_into(self, buf, nbytes: int = 0) -> int:
        return self._sock.recv_into(buf, nbytes)

    def settimeout(self, t) -> None:
        self._sock.settimeout(t)

    def setsockopt(self, *a) -> None:
        self._sock.setsockopt(*a)

    def fileno(self) -> int:
        return self._sock.fileno()

    def shutdown(self, how: int = socket.SHUT_RDWR) -> None:
        try:
            self._sock.shutdown(how)
        except OSError:
            pass

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


class ChaosListener:
    """Accept wrapper: accepted connections get the chaos treatment, so
    server→worker frames (acks, pull responses) are faulted too.
    ``port`` is the bound listen port — with BYTEPS_CHAOS_TARGET_PORT
    set, only the one server bound there faults its response lanes."""

    def __init__(self, inner, params: ChaosParams, port: int = 0) -> None:
        self._inner = inner
        self._params = params
        self._port = port

    def accept(self):
        conn, addr = self._inner.accept()
        return (
            ChaosSocket(conn, self._params, _next_conn_index(),
                        peer_port=self._port),
            addr,
        )

    def shutdown(self, how: int = socket.SHUT_RDWR) -> None:
        try:
            self._inner.shutdown(how)
        except OSError:
            pass

    def close(self) -> None:
        try:
            self._inner.close()
        except OSError:
            pass


def make_chaos_van(inner):
    """Build the chaos wrapper around an inner Van instance.

    Lives here (not van.py) so the van registry needs no chaos imports
    unless chaos is actually selected.
    """
    from byteps_tpu.comm.van import Van

    class ChaosVan(Van):
        name = f"chaos:{inner.name}"

        def __init__(self) -> None:
            self.inner = inner
            self.params = ChaosParams.from_env()

        def listen(self, host: str):
            lsock, phost, port = self.inner.listen(host)
            return (
                ChaosListener(lsock, self.params, port=port),
                CHAOS_PREFIX + phost,
                port,
            )

        def connect(self, host: str, port: int, timeout: float = 30.0,
                    next_index=_next_conn_index):
            if host.startswith(CHAOS_PREFIX):
                host = host[len(CHAOS_PREFIX):]
            sock = self.inner.connect(host, port, timeout=timeout)
            return ChaosSocket(sock, self.params, next_index(),
                               peer_port=port)

        def connect_added_lane(self, host: str, port: int, timeout: float = 30.0):
            return self.connect(host, port, timeout, _next_added_conn_index)

    return ChaosVan()
