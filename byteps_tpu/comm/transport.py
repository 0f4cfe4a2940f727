"""Framed TCP transport for the PS plane — the ZeroMQ-van replacement.

The reference's inter-host layer is ps-lite's "van" over ZMQ TCP / RDMA /
UCX (SURVEY §2.4).  The TPU build's DCN transport starts as plain TCP with
a fixed 32-byte binary header + raw payload (zero-copy into numpy on
receive); the framing is transport-agnostic so an RDMA-class backend can
slot in behind the same interface.

Header layout (network byte order):

    u8  magic      0xB5
    u8  op         Op enum
    u8  status     0 = OK
    u8  flags
    u32 seq        request/response matching id
    u64 key        partition key
    u32 cmd        Cantor-encoded (RequestType, DataType) (common.cc:98)
    u32 version    round / generation
    u64 length     payload byte count

Optional trace context (docs/observability.md): when ``status`` carries
``TRACE_FLAG`` (bit 7 — requests are otherwise status 0, so the bit is
free on the request direction), a 16-byte block ``u64 trace_id + u64
span_id`` follows the header, BEFORE the payload; ``length`` still
counts only the payload.  Decoders that don't trace (the native C++
engine) skip the block — old and new frames interoperate both ways.

Optional end-to-end integrity (docs/robustness.md "Wire integrity"):
when ``status`` carries ``CHECKSUM_FLAG`` (bit 6), a 4-byte big-endian
CRC32C follows the header (after the trace block when both are
present), BEFORE the payload.  The CRC covers EVERYTHING after the
fixed 32-byte header except itself — the trace block and the whole
payload (fused member blocks, span trailer, compressed bytes included)
— so a single flipped payload bit that TCP's 16-bit checksum missed is
detected at the receiver before the frame reaches any sum core or
demux.  Stamping is opt-in per process (``BYTEPS_WIRE_CHECKSUM=1``,
data-plane ops only — control frames stay byte-identical);
verification is self-describing: any receiver that sees the flag
checks it.  A mismatch is a DROP (:class:`ChecksumError` after the
stream is fully consumed — framing survives), healed by the ordinary
deadline/retry + exactly-once-ledger machinery; repeated mismatches on
one connection escalate to teardown (``BYTEPS_CHECKSUM_CONN_LIMIT``)
so connection revival re-dials a possibly-bad path.
"""

from __future__ import annotations

import enum
import os
import socket
import struct
import threading
from typing import Optional, Tuple

from byteps_tpu.core.tracing import releasing

MAGIC = 0xB5
HEADER_FMT = "!BBBBIQIIQ"
HEADER_SIZE = struct.calcsize(HEADER_FMT)
assert HEADER_SIZE == 32

#: status-byte bit: a 16-byte (trace_id, span_id) block follows the header
TRACE_FLAG = 0x80
_TRACE_FMT = "!QQ"
TRACE_SIZE = struct.calcsize(_TRACE_FMT)
assert TRACE_SIZE == 16

#: status-byte bit: a 4-byte big-endian CRC32C of (trace block + payload)
#: follows the header (after the trace block), BEFORE the payload
CHECKSUM_FLAG = 0x40
_CHECKSUM_FMT = "!I"
CHECKSUM_SIZE = struct.calcsize(_CHECKSUM_FMT)
assert CHECKSUM_SIZE == 4

#: status-byte bit: the payload is a lossless container
#: (compression/lossless.py frame format) — the header ``length`` and
#: the CRC32C cover the COMPRESSED bytes, so integrity is verified
#: before the decompressor runs.  Versioning by construction: no
#: pre-lossless decoder ever sets or strips this bit, so an old
#: receiver sees a nonzero status and refuses the frame cleanly
#: instead of mis-parsing the body (wire.h kLosslessFlag).
LOSSLESS_FLAG = 0x20


class ChecksumError(ValueError):
    """A frame's CRC32C did not match its bytes — payload corruption the
    framing layer cannot see.  Raised AFTER the frame is fully consumed,
    so the stream stays framed and the caller may keep the connection
    (drop semantics: discard the frame, let deadlines/retries heal it).
    A ``ValueError`` subclass so callers that treat malformed bodies as
    retryable failures (migration shipping, control decode guards)
    already do the right thing."""

    def __init__(self, op, expected: int, got: int) -> None:
        super().__init__(
            f"wire checksum mismatch on {getattr(op, 'name', op)} frame: "
            f"expected {expected:#010x}, computed {got:#010x}"
        )
        self.op = op
        self.expected = expected
        self.got = got


# the lossless twin of ChecksumError, re-exported so receivers catch the
# two corrupt-frame classes side by side (server _serve_conn_loop,
# client _recv_loop, tools/wire_fuzz.py)
from byteps_tpu.compression.lossless import LosslessError  # noqa: E402


class Op(enum.IntEnum):
    # scheduler plane (ps-lite Postoffice equivalents)
    REGISTER = 1      # node → scheduler: {role, host, port}
    ADDRBOOK = 2      # scheduler → nodes: {rank, servers: [(host, port)]}
    BARRIER = 3       # node → scheduler; response released when group full
    # data plane (KVWorker/KVServer equivalents)
    INIT = 10         # declare key storage; response is the init barrier
    PUSH = 11         # gradient payload; response = ack
    PULL = 12         # request payload; response = aggregated bytes
    REGISTER_COMPRESSOR = 13  # serialized compressor kwargs (operations.cc:396-408)
    FUSED = 14        # multi-key fused push+pull: request packs N small
                      # sub-pushes for one server; the response is the N
                      # merged round payloads (small-tensor coalescing,
                      # docs/fusion.md).  One seq / deadline / retry state
                      # covers the whole frame.
    # control
    PING = 20
    SHUTDOWN = 21
    QUERY = 22        # cluster liveness snapshot (heartbeat ages)
    # recovery plane (docs/robustness.md "healing flow"): a worker that
    # exhausted its RPC retries against a LIVE server asks that server
    # for its authoritative per-key round/ledger state, replays only the
    # journaled pushes the server never absorbed, and rejoins in place —
    # no global re-init barrier, no peer participation.  Served by BOTH
    # engines (the C++ server answers from its native ledger).
    RESYNC_QUERY = 23  # worker → server: {worker flag, keys of interest}
    RESYNC_STATE = 24  # server → worker: per-key {store_version, seen, ...}
    # elastic resharding plane (docs/robustness.md "migration flow"): the
    # key→server ownership map is versioned (consistent-hash ring,
    # epoch-stamped like worker membership); when the server set changes
    # the old owner ships each re-homed key's authoritative state —
    # store + exactly-once ledger + init-token record — to the new owner,
    # and answers stale-map requests with a redirect carrying the new map
    # epoch.  Workers chase the redirect the way they chase RESYNC;
    # the migrated ledger makes the handoff exactly-once.
    MIGRATE_STATE = 25  # old owner → new owner: one key's full state
    WRONG_OWNER = 26    # server → worker reply: {new owner rank};
                        # header ``version`` carries the new map epoch
    # observability plane (docs/observability.md "One scrape, both ends"):
    # a worker's get_metrics() asks each linked server for its registry AT
    # the call; the serve thread answers inline with MetricsRegistry.
    # snapshot() as JSON — raw cumulative state, the heartbeat delta's
    # baseline untouched.  The C++ engine rejects it (status 1) and the
    # worker's snapshot then holds its own process alone.
    METRICS = 27


# --- end-to-end wire integrity (CHECKSUM_FLAG) ----------------------------
#
# CRC32C (Castagnoli, the iSCSI/ext4 polynomial — hardware-accelerated on
# every server CPU this decade, and the one UCCL-Zip-style lossless wire
# transforms standardize on) over everything after the fixed header.
# The Python side prefers the shared C implementation in native/wire.h
# (``bps_wire_crc32c`` via ctypes — the SAME code the C++ engines stamp
# and verify with, so the two sides cannot drift) and falls back to a
# table-driven pure-Python loop when the lib isn't built.

#: ops that carry a checksum when BYTEPS_WIRE_CHECKSUM=1 — the data
#: plane only; control frames (scheduler link, PING/SHUTDOWN/QUERY)
#: stay byte-identical so arming the knob never perturbs the control
#: wire (mirrored by wire.h checksum_op — change both together)
_CHECKSUM_OPS = frozenset({10, 11, 12, 13, 14, 23, 24, 25, 26})

_TRUTHY_OFF = ("", "0", "false", "no", "off")


def wire_checksum_enabled() -> bool:
    """Stamp outgoing data-plane frames with CRC32C?  Read from
    ``BYTEPS_WIRE_CHECKSUM`` on every call (a dict lookup — cheap against
    a frame encode) so tests toggling the env need no cache reset.
    Verification is NOT gated on this: any received frame carrying
    ``CHECKSUM_FLAG`` is checked."""
    return os.environ.get("BYTEPS_WIRE_CHECKSUM", "").lower() not in _TRUTHY_OFF


#: ops whose payloads auto-compress with the lossless frame codec when
#: BYTEPS_WIRE_LOSSLESS=1 — the bit-exactness-critical control plane
#: only (RESYNC_STATE snapshots, MIGRATE_STATE store+ledger+opt-slot
#: shipments): exactly the megabyte-class frames lossy codecs can't
#: touch.  Gradient-plane frames keep their own per-key codecs.
#: Mirrored by wire.h lossless_op — change both together.
_LOSSLESS_OPS = frozenset({24, 25})


def wire_lossless_enabled() -> bool:
    """Compress outgoing control-plane frames with the lossless codec
    (``BYTEPS_WIRE_LOSSLESS``, default off)?  Same per-call env read as
    :func:`wire_checksum_enabled`.  Decode is NOT gated on this: any
    received frame carrying ``LOSSLESS_FLAG`` is decompressed."""
    return os.environ.get("BYTEPS_WIRE_LOSSLESS", "").lower() not in _TRUTHY_OFF


def checksum_conn_limit() -> int:
    """Mismatches tolerated on one connection before the receiver tears
    it down (``BYTEPS_CHECKSUM_CONN_LIMIT``, default 8; 0 = never) —
    the escalation from "one flipped bit, drop and retry" to "this path
    is corrupting repeatedly, revive the connection"."""
    v = os.environ.get("BYTEPS_CHECKSUM_CONN_LIMIT", "")
    try:
        n = int(v) if v else 8
    except ValueError:
        return 8
    # negatives/garbage = default, matching wire.h checksum_env_conn_limit
    # (a negative here would mean "drop on the FIRST mismatch" — the
    # opposite of what -1 conventionally asks for)
    return n if n >= 0 else 8


_CRC32C_POLY = 0x82F63B78
_crc_table: Optional[list] = None
#: ctypes fast path through native/wire.h crc32c (None = unresolved,
#: False = lib unavailable — pure-Python table takes over)
_crc_native = None


def _crc32c_table() -> list:
    global _crc_table
    if _crc_table is None:
        tbl = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (c >> 1) ^ (_CRC32C_POLY if c & 1 else 0)
            tbl.append(c)
        _crc_table = tbl
    return _crc_table


def _resolve_crc_native():
    global _crc_native
    try:
        from byteps_tpu.native import get_lib

        lib = get_lib()
        if lib is not None and hasattr(lib, "bps_wire_crc32c"):
            _crc_native = lib.bps_wire_crc32c
        else:
            _crc_native = False
    except Exception:  # noqa: BLE001 — any import/build issue → fallback
        _crc_native = False
    return _crc_native


def crc32c(data, crc: int = 0) -> int:
    """CRC32C of ``data`` (bytes / bytearray / memoryview / ndarray),
    chained: ``crc32c(b, crc32c(a)) == crc32c(a + b)``.  Uses the shared
    native implementation when the lib is built (the data plane's
    actual cost), pure Python otherwise."""
    native = _crc_native if _crc_native is not None else _resolve_crc_native()
    n = len(data)
    if not n:
        return crc
    if native:
        import numpy as _np

        a = _np.frombuffer(data, dtype=_np.uint8)  # no-copy view
        return int(native(a.ctypes.data, n, crc))
    tbl = _crc32c_table()
    c = crc ^ 0xFFFFFFFF
    for b in memoryview(data).cast("B"):  # no copy of a payload here either
        c = (c >> 8) ^ tbl[(c ^ b) & 0xFF]
    return c ^ 0xFFFFFFFF


def frame_checksum(trace: Optional[Tuple[int, int]], payload) -> int:
    """The CRC32C a frame's checksum block must carry: everything after
    the fixed header except the block itself — the 16-byte trace block
    (when present) chained with the payload bytes."""
    crc = 0
    if trace is not None:
        crc = crc32c(struct.pack(_TRACE_FMT, trace[0], trace[1]))
    return crc32c(payload, crc)


class Message:
    __slots__ = (
        "op", "status", "flags", "seq", "key", "cmd", "version", "payload",
        "trace", "checksum", "lossless", "_lossless_applied",
    )

    def __init__(
        self,
        op: Op,
        key: int = 0,
        payload: bytes = b"",
        seq: int = 0,
        cmd: int = 0,
        version: int = 0,
        status: int = 0,
        flags: int = 0,
        trace: Optional[Tuple[int, int]] = None,
        checksum: Optional[bool] = None,
        lossless: Optional[bool] = None,
    ) -> None:
        self.op = op
        self.status = status
        self.flags = flags
        self.seq = seq
        self.key = key
        self.cmd = cmd
        self.version = version
        self.payload = payload
        #: optional (trace_id, span_id) propagated in the trace-context
        #: header field (docs/observability.md); None = untraced frame
        self.trace = trace
        #: stamp a CHECKSUM_FLAG CRC32C block?  None (default) = follow
        #: BYTEPS_WIRE_CHECKSUM for data-plane ops; True/False force it
        #: (golden fixtures / fuzzing)
        self.checksum = checksum
        #: compress the payload with the lossless frame codec?  None
        #: (default) = follow BYTEPS_WIRE_LOSSLESS for _LOSSLESS_OPS;
        #: True = attempt on any op (the tuner's per-key lossless arm);
        #: False = never.  The frame carries LOSSLESS_FLAG only when the
        #: container actually came out smaller.
        self.lossless = lossless
        #: tri-state transform latch: None = not finalized, True/False =
        #: payload was / wasn't swapped for its compressed container —
        #: the transform runs exactly once even across send retries
        self._lossless_applied = None

    def _stamp_checksum(self) -> bool:
        ck = self.checksum
        if ck is None:
            return int(self.op) in _CHECKSUM_OPS and wire_checksum_enabled()
        return bool(ck)

    def _stamp_lossless(self) -> bool:
        """Finalize the lossless transform (idempotent): when the policy
        says compress AND the container wins, swap ``payload`` for the
        container and return True.  Must run before the header is packed
        — ``length`` and the CRC32C cover the bytes that actually ship,
        so integrity is verified before any receiver decompresses."""
        done = self._lossless_applied
        if done is not None:
            return done
        lz = self.lossless
        if lz is None:
            lz = int(self.op) in _LOSSLESS_OPS and wire_lossless_enabled()
        applied = False
        if lz:
            from byteps_tpu.compression.lossless import (
                MIN_BYTES, compress_frame,
            )

            if len(self.payload) >= MIN_BYTES:
                comp = compress_frame(self.payload)
                if len(comp) < len(self.payload):
                    self.payload = comp
                    applied = True
        self._lossless_applied = applied
        return applied

    def encode_header(self) -> bytes:
        lz = self._stamp_lossless()  # may swap payload — before pack/CRC
        ck = self._stamp_checksum()
        hdr = struct.pack(
            HEADER_FMT,
            MAGIC,
            int(self.op),
            self.status
            | (TRACE_FLAG if self.trace is not None else 0)
            | (CHECKSUM_FLAG if ck else 0)
            | (LOSSLESS_FLAG if lz else 0),
            self.flags,
            self.seq,
            self.key,
            self.cmd,
            self.version,
            len(self.payload),
        )
        if self.trace is not None:
            hdr += struct.pack(_TRACE_FMT, self.trace[0], self.trace[1])
        if ck:
            # computed once per frame per side; the scatter-gather send
            # below ships [header+trace+crc, payload] unchanged
            hdr += struct.pack(
                _CHECKSUM_FMT, frame_checksum(self.trace, self.payload)
            )
        return hdr

    def encode(self) -> bytes:
        return self.encode_header() + self.payload


def recv_into(sock: socket.socket, view: memoryview) -> None:
    """Receive exactly len(view) bytes INTO the caller's buffer — the
    zero-copy receive primitive (ps-lite ZPull pulls into the caller's
    SArray, core_loops.cc:584-618)."""
    n = len(view)
    got = 0
    with releasing():  # blocked in the kernel, and its copy: without the GIL
        while got < n:
            r = sock.recv_into(view[got:], n - got)
            if r == 0:
                raise ConnectionError("peer closed")
            got += r


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    """The small fixed blocks of a frame (header, trace, checksum; the
    shm van's handshake) as ``bytes``.  Payloads go through
    :func:`recv_payload`, which makes no second copy."""
    return bytes(recv_payload(sock, n))


#: payloads under this size never come from a :class:`FramePool`: headers,
#: books, INIT and PULL requests, most fused packs.  The allocator serves
#: them from memory it holds anyway; the pool is for the partitions
POOL_MIN_BYTES = 64 << 10
#: the most idle bytes one pool keeps.  A pool holds what its connection
#: had in flight at once (at most one push a key a worker, by the round
#: gate); a frame returned past this ceiling dies with its last holder
POOL_IDLE_BYTES = 1 << 30


class Frame(bytearray):
    """A payload buffer lent by a :class:`FramePool`: a ``bytearray`` to
    every reader (``np.frombuffer``, ``len``, slicing, ``struct``), plus
    the way back.  :func:`release_frame` returns it once; a frame nobody
    releases dies with its last holder, as a plain ``bytearray`` does."""

    __slots__ = ("_pool",)  # the pool it is out on loan from, or None


class FramePool:
    """The receive buffers of ONE connection, kept between frames.

    ``take(n)`` hands out a buffer of exactly ``n`` bytes that the pool
    already holds, or a fresh one where it holds none (counted:
    ``host_buffers_fresh`` / ``host_buffers_reused``, ``site="frame"``).
    A buffer comes back only by :func:`release_frame` — an explicit call at
    the point where its last holder lets go, never a guess from a
    reference count — and only once: the rule of :func:`recv_payload`
    (whoever holds a payload never sees it change) rests on that.  The
    pool therefore sizes itself: it never holds more buffers of a size
    than the connection had in flight at once."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._idle: dict = {}  # size → [Frame, ...]
        self._idle_bytes = 0

    def take(self, n: int) -> Frame:
        from byteps_tpu.core.telemetry import counters

        with self._lock:
            idle = self._idle.get(n)
            frame = idle.pop() if idle else None
            if frame is not None:
                self._idle_bytes -= n
        counters().bump(
            "host_buffers_fresh" if frame is None else "host_buffers_reused",
            labels=_FRAME_SITE,
        )
        if frame is None:
            frame = Frame(n)
        frame._pool = self
        return frame

    def give(self, frame: Frame) -> bool:
        """Take ``frame`` back; False where it is not out on loan from
        this pool (a second release, another pool's frame)."""
        with self._lock:
            if frame._pool is not self:
                return False
            frame._pool = None
            if self._idle_bytes + len(frame) <= POOL_IDLE_BYTES:
                self._idle.setdefault(len(frame), []).append(frame)
                self._idle_bytes += len(frame)
            return True


_FRAME_SITE = {"site": "frame"}


def release_frame(payload) -> bool:
    """Hand a received payload back to the pool it came from, if it came
    from one: call it where the LAST holder of ``payload`` lets go (its
    sum is in the store; the reply it carried is decoded).  Anything else
    — ``bytes``, a plain ``bytearray``, a frame already returned — is
    left alone."""
    pool = payload._pool if isinstance(payload, Frame) else None
    return pool is not None and pool.give(payload)


def recv_payload(sock: socket.socket, n: int,
                 pool: Optional[FramePool] = None) -> bytearray:
    """Receive a frame's ``n`` payload bytes and return the buffer they
    were received INTO: one pass over the payload on this side of the
    wire.  The returned message owns that buffer alone, so whoever holds
    a payload (a parked push, a fused member, a stored snapshot) never
    sees it change: without ``pool`` it is a fresh ``bytearray``; with
    one, a :class:`Frame` that goes back to the pool only when its holder
    says so (:func:`release_frame`)."""
    if pool is None or n < POOL_MIN_BYTES:
        buf = bytearray(n)
        recv_into(sock, memoryview(buf))
        return buf
    buf = pool.take(n)
    try:
        recv_into(sock, memoryview(buf))
    except BaseException:
        release_frame(buf)  # nobody saw it
        raise
    return buf


def recv_header_ex(sock: socket.socket) -> tuple:
    """Read + parse one header, trace-, checksum- and lossless-aware;
    returns (op, status, flags, seq, key, cmd, version, length, trace,
    crc, lossless) where ``trace`` is (trace_id, span_id) or None,
    ``crc`` is the frame's CHECKSUM_FLAG CRC32C or None, and
    ``lossless`` says the payload is a compressed container.  All flag
    bits are consumed here — ``status`` comes back clean, so frames
    from stamping and non-stamping peers are indistinguishable
    downstream.  The caller that receives the payload owns verification
    and decompression (:func:`verify_checksum` / :func:`recv_message`)."""
    hdr = _recv_exact(sock, HEADER_SIZE)
    magic, op, status, flags, seq, key, cmd, version, length = struct.unpack(
        HEADER_FMT, hdr
    )
    if magic != MAGIC:
        raise ConnectionError(f"bad magic {magic:#x}")
    trace = None
    if status & TRACE_FLAG:
        trace = struct.unpack(_TRACE_FMT, _recv_exact(sock, TRACE_SIZE))
        status &= ~TRACE_FLAG
    crc = None
    if status & CHECKSUM_FLAG:
        (crc,) = struct.unpack(_CHECKSUM_FMT, _recv_exact(sock, CHECKSUM_SIZE))
        status &= ~CHECKSUM_FLAG
    lossless = bool(status & LOSSLESS_FLAG)
    if lossless:
        status &= ~LOSSLESS_FLAG
    return (Op(op), status, flags, seq, key, cmd, version, length, trace,
            crc, lossless)


def recv_header(sock: socket.socket) -> tuple:
    """Read + parse one header; returns
    (op, status, flags, seq, key, cmd, version, length).  Any trace
    context or checksum block on the frame is read off the stream and
    dropped (the optional-on-decode guarantee: a non-verifying consumer
    stays framed)."""
    return recv_header_ex(sock)[:8]


def verify_checksum(crc: Optional[int], trace: Optional[Tuple[int, int]],
                    payload, op=None) -> None:
    """Check a received frame's CRC32C against its bytes; no-op for
    unstamped frames (``crc`` None).  Raises :class:`ChecksumError` on
    mismatch — the frame is already fully consumed, so the caller may
    drop it and keep reading the stream."""
    if crc is None:
        return
    got = frame_checksum(trace, payload)
    if got != crc:
        raise ChecksumError(op, crc, got)


def recv_message(sock: socket.socket,
                 pool: Optional[FramePool] = None) -> Message:
    """Receive one frame (its payload from ``pool``, where one is given:
    :func:`recv_payload`); verifies the CHECKSUM_FLAG CRC32C when the
    sender stamped one, then decompresses a LOSSLESS_FLAG container —
    in that order, so the CRC is checked over the exact bytes that
    shipped and a corrupt container never reaches the decompressor
    unflagged.  Both failures (:class:`ChecksumError` /
    :class:`LosslessError`) raise AFTER the frame is consumed — drop
    semantics, the stream stays framed."""
    return recv_body(sock, recv_header_ex(sock), pool)


def recv_body(sock: socket.socket, header: tuple,
              pool: Optional[FramePool] = None) -> Message:
    """What :func:`recv_message` does after the header (``header``: what
    :func:`recv_header_ex` returned): a reader that accounts for its wait for
    a frame apart from its work on one (the server's serve thread) calls the
    two halves itself."""
    op, status, flags, seq, key, cmd, version, length, trace, crc, lossless = header
    payload = recv_payload(sock, length, pool) if length else b""
    try:
        verify_checksum(crc, trace, payload, op=op)
        if lossless:
            from byteps_tpu.compression.lossless import decompress_frame

            container, payload = payload, decompress_frame(payload, op=op)
            release_frame(container)  # decoded into a payload of its own
    except (ChecksumError, LosslessError):
        release_frame(payload)  # dropped unread
        raise
    return Message(
        op, key=key, payload=payload, seq=seq, cmd=cmd, version=version,
        status=status, flags=flags, trace=trace,
    )


def _send(sock: socket.socket, msg: Message) -> None:
    # header first: encode_header may finalize the lossless transform,
    # swapping msg.payload for its compressed container
    hdr = msg.encode_header()
    payload = msg.payload
    # the socket calls are the sender's releasing calls (tracing.releasing):
    # the kernel's copy of a partition is made by this thread, on the CPU
    # and without the GIL
    if not payload:
        with releasing():
            sock.sendall(hdr)
        return
    sendmsg = getattr(sock, "sendmsg", None)
    if sendmsg is None:
        # van object without scatter-gather: header-then-payload, still no
        # concat copy of the payload
        with releasing():
            sock.sendall(hdr)
            sock.sendall(payload)
        return
    # scatter-gather send: header + payload leave in ONE syscall with ZERO
    # payload memcpys (the kernel gathers straight from the caller's
    # buffer) — ps-lite's zero-copy ZPush property (core_loops.cc:538-582)
    bufs = [memoryview(hdr), memoryview(payload)]
    while bufs:
        with releasing():
            sent = sendmsg(bufs)
        while bufs and sent >= len(bufs[0]):
            sent -= len(bufs[0])
            bufs.pop(0)
        if bufs and sent:
            bufs[0] = bufs[0][sent:]


def send_message(sock: socket.socket, msg: Message, lock: Optional[threading.Lock] = None) -> None:
    if lock is None:
        _send(sock, msg)
        return
    with releasing():  # the wait for a lane another sender is on
        lock.acquire()
    try:
        _send(sock, msg)
    finally:
        lock.release()


def connect(host: str, port: int, timeout: float = 30.0,
            added_lane: bool = False) -> socket.socket:
    """Dial an address from the scheduler book; the van scheme is encoded
    in the host string (``unix://...`` → UDS, else TCP).  ``added_lane``:
    the connection is one a split server link has beside the sockets a
    one-socket link had: a pull lane, the second sender's push lane
    (``Van.connect_added_lane``)."""
    from byteps_tpu.comm.van import van_for_address

    van = van_for_address(host)
    dial = van.connect_added_lane if added_lane else van.connect
    return dial(host, port, timeout=timeout)


def connect_control(host: str, port: int, timeout: float = 30.0) -> socket.socket:
    """Dial the scheduler (control plane).  When the process runs a
    chaos van AND ``BYTEPS_CHAOS_SCHED=1``, the connection is wrapped in
    the client-side fault layer so scheduler-link faults are
    deterministically injectable — ``BYTEPS_CHAOS_TARGET_PORT`` set to
    the scheduler port and symbolic ``BYTEPS_CHAOS_OPS`` names
    (REGISTER/PING/ADDRBOOK) compose (docs/robustness.md
    "Control-plane recovery").  Otherwise identical to :func:`connect`."""
    sock = connect(host, port, timeout=timeout)
    import os

    if os.environ.get("BYTEPS_VAN", "").startswith("chaos:"):
        from byteps_tpu.comm.chaos import wrap_control

        sock = wrap_control(sock, port)
    return sock


# --- multi-key fusion frames (Op.FUSED) ----------------------------------
#
# Request body (network byte order):
#     u32 count
#     count × [u64 key, u32 cmd, u32 version, u64 length, length bytes]
# Response body:
#     u32 count
#     count × [u64 key, u32 version, u64 length, length bytes]
#
# The outer 32-byte header carries the ROUTE key (first member), the frame
# seq, and the worker-identity flags byte; each member keeps its own key,
# Cantor-encoded cmd, and round version so the server sums every sub-push
# through the per-(worker, key) exactly-once ledger — a retried frame
# dedupes atomically per member key.
#
# Tracing (docs/observability.md): the PACK's span rides the outer
# header's trace-context field; the MEMBER span ids ride an optional
# trailer of count × u64 after the last member.  decode_fused_push reads
# exactly ``count`` members and ignores the trailer, so pre-observability
# decoders stay compatible; decode_fused_spans recovers the ids.

_FUSED_MEMBER_FMT = "!QIIQ"
_FUSED_MEMBER_SIZE = struct.calcsize(_FUSED_MEMBER_FMT)
_FUSED_REPLY_FMT = "!QIQ"
_FUSED_REPLY_SIZE = struct.calcsize(_FUSED_REPLY_FMT)


def encode_fused_push(members, span_ids=None) -> bytes:
    """Pack ``[(key, cmd, version, payload), ...]`` into one frame body.
    ``span_ids`` (one u64 per member, same order) appends the optional
    member-span trailer for distributed tracing."""
    parts = [struct.pack("!I", len(members))]
    for key, cmd, version, payload in members:
        parts.append(struct.pack(_FUSED_MEMBER_FMT, key, cmd, version, len(payload)))
        parts.append(bytes(payload) if not isinstance(payload, bytes) else payload)
    if span_ids:
        if len(span_ids) != len(members):
            raise ValueError("span_ids must match members 1:1")
        parts.append(struct.pack(f"!{len(span_ids)}Q", *span_ids))
    return b"".join(parts)


def _walk_fused_members(body: bytes) -> tuple:
    """→ (members, offset-after-last-member)."""
    (count,) = struct.unpack_from("!I", body, 0)
    off = 4
    members = []
    for _ in range(count):
        key, cmd, version, length = struct.unpack_from(_FUSED_MEMBER_FMT, body, off)
        off += _FUSED_MEMBER_SIZE
        if off + length > len(body):
            raise ValueError("fused frame truncated")
        members.append((key, cmd, version, body[off : off + length]))
        off += length
    return members, off


def decode_fused_push(body: bytes) -> list:
    """Inverse of :func:`encode_fused_push` → [(key, cmd, version, bytes)].
    A member-span trailer, if present, is ignored (old-decoder parity)."""
    return _walk_fused_members(body)[0]


def decode_fused_spans(body: bytes):
    """The member-span trailer of a fused frame → [span_id, ...], or
    None when the frame carries none (pre-observability sender)."""
    members, off = _walk_fused_members(body)
    if len(body) - off == 8 * len(members) and members:
        return list(struct.unpack_from(f"!{len(members)}Q", body, off))
    return None


def encode_fused_reply(members) -> bytes:
    """Pack ``[(key, version, payload), ...]`` into one reply body."""
    parts = [struct.pack("!I", len(members))]
    for key, version, payload in members:
        parts.append(struct.pack(_FUSED_REPLY_FMT, key, version, len(payload)))
        parts.append(payload)
    return b"".join(parts)


def decode_fused_reply(body: bytes) -> list:
    """Inverse of :func:`encode_fused_reply` → [(key, version, bytes)]."""
    (count,) = struct.unpack_from("!I", body, 0)
    off = 4
    members = []
    for _ in range(count):
        key, version, length = struct.unpack_from(_FUSED_REPLY_FMT, body, off)
        off += _FUSED_REPLY_SIZE
        if off + length > len(body):
            raise ValueError("fused reply truncated")
        members.append((key, version, body[off : off + length]))
        off += length
    return members


def decode_liveness(payload: bytes) -> dict:
    """Decode an Op.QUERY liveness reply: JSON stringifies rank keys;
    restore ints so consumers index by rank."""
    import json

    raw = json.loads(payload.decode())
    return {role: {int(r): age for r, age in d.items()} for role, d in raw.items()}


# --- recovery-plane frames (Op.RESYNC_QUERY / Op.RESYNC_STATE) ------------
#
# JSON bodies, like the control plane: resync is a rare, human-debuggable
# recovery RPC, not a data-plane hot path, and JSON keeps it greppable in
# packet dumps.  Served by BOTH engines (docs/robustness.md): the C++
# server answers from its own ledger with byte-compatible state bodies
# (ps_server.cc encode_resync_state_bytes, pinned by the golden wire
# fixtures); a PRE-parity native binary answers with a nonzero status
# and the worker's heal path falls back to the global re-init barrier.
#
# Query body:  {"worker": <flags byte>, "keys": [<u64 key>, ...]}
#              (empty "keys" = every key the server holds)
# State body:  {"keys": {"<key>": {"store_version": v, "seen": s,
#                                  "recv_count": c, "init": true}}}
#              where "seen" is the newest version of THIS worker's pushes
#              the server has absorbed into its exactly-once ledger.


def encode_resync_query(worker_flag: int, keys) -> bytes:
    """Body of an Op.RESYNC_QUERY frame."""
    import json

    return json.dumps(
        {"worker": int(worker_flag), "keys": [int(k) for k in keys]}
    ).encode()


def decode_resync_query(payload: bytes) -> Tuple[int, list]:
    """→ (worker_flag, [key, ...]); raises ValueError on a malformed body."""
    import json

    raw = json.loads(payload.decode())
    if not isinstance(raw, dict):
        raise ValueError("resync query body must be a JSON object")
    return int(raw.get("worker", 0)), [int(k) for k in raw.get("keys", [])]


def encode_resync_state(states: dict) -> bytes:
    """Body of an Op.RESYNC_STATE reply; ``states`` maps int key →
    {"store_version", "seen", "recv_count", "init"}."""
    import json

    return json.dumps({"keys": {str(k): v for k, v in states.items()}}).encode()


def decode_resync_state(payload: bytes) -> dict:
    """Inverse of :func:`encode_resync_state` → {int key: info dict}."""
    import json

    raw = json.loads(payload.decode())
    if not isinstance(raw, dict) or not isinstance(raw.get("keys", {}), dict):
        raise ValueError("resync state body must be a JSON object")
    return {int(k): v for k, v in raw.get("keys", {}).items()}


# --- resharding frames (Op.MIGRATE_STATE / Op.WRONG_OWNER) ----------------
#
# MIGRATE_STATE body: u32 json length + JSON metadata + raw store bytes +
# raw accumulator bytes.  The metadata (key, map epoch, dtype, round
# state, the per-(worker) exactly-once ledger ``push_seen``, the
# init-token record ``init_done``, compressor kwargs) is JSON like the
# RESYNC bodies — migration is a rare control-plane event and the state
# is already proven byte-stable in that encoding; the two big arrays ride
# raw after it so a multi-MB store pays no base64 tax.  The receiver acks
# with an empty MIGRATE_STATE reply (nonzero status = refused: resharding
# disabled, or an engine that cannot import state).
#
# WRONG_OWNER body: JSON {"owner": rank, "epoch": map_epoch}; the header
# ``version`` field carries the epoch too so a worker can chase without
# parsing the body.


def encode_migrate_state(meta: dict, store: bytes = b"",
                         accum: bytes = b"") -> bytes:
    """Body of an Op.MIGRATE_STATE frame; ``meta`` must already carry
    ``store_nbytes``/``accum_nbytes`` matching the raw tails."""
    import json

    head = json.dumps(meta).encode()
    return struct.pack("!I", len(head)) + head + store + accum


def decode_migrate_state(payload: bytes) -> Tuple[dict, bytes, bytes]:
    """Inverse of :func:`encode_migrate_state` → (meta, store, accum);
    raises ValueError on a malformed or truncated body."""
    import json

    if len(payload) < 4:
        raise ValueError("migrate frame too short")
    (hlen,) = struct.unpack_from("!I", payload, 0)
    if 4 + hlen > len(payload):
        raise ValueError("migrate frame truncated (header)")
    meta = json.loads(payload[4 : 4 + hlen].decode())
    if not isinstance(meta, dict):
        raise ValueError("migrate metadata must be a JSON object")
    off = 4 + hlen
    sn = int(meta.get("store_nbytes", 0))
    an = int(meta.get("accum_nbytes", 0))
    if sn < 0 or an < 0 or off + sn + an > len(payload):
        raise ValueError("migrate frame truncated (payload)")
    return meta, payload[off : off + sn], payload[off + sn : off + sn + an]


def decode_migrate_extra(payload: bytes, meta: dict) -> bytes:
    """The raw tail *behind* store+accum in a MIGRATE_STATE body —
    optimizer slot bytes (``meta["opt_slot_nbytes"]`` names the split).
    Kept out of :func:`decode_migrate_state`'s pinned 3-tuple so the
    PR 8 codec round-trip tests stay byte-for-byte valid; that decoder
    already tolerates trailing bytes, this one returns them."""
    (hlen,) = struct.unpack_from("!I", payload, 0)
    off = (
        4 + hlen
        + int(meta.get("store_nbytes", 0))
        + int(meta.get("accum_nbytes", 0))
    )
    return payload[off:]


# --- server-opt INIT profile block (bit 1 of the profile byte) ------------
#
# The PR 12 async profile appends ``!Bi`` (profile byte + staleness) to
# the 12-byte INIT body; sync keys stay byte-identical.  The server-side
# optimizer plane turns that byte into a bitmask (bit 0 = async, bit 1 =
# server-opt) and, when bit 1 is set, appends a rule block at offset 17:
# ``!H`` rule-name length + name bytes + ``!I`` hyperparam-JSON length +
# canonical JSON.  Engines that predate the bit reject the whole INIT
# with status=1 (the native engine counts ``native_server_opt_reject``),
# exactly like the async precedent — never a silent downgrade to SUM.


def encode_server_opt_block(rule: str, hp_json: str) -> bytes:
    """The rule block appended after the ``!Bi`` profile extension."""
    nb = str(rule).encode("utf-8")
    hb = hp_json.encode("utf-8")
    return struct.pack("!H", len(nb)) + nb + struct.pack("!I", len(hb)) + hb


def decode_server_opt_block(payload: bytes, off: int) -> Tuple[str, bytes]:
    """Inverse of :func:`encode_server_opt_block` → (rule name, raw
    hyperparam JSON bytes); raises ValueError when truncated."""
    if off + 2 > len(payload):
        raise ValueError("server-opt block truncated (name length)")
    (nlen,) = struct.unpack_from("!H", payload, off)
    off += 2
    if off + nlen + 4 > len(payload):
        raise ValueError("server-opt block truncated (name)")
    name = payload[off : off + nlen].decode("utf-8")
    off += nlen
    (hlen,) = struct.unpack_from("!I", payload, off)
    off += 4
    if off + hlen > len(payload):
        raise ValueError("server-opt block truncated (hyperparams)")
    return name, payload[off : off + hlen]


def encode_wrong_owner(epoch: int, owner: int) -> bytes:
    """Body of an Op.WRONG_OWNER reply."""
    import json

    return json.dumps({"owner": int(owner), "epoch": int(epoch)}).encode()


def decode_wrong_owner(payload: bytes) -> Tuple[int, int]:
    """→ (map_epoch, owner_rank); tolerant of an empty body (the header
    ``version`` field is the authoritative epoch) → (0, -1)."""
    import json

    try:
        raw = json.loads(payload.decode()) if payload else {}
    except (ValueError, UnicodeDecodeError):
        raw = {}
    if not isinstance(raw, dict):
        raw = {}
    return int(raw.get("epoch", 0)), int(raw.get("owner", -1))


def close_socket(sock: Optional[socket.socket]) -> None:
    """shutdown(SHUT_RDWR) then close.

    A bare ``close()`` while another thread is blocked in ``recv`` on the
    same socket does NOT close the fd (CPython defers it until the blocking
    call returns) — no FIN is sent and the peer never learns we left.
    ``shutdown`` sends the FIN immediately and wakes the blocked reader.
    """
    if sock is None:
        return
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


def listen(host: str = "0.0.0.0", port: int = 0) -> Tuple[socket.socket, int]:
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind((host, port))
    srv.listen(128)
    return srv, srv.getsockname()[1]
