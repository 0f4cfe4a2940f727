"""End-to-end wire integrity (docs/robustness.md "Wire integrity"):
payload checksums, corruption quarantine, and the detectable-corruption
chaos mode.

Layers under test:

- the shared CRC32C: known vectors, chaining, pure-Python ↔ native
  (wire.h) parity;
- the CHECKSUM_FLAG codec: stamp/strip round trips (± trace block),
  drop-semantics on mismatch (the stream stays framed — the NEXT frame
  decodes), non-verifying consumers stay framed, control ops never
  stamp, explicit overrides beat the env knob;
- the chaos van's payload-corrupt fault: seeded single-bit flip past
  the fixed header, composing with op targeting and the fault budget;
- tools/wire_fuzz.py smoke (the raise-or-checksum-reject contract);
- verify-and-heal, wire level, parametrized over
  {python, native-s1, native-s4} × {fused, unfused} × {raw, onebit}:
  a corrupted push is dropped without a reply and without touching the
  ledger, the clean resend sums once, a replay dedupes, pulls stay
  bitwise;
- connection quarantine: BYTEPS_CHECKSUM_CONN_LIMIT mismatches drop
  the connection on both server engines (and a fresh dial serves);
- client-side verification: corrupted replies (fused multi-key,
  RESYNC_STATE shapes) are dropped BEFORE the seq demux by the Python
  recv lanes and the native client's C++ lanes, the pending callback
  surviving for the retry; the conn-limit escalation poisons the
  connection so revival re-dials;
- end-to-end: a corrupted fused frame heals through deadline/retry with
  bitwise pulls; a permanently-corrupted RESYNC_STATE stream fails the
  heal CLEANLY to the re-init path (resync_giveup, key marked, no
  hang);
- observability: the corruption_storm flight trigger and the
  wire_corruption doctor rule fire on the right shapes.
"""

import importlib.util
import os
import socket
import struct
import sys
import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.common.types import DataType, RequestType, get_command_type
from byteps_tpu.comm.chaos import ChaosParams, ChaosSocket, reset_fault_budget
from byteps_tpu.comm.transport import (
    CHECKSUM_FLAG,
    HEADER_SIZE,
    ChecksumError,
    Message,
    Op,
    close_socket,
    connect,
    crc32c,
    decode_fused_reply,
    encode_fused_push,
    encode_fused_reply,
    frame_checksum,
    recv_header,
    recv_message,
    send_message,
)
from byteps_tpu.core.telemetry import counters
from conftest import (
    ENGINE_STRIPES,
    ENGINE_STRIPES_IDS,
    make_ps_server,
    require_engine,
    set_stripes,
)

CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, int(DataType.FLOAT32))
CMD_COMP = get_command_type(RequestType.COMPRESSED_PUSH_PULL,
                            int(DataType.FLOAT32))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _flip(frame: bytes, offset: int, bit: int = 0) -> bytes:
    b = bytearray(frame)
    b[offset] ^= 1 << bit
    return bytes(b)


# --------------------------------------------------------------------------
# CRC32C


class TestCrc32c:
    def test_known_vectors(self):
        # iSCSI test vectors (RFC 3720 appendix shapes)
        assert crc32c(b"") == 0
        assert crc32c(b"123456789") == 0xE3069283
        assert crc32c(b"\x00" * 32) == 0x8A9136AA
        assert crc32c(b"\xff" * 32) == 0x62A8AB43

    def test_chaining(self):
        data = os.urandom(999)
        for cut in (0, 1, 511, 998, 999):
            assert crc32c(data[cut:], crc32c(data[:cut])) == crc32c(data)

    def test_buffer_types(self):
        data = os.urandom(64)
        ref = crc32c(data)
        assert crc32c(bytearray(data)) == ref
        assert crc32c(memoryview(data)) == ref
        assert crc32c(np.frombuffer(data, dtype=np.uint8)) == ref

    def test_pure_python_matches_native(self):
        from byteps_tpu import native as bnative
        from byteps_tpu.comm import transport

        lib = bnative.get_lib()
        if lib is None or not hasattr(lib, "bps_wire_crc32c"):
            pytest.skip("native lib (with crc shim) not built")
        saved = transport._crc_native
        try:
            for n in (0, 1, 7, 8, 9, 63, 64, 1024, 4097):
                data = os.urandom(n)
                transport._crc_native = False  # pure-Python table
                pp = transport.crc32c(data, 5)
                transport._crc_native = None  # re-resolve the fast path
                assert transport.crc32c(data, 5) == pp
        finally:
            transport._crc_native = saved


# --------------------------------------------------------------------------
# codec semantics


class _PipeSock:
    """recv_into over a byte string (EOF after)."""

    def __init__(self, data: bytes) -> None:
        self._b = memoryview(bytes(data))
        self._off = 0

    def recv_into(self, view, nbytes: int = 0) -> int:
        n = nbytes or len(view)
        take = min(n, len(self._b) - self._off)
        if take <= 0:
            return 0
        view[:take] = self._b[self._off : self._off + take]
        self._off += take
        return take


class TestChecksumCodec:
    def test_roundtrip_with_and_without_trace(self):
        for trace in (None, (0x1234, 0x5678)):
            m = Message(Op.PUSH, key=9, payload=b"hello wire", seq=3,
                        cmd=CMD_F32, version=2, flags=1, trace=trace,
                        checksum=True)
            out = recv_message(_PipeSock(m.encode()))
            assert out.op == Op.PUSH and out.payload == b"hello wire"
            assert out.status == 0  # flag consumed, status clean
            assert out.trace == trace

    def test_flag_layout(self):
        m = Message(Op.PUSH, key=9, payload=b"xy", seq=3, checksum=True)
        frame = m.encode()
        assert frame[2] & CHECKSUM_FLAG
        assert len(frame) == HEADER_SIZE + 4 + 2
        (crc,) = struct.unpack_from("!I", frame, HEADER_SIZE)
        assert crc == frame_checksum(None, b"xy") == crc32c(b"xy")
        # with trace: header | trace | crc | payload, crc covers both
        mt = Message(Op.PUSH, key=9, payload=b"xy", seq=3,
                     trace=(7, 8), checksum=True)
        ft = mt.encode()
        assert len(ft) == HEADER_SIZE + 16 + 4 + 2
        (crct,) = struct.unpack_from("!I", ft, HEADER_SIZE + 16)
        assert crct == crc32c(b"xy", crc32c(ft[HEADER_SIZE:HEADER_SIZE + 16]))

    def test_mismatch_raises_after_full_consumption(self):
        """Drop semantics: the corrupted frame raises AFTER its bytes
        are consumed, so the NEXT frame on the stream decodes."""
        bad = _flip(Message(Op.PUSH, key=1, payload=b"abcdef", seq=1,
                            checksum=True).encode(), HEADER_SIZE + 4 + 2)
        good = Message(Op.PULL, key=2, seq=2, checksum=True).encode()
        pipe = _PipeSock(bad + good)
        with pytest.raises(ChecksumError) as ei:
            recv_message(pipe)
        assert ei.value.op == Op.PUSH
        nxt = recv_message(pipe)  # stream still framed
        assert nxt.op == Op.PULL and nxt.seq == 2

    def test_every_covered_region_detected(self):
        m = Message(Op.PUSH, key=1, payload=b"abcdef", seq=1,
                    trace=(0xAA, 0xBB), checksum=True)
        frame = m.encode()
        # trace block, crc field itself, payload — all covered
        for off in (HEADER_SIZE, HEADER_SIZE + 15, HEADER_SIZE + 16,
                    HEADER_SIZE + 19, HEADER_SIZE + 20, len(frame) - 1):
            with pytest.raises(ChecksumError):
                recv_message(_PipeSock(_flip(frame, off)))

    def test_non_verifying_consumer_stays_framed(self):
        """recv_header (the zero-copy fast path's header read) consumes
        the checksum block without verifying — oblivious consumers keep
        framing, the TRACE_FLAG contract."""
        m = Message(Op.PUSH, key=1, payload=b"xyz", seq=5, checksum=True)
        pipe = _PipeSock(m.encode())
        op, status, _f, seq, _k, _c, _v, length = recv_header(pipe)
        assert (op, status, seq, length) == (Op.PUSH, 0, 5, 3)
        buf = bytearray(3)
        assert pipe.recv_into(memoryview(buf)) == 3
        assert bytes(buf) == b"xyz"

    def test_env_knob_stamps_data_plane_only(self, monkeypatch):
        monkeypatch.setenv("BYTEPS_WIRE_CHECKSUM", "1")
        assert Message(Op.PUSH, key=1, payload=b"x").encode()[2] & CHECKSUM_FLAG
        assert Message(Op.MIGRATE_STATE, key=1).encode()[2] & CHECKSUM_FLAG
        # control frames stay byte-identical
        for op in (Op.REGISTER, Op.ADDRBOOK, Op.BARRIER, Op.PING,
                   Op.SHUTDOWN, Op.QUERY):
            assert not Message(op, key=1).encode()[2] & CHECKSUM_FLAG
        monkeypatch.delenv("BYTEPS_WIRE_CHECKSUM")
        assert not Message(Op.PUSH, key=1, payload=b"x").encode()[2] & CHECKSUM_FLAG


# --------------------------------------------------------------------------
# chaos payload-corrupt fault


class _SinkSock:
    def __init__(self) -> None:
        self.frames = []

    def sendall(self, data) -> None:
        self.frames.append(bytes(data))


class TestChaosPayloadCorrupt:
    def _sock(self, **kw):
        reset_fault_budget(kw.pop("budget", None))
        inner = _SinkSock()
        cs = ChaosSocket(inner, ChaosParams(seed=3, **kw), conn_index=0)
        return cs, inner

    def test_single_bit_flip_past_header(self):
        counters().reset()
        cs, inner = self._sock(payload_corrupt=1.0)
        frame = Message(Op.PUSH, key=1, payload=bytes(64), seq=1,
                        checksum=True).encode()
        cs.sendall(frame)
        assert len(inner.frames) == 1
        sent = inner.frames[0]
        assert len(sent) == len(frame)
        assert sent[:HEADER_SIZE] == frame[:HEADER_SIZE]  # header intact
        diff = [i for i in range(len(frame)) if sent[i] != frame[i]]
        assert len(diff) == 1 and diff[0] >= HEADER_SIZE
        xor = sent[diff[0]] ^ frame[diff[0]]
        assert xor and (xor & (xor - 1)) == 0  # exactly one bit
        assert counters().get("chaos_payload_corrupt") == 1
        # ...and the mutated frame is exactly what the verifier rejects
        with pytest.raises(ChecksumError):
            recv_message(_PipeSock(sent))

    def test_header_only_frame_passes_untouched(self):
        counters().reset()
        cs, inner = self._sock(payload_corrupt=1.0, budget=1)
        frame = Message(Op.PULL, key=1, seq=1).encode()  # 32 bytes
        cs.sendall(frame)
        assert inner.frames == [frame]
        assert counters().get("chaos_payload_corrupt") == 0
        # the budget was NOT spent on the no-op: the next payload frame
        # still gets its flip
        cs.sendall(Message(Op.PUSH, key=1, payload=b"abcd", seq=2).encode())
        assert counters().get("chaos_payload_corrupt") == 1

    def test_composes_with_op_targeting_and_budget(self):
        counters().reset()
        cs, inner = self._sock(payload_corrupt=1.0,
                               ops=frozenset({int(Op.FUSED)}), budget=1)
        push = Message(Op.PUSH, key=1, payload=b"abcd", seq=1).encode()
        fused = Message(Op.FUSED, key=1, seq=2,
                        payload=encode_fused_push(
                            [(1, CMD_F32, 1, b"wxyz")])).encode()
        cs.sendall(push)     # untargeted op: passes, no RNG roll
        cs.sendall(fused)    # targeted: flipped (budget 1 → spent)
        cs.sendall(fused)    # budget spent: passes
        assert inner.frames[0] == push
        assert inner.frames[1] != fused
        assert inner.frames[2] == fused
        assert counters().get("chaos_payload_corrupt") == 1
        reset_fault_budget()

    def test_seeded_flip_is_deterministic(self):
        outs = []
        for _ in range(2):
            cs, inner = self._sock(payload_corrupt=1.0)
            cs.sendall(Message(Op.PUSH, key=1, payload=bytes(128),
                               seq=1).encode())
            outs.append(inner.frames[0])
        assert outs[0] == outs[1]


def test_wire_fuzz_smoke():
    """Tier-1 wiring for tools/wire_fuzz.py beside the other guards: a
    seeded pass over every codec must reject every mutation."""
    spec = importlib.util.spec_from_file_location(
        "wire_fuzz", os.path.join(REPO, "tools", "wire_fuzz.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("wire_fuzz", mod)
    spec.loader.exec_module(mod)
    stats = mod.run_fuzz(seed=7, flips=240, truncations=120)
    assert stats["flips"] >= 240 and stats["truncations"] >= 120
    assert stats["baseline_silent"] > 0
    # the lossless leg ran: truncated/corrupted containers failed
    # closed and every checksummed flip was a ChecksumError (CRC is
    # verified over the compressed bytes BEFORE the container decode)
    assert stats["lossless_truncations"] > 0
    assert stats["lossless_flips_crc"] > 0
    assert stats["lossless_structural"] > 0


# --------------------------------------------------------------------------
# verify-and-heal, wire level (both engines × fused × codec)


def _init_key(socks_flags, key: int, n: int) -> None:
    payload = struct.pack("!QI", n, int(DataType.FLOAT32))
    for i, (sock, flag) in enumerate(socks_flags):
        send_message(sock, Message(Op.INIT, key=key, seq=100 + i, flags=flag,
                                   payload=payload))
    for sock, _ in socks_flags:
        assert recv_message(sock).op == Op.INIT


def _register_codec(sock, key: int, kwargs: dict, seq: int) -> None:
    body = "\n".join(f"{k}={v}" for k, v in sorted(kwargs.items())).encode()
    send_message(sock, Message(Op.REGISTER_COMPRESSOR, key=key, seq=seq,
                               payload=body))
    assert recv_message(sock).op == Op.REGISTER_COMPRESSOR


def _ck_fails(snap: dict) -> int:
    return snap.get("wire_checksum_fail", 0) + snap.get(
        "native_checksum_fail", 0
    )


def _expect_silence(sock, budget: float = 0.8) -> None:
    """The corrupted frame must be DROPPED: no reply, no teardown."""
    sock.settimeout(budget)
    try:
        recv_message(sock)
    except (socket.timeout, TimeoutError):
        sock.settimeout(15)
        return
    raise AssertionError("corrupted frame was answered")


class TestVerifyAndHeal:
    """Wire-level: a corrupted push is dropped before the sum core, the
    clean resend (the deadline/retry analogue) sums exactly once, a
    replay dedupes, and every pull is bitwise-stable."""

    @pytest.mark.parametrize(("engine", "stripes"), ENGINE_STRIPES,
                             ids=ENGINE_STRIPES_IDS)
    @pytest.mark.parametrize("fused", [False, True],
                             ids=["unfused", "fused"])
    @pytest.mark.parametrize("codec", ["raw", "onebit"])
    def test_corrupted_push_retries_and_dedupes(self, engine, stripes,
                                                fused, codec, monkeypatch):
        require_engine(engine)
        set_stripes(monkeypatch, stripes)
        monkeypatch.setenv("BYTEPS_WIRE_CHECKSUM", "1")
        counters().reset()
        KEY, N = 11, 64
        srv = make_ps_server(engine, Config(num_worker=1, num_server=1))
        if engine != "native":
            srv.start(register=False)
        try:
            sock = connect(srv.host, srv.port)
            sock.settimeout(15)
            _init_key([(sock, 1)], KEY, N)
            g = np.arange(N, dtype=np.float32) - 17.5
            if codec == "onebit":
                from byteps_tpu.compression.registry import create_compressor

                kwargs = {"byteps_compressor_type": "onebit"}
                _register_codec(sock, KEY, kwargs, seq=5)
                comp = create_compressor(dict(kwargs), N, server=False)
                payload = comp.compress(g.copy())
                cmd = CMD_COMP
            else:
                payload = g.tobytes()
                cmd = CMD_F32

            def push_frame(seq):
                if fused:
                    return Message(
                        Op.FUSED, key=KEY, seq=seq, flags=1, cmd=2,
                        payload=encode_fused_push([(KEY, cmd, 1, payload)]),
                    )
                return Message(Op.PUSH, key=KEY, seq=seq, flags=1, cmd=cmd,
                               version=1, payload=payload)

            # 1: the corrupted frame — valid CRC stamp, then one payload
            # byte flipped in transit (what the chaos van injects)
            frame = push_frame(1).encode()
            assert frame[2] & CHECKSUM_FLAG
            sock.sendall(_flip(frame, len(frame) - 3))
            _expect_silence(sock)
            snap = counters().snapshot()
            assert _ck_fails(snap) == 1, snap
            # the ledger was never touched: no dedupe recorded yet
            assert snap.get("push_dedup", 0) == 0
            assert snap.get("native_push_dedup", 0) == 0

            # 2: the clean resend (same seq — the retry) sums once
            send_message(sock, push_frame(1))
            ack = recv_message(sock)
            assert ack.seq == 1 and ack.status == 0
            if fused:
                pull1 = [p for _k, _v, p in decode_fused_reply(ack.payload)][0]
            else:
                send_message(sock, Message(Op.PULL, key=KEY, seq=2, cmd=cmd,
                                           version=1))
                pull1 = recv_message(sock).payload
            if codec == "raw":
                np.testing.assert_array_equal(
                    np.frombuffer(pull1, dtype=np.float32), g
                )

            # 3: replay the SAME round again — the exactly-once ledger
            # dedupes, the published bytes must not move
            send_message(sock, push_frame(3))
            ack2 = recv_message(sock)
            assert ack2.status == 0
            if fused:
                pull2 = [p for _k, _v, p in decode_fused_reply(ack2.payload)][0]
            else:
                send_message(sock, Message(Op.PULL, key=KEY, seq=4, cmd=cmd,
                                           version=1))
                pull2 = recv_message(sock).payload
            assert bytes(pull1) == bytes(pull2)
            snap = counters().snapshot()
            dedupe = (snap.get("push_dedup", 0)
                      + snap.get("native_push_dedup", 0))
            assert dedupe >= 1, snap
            close_socket(sock)
        finally:
            srv.stop()

    @pytest.mark.parametrize(("engine", "stripes"),
                             [("python", 0), ("native", 4)],
                             ids=["python", "native-s4"])
    def test_conn_limit_quarantines_then_fresh_dial_serves(
            self, engine, stripes, monkeypatch):
        """Escalation: BYTEPS_CHECKSUM_CONN_LIMIT mismatches on one
        connection drop it (the receiver's quarantine); a fresh dial —
        what connection revival does — serves normally."""
        require_engine(engine)
        set_stripes(monkeypatch, stripes)
        monkeypatch.setenv("BYTEPS_WIRE_CHECKSUM", "1")
        monkeypatch.setenv("BYTEPS_CHECKSUM_CONN_LIMIT", "3")
        counters().reset()
        KEY, N = 7, 16
        srv = make_ps_server(engine, Config(num_worker=1, num_server=1))
        if engine != "native":
            srv.start(register=False)
        try:
            sock = connect(srv.host, srv.port)
            sock.settimeout(15)
            _init_key([(sock, 1)], KEY, N)
            g = np.ones(N, dtype=np.float32)
            frame = Message(Op.PUSH, key=KEY, seq=1, flags=1, cmd=CMD_F32,
                            version=1, payload=g.tobytes()).encode()
            for _ in range(3):
                sock.sendall(_flip(frame, len(frame) - 1))
            # the third mismatch trips the limit: the server closes the
            # conn — the next read sees EOF, not silence
            sock.settimeout(5)
            with pytest.raises((ConnectionError, OSError)):
                while True:
                    recv_message(sock)
            snap = counters().snapshot()
            assert _ck_fails(snap) == 3, snap
            drops = (snap.get("wire_checksum_conn_drop", 0)
                     + snap.get("native_checksum_conn_drop", 0))
            assert drops == 1, snap
            close_socket(sock)
            # revival: a fresh dial works and the ledger is clean
            sock2 = connect(srv.host, srv.port)
            sock2.settimeout(15)
            send_message(sock2, Message(Op.PUSH, key=KEY, seq=9, flags=1,
                                        cmd=CMD_F32, version=1,
                                        payload=g.tobytes()))
            assert recv_message(sock2).status == 0
            send_message(sock2, Message(Op.PULL, key=KEY, seq=10, cmd=CMD_F32,
                                        version=1))
            np.testing.assert_array_equal(
                np.frombuffer(recv_message(sock2).payload, dtype=np.float32),
                g,
            )
            close_socket(sock2)
        finally:
            srv.stop()


# --------------------------------------------------------------------------
# client-side verification (recv lanes, both client implementations)


def _stub_client_and_conn(sock):
    """A minimal PSClient + _ServerConn pair around one end of a
    socketpair — just enough surface for _recv_loop."""
    from byteps_tpu.comm.ps_client import PSClient, _ServerConn

    client = PSClient.__new__(PSClient)
    client._stop = threading.Event()
    client.zero_copy_pulls = 0
    sc = _ServerConn.__new__(_ServerConn)
    sc.stripes = sc.pull_stripes = [(sock, threading.Lock())]
    sc.cb_lock = threading.Lock()
    sc.callbacks = {}
    sc.sinks = {}
    sc.next_seq = 0
    sc.recv_thread = None
    sc.dead = False
    sc._live_lanes = 1
    sc.server_label = "0"
    sc._ck_fails = 0
    return client, sc


class TestClientRecvVerify:
    def _reply(self, seq, payload, op=Op.FUSED):
        return Message(op, key=1, payload=payload, seq=seq,
                       checksum=True).encode()

    def test_corrupted_reply_dropped_before_demux_then_refetch_lands(self):
        """A corrupted fused multi-key reply must NOT fire the seq
        callback (no double-publish path exists: the demux never saw
        it); the re-fetched clean reply lands normally."""
        counters().reset()
        a, b = socket.socketpair()
        client, sc = _stub_client_and_conn(a)
        got = []
        done = threading.Event()
        seq = sc.alloc_seq(lambda m: (got.append(m), done.set()))
        t = threading.Thread(target=client._recv_loop, args=(sc, a),
                             daemon=True)
        t.start()
        reply = encode_fused_reply([(1, 1, b"abcd"), (2, 1, b"wxyz")])
        frame = self._reply(seq, reply)
        b.sendall(_flip(frame, len(frame) - 2))  # corrupted in transit
        time.sleep(0.3)
        assert not done.is_set()                 # demux never fired
        assert sc.pop_cb(seq) is not None        # cb still registered...
        sc.callbacks[seq] = lambda m: (got.append(m), done.set())  # restore
        snap = counters().snapshot_labeled().get("wire_checksum_fail", {})
        assert any(dict(k).get("side") == "client" and
                   dict(k).get("op") == "FUSED" for k in snap), snap
        b.sendall(frame)                         # the re-fetch
        assert done.wait(5)
        assert got[0] is not None and got[0].payload == reply
        client._stop.set()
        close_socket(b)
        close_socket(a)
        t.join(timeout=5)

    def test_corrupted_resync_state_reply_dropped(self):
        from byteps_tpu.comm.transport import encode_resync_state

        counters().reset()
        a, b = socket.socketpair()
        client, sc = _stub_client_and_conn(a)
        got = []
        seq = sc.alloc_seq(got.append)
        t = threading.Thread(target=client._recv_loop, args=(sc, a),
                             daemon=True)
        t.start()
        state = encode_resync_state(
            {5: {"store_version": 4, "seen": 3, "recv_count": 1,
                 "init": True}}
        )
        frame = self._reply(seq, state, op=Op.RESYNC_STATE)
        b.sendall(_flip(frame, HEADER_SIZE + 4 + 10))
        time.sleep(0.3)
        assert got == []  # dropped before the demux
        snap = counters().snapshot_labeled().get("wire_checksum_fail", {})
        assert any(dict(k).get("op") == "RESYNC_STATE" for k in snap), snap
        client._stop.set()
        close_socket(b)
        close_socket(a)
        t.join(timeout=5)

    def test_conn_limit_poisons_connection_for_revival(self, monkeypatch):
        """BYTEPS_CHECKSUM_CONN_LIMIT mismatches on the client lane end
        the recv loop — the connection dies the same way a transport
        failure kills it, so the existing revival machinery owns it."""
        monkeypatch.setenv("BYTEPS_CHECKSUM_CONN_LIMIT", "2")
        counters().reset()
        a, b = socket.socketpair()
        client, sc = _stub_client_and_conn(a)
        got = []
        seq = sc.alloc_seq(got.append)
        t = threading.Thread(target=client._recv_loop, args=(sc, a),
                             daemon=True)
        t.start()
        frame = self._reply(seq, b"payload-bytes", op=Op.PULL)
        b.sendall(_flip(frame, len(frame) - 1))
        b.sendall(_flip(frame, len(frame) - 2))
        t.join(timeout=5)
        assert not t.is_alive()  # the lane exited at the limit
        # the loop's finally drained the pending cb with None (dead conn)
        assert got == [None]
        assert sc.dead
        assert counters().get("wire_checksum_conn_drop") == 1
        close_socket(b)

    def test_zero_copy_sink_verified(self):
        """A corrupted zero-copy pull (payload received INTO the
        caller's buffer) is still verified and dropped; the retried
        response overwrites the garbage before the caller wakes."""
        counters().reset()
        a, b = socket.socketpair()
        client, sc = _stub_client_and_conn(a)
        sink = np.zeros(8, dtype=np.float32)
        got = []
        done = threading.Event()
        seq = sc.alloc_seq(lambda m: (got.append(m), done.set()),
                           sink=memoryview(sink).cast("B"))
        t = threading.Thread(target=client._recv_loop, args=(sc, a),
                             daemon=True)
        t.start()
        want = np.arange(8, dtype=np.float32)
        frame = self._reply(seq, want.tobytes(), op=Op.PULL)
        b.sendall(_flip(frame, len(frame) - 4))
        time.sleep(0.3)
        assert not done.is_set()
        assert client.zero_copy_pulls == 0  # rejected frames don't count
        b.sendall(frame)
        assert done.wait(5)
        np.testing.assert_array_equal(sink, want)
        assert client.zero_copy_pulls == 1
        client._stop.set()
        close_socket(b)
        close_socket(a)
        t.join(timeout=5)


class TestNativeClientVerify:
    """The C++ recv lanes verify replies before the seq demux: a
    corrupted reply is dropped in C++ (pending entry survives), Python
    is notified through the op=-3 record, and the clean retry lands."""

    def _fake_server(self):
        lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lsock.bind(("127.0.0.1", 0))
        lsock.listen(4)
        return lsock, lsock.getsockname()[1]

    def _native_conn(self, port):
        from byteps_tpu.comm.ps_client import _NativeServerConn
        from byteps_tpu.native import get_lib

        lib = get_lib()
        if lib is None or not hasattr(lib, "bpsc_drain"):
            pytest.skip("native client library unavailable")
        return _NativeServerConn("127.0.0.1", port, streams=1)

    def test_corrupted_reply_dropped_then_clean_lands(self):
        counters().reset()
        lsock, port = self._fake_server()
        conn = None
        try:
            conn = self._native_conn(port)
            peer, _ = lsock.accept()
            got = []
            done = threading.Event()
            seq = conn.alloc_seq(lambda m: (got.append(m), done.set()))
            frame = Message(Op.PULL, key=3, payload=b"pull-bytes",
                            seq=seq, checksum=True).encode()
            peer.sendall(_flip(frame, len(frame) - 3))
            time.sleep(0.4)
            assert not done.is_set()
            snap = counters().snapshot_labeled().get("wire_checksum_fail", {})
            assert any(dict(k).get("side") == "client" and
                       dict(k).get("op") == "PULL" for k in snap), snap
            peer.sendall(frame)
            assert done.wait(5)
            assert got[0] is not None and got[0].payload == b"pull-bytes"
            close_socket(peer)
        finally:
            if conn is not None:
                conn.close_all()
            close_socket(lsock)

    def test_conn_limit_poisons_native_connection(self, monkeypatch):
        monkeypatch.setenv("BYTEPS_CHECKSUM_CONN_LIMIT", "2")
        counters().reset()
        lsock, port = self._fake_server()
        conn = None
        try:
            conn = self._native_conn(port)  # limit read at create
            peer, _ = lsock.accept()
            got = []
            done = threading.Event()
            seq = conn.alloc_seq(lambda m: (got.append(m), done.set()))
            frame = Message(Op.PULL, key=3, payload=b"pull-bytes",
                            seq=seq, checksum=True).encode()
            peer.sendall(_flip(frame, len(frame) - 3))
            peer.sendall(_flip(frame, len(frame) - 5))
            # the second mismatch trips the limit: the lane dies and the
            # drain fails the pending request (cb(None)) — exactly the
            # dead-conn shape the revival machinery heals
            assert done.wait(5)
            assert got == [None]
            assert conn.dead
            # the Python mirror recorded the quarantine exactly once
            assert counters().get("wire_checksum_conn_drop") == 1
            close_socket(peer)
        finally:
            if conn is not None:
                conn.close_all()
            close_socket(lsock)


# --------------------------------------------------------------------------
# end-to-end heals


class TestEndToEndHeal:
    def _cluster_env(self, monkeypatch, sched_port):
        for k, v in {
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched_port),
            "DMLC_NUM_WORKER": "1",
            "DMLC_NUM_SERVER": "1",
            "BYTEPS_FORCE_DISTRIBUTED": "1",
            "BYTEPS_HEARTBEAT_INTERVAL": "0.2",
            "BYTEPS_RPC_DEADLINE_S": "0.3",
            "BYTEPS_RPC_RETRIES": "3",
            "BYTEPS_RPC_BACKOFF_S": "0.05",
            "BYTEPS_INIT_DEADLINE_S": "1.0",
            "BYTEPS_CONNECT_RETRY_S": "0.2",
            "BYTEPS_WIRE_CHECKSUM": "1",
        }.items():
            monkeypatch.setenv(k, v)

    def test_corrupted_fused_frame_heals_bitwise(self, monkeypatch):
        """One seeded payload flip on the first FUSED frame: the server
        drops it before the sum core, the deadline retry re-sends, the
        pull is bitwise — and nothing double-publishes (the corrupted
        frame never reached the ledger)."""
        from byteps_tpu.comm.chaos import reset_conn_indices
        from byteps_tpu.comm.rendezvous import Scheduler
        from byteps_tpu.server.server import PSServer

        monkeypatch.setenv("BYTEPS_VAN", "chaos:tcp")
        monkeypatch.setenv("BYTEPS_CHAOS_SEED", "5")
        monkeypatch.setenv("BYTEPS_CHAOS_PAYLOAD_CORRUPT", "1.0")
        monkeypatch.setenv("BYTEPS_CHAOS_OPS", "FUSED")
        monkeypatch.setenv("BYTEPS_CHAOS_FAULT_BUDGET", "1")
        monkeypatch.setenv("BYTEPS_FUSION_THRESHOLD", "65536")
        counters().reset()
        reset_fault_budget()
        reset_conn_indices()
        sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
        sched.start()
        self._cluster_env(monkeypatch, sched.port)
        srv = PSServer(Config.from_env())
        threading.Thread(target=srv.start, daemon=True).start()

        import byteps_tpu as bps

        try:
            bps.init()
            rng = np.random.default_rng(1)
            for _step in range(3):
                x = rng.standard_normal(257).astype(np.float32)
                out = bps.push_pull(x, name="integrity.fused", average=False)
                np.testing.assert_array_equal(np.asarray(out), x)
            snap = bps.get_robustness_counters()
            assert snap.get("chaos_payload_corrupt", 0) == 1, snap
            assert snap.get("wire_checksum_fail", 0) == 1, snap
            assert snap.get("fused_frames", 0) >= 3, snap
            assert snap.get("rpc_giveup", 0) == 0, snap
            assert snap.get("degraded_jobs", 0) == 0, snap
        finally:
            bps.shutdown()
            srv.stop()
            sched.stop()
            reset_fault_budget()

    def test_corrupted_resync_state_fails_heal_cleanly(self, monkeypatch):
        """Every PUSH and every RESYNC_STATE corrupted forever: the
        give-up's in-place heal cannot complete (its state replies never
        verify), so it fails CLEANLY — resync_giveup, the key marked
        for re-init, a DegradedError to the caller — instead of
        training on a corrupt ledger snapshot or hanging."""
        from byteps_tpu.common.types import DegradedError
        from byteps_tpu.comm.chaos import reset_conn_indices
        from byteps_tpu.comm.rendezvous import Scheduler
        from byteps_tpu.server.server import PSServer

        monkeypatch.setenv("BYTEPS_VAN", "chaos:tcp")
        monkeypatch.setenv("BYTEPS_CHAOS_SEED", "5")
        monkeypatch.setenv("BYTEPS_CHAOS_PAYLOAD_CORRUPT", "1.0")
        monkeypatch.setenv("BYTEPS_CHAOS_OPS", "PUSH,RESYNC_STATE")
        monkeypatch.setenv("BYTEPS_CHAOS_FAULT_BUDGET", "-1")
        monkeypatch.setenv("BYTEPS_CHECKSUM_CONN_LIMIT", "0")
        monkeypatch.setenv("BYTEPS_RESYNC_DEADLINE_S", "1.0")
        monkeypatch.setenv("BYTEPS_DEGRADED_STEP_RETRIES", "0")
        counters().reset()
        reset_fault_budget()
        reset_conn_indices()
        sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
        sched.start()
        self._cluster_env(monkeypatch, sched.port)
        srv = PSServer(Config.from_env())
        threading.Thread(target=srv.start, daemon=True).start()

        import byteps_tpu as bps

        try:
            bps.init()
            x = np.full(64, 2.5, dtype=np.float32)
            with pytest.raises(DegradedError):
                bps.push_pull(x, name="integrity.resync", average=False)
            snap = bps.get_robustness_counters()
            assert snap.get("resync_attempt", 0) >= 1, snap
            assert snap.get("resync_giveup", 0) >= 1, snap
            assert snap.get("wire_checksum_fail", 0) >= 1, snap
            labeled = counters().snapshot_labeled().get(
                "wire_checksum_fail", {}
            )
            assert any(dict(k).get("op") == "RESYNC_STATE"
                       for k in labeled), labeled
            # clean failure TO the re-init path: the key is marked
            from byteps_tpu.core.state import get_state

            assert "integrity.resync" in get_state().engine._reinit_names
        finally:
            bps.shutdown()
            srv.stop()
            sched.stop()
            reset_fault_budget()


# --------------------------------------------------------------------------
# observability bindings


class TestObservability:
    def test_corruption_storm_rule(self):
        from byteps_tpu.core.flightrec import _rule_corruption_storm

        fire = _rule_corruption_storm(None, {"events": {
            "wire_checksum_fail": 5, "chaos_payload_corrupt": 5,
        }})
        assert fire == {"checksum_fails": 5, "conn_drops": 0, "injected": 5}
        # a single mismatch is the retry machinery's job, not a storm
        assert _rule_corruption_storm(None, {"events": {
            "wire_checksum_fail": 1,
        }}) is None
        # ...but any conn-limit quarantine is
        fire = _rule_corruption_storm(None, {"events": {
            "wire_checksum_fail": 1, "wire_checksum_conn_drop": 1,
        }})
        assert fire is not None and fire["conn_drops"] == 1
        # the C++ engine's rejections (provider-merged native_* deltas)
        # arm the rule the same way
        fire = _rule_corruption_storm(None, {"events": {
            "native_checksum_fail": 4,
        }})
        assert fire is not None and fire["checksum_fails"] == 4
        assert _rule_corruption_storm(None, {"events": {
            "native_checksum_conn_drop": 1,
        }}) is not None
        assert _rule_corruption_storm(None, {"events": {}}) is None

    def test_wire_checksum_fail_rides_flight_events(self):
        from byteps_tpu.core.flightrec import EVENT_COUNTERS

        for name in ("wire_checksum_fail", "wire_checksum_conn_drop",
                     "chaos_payload_corrupt"):
            assert name in EVENT_COUNTERS

    def test_doctor_wire_corruption_rule(self):
        spec = importlib.util.spec_from_file_location(
            "bps_doctor", os.path.join(REPO, "tools", "bps_doctor.py")
        )
        doctor = importlib.util.module_from_spec(spec)
        sys.modules.setdefault("bps_doctor", doctor)
        spec.loader.exec_module(doctor)
        doctor = sys.modules["bps_doctor"]
        v = doctor.View()
        v.counters = {"wire_checksum_fail": 12.0,
                      "wire_checksum_conn_drop": 1.0}
        v.labeled = {"wire_checksum_fail": [
            ({"side": "client", "op": "PULL", "server": "1"}, 9.0),
            ({"side": "server", "op": "PUSH"}, 3.0),
        ]}
        findings = doctor.diagnose(v)
        rules = [f.rule for f in findings]
        assert "wire_corruption" in rules, rules
        f = findings[rules.index("wire_corruption")]
        assert any("server 1" in ev for ev in f.evidence), f.evidence
        # silent when nothing failed
        assert doctor._r_wire_corruption(doctor.View()) is None
