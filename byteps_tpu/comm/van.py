"""Pluggable transport "vans" for the PS data plane.

ps-lite ships three vans — ZeroMQ-TCP, RDMA verbs, UCX (SURVEY §2.4,
setup.py:312-330) — selected by env (``DMLC_ENABLE_RDMA``).  The TPU
build keeps the same seam: a Van owns listening/connecting for one
transport scheme while the 32-byte framing (transport.py) stays shared,
so an RDMA-class backend can slot in without touching the KV logic.

Vans:

- ``tcp``  — framed TCP (the ZMQ-class default).
- ``uds``  — Unix-domain stream sockets for same-host worker↔server
  traffic (honors ``BYTEPS_SOCKET_PATH`` like the reference's local
  plane, communicator.cc:99-107).
- ``shm``  — headers ride a UDS control socket, payload bytes move
  through mmap'd shared-memory rings (shm_ring.py): the bulk path makes
  no syscalls and touches no kernel socket buffers, the RDMA-class
  zero-copy seam (reference: ps-lite ZPush/ZPull zero-copy SArrays +
  BytePS_ShM staging, core_loops.cc:538-618, shared_memory.cc:28-50).
  Python server only (the native C++ engine speaks fd streams).

Selection: ``BYTEPS_VAN=tcp|uds|shm`` (server side — the address it
publishes in the scheduler book encodes the scheme, so clients need no
config).  Addresses stay ``(host, port)`` shaped for the control plane:
a UDS address is ``("unix://<path>", 0)``, an shm address is
``("shm+unix://<path>", 0)``.

``BYTEPS_VAN=chaos:<inner>`` wraps any van in the fault-injection layer
(comm/chaos.py): the published address gains a ``chaos+`` prefix so
dialing clients wrap their side too.  See docs/robustness.md.

``connect()`` retries refused/missing-endpoint dials with backoff for up
to ``BYTEPS_CONNECT_RETRY_S`` (default 2s, bounded by the connect
timeout): during cluster bring-up the worker/server/scheduler start
order no longer matters.  A down endpoint still fails fast enough for
the elastic rebuild path to notice.
"""

from __future__ import annotations

import os
import socket
import struct
import tempfile
import threading
import uuid
from typing import Tuple

UNIX_PREFIX = "unix://"
SHM_PREFIX = "shm+unix://"
CHAOS_PREFIX = "chaos+"

#: bring-up races surface as these: the peer's port/socket-file does not
#: exist yet (ECONNREFUSED / ENOENT) — transient by nature, so connect()
#: retries them with backoff inside a bounded budget
_RETRYABLE_DIAL_ERRORS = (ConnectionRefusedError, FileNotFoundError)


def _dial_retry_budget(timeout: float) -> float:
    """Seconds to keep re-dialing a refused endpoint.  Deliberately small
    by default: bring-up races close in well under 2s, while the elastic
    rebuild/revive paths need a DOWN server to fail fast."""
    raw = os.environ.get("BYTEPS_CONNECT_RETRY_S", "2")
    try:
        budget = float(raw or 0)
    except ValueError:
        budget = 2.0
    return max(0.0, min(budget, timeout))


def _dial_with_retry(dial, timeout: float):
    from byteps_tpu.comm.retry import call_with_retries

    return call_with_retries(
        dial, _dial_retry_budget(timeout), _RETRYABLE_DIAL_ERRORS
    )


class Van:
    """One transport scheme.  Framing/recv/send stay in transport.py."""

    name = "base"

    def listen(self, host: str) -> Tuple[socket.socket, str, int]:
        """Bind + listen; returns (socket, published_host, published_port)."""
        raise NotImplementedError

    def connect(self, host: str, port: int, timeout: float = 30.0) -> socket.socket:
        raise NotImplementedError

    def connect_added_lane(self, host: str, port: int,
                           timeout: float = 30.0) -> socket.socket:
        """Dial a lane a split server link added to its first sockets — a
        pull lane, the second sender's push lane (ps_client._ServerConn):
        a connection like any other to every van but the chaos van, which
        indexes its fault schedule apart from the first push lanes'."""
        return self.connect(host, port, timeout=timeout)


class TcpVan(Van):
    name = "tcp"

    def listen(self, host: str) -> Tuple[socket.socket, str, int]:
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host, 0))
        srv.listen(128)
        return srv, host, srv.getsockname()[1]

    def connect(self, host: str, port: int, timeout: float = 30.0) -> socket.socket:
        def dial():
            return socket.create_connection((host, port), timeout=timeout)

        sock = _dial_with_retry(dial, timeout)
        sock.settimeout(None)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return sock


class UdsVan(Van):
    name = "uds"

    def listen(self, host: str) -> Tuple[socket.socket, str, int]:
        base = os.environ.get("BYTEPS_SOCKET_PATH", tempfile.gettempdir())
        path = os.path.join(base, f"byteps_uds_{os.getpid()}_{uuid.uuid4().hex[:8]}.sock")
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        srv.bind(path)
        srv.listen(128)
        return srv, UNIX_PREFIX + path, 0

    def connect(self, host: str, port: int, timeout: float = 30.0) -> socket.socket:
        path = host[len(UNIX_PREFIX):]

        def dial():
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.settimeout(timeout)
            try:
                sock.connect(path)
            except BaseException:
                sock.close()
                raise
            return sock

        sock = _dial_with_retry(dial, timeout)
        sock.settimeout(None)
        return sock


class ShmConnection:
    """Socket-shaped duplex connection whose payload path is a pair of
    shared-memory rings.  The UDS socket carries only the handshake and
    afterwards serves as the liveness backstop: a SIGKILLed peer never
    sets the ring's closed flag, but the kernel closes its fds, so an
    EOF on the control socket unblocks ring waits."""

    family = socket.AF_UNIX  # accept loops branch on family for TCP opts

    def __init__(self, sock: socket.socket, tx, rx, server_side: bool = False) -> None:
        self._sock = sock
        self._tx = tx
        self._rx = rx
        self._hs_lock = threading.Lock()
        if server_side:
            # handshake completes lazily on first use, in the server's
            # per-connection thread — doing it inside accept() would let
            # one stalled client head-of-line-block every other worker
            assert tx is None and rx is None
        else:
            sock.setblocking(False)
            tx.kick = rx.kick = self._kick

    def _ensure_handshake(self) -> None:
        if self._rx is not None:
            return
        with self._hs_lock:
            if self._rx is not None:
                return
            from byteps_tpu.comm.shm_ring import ShmRing
            from byteps_tpu.comm.transport import _recv_exact

            try:
                self._sock.settimeout(10.0)
                names = []
                for _ in range(2):
                    (ln,) = struct.unpack("!H", _recv_exact(self._sock, 2))
                    names.append(_recv_exact(self._sock, ln).decode())
                self._sock.settimeout(None)
                # client's c2s ring is our rx; attach then unlink
                # immediately — the mappings stay alive and the files
                # cannot leak whatever happens to either process
                rx = ShmRing(names[0], "consumer")
                tx = ShmRing(names[1], "producer")
            except Exception as e:
                raise ConnectionError(f"shm handshake failed: {e!r}") from e
            for name in names:
                try:
                    os.unlink(name)
                except OSError:
                    pass
            self._sock.setblocking(False)
            tx.kick = rx.kick = self._kick
            self._tx, self._rx = tx, rx

    def _kick(self) -> None:
        """Doorbell: one byte on the control socket wakes the peer's
        parked select() instantly (shm_ring.py park protocol).  A full
        socket buffer or dead peer is fine — the first means wakeups are
        already pending, the second is detected by the waiter."""
        try:
            self._sock.send(b"\x01")
        except (BlockingIOError, InterruptedError, OSError):
            pass

    def _peer_gone(self) -> bool:
        """Drain every pending doorbell byte; True on EOF (peer exited)."""
        try:
            while True:
                b = self._sock.recv(4096)
                if b == b"":
                    return True  # EOF: peer process exited
                if len(b) < 4096:
                    return False
        except (BlockingIOError, InterruptedError):
            return False
        except OSError:
            return True

    def _wait(self, timeout: float) -> bool:
        """Ring park wait: sleep in select() on the control socket —
        woken instantly by the peer's doorbell byte or by a dead peer
        (kernel-closed fd → readable EOF).  Returns False when the peer
        is gone."""
        import select

        try:
            readable, _, _ = select.select([self._sock], [], [], timeout)
        except (OSError, ValueError):
            return False
        if readable:
            return not self._peer_gone()
        return True

    # socket surface used by transport.py ---------------------------------
    def sendall(self, data) -> None:
        self._ensure_handshake()
        self._tx.write(data, wait=self._wait)

    def recv_into(self, buf, nbytes: int = 0) -> int:
        self._ensure_handshake()
        return self._rx.recv_into(buf, nbytes, wait=self._wait)

    def recv(self, n: int) -> bytes:
        buf = bytearray(n)
        got = self.recv_into(buf, n)
        return bytes(buf[:got])

    def shutdown(self, how: int = socket.SHUT_RDWR) -> None:
        if self._tx is not None:
            self._tx.mark_closed()
        if self._rx is not None:
            self._rx.mark_closed()
        try:
            self._sock.shutdown(how)
        except OSError:
            pass

    def close(self) -> None:
        if self._tx is not None:
            self._tx.close()
        if self._rx is not None:
            self._rx.close()
        try:
            self._sock.close()
        except OSError:
            pass


class ShmListener:
    """Accept wrapper: completes the ring handshake before handing the
    connection to the server's per-connection thread."""

    def __init__(self, sock: socket.socket, path: str) -> None:
        self._sock = sock
        self._path = path

    def accept(self):
        # return immediately: the ring handshake completes lazily in the
        # per-connection thread (ShmConnection._ensure_handshake), so a
        # stalled or malicious client can neither head-of-line-block
        # other workers' connects nor kill the accept loop — its failure
        # surfaces as ConnectionError on first use, which server loops
        # already treat as a dropped connection
        conn, addr = self._sock.accept()
        return ShmConnection(conn, tx=None, rx=None, server_side=True), addr

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
        try:
            os.unlink(self._path)
        except OSError:
            pass


def _check_shm_arch() -> None:
    """The ring's data-then-counter publication order relies on x86-64's
    TSO memory model (shm_ring.py docstring); on weaker models (aarch64)
    a consumer could observe the head before the payload bytes.  Refuse
    loudly rather than corrupt gradients silently."""
    import platform

    if platform.machine() not in ("x86_64", "AMD64", "i686"):
        raise RuntimeError(
            "BYTEPS_VAN=shm requires an x86-64 host (TSO store ordering); "
            f"got {platform.machine()!r} — use the uds van instead"
        )


class ShmVan(Van):
    name = "shm"

    def listen(self, host: str) -> Tuple[object, str, int]:
        _check_shm_arch()
        base = os.environ.get("BYTEPS_SOCKET_PATH", tempfile.gettempdir())
        path = os.path.join(base, f"byteps_shm_{os.getpid()}_{uuid.uuid4().hex[:8]}.sock")
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        srv.bind(path)
        srv.listen(128)
        return ShmListener(srv, path), SHM_PREFIX + path, 0

    def connect(self, host: str, port: int, timeout: float = 30.0):
        from byteps_tpu.comm.shm_ring import ShmRing, create_ring_file

        _check_shm_arch()
        path = host[len(SHM_PREFIX):]

        def dial():
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.settimeout(timeout)
            try:
                s.connect(path)
            except BaseException:
                s.close()
                raise
            return s

        sock = _dial_with_retry(dial, timeout)
        # default 512KB (was 16MB): payloads larger than the ring stream
        # through it with cheap park/kick handoffs, so capacity buys
        # nothing — while SMALL rings keep the working set in cache/TLB:
        # 8 workers × 8 servers cycle 64 conns × 2 × 16MB = 2GB of
        # wrap-around pages at the old size, 64MB at this one.
        size = int(os.environ.get("BYTEPS_SHM_RING_BYTES", str(512 << 10)))
        created = []
        tx = rx = None
        try:
            c2s = create_ring_file(size, tag="c2s_")
            created.append(c2s)
            s2c = create_ring_file(size, tag="s2c_")
            created.append(s2c)
            # map BEFORE announcing the names: the server unlinks the
            # files the moment it has attached, so announcing first
            # races our own open() against that unlink.  unlink=True
            # covers a server that dies before attaching (ENOENT ok).
            tx = ShmRing(c2s, "producer", unlink=True)
            rx = ShmRing(s2c, "consumer", unlink=True)
            for name in (c2s, s2c):
                b = name.encode()
                sock.sendall(struct.pack("!H", len(b)) + b)
            sock.settimeout(None)
            return ShmConnection(sock, tx=tx, rx=rx)
        except Exception:
            # a half-built connection must not orphan its two rings in /dev/shm
            for ring in (tx, rx):
                if ring is not None:
                    ring.close()
            for path in created:
                try:
                    os.unlink(path)
                except OSError:
                    pass
            try:
                sock.close()
            except OSError:
                pass
            raise


_VANS = {v.name: v for v in (TcpVan(), UdsVan(), ShmVan())}


def get_van(name: str = "") -> Van:
    """Server-side van selection (``BYTEPS_VAN``, default tcp).

    ``chaos:<inner>`` wraps the inner van in the fault-injection layer
    (comm/chaos.py) — its listener chaos-wraps accepted connections and
    publishes a ``chaos+``-prefixed address so clients wrap theirs."""
    name = name or os.environ.get("BYTEPS_VAN", "tcp")
    if name.startswith("chaos:"):
        inner = name[len("chaos:"):]
        if not inner or inner.startswith("chaos:"):
            # an empty inner name would re-read BYTEPS_VAN and recurse
            raise ValueError(
                f"BYTEPS_VAN={name!r}: chaos needs a concrete inner van "
                f"(chaos:tcp | chaos:uds | chaos:shm)"
            )
        from byteps_tpu.comm.chaos import make_chaos_van

        return make_chaos_van(get_van(inner))
    if name not in _VANS:
        raise ValueError(
            f"unknown van {name!r}; available: {sorted(_VANS)} "
            "(or chaos:<inner>)"
        )
    return _VANS[name]


def strip_chaos(host: str) -> str:
    """The inner-scheme address of a possibly chaos-prefixed one."""
    return host[len(CHAOS_PREFIX):] if host.startswith(CHAOS_PREFIX) else host


def van_for_address(host: str) -> Van:
    """Client-side dispatch: the scheme is encoded in the address."""
    if host.startswith(CHAOS_PREFIX):
        from byteps_tpu.comm.chaos import make_chaos_van

        return make_chaos_van(van_for_address(strip_chaos(host)))
    if host.startswith(SHM_PREFIX):
        return _VANS["shm"]
    return _VANS["uds"] if host.startswith(UNIX_PREFIX) else _VANS["tcp"]
