"""ctypes bindings for the native C++ core (reducer + compression codecs).

The library is built from byteps_tpu/native/*.cc via the Makefile; import
succeeds (``HAVE_NATIVE = False``) even when the .so is missing so pure-
Python fallbacks can take over (the reference hard-requires its C++ core;
we degrade gracefully for portability but production runs should build it).

Build: ``make -C byteps_tpu/native`` (auto-attempted on first import).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

from byteps_tpu.common.logging import logger

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libbyteps_tpu.so")

#: completion-callback signature of the native worker client
#: (ps_client.cc bpsc_cb_t): (ctx, op, status, flags, seq, key, cmd,
#: version, payload_ptr, length, zero_copied).  Since r5 this fires ONLY
#: as the batched-delivery doorbell (op=-2, other args zero); records
#: are then pulled in bulk via ``bpsc_drain``.
BPSC_CALLBACK = ctypes.CFUNCTYPE(
    None,
    ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_uint32,
    ctypes.c_uint32, ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
    ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64, ctypes.c_int32,
)

#: DrainRec mirror (ps_client.cc — change both together).  64-bit fields
#: first so the C struct has no padding holes; one trailing pad int.
DRAIN_REC_DTYPE = np.dtype([
    ("key", "<u8"), ("len", "<u8"), ("off", "<u8"),
    ("op", "<i4"), ("status", "<i4"), ("flags", "<u4"), ("seq", "<u4"),
    ("cmd", "<u4"), ("version", "<u4"), ("zc", "<i4"), ("_pad", "<i4"),
])
assert DRAIN_REC_DTYPE.itemsize == 56

#: SpanRec mirror (ps_server.cc — change both together): one child-span
#: record drained from the native engine's trace ring via
#: ``bps_native_server_drain_spans`` (docs/observability.md).  ``stripe``
#: is the reducer lane that executed the stage (-1 = a serve/control
#: thread); the drain maps each stripe to its own Perfetto track.
SPAN_REC_DTYPE = np.dtype([
    ("trace", "<u8"), ("parent", "<u8"), ("key", "<u8"),
    ("ts", "<f8"), ("dur", "<f8"), ("kind", "<i4"), ("flags", "<u4"),
    ("stripe", "<i4"), ("_pad", "<u4"),
])
assert SPAN_REC_DTYPE.itemsize == 56

#: SpanKind index order (ps_server.cc) → span names matching the Python
#: server's child-span model (server.py _child_span call sites)
NATIVE_SPAN_KINDS = ("recv", "sum", "publish", "reply", "resync")

#: SpanRec.flags bits
SPAN_FLAG_DEDUPE = 1
SPAN_FLAG_FUSED = 2

_lib: Optional[ctypes.CDLL] = None


def _try_build() -> None:
    """Run make under a file lock: many worker processes import this module
    concurrently on a fresh checkout, and only one should compile.  A
    failed build is logged, not raised — the numpy paths take over — so a
    run that must have the native core checks ``HAVE_NATIVE``."""
    import fcntl

    try:
        with open(os.path.join(_DIR, ".build.lock"), "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            subprocess.run(
                ["make", "-C", _DIR, "-s"],
                check=True,
                capture_output=True,
                timeout=120,
            )
    except subprocess.CalledProcessError as e:
        logger.warning(
            "native build failed (rc %s), using the numpy paths: %s",
            e.returncode, e.stderr.decode(errors="replace")[-400:],
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native build did not run, using the numpy paths: %r", e)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.bps_sum.argtypes = [c.c_void_p, c.c_void_p, c.c_int64, c.c_int32]
    lib.bps_sum.restype = c.c_int32
    lib.bps_sum_scaled_f32.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64, c.c_float]
    lib.bps_sum_scaled_f32.restype = c.c_int32
    lib.bps_onebit_size.argtypes = [c.c_int64]
    lib.bps_onebit_size.restype = c.c_int64
    lib.bps_onebit_compress.argtypes = [c.c_void_p, c.c_int64, c.c_void_p, c.c_int32]
    lib.bps_onebit_compress.restype = c.c_int64
    lib.bps_onebit_decompress.argtypes = [c.c_void_p, c.c_int64, c.c_void_p]
    lib.bps_onebit_decompress.restype = c.c_int32
    lib.bps_topk_compress.argtypes = [c.c_void_p, c.c_int64, c.c_int64, c.c_void_p]
    lib.bps_topk_compress.restype = c.c_int64
    lib.bps_topk_decompress.argtypes = [c.c_void_p, c.c_int64, c.c_void_p, c.c_int64]
    lib.bps_topk_decompress.restype = c.c_int32
    lib.bps_topk_sum_into.argtypes = [c.c_void_p, c.c_int64, c.c_void_p, c.c_int64]
    lib.bps_topk_sum_into.restype = c.c_int32
    lib.bps_randomk_compress.argtypes = [
        c.c_void_p, c.c_int64, c.c_int64, c.c_uint64, c.c_uint64, c.c_void_p,
    ]
    lib.bps_randomk_compress.restype = c.c_int64
    lib.bps_dithering_compress.argtypes = [
        c.c_void_p, c.c_int64, c.c_int32, c.c_int32, c.c_int32,
        c.c_uint64, c.c_uint64, c.c_void_p,
    ]
    lib.bps_dithering_compress.restype = c.c_int64
    lib.bps_dithering_decompress.argtypes = [
        c.c_void_p, c.c_int64, c.c_int32, c.c_int32, c.c_void_p,
    ]
    lib.bps_dithering_decompress.restype = c.c_int32
    # native PS server data plane (ps_server.cc) — may be absent in a
    # stale .so; codecs/reducer still work without it
    if hasattr(lib, "bps_native_server_start"):
        lib.bps_native_server_start.argtypes = [c.c_int32, c.c_int32, c.c_int32]
        lib.bps_native_server_start.restype = c.c_int32
        lib.bps_native_server_set_num_workers.argtypes = [c.c_int32, c.c_int32]
        lib.bps_native_server_set_num_workers.restype = None
        lib.bps_native_server_stop.argtypes = [c.c_int32]
        lib.bps_native_server_stop.restype = None
    if hasattr(lib, "bps_native_server_start_unix"):
        lib.bps_native_server_start_unix.argtypes = [
            c.c_char_p, c.c_int32, c.c_int32, c.c_int32,
        ]
        lib.bps_native_server_start_unix.restype = c.c_int32
    # protocol-parity surface (FUSED/ledger/RESYNC port): observability
    # counters, the zombie-fence feed, and the golden wire-codec shims
    if hasattr(lib, "bps_native_server_counters"):
        lib.bps_native_server_counters.argtypes = [
            c.c_int32, c.POINTER(c.c_uint64), c.c_int32,
        ]
        lib.bps_native_server_counters.restype = c.c_int32
        lib.bps_native_server_set_live_workers.argtypes = [
            c.c_int32, c.POINTER(c.c_uint8), c.c_int32,
        ]
        lib.bps_native_server_set_live_workers.restype = None
        # elastic resharding plane (docs/robustness.md "migration flow")
        if hasattr(lib, "bps_native_server_set_ownership"):
            lib.bps_native_server_set_ownership.argtypes = [
                c.c_int32, c.c_int32, c.c_uint32, c.c_int32,
                c.POINTER(c.c_uint64), c.POINTER(c.c_int32),
            ]
            lib.bps_native_server_set_ownership.restype = None
        lib.bps_wire_golden.argtypes = [c.c_void_p, c.c_uint64]
        lib.bps_wire_golden.restype = c.c_int64
        # compressed-wire-path fixtures (may be absent in a stale .so;
        # the golden test skips that lane rather than failing it)
        if hasattr(lib, "bps_wire_golden_compressed"):
            lib.bps_wire_golden_compressed.argtypes = [c.c_void_p, c.c_uint64]
            lib.bps_wire_golden_compressed.restype = c.c_int64
        lib.bps_wire_fused_echo.argtypes = [
            c.c_void_p, c.c_uint64, c.c_void_p, c.c_uint64,
        ]
        lib.bps_wire_fused_echo.restype = c.c_int64
        lib.bps_wire_resync_echo.argtypes = [
            c.c_void_p, c.c_uint64, c.c_void_p, c.c_uint64,
        ]
        lib.bps_wire_resync_echo.restype = c.c_int64
    # observability-parity surface (span drain + histogram feeds) — may
    # be absent in a stale .so; counters/data plane still work without it
    if hasattr(lib, "bps_native_server_drain_spans"):
        lib.bps_native_server_set_trace.argtypes = [c.c_int32, c.c_int32]
        lib.bps_native_server_set_trace.restype = None
        lib.bps_native_server_drain_spans.argtypes = [
            c.c_int32, c.c_void_p, c.c_int32,
        ]
        lib.bps_native_server_drain_spans.restype = c.c_int32
        lib.bps_native_server_metrics_json.argtypes = [
            c.c_int32, c.c_void_p, c.c_uint64,
        ]
        lib.bps_native_server_metrics_json.restype = c.c_int64
        lib.bps_wire_fused_spans_echo.argtypes = [
            c.c_void_p, c.c_uint64, c.POINTER(c.c_uint64), c.c_int64,
        ]
        lib.bps_wire_fused_spans_echo.restype = c.c_int64
        lib.bps_wire_client_frame.argtypes = [
            c.c_int32, c.c_uint32, c.c_uint64, c.c_uint32, c.c_uint32,
            c.c_uint32, c.c_uint64, c.c_uint64, c.c_void_p, c.c_uint64,
            c.c_void_p, c.c_uint64,
        ]
        lib.bps_wire_client_frame.restype = c.c_int64
    # key-striped reducer plane (ISSUE 7): per-stripe queue-depth feed +
    # the live key→stripe mapping shim — also the layout marker for the
    # 56-byte SpanRec (older libs drained 48-byte records)
    if hasattr(lib, "bps_native_server_stripe_queue_depths"):
        lib.bps_native_server_stripe_queue_depths.argtypes = [
            c.c_int32, c.POINTER(c.c_uint64), c.c_int32,
        ]
        lib.bps_native_server_stripe_queue_depths.restype = c.c_int32
        lib.bps_wire_key_stripe.argtypes = [c.c_uint64, c.c_int32]
        lib.bps_wire_key_stripe.restype = c.c_int32
    if hasattr(lib, "bps_wire_ring_hash"):
        lib.bps_wire_ring_hash.argtypes = [c.c_uint64]
        lib.bps_wire_ring_hash.restype = c.c_uint64
    # end-to-end wire integrity (docs/robustness.md "Wire integrity"):
    # the shared CRC32C (transport.py's ctypes fast path) + the
    # checksummed golden shims — may be absent in a stale .so; the
    # pure-Python CRC takes over and the golden lanes skip
    if hasattr(lib, "bps_wire_crc32c"):
        lib.bps_wire_crc32c.argtypes = [c.c_void_p, c.c_uint64, c.c_uint32]
        lib.bps_wire_crc32c.restype = c.c_uint32
        lib.bps_wire_golden_checksum.argtypes = [c.c_void_p, c.c_uint64]
        lib.bps_wire_golden_checksum.restype = c.c_int64
        lib.bps_wire_client_frame_ck.argtypes = [
            c.c_int32, c.c_uint32, c.c_uint64, c.c_uint32, c.c_uint32,
            c.c_uint32, c.c_uint64, c.c_uint64, c.c_void_p, c.c_uint64,
            c.c_void_p, c.c_uint64,
        ]
        lib.bps_wire_client_frame_ck.restype = c.c_int64
    # lossless wire-frame codec (compression/lossless.py's ctypes fast
    # path + the C/Python parity anchor) — may be absent in a stale .so;
    # the pure-Python codec takes over
    if hasattr(lib, "bps_wire_lossless_compress"):
        lib.bps_wire_lossless_compress.argtypes = [
            c.c_char_p, c.c_uint64, c.c_void_p, c.c_uint64,
        ]
        lib.bps_wire_lossless_compress.restype = c.c_int64
        lib.bps_wire_lossless_decompress.argtypes = [
            c.c_char_p, c.c_uint64, c.c_void_p, c.c_uint64,
        ]
        lib.bps_wire_lossless_decompress.restype = c.c_int64
    # native worker client data plane (ps_client.cc) — may be absent in a
    # stale .so; the pure-Python client covers every van without it
    if hasattr(lib, "bpsc_create"):
        lib.bpsc_create.argtypes = [c.c_char_p, c.c_int32, c.c_int32, c.c_int32]
        lib.bpsc_create.restype = c.c_int64
        lib.bpsc_set_cb.argtypes = [c.c_int64, BPSC_CALLBACK, c.c_void_p]
        lib.bpsc_set_cb.restype = None
        lib.bpsc_alloc_seq.argtypes = [c.c_int64, c.c_void_p, c.c_uint64]
        lib.bpsc_alloc_seq.restype = c.c_int64
        lib.bpsc_send.argtypes = [
            c.c_int64, c.c_int32, c.c_uint32, c.c_uint64, c.c_uint32,
            c.c_uint32, c.c_uint32, c.c_void_p, c.c_uint64,
        ]
        lib.bpsc_send.restype = c.c_int32
        lib.bpsc_close.argtypes = [c.c_int64]
        lib.bpsc_close.restype = None
        if hasattr(lib, "bpsc_drain"):
            lib.bpsc_drain.argtypes = [
                c.c_int64, c.c_void_p, c.c_int64, c.c_void_p, c.c_uint64,
            ]
            lib.bpsc_drain.restype = c.c_int64
        if hasattr(lib, "bpsc_send2"):
            # trace-context-aware send + the client histogram feed
            lib.bpsc_send2.argtypes = [
                c.c_int64, c.c_int32, c.c_uint32, c.c_uint64, c.c_uint32,
                c.c_uint32, c.c_uint32, c.c_void_p, c.c_uint64, c.c_uint64,
                c.c_uint64,
            ]
            lib.bpsc_send2.restype = c.c_int32
            lib.bpsc_metrics_json.argtypes = [c.c_int64, c.c_void_p, c.c_uint64]
            lib.bpsc_metrics_json.restype = c.c_int64
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None:
        return _lib
    autobuild = os.environ.get("BYTEPS_NATIVE_AUTOBUILD", "1") != "0"
    if autobuild:
        # the .so is not committed (build artifact); make is a fast no-op
        # when sources are unchanged and rebuilds on .cc edits
        _try_build()
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:  # corrupt/partial .so → pure-Python fallbacks
        logger.warning("cannot load %s, using the numpy paths: %s", _LIB_PATH, e)
        return None
    if not hasattr(lib, "bps_wire_lossless_compress") and autobuild:
        # stale library from before the newest entry points (currently
        # the lossless wire-frame codec plane): rebuild, then
        # load via a temp COPY — dlopen dedups by path/inode, so
        # reloading the original path can hand back the old mapping
        _try_build()
        try:
            import shutil
            import tempfile

            tmp = tempfile.NamedTemporaryFile(
                suffix=".so", prefix="libbyteps_tpu_", delete=False
            )
            tmp.close()
            shutil.copy(_LIB_PATH, tmp.name)
            fresh = ctypes.CDLL(tmp.name)
            if hasattr(fresh, "bps_wire_lossless_compress"):
                lib = fresh
        except OSError:
            pass
    _lib = _bind(lib)
    return _lib


def get_lib() -> Optional[ctypes.CDLL]:
    return _load()


HAVE_NATIVE = _load() is not None

#: ``bps_native_server_counters`` index order (ps_server.cc
#: ``NativeCounter`` — change both together).  Distinct ``native_``-
#: prefixed names: in-process test clusters share one counter registry
#: between worker and server roles, and the worker side already bumps
#: ``wire_rpc``/``fused_frames``/``push_dedup`` — colliding names would
#: double-count (docs/observability.md).
NATIVE_COUNTER_NAMES = (
    "native_wire_rpc",
    "native_fused_frames",
    "native_fused_keys",
    "native_push_dedup",
    "native_init_replay_ack",
    "native_resync_query",
    "native_zombie_reject",
    "native_span_drop",
    "native_wrong_owner",
    "native_job_reject",
    "native_async_reject",
    "native_checksum_fail",
    "native_checksum_conn_drop",
    "native_server_opt_reject",
    "native_lossless_fail",
)


def native_server_counters(server_id: int) -> dict:
    """One native server instance's observability counters as
    ``{name: int}``; empty once the instance is stopped (or the lib
    predates the getter) — the ``get_robustness_counters()`` merge path
    (see :meth:`RobustnessCounters.register_provider`)."""
    lib = _load()
    if lib is None or not hasattr(lib, "bps_native_server_counters"):
        return {}
    out = (ctypes.c_uint64 * len(NATIVE_COUNTER_NAMES))()
    n = lib.bps_native_server_counters(
        server_id, out, len(NATIVE_COUNTER_NAMES)
    )
    if n <= 0:
        return {}
    return {NATIVE_COUNTER_NAMES[i]: int(out[i]) for i in range(n)}


def _metrics_json(call, ident) -> list:
    """Shared grow-and-retry wrapper for the native metrics-JSON exports
    → the ``register_hist_provider`` record list (empty when the source
    is gone / the lib predates the export / the body is malformed)."""
    import json

    cap = 1 << 16
    for _ in range(8):  # 64 KiB → 8 MiB: bounded growth, no spin
        buf = (ctypes.c_uint8 * cap)()
        n = call(ident, buf, cap)
        if n == -1 or n == 0:
            return []
        if n < 0:
            cap = max(-int(n), cap * 2)
            continue
        try:
            doc = json.loads(bytes(buf[:n]).decode())
        except (ValueError, UnicodeDecodeError):
            return []
        return list(doc.get("histograms") or [])
    return []


def native_server_histograms(server_id: int) -> list:
    """One native server instance's histograms (``native_server_sum_seconds``
    per key, ``native_request_bytes`` per key, ``native_server_publish_seconds``)
    as histogram-provider records — the feed behind
    :meth:`MetricsRegistry.register_hist_provider`
    (docs/observability.md)."""
    lib = _load()
    if lib is None or not hasattr(lib, "bps_native_server_metrics_json"):
        return []
    return _metrics_json(lib.bps_native_server_metrics_json, server_id)


def native_client_histograms(handle: int) -> list:
    """One native client handle's histograms
    (``native_rpc_round_trip_seconds``) as histogram-provider records."""
    lib = _load()
    if lib is None or not hasattr(lib, "bpsc_metrics_json"):
        return []
    return _metrics_json(lib.bpsc_metrics_json, handle)


def native_server_drain_spans(server_id: int, max_recs: int = 4096):
    """Drain the native engine's child-span ring (docs/observability.md):
    returns a structured ndarray of :data:`SPAN_REC_DTYPE` records
    (empty once the instance is stopped or the lib predates the span
    plane).  The caller — NativePSServer's drain loop — replays them
    into the process tracer.  Gated on the striping surface too: a
    pre-striping lib writes 48-byte records the 56-byte dtype would
    mis-decode."""
    lib = _load()
    if (lib is None
            or not hasattr(lib, "bps_native_server_drain_spans")
            or not hasattr(lib, "bps_native_server_stripe_queue_depths")):
        return np.zeros(0, dtype=SPAN_REC_DTYPE)
    recs = np.zeros(max_recs, dtype=SPAN_REC_DTYPE)
    n = lib.bps_native_server_drain_spans(
        server_id, recs.ctypes.data_as(ctypes.c_void_p), max_recs
    )
    if n <= 0:
        return np.zeros(0, dtype=SPAN_REC_DTYPE)
    return recs[:n]


def native_server_stripe_depths(server_id: int) -> list:
    """Current task backlog per reducer stripe of one native server
    instance (the ``native_stripe_queue_depth{stripe}`` gauge feed;
    docs/fusion.md hot-stripe note).  Empty once the instance is stopped
    or the lib predates the striping surface."""
    lib = _load()
    if lib is None or not hasattr(lib, "bps_native_server_stripe_queue_depths"):
        return []
    out = (ctypes.c_uint64 * 64)()
    n = lib.bps_native_server_stripe_queue_depths(server_id, out, 64)
    if n <= 0:
        return []
    return [int(out[i]) for i in range(n)]


def key_stripe(key: int, n_stripes: int) -> int:
    """The live key→reducer-stripe mapping (wire.h ``key_stripe``), or
    ``key % n_stripes`` as a stand-in when the lib is unavailable (only
    tests use this helper; the engine always uses the native hash)."""
    lib = _load()
    if lib is None or not hasattr(lib, "bps_wire_key_stripe"):
        return int(key) % max(1, int(n_stripes))
    return int(lib.bps_wire_key_stripe(key, n_stripes))


def native_server_set_trace(server_id: int, on: bool) -> None:
    """Mirror the wrapper's tracing decision (cfg.trace_on &&
    cfg.trace_spans) into the C++ engine's span gate."""
    lib = _load()
    if lib is not None and hasattr(lib, "bps_native_server_set_trace"):
        lib.bps_native_server_set_trace(server_id, int(bool(on)))


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


class cpu_reducer:
    """Namespace mirroring CpuReducer (cpu_reducer.h:40-205)."""

    @staticmethod
    def sum_into(dst: np.ndarray, src: np.ndarray) -> None:
        """dst[:len(src)] += src (native when available)."""
        from byteps_tpu.common.types import to_datatype

        lib = _load()
        n = src.size
        if lib is None or not dst.flags.c_contiguous or not src.flags.c_contiguous:
            np.add(dst[:n], src, out=dst[:n])
            return
        rc = lib.bps_sum(_ptr(dst), _ptr(src), n, int(to_datatype(src.dtype)))
        if rc != 0:  # unsupported dtype → numpy
            np.add(dst[:n], src, out=dst[:n])
