"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's single-host fake-cluster strategy (SURVEY §4,
tests/meta_test.py:26-86) translated to JAX: multi-device behavior is
exercised on one machine via ``--xla_force_host_platform_device_count``;
the PS path is exercised with an in-process scheduler + server
(BYTEPS_FORCE_DISTRIBUTED=1 equivalent, global.cc:149-152).

This file must set env before jax is imported anywhere.
"""

import os
import re

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
_pat = r"--xla_force_host_platform_device_count=\d+"
_m = re.search(_pat, _flags)
if _m is None:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
elif int(_m.group().rsplit("=", 1)[1]) < 8:
    _flags = re.sub(_pat, "--xla_force_host_platform_device_count=8", _flags)
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


# --- fail-fast guard for native-lane tests -------------------------------
#
# History: a native-server lifecycle bug once parked teardown forever
# (AF_UNIX accept() ignores listener shutdown), and tier-1's budget — 870 s
# then; since PR 60 1470 s for the driver's command, six xdist workers with
# --dist loadfile and -m 'not slow' (/root/TESTS_LAST_RUN.json `commands`;
# ROADMAP.md's serial "Tier-1 verify" line is one process) — burned idle
# from the first native test onward: every test sorting after it was simply
# never counted.  The bug is fixed, but a REGRESSION must
# fail fast, not eat the rest of the suite.  Two layers, because the hang
# classes differ:
#
# - SIGALRM (the soft layer): raises TimeoutError in the main thread for
#   Python-level waits (Event.wait, socket recv) — the test fails, the
#   run continues.  pytest-timeout without the dependency.
# - faulthandler.dump_traceback_later with exit=True (the hard layer, 2×
#   the soft budget): a hang INSIDE a ctypes call — e.g. a C-level
#   pthread_join in bps_native_server_stop, which is exactly what the
#   original bug was — never re-enters the eval loop, so the SIGALRM
#   handler can never run.  faulthandler's C watchdog thread needs no
#   interpreter: it dumps every thread's stack and _exit()s, killing the
#   run loudly with diagnostics instead of idling out that budget.

_NATIVE_GUARD_S = int(os.environ.get("BYTEPS_NATIVE_TEST_TIMEOUT_S", "60"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    import faulthandler
    import signal
    import threading

    guard = (
        _NATIVE_GUARD_S > 0
        and "native" in item.nodeid
        and threading.current_thread() is threading.main_thread()
        and hasattr(signal, "SIGALRM")
    )
    if not guard:
        yield
        return

    def _alarm(_signum, _frame):
        raise TimeoutError(
            f"native test guard: {item.nodeid} exceeded "
            f"{_NATIVE_GUARD_S}s (BYTEPS_NATIVE_TEST_TIMEOUT_S)"
        )

    prev = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(_NATIVE_GUARD_S)
    faulthandler.dump_traceback_later(2 * _NATIVE_GUARD_S, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


# --- server-engine selection helpers (native-parity suites) --------------
#
# Shared by test_fusion.py / test_resync.py so every suite gates on the
# SAME symbol: bps_native_server_counters is the newest parity entry
# point, so a stale pre-parity .so (no compiler to rebuild it) SKIPS the
# native lanes instead of failing them against an engine that cannot
# serve FUSED/RESYNC.


def have_native_parity_server() -> bool:
    from byteps_tpu.native import get_lib

    lib = get_lib()
    return lib is not None and hasattr(lib, "bps_native_server_counters")


def require_engine(engine: str) -> None:
    if engine == "native" and not have_native_parity_server():
        pytest.skip("native lib (with parity surface) not built")


#: engine × reducer-stripe matrix for the parity suites (the key-striped
#: native data plane): the native lanes run at 1 stripe — the
#: single-reducer shape, behaviorally the pre-striping engine — AND at 4
#: stripes (the multi-core default), pinning that striping changes no
#: bytes and no semantics.  ``stripes=0`` on the python lane means "not
#: applicable" (the knob only steers the C++ engine).
ENGINE_STRIPES = [("python", 0), ("native", 1), ("native", 4)]
ENGINE_STRIPES_IDS = ["python", "native-s1", "native-s4"]


def set_stripes(monkeypatch, stripes: int) -> None:
    """Pin BYTEPS_SERVER_STRIPES for a parity lane (read by the C++
    engine at start; must run before the native server is built)."""
    if stripes > 0:
        monkeypatch.setenv("BYTEPS_SERVER_STRIPES", str(stripes))


def make_ps_server(engine: str, cfg):
    """One PS server of the requested engine — the GIL-free C++ data
    plane speaks the full fused/ledger/resync protocol since the
    native-parity port, so suites parametrize over both."""
    if engine == "native":
        from byteps_tpu.server.server import NativePSServer

        return NativePSServer(cfg)
    from byteps_tpu.server.server import PSServer

    return PSServer(cfg)


@pytest.fixture(autouse=True, scope="session")
def _flight_bundles_to_tmp(tmp_path_factory):
    """Route flight-recorder diagnostic bundles into a session tmp dir:
    chaos/deadline tests legitimately produce slow steps, and their
    triggered bundle dumps must never litter the repo tree.  Tests that
    assert on bundles set BYTEPS_FLIGHT_DIR themselves (env wins over
    this default only in subprocesses they spawn; in-process they use
    recorder.bundle_dir directly)."""
    if not os.environ.get("BYTEPS_FLIGHT_DIR"):
        os.environ["BYTEPS_FLIGHT_DIR"] = str(
            tmp_path_factory.mktemp("flight_bundles")
        )
    yield


@pytest.fixture(autouse=True)
def _clean_runtime():
    """Reset global runtime state between tests."""
    yield
    from byteps_tpu.common import config as _config
    from byteps_tpu.common import registry as _registry
    from byteps_tpu.core import state as _state

    _state.shutdown_state()
    _registry.reset_registry()
    _config.clear_config()


@pytest.fixture
def mesh8():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), ("dp",))
