"""The entry points that must not hide the device (ISSUE 21).

``chip_smoke.py`` is the on-chip bring-up proof; here, on the CPU, only its
control flow is checked (``--cpu-dry-run``) and that without the flag — and
likewise ``benchmark/run.py`` without ``--rehearse`` — it refuses to produce a
result when there is no TPU.
Also: where ``bps.init()`` places the compile cache, and that the PS roles
never initialise a JAX backend (they must not take the chip from the worker).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    """Run a repo script on one CPU device (the suite's 8-device XLA_FLAGS
    would multiply the dry run's work), with ``env`` overriding."""
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=full,
        capture_output=True, text=True, timeout=300,
    )


#: the dry run in groups of legs, each a subprocess under its own limit (all
#: eight in one took 121 s alone and over its 300 s beside five other workers):
#: legs → the lines a run of them must print
DRY_RUN_GROUPS = {
    "ABCD": ("leg A", "leg B", "leg C flash", "leg C flash latent", "leg C onebit"),
    "E": ("leg E",), "F": ("leg F",), "G": ("leg G",), "H": ("leg H",),
    "I": ("leg I",),
}


class TestChipSmoke:
    @pytest.mark.parametrize("legs", sorted(DRY_RUN_GROUPS))
    def test_cpu_dry_run_passes_and_says_it_is_one(self, legs):
        out = _run(["chip_smoke.py", "--cpu-dry-run", "--legs", legs])
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
        lines = out.stdout.strip().splitlines()
        result = json.loads(lines[-1])
        assert result["ok"] is True and result["dry_run"] is True
        assert result["device"]["platform"] == "cpu"
        assert all("DRY-RUN" in ln for ln in lines[:-1])
        for leg in DRY_RUN_GROUPS[legs]:
            assert any(leg in ln for ln in lines), leg
        assert any(f"legs {legs} passed" in ln for ln in lines)

    def test_without_the_flag_no_tpu_is_a_failure(self):
        out = _run(["chip_smoke.py"])
        assert out.returncode != 0
        assert "cpu" in out.stderr and "TPU" in out.stderr
        assert '"ok"' not in out.stdout


class TestBenchmarkRefusesToHideTheDevice:
    def test_cpu_is_a_nonzero_exit_with_no_result(self):
        """``benchmark/run.py`` is the yardstick: pinned to the CPU without
        ``--rehearse`` it refuses before it builds or starts anything."""
        out = _run(["benchmark/run.py", "--workload", "vgg16_local",
                    "--seed", "1", "--seconds", "1"])
        assert out.returncode != 0
        assert "cpu" in out.stderr and "TPU" in out.stderr
        assert out.stdout.strip() == ""


class TestCompileCachePlacement:
    def test_variable_set_means_nothing_is_set_in_code(self, monkeypatch, tmp_path):
        import jax

        from byteps_tpu.core import state

        calls = []
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
        state.place_compile_cache()
        assert calls == []

    def test_unset_is_a_fixed_path_in_the_checkout(self, monkeypatch):
        from byteps_tpu.core import state

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        here = state.place_compile_cache()
        assert here == os.path.join(REPO, ".jax_cache")
        # identical from a second process: no pid, time or temp name in it
        other = _run(["-c", "from byteps_tpu.core.state import place_compile_cache"
                            "; print(place_compile_cache())"])
        assert other.stdout.strip().splitlines()[-1] == here, other.stderr[-2000:]


def test_ps_roles_import_jax_but_initialise_no_backend():
    """Scheduler and servers start before the worker and must leave the
    chip to it: importing their entry point may import jax, never more."""
    out = _run(["-c", "import byteps_tpu.server.__main__, jax._src.xla_bridge as xb"
                      "; raise SystemExit(int(xb.backends_are_initialized()))"])
    assert out.returncode == 0, out.stderr[-2000:]
