"""Device codecs wired into the engine pipeline.

For a jax-Array input with a bare codec config, COMPRESS must run on
DEVICE before the D2H (COPYD2H stages the packed payload, not the raw
fp32), and the pull side must decode on device (topk scatter / onebit
unpack / dithering dequant) with the result assembled on device.  The
wire format is unchanged, so the SAME servers aggregate payloads from
device- and host-compressing workers.

Runs on the CPU backend (conftest's 8-device virtual mesh env): the
Pallas onebit packer falls back to its jnp twin off-TPU — identical
math, same payload.
"""

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.server.server import PSServer


@pytest.fixture()
def fake_cluster(monkeypatch):
    import threading

    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
    srv = PSServer(Config.from_env())
    threading.Thread(target=srv.start, daemon=True).start()
    yield srv
    srv.stop()
    sched.stop()


def _engine():
    from byteps_tpu.core.state import get_state

    return get_state().engine


def _spy(dc, calls):
    orig_c, orig_d = dc.compress, dc.decompress

    def compress(sl):
        calls["compress"] += 1
        return orig_c(sl)

    def decompress(payload, n):
        calls["decompress"] += 1
        return orig_d(payload, n)

    dc.compress, dc.decompress = compress, decompress


class TestDeviceCodecPipeline:
    def test_topk_device_path_runs_and_is_lossless_at_full_k(self, fake_cluster):
        import jax
        import jax.numpy as jnp

        import byteps_tpu as bps

        bps.init()
        n = 300
        bps.declare_tensor(
            "dc.topk", byteps_compressor_type="topk", byteps_compressor_k=str(n)
        )
        x = jnp.asarray(
            np.random.default_rng(0).normal(size=n).astype(np.float32)
        )
        # first round instantiates the codecs; spy after declare-on-submit
        out0 = bps.push_pull(x, name="dc.topk", average=False)
        eng = _engine()
        assert eng._device_codecs, "device codec never registered"
        calls = {"compress": 0, "decompress": 0}
        for dc in eng._device_codecs.values():
            _spy(dc, calls)
        out = bps.push_pull(x + 1, name="dc.topk", average=False)
        assert isinstance(out, jax.Array)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) + 1, rtol=1e-6)
        assert calls["compress"] >= 1, "COMPRESS did not run on device"
        assert calls["decompress"] >= 1, "DECOMPRESS did not run on device"
        np.testing.assert_allclose(np.asarray(out0), np.asarray(x), rtol=1e-6)
        bps.shutdown()

    def test_onebit_device_payload_matches_host_codec(self, fake_cluster):
        """Same tensor through the device path (jax input) and the host
        path (numpy input, separate key) must produce identical results —
        the device packer is bit-compatible with the host wire format."""
        import jax.numpy as jnp

        import byteps_tpu as bps

        bps.init()
        n = 512
        for name in ("dc.ob.dev", "dc.ob.host"):
            bps.declare_tensor(
                name,
                byteps_compressor_type="onebit",
                byteps_compressor_onebit_scaling="True",
            )
        x = np.random.default_rng(1).normal(size=n).astype(np.float32)
        out_dev = np.asarray(
            bps.push_pull(jnp.asarray(x), name="dc.ob.dev", average=False)
        )
        out_host = np.asarray(bps.push_pull(x, name="dc.ob.host", average=False))
        np.testing.assert_allclose(out_dev, out_host, rtol=1e-5, atol=1e-7)
        bps.shutdown()

    def test_partitioned_device_tensor_reassembles(self, fake_cluster, monkeypatch):
        monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "256")
        import jax.numpy as jnp

        import byteps_tpu as bps

        bps.init()
        n = 1000
        # k = the 64-element partition size (256 bytes / f32): full-k per
        # partition ⇒ lossless, so reassembly errors can't hide
        bps.declare_tensor(
            "dc.part", byteps_compressor_type="topk", byteps_compressor_k="64"
        )
        x = np.random.default_rng(2).normal(size=n).astype(np.float32)
        out = bps.push_pull(jnp.asarray(x), name="dc.part", average=False)
        eng = _engine()
        from byteps_tpu.common.registry import get_registry

        parts = get_registry().get("dc.part").partitions
        assert len(parts) > 5
        assert all(p.key in eng._device_codecs for p in parts)
        np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)
        bps.shutdown()

    def test_dithering_device_levels_decode_exactly(self, fake_cluster):
        """Dithering: the server/host decode of a device payload is exact
        (levels grid shared); the stochastic draw differs from the host
        xorshift by design, so compare against the level grid, not the
        host trajectory."""
        import jax.numpy as jnp

        import byteps_tpu as bps

        bps.init()
        n, s = 256, 8
        bps.declare_tensor(
            "dc.dith", byteps_compressor_type="dithering",
            byteps_compressor_k=str(s),
        )
        x = np.random.default_rng(3).normal(size=n).astype(np.float32)
        out = np.asarray(
            bps.push_pull(jnp.asarray(x), name="dc.dith", average=False)
        )
        # every element must sit on the level grid of SOME norm: out/x sign
        # preserved and |out| <= norm with quantized magnitudes
        assert out.shape == (n,)
        nonzero = out != 0
        assert np.all(np.sign(out[nonzero]) == np.sign(x[nonzero]))
        # reconstruct the norm from the largest magnitude: levels/s grid
        norm = np.abs(out).max() * 1.0
        lv = np.abs(out) / norm * s  # should be near-integers (double pass)
        # two quantization passes (worker + pull) stay on the grid
        assert np.allclose(lv, np.round(lv), atol=1e-4)
        bps.shutdown()

    def test_ef_chain_keeps_host_path(self, fake_cluster):
        """EF/momentum chains are stateful host transforms — a jax input
        with an EF config must NOT take the device path."""
        import jax.numpy as jnp

        import byteps_tpu as bps

        bps.init()
        bps.declare_tensor(
            "dc.ef", byteps_compressor_type="topk",
            byteps_compressor_k="64", byteps_ef_type="vanilla",
        )
        x = np.random.default_rng(4).normal(size=256).astype(np.float32)
        bps.push_pull(jnp.asarray(x), name="dc.ef", average=False)
        eng = _engine()
        from byteps_tpu.common.registry import get_registry

        parts = get_registry().get("dc.ef").partitions
        assert all(p.key not in eng._device_codecs for p in parts)
        bps.shutdown()

    def test_debug_sampler_on_device_path(self, fake_cluster, monkeypatch, capsys):
        """BYTEPS_DEBUG_SAMPLE_TENSOR with a device-codec job: the
        pull-side sampler must read the DEVICE partition (job.result is
        never written on this path) — garbage host-buffer norms would
        mislead exactly the race diagnosis the knob exists for."""
        monkeypatch.setenv("BYTEPS_DEBUG_SAMPLE_TENSOR", "dbg.dev")
        monkeypatch.setenv("BYTEPS_LOG_LEVEL", "INFO")
        import jax.numpy as jnp

        import byteps_tpu as bps

        bps.init()
        n = 256
        bps.declare_tensor(
            "dbg.dev", byteps_compressor_type="topk",
            byteps_compressor_k=str(n),
        )
        x = jnp.asarray(np.arange(n, dtype=np.float32) - 100.0)
        out = bps.push_pull(x, name="dbg.dev", average=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)
        err = capsys.readouterr().err
        lines = [l for l in err.splitlines() if "sample dbg.dev" in l]
        assert any("DECOMPRESS" in l for l in lines), err[-1000:]
        # the sampled norm must be the REAL tensor norm, not uninitialized
        # host memory
        import re

        true_norm = float(np.linalg.norm(np.asarray(x, np.float64)))
        dec = [l for l in lines if "DECOMPRESS" in l][0]
        norm = float(re.search(r"norm=([0-9.eE+-]+)", dec).group(1))
        assert abs(norm - true_norm) / true_norm < 1e-3, (norm, true_norm)
        bps.shutdown()

    def test_randomk_stays_host_only(self):
        from byteps_tpu.core.device_codec import device_codec_for

        assert device_codec_for(
            {"byteps_compressor_type": "randomk", "byteps_compressor_k": "8"}, 64
        ) is None
        assert device_codec_for(
            {"byteps_compressor_type": "topk", "byteps_compressor_k": "8"}, 64
        ) is not None
