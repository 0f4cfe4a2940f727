"""MXNet plugin tests.

The pure policy layer (naming, priorities, compression-params
translation, EF lr plumbing) runs everywhere; the mxnet-dependent
surface tests skip when mxnet isn't installed (it is not in this image —
reference coverage: tests/test_mxnet.py:30-126)."""

import threading
import types

import numpy as np
import pytest

from byteps_tpu.mxnet._naming import (
    gradient_name,
    gradient_priority,
    parameter_name,
    trainer_compression_kwargs,
    weight_name,
)


class TestNamingPolicy:
    def test_names(self):
        assert gradient_name(3) == "gradient_3"
        assert parameter_name(0) == "parameter_0"
        assert weight_name(7) == "weight_7"

    def test_priority_is_negative_index(self):
        # earlier layers win the scheduler (mxnet/__init__.py:56)
        assert gradient_priority(0) == 0
        assert gradient_priority(12) == -12


class TestCompressionKwargs:
    def test_empty(self):
        kwargs, opt, fp16 = trainer_compression_kwargs(None, {"learning_rate": 0.1})
        assert kwargs == {} and opt == {"learning_rate": 0.1} and not fp16

    def test_fp16_only(self):
        kwargs, opt, fp16 = trainer_compression_kwargs({"fp16": True}, {})
        assert kwargs == {} and fp16

    def test_full_chain_lifts_optimizer_momentum(self):
        # momentum compression consumes the optimizer's mu — the chain
        # applies it once server-side; the local optimizer must not
        # apply it again (mxnet/__init__.py:300-321)
        kwargs, opt, _ = trainer_compression_kwargs(
            {"compressor": "onebit", "ef": "vanilla", "momentum": "nesterov",
             "scaling": True, "seed": 13},
            {"learning_rate": 0.1, "momentum": 0.9},
        )
        assert kwargs["byteps_compressor_type"] == "onebit"
        assert kwargs["byteps_ef_type"] == "vanilla"
        assert kwargs["byteps_momentum_type"] == "nesterov"
        assert kwargs["byteps_momentum_mu"] == "0.9"
        assert kwargs["byteps_compressor_onebit_scaling"] == "True"
        assert "momentum" not in opt and opt["learning_rate"] == 0.1

    def test_momentum_without_mu_raises(self):
        with pytest.raises(KeyError):
            trainer_compression_kwargs(
                {"compressor": "topk", "k": 0.1, "momentum": "nesterov"}, {}
            )

    def test_inputs_not_mutated(self):
        cp = {"compressor": "randomk", "k": 8, "momentum": "nesterov"}
        op = {"momentum": 0.9}
        trainer_compression_kwargs(cp, op)
        assert op == {"momentum": 0.9} and "momentum" in cp


class TestCompressionLrPlumbing:
    def test_engine_walks_decorator_chains(self):
        from byteps_tpu.compression.registry import create_compressor
        from byteps_tpu.core.engine import PipelineEngine

        chain = create_compressor(
            {"byteps_compressor_type": "onebit", "byteps_ef_type": "vanilla",
             "byteps_momentum_type": "nesterov", "byteps_momentum_mu": "0.9"},
            size=256,
        )
        sent = []
        fake = types.SimpleNamespace(
            _compressors={0: chain},
            _compression_lr=1.0,
            _lr_sent_to_servers=1.0,
            client=types.SimpleNamespace(set_compression_lr=sent.append),
        )
        fake._apply_lr_to_chain = PipelineEngine._apply_lr_to_chain
        fake._maybe_send_lr = lambda: PipelineEngine._maybe_send_lr(fake)
        PipelineEngine.set_compression_lr(fake, 0.25)
        # the EF stage sits under the momentum decorator
        assert chain.inner.lr == 0.25
        assert sent == [0.25]  # servers get the lr over the wire
        PipelineEngine.set_compression_lr(fake, 0.25)
        assert sent == [0.25]  # unchanged lr: no repeat wire traffic

    def test_api_noop_without_engine(self):
        import byteps_tpu as bps

        bps.init()
        bps.api.set_compression_lr(0.5)  # non-distributed: engine is None
        bps.shutdown()


@pytest.fixture
def mx_cluster(monkeypatch):
    pytest.importorskip("mxnet")  # the surface tests need real mxnet
    from byteps_tpu.common.config import Config
    from byteps_tpu.comm.rendezvous import Scheduler
    from byteps_tpu.server.server import PSServer

    sched = Scheduler(num_workers=1, num_servers=1, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    srv = PSServer(Config.from_env())
    threading.Thread(target=srv.start, daemon=True).start()
    yield
    srv.stop()
    sched.stop()


class TestMXNetSurface:
    def test_push_pull_identity(self, mx_cluster):
        import mxnet as mx

        import byteps_tpu.mxnet as bps

        bps.init()
        x = mx.nd.array(np.arange(64, dtype=np.float32))
        bps.byteps_declare_tensor("mx.t0")
        out = bps.byteps_push_pull(x, name="mx.t0", is_average=True)
        np.testing.assert_allclose(out.asnumpy(), np.arange(64, dtype=np.float32))
        bps.shutdown()

    def test_broadcast_parameters(self, mx_cluster):
        import mxnet as mx

        import byteps_tpu.mxnet as bps

        bps.init()
        params = {"w": mx.nd.ones((4, 4)), "b": mx.nd.full((4,), 3.0)}
        bps.broadcast_parameters(params, root_rank=0)
        np.testing.assert_allclose(params["w"].asnumpy(), np.ones((4, 4)))
        bps.shutdown()

    def test_trainer_step(self, mx_cluster):
        import mxnet as mx

        import byteps_tpu.mxnet as bps

        bps.init()
        net = mx.gluon.nn.Dense(2)
        net.initialize()
        x = mx.nd.ones((8, 4))
        with mx.autograd.record():
            y = net(x)
            loss = (y * y).mean()
        loss.backward()
        trainer = bps.DistributedTrainer(
            net.collect_params(), "sgd", {"learning_rate": 0.1}
        )
        trainer.step(8)
        bps.shutdown()


_MX_WORKER_SCRIPT = """
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet as mx                    # tests/mxnet_shim on PYTHONPATH
import byteps_tpu.mxnet as bps

bps.init()
r = bps.rank()

# --- DistributedTrainer: 2-worker gradient averaging (sync mode) -----
params = [
    mx.gluon.Parameter("w0", np.zeros((2, 3), np.float32)),
    mx.gluon.Parameter("w1", np.zeros(4, np.float32)),
]
trainer = bps.DistributedTrainer(params, "sgd", {"learning_rate": 0.5})
for p in params:
    p.list_grad()[0][:] = np.full(p.data().shape, float(r + 1), np.float32)
trainer.step(batch_size=1)
# grads normalized by scale*size then summed: (1+2)/2 = 1.5 -> w = -0.75
for p in params:
    assert np.allclose(p.data().asnumpy(), -0.75), (r, p.name, p.data().asnumpy())

# --- broadcast_parameters: root wins ---------------------------------
bparams = {
    "a": mx.nd.array(np.full(6, float(10 * (r + 1)), np.float32)),
}
bps.broadcast_parameters(bparams, root_rank=0)
assert np.allclose(bparams["a"].asnumpy(), 10.0), bparams["a"].asnumpy()

# --- DistributedOptimizer wrap ---------------------------------------
bps.byteps_declare_tensor("gradient_7")
opt = bps.DistributedOptimizer(mx.optimizer.SGD(learning_rate=1.0))
wt = mx.nd.array(np.zeros(4, np.float32))
gd = mx.nd.array(np.full(4, float(r + 1), np.float32))
opt.update(7, wt, gd, None)
# push_pull averages (1+2)/2 = 1.5; sgd lr 1 -> w = -1.5
assert np.allclose(wt.asnumpy(), -1.5), wt.asnumpy()

bps.shutdown()
print(f"MX_WORKER_{r}_OK")
"""


# gradient/parameter keys are INDEX-based (reference mxnet/__init__.py:52-74),
# so a differently-shaped model needs a fresh cluster — phase 2 runs the
# compressed trainer against its own scheduler/server
_MX_COMPRESSED_SCRIPT = """
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet as mx
import byteps_tpu.mxnet as bps

bps.init()
r = bps.rank()

cparams = [mx.gluon.Parameter("c0", np.zeros(128, np.float32))]
t2 = bps.DistributedTrainer(
    cparams, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
    compression_params={"compressor": "onebit", "ef": "vanilla",
                        "momentum": "nesterov", "scaling": True, "fp16": True},
)
# momentum lifted OFF the local optimizer into the compressor chain
assert not hasattr(t2._optimizer, "momentum") or t2._optimizer.momentum != 0.9
from byteps_tpu.common.registry import get_registry
kw = get_registry().get("gradient_0").kwargs
assert kw.get("byteps_compressor_type") == "onebit", kw
assert kw.get("byteps_ef_type") == "vanilla", kw
assert kw.get("byteps_momentum_type") == "nesterov", kw
assert kw.get("byteps_momentum_mu") == "0.9", kw  # lifted off the optimizer
cparams[0].list_grad()[0][:] = np.linspace(-1, 1, 128).astype(np.float32)
t2.step(batch_size=1)
w = cparams[0].data().asnumpy()
assert np.all(np.isfinite(w)) and np.any(w != 0), w[:8]

bps.shutdown()
print(f"MX_COMPRESSED_{r}_OK")
"""


class TestMxnetPluginExecution:
    """EXECUTE the mxnet plugin: 2 worker
    subprocesses with the faithful tests/mxnet_shim on PYTHONPATH run
    DistributedTrainer (sync sum), broadcast_parameters,
    DistributedOptimizer, and (fresh cluster — keys are index-based) the
    compression_params-configured trainer against live scheduler + PS."""

    @staticmethod
    def _run_two_workers(script_text, tmp_path, tag):
        import os
        import subprocess
        import sys
        import threading

        from byteps_tpu.common.config import Config
        from byteps_tpu.comm.rendezvous import Scheduler
        from byteps_tpu.server.server import PSServer

        sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
        sched.start()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        shim = os.path.join(repo, "tests", "mxnet_shim")
        env_common = {
            **os.environ,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_NUM_SERVER": "1",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": f"{shim}:{repo}",
            "BYTEPS_MIN_COMPRESS_BYTES": "0",  # compress tiny test tensors
            "BYTEPS_PARTITION_BYTES": str(1 << 31),
        }
        scfg = Config.from_env()
        scfg.num_worker = 2
        scfg.num_server = 1
        scfg.ps_root_uri = "127.0.0.1"
        scfg.ps_root_port = sched.port
        srv = PSServer(scfg)
        threading.Thread(target=srv.start, daemon=True).start()

        script = tmp_path / f"mx_{tag}.py"
        script.write_text(script_text)
        procs = [
            subprocess.Popen(
                [sys.executable, str(script)],
                env={**env_common, "BYTEPS_GLOBAL_RANK": str(i)},
                cwd=repo,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        outs = [p.communicate(timeout=180)[0] for p in procs]
        srv.stop()
        sched.stop()
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"mx {tag} worker {i} failed:\n{out}"
        return "".join(outs)

    def test_two_workers_full_surface(self, tmp_path):
        out = self._run_two_workers(_MX_WORKER_SCRIPT, tmp_path, "plain")
        assert "MX_WORKER_0_OK" in out and "MX_WORKER_1_OK" in out

    def test_two_workers_compressed_trainer(self, tmp_path):
        out = self._run_two_workers(_MX_COMPRESSED_SCRIPT, tmp_path, "comp")
        assert "MX_COMPRESSED_0_OK" in out and "MX_COMPRESSED_1_OK" in out
