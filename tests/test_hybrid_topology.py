"""Full-topology composition: shard_map mesh training
whose cross-host gradient hop rides the real PS plane, in ONE loop.

Two worker subprocesses, each with a 4-device virtual CPU mesh
({dp:2, tp:2}, Megatron-style column+row parallel MLP), train through
HybridDataParallel: grads pmean over dp on ICI, then push_pull across
workers through an in-process scheduler + server.  The trajectory must
match a pure-jax single-mesh baseline on the combined batch — the two
planes compose to exactly synchronous data parallelism.
"""

import os
import subprocess
import sys
import threading

import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.server.server import PSServer

_WORKER = '''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

rank = int(os.environ["BYTEPS_GLOBAL_RANK"])
D, H, B, STEPS, LR = 8, 16, 8, 4, 0.2

def init_params():
    r = np.random.default_rng(7)
    return {
        "w1": r.normal(0, 0.3, (D, H)).astype(np.float32),
        "w2": r.normal(0, 0.3, (H, D)).astype(np.float32),
    }

def data(worker):
    r = np.random.default_rng(100 + worker)
    x = r.normal(size=(STEPS, B, D)).astype(np.float32)
    y = r.normal(size=(STEPS, B, D)).astype(np.float32)
    return x, y

def loss_fn(p, batch):
    x, y = batch
    h = jnp.tanh(x @ p["w1"])          # column-parallel: w1 sharded (None, tp)
    o = lax.psum(h @ p["w2"], "tp")    # row-parallel: w2 sharded (tp, None)
    return jnp.mean((o - y) ** 2)

mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("dp", "tp"))
specs = {"w1": P(None, "tp"), "w2": P("tp", None)}

import byteps_tpu as bps
from byteps_tpu.parallel.hybrid import HybridDataParallel

bps.init()
hdp = HybridDataParallel(
    loss_fn, init_params(), optax.sgd(LR), mesh=mesh,
    param_specs=specs, batch_spec=(P("dp"), P("dp")),
)
x, y = data(rank)
losses = []
for s in range(STEPS):  # fixed batch: loss must strictly descend
    losses.append(hdp.step((x[0], y[0])))
final = {k: np.asarray(v) for k, v in hdp.params.items()}
bps.shutdown()

# pure-jax baseline on the COMBINED batch (both workers' data), no mesh,
# no PS: the two-level topology must reproduce it exactly
bp = {k: jnp.asarray(v) for k, v in init_params().items()}

def base_loss(p, batch):
    x, y = batch
    o = jnp.tanh(x @ p["w1"]) @ p["w2"]
    return jnp.mean((o - y) ** 2)

gfn = jax.jit(jax.value_and_grad(base_loss))
x0, y0 = data(0); x1, y1 = data(1)
base_losses = []
for s in range(STEPS):
    xb = jnp.concatenate([x0[0], x1[0]]); yb = jnp.concatenate([y0[0], y1[0]])
    l, g = gfn(bp, (xb, yb))
    base_losses.append(float(l))
    bp = {k: v - LR * g[k] for k, v in bp.items()}

for k in final:
    np.testing.assert_allclose(final[k], np.asarray(bp[k]), rtol=2e-4, atol=2e-5)
# each worker's reported loss is over ITS half of the data; the combined
# loss is their average — only the parameter trajectory is identical,
# which is the equivalence that matters (and it decreased: training ran)
assert losses[-1] < losses[0], losses
assert base_losses[-1] < base_losses[0], base_losses
print(f"WORKER_{rank}_OK losses={losses}")
'''


class TestHybridTopology:
    def test_mesh_plus_ps_equals_pure_jax(self, tmp_path):
        sched = Scheduler(num_workers=2, num_servers=1, host="127.0.0.1")
        sched.start()
        env = {
            **os.environ,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(sched.port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_NUM_SERVER": "1",
            "PYTHONPATH": "/root/repo",
        }
        scfg = Config.from_env()
        scfg.num_worker = 2
        scfg.num_server = 1
        scfg.ps_root_uri = "127.0.0.1"
        scfg.ps_root_port = sched.port
        srv = PSServer(scfg)
        threading.Thread(target=srv.start, daemon=True).start()
        script = tmp_path / "hybrid_worker.py"
        script.write_text(_WORKER)
        procs = [
            subprocess.Popen(
                [sys.executable, str(script)],
                env={**env, "BYTEPS_GLOBAL_RANK": str(i)},
                cwd="/root/repo",
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        outs = [p.communicate(timeout=240)[0] for p in procs]
        srv.stop()
        sched.stop()
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"
        combined = "".join(outs)
        assert "WORKER_0_OK" in combined and "WORKER_1_OK" in combined
