"""Conv model zoo tests: ResNet/VGG train data-parallel on the CPU mesh
(the reference's ResNet-50/VGG-16 benchmark models, BASELINE.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models.resnet import ResNet50, ResNetTiny
from byteps_tpu.models.vgg import VGG16, VGGTiny
from byteps_tpu.optim import build_flax_data_parallel_step


def _xent(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def _fake_data(n=16, hw=32, classes=10, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, hw, hw, 3)).astype(np.float32)
    y = rng.integers(0, classes, size=(n,)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


class TestResNet:
    def test_resnet50_builds(self):
        model = ResNet50(num_classes=1000)
        x = jnp.zeros((1, 64, 64, 3))
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        out = model.apply(variables, x, train=False)
        assert out.shape == (1, 1000)
        n_params = sum(p.size for p in jax.tree_util.tree_leaves(variables["params"]))
        assert 2.4e7 < n_params < 2.7e7  # ~25.5M — ResNet-50

    def test_tiny_trains_ddp(self, mesh8):
        model = ResNetTiny()
        x, y = _fake_data()
        variables = model.init(jax.random.PRNGKey(0), x[:1], train=True)
        tx = optax.sgd(0.05)
        opt_state = jax.jit(tx.init)(variables["params"])
        step = build_flax_data_parallel_step(
            model.apply, _xent, tx, mesh=mesh8, donate=False
        )
        losses = []
        for _ in range(8):
            variables, opt_state, loss = step(variables, opt_state, (x, y))
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert "batch_stats" in variables  # BN stats updated & synced


class TestVGG:
    def test_vgg16_builds(self):
        model = VGG16()
        x = jnp.zeros((1, 32, 32, 3))
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        out = model.apply(variables, x, train=False)
        assert out.shape == (1, 1000)
        n_params = sum(p.size for p in jax.tree_util.tree_leaves(variables["params"]))
        assert n_params > 3e7  # dense-heavy, communication-bound

    def test_tiny_trains_ddp(self, mesh8):
        model = VGGTiny()
        x, y = _fake_data()
        variables = model.init(jax.random.PRNGKey(0), x[:1], train=True)
        tx = optax.sgd(0.05)
        opt_state = jax.jit(tx.init)(variables["params"])
        step = build_flax_data_parallel_step(
            model.apply, _xent, tx, mesh=mesh8, donate=False
        )
        losses = []
        for _ in range(8):
            variables, opt_state, loss = step(variables, opt_state, (x, y))
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestLauncher:
    def test_check_env(self):
        from byteps_tpu.launcher.launch import check_env

        with pytest.raises(SystemExit, match="missing"):
            check_env({"DMLC_ROLE": "worker", "DMLC_NUM_WORKER": "2"})
        check_env({"DMLC_ROLE": "worker", "DMLC_NUM_WORKER": "1"})  # ok

    def test_tpu_topology_discovery(self):
        from byteps_tpu.launcher.launch import discover_tpu_topology

        env = {"TPU_WORKER_HOSTNAMES": "host-a,host-b,host-c", "TPU_WORKER_ID": "1"}
        out = discover_tpu_topology(env)
        assert out["DMLC_NUM_WORKER"] == "3"
        assert out["DMLC_WORKER_ID"] == "1"
        assert out["DMLC_PS_ROOT_URI"] == "host-a"
        assert out["BYTEPS_GLOBAL_RANK"] == "1"

    def test_topology_noop_without_metadata(self):
        from byteps_tpu.launcher.launch import discover_tpu_topology

        assert discover_tpu_topology({}) == {}

    def test_role_env_building(self):
        from byteps_tpu.launcher.dist_launcher import build_role_env

        env = build_role_env("worker", 2, 4, 2, "10.0.0.1", 9000, {"FOO": "1"})
        assert env["DMLC_WORKER_ID"] == "2"
        assert env["BYTEPS_GLOBAL_RANK"] == "2"
        assert env["FOO"] == "1"
        senv = build_role_env("server", 0, 4, 2, "10.0.0.1", 9000, {})
        assert "DMLC_WORKER_ID" not in senv

    def test_ssh_command_quoting(self):
        from byteps_tpu.launcher.dist_launcher import ssh_command

        argv = ssh_command("h1", {"A": "x y"}, ["python", "train.py"])
        assert argv[0] == "ssh" and "h1" in argv
        assert "A='x y' python train.py" in argv[-1]

    def test_worker_launch_end_to_end(self, tmp_path):
        """bpslaunch actually runs a worker command with role env set."""
        import os, pathlib, subprocess, sys

        repo = str(pathlib.Path(__file__).resolve().parents[1])
        out = subprocess.run(
            [sys.executable, "-m", "byteps_tpu.launcher.launch", "--",
             sys.executable, "-c",
             "import os; print(os.environ['BYTEPS_LOCAL_RANK'], os.environ['DMLC_ROLE'])"],
            env={**os.environ, "DMLC_ROLE": "worker", "PYTHONPATH": repo},
            capture_output=True, text=True, cwd=repo,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip().endswith("0 worker")


class TestNumaAutoQuota:
    """allocate_cpu (launch.py:49-141 parity): per-process core quotas
    from NUMA topology, root gets the remainder, knobs honored."""

    def _nodes(self):
        return [[0, 1, 2, 3], [4, 5, 6, 7]]  # 8 physical cores, 2 nodes

    def test_default_split_root_gets_rest(self):
        from byteps_tpu.launcher.launch import allocate_cpu

        plan = allocate_cpu(2, env={"BYTEPS_MULTITHREADED_CPU": "0"}, nodes=self._nodes())
        assert len(plan) == 2
        # default quota 8//2=4; root gets 8-4=4 (clamped to node size 4)
        assert plan[0] == [0, 1, 2, 3]
        assert plan[1] == [4, 5, 6, 7]

    def test_quota_env_override_and_blacklist(self):
        from byteps_tpu.launcher.launch import allocate_cpu

        plan = allocate_cpu(
            2,
            env={
                "BYTEPS_MULTITHREADED_CPU": "0",
                "BYTEPS_NUMA_DEFAULT_QUOTA": "2",
                "BYTEPS_NUMA_ROOT_QUOTA": "3",
                "BYTEPS_CPU_BLACKLIST": "0",
            },
            nodes=self._nodes(),
        )
        assert plan[0] == [1]  # quota 2 from node0 minus blacklisted core 0
        # root quota 3: node0 has only [2,3] left, node1 satisfies it whole
        assert plan[1] == [4, 5, 6]

    def test_hyperthread_siblings_added(self):
        from byteps_tpu.launcher.launch import allocate_cpu

        plan = allocate_cpu(1, env={"BYTEPS_MULTITHREADED_CPU": "1"}, nodes=self._nodes())
        # root gets all 8 physical + 8 sibling ids (offset by core count)
        assert plan[0][:4] == [0, 1, 2, 3]
        assert 0 + 8 in plan[0]

    def test_no_numa_info_returns_none(self):
        from byteps_tpu.launcher.launch import allocate_cpu

        assert allocate_cpu(2, env={}, nodes=[]) is None

    def test_numa_prefix_uses_plan(self, monkeypatch):
        import byteps_tpu.launcher.launch as launch

        monkeypatch.setattr(launch.shutil, "which", lambda _: "/usr/bin/numactl")
        monkeypatch.setattr(
            launch, "get_numa_nodes", lambda cpu_mt=True, numa_path="": [[0, 1], [2, 3]]
        )
        env = {"BYTEPS_MULTITHREADED_CPU": "0", "BYTEPS_LOCAL_SIZE": "2",
               "BYTEPS_LOCAL_RANK": "1"}
        prefix = launch.numa_prefix(env)
        assert prefix and prefix[0] == "numactl"
        assert prefix[1] == "--physcpubind=2,3"

    def test_explicit_cores_win(self, monkeypatch):
        import byteps_tpu.launcher.launch as launch

        monkeypatch.setattr(launch.shutil, "which", lambda _: "/usr/bin/numactl")
        env = {"BYTEPS_VISIBLE_CPU_CORES": "5,6"}
        assert launch.numa_prefix(env) == ["numactl", "--physcpubind=5,6"]

    def test_single_process_gets_all_nodes(self):
        """local_size=1 (the TPU default: one process per host) must span
        every NUMA node, not be confined to node 0."""
        from byteps_tpu.launcher.launch import allocate_cpu

        plan = allocate_cpu(1, env={"BYTEPS_MULTITHREADED_CPU": "0"}, nodes=self._nodes())
        assert plan[0] == [0, 1, 2, 3, 4, 5, 6, 7]

    def test_quota_spans_nodes_when_needed(self):
        """A quota larger than any single node fills from multiple nodes."""
        from byteps_tpu.launcher.launch import allocate_cpu

        plan = allocate_cpu(
            2,
            env={"BYTEPS_MULTITHREADED_CPU": "0"},
            nodes=[[0, 1], [2, 3], [4, 5], [6, 7]],
        )
        # non-root quota 8//2=4 > any node's 2 → spans two nodes; the
        # shared-host root stays NUMA-local (clamped to one node's size,
        # reference launch.py:119-124)
        assert plan[0] == [0, 1, 2, 3]
        assert len(plan[1]) == 2
