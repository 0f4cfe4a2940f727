"""COPYH2D does its name: every pulled (or host-decoded) partition of a jax
job is averaged in place and put on the device as it lands, ``_finalize`` is
left with a device-side assemble, and the job lets go of its buffers when it
hands the result back.  The results must stay bit-equal to the arithmetic the
host-assembled path had (``sum / num_workers`` in the tensor's dtype, no
divide for one worker or ``average=False``); numpy callers keep the host path
and count no ``h2d_bytes``.  The result is made in the sharding the tensor was
submitted in (ISSUE 67): a tensor replicated over several devices has each
partition put ONCE — the full-length ones dealt whole to the devices in turn
while they make whole runs, the rest cut evenly over them where the length
divides (both counted ``h2d_sharded_parts``) and whole on each where it does
not — and the assemble program gathers; every device then holds the same
bits the one-device path gives.

One parametrised test against an in-process scheduler + server.  The other
workers of a 2- or 3-worker case are bare ``PSClient``s that join the init
barrier and push their share of each round, so the sum and the divide are
real; their payloads are small integers, whose f32 sums are exact in any
arrival order, while a divide by 3 still rounds."""

import dataclasses
import threading
import weakref

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.comm.rendezvous import Scheduler
from byteps_tpu.server.server import PSServer

PART_ELEMS = 1024  # BYTEPS_PARTITION_BYTES=4096 of f32
ROUNDS = 2


@dataclasses.dataclass(frozen=True)
class Case:
    id: str
    shape: tuple
    workers: int = 1
    average: bool = True
    jax_input: bool = True
    codec: bool = False    # topk at full k under error feedback: the HOST codec path
    fusion: int = 0        # BYTEPS_FUSION_THRESHOLD
    # the submitted array: 0 as ``jnp.asarray`` makes it (one device, no
    # name), n replicated over a mesh of the first n devices (1: a
    # NamedSharding that names one chip, as a one-chip HybridDataParallel's)
    devices: int = 0

    @property
    def parts(self) -> int:
        return -(-int(np.prod(self.shape)) // PART_ELEMS)

    @property
    def shared_parts(self) -> int:
        """Partitions whose bytes go out once over the devices: the
        full-length ones that make whole runs of one a device (dealt, whole,
        to the devices in turn), and of the rest those the device count
        divides (cut evenly)."""
        n, d = int(np.prod(self.shape)), self.devices
        if d < 2:
            return 0
        lengths = [min(PART_ELEMS, n - off) for off in range(0, n, PART_ELEMS)]
        dealt = sum(ln == lengths[0] for ln in lengths) // d * d
        return dealt + sum(ln % d == 0 for ln in lengths[dealt:])


CASES = [
    Case(f"raw-{'avg' if avg else 'sum'}-w{w}", (5, PART_ELEMS + 100), workers=w, average=avg)
    for w in (1, 2, 3) for avg in (True, False)
] + [
    Case("raw-one-part-leaf", (8, 16)),
    Case("raw-flat-exact-multiple", (4 * PART_ELEMS,), workers=2),
    Case("numpy-avg-w1", (3 * PART_ELEMS + 7,), jax_input=False),
    Case("numpy-avg-w3", (3 * PART_ELEMS + 7,), workers=3, jax_input=False),
    Case("host-codec-topk-ef", (4 * PART_ELEMS,), average=False, codec=True),
    Case("host-codec-topk-ef-avg", (2, 2 * PART_ELEMS), codec=True),
    Case("fused-small", (500,), fusion=16384),
    Case("fused-small-2d", (20, 30), average=False, fusion=16384),
    Case("named-one-device", (5, PART_ELEMS + 100), devices=1),
    Case("named-one-device-one-part", (8, 16), workers=2, devices=1),
    Case("replicated-2-divides", (3 * PART_ELEMS,), workers=2, devices=2),
    Case("replicated-2-one-odd-part", (7, 9), devices=2),
    Case("replicated-4-last-does-not-divide", (2 * PART_ELEMS + 6,), devices=4),
    Case("replicated-4-one-part", (8, 16), average=False, devices=4),
    Case("replicated-4-avg-w3", (5, PART_ELEMS + 100), workers=3, devices=4),
    Case("replicated-2-runs-and-rest", (7 * PART_ELEMS + 3,), workers=2, devices=2),
    Case("replicated-4-two-runs", (8, PART_ELEMS), average=False, devices=4),
    Case("replicated-2-host-codec", (2, 2 * PART_ELEMS), codec=True, devices=2),
    Case("replicated-4-fused-small", (500,), fusion=16384, devices=4),
]


class Peer:
    """Another worker, as far as the server can tell: joins the init barrier
    of every partition and pushes ``payload`` each round."""

    def __init__(self, cfg, uid, payload):
        from byteps_tpu.comm.ps_client import PSClient

        self.client = PSClient(cfg, node_uid=uid)
        self.payload = payload
        self.go = [threading.Event() for _ in range(ROUNDS)]
        self.error = None
        self.connecting = threading.Thread(target=self.client.connect, daemon=True)
        self.connecting.start()

    def run(self, partitions, dtype_id):
        def body():
            try:
                self.connecting.join(30)
                for p in partitions:
                    self.client.init_tensor(p.key, p.length, dtype_id)
                for version, go in enumerate(self.go, 1):
                    assert go.wait(60)
                    acked = threading.Semaphore(0)
                    for p in partitions:
                        self.client.push(
                            p.key, self.payload[p.offset : p.offset + p.length].tobytes(),
                            dtype_id, version, acked.release,
                        )
                    for _ in partitions:
                        assert acked.acquire(timeout=60)
            except BaseException as e:  # surfaced by the test's own assert
                self.error = e

        self.thread = threading.Thread(target=body, daemon=True)
        self.thread.start()


@pytest.fixture
def cluster(request, monkeypatch):
    case = request.param
    sched = Scheduler(num_workers=case.workers, num_servers=1, host="127.0.0.1")
    sched.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", str(case.workers))
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", str(4 * PART_ELEMS))
    monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
    if case.fusion:
        monkeypatch.setenv("BYTEPS_FUSION_THRESHOLD", str(case.fusion))
        monkeypatch.setenv("BYTEPS_FUSION_CYCLE_MS", "2")
    cfg = Config.from_env()
    srv = PSServer(cfg)
    threading.Thread(target=srv.start, daemon=True).start()
    rng = np.random.default_rng(28)
    n = int(np.prod(case.shape))
    peers = [
        Peer(cfg, f"copyh2d-peer{i}", rng.integers(-999, 1000, n).astype(np.float32))
        for i in range(1, case.workers)
    ]
    yield case, peers
    for peer in peers:
        peer.client.close()
    srv.stop()
    sched.stop()


@pytest.mark.parametrize("cluster", CASES, ids=lambda c: c.id, indirect=True)
def test_copyh2d_puts_partitions_and_finalize_assembles(cluster):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import byteps_tpu as bps
    from byteps_tpu.common.partition import partition_tensor
    from byteps_tpu.common.registry import get_registry
    from byteps_tpu.common.types import to_datatype
    from byteps_tpu.core.state import get_state

    case, peers = cluster
    name = f"copyh2d.{case.id}"
    n = int(np.prod(case.shape))
    bps.init()
    try:
        engine = get_state().engine
        assert engine.client.num_workers == case.workers
        if case.codec:
            bps.declare_tensor(name, byteps_compressor_type="topk",
                               byteps_compressor_k=str(PART_ELEMS), byteps_ef_type="vanilla")
        if peers:
            # the layout submit would build, built first: the peers need its keys
            ctx = get_registry().declare(name)
            partition_tensor(ctx, n, 4, engine.cfg.partition_bytes)
            for peer in peers:
                peer.run(ctx.partitions, int(to_datatype(np.dtype(np.float32))))

        finalized = []
        orig_finalize = engine._finalize

        def spy(job):
            parts = list((job.device_parts or {}).values())
            finalized.append((job, len(parts), [weakref.ref(p) for p in parts],
                              job.result is not None))
            del parts
            orig_finalize(job)

        engine._finalize = spy
        for rnd in range(ROUNDS):
            x = np.random.default_rng(rnd).integers(-999, 1000, n).astype(np.float32)
            x = x.reshape(case.shape)
            before = bps.get_robustness_counters()
            for peer in peers:
                peer.go[rnd].set()
            sent = x
            if case.devices:
                mesh = Mesh(np.array(jax.devices()[:case.devices]), ("dp",))
                sent = jax.device_put(x, NamedSharding(mesh, PartitionSpec()))
            elif case.jax_input:
                sent = jnp.asarray(x)
            out = bps.push_pull(sent, name=name, average=case.average)
            after = bps.get_robustness_counters()

            # the parent's arithmetic: assemble on the host, then one divide
            want = x.reshape(-1).copy()
            for peer in peers:
                want = want + peer.payload
            if case.average:
                want = want / case.workers
            want = want.reshape(case.shape)
            assert isinstance(out, jax.Array if case.jax_input else np.ndarray)
            assert out.shape == case.shape and out.dtype == np.float32
            np.testing.assert_array_equal(np.asarray(out).view(np.uint32), want.view(np.uint32))
            if case.jax_input:
                # in the submitted sharding, and every device's copy the same bits
                assert out.sharding.is_equivalent_to(sent.sharding, out.ndim)
                assert len(out.addressable_shards) == max(case.devices, 1)
                for shard in out.addressable_shards:
                    np.testing.assert_array_equal(
                        np.asarray(shard.data).view(np.uint32), want.view(np.uint32))

            grew = {k: after.get(k, 0) - before.get(k, 0)
                    for k in ("h2d_bytes", "d2h_bytes", "fused_frames", "h2d_sharded_parts")}
            moved = x.nbytes if case.jax_input else 0
            assert grew == {"h2d_bytes": moved, "d2h_bytes": moved,
                            "fused_frames": int(bool(case.fusion)),
                            "h2d_sharded_parts": case.shared_parts}

            job, on_device, refs, had_result = finalized.pop()
            assert not finalized  # one finalize a job
            assert had_result  # raw and host-codec jobs pull into a host buffer
            assert job.device_parts is None
            if case.jax_input:
                assert on_device == case.parts
                assert job.result is None
                if case.parts > 1:
                    # the stage thread may still be leaving _finalize; its
                    # references were dropped before mark_done woke us
                    assert all(ref() is None for ref in refs)
            else:
                assert on_device == 0 and job.result is not None

        parts = get_registry().get(name).partitions
        assert len(parts) == case.parts
        assert all((p.key in engine._compressors) == case.codec for p in parts)
        assert not any(p.key in engine._device_codecs for p in parts)
        for peer in peers:
            peer.thread.join(30)
            assert peer.error is None and not peer.thread.is_alive()
    finally:
        bps.shutdown()
