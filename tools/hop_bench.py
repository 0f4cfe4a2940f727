#!/usr/bin/env python3
"""Host-path probe: what one partition costs on the PS hop's wire, alone.

For sizing a van, journal or server change ON THE CHIP'S HOST, through
``chiprun -- python tools/hop_bench.py --mode ...``, BEFORE predicting what it
buys a PS cell (ROADMAP's first rule: ISSUE 30 sized its change in a CPU
sandbox and predicted six times the gain the chip's host gave).  Its numbers
are host-plane (Python, memcpy, loopback TCP between two processes), never a
device metric.  One JSON line on stdout: median, p10, p90 ms a frame over
``REPS`` timed passes after a warm-up pass.  No file; ``--mode d2h`` and
``--mode h2d`` alone start a jax backend (and so want the chip to themselves).

``--mode frame``: ``--frames`` frames of ``--bytes`` one way through this
tree's ``send_message`` / ``recv_message``, each acked by a bare header, with
and without a 2-round 64 MiB ``RoundJournal``.  The payload is a read-only owned
``ndarray``, as ``np.asarray(slice)`` is on the TPU: the journal keeps a reference.
``--mode brackets`` (PR 71): µs a call of ``core/tracing``'s ``releasing`` bracket
and ``sampled`` service, unsampled and sampled, and ``--mode frame``'s frames
with the sender's service sampled at the program's rate (one in
``sampled.EVERY``), every one, or never.
``--mode echo``: ``--frames`` partitions pushed and pulled back over ONE
connection from a child shaped like the server: a serve thread a connection that
receives, an engine thread that copies into the store, acks and replies.  Five
readings: ``fresh`` (the server up to PR 33: a new ``bytearray`` a received frame,
the store's ``tobytes()`` a reply), ``held`` (since PR 34: frames from the
connection's ``FramePool``, released once copied; the reply a view of the store),
``split`` (since PR 35: ``held``, with the puller's requests and the replies
on a SECOND connection to the same child, so bulk travels one way on a socket,
as a TCP link's push lane and pull lane carry it), ``two_senders`` (since
PR 39: ``split`` with TWO pushing threads, each on a push connection of its own,
the odd keys on the second, and the puller on a third) and ``two_each``
(``two_senders`` with two pullers, a pull connection each: a TCP link as it is
since PR 39).  The child of the last three has four engine threads, a key's
chosen as ``server/server.py`` ``_thread_for`` chooses it, as the real server
has: with one, the probe stops at that thread's copy and 4 MB reply whatever
the sender does.
``--mode d2h``: ``--frames`` slices of ``--bytes`` off ONE array on the first
device, onto the host two ways: sliced and read one at a time (COPYD2H's loop
up to PR 31), and through ``PipelineEngine._start_d2h``, which ``engine.submit``
calls since PR 32: one split program, then every partition's
``copy_to_host_async`` issued before the first is read.  Both keep a pass's host
buffers until the next pass has its own (a step's cycles did, up to PR 33);
``issued_first_freed`` lets them go first, as a step does since PR 34.
``--mode h2d``: ``--frames`` partitions of ``--bytes``, views of one host buffer
as a job's are of its pull target, put on the device one thread as COPYH2D is,
through ``PipelineEngine._h2d``, four ways: ``whole_to_one`` (no placement: the
default device, the engine up to PR 66), ``dealt`` (whole, to the devices in
turn: a full-length partition of a tensor that came in replicated, since PR
67), ``split_over_all`` (cut evenly over every device: such a tensor's
left-over partitions) and ``whole_to_each`` (whole on every device: a length the
device count does not divide).  A reading: the put's own wall and thread-CPU µs
a partition (``issue_*``: what the stage thread pays) and the pass until every
array is ready.  Then a leaf of fc6's shape (25088 x 4096 f32, 101 partitions of
``--bytes``) assembled three ways, wall from dispatch to ready:
``assemble_on_one`` + ``reput`` (the engine's program on the default device,
then ``hybrid.reput``'s put to every device), ``assemble_placed`` (the engine's
own placement: runs of dealt partitions stitched, the rest cut, an all-gather an
array inside the program) and ``assemble_all_split`` (every partition cut).  On
one device the puts and the programs are the same.
"""

import argparse
import contextlib
import functools
import json
import multiprocessing
import os
import queue
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from byteps_tpu.comm.journal import RoundJournal  # noqa: E402
from byteps_tpu.comm.transport import (  # noqa: E402
    FramePool, Message, Op, connect, listen, recv_header_ex, recv_into, recv_message,
    release_frame, send_message,
)
from byteps_tpu.core.telemetry import counters  # noqa: E402

REPS = 5  # timed passes; one more runs first, unwarmed and uncounted
POOL = 40  # distinct payload buffers, so no frame is sent from a warm cache line


def _child(port_out, engine_threads: int, held: bool = False, conns: int = 1) -> None:
    """The server's side: a PUSH is kept (with ``engine_threads`` copied into the
    store, as a sum is) and acked, a PULL answered with the store's bytes
    (``tobytes()``; with ``held`` a view, and received frames from a pool);
    with ``engine_threads`` a serve thread only receives, and the key's engine
    thread (the least loaded when the key first came, as ``_thread_for``) does
    the rest.  Each of the ``conns`` accepted connections has a serve thread, a
    send lock and a pool of its own, and is answered on itself, as the server's are."""
    srv, port = listen("127.0.0.1", 0)
    port_out.send(port)
    inboxes, store = [queue.Queue() for _ in range(engine_threads)], {}
    thread_of, load, choosing = {}, [0] * engine_threads, threading.Lock()

    def inbox_for(key, length):
        with choosing:
            tid = thread_of.get(key)
            if tid is None:
                tid = thread_of[key] = load.index(min(load))
            load[tid] += length
            return inboxes[tid]

    def handle(msg, conn, lock):
        reply = Message(msg.op, key=msg.key, seq=msg.seq)
        if msg.op == Op.PULL:
            reply.payload = memoryview(store[msg.key]) if held else store[msg.key].tobytes()
        elif engine_threads:
            if msg.key not in store:
                store[msg.key] = np.empty(len(msg.payload), np.uint8)
            store[msg.key][:] = np.frombuffer(msg.payload, np.uint8)
            release_frame(msg.payload)
        else:
            store[0] = msg.payload  # the previous frame dies here, as a store's does
        send_message(conn, reply, lock)

    def engine(inbox):
        while True:
            handle(*inbox.get())

    def serve(conn):
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        lock, pool = threading.Lock(), FramePool() if held else None
        while True:
            try:
                msg = recv_message(conn, pool)
            except ConnectionError:
                return
            if engine_threads:
                inbox_for(msg.key, len(msg.payload)).put((msg, conn, lock))
            else:
                handle(msg, conn, lock)

    for inbox in inboxes:
        threading.Thread(target=engine, args=(inbox,), daemon=True).start()
    serving = [threading.Thread(target=serve, args=(srv.accept()[0],), daemon=True)
               for _ in range(conns)]
    for t in serving:
        t.start()
    for t in serving:
        t.join()


@contextlib.contextmanager
def _connected(engine_threads: int, held: bool = False, conns: int = 1):
    """Spawn the child; yield ``conns`` sockets dialled, as a worker's are, to its port."""
    ctx = multiprocessing.get_context("spawn")
    port_in, port_out = ctx.Pipe(duplex=False)
    proc = ctx.Process(target=_child, args=(port_out, engine_threads, held, conns), daemon=True)
    proc.start()
    try:
        if not port_in.poll(120):
            raise SystemExit("hop_bench: the child never listened")
        port = port_in.recv()
        with contextlib.ExitStack() as stack:
            yield [stack.enter_context(connect("127.0.0.1", port)) for _ in range(conns)]
    finally:
        proc.kill()


def _payloads(nbytes: int) -> list:
    pool = [np.full(nbytes, i, np.uint8) for i in range(POOL)]
    for a in pool:
        a.flags.writeable = False  # owned and read-only: a device-to-host copy
    return [a.data for a in pool]


def _on_each_header(sock, handle) -> None:
    """A daemon thread calls ``handle(op, key)`` after every header it reads."""
    def loop():
        try:
            while True:
                op, _, _, _, key, *_ = recv_header_ex(sock)
                handle(op, key)
        except (ConnectionError, OSError):
            return  # the bench closed the socket
    threading.Thread(target=loop, daemon=True).start()


def _summary(ms: list) -> dict:
    p10, median, p90 = np.percentile(ms[1:], [10, 50, 90])  # the first pass is warm-up
    return {"median_ms": float(median), "p10_ms": float(p10), "p90_ms": float(p90)}


def _passes(frames: int, send_one, done: threading.Semaphore, senders: int = 1) -> dict:
    """``REPS`` + 1 passes of ``send_one(i, version)``, each waited out on ``done``;
    with ``senders`` > 1 as many threads send, thread s every frame i ≡ s."""
    def send_stride(first, version):
        for i in range(first, frames, senders):
            send_one(i, version)

    ms = []
    for rep in range(REPS + 1):
        t0 = time.perf_counter()
        others = [threading.Thread(target=send_stride, args=(s, rep + 1), daemon=True)
                  for s in range(1, senders)]
        for t in others:
            t.start()
        send_stride(0, rep + 1)
        for t in others:
            t.join()
        for _ in range(frames):
            done.acquire()
        ms.append((time.perf_counter() - t0) / frames * 1e3)
    return _summary(ms)


def _frame_passes(pool: list, frames: int, journal) -> dict:
    acks = threading.Semaphore(0)
    with _connected(engine_threads=0) as (sock,):
        def push(i, version):
            if journal is not None:
                journal.record(i, version, 0, pool[i % POOL])
            send_message(sock, Message(Op.PUSH, key=i, seq=i, payload=pool[i % POOL]))

        _on_each_header(sock, lambda op, key: acks.release())
        return _passes(frames, push, acks)


def frame(frames: int, nbytes: int) -> dict:
    pool = _payloads(nbytes)
    before = counters().snapshot()
    out = {"journal_on": _frame_passes(pool, frames, RoundJournal(2, 64 << 20)),
           "journal_off": _frame_passes(pool, frames, None)}
    after = counters().snapshot()
    for name in ("journal_ref_bytes", "journal_copy_bytes"):
        out[name] = after.get(name, 0) - before.get(name, 0)
    return out


def brackets(frames: int, nbytes: int) -> dict:
    """What the split of a sampled service costs (``core/tracing.releasing`` |
    ``sampled``), in µs a call over 20 000 calls: an empty bracket outside a
    sampled service (the flag and its branch), one inside (four clock
    readings), a service's ``begin()`` + ``end()`` unsampled and sampled, and
    a ``time.thread_time()`` pair alone.  Then ``--frames`` frames one way as
    ``--mode frame``'s ``journal_off`` has them, the sender inside a service of
    which one in ``sampled.EVERY`` (the program's rate), every one or none is
    sampled: whether the brackets show in a sender's service."""
    from byteps_tpu.core import tracing

    def each_us(fn, calls=20_000):
        t0 = time.perf_counter()
        for _ in range(calls):
            fn()
        return (time.perf_counter() - t0) / calls * 1e6

    def bracket():
        with tracing.releasing():
            pass

    def service(sample):
        def one():
            sample.begin()
            sample.end()
        return one

    out = {"bracket_unsampled_us": each_us(bracket)}
    every_one = tracing.sampled("hop_bench.bracket", every=1)
    every_one.begin()
    out["bracket_sampled_us"] = each_us(bracket)
    every_one.end()
    out["service_unsampled_us"] = each_us(service(tracing.sampled("hop_bench.never", every=1 << 62)))
    out["service_sampled_us"] = each_us(service(tracing.sampled("hop_bench.always", every=1)))
    out["thread_time_pair_us"] = each_us(lambda: time.thread_time() - time.thread_time())
    pool = _payloads(nbytes)
    for name, every in (("as_the_program", None), ("every_one", 1), ("none", 1 << 62)):
        sample = tracing.sampled("hop_bench." + name, every=every)
        acks = threading.Semaphore(0)
        with _connected(engine_threads=0) as (sock,):
            def push(i, version, sample=sample, sock=sock):
                sample.begin()
                try:
                    send_message(sock, Message(Op.PUSH, key=i, seq=i, payload=pool[i % POOL]))
                finally:
                    sample.end()

            _on_each_header(sock, lambda op, key, acks=acks: acks.release())
            out["sent_sampling_" + name] = _passes(frames, push, acks)
    return out


def echo(frames: int, nbytes: int) -> dict:
    pool = _payloads(nbytes)
    return {"fresh": _echo_passes(pool, frames, nbytes, held=False),
            "held": _echo_passes(pool, frames, nbytes, held=True),
            "split": _echo_passes(pool, frames, nbytes, held=True, pulls=1, engine_threads=4),
            "two_senders": _echo_passes(pool, frames, nbytes, held=True, pushes=2, pulls=1,
                                        engine_threads=4),
            "two_each": _echo_passes(pool, frames, nbytes, held=True, pushes=2, pulls=2,
                                     engine_threads=4)}


def _echo_passes(pool: list, frames: int, nbytes: int, held: bool, pushes: int = 1,
                 pulls: int = 0, engine_threads: int = 1) -> dict:
    journal = RoundJournal(2, 64 << 20)
    result = np.empty(frames * nbytes, np.uint8)
    landed = threading.Semaphore(0)
    with _connected(engine_threads, held=held, conns=pushes + pulls) as socks:
        # ``pushes`` push connections, a pushing thread each, then ``pulls``
        # pull connections, a puller each (none: the one connection carries
        # both); key i on connection i mod them, either way
        lanes = [(sock, threading.Lock()) for sock in socks]
        push_lanes, pull_lanes = lanes[:pushes], lanes[pushes:] or lanes
        to_pull = [queue.Queue() for _ in pull_lanes]

        def on_header(sock, op, key):
            if op == Op.PUSH:  # the ack: the key's puller asks for the partition back
                to_pull[key % len(pull_lanes)].put(key)
            else:
                recv_into(sock, memoryview(result[key * nbytes:(key + 1) * nbytes]))
                landed.release()

        def puller(asked, pull_sock, pull_lock):
            while True:
                key = asked.get()
                send_message(pull_sock, Message(Op.PULL, key=key, seq=key), pull_lock)

        def push(i, version):
            journal.record(i, version, 0, pool[i % POOL])
            push_sock, push_lock = push_lanes[i % pushes]
            send_message(push_sock, Message(Op.PUSH, key=i, seq=i, payload=pool[i % POOL]),
                         push_lock)

        for sock, _ in lanes:
            _on_each_header(sock, functools.partial(on_header, sock))
        for asked, (pull_sock, pull_lock) in zip(to_pull, pull_lanes):
            threading.Thread(target=puller, args=(asked, pull_sock, pull_lock), daemon=True).start()
        out = _passes(frames, push, landed, senders=pushes)
    if bytes(result[-nbytes:]) != bytes(pool[(frames - 1) % POOL]):
        raise SystemExit("hop_bench: the last partition came back changed")
    return out


def d2h(frames: int, nbytes: int) -> dict:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from byteps_tpu.common.types import Partition
    from byteps_tpu.core.engine import PipelineEngine

    n = max(1, nbytes // 4)
    host = np.arange(frames * n, dtype=np.float32)
    flat = jax.block_until_ready(jax.device_put(host, jax.devices()[0]))
    partitions = [Partition(key=i, offset=i * n, length=n) for i in range(frames)]
    issue_ms = []

    def one_at_a_time(src):
        return [np.asarray(src[i * n:(i + 1) * n]) for i in range(frames)]

    def issued_first(src):
        t0 = time.perf_counter()
        parts = PipelineEngine._start_d2h(src, partitions)  # the engine's own
        issue_ms.append((time.perf_counter() - t0) / frames * 1e3)
        return [np.asarray(parts.pop(p.offset)) for p in partitions]

    readings = [("one_at_a_time", one_at_a_time, flat), ("issued_first", issued_first, flat),
                ("issued_first_freed", issued_first, flat)]
    if len(jax.devices()) > 1:
        # the gradient of a dp > 1 step: whole on every chip, and every
        # partial slice of it a gather program on all of them
        everywhere = NamedSharding(Mesh(np.array(jax.devices()), ("dp",)), PartitionSpec())
        readings.append(("one_at_a_time_replicated", one_at_a_time,
                         jax.block_until_ready(jax.device_put(host, everywhere))))
    out = {"device": jax.devices()[0].device_kind, "devices": len(jax.devices())}
    for name, one_pass, src in readings:
        ms, held = [], None
        for _ in range(REPS + 1):
            t0 = time.perf_counter()
            # a pass's host buffers live until the next has its own, as a
            # step's cycles kept them: every pass lands in fresh memory
            if name.endswith("_freed"):
                held = None  # as a step's do now: freed, then made again
            held = one_pass(src)
            ms.append((time.perf_counter() - t0) / frames * 1e3)
            if not np.array_equal(held[-1], host[-n:]):
                raise SystemExit(f"hop_bench: {name} read the last slice changed")
        del held
        out[name] = _summary(ms)
        out[name]["gb_per_s"] = n * 4 / out[name]["median_ms"] / 1e6
    out["issued_first"]["issue_ms"] = float(np.median(issue_ms[1:]))
    return out


def h2d(frames: int, nbytes: int) -> dict:
    import types

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from byteps_tpu.common.types import Partition
    from byteps_tpu.core import engine

    n = max(1, nbytes // 4)
    devices = jax.devices()
    everyone = NamedSharding(Mesh(np.array(devices), ("dp",)), PartitionSpec())
    split, everywhere = engine._partition_shardings(everyone)
    # the engine's own put, of a one-worker engine (no divide)
    put = functools.partial(engine.PipelineEngine._h2d,
                            types.SimpleNamespace(client=types.SimpleNamespace(num_workers=1)))

    def partitions(host):
        return [host[off:off + n] for off in range(0, host.size, n)]

    out = {"device": devices[0].device_kind, "devices": len(devices)}
    bufs = partitions(np.arange(frames * n, dtype=np.float32))
    ways = {"whole_to_one": lambda i: None, "dealt": lambda i: devices[i % len(devices)],
            "split_over_all": lambda i: split, "whole_to_each": lambda i: everywhere}
    for name, where in ways.items():
        wall_us, cpu_us, ready_ms = [], [], []
        for _ in range(REPS + 1):
            t0, c0 = time.perf_counter(), time.thread_time()
            parts = [put(buf, False, where(i)) for i, buf in enumerate(bufs)]
            t1, c1 = time.perf_counter(), time.thread_time()
            jax.block_until_ready(parts)
            ready_ms.append((time.perf_counter() - t0) / frames * 1e3)
            wall_us.append((t1 - t0) / frames * 1e6)
            cpu_us.append((c1 - c0) / frames * 1e6)
            for shard in parts[-1].addressable_shards:
                if not np.array_equal(shard.data, bufs[-1][shard.index]):
                    raise SystemExit(f"hop_bench: {name} put the last partition changed")
            del parts
        out[name] = {**_summary(ready_ms), "issue_wall_us": float(np.median(wall_us[1:])),
                     "issue_cpu_us": float(np.median(cpu_us[1:]))}
        out[name]["gb_per_s"] = n * 4 / out[name]["median_ms"] / 1e6

    shape = (25088, 4096)  # vgg16's fc6: the leaf most of a step's partitions belong to
    leaf = partitions(np.arange(shape[0] * shape[1], dtype=np.float32))
    keyed = [Partition(key=i, offset=i * n, length=buf.size) for i, buf in enumerate(leaf)]
    placed, all_split = engine._Placement(everyone, keyed), engine._Placement(everyone, keyed)
    all_split.dealt = 0  # nothing dealt: every partition cut, PR 67's first form
    ms = {"assemble_on_one": [], "reput": [], "assemble_placed": [], "assemble_all_split": []}
    for _ in range(REPS + 1):
        parts = jax.block_until_ready([put(buf, False) for buf in leaf])
        t0 = time.perf_counter()
        on_one = jax.block_until_ready(engine._assemble(parts, shape))
        t1 = time.perf_counter()
        reput = jax.block_until_ready(jax.device_put(on_one, everyone))
        ms["assemble_on_one"].append((t1 - t0) * 1e3)
        ms["reput"].append((time.perf_counter() - t1) * 1e3)
        del parts, on_one
        for name, placement in (("assemble_placed", placed), ("assemble_all_split", all_split)):
            parts = jax.block_until_ready(
                [put(buf, False, placement.where(i * n, buf.size)[0]) for i, buf in enumerate(leaf)])
            t0 = time.perf_counter()
            made = jax.block_until_ready(engine._assemble(parts, shape, placement))
            ms[name].append((time.perf_counter() - t0) * 1e3)
            if jax.device_put(made, everyone) is not made:
                raise SystemExit(f"hop_bench: {name}'s leaf is not where hybrid.reput wants it")
            for a, b in zip(reput.addressable_shards, made.addressable_shards):
                if not np.array_equal(a.data, b.data):
                    raise SystemExit(f"hop_bench: {name}'s leaf differs on {a.device}")
            del parts, made
        del reput
    out["fc6_leaf"] = {"partitions": len(leaf), "dealt": placed.dealt,
                       **{name: _summary(readings) for name, readings in ms.items()}}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mode", choices=("frame", "brackets", "echo", "d2h", "h2d"), required=True)
    ap.add_argument("--frames", type=int, default=None,
                    help="frames a pass (default: 150 one way, 162 echoed, read or put — a vgg16 step)")
    ap.add_argument("--bytes", type=int, default=4_096_000, help="bytes a frame")
    args = ap.parse_args()
    frames = (150 if args.mode == "frame" else 162) if args.frames is None else args.frames
    if frames < 1 or args.bytes < 1:
        ap.error("--frames and --bytes are positive")
    reading = {"frame": frame, "brackets": brackets, "echo": echo, "d2h": d2h,
               "h2d": h2d}[args.mode](frames, args.bytes)
    print(json.dumps({"mode": args.mode, "frames": frames, "bytes": args.bytes,
                      "timed_passes": REPS, "host_cores": os.cpu_count(),
                      "plane": "host", **reading}), flush=True)


if __name__ == "__main__":
    main()
