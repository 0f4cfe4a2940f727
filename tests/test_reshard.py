"""Elastic server resharding (docs/robustness.md "migration flow"):
versioned key→server ownership, live key migration, exactly-once handoff.

Layers under test:

- the consistent-hash ownership ring: balance, minimal movement on a
  rank join, ``fn="ring"`` routing, and bit-identical coordinates
  between Python (hashing.ring_key_hash) and the C++ engine
  (wire.h ring_key_hash via the golden shim);
- wire codecs for Op.MIGRATE_STATE / Op.WRONG_OWNER, plus symbolic op
  names in BYTEPS_CHAOS_OPS (the deterministic-test targeting knob);
- wire-level migration: the old owner ships a key's store + exactly-once
  ledger + init-token record, tombstones it, and redirects; the new
  owner serves the continued version sequence and DEDUPES a replayed
  round (no double-sum — the handoff is exactly-once);
- map-epoch skew: a worker holding a stale map pushes to the old owner,
  is redirected, waits for the new book, and its resend lands on the new
  owner (async push chase AND blocking init chase);
- migration parking: a request reaching the new owner before its state
  does parks until the MIGRATE_STATE frame lands; an evicted previous
  owner (state is gone) must NOT park — the re-init path owns rebirth;
- the native engine's ownership awareness: WRONG_OWNER replies for
  un-held keys the map homes elsewhere, held keys stay authoritative,
  MIGRATE_STATE is refused with the clean status=1 echo;
- gauges riding the heartbeat delta (server_owned_keys & co. toward the
  scheduler aggregate that tools/bps_top.py renders);
- end-to-end: a live scale-up then scale-down against a real scheduler
  with a real PSClient — bitwise pulls throughout, migration counters
  move, NO re-init generation bump, and the drained server stops itself.
"""

import json
import struct
import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config
from byteps_tpu.common.hashing import (
    HashRing,
    OwnershipMap,
    assign_server,
    ring_key_hash,
)
from byteps_tpu.common.types import DataType, RequestType, get_command_type
from byteps_tpu.comm.transport import (
    Message,
    Op,
    close_socket,
    connect,
    decode_migrate_state,
    decode_wrong_owner,
    encode_fused_push,
    encode_migrate_state,
    encode_wrong_owner,
    recv_message,
    send_message,
)
from byteps_tpu.core.telemetry import counters
from byteps_tpu.server.server import PSServer
from conftest import have_native_parity_server

CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, int(DataType.FLOAT32))
F32 = int(DataType.FLOAT32)


def _key_owned_by(rank: int, ranks, vnodes: int = 64, start: int = 0) -> int:
    """Smallest key (stepping the partition-key stride) the ring homes on
    ``rank`` — deterministic, so tests pick real migration victims."""
    ring = HashRing(ranks, vnodes=vnodes)
    for k in range(start, start + (1 << 12)):
        key = k << 16
        if ring.owner(key) == rank:
            return key
    raise AssertionError(f"no key owned by rank {rank} in probe range")


def _wire_server(num_workers: int = 1, reshard: bool = True) -> PSServer:
    srv = PSServer(Config(num_worker=num_workers, num_server=1,
                          elastic_reshard=reshard))
    srv.start(register=False)
    return srv


def _init_key(socks_flags, key: int, n: int, token: int = 77):
    payload = struct.pack("!QI", n, F32)
    for i, (sock, flag) in enumerate(socks_flags):
        send_message(sock, Message(Op.INIT, key=key, seq=100 + i, flags=flag,
                                   version=token, payload=payload))
    for sock, _ in socks_flags:
        assert recv_message(sock).op == Op.INIT


def _book(epoch, ranks, servers, drain=False):
    b = {"map_epoch": epoch, "server_ranks": list(ranks),
         "servers": [list(s) for s in servers]}
    if drain:
        b["drain"] = True
    return b


def _wait(pred, timeout=10.0, msg="condition never held"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(msg)


class TestOwnershipRing:
    def test_balance(self):
        ring = HashRing([0, 1, 2], vnodes=64)
        from collections import Counter

        owners = Counter(ring.owner(k << 16) for k in range(3000))
        for r in (0, 1, 2):
            # consistent hashing is approximate; vnodes=64 keeps every
            # rank within a sane band (a broken point hash collapses
            # the whole space onto one rank — the bug this pins)
            assert owners[r] > 3000 * 0.15, owners

    def test_minimal_movement_on_join(self):
        r2 = HashRing([0, 1], vnodes=64)
        r3 = HashRing([0, 1, 2], vnodes=64)
        keys = [k << 16 for k in range(2000)]
        moved = [k for k in keys if r2.owner(k) != r3.owner(k)]
        # every re-homed key moved TO the joiner — survivors never
        # shuffle keys among themselves (the bounded-window property)
        assert moved and all(r3.owner(k) == 2 for k in moved)
        assert len(moved) < len(keys) * 0.5  # ≈ 1/3 ideally

    def test_ring_fn_routes_like_the_ring(self):
        ring = HashRing(range(3), vnodes=64)
        for k in range(0, 1 << 20, 1 << 16):
            assert assign_server(k, 3, fn="ring") == ring.owner(k)

    def test_ownership_map_carries_epoch(self):
        m = OwnershipMap([0, 2, 5], epoch=7)
        assert m.epoch == 7 and m.ranks == (0, 2, 5)
        assert m.owner(123) in (0, 2, 5)

    @pytest.mark.skipif(not have_native_parity_server(),
                        reason="native lib unavailable")
    def test_ring_key_hash_native_parity(self):
        import ctypes

        from byteps_tpu.native import get_lib

        lib = get_lib()
        if not hasattr(lib, "bps_wire_ring_hash"):
            pytest.skip("native lib predates the resharding plane")
        for k in [0, 1, 65536, 1 << 33, (1 << 40) + 17, 999 << 16]:
            assert lib.bps_wire_ring_hash(ctypes.c_uint64(k).value) == (
                ring_key_hash(k)
            ), f"ring hash diverged for key {k}"


class TestReshardCodecs:
    def test_migrate_state_roundtrip(self):
        store = np.arange(32, dtype=np.float32).tobytes()
        accum = np.full(32, 2.5, dtype=np.float32).tobytes()
        meta = {"key": 7, "epoch": 3, "dtype": "float32",
                "store_version": 5, "push_seen": {"1": 5, "2": 4},
                "init_done": {"1": 77},
                "store_nbytes": len(store), "accum_nbytes": len(accum)}
        m2, s2, a2 = decode_migrate_state(
            encode_migrate_state(meta, store, accum)
        )
        assert m2 == meta and s2 == store and a2 == accum

    def test_migrate_state_truncation_raises(self):
        store = b"x" * 64
        meta = {"key": 1, "store_nbytes": 64, "accum_nbytes": 0}
        body = encode_migrate_state(meta, store)
        with pytest.raises(ValueError):
            decode_migrate_state(body[: len(body) - 8])
        with pytest.raises(ValueError):
            decode_migrate_state(b"\x00\x00")

    def test_wrong_owner_roundtrip(self):
        assert decode_wrong_owner(encode_wrong_owner(9, 2)) == (9, 2)
        # empty / garbage bodies fall back to header-only semantics
        assert decode_wrong_owner(b"") == (0, -1)
        assert decode_wrong_owner(b"\xff\xfe") == (0, -1)

    def test_chaos_ops_accepts_symbolic_names(self, monkeypatch):
        from byteps_tpu.comm.chaos import ChaosParams

        monkeypatch.setenv("BYTEPS_CHAOS_OPS",
                           "MIGRATE_STATE, wrong_owner, 11")
        assert ChaosParams.from_env().ops == frozenset(
            {int(Op.MIGRATE_STATE), int(Op.WRONG_OWNER), int(Op.PUSH)}
        )
        monkeypatch.setenv("BYTEPS_CHAOS_OPS", "NOT_AN_OP")
        with pytest.raises(ValueError):
            ChaosParams.from_env()


class TestMigrationWire:
    """Wire-level handoff between two real Python servers."""

    def test_migration_moves_state_redirects_and_dedupes(self):
        a = _wire_server()
        b = _wire_server()
        a.rank, b.rank = 0, 1
        key = _key_owned_by(1, [0, 1])  # re-homes to b under epoch 2
        n = 16
        g1 = np.arange(n, dtype=np.float32)
        g2 = np.full(n, 3.5, dtype=np.float32)
        w = connect(a.host, a.port)
        w.settimeout(15)
        try:
            _init_key([(w, 1)], key, n)
            for ver, g in ((1, g1), (2, g2)):
                send_message(w, Message(Op.PUSH, key=key, seq=ver, flags=1,
                                        cmd=CMD_F32, version=ver,
                                        payload=g.tobytes()))
                assert recv_message(w).op == Op.PUSH
            # the scheduler's new book lands on BOTH servers (b adopts
            # the map too, so it won't park forever on its own keys)
            servers = [(a.host, a.port), (b.host, b.port)]
            book = _book(2, [0, 1], servers)
            b._adopt_book(dict(book, rank=1))
            a._adopt_book(dict(book, rank=0))
            _wait(lambda: key in b._keys
                  and b._keys[key].store is not None,
                  msg="migration never landed on the new owner")
            st = b._keys[key]
            assert st.store_version == 2
            assert st.push_seen.get(1) == 2      # ledger traveled
            assert st.init_done.get(1) is not None  # token record traveled
            np.testing.assert_array_equal(
                st.store, g2
            )  # round-2 publish traveled bitwise
            assert a._keys[key].migrated_to == 1  # tombstone at old owner
            # the old owner frees the bulk once the new one has acknowledged
            _wait(lambda: a._keys[key].store is None,
                  msg="the old owner never freed the migrated key's bulk")
            # stale-map push to the OLD owner redirects with the epoch
            send_message(w, Message(Op.PUSH, key=key, seq=9, flags=1,
                                    cmd=CMD_F32, version=3,
                                    payload=g1.tobytes()))
            r = recv_message(w)
            assert r.op == Op.WRONG_OWNER and r.version == 2
            assert decode_wrong_owner(r.payload) == (2, 1)
            # exactly-once handoff: replaying the ALREADY-SUMMED round 2
            # at the new owner dedupes — the sum must not move
            wb = connect(b.host, b.port)
            wb.settimeout(15)
            send_message(wb, Message(Op.PUSH, key=key, seq=10, flags=1,
                                     cmd=CMD_F32, version=2,
                                     payload=g2.tobytes()))
            assert recv_message(wb).op == Op.PUSH
            send_message(wb, Message(Op.PULL, key=key, seq=11, cmd=CMD_F32,
                                     version=2))
            pull = recv_message(wb)
            assert pull.op == Op.PULL and pull.version == 2
            np.testing.assert_array_equal(
                np.frombuffer(pull.payload, dtype=np.float32), g2
            )
            # ...and the version sequence CONTINUES in place: round 3
            send_message(wb, Message(Op.PUSH, key=key, seq=12, flags=1,
                                     cmd=CMD_F32, version=3,
                                     payload=g1.tobytes()))
            assert recv_message(wb).op == Op.PUSH
            send_message(wb, Message(Op.PULL, key=key, seq=13, cmd=CMD_F32,
                                     version=3))
            np.testing.assert_array_equal(
                np.frombuffer(recv_message(wb).payload, dtype=np.float32), g1
            )
            close_socket(wb)
        finally:
            close_socket(w)
            a.stop()
            b.stop()

    def test_fused_frame_redirects_whole_frame_once(self):
        a = _wire_server()
        a.rank = 0
        key = _key_owned_by(1, [0, 1])
        w = connect(a.host, a.port)
        w.settimeout(15)
        try:
            # key never held here + map homes it on rank 1 → redirect;
            # the FRAME gets ONE WrongOwner on its own seq (abort fence)
            a._adopt_book(_book(2, [0, 1], [(a.host, a.port),
                                            ("127.0.0.1", 1)]))
            g = np.ones(8, dtype=np.float32)
            frame = encode_fused_push([(key, CMD_F32, 1, g.tobytes())])
            send_message(w, Message(Op.FUSED, key=key, seq=44, flags=1,
                                    cmd=1, payload=frame))
            r = recv_message(w)
            assert r.op == Op.WRONG_OWNER and r.seq == 44
            assert decode_wrong_owner(r.payload)[1] == 1
        finally:
            close_socket(w)
            a.stop()

    def test_request_parks_until_migration_lands(self):
        b = _wire_server()
        b.rank = 1
        key = _key_owned_by(1, [0, 1])
        n = 8
        g = np.full(n, 2.0, dtype=np.float32)
        # b owns the key under the adopted map but has no state yet —
        # the previous owner (rank 0, still in the rank list) will ship
        b._adopt_book(_book(2, [0, 1], [("127.0.0.1", 1),
                                        (b.host, b.port)]))
        w = connect(b.host, b.port)
        peer = connect(b.host, b.port)  # plays the migrating old owner
        w.settimeout(15)
        peer.settimeout(15)
        try:
            send_message(w, Message(Op.PUSH, key=key, seq=1, flags=1,
                                    cmd=CMD_F32, version=2,
                                    payload=g.tobytes()))
            time.sleep(0.3)  # parked, NOT acked, NOT dropped
            store = np.arange(n, dtype=np.float32)
            meta = {"key": key, "epoch": 2, "dtype": "float32",
                    "store_version": 1, "recv_count": 0, "pushed_total": 1,
                    "push_seen": {"1": 1}, "init_done": {},
                    "compressor_kwargs": {},
                    "store_nbytes": store.nbytes, "accum_nbytes": 0}
            send_message(peer, Message(
                Op.MIGRATE_STATE, key=key, version=2,
                payload=encode_migrate_state(meta, store.tobytes()),
            ))
            assert recv_message(peer).status == 0  # installed + acked
            # the parked push wakes, sums round 2, acks
            assert recv_message(w).op == Op.PUSH
            send_message(w, Message(Op.PULL, key=key, seq=2, cmd=CMD_F32,
                                    version=2))
            np.testing.assert_array_equal(
                np.frombuffer(recv_message(w).payload, dtype=np.float32), g
            )
        finally:
            close_socket(w)
            close_socket(peer)
            b.stop()

    def test_evicted_previous_owner_does_not_park(self):
        b = _wire_server()
        b.rank = 1
        key = _key_owned_by(1, [0, 1])
        # epoch 2: {0, 1}; epoch 3: rank 0 CRASHED out — nothing will
        # ever migrate, so an uninitialized push must fail fast into the
        # worker's re-init path (dropped conn), not park to the deadline
        b._adopt_book(_book(2, [0, 1], [("127.0.0.1", 1),
                                        (b.host, b.port)]))
        b._adopt_book(_book(3, [1], [(b.host, b.port)]))
        w = connect(b.host, b.port)
        w.settimeout(5)
        try:
            send_message(w, Message(Op.PUSH, key=key, seq=1, flags=1,
                                    cmd=CMD_F32, version=1,
                                    payload=np.ones(4, np.float32).tobytes()))
            with pytest.raises((ConnectionError, OSError, TimeoutError)):
                msg = recv_message(w)
                raise AssertionError(f"expected dropped conn, got {msg.op}")
        finally:
            close_socket(w)
            b.stop()

    def test_live_key_refuses_inbound_migration_as_complete(self):
        # the stale-snapshot-resurrection guard: a key that is LIVE at
        # the receiver (installed by an earlier attempt whose ack was
        # lost, or re-created by the degraded fallback with restarted
        # version numbering) must refuse a shipment AS COMPLETE (status
        # 3 → the sender drops its copy) instead of installing a stale
        # snapshot whose higher store_version would serve old rounds
        srv = _wire_server()
        srv.rank = 0
        key = _key_owned_by(0, [0])
        n = 8
        live = np.full(n, 2.0, dtype=np.float32)
        w = connect(srv.host, srv.port)
        w.settimeout(10)
        try:
            srv._adopt_book(_book(3, [0], [(srv.host, srv.port)]))
            _init_key([(w, 1)], key, n)
            send_message(w, Message(Op.PUSH, key=key, seq=1, flags=1,
                                    cmd=CMD_F32, version=1,
                                    payload=live.tobytes()))
            assert recv_message(w).op == Op.PUSH
            stale = np.full(n, 9.0, dtype=np.float32)
            send_message(w, Message(
                Op.MIGRATE_STATE, key=key, version=2,
                payload=encode_migrate_state(
                    {"key": key, "epoch": 2, "dtype": "float32",
                     "store_version": 40, "store_nbytes": stale.nbytes,
                     "accum_nbytes": 0},
                    stale.tobytes(),
                ),
            ))
            r = recv_message(w)
            assert r.op == Op.MIGRATE_STATE and r.status == 3
            st = srv._keys[key]
            assert st.store_version == 1  # live state untouched
            np.testing.assert_array_equal(st.store, live)
        finally:
            close_socket(w)
            srv.stop()

    def test_migrate_refused_when_reshard_off(self):
        srv = _wire_server(reshard=False)
        w = connect(srv.host, srv.port)
        w.settimeout(10)
        try:
            send_message(w, Message(
                Op.MIGRATE_STATE, key=5, version=1,
                payload=encode_migrate_state(
                    {"key": 5, "store_nbytes": 0, "accum_nbytes": 0}
                ),
            ))
            r = recv_message(w)
            assert r.op == Op.MIGRATE_STATE and r.status != 0
        finally:
            close_socket(w)
            srv.stop()


class TestOptimizerStateMigration:
    """Server-side optimizer keys (docs/architecture.md "Server-side
    optimizer") migrate their rule WITH the store: slot tensors ride the
    MIGRATE_STATE frame as a raw tail after the accum blob, the step
    count and per-worker seed ledger ride the meta, and the trajectory
    at the new owner continues BITWISE — a reshard mid-run is invisible
    to the update math."""

    def test_reshard_moves_adam_slots_and_trajectory_stays_bitwise(self):
        from byteps_tpu.comm.transport import encode_server_opt_block
        from byteps_tpu.server.update_rules import canonical_hp, make_rule

        a = _wire_server()
        b = _wire_server()
        a.rank, b.rank = 0, 1
        key = _key_owned_by(1, [0, 1])  # re-homes to b under epoch 2
        n = 32
        hp = {"lr": 0.002}
        rng = np.random.default_rng(21)
        x0 = rng.standard_normal(n).astype(np.float32)
        # local reference: same rule class, same op order, 1 worker
        ref = make_rule("adam", hp, n, np.dtype(np.float32))
        ref_params = x0.copy()
        ref_t = 0

        def _ref_step(g):
            nonlocal ref_t
            ref_t += 1
            ref.apply(ref_params, g, 1, ref_t)
            return ref_params

        payload = (struct.pack("!QI", n, F32)
                   + struct.pack("!Bi", 2, -1)
                   + encode_server_opt_block("adam", canonical_hp(hp)))
        w = connect(a.host, a.port)
        w.settimeout(15)
        try:
            send_message(w, Message(Op.INIT, key=key, seq=1, flags=1,
                                    version=77, payload=payload))
            r = recv_message(w)
            assert r.op == Op.INIT and r.status == 0
            # seed round, then two Adam rounds at the OLD owner
            grads = {}
            for ver in (1, 2, 3):
                g = x0 if ver == 1 else rng.standard_normal(n).astype(
                    np.float32)
                grads[ver] = g
                send_message(w, Message(Op.PUSH, key=key, seq=ver + 1,
                                        flags=1, cmd=CMD_F32, version=ver,
                                        payload=g.tobytes()))
                assert recv_message(w).op == Op.PUSH
                if ver > 1:
                    _ref_step(g)
            send_message(w, Message(Op.PULL, key=key, seq=9, cmd=CMD_F32,
                                    version=3))
            np.testing.assert_array_equal(
                np.frombuffer(recv_message(w).payload, dtype=np.float32),
                ref_params)
            # the reshard: b adopts the key, a ships store + slots
            servers = [(a.host, a.port), (b.host, b.port)]
            book = _book(2, [0, 1], servers)
            b._adopt_book(dict(book, rank=1))
            a._adopt_book(dict(book, rank=0))
            _wait(lambda: key in b._keys
                  and b._keys[key].store is not None,
                  msg="migration never landed on the new owner")
            st = b._keys[key]
            assert st.opt_rule is not None
            assert st.opt_rule_name == "adam"
            assert st.opt_step == 3  # seed + 2 grad rounds published
            # slot tensors traveled BITWISE (m and v, in slot order)
            np.testing.assert_array_equal(st.opt_rule.m, ref.m)
            np.testing.assert_array_equal(st.opt_rule.v, ref.v)
            np.testing.assert_array_equal(st.store, ref_params)
            # the old owner tombstoned AND dropped its rule state
            assert a._keys[key].migrated_to == 1
            assert a._keys[key].opt_rule is None
            # the trajectory CONTINUES bitwise at the new owner —
            # including the bias-correction schedule (t keeps counting)
            wb = connect(b.host, b.port)
            wb.settimeout(15)
            for ver in (4, 5):
                g = rng.standard_normal(n).astype(np.float32)
                send_message(wb, Message(Op.PUSH, key=key, seq=ver + 10,
                                         flags=1, cmd=CMD_F32, version=ver,
                                         payload=g.tobytes()))
                assert recv_message(wb).op == Op.PUSH
                send_message(wb, Message(Op.PULL, key=key, seq=ver + 20,
                                         cmd=CMD_F32, version=ver))
                np.testing.assert_array_equal(
                    np.frombuffer(recv_message(wb).payload,
                                  dtype=np.float32),
                    _ref_step(g))
            # exactly-once across the handoff: replaying round 3 (summed
            # at the OLD owner, ledger traveled) cannot re-fire the rule
            step_before = b._keys[key].opt_step
            send_message(wb, Message(Op.PUSH, key=key, seq=99, flags=1,
                                     cmd=CMD_F32, version=3,
                                     payload=grads[3].tobytes()))
            assert recv_message(wb).op == Op.PUSH
            assert b._keys[key].opt_step == step_before
            np.testing.assert_array_equal(b._keys[key].store, ref_params)
            close_socket(wb)
        finally:
            close_socket(w)
            a.stop()
            b.stop()


class TestStaleMapChase:
    """Map-epoch skew: the worker-side WRONG_OWNER chase re-routes the
    RPC once the redirect's book lands (async push AND blocking init)."""

    def _cluster(self):
        cfg = Config(num_worker=1, num_server=2, elastic_reshard=True,
                     rpc_retries=4, rpc_deadline_s=2.0)
        a = PSServer(cfg)
        b = PSServer(cfg)
        a.start(register=False)
        b.start(register=False)
        a.rank, b.rank = 0, 1
        return cfg, a, b

    def _stale_client(self, cfg, a):
        from byteps_tpu.comm.ps_client import PSClient

        pc = PSClient(cfg)
        pc.rank = 0
        pc.num_servers = 1
        pc._servers = [pc._new_conn(a.host, a.port)]
        pc._server_addrs = [(a.host, a.port)]
        # the STALE world: one server, map epoch 1
        pc._install_routing(pc._servers, [0], OwnershipMap([0], epoch=1))
        return pc

    def test_async_push_chases_redirect_to_new_owner(self):
        cfg, a, b = self._cluster()
        key = _key_owned_by(1, [0, 1])
        n = 8
        g1 = np.arange(n, dtype=np.float32)
        g2 = np.full(n, 5.0, dtype=np.float32)
        pc = None
        w = connect(a.host, a.port)
        w.settimeout(15)
        try:
            _init_key([(w, 1)], key, n)
            send_message(w, Message(Op.PUSH, key=key, seq=1, flags=1,
                                    cmd=CMD_F32, version=1,
                                    payload=g1.tobytes()))
            assert recv_message(w).op == Op.PUSH
            # the cluster reshards: a ships the key to b, tombstones
            servers = [(a.host, a.port), (b.host, b.port)]
            a._adopt_book(dict(_book(2, [0, 1], servers)))
            b._adopt_book(dict(_book(2, [0, 1], servers)))
            _wait(lambda: key in b._keys and b._keys[key].store is not None,
                  msg="migration never landed")
            before = counters().get("wrong_owner_redirect")
            pc = self._stale_client(cfg, a)
            acked = threading.Event()
            pc.push(key, g2.tobytes(), F32, 2, lambda: acked.set(),
                    on_error=lambda: acked.set())

            def deliver_book():
                time.sleep(0.3)
                connb = pc._new_conn(b.host, b.port)
                pc._servers = [pc._servers[0], connb]
                pc._install_routing(pc._servers, [0, 1],
                                    OwnershipMap([0, 1], epoch=2))

            threading.Thread(target=deliver_book, daemon=True).start()
            assert acked.wait(15), "chase never resolved"
            assert counters().get("wrong_owner_redirect") > before
            # the resend landed on the NEW owner and advanced the round
            assert b._keys[key].store_version == 2
            np.testing.assert_array_equal(b._keys[key].store, g2)
        finally:
            if pc is not None:
                pc.close()
            close_socket(w)
            a.stop()
            b.stop()

    def test_blocking_init_chases_redirect(self):
        cfg, a, b = self._cluster()
        # a NEVER held this key; its map homes it on b → the blocking
        # init-push must chase and complete the barrier at b
        key = _key_owned_by(1, [0, 1])
        servers = [(a.host, a.port), (b.host, b.port)]
        a._adopt_book(dict(_book(2, [0, 1], servers)))
        b._adopt_book(dict(_book(2, [0, 1], servers)))
        pc = self._stale_client(cfg, a)
        try:
            done = threading.Event()
            err: list = []

            def do_init():
                try:
                    pc.init_tensor(key, 8, F32)
                except BaseException as e:  # noqa: BLE001
                    err.append(e)
                finally:
                    done.set()

            threading.Thread(target=do_init, daemon=True).start()
            time.sleep(0.3)
            connb = pc._new_conn(b.host, b.port)
            pc._servers = [pc._servers[0], connb]
            pc._install_routing(pc._servers, [0, 1],
                                OwnershipMap([0, 1], epoch=2))
            assert done.wait(20), "init chase never resolved"
            assert not err, f"init failed: {err}"
            assert key in b._keys and b._keys[key].store is not None
            assert key not in a._keys or a._keys[key].store is None
        finally:
            pc.close()
            a.stop()
            b.stop()


@pytest.mark.skipif(not have_native_parity_server(),
                    reason="native lib unavailable")
class TestNativeOwnership:
    """The C++ engine's ownership awareness: redirects for un-held keys
    the map homes elsewhere, held keys stay authoritative, MIGRATE_STATE
    refused cleanly (state migration is Python-engine-only)."""

    def _native(self):
        from byteps_tpu.server.server import NativePSServer

        srv = NativePSServer(Config(num_worker=1, num_server=1))
        srv.start(register=False)
        return srv

    def _install(self, srv, my_rank, epoch, ranks):
        import ctypes

        pts = HashRing(ranks, vnodes=64).points()
        hashes = (ctypes.c_uint64 * len(pts))(*[h for h, _ in pts])
        rks = (ctypes.c_int32 * len(pts))(*[r for _, r in pts])
        srv._lib.bps_native_server_set_ownership(
            srv._id, my_rank, epoch, len(pts), hashes, rks
        )

    def test_redirect_and_held_key_rules(self):
        srv = self._native()
        lib_ok = hasattr(srv._lib, "bps_native_server_set_ownership")
        if not lib_ok:
            srv.stop()
            pytest.skip("native lib predates the resharding plane")
        mine = _key_owned_by(0, [0, 1])
        theirs = _key_owned_by(1, [0, 1])
        n = 8
        g = np.arange(n, dtype=np.float32)
        w = connect(srv.host, srv.port)
        w.settimeout(15)
        try:
            # held BEFORE the map: stays authoritative afterwards
            _init_key([(w, 1)], theirs, n)
            self._install(srv, 0, 5, [0, 1])
            send_message(w, Message(Op.PUSH, key=theirs, seq=1, flags=1,
                                    cmd=CMD_F32, version=1,
                                    payload=g.tobytes()))
            assert recv_message(w).op == Op.PUSH  # pre-ship rule: served
            # owned key inits + serves normally under the map
            _init_key([(w, 1)], mine, n)
            send_message(w, Message(Op.PUSH, key=mine, seq=2, flags=1,
                                    cmd=CMD_F32, version=1,
                                    payload=g.tobytes()))
            assert recv_message(w).op == Op.PUSH
            # un-held key the map homes elsewhere: WRONG_OWNER w/ epoch
            other = _key_owned_by(1, [0, 1], start=2048)
            assert other != theirs
            send_message(w, Message(Op.PUSH, key=other, seq=3, flags=1,
                                    cmd=CMD_F32, version=1,
                                    payload=g.tobytes()))
            r = recv_message(w)
            assert r.op == Op.WRONG_OWNER and r.version == 5
            assert decode_wrong_owner(r.payload) == (5, 1)
            # ...same for INIT and PULL
            send_message(w, Message(Op.INIT, key=other, seq=4, flags=1,
                                    payload=struct.pack("!QI", n, F32)))
            assert recv_message(w).op == Op.WRONG_OWNER
            send_message(w, Message(Op.PULL, key=other, seq=5, cmd=CMD_F32,
                                    version=1))
            assert recv_message(w).op == Op.WRONG_OWNER
            # MIGRATE_STATE: clean unknown-op rejection, stream framed
            send_message(w, Message(
                Op.MIGRATE_STATE, key=other, seq=6,
                payload=encode_migrate_state(
                    {"key": other, "store_nbytes": 0, "accum_nbytes": 0}
                ),
            ))
            r = recv_message(w)
            assert r.op == Op.MIGRATE_STATE and r.status != 0
            # counter surfaced through the provider seam
            from byteps_tpu.native import native_server_counters

            assert native_server_counters(srv._id).get(
                "native_wrong_owner", 0
            ) >= 3
        finally:
            close_socket(w)
            srv.stop()

    def test_fused_member_redirect_aborts_frame(self):
        srv = self._native()
        if not hasattr(srv._lib, "bps_native_server_set_ownership"):
            srv.stop()
            pytest.skip("native lib predates the resharding plane")
        self._install(srv, 0, 7, [0, 1])
        key = _key_owned_by(1, [0, 1])
        w = connect(srv.host, srv.port)
        w.settimeout(15)
        try:
            g = np.ones(8, dtype=np.float32)
            frame = encode_fused_push([(key, CMD_F32, 1, g.tobytes())])
            send_message(w, Message(Op.FUSED, key=key, seq=31, flags=1,
                                    cmd=1, payload=frame))
            r = recv_message(w)
            assert r.op == Op.WRONG_OWNER and r.seq == 31
            assert decode_wrong_owner(r.payload) == (7, 1)
        finally:
            close_socket(w)
            srv.stop()


class TestGaugeDelta:
    """Gauges ride the heartbeat delta to the scheduler aggregate (the
    feed bps_top's ownership view renders)."""

    def test_gauge_values_ship_and_merge(self):
        from byteps_tpu.core.telemetry import MetricsRegistry

        src, agg = MetricsRegistry(), MetricsRegistry()
        src.gauge_set("server_owned_keys", 12, labels={"rank": "1"})
        d = src.delta_snapshot()
        assert {"n": "server_owned_keys", "l": [["rank", "1"]], "v": 12.0} \
            in d.get("g", [])
        agg.merge_delta(d, labels={"role": "server"})
        snap = agg.snapshot()
        assert snap["gauges"][
            'server_owned_keys{rank="1",role="server"}'
        ] == 12.0
        # unchanged → not re-shipped
        assert "g" not in (src.delta_snapshot() or {})
        # changed → ships again
        src.gauge_set("server_owned_keys", 9, labels={"rank": "1"})
        assert src.delta_snapshot()["g"][0]["v"] == 9.0

    def test_gauge_removal_ships_and_drops(self):
        from byteps_tpu.core.telemetry import MetricsRegistry

        src, agg = MetricsRegistry(), MetricsRegistry()
        src.gauge_set("server_owned_keys", 3, labels={"rank": "2"})
        agg.merge_delta(src.delta_snapshot())
        src.gauge_remove("server_owned_keys", labels={"rank": "2"})
        d = src.delta_snapshot()
        assert d.get("gr"), d
        agg.merge_delta(d)
        assert "server_owned_keys" not in str(agg.snapshot()["gauges"])

    def test_requeued_gauges_reship(self):
        from byteps_tpu.core.telemetry import MetricsRegistry

        src = MetricsRegistry()
        src.gauge_set("server_map_epoch", 4, labels={"rank": "0"})
        d = src.delta_snapshot()
        src.requeue_delta(d)  # the beat failed to send
        d2 = src.delta_snapshot()
        assert any(rec["n"] == "server_map_epoch" for rec in d2.get("g", []))

    def test_requeued_removal_does_not_kill_reappeared_series(self):
        # a removal marker from a FAILED beat must not delete a series
        # that reappeared before the next beat (the receiver applies "g"
        # then "gr" per payload, so a stale requeued "gr" would win over
        # the fresh value — e.g. a restarted server's owned-key gauge
        # silently vanishing from the aggregate)
        from byteps_tpu.core.telemetry import MetricsRegistry

        src, agg = MetricsRegistry(), MetricsRegistry()
        lbl = {"rank": "1"}
        src.gauge_set("server_owned_keys", 5, labels=lbl)
        agg.merge_delta(src.delta_snapshot())
        src.gauge_remove("server_owned_keys", labels=lbl)
        d = src.delta_snapshot()
        assert d.get("gr")
        src.requeue_delta(d)  # the removal beat failed to send
        src.gauge_set("server_owned_keys", 7, labels=lbl)  # reappears
        merged = src.delta_snapshot()
        agg.merge_delta(merged)
        snap = agg.snapshot()["gauges"]
        assert snap['server_owned_keys{rank="1"}'] == 7.0
        # the converse: a requeued VALUE must not resurrect a series
        # removed in the newer beat
        src.gauge_set("server_owned_keys", 8, labels=lbl)
        d = src.delta_snapshot()
        src.requeue_delta(d)
        src.gauge_remove("server_owned_keys", labels=lbl)
        agg.merge_delta(src.delta_snapshot())
        assert "server_owned_keys" not in str(agg.snapshot()["gauges"])

    def test_bps_top_renders_ownership(self):
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "bps_top", os.path.join(os.path.dirname(__file__), "..",
                                    "tools", "bps_top.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        cur = {
            ("byteps_cluster_map_epoch", ""): 3.0,
            ("byteps_server_owned_keys", '{rank="0"}'): 5.0,
            ("byteps_server_owned_keys", '{rank="1"}'): 7.0,
            ("byteps_server_map_epoch", '{rank="0"}'): 3.0,
            ("byteps_server_map_epoch", '{rank="1"}'): 2.0,  # lagging
        }
        out = mod.render("x", cur, {}, 1.0)
        assert "ownership map" in out and "epoch 3" in out
        assert "r0=5" in out and "r1=7*" in out  # laggard starred


class TestElasticReshardingE2E:
    """Live scale-up then scale-down against a real scheduler: bitwise
    pulls throughout, migration counters move, NO re-init generation
    bump, and the drained server stops itself."""

    def test_scale_up_then_drain_down(self, monkeypatch):
        from byteps_tpu.comm.ps_client import PSClient
        from byteps_tpu.comm.rendezvous import Scheduler

        monkeypatch.setenv("BYTEPS_ELASTIC_RESHARD", "1")
        cfg = Config(num_worker=1, num_server=2, elastic_reshard=True,
                     heartbeat_interval=0.1, rpc_retries=4,
                     rpc_deadline_s=2.0)
        sched = Scheduler(num_workers=1, num_servers=2, host="127.0.0.1")
        sched.start()
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(sched.port))
        monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
        cfg = Config(num_worker=1, num_server=2, elastic_reshard=True,
                     heartbeat_interval=0.1, rpc_retries=4,
                     rpc_deadline_s=2.0, ps_root_port=sched.port)
        fleet = [PSServer(Config(num_worker=1, num_server=2,
                                 elastic_reshard=True,
                                 heartbeat_interval=0.1,
                                 ps_root_port=sched.port))
                 for _ in range(2)]
        for s in fleet:
            threading.Thread(target=s.start, daemon=True).start()
        pc = PSClient(cfg)
        extra = None
        before_moved = counters().get("migration_keys_moved")
        try:
            pc.connect()
            keys = [k << 16 for k in range(8)]
            n = 16
            for k in keys:
                pc.init_tensor(k, n, F32)
            rng = np.random.default_rng(3)
            grads = {k: rng.standard_normal(n).astype(np.float32)
                     for k in keys}

            def round_trip(ver):
                for k in keys:
                    acked = threading.Event()
                    pc.push(k, grads[k].tobytes(), F32, ver,
                            lambda e=acked: e.set())
                    assert acked.wait(15), f"push {k} v{ver} hung"
                for k in keys:
                    got = threading.Event()
                    box: list = []

                    def cb(payload, b=box, e=got):
                        b.append(payload)
                        e.set()

                    pc.pull(k, ver, cb)
                    assert got.wait(15), f"pull {k} v{ver} hung"
                    np.testing.assert_array_equal(
                        np.frombuffer(box[0], dtype=np.float32), grads[k]
                    )

            round_trip(1)
            # ---- live scale-UP to 3 (reply parks until joiner arrives)
            rt = threading.Thread(
                target=pc.request_resize, kwargs={"num_servers": 3},
                daemon=True,
            )
            rt.start()
            _wait(lambda: sched.num_servers == 3, msg="resize not adopted")
            extra = PSServer(Config(num_worker=1, num_server=3,
                                    elastic_reshard=True,
                                    heartbeat_interval=0.1,
                                    ps_root_port=sched.port))
            threading.Thread(target=extra.start, daemon=True).start()
            rt.join(timeout=20)
            assert not rt.is_alive(), "scale-up resize hung"
            _wait(lambda: counters().get("migration_keys_moved")
                  > before_moved, msg="no keys migrated on scale-up")
            round_trip(2)  # bitwise through the migration window
            assert pc.server_generation == 0  # NO re-init barrier fired
            assert pc.map_epoch >= 2 and len(pc._servers) == 3
            # ---- live scale-DOWN back to 2: the joiner drains + stops
            pc.request_resize(num_servers=2)
            _wait(lambda: extra._stop.is_set(), timeout=15,
                  msg="drained server never stopped itself")
            round_trip(3)
            assert pc.server_generation == 0
            assert counters().get("migration_keys_received") > 0
        finally:
            pc.close()
            for s in fleet:
                s.stop()
            if extra is not None:
                extra.stop()
            sched.stop()
