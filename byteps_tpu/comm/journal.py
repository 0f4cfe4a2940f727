"""Round journal: bounded worker-side record of emitted push payloads.

The recovery plane's sender-side half (docs/robustness.md "healing
flow").  The engine records every data-plane push it emits — key, round
version, Cantor-encoded cmd, the exact wire payload, and whether the
bytes left inside a fused pack — so a worker that exhausted its RPC
retries against a *live* server can later replay exactly the rounds that
server never absorbed (Op.RESYNC_QUERY tells it which) and rejoin in
place, with no global re-init barrier and no peer participation.

Bounded two ways, because gradients are big and recovery only ever needs
the recent past (the per-key round gate admits at most one in-flight
round per key, so a live server can be behind by at most one round per
key — extra depth is slack for pipelined multi-key jobs):

- ``BYTEPS_JOURNAL_ROUNDS`` — rounds retained per key (depth);
- ``BYTEPS_JOURNAL_BYTES`` — total payload bytes across all keys; the
  globally OLDEST recorded rounds are evicted first when exceeded.

Generation safety: entries replay only into the round numbering they
were recorded under.  The engine clears a key's entries whenever it
re-runs that key's init barrier (elastic resize, engine restart, forced
re-init) — a stale entry replayed into a re-numbered generation would
corrupt sums, so the journal must never outlive the numbering.

The payload is kept by REFERENCE where nothing can write its buffer, and
copied where something can — read off the buffer itself (``_held``),
not a knob and not the caller's type: ``bytes`` (codec and row-sparse
payloads) is kept as is; a read-only ``memoryview`` whose exporters are
read-only all the way down and no larger than the view — the engine's
staging array of a jax job, ``np.asarray(slice)``, which jax hands out
read-only and nothing writes — is kept as that view (the view keeps
the staging array alive past its task); anything writable (a numpy
job's partition may alias the caller's array, which the caller may
overwrite after ``synchronize``) is copied.
Both bounds count referenced bytes like copied ones, so the journal pins
at most ``BYTEPS_JOURNAL_BYTES`` of staging memory — what it used to
allocate.  Counters ``journal_ref_bytes`` / ``journal_copy_bytes`` say
which way each recorded byte went; on the raw jax path the record costs
no pass over the partition.

Server-side optimizer keys (docs/architecture.md "Server-side
optimizer") change nothing here: the journal records gradient pushes
exactly as for SUM keys, and replay safety is the server's exactly-once
ledger — a replayed push dedupes BEFORE it can count toward a round
barrier, so the server's update rule fires exactly once per completed
round no matter how many journaled retransmits land.  The seed round's
parameter push is journaled like any other; replaying it is harmless
for the same reason (the ledger already marks it summed).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Union

import numpy as np


def _owned_unwritable(view: memoryview) -> bool:
    """Is ``view`` the whole of a buffer that nothing can write?  Walks
    the chain of exporters: every ``memoryview`` must be read-only and
    every ``ndarray`` non-writeable, down to the owner of the memory,
    and each must be exactly as large as ``view`` — a window into a
    larger buffer would pin all of it, past the journal's byte bound.
    An owner that exports a buffer itself must export it read-only
    (``bytearray``, ``array``, a writable ``mmap`` do not); one that
    exports none (the capsule behind a device-to-host copy) cannot be
    written from Python at all."""
    link = view
    while link is not None:
        if isinstance(link, memoryview):
            if not link.readonly or link.nbytes != view.nbytes:
                return False
            link = link.obj
        elif isinstance(link, np.ndarray):
            if link.flags.writeable or link.nbytes != view.nbytes:
                return False
            link = link.base
        else:
            try:
                owner = memoryview(link)
            except TypeError:
                return True
            return owner.readonly and owner.nbytes == view.nbytes
    return True


def _held(payload) -> Union[bytes, memoryview]:
    """What the journal keeps of ``payload``: the object itself when it
    is ``bytes`` or a flat byte view of memory nothing can write, a
    ``bytes`` copy otherwise."""
    if isinstance(payload, bytes):
        return payload
    if (isinstance(payload, memoryview) and payload.nbytes == len(payload)
            and _owned_unwritable(payload)):
        return payload
    return bytes(payload)


@dataclass(frozen=True)
class JournalEntry:
    """One journaled push: the exact bytes (and framing metadata) the
    engine emitted for (key, version).  ``payload`` is ``bytes`` or a
    read-only ``memoryview`` of unsigned bytes over memory nothing
    writes (module docstring); either goes to ``send_message`` as is."""

    version: int
    cmd: int
    payload: Union[bytes, memoryview]
    fused: bool = False  # emitted inside an Op.FUSED pack (replay is
    #                      per-key unfused — the server sums identically)


class RoundJournal:
    """Thread-safe bounded (rounds/bytes) per-key push journal."""

    def __init__(self, max_rounds: int, max_bytes: int) -> None:
        self.max_rounds = max(1, int(max_rounds))
        self.max_bytes = max(1, int(max_bytes))
        self._lock = threading.Lock()
        # key → {version: JournalEntry}, insertion-ordered per key
        self._entries: Dict[int, "OrderedDict[int, JournalEntry]"] = {}
        # global FIFO of (key, version) in record order — byte-cap
        # eviction drops the OLDEST round anywhere, not a random key's
        self._fifo: "OrderedDict[tuple, None]" = OrderedDict()
        self._bytes = 0
        self.evicted = 0  # rounds dropped by either bound (observability)

    def record(self, key: int, version: int, cmd: int, payload,
               fused: bool = False) -> None:
        """Record (or replace — an unfuse fallback re-emits the same
        round) one push's wire payload."""
        held = _held(payload)
        entry = JournalEntry(int(version), int(cmd), held, fused)
        from byteps_tpu.core.telemetry import counters

        counters().bump(
            "journal_ref_bytes" if held is payload else "journal_copy_bytes",
            len(held),
        )
        with self._lock:
            per = self._entries.get(key)
            if per is None:
                per = self._entries[key] = OrderedDict()
            old = per.pop(entry.version, None)
            if old is not None:
                self._bytes -= len(old.payload)
                self._fifo.pop((key, entry.version), None)
            per[entry.version] = entry
            self._fifo[(key, entry.version)] = None
            self._bytes += len(entry.payload)
            while len(per) > self.max_rounds:
                self._evict_locked(key, next(iter(per)))
            while self._bytes > self.max_bytes and self._fifo:
                ek, ev = next(iter(self._fifo))
                self._evict_locked(ek, ev)

    def _evict_locked(self, key: int, version: int) -> None:
        per = self._entries.get(key)
        if per is None:
            return
        dropped = per.pop(version, None)
        if dropped is not None:
            self._bytes -= len(dropped.payload)
            self.evicted += 1
        self._fifo.pop((key, version), None)
        if not per:
            del self._entries[key]

    def entries_after(self, key: int, version: int) -> List[JournalEntry]:
        """Journaled rounds of ``key`` NEWER than ``version`` (the
        server-reported absorbed watermark), oldest first — exactly what
        a resync replay must re-send."""
        with self._lock:
            per = self._entries.get(key)
            if per is None:
                return []
            return sorted(
                (e for e in per.values() if e.version > version),
                key=lambda e: e.version,
            )

    def keys(self) -> List[int]:
        with self._lock:
            return list(self._entries)

    def clear_key(self, key: int) -> None:
        """Drop a key's entries — called when its init barrier re-runs
        (round numbering restarts; stale entries must never replay)."""
        with self._lock:
            per = self._entries.pop(key, None)
            if not per:
                return
            for version, e in per.items():
                self._bytes -= len(e.payload)
                self._fifo.pop((key, version), None)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._fifo.clear()
            self._bytes = 0

    def stats(self) -> dict:
        with self._lock:
            return {
                "keys": len(self._entries),
                "rounds": len(self._fifo),
                "bytes": self._bytes,
                "evicted": self.evicted,
            }


#: process-global journal — the engine configures it at start (it owns
#: the config snapshot); the PS client's heal path reads it.  None =
#: journaling disabled (BYTEPS_JOURNAL_ROUNDS=0): resync still works but
#: can only heal give-ups whose pushes the server already absorbed.
_journal: Optional[RoundJournal] = None
_journal_lock = threading.Lock()


def configure_journal(max_rounds: int, max_bytes: int) -> Optional[RoundJournal]:
    """(Re)build the process journal from config; returns it (or None
    when disabled).  An engine restart reconfigures rather than appends —
    the old generation's entries must not survive into the new one."""
    global _journal
    with _journal_lock:
        _journal = (
            RoundJournal(max_rounds, max_bytes) if max_rounds > 0 else None
        )
        return _journal


def get_journal() -> Optional[RoundJournal]:
    with _journal_lock:
        return _journal
