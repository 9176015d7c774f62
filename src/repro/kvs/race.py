"""RACE hashing (Zuo et al., ATC'21) — the paper's flagship application.

Two deployments:

* ``RaceKVStore`` — disaggregated KV store over the simulated RDMA fabric:
  data lives in a storage node's registered memory; *compute-node clients
  are fully one-sided* (lookup = two bucket READs issued in ONE doorbell
  batch — exactly the Fig 7 example the paper uses to show why the
  low-level API matters vs LITE's one-READ-per-roundtrip). ``lookup_many``
  scales the same discipline across keys: a whole chunk's bucket READs in
  one ``qpush_batch`` doorbell with a single CQE.

* ``DeviceRaceTable`` — the TPU-native analogue used by the elastic
  runtime's metadata service: the bucket array lives in device HBM and
  batched lookups run through the Pallas race_lookup kernel.

Bucket layout in storage-node memory (binary, little-endian):
    bucket b, slot s at offset (b * NSLOT + s) * 16:
        [ fingerprint: u32 | vlen: u32 | value: 8B ]

* :class:`PoolRaceTable` — RACE's own layout on the device: an index of
  8-byte slots (fingerprint, length, pointer) over a pool of KV blocks
  that carry their keys, both in HBM, read by the two-level pool kernel.

* :class:`MeshPoolRaceTable` — RACE's pool over several memory nodes,
  one :class:`PoolRaceTable` each, laid out over a mesh of devices and
  read by one SPMD program that routes each key to its node.

* :class:`ShardClient` / :class:`ShardedDeviceRaceTable` — the
  shard-aware deployments: a store is ONE SHARD of the elastic dkv
  service (``src/repro/dkv``), addressed through the shard directory by
  geometry (rkeys + n_buckets + epoch) and fenced against live
  resharding by the state word in its control MR.

Bucket-version path (Storm-style optimistic concurrency): the store owns
a registered u64 **table version** that every mutation bumps. Client
inserts are fully one-sided — claim an empty slot with an 8-byte CAS on
its ``[fp|vlen]`` header word, WRITE the value, then publish by bumping
the version with **fetch-and-add** (``session.faa``). The FAA replaced
the old read-modify-write bump (READ version + WRITE version+1), which
lost increments whenever two clients interleaved — the CAS-loop
equivalent is kept as :meth:`RaceClient.bump_version_casloop` purely as
the equivalence/contention oracle for the tests. Readers use
:meth:`RaceClient.versioned_lookup`: version READ before and after the
bucket READs, retry when a concurrent insert moved it (torn-read guard).
"""

from __future__ import annotations

import dataclasses
import struct
from typing import Generator, List, Optional, Tuple

import numpy as np

from repro.core.fabric import MemoryRegion, Node
from repro.core.module import KRCoreModule
from repro.core.session import Session, connect

NSLOT = 8
SLOT_BYTES = 16
_SLOT = struct.Struct("<II8s")
#: vlen sentinel marking a slot claimed (CAS won) but not yet published —
#: readers treat it as absent until the final header lands
CLAIMED = 0xFFFFFFFF

# ------------------------------------------------ shard lifecycle (dkv)
#: byte offset of the shard-state word inside the control MR (the table
#: version u64 lives at offset 0 — its own cacheline)
STATE_OFF = 64
#: shard states, encoded with the shard epoch as ``(epoch << 8) | state``
#: in one u64 so a single 8B CAS can fence both at once
STATE_SERVING = 1
STATE_FROZEN = 2          # migration in progress: writes redirect
STATE_MOVED = 3           # shard left this node: reads+writes redirect


def state_word(state: int, epoch: int) -> int:
    """Encode (state, epoch) into the shard's u64 state word."""
    return ((epoch & 0xFFFFFFFF) << 8) | (state & 0xFF)


def parse_state(word: int) -> Tuple[int, int]:
    """Decode the state word -> (state, epoch)."""
    return word & 0xFF, (word >> 8) & 0xFFFFFFFF


def shard_of_key(key: int, n_shards: int) -> int:
    """key -> shard id (independent of the intra-shard bucket hashes so
    resharding never correlates with bucket placement)."""
    return ((key * 0x9E3779B1 + 0x85EBCA77) & 0xFFFFFFFF) % n_shards


def _key_array(keys) -> np.ndarray:
    """``keys`` as an integer array of up to 64 bits (an empty batch of
    any dtype as int64)."""
    keys = np.asarray(keys)
    if keys.dtype.kind not in "iu":
        if keys.size:
            raise TypeError(f"keys must be integers, not {keys.dtype}")
        keys = keys.astype(np.int64)
    return keys


def shard_of_keys(keys, n_shards: int) -> np.ndarray:
    """:func:`shard_of_key` of every key of ``keys``, (NQ,) i32."""
    u = _key_array(keys).astype(np.uint64)
    return (((u * 0x9E3779B1 + 0x85EBCA77) & 0xFFFFFFFF)
            % n_shards).astype(np.int32)


def _h1(k: int, nb: int) -> int:
    return (k * 2654435761 + 7) % nb

def _h2(k: int, nb: int) -> int:
    # the high bits of a second multiplicative hash: a linear function of
    # k taken mod nb, like _h1, would make the second choice a function of
    # the first, and two-choice placement would overflow at half load
    return (((k * 0x85EBCA6B + 0x9E3779B9) & 0xFFFFFFFF) >> 8) % nb

def _fp(k: int) -> int:
    fp = (k * 2246822519 + 1) & 0xFFFFFFFF
    return fp or 1


def prep_keys(keys, n_buckets: int) -> Tuple[np.ndarray, np.ndarray]:
    """The device tables' host hashing of ``keys``, an integer array or
    list: (31-bit fingerprints (NQ,) i32, never 0, and the two candidate
    buckets (NQ, 2) i32), element for element those of ``_fp``, ``_h1``
    and ``_h2``."""
    keys = _key_array(keys)
    # the keys mod 2^64: a hash that keeps only the low 32 bits of its
    # product is exact in wrapping uint64
    u = keys.astype(np.uint64)
    fps = np.maximum((u * 2246822519 + 1) & 0x7FFFFFFF, 1)
    # _h1 reduces the whole product, which 64 bits cannot hold: reduce
    # the factors first (floor mod, as Python's; exact for nb < 2^31)
    h1 = ((keys % n_buckets).astype(np.int64) * (2654435761 % n_buckets)
          + 7) % n_buckets
    h2 = (((u * 0x85EBCA6B + 0x9E3779B9) & 0xFFFFFFFF) >> 8) % n_buckets
    return (fps.astype(np.int32),
            np.stack([h1.astype(np.int32), h2.astype(np.int32)], axis=1))


def _two_choice(loads, b1: int, b2: int, nslot: int) -> int:
    """The bucket a new key goes to: the less loaded of its two (``b1`` on
    a tie), or the other when that one is full."""
    b = b1 if loads[b1] <= loads[b2] else b2
    if loads[b] >= nslot:
        b = b2 if b == b1 else b1
        if loads[b] >= nslot:
            raise RuntimeError("bucket overflow")
    return b


class RaceKVStore:
    """Server side: owns the bucket array and a control MR (table-version
    word + shard-state word) in registered memory.

    A store doubles as ONE SHARD of the elastic dkv service: ``shard_id``
    / ``epoch`` identify it in the shard directory, and the state word at
    ``STATE_OFF`` of the control MR drives the live-resharding fence
    (SERVING -> FROZEN -> MOVED, CAS-transitioned by the migrator)."""

    def __init__(self, node: Node, n_buckets: int = 4096,
                 shard_id: int = 0, epoch: int = 1,
                 state: int = STATE_SERVING):
        self.node = node
        self.n_buckets = n_buckets
        self.shard_id = shard_id
        self.epoch = epoch
        nbytes = n_buckets * NSLOT * SLOT_BYTES
        self.addr = node.alloc(nbytes)
        self.mr = node.reg_mr(self.addr, nbytes)
        # control MR: table version u64 at offset 0 (its own cacheline,
        # bumped by every mutation — server-local inserts and client FAA
        # publishes) and the shard-state word u64 at STATE_OFF
        self.version_addr = node.alloc(128)
        self.version_mr = node.reg_mr(self.version_addr, 128)
        self.set_state_local(state, epoch)
        if hasattr(node, "krcore"):
            node.krcore.validmr.add(self.mr)
            node.krcore.validmr.add(self.version_mr)

    @property
    def table_bytes(self) -> int:
        return self.n_buckets * NSLOT * SLOT_BYTES

    @property
    def version(self) -> int:
        raw = self.node.read_bytes(self.version_addr, 0, 8)
        return int(raw.view(np.uint64)[0])

    def set_version_local(self, v: int) -> None:
        buf = self.node.buffer(self.version_addr)
        buf[:8].view(np.uint64)[0] = v & 0xFFFFFFFFFFFFFFFF

    def read_state_word(self) -> int:
        raw = self.node.read_bytes(self.version_addr, STATE_OFF, 8)
        return int(raw.view(np.uint64)[0])

    def set_state_local(self, state: int, epoch: Optional[int] = None) -> None:
        if epoch is not None:
            self.epoch = epoch
        buf = self.node.buffer(self.version_addr)
        buf[STATE_OFF:STATE_OFF + 8].view(np.uint64)[0] = \
            state_word(state, self.epoch)

    def _bump_version_local(self) -> None:
        buf = self.node.buffer(self.version_addr)
        v = buf[:8].view(np.uint64)
        v[0] = (int(v[0]) + 1) & 0xFFFFFFFFFFFFFFFF

    # storage-side insert (clients of the *elastic* app do one-sided GETs;
    # inserts can also come from clients one-sided — RaceClient.insert)
    def insert(self, key: int, value: bytes) -> None:
        assert len(value) <= 8
        buf = self.node.buffer(self.addr)
        for b in (_h1(key, self.n_buckets), _h2(key, self.n_buckets)):
            for s in range(NSLOT):
                off = (b * NSLOT + s) * SLOT_BYTES
                fp, vlen, _ = _SLOT.unpack_from(buf, off)
                if fp == 0 or fp == _fp(key):
                    _SLOT.pack_into(buf, off, _fp(key), len(value),
                                    value.ljust(8, b"\0"))
                    self._bump_version_local()
                    return
        raise RuntimeError("RACE bucket overflow")

    def bucket_offsets(self, key: int) -> Tuple[int, int]:
        return (_h1(key, self.n_buckets) * NSLOT * SLOT_BYTES,
                _h2(key, self.n_buckets) * NSLOT * SLOT_BYTES)


class RaceClient:
    """Compute-node client: one-sided lookups through a KRCORE Session.

    ``lookup`` is the paper's Fig 7 example (2 READs, one doorbell — the
    session's op planner coalesces the two futures posted in one batch
    scope); ``lookup_many`` extends the same discipline across keys: ALL
    bucket READs of a chunk ride one planned doorbell (one syscall
    crossing, one CQE per chunk), then every key's slots are compared
    locally.
    """

    BUCKET_BYTES = NSLOT * SLOT_BYTES

    def __init__(self, module: KRCoreModule, store: RaceKVStore,
                 mr_bytes: int = 4096, session: Optional[Session] = None):
        self.module = module
        self.store = store
        self.mr_bytes = mr_bytes
        #: shard-aware deployments pass a shared per-node session so ONE
        #: connection serves every shard hosted on that memory node
        self.session: Optional[Session] = session
        self.qd: Optional[int] = session.qd if session is not None else None

    def bootstrap(self) -> Generator:
        """The elastic-scaling critical path: connect() = queue +
        qconnect + a scratch pool. With KRCORE this is microseconds; with
        Verbs it is ~16 ms. A no-op when a shared session was injected."""
        if self.session is None:
            self.session = yield from connect(self.module,
                                              self.store.node.name,
                                              pool_bytes=self.mr_bytes)
            self.qd = self.session.qd
        return self.qd

    def lookup(self, key: int) -> Generator:
        """Two bucket READs in ONE doorbell batch (Fig 7), then local
        slot compare. Returns value bytes or None."""
        off1, off2 = self.store.bucket_offsets(key)
        with self.session.batch():
            futs = [self.session.read(self.store.mr.rkey, off,
                                      self.BUCKET_BYTES)
                    for off in (off1, off2)]
        b1, b2 = yield from self.session.wait_all(futs)
        return self._scan_buckets(b1.tobytes() + b2.tobytes(), key)

    @staticmethod
    def _scan_buckets(raw: bytes, key: int) -> Optional[bytes]:
        """Local fingerprint compare over two gathered buckets. A slot
        still carrying the CLAIMED sentinel is an in-flight insert: not
        yet published, reported absent."""
        want = _fp(key)
        for s in range(2 * NSLOT):
            fp, vlen, val = _SLOT.unpack_from(raw, s * SLOT_BYTES)
            if fp == want and vlen != CLAIMED:
                return bytes(val[:vlen])
        return None

    # ----------------------------------------- bucket-version path (FAA)
    def read_version(self) -> Generator:
        """One-sided READ of the table version (u64)."""
        raw = yield from self.session.read(self.store.version_mr.rkey,
                                           0, 8).wait()
        return int(raw.view(np.uint64)[0])

    def bump_version(self, n: int = 1) -> Generator:
        """Publish a mutation: fetch-and-add the table version. ONE
        wait-free atomic — this replaced the read-modify-write bump
        (READ + WRITE of version+1) that dropped increments under
        concurrent writers. Returns the pre-bump version."""
        old = yield from self.session.faa(self.store.version_mr.rkey,
                                          0, n).wait()
        return old

    def bump_version_casloop(self, n: int = 1) -> Generator:
        """The retired read-modify-write idiom, made lossless the hard
        way: READ + CAS, retried until the CAS wins. Kept ONLY as the
        FAA-vs-CAS-loop equivalence/contention oracle for the tests —
        under contention it costs 2+ round trips where faa costs one.
        Returns the version this caller's increment applied to."""
        while True:
            cur = yield from self.read_version()
            old = yield from self.session.cas(
                self.store.version_mr.rkey, 0, compare=cur,
                swap=(cur + n) & 0xFFFFFFFFFFFFFFFF).wait()
            if old == cur:
                return cur

    def versioned_lookup(self, key: int, max_retries: int = 8) -> Generator:
        """Torn-read-guarded lookup: version READ rides the same doorbell
        as the two bucket READs, and a trailing version READ detects a
        concurrent mutation — retry instead of returning a half-written
        slot. Returns (value-or-None, version)."""
        off1, off2 = self.store.bucket_offsets(key)
        vkey = self.store.version_mr.rkey
        for _ in range(max_retries):
            with self.session.batch():
                vf = self.session.read(vkey, 0, 8)
                futs = [self.session.read(self.store.mr.rkey, off,
                                          self.BUCKET_BYTES)
                        for off in (off1, off2)]
            v0_raw, b1, b2 = yield from self.session.wait_all([vf] + futs)
            v0 = int(v0_raw.view(np.uint64)[0])
            v1_raw = yield from self.session.read(vkey, 0, 8).wait()
            v1 = int(v1_raw.view(np.uint64)[0])
            if v0 == v1:
                return (self._scan_buckets(b1.tobytes() + b2.tobytes(),
                                           key), v1)
        # writer storm: fall back to an unguarded read of the last state
        val = yield from self.lookup(key)
        ver = yield from self.read_version()
        return (val, ver)

    def insert(self, key: int, value: bytes) -> Generator:
        """Fully one-sided client insert (RACE's CAS-claim protocol):

        1. READ both buckets (one doorbell);
        2. CAS an empty slot's ``[fp|vlen]`` header from 0 to
           ``[fp|CLAIMED]`` — the sentinel keeps readers from consuming
           the slot before its value lands;
        3. WRITE the final ``[fp|vlen|value]`` slot image;
        4. publish with :meth:`bump_version` — ONE fetch-and-add, where
           the pre-FAA idiom was a racy READ + WRITE of version+1.

        A lost CAS (another client claimed first) re-reads and retries.
        Re-inserting an existing key updates its slot in place. Returns
        the slot's byte offset."""
        assert len(value) <= 8
        fp = _fp(key)
        final = _SLOT.pack(fp, len(value), value.ljust(8, b"\0"))
        claim = np.uint64(fp | (CLAIMED << 32))
        for _ in range(4 * NSLOT):
            off1, off2 = self.store.bucket_offsets(key)
            with self.session.batch():
                futs = [self.session.read(self.store.mr.rkey, off,
                                          self.BUCKET_BYTES)
                        for off in (off1, off2)]
            b1, b2 = yield from self.session.wait_all(futs)
            raw = b1.tobytes() + b2.tobytes()

            def slot_off(s: int) -> int:
                return (off1 if s < NSLOT else off2) \
                    + (s % NSLOT) * SLOT_BYTES

            for s in range(2 * NSLOT):       # update-in-place on re-insert
                sfp, vlen, _val = _SLOT.unpack_from(raw, s * SLOT_BYTES)
                if sfp == fp and vlen != CLAIMED:
                    yield from self.session.write(
                        self.store.mr.rkey, slot_off(s), final).wait()
                    yield from self.bump_version()
                    return slot_off(s)
            for s in range(2 * NSLOT):
                sfp, _vlen, _val = _SLOT.unpack_from(raw, s * SLOT_BYTES)
                if sfp != 0:
                    continue
                old = yield from self.session.cas(
                    self.store.mr.rkey, slot_off(s), compare=0,
                    swap=int(claim)).wait()
                if old != 0:
                    break                    # lost the claim: re-read
                yield from self.session.write(
                    self.store.mr.rkey, slot_off(s), final).wait()
                yield from self.bump_version()
                return slot_off(s)
        raise RuntimeError("RACE insert: no claimable slot")

    def lookup_many(self, keys: List[int]) -> Generator:
        """Batched lookup: both bucket READs of EVERY key in a chunk ride
        one planned doorbell (one syscall + one CQE per chunk vs two
        syscalls + a CQE per key). Returns values aligned with ``keys``."""
        results: List[Optional[bytes]] = [None] * len(keys)
        per_key = 2 * self.BUCKET_BYTES
        cap = max(self.mr_bytes // per_key, 1)
        for base in range(0, len(keys), cap):
            chunk = keys[base:base + cap]
            with self.session.batch():
                futs = []
                for key in chunk:
                    for off in self.store.bucket_offsets(key):
                        futs.append(self.session.read(
                            self.store.mr.rkey, off, self.BUCKET_BYTES))
            bufs = yield from self.session.wait_all(futs)
            for j, key in enumerate(chunk):
                results[base + j] = self._scan_buckets(
                    bufs[2 * j].tobytes() + bufs[2 * j + 1].tobytes(), key)
        return results


class ShardClient:
    """Shard-aware RACE client: the directory-driven sibling of
    :class:`RaceClient`. Bound to one shard through its directory
    geometry (rkeys + n_buckets + epoch) instead of a server-object ref,
    and riding a SHARED per-memory-node session, so an elastic worker
    holds one connection per node no matter how many shards live there
    (multi-table, single session).

    Both ops are **fenced** against live resharding: the shard-state word
    rides the same doorbell as the data READs, and a state that is not
    ``SERVING`` at this client's epoch makes the op return
    ``("redirect", ...)`` instead of stale data — the caller re-resolves
    the directory and retries at the new owner. Inserts additionally
    re-check the state AFTER the FAA publish: an insert racing the
    migration freeze may not have made the copy, so it reports redirect
    and is re-applied (idempotently) at the destination.
    """

    BUCKET_BYTES = NSLOT * SLOT_BYTES

    def __init__(self, session: Session, n_buckets: int, table_rkey: int,
                 ctl_rkey: int, epoch: int):
        self.session = session
        self.n_buckets = n_buckets
        self.table_rkey = table_rkey
        self.ctl_rkey = ctl_rkey
        self.epoch = epoch

    def bucket_offsets(self, key: int) -> Tuple[int, int]:
        return (_h1(key, self.n_buckets) * NSLOT * SLOT_BYTES,
                _h2(key, self.n_buckets) * NSLOT * SLOT_BYTES)

    def _serving(self, word: int) -> bool:
        st, ep = parse_state(word)
        return st == STATE_SERVING and ep == self.epoch

    def read_state(self) -> Generator:
        raw = yield from self.session.read(self.ctl_rkey, STATE_OFF,
                                           8).wait()
        return int(raw.view(np.uint64)[0])

    def lookup_fenced(self, key: int, max_retries: int = 16) -> Generator:
        """Torn-read-guarded, migration-fenced lookup.

        One doorbell carries [state, version, bucket1, bucket2] READs; a
        trailing version READ detects a concurrent mutation (retry) and
        the state word detects a migration (redirect). Returns
        ``("ok", value-or-None)`` or ``("redirect", None)``.
        """
        off1, off2 = self.bucket_offsets(key)
        for _ in range(max_retries):
            with self.session.batch():
                sf = self.session.read(self.ctl_rkey, STATE_OFF, 8)
                vf = self.session.read(self.ctl_rkey, 0, 8)
                futs = [self.session.read(self.table_rkey, off,
                                          self.BUCKET_BYTES)
                        for off in (off1, off2)]
            s_raw, v0_raw, b1, b2 = yield from self.session.wait_all(
                [sf, vf] + futs)
            if not self._serving(int(s_raw.view(np.uint64)[0])):
                return ("redirect", None)
            v0 = int(v0_raw.view(np.uint64)[0])
            v1_raw = yield from self.session.read(self.ctl_rkey, 0,
                                                  8).wait()
            if v0 == int(v1_raw.view(np.uint64)[0]):
                return ("ok", RaceClient._scan_buckets(
                    b1.tobytes() + b2.tobytes(), key))
        raise RuntimeError(
            f"lookup_fenced: version storm on shard (epoch {self.epoch}) "
            f"— {max_retries} retries exhausted")

    def insert_fenced(self, key: int, value: bytes) -> Generator:
        """Fully one-sided fenced insert (CAS-claim + WRITE + FAA publish
        + state re-check). Returns ``("ok", slot_off)`` or
        ``("redirect", None)`` when the shard froze/moved under us —
        the caller re-resolves and re-applies (idempotent)."""
        assert len(value) <= 8
        fp = _fp(key)
        final = _SLOT.pack(fp, len(value), value.ljust(8, b"\0"))
        claim = np.uint64(fp | (CLAIMED << 32))
        off1, off2 = self.bucket_offsets(key)

        def slot_off(s: int) -> int:
            return (off1 if s < NSLOT else off2) + (s % NSLOT) * SLOT_BYTES

        for _ in range(4 * NSLOT):
            with self.session.batch():
                sf = self.session.read(self.ctl_rkey, STATE_OFF, 8)
                futs = [self.session.read(self.table_rkey, off,
                                          self.BUCKET_BYTES)
                        for off in (off1, off2)]
            s_raw, b1, b2 = yield from self.session.wait_all([sf] + futs)
            if not self._serving(int(s_raw.view(np.uint64)[0])):
                return ("redirect", None)
            raw = b1.tobytes() + b2.tobytes()
            target: Optional[int] = None
            for s in range(2 * NSLOT):      # update-in-place on re-insert
                sfp, vlen, _v = _SLOT.unpack_from(raw, s * SLOT_BYTES)
                if sfp == fp and vlen != CLAIMED:
                    target = slot_off(s)
                    break
            if target is None:
                for s in range(2 * NSLOT):
                    sfp, _vl, _v = _SLOT.unpack_from(raw, s * SLOT_BYTES)
                    if sfp != 0:
                        continue
                    old = yield from self.session.cas(
                        self.table_rkey, slot_off(s), compare=0,
                        swap=int(claim)).wait()
                    if old != 0:
                        break               # lost the claim: re-read
                    target = slot_off(s)
                    break
                if target is None:
                    continue
            yield from self.session.write(self.table_rkey, target,
                                          final).wait()
            yield from self.session.faa(self.ctl_rkey, 0, 1).wait()
            # migration fence: a freeze between our bucket READ and the
            # FAA means the copy may have missed this write — report
            # redirect so the caller re-applies at the new owner
            post = yield from self.read_state()
            if not self._serving(post):
                return ("redirect", None)
            return ("ok", target)
        raise RuntimeError("insert_fenced: no claimable slot")


@dataclasses.dataclass
class LookupStats:
    """Totals over every ``lookup_batch`` a device RACE table served,
    counted by the spans of :mod:`repro.obs` as they open."""
    calls: int = 0
    #: keys asked for
    keys: int = 0
    #: bytes copied from the host to the device: table ships and query
    #: operands (sharded: the grouped queries and each key's output slot)
    h2d_bytes: int = 0
    #: pool layout: device buffers the host-to-device puts created, the
    #: device shards of each array put (a lookup's packed queries: one a
    #: device; a ship: one a host array)
    h2d_buffers: int = 0
    #: whole-table copies to the device: one on the first lookup and on
    #: each lookup that follows an insert, none while the device copy is
    #: current
    table_ships: int = 0
    #: query slots the sharded kernel ran: shards x padded queries a shard
    slots: int = 0
    #: of those, padding that holds no query
    padded_slots: int = 0
    #: pool layout: KV blocks the kernel fetched (true and false
    #: fingerprint matches), summed on the device; ``int()`` reads it
    blocks: int = 0


class _Resident:
    """A device copy of a table's host arrays (``tables()``), kept with
    the ``version`` of the host arrays it holds: a lookup ships the
    tables only when an insert has moved ``version`` since the last
    ship."""

    _dev: Optional[Tuple] = None
    _dev_version = -1
    #: count ``stats.h2d_buffers`` (the pool tables)
    counts_buffers = False

    def _ship_if_stale(self) -> Tuple:
        """The device copy of ``tables()``; shipped first, in a
        ``race.to_device`` span of its own counting ``table_ships``, if
        ``version`` has moved since the last ship."""
        version = self.version
        if self._dev_version != version:
            import jax

            from repro import obs
            self._dev = None        # free the stale copy before the ship
            host = self.tables()
            leaves = jax.tree.leaves(host)
            counts = {"table_ships": 1,
                      "h2d_bytes": sum(a.nbytes for a in leaves)}
            if self.counts_buffers:
                # each host array goes whole to one device
                counts["h2d_buffers"] = len(leaves)
            with obs.span("race.to_device", **counts):
                self._dev = jax.block_until_ready(self._put(host))
            self._dev_version = version
        return self._dev

    def _put(self, host) -> Tuple:
        """The device copy of the host tables ``host``: on the default
        device."""
        import jax
        return jax.device_put(host)


class DeviceRaceTable(_Resident):
    """TPU-resident RACE table: batched lookups via the Pallas kernel.

    Residency: the host arrays (``tables()``) are the table; ``insert``
    writes them and bumps ``version``. The device holds one copy of them
    between lookups. A lookup ships the whole table only when ``version``
    has moved since the last ship (the first lookup, or the first after
    any insert); otherwise only the query operands cross to the device.

    Each ``lookup_batch`` is one :func:`repro.obs.request` counting into
    ``stats``, with the spans ``race.prep`` (hashing), ``race.to_device``
    (the table, only when it ships, with ``table_ships``),
    ``race.to_device`` (the query operands, until they are on the
    device) and ``race.kernel`` (dispatch of the jitted lookup,
    ``variant="scalar"``; the answers stay on the device).

    ``fp`` and ``val`` are host arrays to hold the table in (a sharded
    table's shards are views into its stacked arrays); fresh zeros if
    not given."""

    def __init__(self, n_buckets: int = 1024, nslot: int = 8,
                 vdim: int = 128, *, fp: Optional[np.ndarray] = None,
                 val: Optional[np.ndarray] = None):
        self.n_buckets = n_buckets
        self.nslot = nslot
        self.vdim = vdim
        self._fp = (np.zeros((n_buckets, nslot), np.int32)
                    if fp is None else fp)
        self._val = (np.zeros((n_buckets, nslot, vdim), np.float32)
                     if val is None else val)
        self._loads = np.zeros(n_buckets, np.int32)
        #: inserts so far: the version of the host tables
        self.version = 0
        self.stats = LookupStats()

    def insert(self, key: int, value: np.ndarray) -> None:
        b = _two_choice(self._loads, _h1(key, self.n_buckets),
                        _h2(key, self.n_buckets), self.nslot)
        s = self._loads[b]
        self._fp[b, s] = np.int32(_fp(key) & 0x7FFFFFFF) or 1
        self._val[b, s, :len(value)] = value
        self._loads[b] += 1
        self.version += 1

    def prep(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(fingerprints (NQ,) i32, candidate bucket rows (NQ, 2) i32) of
        ``keys`` — the kernel's query operands."""
        return prep_keys(keys, self.n_buckets)

    def tables(self) -> Tuple[np.ndarray, np.ndarray]:
        """(fp (NB, NSLOT) i32, val (NB, NSLOT, VDIM) f32) host tables."""
        return self._fp, self._val

    def lookup_batch(self, keys: np.ndarray, impl: str = "pallas"):
        import jax

        from repro import obs
        from repro.kernels.race_lookup.ops import race_lookup
        with obs.request(self.stats):
            with obs.span("race.prep", keys=len(keys)):
                fps, bidx = self.prep(keys)
            fp_table, val_table = self._ship_if_stale()
            with obs.span("race.to_device",
                          h2d_bytes=fps.nbytes + bidx.nbytes):
                fps, bidx = jax.block_until_ready(
                    jax.device_put((fps, bidx)))
            with obs.span("race.kernel",
                          variant="scalar" if impl == "pallas" else impl):
                return race_lookup(fp_table, val_table, fps, bidx,
                                   impl=impl)


class ShardedDeviceRaceTable(_Resident):
    """Multi-shard TPU-resident RACE table: the device analogue of the
    dkv shard map. Per-shard tables share one geometry and batched
    lookups run through the SHARDED Pallas kernel
    (``race_lookup_sharded``): the grid gains a shard dimension and only
    ONE shard's table is resident per grid step, so one shard must fit
    the kernel's VMEM budget (``SHARD_VMEM_BUDGET_BYTES``); a larger shard
    geometry raises ``ValueError``.

    Residency: the host tables are held stacked from construction, (NS,
    NB, NSLOT) and (NS, NB, NSLOT, VDIM), and each shard's tables are
    views into them, so an insert through this table or through a
    shard's own ``insert`` writes the stacked arrays and moves
    ``version``. The device holds one copy of the stacked tables between
    lookups, shipped whole only when ``version`` has moved since the last
    ship; otherwise only the grouped queries cross to the device, and
    the answers, put in the keys' order there, are returned as device
    arrays.

    Each ``lookup_batch`` is one :func:`repro.obs.request` counting into
    ``stats``: ``race.prep`` (hashing and shard routing), then
    ``race.to_device`` (the stacked tables, only when they ship, with
    ``table_ships``) and ``race.stack`` (the resident stacked tables
    handed to the kernel path) here, then those of the sharded kernel
    path (``race_lookup_pallas_sharded``)."""

    def __init__(self, n_shards: int = 4, n_buckets: int = 256,
                 nslot: int = 8, vdim: int = 128):
        from repro.kernels.race_lookup.race_lookup import (
            SHARD_VMEM_BUDGET_BYTES, table_vmem_bytes)
        shard_bytes = table_vmem_bytes((n_buckets, nslot),
                                       (n_buckets, nslot, vdim))
        if shard_bytes > SHARD_VMEM_BUDGET_BYTES:
            raise ValueError(
                f"a shard of {n_buckets} x {nslot} x {vdim} takes "
                f"{shard_bytes} B of VMEM, over the sharded kernel's "
                f"{SHARD_VMEM_BUDGET_BYTES} B")
        self.n_shards = n_shards
        self.n_buckets = n_buckets
        self.nslot = nslot
        self.vdim = vdim
        self._fp = np.zeros((n_shards, n_buckets, nslot), np.int32)
        self._val = np.zeros((n_shards, n_buckets, nslot, vdim), np.float32)
        self.shards = [DeviceRaceTable(n_buckets, nslot, vdim,
                                       fp=self._fp[i], val=self._val[i])
                       for i in range(n_shards)]
        self.stats = LookupStats()

    @property
    def version(self) -> int:
        """Inserts so far, over every shard."""
        return sum(s.version for s in self.shards)

    def shard_of(self, key: int) -> int:
        return shard_of_key(int(key), self.n_shards)

    def insert(self, key: int, value: np.ndarray) -> None:
        self.shards[self.shard_of(key)].insert(key, value)

    def prep(self, keys: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(fingerprints, intra-shard bucket rows (NQ, 2), owning shard
        (NQ,)) of ``keys``; every shard shares one bucket geometry."""
        fps, bidx = self.shards[0].prep(keys)
        return fps, bidx, shard_of_keys(keys, self.n_shards)

    def tables(self) -> Tuple[np.ndarray, np.ndarray]:
        """The per-shard host tables, stacked (no copy): (NS, NB, NSLOT)
        and (NS, NB, NSLOT, VDIM)."""
        return self._fp, self._val

    def lookup_batch(self, keys: np.ndarray, impl: str = "pallas"):
        from repro import obs
        from repro.kernels.race_lookup.ops import race_lookup_sharded
        with obs.request(self.stats):
            with obs.span("race.prep", keys=len(keys)):
                fps, bidx, sidx = self.prep(keys)
            self._ship_if_stale()
            with obs.span("race.stack"):
                fp_tables, val_tables = self._dev
            return race_lookup_sharded(fp_tables, val_tables, fps, bidx,
                                       sidx, impl=impl)


#: 32-bit words of one row of the pool layout's index and key arrays: the
#: kernel's DMAs move whole 128-lane rows
POOL_LANES = 128
#: RACE's slot length field counts the KV block in 64-byte units
BLOCK_UNIT = 64


def fp8(fps) -> np.ndarray:
    """The 8-bit fingerprint of RACE's slot, from the 31-bit fingerprints
    of :func:`prep_keys`: their top byte, never 0."""
    return np.maximum((np.asarray(fps, np.int32) >> 23) & 0xFF, 1)


class PoolRaceTable(_Resident):
    """RACE hashing's own layout on the device: an index of 8-byte slots
    and a pool of KV blocks, each block carrying its key.

    A slot is two int32 words, ``hi = fp8 << 24 | len << 16 | ptr >> 32``
    and ``lo = ptr & 0xFFFFFFFF``: the 8-bit fingerprint (never 0, so an
    occupied slot never reads as the all-zero empty slot), the block's
    length in 64-byte units and a 48-bit pointer, here a row of the pool.
    The pool holds ``capacity`` blocks: the record, ``(capacity, 1,
    VDIM)`` f32, and its key, the block's header, in ``keys`` aligned
    with the rows. Keys are below 2^31 and inserted once.

    The host arrays are held in the shapes the kernel reads them in
    (``tables()``): index and keys as rows of 128 words (eight 8-slot
    buckets a row). ``insert`` appends the block, then writes the slot,
    as RACE writes the block before it publishes the slot, with
    :class:`DeviceRaceTable`'s two-choice placement, and bumps
    ``version``; ``insert_many`` places a batch exactly as that many
    inserts would, with one bump. The device holds one copy between
    lookups, shipped whole when ``version`` has moved.

    Each ``lookup_batch`` is one :func:`repro.obs.request` counting into
    ``stats``, with the spans of :class:`DeviceRaceTable` in the same
    order: ``race.prep``, ``race.to_device`` (the tables, only when they
    ship, with ``table_ships``; then the query operands, packed into one
    array by :func:`~repro.kernels.race_lookup.ops.pack_queries` and put
    as one device buffer, ``h2d_buffers``) and ``race.kernel``
    (``variant="pool"``: :func:`~repro.kernels.race_lookup.ops.
    pool_lookup`, which unpacks them on the device). ``stats.blocks``
    sums on the device the KV blocks each lookup fetched, so no call
    waits for it."""

    counts_buffers = True

    def __init__(self, n_buckets: int = 1024, nslot: int = 8,
                 vdim: int = 128, capacity: int = 4096):
        words = 2 * nslot
        if POOL_LANES % words:
            raise ValueError(f"{nslot}-slot buckets do not tile a "
                             f"{POOL_LANES}-word row")
        if capacity >= 2 ** 31:
            raise ValueError("pool rows are int32")
        import jax.numpy as jnp
        self.n_buckets = n_buckets
        self.nslot = nslot
        self.vdim = vdim
        self.capacity = capacity
        self._index = np.zeros((-(-n_buckets * words // POOL_LANES), 1,
                                POOL_LANES), np.int32)
        #: (NB, NSLOT, 2) view of the index: each slot's (hi, lo)
        self._slots = self._index.reshape(-1)[:n_buckets * words].reshape(
            n_buckets, nslot, 2)
        self._keys = np.zeros((-(-capacity // POOL_LANES), 1, POOL_LANES),
                              np.int32)
        self._pool = np.zeros((capacity, 1, vdim), np.float32)
        #: keys per bucket, a list: the bulk insert's loop reads it per key
        self._loads = [0] * n_buckets
        #: pool rows in use: the next block goes to row ``size``
        self.size = 0
        self.version = 0
        self.stats = LookupStats()
        # a device zero, so that every lookup adds two device int32s: one
        # compiled add, made on the first lookup
        self.stats.blocks = jnp.zeros((), jnp.int32)
        self._len = min(-(-(4 + 4 * vdim) // BLOCK_UNIT), 0xFF)

    def _hi(self, fps) -> np.ndarray:
        """The slots' high words for keys of these 31-bit fingerprints:
        ``fp8 << 24 | len << 16`` (the pointer's top 16 bits are 0)."""
        hi = (fp8(fps).astype(np.uint32) << 24) | (self._len << 16)
        return hi.view(np.int32)

    def insert(self, key: int, value: np.ndarray) -> None:
        self.insert_many(np.array([key]), np.asarray(value)[None])

    def insert_many(self, keys: np.ndarray, values: np.ndarray) -> None:
        """Insert ``keys`` with their ``values`` rows in order, each as
        :class:`DeviceRaceTable` places it given the keys before it, with
        one ``version`` bump. Nothing is written if a key would overflow
        its buckets."""
        keys = np.asarray(keys)
        n = len(keys)
        if n and not (0 <= keys.min() and keys.max() < 2 ** 31):
            raise ValueError("keys are int32")
        if self.size + n > self.capacity:
            raise RuntimeError("pool full")
        fps, bidx = prep_keys(keys, self.n_buckets)
        loads = self._loads
        bucket, slot = [], []
        try:
            for b1, b2 in bidx.tolist():
                b = _two_choice(loads, b1, b2, self.nslot)
                bucket.append(b)
                slot.append(loads[b])
                loads[b] += 1
        except RuntimeError:
            for b in bucket:
                loads[b] -= 1
            raise
        rows = np.arange(self.size, self.size + n)
        self._pool[self.size:self.size + n, 0, :np.shape(values)[-1]] = values
        self._keys.reshape(-1)[rows] = keys
        self._slots[bucket, slot] = np.stack([self._hi(fps), rows], axis=1)
        self.size += n
        self.version += 1

    def prep(self, keys: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(keys (NQ,) i32, 8-bit fingerprints (NQ,) i32, candidate
        buckets (NQ, 2) i32) — the kernel's query operands."""
        fps, bidx = prep_keys(keys, self.n_buckets)
        return np.asarray(keys).astype(np.int32), fp8(fps), bidx

    def tables(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(index (ceil(NB * 2 * NSLOT / 128), 1, 128) i32, keys
        (ceil(CAP / 128), 1, 128) i32, pool (CAP, 1, VDIM) f32) host
        arrays."""
        return self._index, self._keys, self._pool

    def lookup_batch(self, keys: np.ndarray, impl: str = "pallas"):
        import jax

        from repro import obs
        from repro.kernels.race_lookup import ops
        with obs.request(self.stats):
            with obs.span("race.prep", keys=len(keys)):
                operands = self.prep(keys)
            index, bkeys, pool = self._ship_if_stale()
            with obs.span("race.to_device") as add:
                queries = ops.pack_queries(*operands)
                add(h2d_bytes=queries.nbytes)
                queries = jax.block_until_ready(jax.device_put(queries))
                add(h2d_buffers=len(queries.sharding.device_set))
            with obs.span("race.kernel",
                          variant="pool" if impl == "pallas" else impl):
                values, found, blocks = ops.pool_lookup(
                    index, bkeys, pool, queries, nq=len(operands[0]),
                    nslot=self.nslot, impl=impl)
                self.stats.blocks = self.stats.blocks + blocks
            return values, found


class MeshPoolRaceTable(_Resident):
    """RACE's pool over ``n_nodes`` memory nodes, laid out over a mesh of
    devices: one :class:`PoolRaceTable` of one geometry per node, each
    holding the keys that the dkv shard map (:func:`shard_of_keys`)
    sends it. ``capacity`` is per node.

    Placement: the devices form a 1-D mesh, ``mesh``, along
    ``NODE_AXIS``; their number divides ``n_nodes``, and device ``d``
    holds nodes ``d * n_nodes / D`` up to the next device's first, whole
    (one node per device where there are as many devices as nodes).

    Residency: the nodes' host arrays are the table; ``insert`` and
    ``insert_many`` route each key to its node, where it is placed as
    that node's own ``insert_many`` places it, so each node's index,
    keys and pool are exactly those of a :class:`PoolRaceTable` loaded
    with the keys routed to it, in their order. ``version`` sums the
    nodes'. The devices hold one copy between lookups: node ``i``'s
    ``tables()`` on its device, assembled into index, keys and pool
    arrays with a leading node axis sharded over the mesh, shipped whole
    (no host stacking) only when ``version`` has moved.

    Each ``lookup_batch`` is one :func:`repro.obs.request` counting into
    ``stats``, in :class:`PoolRaceTable`'s span order: ``race.prep``
    (hashing and node routing), ``race.to_device`` (the tables, only
    when they ship, with ``table_ships``), ``race.group`` (the queries
    grouped and padded per node to the pool kernel's tile, as the
    sharded kernel's are; ``slots``, ``padded_slots``, ``qcap``), then
    :meth:`lookup_grouped`'s ``race.to_device`` (the grouped queries and
    each key's slot in the padded answers, packed into one array by
    :func:`~repro.kernels.race_lookup.ops.pack_queries` and put once,
    sharded on the mesh: one device buffer a device, ``h2d_buffers``)
    and ``race.kernel`` (``variant="pool_mesh"``: dispatch of one SPMD
    program, :func:`~repro.kernels.race_lookup.ops.pool_mesh_lookup`,
    that unpacks the queries on each device, runs the pool kernel per
    node and gathers the answers into the keys' order across the
    devices). Nothing is replicated from the host. The answers come back
    as device arrays; ``stats.blocks`` sums on the devices the KV blocks
    fetched on every node."""

    counts_buffers = True

    def __init__(self, n_nodes: int = 4, n_buckets: int = 1024,
                 nslot: int = 8, vdim: int = 128, capacity: int = 4096,
                 devices=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.kernels.race_lookup.ops import NODE_AXIS
        devices = jax.devices() if devices is None else list(devices)
        if n_nodes % len(devices):
            raise ValueError(f"{n_nodes} nodes do not divide over "
                             f"{len(devices)} devices")
        self.n_nodes = n_nodes
        self.n_buckets = n_buckets
        self.nslot = nslot
        self.vdim = vdim
        self.nodes = [PoolRaceTable(n_buckets, nslot, vdim, capacity)
                      for _ in range(n_nodes)]
        self.mesh = Mesh(np.array(devices), (NODE_AXIS,))
        self._by_node = NamedSharding(self.mesh, P(NODE_AXIS))
        self._replicated = NamedSharding(self.mesh, P())
        self.stats = LookupStats()
        # on the mesh, as every lookup's block count is: one compiled add
        self.stats.blocks = jax.device_put(jnp.zeros((), jnp.int32),
                                           self._replicated)

    @property
    def version(self) -> int:
        """Inserts so far, over every node."""
        return sum(n.version for n in self.nodes)

    def insert(self, key: int, value: np.ndarray) -> None:
        self.insert_many(np.array([key]), np.asarray(value)[None])

    def insert_many(self, keys: np.ndarray, values: np.ndarray) -> None:
        """Route ``keys`` to their nodes and insert each node's share, in
        order, with that node's ``insert_many``. A node that refuses its
        share (pool full, bucket overflow) raises; the nodes before it
        keep theirs."""
        keys = np.asarray(keys)
        node = shard_of_keys(keys, self.n_nodes)
        for i, table in enumerate(self.nodes):
            mine = node == i
            if mine.any():
                table.insert_many(keys[mine], values[mine])

    def prep(self, keys: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """(keys (NQ,) i32, 8-bit fingerprints (NQ,) i32, candidate
        buckets (NQ, 2) i32 within the node, node (NQ,) i32); every node
        shares one bucket geometry."""
        return (*self.nodes[0].prep(keys), shard_of_keys(keys, self.n_nodes))

    def tables(self) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Each node's host ``(index, keys, pool)``, in node order."""
        return [n.tables() for n in self.nodes]

    def _put(self, host) -> Tuple:
        """(index, keys, pool) as arrays with a leading node axis, sharded
        over the mesh: each node's arrays are put on its device alone."""
        import jax
        import jax.numpy as jnp
        devices = self.mesh.devices.tolist()
        per = self.n_nodes // len(devices)
        out = []
        for arrays in zip(*host):            # every node's index, then ...
            shards = []
            for d, device in enumerate(devices):
                parts = jax.device_put(
                    [a[None] for a in arrays[d * per:(d + 1) * per]], device)
                shards.append(parts[0] if per == 1 else jnp.concatenate(parts))
            out.append(jax.make_array_from_single_device_arrays(
                (self.n_nodes, *arrays[0].shape), self._by_node, shards))
        return tuple(out)

    def lookup_grouped(self, qkeys: np.ndarray, qfps: np.ndarray,
                       bucket_idx: np.ndarray, inv: np.ndarray,
                       impl: str = "pallas"):
        """The device half of ``lookup_batch``, on queries already grouped
        per node: qkeys, qfps (N, QCAP) i32, bucket_idx (N, QCAP, 2) i32,
        inv (NQ,) i32, each key's slot ``node * QCAP + within``. Ships the
        tables first if they are stale, packs the queries into one array
        (:func:`~repro.kernels.race_lookup.ops.pack_queries`), puts it
        once, sharded on the mesh, and dispatches the program
        (:func:`~repro.kernels.race_lookup.ops.pool_mesh_lookup`), which
        unpacks it on the devices. Returns device arrays (values (NQ,
        VDIM), found (NQ,) i32, blocks fetched, an i32 scalar)."""
        import jax

        from repro import obs
        from repro.kernels.race_lookup.ops import (pack_queries,
                                                   pool_mesh_lookup)
        tables = self._ship_if_stale()
        with obs.span("race.to_device") as add:
            queries = pack_queries(qkeys, qfps, bucket_idx, inv)
            add(h2d_bytes=queries.nbytes)
            queries = jax.block_until_ready(
                jax.device_put(queries, self._by_node))
            add(h2d_buffers=len(queries.sharding.device_set))
        with obs.span("race.kernel",
                      variant="pool_mesh" if impl == "pallas" else impl):
            return pool_mesh_lookup(*tables, queries, nq=len(inv),
                                    qcap=qfps.shape[1], mesh=self.mesh,
                                    nslot=self.nslot, impl=impl)

    def lookup_batch(self, keys: np.ndarray, impl: str = "pallas"):
        import jax.numpy as jnp

        from repro import obs
        from repro.kernels.race_lookup.pool import QBLOCK
        from repro.kernels.race_lookup.race_lookup import _group_slots
        with obs.request(self.stats):
            with obs.span("race.prep", keys=len(keys)):
                qkeys, qfps, bidx, node = self.prep(keys)
            self._ship_if_stale()
            if not len(qkeys):
                return (jnp.zeros((0, self.vdim), jnp.float32),
                        jnp.zeros((0,), jnp.int32))
            with obs.span("race.group") as add:
                qfps, bidx, inv, _ = _group_slots(qfps, bidx, node,
                                                  self.n_nodes, QBLOCK)
                grouped_keys = np.zeros(qfps.size, np.int32)
                grouped_keys[inv] = qkeys
                add(slots=qfps.size, padded_slots=qfps.size - len(qkeys),
                    qcap=qfps.shape[1])
            values, found, blocks = self.lookup_grouped(
                grouped_keys.reshape(qfps.shape), qfps, bidx, inv, impl)
            self.stats.blocks = self.stats.blocks + blocks
            return values, found
