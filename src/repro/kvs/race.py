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


class DeviceRaceTable:
    """TPU-resident RACE table: batched lookups via the Pallas kernel."""

    def __init__(self, n_buckets: int = 1024, nslot: int = 8,
                 vdim: int = 128):
        self.n_buckets = n_buckets
        self.nslot = nslot
        self.vdim = vdim
        self._fp = np.zeros((n_buckets, nslot), np.int32)
        self._val = np.zeros((n_buckets, nslot, vdim), np.float32)
        self._loads = np.zeros(n_buckets, np.int32)

    def insert(self, key: int, value: np.ndarray) -> None:
        b1, b2 = _h1(key, self.n_buckets), _h2(key, self.n_buckets)
        b = b1 if self._loads[b1] <= self._loads[b2] else b2
        if self._loads[b] >= self.nslot:
            b = b2 if b == b1 else b1
            if self._loads[b] >= self.nslot:
                raise RuntimeError("bucket overflow")
        s = self._loads[b]
        self._fp[b, s] = np.int32(_fp(key) & 0x7FFFFFFF) or 1
        self._val[b, s, :len(value)] = value
        self._loads[b] += 1

    def prep(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(fingerprints (NQ,) i32, candidate bucket rows (NQ, 2) i32) of
        ``keys`` — the kernel's query operands."""
        keys = np.asarray(keys)
        fps = np.array([(_fp(int(k)) & 0x7FFFFFFF) or 1 for k in keys],
                       np.int32)
        bidx = np.stack(
            [[_h1(int(k), self.n_buckets) for k in keys],
             [_h2(int(k), self.n_buckets) for k in keys]],
            axis=1).astype(np.int32)
        return fps, bidx

    def tables(self) -> Tuple[np.ndarray, np.ndarray]:
        """(fp (NB, NSLOT) i32, val (NB, NSLOT, VDIM) f32) host tables."""
        return self._fp, self._val

    def lookup_batch(self, keys: np.ndarray, impl: str = "pallas"):
        from repro.kernels.race_lookup.ops import race_lookup
        fps, bidx = self.prep(keys)
        return race_lookup(self._fp, self._val, fps, bidx, impl=impl)


class ShardedDeviceRaceTable:
    """Multi-shard TPU-resident RACE table: the device analogue of the
    dkv shard map. Per-shard tables share one geometry and batched
    lookups run through the SHARDED Pallas kernel
    (``race_lookup_sharded``): the grid gains a shard dimension and only
    ONE shard's table is resident per grid step, instead of the whole
    multi-shard array pinned VMEM-resident at once."""

    def __init__(self, n_shards: int = 4, n_buckets: int = 256,
                 nslot: int = 8, vdim: int = 128):
        self.n_shards = n_shards
        self.n_buckets = n_buckets
        self.nslot = nslot
        self.vdim = vdim
        self.shards = [DeviceRaceTable(n_buckets, nslot, vdim)
                       for _ in range(n_shards)]

    def shard_of(self, key: int) -> int:
        return shard_of_key(int(key), self.n_shards)

    def insert(self, key: int, value: np.ndarray) -> None:
        self.shards[self.shard_of(key)].insert(key, value)

    def prep(self, keys: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(fingerprints, intra-shard bucket rows (NQ, 2), owning shard
        (NQ,)) of ``keys``; every shard shares one bucket geometry."""
        fps, bidx = self.shards[0].prep(keys)
        sidx = np.array([self.shard_of(int(k)) for k in np.asarray(keys)],
                        np.int32)
        return fps, bidx, sidx

    def tables(self) -> Tuple[np.ndarray, np.ndarray]:
        """Per-shard tables stacked: (NS, NB, NSLOT) and (NS, NB, NSLOT,
        VDIM)."""
        return (np.stack([s._fp for s in self.shards]),
                np.stack([s._val for s in self.shards]))

    def lookup_batch(self, keys: np.ndarray, impl: str = "pallas"):
        from repro.kernels.race_lookup.ops import race_lookup_sharded
        fps, bidx, sidx = self.prep(keys)
        fp_tables, val_tables = self.tables()
        return race_lookup_sharded(fp_tables, val_tables, fps, bidx, sidx,
                                   impl=impl)
