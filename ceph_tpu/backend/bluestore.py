"""BlueStore-lite: an extent-allocator object store over one flat file.

The durable store modeled on the reference's flagship ObjectStore
(reference: src/os/bluestore/BlueStore.cc — structure, not scale):

- **data** lives in ONE flat block file, allocated in ``min_alloc``-sized
  units by a run-list allocator with bitmap semantics (the reference's
  BitmapAllocator, src/os/bluestore/BitmapAllocator.h);
- **blobs** are immutable physical regions: every write allocates a fresh
  blob and remaps logical extents onto it (the reference's copy-on-write
  blob model), so a crash mid-write leaves old metadata pointing at old
  bytes — never torn data;
- **checksums at rest**: each blob stores the crc32c of its physical
  bytes, verified on EVERY read (``bluestore_csum_type=crc32c``); a
  mismatch raises :class:`ChecksumError` (EIO), which deep scrub surfaces
  without any majority vote;
- **inline compression** via the CompressorRegistry: blobs compress when
  the configured compressor saves at least one allocation unit, storing
  ``raw_len`` for exact reconstruction (``bluestore_compression_mode``);
- **clones share blobs** by refcount — O(extent-map) clone, no data copy
  (the snapshot COW path rides this);
- **metadata** (onodes: size + extent maps + xattrs + omap; the blob
  table) journals through a WAL and periodic checkpoints, exactly like
  :class:`~ceph_tpu.backend.filestore.FileStore` — but checkpoints carry
  ONLY metadata, so their cost scales with object count, not data volume
  (the r4 whole-store-pickle weakness), and a WAL record carries what its
  transaction CHANGED: a touched onode's small fields and the omap keys
  set and removed, never the omap (a PG's ``_pgmeta_`` omap holds its
  whole log, and every sub-write touches it).  The allocator's free list is
  REBUILT from the blob table on open (self-healing, like the
  reference's freelist-from-RocksDB startup);
- **a commit callback**: ``queue_transaction(t, on_commit)`` stages on the
  caller's thread and queues the I/O (blob writes, journal record, their
  fsyncs where ``sync``); a group commit (:func:`commit_queued`, run by
  whoever waits) writes the queued transactions of the stores it waits
  for in one native call and calls each ``on_commit`` once its record is
  in (BlueStore's
  txc state machine, ``queue_transactions``'s ``on_commit`` context and
  the kv sync thread's batching).  A store's transactions reach its files
  in ``committed_seq`` order, and every other entry point waits for its
  queue first.

Implements the full MemStore/FileStore ObjectStore surface, so it can
back OSD daemons via collections unchanged.
"""
from __future__ import annotations

import ctypes
import errno
import os
import pickle
import struct
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

from ..common.perf_counters import PerfCountersBuilder
from ..common.tracer import trace_span
from .ecutil import crc32c
from .memstore import GObject, Transaction, _Object

_FRAME = struct.Struct("<II")        # payload length, crc32c(payload)
# first item of a WAL record: the shape of the rest.  "omap-delta": per
# touched object None (removed) or (size, extents, xattrs, omap_header,
# omap_cleared, omap_set, omap_rm).  A record without it (the whole-onode
# records of the code before) is refused at open, never guessed at.
_RECORD = "omap-delta"
_SNAP = "kv.snap"
_WAL = "kv.log"
_BLOCK = "block"


_store_io = None


def _native_io():
    """The native library's batched transaction I/O, or None where it
    cannot be built (the calls are then made one by one from Python)."""
    global _store_io
    if _store_io is None:
        try:
            from ..native import registry_lib
            _store_io = registry_lib().ec_store_commit_batch
        except Exception:                   # noqa: BLE001 — no toolchain
            _store_io = False
    return _store_io or None


def _write_txns(txns: list) -> list:
    """Transactions' I/O, in order: each its blobs' bytes (and the block
    fsync), then the journal's frame and record (and its fsync) — data
    precedes its metadata.  ``txns``: (store, blob writes, frame, record).
    Returns (errno or 0, block ns, journal ns, CPU ns) for each; after a
    failure a store's later transactions are not written (ECANCELED).
    In one native call where the library is built, which lets the
    interpreter go once and times each transaction with its own clocks;
    else call by call, timed around the calls."""
    io = _native_io()
    if io is None:
        out, failed = [], set()
        for store, writes, frame, payload in txns:
            if store in failed:
                out.append((errno.ECANCELED, 0, 0, 0))
                continue
            c0, t0 = time.thread_time_ns(), time.perf_counter_ns()
            t1 = t0
            try:
                fd = store._block.fileno()
                for poff, stored in writes:
                    os.pwrite(fd, stored, poff)
                if store.sync:
                    os.fsync(fd)
                t1 = time.perf_counter_ns()
                fd = store._wal.fileno()
                os.writev(fd, (frame, payload))
                if store.sync:
                    os.fsync(fd)
                rc = 0
            except OSError as e:
                rc = e.errno or errno.EIO
                failed.add(store)
            out.append((rc, t1 - t0, time.perf_counter_ns() - t1,
                        time.thread_time_ns() - c0))
        return out
    n = len(txns)
    blobs = [w for txn in txns for w in txn[1]]
    m = len(blobs)
    i64, i32 = ctypes.c_int64, ctypes.c_int
    rcs, ns = (i32 * n)(), (i64 * (3 * n))()
    io(n, (i32 * n)(*(t[0]._block.fileno() for t in txns)),
       (i32 * n)(*(t[0]._wal.fileno() for t in txns)),
       (i32 * n)(*(t[0].sync for t in txns)),
       (i32 * n)(*(len(t[1]) for t in txns)),
       (ctypes.c_char_p * m)(*(w[1] for w in blobs)),
       (i64 * m)(*(len(w[1]) for w in blobs)),
       (i64 * m)(*(w[0] for w in blobs)),
       (ctypes.c_char_p * n)(*(t[2] for t in txns)),
       (i64 * n)(*(len(t[2]) for t in txns)),
       (ctypes.c_char_p * n)(*(t[3] for t in txns)),
       (i64 * n)(*(len(t[3]) for t in txns)), rcs, ns)
    return [(-rcs[i], ns[3 * i], ns[3 * i + 1], ns[3 * i + 2])
            for i in range(n)]


def commit_queued(stores) -> None:
    """Write the queued transactions of ``stores`` (distinct
    :class:`BlueStoreLite`), all in one batch, each store's in their
    order, then call their ``on_commit`` in that order.  A transaction
    that fails keeps its error on its store (raised by the store's next
    :meth:`~BlueStoreLite.wait_committed`), and neither it nor the
    store's later ones call back.  Run by whoever waits: the bus for the
    stores its held replies wait for, a store for itself."""
    items = [(s, item) for s in stores if s._failed is None
             for item in s._queue]
    try:
        results = _write_txns([(s, item[1], item[2], item[3])
                               for s, item in items]) if items else []
        for (s, item), (rc, *ns) in zip(items, results):
            if s._failed is not None:
                continue
            if rc:
                s._failed = OSError(rc, f"{s.path}: transaction "
                                        f"{item[0]}: {os.strerror(rc)}")
                continue
            if item[5]:
                # a timed transaction: its I/O phases by the call's own
                # clocks, weighted as its caller's phases were
                block_ns, wal_ns, cpu_ns = ns
                s._clk.commit_booked(
                    {"block_io_us": block_ns * 1e-9,
                     "wal_io_us": wal_ns * 1e-9}, cpu_ns, s.TIMED_EVERY)
            item[4]()
    finally:
        for s in stores:
            s._queue = []


class ChecksumError(IOError):
    """A blob's bytes at rest no longer match their stored crc32c (the
    reference returns -EIO from _verify_csum)."""


@dataclass
class Blob:
    """An immutable physical region of the block file."""
    poff: int            # byte offset in the block file
    plen: int            # stored (possibly compressed) byte length
    alloc: int           # allocated bytes (plen rounded up to units)
    raw_len: int         # decompressed length
    csum: int            # crc32c of the STORED bytes
    comp: str | None     # compressor name, None = raw
    refs: int = 1        # extents (across all onodes) mapping this blob


@dataclass
class Extent:
    """A logical range of an object mapped onto part of a blob."""
    loff: int            # logical offset in the object
    length: int
    blob: int            # blob id
    boff: int            # offset into the blob's RAW content


@dataclass
class Onode:
    size: int = 0
    extents: list[Extent] = field(default_factory=list)   # sorted by loff
    xattrs: dict[str, Any] = field(default_factory=dict)
    omap: dict[str, bytes] = field(default_factory=dict)
    omap_header: bytes = b""


@dataclass
class _StagedOnode:
    """A touched onode inside one transaction: the small fields copied,
    the omap as an overlay on the live one — keys set, keys removed, a
    "cleared" mark — never a copy of it."""
    size: int = 0
    extents: list[Extent] = field(default_factory=list)
    xattrs: dict[str, Any] = field(default_factory=dict)
    omap_header: bytes = b""
    omap_base: dict[str, bytes] | None = None   # the live omap; None = cleared
    omap_set: dict[str, bytes] = field(default_factory=dict)
    omap_rm: set[str] = field(default_factory=set)

    @classmethod
    def of(cls, o: "Onode | _StagedOnode", omap_base) -> "_StagedOnode":
        """``o``'s small fields copied, over ``omap_base``."""
        return cls(o.size, [Extent(e.loff, e.length, e.blob, e.boff)
                            for e in o.extents],
                   dict(o.xattrs), o.omap_header, omap_base)

    def clone(self) -> "_StagedOnode":
        """A clone's destination: cleared + the source's keys as they
        stand in this transaction."""
        dst = self.of(self, None)
        dst.omap_set.update(self.omap_base or ())
        for key in self.omap_rm:
            dst.omap_set.pop(key, None)
        dst.omap_set.update(self.omap_set)
        return dst

    def record(self) -> tuple:
        return (self.size, self.extents, self.xattrs, self.omap_header,
                self.omap_base is None, self.omap_set, self.omap_rm)


class RunListAllocator:
    """Free-space tracking with bitmap semantics over allocation units:
    sorted, coalesced (start, length) free runs below a growth watermark
    (BitmapAllocator.h behavior at run-list cost)."""

    def __init__(self, unit: int):
        self.unit = unit
        self.runs: list[list[int]] = []     # sorted [start_unit, n_units]
        self.watermark = 0                  # units ever claimed

    def alloc(self, nbytes: int) -> tuple[int, int]:
        """(byte offset, allocated bytes) — first-fit over the free runs,
        else grow the watermark."""
        units = max(1, -(-nbytes // self.unit))
        for i, (start, n) in enumerate(self.runs):
            if n >= units:
                self.runs[i][0] += units
                self.runs[i][1] -= units
                if self.runs[i][1] == 0:
                    del self.runs[i]
                return start * self.unit, units * self.unit
        start = self.watermark
        self.watermark += units
        return start * self.unit, units * self.unit

    def free(self, poff: int, nbytes: int) -> None:
        start, units = poff // self.unit, max(1, -(-nbytes // self.unit))
        import bisect
        i = bisect.bisect_left(self.runs, [start, 0])
        self.runs.insert(i, [start, units])
        # coalesce with neighbours
        if i + 1 < len(self.runs) and \
                self.runs[i][0] + self.runs[i][1] == self.runs[i + 1][0]:
            self.runs[i][1] += self.runs[i + 1][1]
            del self.runs[i + 1]
        if i > 0 and self.runs[i - 1][0] + self.runs[i - 1][1] == \
                self.runs[i][0]:
            self.runs[i - 1][1] += self.runs[i][1]
            del self.runs[i]

    def free_bytes(self) -> int:
        return sum(n for _s, n in self.runs) * self.unit

    def rebuild(self, blobs: dict[int, Blob]) -> None:
        """Free list = everything under the watermark not covered by a
        live blob (freelist-from-metadata startup)."""
        self.runs = []
        covered = sorted((b.poff // self.unit, b.alloc // self.unit)
                        for b in blobs.values())
        self.watermark = 0
        pos = 0
        for start, units in covered:
            if start > pos:
                self.runs.append([pos, start - pos])
            pos = max(pos, start + units)
        self.watermark = pos


class BlueStoreLite:
    """Durable ObjectStore over ONE block file + metadata WAL/checkpoint;
    same surface as MemStore/FileStore."""

    # one committed transaction in this many is timed: a phase clock
    # books where its wall time went and its thread's CPU time (two
    # reads, first and last in the store.commit span and in no phase),
    # each microsecond this many times, so the sums estimate all
    # transactions'.  The CPU clock is a system call that keeps the
    # interpreter, 5.6 us a read on the chip's host and some 17 in a
    # busy server, and it ticks at 100 Hz there: six reads on every
    # commit lengthened a put's hold by 15% (PERF.md, PR 37).  A
    # constant, and a prime, so that no short pattern in a store's
    # transactions keeps step with it (a sub-write and its roll-forward
    # kick alternate on an idle pool: an even stride would time one
    # kind only); tests patch it to 1
    TIMED_EVERY = 17

    def __init__(self, path: str | os.PathLike, min_alloc: int = 4096,
                 compression: str | None = None, sync: bool = False,
                 checkpoint_every: int = 512, name: str | None = None,
                 cct=None):
        self.path = Path(path)
        # what a committed transaction cost, summed: `perf dump` shows
        # the collection while the store lives where a Context is given
        self.perf = (
            PerfCountersBuilder(f"bluestore.{name or self.path.name}")
            .add_u64_counter("transactions", "transactions committed")
            .add_u64_counter("commits_queued",
                             "of them, those whose I/O was queued for a "
                             "group commit, acked by their on_commit "
                             "callback")
            .add_u64_counter("txn_ops", "ops in them")
            .add_u64_counter("block_bytes",
                             "bytes written to the block file, as "
                             "allocated (whole min_alloc units)")
            .add_u64_counter("wal_bytes",
                             "bytes appended to the metadata journal "
                             "(frame header + pickled record)")
            .add_u64_counter("checkpoints",
                             "metadata snapshots written (every onode "
                             "pickled whole, the journal restarted)")
            # where a committed transaction's wall time went, in
            # microseconds: four consecutive phases of one clock that
            # tile the store.commit span (BlueStore.cc's state_*_lat and
            # kv_*_lat, as sums), and the thread's CPU clock over the
            # same stretch; from one transaction in TIMED_EVERY, each
            # booked that many times.  The two I/O phases are timed by
            # the native call that makes them; a queued commit's run in
            # the group commit, after the span ends
            .add_u64_counter("stage_us",
                             "staging: the ops applied to the staged "
                             "onodes (less their block writes), blob "
                             "refcounts, the record's deltas, the onode "
                             "table")
            .add_u64_counter("record_us",
                             "the journal record pickled and its crc")
            .add_u64_counter("block_io_us",
                             "block file calls: a blob's seek + write, "
                             "the flush (and fsync where sync) before "
                             "the record")
            .add_u64_counter("wal_io_us",
                             "journal calls: the frame and record "
                             "written, the flush (and fsync where sync)")
            .add_u64_counter("commit_cpu_us",
                             "the committing threads' CPU clocks over "
                             "the four phases (the caller's over the "
                             "first two and the group commit's over "
                             "the I/O of a queued commit); their sum "
                             "less this is what the threads did not "
                             "run: blocked in a call or waiting for "
                             "the interpreter")
            .add_u64_counter("checkpoint_us",
                             "wall time of the metadata snapshots "
                             "(beside checkpoints)")
            .create_perf_counters())
        self.path.mkdir(parents=True, exist_ok=True)
        self.min_alloc = min_alloc
        self.sync = sync
        self.checkpoint_every = checkpoint_every
        self.onodes: dict[GObject, Onode] = {}
        self.blobs: dict[int, Blob] = {}
        self.next_blob = 1
        self.committed_seq = 0
        self.alloc = RunListAllocator(min_alloc)
        self._compressor = None
        self.compression = compression
        if compression:
            from ..compressor import CompressorRegistry
            self._compressor = CompressorRegistry.instance().create(
                compression)
        self._wal_records = 0
        # which of every TIMED_EVERY: by the store's name, so that a
        # put's twelve stores do not all time the same put's commits
        self._timed_at = crc32c(0, self.perf.name.encode())
        self._clk = self.perf.phase_clock("commit_cpu_us")
        self._load()
        # unbuffered: every write reaches the file at once (the commits
        # write by descriptor, at their offsets)
        self._block = open(self.path / _BLOCK, "r+b", buffering=0)
        self._wal = open(self.path / _WAL, "ab")
        # staged transactions whose I/O is still to do, in order: (seq,
        # blob writes, frame, record, on_commit, timed); the first I/O
        # error of a group commit, kept and raised on the caller's
        # thread (the store then refuses work: a later record must not
        # follow one that never landed)
        self._queue: list = []
        self._failed: OSError | None = None
        self._cct = cct
        if cct is not None:
            cct.perf.add(self.perf)

    # -- persistence --------------------------------------------------------

    def _load(self) -> None:
        (self.path / _BLOCK).touch()
        snap = self.path / _SNAP
        if snap.exists():
            with open(snap, "rb") as f:
                (self.committed_seq, self.onodes, self.blobs,
                 self.next_blob) = pickle.load(f)
        wal = self.path / _WAL
        if wal.exists():
            with open(wal, "rb") as f:
                buf = f.read()
            off = 0
            snap_seq = self.committed_seq
            while off + _FRAME.size <= len(buf):
                length, crc = _FRAME.unpack_from(buf, off)
                payload = buf[off + _FRAME.size:off + _FRAME.size + length]
                if len(payload) < length or \
                        crc32c(0xFFFFFFFF, payload) != crc:
                    break             # torn tail: never committed
                off += _FRAME.size + length
                record = pickle.loads(payload)
                if record[0] != _RECORD:
                    raise RuntimeError(
                        f"{wal}: a committed record of another shape than "
                        f"{_RECORD!r} (a store killed under older code): "
                        f"reopen and close it with the code that wrote it")
                _, seq, onode_delta, blob_delta, freed, nb = record
                if seq <= snap_seq:
                    continue          # predates the checkpoint
                self._apply_meta(onode_delta, blob_delta, freed)
                self.next_blob = max(self.next_blob, nb)
                self.committed_seq = seq
                self._wal_records += 1
            if off < len(buf):
                os.truncate(wal, off)
        # the free list is DERIVED state: rebuild from live blobs
        self.alloc.rebuild(self.blobs)

    def _apply_meta(self, onode_delta, blob_delta, freed) -> None:
        for bid in freed:
            self.blobs.pop(bid, None)
        self.blobs.update(blob_delta)
        self._apply_onodes(onode_delta)

    def _apply_onodes(self, onode_delta) -> None:
        """What a record says of its touched objects, onto the onode table:
        a commit's last step on the live table, and a replay's."""
        for obj, rec in onode_delta.items():
            if rec is None:
                self.onodes.pop(obj, None)
                continue
            # the overlay lands on the onode the records before left
            o = self.onodes.get(obj)
            if o is None:
                o = self.onodes[obj] = Onode()
            (o.size, o.extents, o.xattrs, o.omap_header,
             cleared, omap_set, omap_rm) = rec
            if cleared:
                o.omap.clear()
            for key in omap_rm:
                o.omap.pop(key, None)
            o.omap.update(omap_set)

    def checkpoint(self) -> None:
        """Metadata-only snapshot (onodes + blob table): cost scales with
        object count, never data volume — the block file IS the data.
        Follows every queued record: the snapshot covers what they say
        and the journal restarts after them."""
        self.wait_committed()
        with trace_span("store.checkpoint") as span:
            if self.sync:
                os.fsync(self._block.fileno())
            tmp = self.path / (_SNAP + ".tmp")
            with open(tmp, "wb") as f:
                pickle.dump((self.committed_seq, self.onodes, self.blobs,
                             self.next_blob), f,
                            protocol=pickle.HIGHEST_PROTOCOL)
                f.flush()
                if self.sync:
                    os.fsync(f.fileno())
            os.replace(tmp, self.path / _SNAP)
            self._wal.close()
            self._wal = open(self.path / _WAL, "wb")
            self._wal_records = 0
        self.perf.inc("checkpoints")
        self.perf.inc("checkpoint_us", int(span.dur * 1e6 + 0.5))

    def close(self, checkpoint: bool = True) -> None:
        """Write the queued commits and checkpoint, then close the files;
        a kept I/O error is raised once they are closed."""
        if self._cct is not None:
            self._cct.perf.remove(self.perf.name)
        try:
            self.wait_committed()
            if checkpoint:
                self.checkpoint()
        finally:
            self._wal.close()
            self._block.close()

    @property
    def committer(self) -> "BlueStoreLite":
        """The store whose queue holds this one's commits: itself (a
        collection or a fault wrapper answers with the store behind)."""
        return self

    def wait_committed(self) -> None:
        """Return once every queued transaction is written; raise the I/O
        error the store kept, if any."""
        if self._queue:
            commit_queued([self])
        if self._failed is not None:
            raise self._failed

    # -- blob IO ------------------------------------------------------------

    def _write_blob(self, data: bytes, new_blobs: dict[int, Blob],
                    writes: list) -> int:
        """Store ``data`` as a fresh blob (maybe compressed); returns the
        blob id.  The bytes join ``writes``, which reach the block file
        before the record that names them — old metadata never references
        them, so a crash in between leaks nothing and tears nothing
        (COW)."""
        raw_len = len(data)
        comp = None
        stored = data
        if self._compressor is not None and raw_len > self.min_alloc:
            candidate = self._compressor.compress(data)
            # worth it only if it saves at least one allocation unit
            # (bluestore_compression_required_ratio in spirit)
            if (-(-len(candidate) // self.min_alloc)
                    < -(-raw_len // self.min_alloc)):
                stored = candidate
                comp = self.compression
        poff, alloc = self.alloc.alloc(max(1, len(stored)))
        writes.append((poff, stored))
        bid = self.next_blob
        self.next_blob += 1
        blob = Blob(poff=poff, plen=len(stored), alloc=alloc,
                    raw_len=raw_len, csum=crc32c(0xFFFFFFFF, stored),
                    comp=comp)
        new_blobs[bid] = blob
        self.blobs[bid] = blob
        return bid

    def _read_blob(self, bid: int) -> bytes:
        b = self.blobs[bid]
        self.wait_committed()           # its bytes may still be queued
        stored = os.pread(self._block.fileno(), b.plen, b.poff)
        if crc32c(0xFFFFFFFF, stored) != b.csum:
            raise ChecksumError(
                f"blob {bid} at {b.poff}+{b.plen}: stored crc mismatch "
                f"(bitrot at rest)")
        if b.comp is not None:
            from ..compressor import CompressorRegistry
            return CompressorRegistry.instance().create(
                b.comp).decompress(stored)
        return stored

    # -- extent-map surgery --------------------------------------------------

    @staticmethod
    def _punch(onode: Onode, off: int, length: int,
               deref: list[int], addref: list[int]) -> None:
        """Drop the logical range [off, off+length) from the extent map,
        splitting boundary extents.  Blob refs count EXTENTS: a fully
        unmapped extent collects in ``deref``; a mid-split (one extent
        becoming two remainders) collects in ``addref``."""
        end = off + length
        out: list[Extent] = []
        for e in onode.extents:
            e_end = e.loff + e.length
            if e_end <= off or e.loff >= end:
                out.append(e)
                continue
            pieces = 0
            if e.loff < off:                    # left remainder
                out.append(Extent(e.loff, off - e.loff, e.blob, e.boff))
                pieces += 1
            if e_end > end:                     # right remainder
                out.append(Extent(end, e_end - end, e.blob,
                                  e.boff + (end - e.loff)))
                pieces += 1
            if pieces == 0:
                deref.append(e.blob)
            elif pieces == 2:
                addref.append(e.blob)
        onode.extents = sorted(out, key=lambda e: e.loff)

    def _deref(self, bids, freed: list[int]) -> None:
        for bid in bids:
            b = self.blobs.get(bid)
            if b is None:
                continue
            b.refs -= 1
            if b.refs <= 0:
                del self.blobs[bid]
                self.alloc.free(b.poff, b.alloc)
                freed.append(bid)

    # -- transactions --------------------------------------------------------

    def queue_transaction(self, t: Transaction, on_commit=None) -> int:
        """Apply atomically; journal the metadata delta; return the seq.

        Staging mirrors MemStore, less the omap: the touched onodes'
        small fields are copied, their omap changes collect as an overlay
        (:class:`_StagedOnode`) that is applied to the live omap in place
        on success and is all the record says of the omap; blob refcount
        changes are tracked and only applied on success.  Staging, the
        onode table's update and the record's pickle run here, so a later
        transaction sees this one's state at once.

        Without ``on_commit`` the I/O runs here too, after every queued
        transaction's, and the call returns durable.  With it, the I/O
        is queued for the next group commit (:func:`commit_queued`, run
        by whoever waits: the bus that holds the sub-write's reply, a
        read, a checkpoint), which calls ``on_commit()`` once the record
        is written (and fsynced where ``sync``); an I/O error there is
        kept, ``on_commit`` is never called, and the next
        :meth:`wait_committed` raises it."""
        if self._failed is not None:
            raise self._failed
        if on_commit is None:
            self.wait_committed()
        touched: set[GObject] = set()
        for op in t.ops:
            touched.add(op[1])
            if op[0] == "clone":
                touched.add(op[2])
        staged: dict[GObject, _StagedOnode | None] = {}
        for obj in touched:
            o = self.onodes.get(obj)
            staged[obj] = None if o is None else _StagedOnode.of(o, o.omap)
        new_blobs: dict[int, Blob] = {}
        writes: list[tuple[int, bytes]] = []    # (block offset, bytes)
        deref: list[int] = []       # blob ids losing one reference
        addref: list[int] = []      # blob ids gaining one (clone/split)
        clk = self._clk
        with trace_span("store.commit"):
            clk.start(not (self.committed_seq + self._timed_at)
                      % self.TIMED_EVERY)
            timed = clk.on
            try:
                for op in t.ops:
                    self._apply(staged, op, new_blobs, deref, addref,
                                writes)
            except Exception:
                # all-or-nothing: nothing was written; free the space the
                # transaction's blobs took (nothing references it)
                for bid, b in new_blobs.items():
                    self.blobs.pop(bid, None)
                    self.alloc.free(b.poff, b.alloc)
                raise
            # commit: refcounts, onode table, WAL
            for bid in addref:
                self.blobs[bid].refs += 1
            freed: list[int] = []
            self._deref(deref, freed)
            onode_delta = {obj: o.record() if o is not None else None
                           for obj, o in staged.items()}
            blob_delta = {bid: self.blobs[bid] for bid in
                          set(new_blobs) - set(freed)} | \
                         {bid: self.blobs[bid] for bid in addref + deref
                          if bid in self.blobs}
            self._apply_onodes(onode_delta)
            self.committed_seq += 1
            clk.mark("stage_us")
            payload = pickle.dumps(
                (_RECORD, self.committed_seq, onode_delta, blob_delta,
                 freed, self.next_blob),
                protocol=pickle.HIGHEST_PROTOCOL)
            frame = _FRAME.pack(len(payload),
                                crc32c(0xFFFFFFFF, payload))
            if on_commit is None:
                clk.mark("record_us")
                [(rc, block_ns, wal_ns, _cpu)] = _write_txns(
                    [(self, writes, frame, payload)])
                if rc:
                    raise OSError(rc, f"{self.path}: {os.strerror(rc)}")
                clk.book("block_io_us", block_ns * 1e-9)
                clk.book("wal_io_us", wal_ns * 1e-9)
                clk.stop(None)
            else:
                clk.stop("record_us")
                self._queue.append((self.committed_seq, writes, frame,
                                    payload, on_commit, timed))
        clk.commit(self.TIMED_EVERY)
        perf = self.perf
        perf.inc("transactions")
        if on_commit is not None:
            perf.inc("commits_queued")
        perf.inc("txn_ops", len(t.ops))
        perf.inc("block_bytes", sum(b.alloc for b in new_blobs.values()))
        perf.inc("wal_bytes", _FRAME.size + len(payload))
        self._wal_records += 1
        if self._wal_records >= self.checkpoint_every:
            self.checkpoint()
        return self.committed_seq

    def _apply(self, staged, op, new_blobs, deref, addref, writes) -> None:
        kind = op[0]
        obj = op[1]

        def node() -> _StagedOnode:
            if staged.get(obj) is None:
                staged[obj] = _StagedOnode()    # new: nothing to overlay
            return staged[obj]

        if kind == "write":
            _, _, offset, data = op
            o = node()
            if data:
                self._punch(o, offset, len(data), deref, addref)
                bid = self._write_blob(bytes(data), new_blobs, writes)
                o.extents.append(Extent(offset, len(data), bid, 0))
                o.extents.sort(key=lambda e: e.loff)
            o.size = max(o.size, offset + len(data))
        elif kind == "zero":
            _, _, offset, length = op
            o = node()
            self._punch(o, offset, length, deref, addref)
            o.size = max(o.size, offset + length)
        elif kind == "truncate":
            _, _, size = op
            o = node()
            if size < o.size:
                self._punch(o, size, o.size - size, deref, addref)
            o.size = size
        elif kind == "remove":
            o = staged.get(obj)
            if o is not None:
                deref.extend(e.blob for e in o.extents)
            staged[obj] = None
        elif kind == "touch":
            node()
        elif kind == "clone":
            _, src, dst = op
            so = staged.get(src)
            old = staged.get(dst)
            if old is not None:
                deref.extend(e.blob for e in old.extents)
            if so is None:
                staged[dst] = _StagedOnode()
            else:
                staged[dst] = so.clone()
                addref.extend(e.blob for e in so.extents)
        elif kind == "setattr":
            node().xattrs[op[2]] = op[3]
        elif kind == "rmattr":
            node().xattrs.pop(op[2], None)
        elif kind == "omap_setkeys":
            o = node()
            o.omap_set.update(op[2])
            o.omap_rm.difference_update(op[2])
        elif kind == "omap_rmkeys":
            o = node()
            for key in op[2]:
                o.omap_set.pop(key, None)
            if o.omap_base is not None:
                o.omap_rm.update(op[2])
        elif kind == "omap_clear":
            o = node()
            o.omap_base = None
            o.omap_set.clear()
            o.omap_rm.clear()
            o.omap_header = b""
        elif kind == "omap_setheader":
            node().omap_header = op[2]
        else:
            raise ValueError(f"unknown op {kind}")

    # -- reads ---------------------------------------------------------------

    def _node(self, obj: GObject) -> Onode:
        o = self.onodes.get(obj)
        if o is None:
            raise FileNotFoundError(obj)
        return o

    def read(self, obj: GObject, offset: int = 0,
             length: int | None = None) -> bytes:
        o = self._node(obj)
        if length is None:
            length = max(o.size - offset, 0)
        end = min(offset + length, o.size)
        if end <= offset:
            return b""
        out = bytearray(end - offset)       # gaps read as zeros
        for e in o.extents:
            e_end = e.loff + e.length
            if e_end <= offset or e.loff >= end:
                continue
            s = max(e.loff, offset)
            t_ = min(e_end, end)
            raw = self._read_blob(e.blob)
            piece = raw[e.boff + (s - e.loff):e.boff + (t_ - e.loff)]
            out[s - offset:s - offset + len(piece)] = piece
        return bytes(out)

    def stat(self, obj: GObject) -> int:
        return self._node(obj).size

    def exists(self, obj: GObject) -> bool:
        return obj in self.onodes

    def getattr(self, obj: GObject, name: str):
        return self._node(obj).xattrs[name]

    def getattrs(self, obj: GObject):
        return dict(self._node(obj).xattrs)

    def get_omap(self, obj: GObject) -> dict[str, bytes]:
        return dict(self._node(obj).omap)

    def get_omap_header(self, obj: GObject) -> bytes:
        return self._node(obj).omap_header

    def list_objects(self) -> list[GObject]:
        return sorted(self.onodes, key=lambda g: (g.oid, g.shard))

    # -- compat: the dict-shaped objects view --------------------------------

    @property
    def objects(self) -> "_OnodeObjectsView":
        return _OnodeObjectsView(self)

    # -- introspection (admin socket / tests) --------------------------------

    def usage(self) -> dict:
        """Allocator + blob stats ('bluestore allocator dump' shape)."""
        stored = sum(b.plen for b in self.blobs.values())
        raw = sum(b.raw_len for b in self.blobs.values())
        return {
            "min_alloc": self.min_alloc,
            "blobs": len(self.blobs),
            "allocated_bytes": sum(b.alloc for b in self.blobs.values()),
            "stored_bytes": stored,
            "raw_bytes": raw,
            "compressed_blobs": sum(1 for b in self.blobs.values()
                                    if b.comp),
            "free_bytes": self.alloc.free_bytes(),
            "watermark_bytes": self.alloc.watermark * self.min_alloc,
        }


class _OnodeObjectsView:
    """Read-mostly mapping compat layer: ``store.objects[g]`` returns an
    _Object-shaped proxy (materialized data, live xattr/omap dicts) for
    the backend code paths that peek directly."""

    def __init__(self, store: BlueStoreLite):
        self._s = store

    def __getitem__(self, g: GObject) -> _Object:
        onode = self._s.onodes.get(g)
        if onode is None:
            raise KeyError(g)       # dict semantics: .get() relies on it
        return _Object(bytearray(self._s.read(g)), onode.xattrs,
                       onode.omap, onode.omap_header)

    def get(self, g: GObject, default=None):
        try:
            return self[g]
        except KeyError:
            return default

    def __contains__(self, g) -> bool:
        return g in self._s.onodes

    def __iter__(self):
        return iter(self._s.onodes)

    def __len__(self) -> int:
        return len(self._s.onodes)

    def keys(self):
        return self._s.onodes.keys()
