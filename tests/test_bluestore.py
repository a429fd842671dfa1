"""BlueStore-lite: extent allocation, checksums at rest, compression,
blob-sharing clones, restart survival (r4 VERDICT missing #2; reference:
src/os/bluestore/BlueStore.cc structure, src/os/ObjectStore.h contract)."""
import copy
import os
import pickle
import shutil

import numpy as np
import pytest

from ceph_tpu.backend.bluestore import (BlueStoreLite, ChecksumError,
                                        RunListAllocator)
from ceph_tpu.backend.memstore import GObject, MemStore, Transaction


def _refs_count_extents(store):
    """Every live blob is referenced by exactly its extent count."""
    refcount = {}
    for onode in store.onodes.values():
        for e in onode.extents:
            refcount[e.blob] = refcount.get(e.blob, 0) + 1
    return refcount == {bid: b.refs for bid, b in store.blobs.items()}


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


@pytest.fixture
def bs(tmp_path):
    s = BlueStoreLite(tmp_path / "bs", min_alloc=512)
    yield s
    s.close()


class TestAllocator:
    def test_alloc_free_coalesce(self):
        a = RunListAllocator(512)
        o1, l1 = a.alloc(1000)          # 2 units
        o2, l2 = a.alloc(512)           # 1 unit
        assert (o1, l1) == (0, 1024) and (o2, l2) == (1024, 512)
        a.free(o1, l1)
        a.free(o2, l2)                  # coalesces into one run
        assert a.runs == [[0, 3]]
        o3, _ = a.alloc(1536)           # first-fit reuses the hole
        assert o3 == 0
        assert a.watermark == 3

    def test_rebuild_from_blobs(self):
        from ceph_tpu.backend.bluestore import Blob
        a = RunListAllocator(512)
        blobs = {1: Blob(poff=512, plen=400, alloc=512, raw_len=400,
                         csum=0, comp=None),
                 2: Blob(poff=2048, plen=512, alloc=512, raw_len=512,
                         csum=0, comp=None)}
        a.rebuild(blobs)
        assert a.runs == [[0, 1], [2, 2]]
        assert a.watermark == 5


class TestStoreContract:
    """MemStore-equivalence: every Transaction op produces identical
    observable state on both stores."""

    OPS = [
        lambda t, g: t.write(g, 0, _data(700, 1)),
        lambda t, g: t.write(g, 300, _data(600, 2)),   # overlapping rmw
        lambda t, g: t.zero(g, 100, 250),
        lambda t, g: t.truncate(g, 450),
        lambda t, g: t.truncate(g, 900),               # extend
        lambda t, g: t.write(g, 2000, _data(64, 3)),   # hole
        lambda t, g: t.setattr(g, "a", {"x": 1}),
        lambda t, g: t.omap_setkeys(g, {"k": b"v"}),
        lambda t, g: t.omap_setheader(g, b"hdr"),
    ]

    def test_matches_memstore(self, bs):
        mem = MemStore()
        g = GObject("o", 3)
        for op in self.OPS:
            for store in (bs, mem):
                t = Transaction()
                op(t, g)
                store.queue_transaction(t)
            assert bs.read(g) == mem.read(g)
            assert bs.stat(g) == mem.stat(g)
        assert bs.getattrs(g) == mem.getattrs(g)
        assert bs.get_omap(g) == mem.get_omap(g)
        assert bs.get_omap_header(g) == mem.get_omap_header(g)

    def test_random_rmw_fuzz_matches_memstore(self, bs):
        """rmw-heavy fuzz: random overlapping writes/zeros/truncates must
        track MemStore byte-for-byte (the extent-map surgery is the
        riskiest code here)."""
        rng = np.random.default_rng(42)
        mem = MemStore()
        g = GObject("fuzz", 0)
        for i in range(300):
            t1, t2 = Transaction(), Transaction()
            kind = rng.integers(0, 10)
            off = int(rng.integers(0, 5000))
            ln = int(rng.integers(1, 2000))
            if kind < 6:
                d = _data(ln, 1000 + i)
                t1.write(g, off, d)
                t2.write(g, off, d)
            elif kind < 8:
                t1.zero(g, off, ln)
                t2.zero(g, off, ln)
            else:
                t1.truncate(g, off)
                t2.truncate(g, off)
            bs.queue_transaction(t1)
            mem.queue_transaction(t2)
            if i % 37 == 0:
                assert bs.read(g) == mem.read(g), i
        assert bs.read(g) == mem.read(g)
        assert _refs_count_extents(bs)

    def test_remove_frees_space(self, bs):
        g = GObject("big", 0)
        bs.queue_transaction(Transaction().write(g, 0, _data(8192, 5)))
        used = bs.usage()["allocated_bytes"]
        assert used >= 8192
        bs.queue_transaction(Transaction().remove(g))
        assert bs.usage()["allocated_bytes"] == 0
        assert bs.usage()["free_bytes"] >= used
        # the freed space is REUSED, not appended after
        wm = bs.alloc.watermark
        bs.queue_transaction(Transaction().write(GObject("n", 0), 0,
                                                 _data(4096, 6)))
        assert bs.alloc.watermark == wm

    def test_clone_shares_blobs(self, bs):
        g, c = GObject("h", 0), GObject("h\x00snap\x001", 0)
        payload = _data(4096, 7)
        bs.queue_transaction(Transaction().write(g, 0, payload)
                             .setattr(g, "t", b"v"))
        before = bs.usage()["allocated_bytes"]
        bs.queue_transaction(Transaction().clone(g, c))
        # O(extent-map) clone: no new data allocation
        assert bs.usage()["allocated_bytes"] == before
        assert bs.read(c) == payload
        assert bs.getattr(c, "t") == b"v"
        # COW: overwriting the head leaves the clone intact
        bs.queue_transaction(Transaction().write(g, 0, _data(4096, 8)))
        assert bs.read(c) == payload
        # dropping the head keeps the shared blob alive for the clone
        bs.queue_transaction(Transaction().remove(g))
        assert bs.read(c) == payload


class TestChecksums:
    def test_bitrot_at_rest_detected(self, bs):
        g = GObject("x", 0)
        bs.queue_transaction(Transaction().write(g, 0, _data(2048, 9)))
        blob = next(iter(bs.blobs.values()))
        # flip one byte of the stored data behind the store's back
        bs._block.seek(blob.poff + 100)
        orig = bs._block.read(1)
        bs._block.seek(blob.poff + 100)
        bs._block.write(bytes([orig[0] ^ 0xFF]))
        with pytest.raises(ChecksumError):
            bs.read(g)
        # repair (rewrite) clears the error
        bs.queue_transaction(Transaction().write(g, 0, _data(2048, 9)))
        assert bs.read(g) == _data(2048, 9)


class TestCompression:
    def test_compressible_data_saves_units(self, tmp_path):
        s = BlueStoreLite(tmp_path / "c", min_alloc=512,
                          compression="zlib")
        g = GObject("z", 0)
        payload = b"A" * 65536                   # wildly compressible
        s.queue_transaction(Transaction().write(g, 0, payload))
        u = s.usage()
        assert u["compressed_blobs"] == 1
        assert u["allocated_bytes"] < len(payload) // 4
        assert s.read(g) == payload
        # partial reads decompress and slice exactly
        assert s.read(g, 1000, 500) == payload[1000:1500]
        s.close()
        # survives restart (comp metadata persisted)
        s2 = BlueStoreLite(tmp_path / "c", min_alloc=512,
                           compression="zlib")
        assert s2.read(g) == payload
        s2.close()

    def test_incompressible_data_stays_raw(self, tmp_path):
        s = BlueStoreLite(tmp_path / "r", min_alloc=512,
                          compression="zlib")
        g = GObject("rnd", 0)
        payload = _data(8192, 11)               # random: incompressible
        s.queue_transaction(Transaction().write(g, 0, payload))
        assert s.usage()["compressed_blobs"] == 0
        assert s.read(g) == payload
        s.close()


class TestDurability:
    def test_restart_survival(self, tmp_path):
        s = BlueStoreLite(tmp_path / "d", min_alloc=512)
        g1, g2 = GObject("a", 0), GObject("b", 1)
        s.queue_transaction(Transaction().write(g1, 0, _data(3000, 12))
                            .setattr(g1, "k", b"v")
                            .omap_setkeys(g1, {"o": b"m"}))
        s.queue_transaction(Transaction().write(g2, 100, _data(700, 13)))
        s.close()                               # checkpoint path
        s2 = BlueStoreLite(tmp_path / "d", min_alloc=512)
        assert s2.read(g1) == _data(3000, 12)
        assert s2.getattr(g1, "k") == b"v"
        assert s2.get_omap(g1) == {"o": b"m"}
        assert s2.read(g2, 100, 700) == _data(700, 13)
        assert s2.stat(g2) == 800
        s2.close()

    def test_wal_replay_without_checkpoint(self, tmp_path):
        s = BlueStoreLite(tmp_path / "w", min_alloc=512)
        g = GObject("a", 0)
        s.queue_transaction(Transaction().write(g, 0, _data(1500, 14)))
        s.queue_transaction(Transaction().write(g, 500, _data(400, 15)))
        want = s.read(g)
        s._wal.flush()
        s._block.flush()
        # crash: no close/checkpoint
        s2 = BlueStoreLite(tmp_path / "w", min_alloc=512)
        assert s2.read(g) == want
        # allocator rebuilt: new writes do not clobber live blobs
        s2.queue_transaction(Transaction().write(GObject("n", 0), 0,
                                                 _data(2048, 16)))
        assert s2.read(g) == want
        s2.close()

    def test_torn_wal_tail_discarded(self, tmp_path):
        s = BlueStoreLite(tmp_path / "t", min_alloc=512)
        g = GObject("a", 0)
        s.queue_transaction(Transaction().write(g, 0, b"committed"))
        s._wal.flush()
        s._block.flush()
        # simulate a crash mid-append: garbage half-record at the tail
        with open(s.path / "kv.log", "ab") as f:
            f.write(b"\x99" * 7)
        s2 = BlueStoreLite(tmp_path / "t", min_alloc=512)
        assert s2.read(g) == b"committed"
        # the store keeps working (tail truncated)
        s2.queue_transaction(Transaction().write(g, 0, b"next"))
        s2.close()
        s3 = BlueStoreLite(tmp_path / "t", min_alloc=512)
        assert s3.read(g, 0, 4) == b"next"
        s3.close()

    def test_metadata_checkpoint_excludes_data(self, tmp_path):
        """The checkpoint is metadata-only: its size must not scale with
        data volume (the r4 FileStore whole-store-pickle weakness)."""
        s = BlueStoreLite(tmp_path / "m", min_alloc=4096)
        for i in range(8):
            s.queue_transaction(Transaction().write(
                GObject(f"o{i}", 0), 0, _data(1 << 18, i)))   # 2 MiB total
        s.close()
        snap_size = (tmp_path / "m" / "kv.snap").stat().st_size
        block_size = (tmp_path / "m" / "block").stat().st_size
        assert block_size >= 1 << 21
        assert snap_size < 64 * 1024


class TestScrubWithChecksumsAtRest:
    def test_scrub_flags_rotten_blob(self, tmp_path):
        """Bitrot injected into a replica's blob AT REST: the store's own
        crc32c locates it during deep scrub — no majority vote needed —
        and repair restores the copy."""
        from ceph_tpu.cluster import MiniCluster
        c = MiniCluster(n_osds=6, osds_per_host=2, chunk_size=512,
                        data_dir=tmp_path, store_backend="bluestore")
        pid = c.create_replicated_pool("p", size=3, pg_num=4)
        payload = _data(3000, 77)
        c.put(pid, "rotten", payload)
        g = c.pg_group(pid, "rotten")
        peer = next(s for s in g.acting if s != g.backend.whoami)
        _rot_shard_copy(c, pid, "rotten", peer)
        rep = c.scrub_pool(pid)
        assert any("rotten" in o for bad in rep.values() for o in bad)
        # scrub's repair rewrote the copy: clean now, reads fine
        assert c.scrub_pool(pid) == {}
        assert c.get(pid, "rotten", len(payload)) == payload
        c.shutdown()


def _rot_shard_copy(c, pid, oid, shard):
    """Flip one at-rest byte of ``oid``'s copy on ``shard`` behind the
    store's back (the blob-level bitrot injection)."""
    bs = c.osds[shard].store
    target = next(go for go in bs.onodes
                  if go.oid.endswith(oid) and go.shard == shard)
    blob = bs.blobs[bs.onodes[target].extents[0].blob]
    bs._block.seek(blob.poff)
    b0 = bs._block.read(1)
    bs._block.seek(blob.poff)
    bs._block.write(bytes([b0[0] ^ 0xFF]))
    bs._block.flush()


class TestRottenSourceRecovery:

    def test_ec_rmw_read_retries_past_rotten_chunk(self, tmp_path):
        """A partial-stripe overwrite whose RMW read hits a rotten source
        chunk must widen to a parity chunk, not hand the decode k-1
        chunks (regression: reply errors were silently discarded)."""
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.osd.osd_ops import ObjectOperation
        c = MiniCluster(n_osds=6, osds_per_host=2, chunk_size=512,
                        data_dir=tmp_path, store_backend="bluestore")
        pid = c.create_ec_pool("p", {"k": "2", "m": "2",
                                     "device": "numpy"}, pg_num=4)
        payload = _data(2048, 21)
        c.operate(pid, "rmw", ObjectOperation().write_full(payload))
        g = c.pg_group(pid, "rmw")
        data_shard = g.acting[1]              # a non-primary data chunk
        _rot_shard_copy(c, pid, "rmw", data_shard)
        # partial overwrite: RMW reads the stripe, hits the rot, widens
        patch = _data(100, 22)
        c.operate(pid, "rmw", ObjectOperation().write(300, patch))
        want = bytearray(payload)
        want[300:400] = patch
        r = c.operate(pid, "rmw", ObjectOperation().read(0, 0))
        assert r.outdata(0)[:len(want)] == bytes(want)
        c.shutdown()

    def test_ec_recovery_rebuilds_rotten_source_too(self, tmp_path):
        """Recovery reading a rotten source must drop it, rebuild from
        clean chunks, and repair the rotten shard as well (regression:
        the -5 reply failed the whole recovery op forever)."""
        from ceph_tpu.backend.pg_backend import RecoveryState
        from ceph_tpu.cluster import MiniCluster
        c = MiniCluster(n_osds=8, osds_per_host=2, chunk_size=512,
                        data_dir=tmp_path, store_backend="bluestore")
        pid = c.create_ec_pool("p", {"k": "2", "m": "2",
                                     "device": "numpy"}, pg_num=4)
        payload = _data(2048, 23)
        c.put(pid, "rec", payload)
        g = c.pg_group(pid, "rec")
        rotten = g.acting[2]
        _rot_shard_copy(c, pid, "rec", rotten)
        missing_chunk = 3                     # rebuild the last chunk
        rop = g.backend.recover_object("rec", {missing_chunk})
        g.bus.deliver_all()
        assert rop.state == RecoveryState.COMPLETE
        # the rotten chunk was detected and repaired alongside
        assert 2 in rop.missing_shards
        assert c.get(pid, "rec", len(payload)) == payload
        assert c.scrub_pool(pid) == {}
        c.shutdown()


class TestClusterIntegration:
    def test_minicluster_on_bluestore(self, tmp_path):
        """A durable cluster on BlueStore-lite: EC pool IO, rmw-heavy
        churn, restart, deep scrub with checksums at rest."""
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.osd.osd_ops import ObjectOperation
        c = MiniCluster(n_osds=6, osds_per_host=2, chunk_size=512,
                        data_dir=tmp_path, store_backend="bluestore")
        pid = c.create_ec_pool("p", {"k": "2", "m": "1",
                                     "device": "numpy"}, pg_num=8)
        rng = np.random.default_rng(0)
        model = {}
        for i in range(10):
            model[f"o{i}"] = _data(1500 + 37 * i, 50 + i)
            c.operate(pid, f"o{i}", ObjectOperation()
                      .write_full(model[f"o{i}"]).setxattr("t", b"x"))
        for step in range(60):                   # rmw churn
            oid = f"o{int(rng.integers(0, 10))}"
            off = int(rng.integers(0, 1000))
            d = _data(int(rng.integers(50, 600)), 500 + step)
            c.operate(pid, oid, ObjectOperation().write(off, d))
            cur = bytearray(model[oid])
            if len(cur) < off + len(d):
                cur.extend(b"\0" * (off + len(d) - len(cur)))
            cur[off:off + len(d)] = d
            model[oid] = bytes(cur)
        c.shutdown()
        c2 = MiniCluster.load(tmp_path)
        for oid, want in model.items():
            r = c2.operate(pid, oid, ObjectOperation().read(0, 0))
            assert r.outdata(0)[:len(want)] == want, oid
        assert c2.scrub_pool(pid) == {}
        c2.shutdown()


class TestBlueStoreComposition:
    def test_snaps_kills_rot_restart_campaign(self, tmp_path):
        """Everything at once on the bluestore backend: snapshots with
        COW clones, an OSD death and revival mid-writes, at-rest bitrot
        located by the store's checksums and repaired by scrub, then a
        full restart recovering every PG from the per-OSD block files."""
        from ceph_tpu.cluster import BlockedWriteError, MiniCluster
        from ceph_tpu.common import Context
        from ceph_tpu.osd.osd_ops import ObjectOperation
        cct = Context(overrides={"mon_osd_down_out_interval": 10_000})
        c = MiniCluster(n_osds=6, osds_per_host=2, chunk_size=512,
                        data_dir=tmp_path, store_backend="bluestore",
                        cct=cct)
        pid = c.create_replicated_pool("p", size=3, pg_num=8)
        model, snaps = {}, {}
        for i in range(12):
            model[f"o{i}"] = _data(900 + 31 * i, i)
            c.operate(pid, f"o{i}", ObjectOperation()
                      .write_full(model[f"o{i}"]))
        sid = c.create_pool_snap(pid, "s1")
        snaps[sid] = dict(model)
        # kill an OSD, write through the degradation
        victim = c.pg_group(pid, "o0").acting[1]
        c.bus.mark_down(victim)
        for i in range(12):
            new = _data(700 + 13 * i, 100 + i)
            try:
                c.operate(pid, f"o{i}",
                          ObjectOperation().write_full(new))
                model[f"o{i}"] = new
            except BlockedWriteError:
                c.bus.mark_up(victim)
                c.bus.deliver_all()
                model[f"o{i}"] = new
                c.bus.mark_down(victim)
        c.bus.mark_up(victim)
        c.bus.deliver_all()
        # at-rest rot on a non-primary copy of one object
        g = c.pg_group(pid, "o3")
        peer = next(s for s in g.acting if s != g.backend.whoami)
        _rot_shard_copy(c, pid, "o3", peer)
        rep = c.scrub_pool(pid)
        assert any("o3" in o for bad in rep.values() for o in bad)
        assert c.scrub_pool(pid) == {}          # repaired
        # snapshot isolation held through all of it
        r = c.operate(pid, "o5", ObjectOperation().read(0, 0), snapid=sid)
        assert r.outdata(0)[:len(snaps[sid]["o5"])] == snaps[sid]["o5"]
        c.shutdown()
        # restart: everything recovers from the per-OSD block files
        c2 = MiniCluster.load(tmp_path)
        for oid, want in model.items():
            r = c2.operate(pid, oid, ObjectOperation().read(0, 0))
            assert r.outdata(0)[:len(want)] == want, oid
        r = c2.operate(pid, "o5", ObjectOperation().read(0, 0),
                       snapid=sid)
        assert r.outdata(0)[:len(snaps[sid]["o5"])] == snaps[sid]["o5"]
        assert c2.scrub_pool(pid) == {}
        c2.shutdown()


# -- the WAL record is a delta (ISSUE 36) ------------------------------------

KINDS = ("write", "zero", "truncate", "remove", "touch", "clone", "setattr",
         "rmattr", "omap_setkeys", "omap_rmkeys", "omap_clear",
         "omap_setheader")
N_TXNS = 240
TORN = (1, 2, 57, 119, 120, 203, N_TXNS - 1)     # records torn inside


def _seeded_transactions(seed=36):
    """N_TXNS transactions over six objects: every op kind, and in ONE
    transaction each of: omap_clear then set; remove then re-create;
    clone then diverge both sides' omaps."""
    rng = np.random.default_rng(seed)
    objs = [GObject(f"o{i}", i % 3) for i in range(6)]

    def pick():
        return objs[int(rng.integers(len(objs)))]

    def key():
        return f"k{int(rng.integers(40)):02d}"

    def add(t, kind):
        g = pick()
        if kind == "write":
            t.write(g, int(rng.integers(3000)),
                    _data(int(rng.integers(1, 1500)), int(rng.integers(1e6))))
        elif kind == "zero":
            t.zero(g, int(rng.integers(3000)), int(rng.integers(1, 900)))
        elif kind == "truncate":
            t.truncate(g, int(rng.integers(4000)))
        elif kind == "remove":
            t.remove(g)
        elif kind == "touch":
            t.touch(g)
        elif kind == "clone":
            t.clone(g, pick())
        elif kind == "setattr":
            t.setattr(g, key(), {"v": int(rng.integers(99))})
        elif kind == "rmattr":
            t.rmattr(g, key())
        elif kind == "omap_setkeys":
            t.omap_setkeys(g, {key(): _data(int(rng.integers(1, 60)),
                                            int(rng.integers(1e6)))
                               for _ in range(int(rng.integers(1, 5)))})
        elif kind == "omap_rmkeys":
            t.omap_rmkeys(g, [key() for _ in range(int(rng.integers(1, 4)))])
        elif kind == "omap_clear":
            t.omap_clear(g)
        elif kind == "omap_setheader":
            t.omap_setheader(g, _data(8, int(rng.integers(1e6))))

    txns = []
    for i in range(N_TXNS):
        t = Transaction()
        if i % 20 == 5:         # clear, then set, in one transaction
            g = pick()
            t.omap_setkeys(g, {key(): b"before"}).omap_clear(g) \
                .omap_setkeys(g, {key(): b"after", "kept": b"%d" % i})
        elif i % 20 == 11:      # remove, then re-create
            g = pick()
            t.omap_setkeys(g, {"gone": b"x"}).remove(g) \
                .write(g, 10, _data(300, i)).omap_setkeys(g, {key(): b"new"})
        elif i % 20 == 17:      # clone, then both sides' omaps diverge
            src, dst = objs[i % 6], objs[(i + 1) % 6]
            t.omap_setkeys(src, {"shared": b"s"}).clone(src, dst) \
                .omap_setkeys(src, {"src_only": b"1"}) \
                .omap_rmkeys(dst, ["shared"]) \
                .omap_setkeys(dst, {"dst_only": b"2"})
        else:
            for _ in range(int(rng.integers(1, 5))):
                add(t, KINDS[int(rng.integers(len(KINDS)))])
        txns.append(t)
    assert {op[0] for t in txns for op in t.ops} == set(KINDS)
    return txns


def _state(store):
    return {g: (store.read(g), store.stat(g), store.getattrs(g),
                store.get_omap(g), store.get_omap_header(g))
            for g in store.list_objects()}


@pytest.fixture(scope="module")
def journaled(tmp_path_factory):
    """A store fed the seeded transactions and dropped without close (no
    checkpoint: the journal is all there is), where each record of its
    kv.log ends, and the directory as it stood when each of TORN's
    records had just been appended (later transactions reuse freed
    space of the block file, as they may once a record is durable)."""
    root = tmp_path_factory.mktemp("journaled")
    txns = _seeded_transactions()
    s = BlueStoreLite(root / "s", min_alloc=512, checkpoint_every=10 ** 9)
    ends = []
    for i, t in enumerate(txns):
        s.queue_transaction(t)
        ends.append(s.perf.dump()["wal_bytes"])
        if i in TORN:
            shutil.copytree(root / "s", root / f"at{i}")
    assert not (root / "s" / "kv.snap").exists()
    assert ends[-1] == (root / "s" / "kv.log").stat().st_size
    return root, txns, ends


def _reference(txns):
    mem = MemStore()
    for t in txns:
        mem.queue_transaction(t)
    return _state(mem)


def test_replay_equals_the_plain_reference(journaled, tmp_path):
    root, txns, _ends = journaled
    shutil.copytree(root / "s", tmp_path / "s")
    s = BlueStoreLite(tmp_path / "s", min_alloc=512)
    assert s.committed_seq == N_TXNS
    assert _state(s) == _reference(txns)
    # blob refs still count extents, and the rebuilt free list excludes
    # every live blob: the store goes on where it was dropped
    assert _refs_count_extents(s)
    s.queue_transaction(Transaction().write(GObject("late", 0), 0,
                                            _data(5000, 1)))
    assert {g: v for g, v in _state(s).items() if g.oid != "late"} == \
        _reference(txns)
    s.close()


@pytest.mark.parametrize("torn", TORN)
@pytest.mark.parametrize("cut", ["in_the_frame", "in_the_payload",
                                 "one_byte_short"])
def test_a_torn_record_leaves_exactly_the_committed_prefix(journaled, tmp_path,
                                                           torn, cut):
    root, txns, ends = journaled
    shutil.copytree(root / f"at{torn}", tmp_path / "s")
    start, end = ends[torn - 1], ends[torn]
    assert (tmp_path / "s" / "kv.log").stat().st_size == end
    at = {"in_the_frame": start + 3, "in_the_payload": (start + end) // 2,
          "one_byte_short": end - 1}[cut]
    os.truncate(tmp_path / "s" / "kv.log", at)
    s = BlueStoreLite(tmp_path / "s", min_alloc=512)
    assert s.committed_seq == torn
    assert _state(s) == _reference(txns[:torn])
    assert (tmp_path / "s" / "kv.log").stat().st_size == start
    s.close()


def test_replay_after_a_checkpoint_skips_what_the_snapshot_holds(tmp_path):
    """The crash between kv.snap's replace and the journal's restart: the
    records the snapshot already holds are in kv.log still, and are not
    applied a second time onto it."""
    txns = _seeded_transactions(seed=37)
    s = BlueStoreLite(tmp_path / "s", min_alloc=512, checkpoint_every=10 ** 9)
    for t in txns[:100]:
        s.queue_transaction(t)
    wal = (tmp_path / "s" / "kv.log").read_bytes()
    s.checkpoint()
    for t in txns[100:]:
        s.queue_transaction(t)
    tail = (tmp_path / "s" / "kv.log").read_bytes()
    (tmp_path / "s" / "kv.log").write_bytes(wal + tail)
    s2 = BlueStoreLite(tmp_path / "s", min_alloc=512)
    assert s2.committed_seq == N_TXNS
    assert _state(s2) == _reference(txns)
    s2.close()


def test_the_record_does_not_grow_with_the_omap(tmp_path):
    def appended(store, i):
        g = GObject("_pgmeta_", 0)
        before = store.perf.dump()["wal_bytes"]
        store.queue_transaction(
            Transaction().omap_setkeys(g, {f"log.{i:010d}": b"e" * 300})
            .omap_rmkeys(g, [f"log.{i - 1:010d}"]))
        return store.perf.dump()["wal_bytes"] - before

    s = BlueStoreLite(tmp_path / "s", checkpoint_every=10 ** 9)
    g = GObject("_pgmeta_", 0)
    s.queue_transaction(Transaction().touch(g))
    on_empty = appended(s, 1)
    s.queue_transaction(Transaction().omap_setkeys(
        g, {f"log.{i:010d}": b"e" * 300 for i in range(10, 1510)}))
    assert len(s.get_omap(g)) == 1501
    on_full = appended(s, 1510)
    assert on_full <= 2 * on_empty
    assert on_full < 1000 < 1500 * 300
    want = s.get_omap(g)
    s2 = BlueStoreLite(tmp_path / "s")          # dropped, not closed
    assert s2.get_omap(g) == want and len(want) == 1501
    s2.close()


def test_a_transaction_that_raises_midway_changes_nothing(tmp_path):
    s = BlueStoreLite(tmp_path / "s", min_alloc=512, checkpoint_every=10 ** 9)
    g, c = GObject("a", 0), GObject("c", 0)
    s.queue_transaction(Transaction().write(g, 0, _data(3000, 1))
                        .setattr(g, "x", 1).omap_setheader(g, b"h")
                        .omap_setkeys(g, {f"k{i}": b"v" for i in range(50)}))
    live_omap = s.onodes[g].omap                # the dict itself
    def allocated():        # units under the watermark on no free run
        free = {u for start, n in s.alloc.runs
                for u in range(start, start + n)}
        return set(range(s.alloc.watermark)) - free

    before = (_state(s), copy.deepcopy(s.blobs), allocated(),
              s.committed_seq, (tmp_path / "s" / "kv.log").stat().st_size)
    bad = (Transaction().write(g, 100, _data(2000, 2)).zero(g, 0, 50)
           .omap_setkeys(g, {"k1": b"changed", "fresh": b"f"})
           .omap_rmkeys(g, ["k2"]).clone(g, c).omap_clear(g)
           .setattr(g, "x", 2).remove(g))
    bad.ops.append(("no_such_op", g))
    counted = s.perf.dump()         # after _state's reads, which count
    with pytest.raises(ValueError):
        s.queue_transaction(bad)
    assert s.perf.dump() == counted
    assert s.onodes[g].omap is live_omap and len(live_omap) == 50
    assert (_state(s), s.blobs, allocated(), s.committed_seq,
            (tmp_path / "s" / "kv.log").stat().st_size) == before
    assert not s.exists(c)
    s2 = BlueStoreLite(tmp_path / "s", min_alloc=512)
    assert _state(s2) == before[0]
    s2.close()


def test_a_record_of_another_shape_is_refused_loudly(tmp_path):
    """A kv.log left by the code before (whole onodes, no tag) is never
    misread: the store does not open."""
    from ceph_tpu.backend.bluestore import _FRAME, Onode
    from ceph_tpu.backend.ecutil import crc32c
    (tmp_path / "s").mkdir()
    old = pickle.dumps((1, {GObject("a", 0): Onode(omap={"k": b"v"})}, {},
                        [], 1), protocol=pickle.HIGHEST_PROTOCOL)
    (tmp_path / "s" / "kv.log").write_bytes(
        _FRAME.pack(len(old), crc32c(0xFFFFFFFF, old)) + old)
    with pytest.raises(RuntimeError, match="omap-delta"):
        BlueStoreLite(tmp_path / "s")
    # a store the code before closed cleanly has an empty journal, and
    # kv.snap's format is what it was: it opens
    (tmp_path / "s" / "kv.log").write_bytes(b"")
    with open(tmp_path / "s" / "kv.snap", "wb") as f:
        pickle.dump((1, {GObject("a", 0): Onode(omap={"k": b"v"})}, {}, 1), f)
    s = BlueStoreLite(tmp_path / "s")
    assert s.get_omap(GObject("a", 0)) == {"k": b"v"}
    s.close()


def test_a_checkpoint_is_counted_and_traced(tmp_path):
    from ceph_tpu.common.tracer import default_tracer
    s = BlueStoreLite(tmp_path / "s", checkpoint_every=4)
    spans = lambda: sum(1 for e in default_tracer().dump()["traceEvents"]
                        if e.get("name") == "store.checkpoint")
    before = spans()
    for i in range(9):
        s.queue_transaction(Transaction().touch(GObject(f"o{i}", 0)))
    assert s.perf.dump()["checkpoints"] == 2
    assert spans() - before == 2
    s.close()


# -- a crc32c per csum block: a read verifies the blocks it covers ---------------

BLOCK = 4096
SIZE = 5 * BLOCK + 1000                 # the last block is short


def _blob_rot(store, bid, at):
    """Flip the stored byte ``at`` of blob ``bid`` behind the store."""
    fd = store._block.fileno()
    poff = store.blobs[bid].poff + at
    os.pwrite(fd, bytes([os.pread(fd, 1, poff)[0] ^ 0xFF]), poff)


@pytest.fixture
def split(tmp_path):
    """One 5.25-block object put whole, then a 4 KiB overwrite of its
    third block: the base blob maps as two extents around the new one."""
    s = BlueStoreLite(tmp_path / "blk", min_alloc=BLOCK)
    g = GObject("img", 0)
    model = bytearray(_data(SIZE, 30))
    s.queue_transaction(Transaction().write(g, 0, bytes(model)))
    patch = _data(BLOCK, 31)
    s.queue_transaction(Transaction().write(g, 2 * BLOCK, patch))
    model[2 * BLOCK:3 * BLOCK] = patch
    yield s, g, bytes(model)
    s.close()


RANGES = {
    "one_block": (BLOCK, BLOCK),
    "inside_a_block": (BLOCK + 904, 100),
    "across_blocks": (BLOCK - 96, BLOCK + 200),
    "across_two_extents_of_one_blob": (BLOCK + 17, 3 * BLOCK),
    "the_short_last_block": (5 * BLOCK + 300, 700),
    "to_the_end": (3 * BLOCK + 5, None),
    "whole": (0, None),
}


@pytest.mark.parametrize("case", list(RANGES))
def test_a_range_read_is_the_slice_of_the_whole_read(case, split):
    s, g, model = split
    off, n = RANGES[case]
    end = SIZE if n is None else off + n
    whole = s.read(g)
    assert whole == model
    assert s.read(g, off, n) == whole[off:end] == model[off:end]


@pytest.mark.parametrize("case", list(RANGES))
def test_a_range_read_counts_the_blocks_it_covers(case, split):
    """``read_block_bytes``: each extent's range rounded out to whole
    blocks of its blob (the short last block as stored), read once."""
    s, g, _model = split
    off, n = RANGES[case]
    end = SIZE if n is None else off + n
    want = 0
    for e in s.onodes[g].extents:
        lo = max(e.loff, off) - e.loff + e.boff
        hi = min(e.loff + e.length, end) - e.loff + e.boff
        if hi > lo:
            want += min(-(-hi // BLOCK) * BLOCK,
                        s.blobs[e.blob].plen) - lo // BLOCK * BLOCK
    before = s.perf.dump()
    s.read(g, off, n)
    after = s.perf.dump()
    assert after["read_bytes"] - before["read_bytes"] == end - off
    assert after["read_block_bytes"] - before["read_block_bytes"] == want
    assert want < end - off + 2 * 2 * BLOCK


def test_a_4k_read_of_a_512k_blob_reads_4k(tmp_path):
    s = BlueStoreLite(tmp_path / "s", min_alloc=BLOCK)
    g = GObject("shard", 0)
    payload = _data(512 * 1024, 32)
    s.queue_transaction(Transaction().write(g, 0, payload))
    [blob] = s.blobs.values()
    assert (blob.csum_chunk, len(blob.csum)) == (BLOCK, 128 * 4)
    before = s.perf.dump()
    assert s.read(g, 45 * BLOCK, BLOCK) == \
        payload[45 * BLOCK:46 * BLOCK]
    after = s.perf.dump()
    assert after["read_bytes"] - before["read_bytes"] == BLOCK
    assert after["read_block_bytes"] - before["read_block_bytes"] == BLOCK
    s.close()


@pytest.mark.parametrize("where", ["covered", "uncovered"])
def test_rot_fails_the_reads_that_cover_its_block(where, split):
    """Rot in a block a range read covers fails it and the whole read;
    rot in a block it does not cover leaves it right, and the whole
    read (deep scrub's) still finds it."""
    s, g, model = split
    base = s.onodes[g].extents[0].blob
    block = 1 if where == "covered" else 4
    _blob_rot(s, base, block * BLOCK + 77)
    off, n = BLOCK + 10, 200
    if where == "covered":
        with pytest.raises(ChecksumError, match="stored crc mismatch"):
            s.read(g, off, n)
    else:
        assert s.read(g, off, n) == model[off:off + n]
    with pytest.raises(ChecksumError, match="stored crc mismatch"):
        s.read(g)


def test_a_compressed_blob_keeps_one_crc_and_reads_whole(tmp_path):
    s = BlueStoreLite(tmp_path / "z", min_alloc=BLOCK, compression="zlib")
    g = GObject("z", 0)
    payload = b"ABCD" * (4 * BLOCK)
    s.queue_transaction(Transaction().write(g, 0, payload))
    [blob] = s.blobs.values()
    assert blob.comp == "zlib" and blob.csum_chunk == 0
    assert isinstance(blob.csum, int)
    before = s.perf.dump()["read_block_bytes"]
    assert s.read(g, BLOCK + 3, 500) == payload[BLOCK + 3:BLOCK + 503]
    assert s.perf.dump()["read_block_bytes"] - before == blob.plen
    assert s.read(g) == payload
    _blob_rot(s, next(iter(s.blobs)), blob.plen - 1)
    with pytest.raises(ChecksumError):
        s.read(g, 0, 10)
    s.close()


def _one_crc_blobs(store):
    """The store's blobs as the code before per-block crcs journaled
    them: one int crc of the stored bytes and no ``csum_chunk`` field
    (what unpickling such a record gives)."""
    from ceph_tpu.backend.bluestore import Blob
    from ceph_tpu.backend.ecutil import crc32c
    for bid, b in list(store.blobs.items()):
        old = Blob.__new__(Blob)
        old.__dict__.update(
            poff=b.poff, plen=b.plen, alloc=b.alloc, raw_len=b.raw_len,
            csum=crc32c(0xFFFFFFFF, os.pread(store._block.fileno(), b.plen,
                                             b.poff)),
            comp=b.comp, refs=b.refs)
        store.blobs[bid] = old


@pytest.mark.parametrize("journal", ["kv.snap", "kv.log"])
def test_a_store_journaled_with_one_crc_a_blob_opens_and_reads(journal,
                                                               tmp_path):
    """Blobs of one crc, from a checkpoint or from a journal record,
    load as one-chunk blobs: every read returns the right bytes and
    checks the whole blob; new writes beside them take per-block crcs."""
    from ceph_tpu.backend import bluestore
    s = BlueStoreLite(tmp_path / "s", min_alloc=BLOCK)
    g = GObject("old", 0)
    payload = _data(3 * BLOCK + 10, 33)
    s.queue_transaction(Transaction().write(g, 0, payload))
    _one_crc_blobs(s)
    if journal == "kv.snap":
        s.close()
    else:
        # a record carries the blobs whose refs it changes: journal a
        # clone of the object after the checkpoint
        s.checkpoint()
        s.queue_transaction(Transaction().clone(g, GObject("cl", 0)))
        record = (tmp_path / "s" / "kv.log").read_bytes()
        assert b"csum_chunk" not in record
        s._wal.close()
        s._block.close()                # killed: no checkpoint
    with open(tmp_path / "s" / journal, "rb") as f:
        assert b"csum_chunk" not in f.read()
    s2 = BlueStoreLite(tmp_path / "s", min_alloc=BLOCK)
    [bid] = s2.blobs
    assert "csum_chunk" not in vars(s2.blobs[bid])
    assert s2.blobs[bid].csum_chunk == 0
    before = s2.perf.dump()["read_block_bytes"]
    assert s2.read(g, BLOCK + 1, 20) == payload[BLOCK + 1:BLOCK + 21]
    assert s2.perf.dump()["read_block_bytes"] - before == len(payload)
    assert s2.read(g) == payload
    s2.queue_transaction(Transaction().write(g, BLOCK, _data(BLOCK, 34)))
    want = payload[:BLOCK] + _data(BLOCK, 34) + payload[2 * BLOCK:]
    assert s2.read(g) == want
    assert {b.csum_chunk for b in s2.blobs.values()} == {0, BLOCK}
    _blob_rot(s2, bid, 2 * BLOCK + 3)
    with pytest.raises(ChecksumError):
        s2.read(g, 0, 10)
    s2.close()


@pytest.mark.parametrize("n", [0, 1, 4095, 4096, 4097, 524288])
def test_the_block_crcs_are_crc32c_of_each_block(n, monkeypatch):
    """One native call gives per-block crc32c, seeded 0xFFFFFFFF, packed
    little-endian; the pure-Python fallback gives the same bytes."""
    from ceph_tpu.backend import ecutil
    data = _data(n, n)
    want = b"".join(ecutil.crc32c(0xFFFFFFFF, data[i:i + BLOCK])
                    .to_bytes(4, "little") for i in range(0, n, BLOCK))
    native = ecutil.crc32c_blocks(data, BLOCK)
    monkeypatch.setattr(ecutil, "_native_crc", False)
    assert native == ecutil.crc32c_blocks(data, BLOCK) == want
    assert len(want) == 4 * -(-n // BLOCK)
