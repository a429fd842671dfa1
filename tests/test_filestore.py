"""Durable store + restart survival.

Covers the reference's durability contract (reference: src/os/ObjectStore.h
transaction semantics; WAL/compaction shape of src/os/bluestore/BlueStore.cc;
boot path OSD::init src/osd/OSD.cc:2719): atomic transactions survive
process restart via WAL replay, checkpoints compact the log, torn WAL tails
are discarded, and a MiniCluster reopened from disk serves every object —
including repairing a shard that restarted stale through the ordinary
PG-log path.
"""
import numpy as np
import pytest

from ceph_tpu.backend.filestore import FileStore
from ceph_tpu.backend.memstore import GObject, Transaction
from ceph_tpu.cluster import MiniCluster


def payload(n, seed=7):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


class TestFileStore:
    def test_reopen_after_close(self, tmp_path):
        fs = FileStore(tmp_path / "s")
        obj = GObject("a", 0)
        fs.queue_transaction(Transaction().write(obj, 0, b"hello")
                             .setattr(obj, "k", {"v": 1})
                             .omap_setkeys(obj, {"ok": b"ov"}))
        fs.close()
        fs2 = FileStore(tmp_path / "s")
        assert fs2.read(obj) == b"hello"
        assert fs2.getattr(obj, "k") == {"v": 1}
        assert fs2.get_omap(obj) == {"ok": b"ov"}

    def test_reopen_without_close_replays_wal(self, tmp_path):
        """Crash model: the process dies without checkpointing — the WAL
        alone must reconstruct the committed state."""
        fs = FileStore(tmp_path / "s")
        obj = GObject("a", 0)
        for i in range(10):
            fs.queue_transaction(
                Transaction().write(obj, i * 4, bytes([i] * 4)))
        fs._wal.flush()                      # crash: no close/checkpoint
        fs2 = FileStore(tmp_path / "s")
        want = b"".join(bytes([i] * 4) for i in range(10))
        assert fs2.read(obj) == want
        assert fs2.committed_seq == 10

    def test_torn_wal_tail_discarded(self, tmp_path):
        fs = FileStore(tmp_path / "s")
        obj = GObject("a", 0)
        fs.queue_transaction(Transaction().write(obj, 0, b"good"))
        fs._wal.flush()
        # simulate a crash mid-append: garbage half-record at the tail
        with open(tmp_path / "s" / "wal.log", "ab") as f:
            f.write(b"\x40\x00\x00\x00\xde\xad\xbe\xefent")
        fs2 = FileStore(tmp_path / "s")
        assert fs2.read(obj) == b"good"      # the good record survived
        assert fs2.committed_seq == 1        # the torn one never committed

    def test_checkpoint_compacts_and_survives(self, tmp_path):
        fs = FileStore(tmp_path / "s", checkpoint_every=4)
        obj = GObject("a", 0)
        for i in range(11):                  # crosses 2 checkpoints
            fs.queue_transaction(Transaction().write(obj, 0, bytes([i] * 8)))
        assert fs._wal_records < 4
        fs2 = FileStore(tmp_path / "s")
        assert fs2.read(obj) == bytes([10] * 8)

    def test_remove_and_truncate_survive(self, tmp_path):
        fs = FileStore(tmp_path / "s")
        a, b = GObject("a", 0), GObject("b", 0)
        fs.queue_transaction(Transaction().write(a, 0, b"xxxx")
                             .write(b, 0, b"yyyyyyyy"))
        fs.queue_transaction(Transaction().remove(a).truncate(b, 3))
        fs.close()
        fs2 = FileStore(tmp_path / "s")
        assert not fs2.exists(a)
        assert fs2.read(b) == b"yyy"


class TestClusterRestart:
    PROFILE = {"plugin": "jax_rs", "k": "4", "m": "2", "device": "numpy",
               "technique": "reed_sol_van"}

    def test_objects_survive_restart(self, tmp_path):
        c1 = MiniCluster(n_osds=12, chunk_size=256, data_dir=tmp_path)
        pid = c1.create_ec_pool("pool", self.PROFILE, pg_num=4)
        want = {f"obj{i}": payload(256 * 4 * 2, seed=i) for i in range(12)}
        for oid, data in want.items():
            c1.put(pid, oid, data)
        c1.shutdown()

        c2 = MiniCluster.load(tmp_path)
        pid2 = c2.pool_ids["pool"]
        for oid, data in sorted(want.items()):
            assert c2.get(pid2, oid, len(data)) == data, \
                f"{oid} lost across restart"

    def test_restart_preserves_pg_log(self, tmp_path):
        c1 = MiniCluster(n_osds=12, chunk_size=256, data_dir=tmp_path)
        pid = c1.create_ec_pool("pool", self.PROFILE, pg_num=2)
        for i in range(6):
            c1.put(pid, f"o{i}", payload(1024, seed=i))
        heads = {ps: g.backend.pg_log.head
                 for ps, g in c1.pools[pid]["pgs"].items()}
        c1.shutdown()
        c2 = MiniCluster.load(tmp_path)
        pid2 = c2.pool_ids["pool"]
        for ps, g in c2.pools[pid2]["pgs"].items():
            assert g.backend.pg_log.head == heads[ps], \
                f"pg {ps} log head diverged across restart"

    def test_stale_shard_repairs_on_boot(self, tmp_path):
        """A shard that 'crashed' (went down) and missed writes restarts
        stale; the boot-time repair pass must catch it up via the PG log
        before it serves."""
        c1 = MiniCluster(n_osds=12, chunk_size=256, data_dir=tmp_path)
        pid = c1.create_ec_pool("pool", self.PROFILE, pg_num=1)
        g = c1.pools[pid]["pgs"][0]
        c1.put(pid, "early", payload(2048, seed=1))
        victim = g.acting[1]
        g.bus.mark_down(victim)              # shard dies...
        c1.put(pid, "late", payload(2048, seed=2))       # ...misses writes
        c1.put(pid, "early", payload(2048, seed=3))      # and an overwrite
        c1.shutdown()                        # whole cluster "restarts"

        c2 = MiniCluster.load(tmp_path)      # boot repair runs here
        pid2 = c2.pool_ids["pool"]
        g2 = c2.pools[pid2]["pgs"][0]
        assert not g2.backend.stale
        assert c2.get(pid2, "early", 2048) == payload(2048, seed=3)
        assert c2.get(pid2, "late", 2048) == payload(2048, seed=2)
        # the repaired shard's chunks are bit-identical: scrub everywhere
        for oid in ("early", "late"):
            report = g2.backend.be_deep_scrub(oid)
            bad = {c for c, ok in report.items() if not ok}
            assert not bad, f"{oid}: dirty chunks {bad} after boot repair"

    def test_deep_scrub_clean_after_restart(self, tmp_path):
        c1 = MiniCluster(n_osds=12, chunk_size=256, data_dir=tmp_path)
        pid = c1.create_ec_pool("pool", self.PROFILE, pg_num=2)
        for i in range(6):
            c1.put(pid, f"o{i}", payload(1024, seed=i))
        c1.shutdown()
        c2 = MiniCluster.load(tmp_path)
        pid2 = c2.pool_ids["pool"]
        for i in range(6):
            g = c2.pg_group(pid2, f"o{i}")
            report = g.backend.be_deep_scrub(f"o{i}")
            assert all(report.values())

    def test_crash_mid_write_rolls_back_on_boot(self, tmp_path):
        """The crash window the two-phase design exists for: a write that
        reached only the primary's own store when the process died.  Boot
        peering must count witnesses, see the write persisted on fewer
        than min_size shards, and roll it back — the acked old data must
        read back intact, not a garbage mix of chunk versions."""
        c1 = MiniCluster(n_osds=12, chunk_size=256, data_dir=tmp_path)
        pid = c1.create_ec_pool("pool", self.PROFILE, pg_num=1)
        g = c1.pools[pid]["pgs"][0]
        old = payload(2048, seed=1)
        c1.put(pid, "x", old)                       # acked everywhere
        new = payload(2048, seed=2)
        g2 = c1.put(pid, "x", new, deliver=False)   # submit, then "crash":
        pr = g2.backend.whoami
        while g2.bus.deliver_one(pr):               # only the primary's own
            pass                                    # sub-write applies
        c1.shutdown()                               # process dies here

        c2 = MiniCluster.load(tmp_path)
        pid2 = c2.pool_ids["pool"]
        got = c2.get(pid2, "x", 2048)
        assert got == old, \
            "crash-recovery mixed chunk versions instead of rolling back"
        gg = c2.pools[pid2]["pgs"][0]
        assert all(gg.backend.be_deep_scrub("x").values())
        # and the PG is writable again afterwards
        c2.put(pid2, "x", new)
        assert c2.get(pid2, "x", 2048) == new

    def test_crash_after_full_commit_rolls_forward(self, tmp_path):
        """Converse case: the write persisted on ALL shards but the
        process died before the roll-forward kick.  Boot peering must keep
        it (witnesses >= min_size) and drop the stale rollback data."""
        c1 = MiniCluster(n_osds=12, chunk_size=256, data_dir=tmp_path)
        pid = c1.create_ec_pool("pool", self.PROFILE, pg_num=1)
        c1.put(pid, "x", payload(2048, seed=1))
        g = c1.pools[pid]["pgs"][0]
        new = payload(2048, seed=2)
        g2 = c1.put(pid, "x", new, deliver=False)
        for osd in g2.acting:                       # all sub-writes apply...
            while g2.bus.deliver_one(osd):
                pass
        c1.shutdown()           # ...but acks/kick die with the process

        c2 = MiniCluster.load(tmp_path)
        pid2 = c2.pool_ids["pool"]
        assert c2.get(pid2, "x", 2048) == new, \
            "fully-persisted write was lost on boot"
        gg = c2.pools[pid2]["pgs"][0]
        from ceph_tpu.backend.ec_backend import OSDShard
        for h in gg.bus.handlers.values():
            shard = h if isinstance(h, OSDShard) else h.local_shard
            assert not shard.pending_rollbacks, \
                "stale rollback data survived boot roll-forward"

    @pytest.mark.parametrize("store_backend", ["file", "bluestore"])
    def test_stop_with_a_deferred_kick_owed_drops_it_on_boot(
            self, tmp_path, store_backend):
        """ISSUE 34's twin of the case above: the write was ACKED, and
        its roll-forward kick deferred because other ops waited, when
        the process stopped.  Boot keeps the write and drops the undo
        record (the old chunk) from RAM and from the pgmeta omap."""
        from ceph_tpu.backend.ec_backend import OSDShard
        from ceph_tpu.backend.pg_backend import PG_META

        def shards(g):
            return [h if isinstance(h, OSDShard) else h.local_shard
                    for h in g.bus.handlers.values()]

        def rb_keys(shard):
            return [k for k in shard.store.get_omap(
                GObject(PG_META, shard.shard)) if k.startswith("rb.")]
        c1 = MiniCluster(n_osds=12, chunk_size=256, data_dir=tmp_path,
                         store_backend=store_backend)
        pid = c1.create_ec_pool("pool", self.PROFILE, pg_num=1)
        c1.others_waiting = lambda: True            # a busy server
        c1.put(pid, "x", payload(2048, seed=1))
        new = payload(2048, seed=2)
        acked = []
        c1.put(pid, "x", new, on_commit=acked.append)
        assert acked
        g = c1.pools[pid]["pgs"][0]
        assert g.backend in c1.kicks_owed
        for shard in shards(g):
            assert len(shard.pending_rollbacks) == 1 and rb_keys(shard)
        c1.shutdown()                               # process stops here

        c2 = MiniCluster.load(tmp_path)
        pid2 = c2.pool_ids["pool"]
        assert c2.get(pid2, "x", 2048) == new, "an acked write was lost"
        gg = c2.pools[pid2]["pgs"][0]
        for shard in shards(gg):
            assert not shard.pending_rollbacks, \
                "stale rollback data survived boot roll-forward"
            assert not rb_keys(shard)
        assert all(gg.backend.be_deep_scrub("x").values())
        c2.put(pid2, "x", payload(2048, seed=3))
        assert c2.get(pid2, "x", 2048) == payload(2048, seed=3)
        c2.shutdown()

    def test_writes_after_restart(self, tmp_path):
        c1 = MiniCluster(n_osds=12, chunk_size=256, data_dir=tmp_path)
        pid = c1.create_ec_pool("pool", self.PROFILE, pg_num=2)
        c1.put(pid, "a", payload(1024, seed=1))
        c1.shutdown()
        c2 = MiniCluster.load(tmp_path)
        pid2 = c2.pool_ids["pool"]
        c2.put(pid2, "b", payload(1024, seed=2))          # new write
        c2.put(pid2, "a", payload(1024, seed=3))          # overwrite
        assert c2.get(pid2, "a", 1024) == payload(1024, seed=3)
        assert c2.get(pid2, "b", 1024) == payload(1024, seed=2)
        c2.shutdown()
        c3 = MiniCluster.load(tmp_path)                   # third generation
        pid3 = c3.pool_ids["pool"]
        assert c3.get(pid3, "a", 1024) == payload(1024, seed=3)
        assert c3.get(pid3, "b", 1024) == payload(1024, seed=2)
