"""Write-availability semantics: min_size gate + two-phase rollback.

Mirrors the reference's EC write durability contract (reference:
doc/dev/osd_internals/erasure_coding/ecbackend.rst:149-206 and the
dummy-transaction rollforward kick at src/osd/ECBackend.cc:2106-2120):

- a write is never acked with fewer than min_size current shards holding it;
- below min_size the PG goes inactive and client writes park, unacked;
- a write that partially applied before shards died ROLLS BACK on the
  survivors (log rewind + inverse transactions), so the old data remains
  the authoritative state;
- once the pipeline drains, the roll-forward point propagates and shards
  drop their rollback data;
- a revived shard is stale (no reads, no write fan-out) until a shard
  repair completes — the PeeringState acting-set semantics.
"""
import numpy as np
import pytest

from ceph_tpu.backend import ECBackend, MessageBus, PGTransaction, StripeInfo
from ceph_tpu.backend.ec_backend import OSDShard, RepairState
from ceph_tpu.backend.memstore import GObject
from ceph_tpu.plugins.registry import ErasureCodePluginRegistry

K, M = 4, 2
N = K + M
CHUNK = 64
STRIPE = K * CHUNK
MIN_SIZE = K + 1


def payload(n, seed=7):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


@pytest.fixture()
def cluster():
    ec = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", {"k": str(K), "m": str(M), "device": "numpy",
                       "technique": "reed_sol_van"})
    bus = MessageBus()
    backend = ECBackend(ec, StripeInfo(K, CHUNK), bus,
                        acting=list(range(N)), whoami=0, min_size=MIN_SIZE)
    for s in range(1, N):
        OSDShard(s, bus)
    return backend, bus


def store_of(bus, backend, shard):
    h = bus.handlers[shard]
    return h.store if isinstance(h, OSDShard) else h.local_shard.store


def shard_obj(bus, backend, shard):
    h = bus.handlers[shard]
    return h if isinstance(h, OSDShard) else h.local_shard


def read_obj(backend, bus, oid, length):
    out = {}
    backend.objects_read_and_reconstruct(
        {oid: [(0, length)]},
        lambda result, errors: out.update(result=result, errors=errors))
    bus.deliver_all()
    if out.get("errors"):
        raise IOError(out["errors"])
    return out["result"][oid][0][2]


class TestMinSizeGate:
    def test_write_parks_below_min_size(self, cluster):
        backend, bus = cluster
        committed = []
        for s in (4, 5):
            bus.mark_down(s)          # current = 4 = k < min_size
        assert not backend.is_active()
        backend.submit_transaction(
            PGTransaction().write("obj", 0, payload(STRIPE)),
            on_commit=committed.append)
        bus.deliver_all()
        assert not committed, "write acked while PG inactive"
        assert len(backend.waiting_state) == 1
        # nothing was dispatched: no shard holds any data
        for s in range(N):
            assert not store_of(bus, backend, s).objects

    def test_parked_write_commits_after_revive_and_repair(self, cluster):
        backend, bus = cluster
        committed = []
        for s in (4, 5):
            bus.mark_down(s)
        backend.submit_transaction(
            PGTransaction().write("obj", 0, payload(STRIPE)),
            on_commit=committed.append)
        bus.deliver_all()
        assert not committed
        bus.mark_up(4)                # auto-repair -> current back to 5
        bus.deliver_all()
        assert committed, "parked write did not re-drive on revival"
        assert read_obj(backend, bus, "obj", STRIPE) == payload(STRIPE)

    def test_active_write_acks_normally(self, cluster):
        backend, bus = cluster
        committed = []
        bus.mark_down(5)              # current = 5 = min_size: still active
        backend.submit_transaction(
            PGTransaction().write("obj", 0, payload(STRIPE)),
            on_commit=committed.append)
        bus.deliver_all()
        assert committed


class TestRollback:
    def _commit_initial(self, backend, bus, data):
        done = []
        backend.submit_transaction(
            PGTransaction().write("obj", 0, data), on_commit=done.append)
        bus.deliver_all()
        assert done
        return done

    def test_partial_write_rolls_back_on_survivors(self, cluster):
        backend, bus = cluster
        data1 = payload(STRIPE, seed=1)
        data2 = payload(STRIPE, seed=2)
        self._commit_initial(backend, bus, data1)
        old_chunks = {s: store_of(bus, backend, s).read(GObject("obj", s))
                      for s in range(N)}

        committed = []
        backend.submit_transaction(
            PGTransaction().write("obj", 0, data2),
            on_commit=committed.append)
        # deliver the sub-writes to shards 1 and 2 only: they APPLY data2
        while bus.deliver_one(1) or bus.deliver_one(2):
            pass
        assert store_of(bus, backend, 1).read(GObject("obj", 1)) != \
            old_chunks[1]
        # shards 3 and 4 die with their sub-writes undelivered:
        # live acks can only reach 4 < min_size 5
        bus.mark_down(3)
        bus.mark_down(4)
        bus.deliver_all()
        assert not committed, "write acked below min_size"
        # survivors rolled back to data1's chunks
        for s in (0, 1, 2, 5):
            assert store_of(bus, backend, s).read(GObject("obj", s)) == \
                old_chunks[s], f"shard {s} kept rolled-back bytes"
        # the authoritative content is still data1
        assert read_obj(backend, bus, "obj", STRIPE) == data1
        # the op is parked, not lost
        assert len(backend.waiting_state) == 1

    def test_rolled_back_write_reexecutes_after_revival(self, cluster):
        backend, bus = cluster
        data1 = payload(STRIPE, seed=1)
        data2 = payload(STRIPE, seed=2)
        self._commit_initial(backend, bus, data1)
        committed = []
        backend.submit_transaction(
            PGTransaction().write("obj", 0, data2),
            on_commit=committed.append)
        while bus.deliver_one(1) or bus.deliver_one(2):
            pass
        bus.mark_down(3)
        bus.mark_down(4)
        bus.deliver_all()
        assert not committed
        bus.mark_up(3)                # repair -> active -> re-execute
        bus.deliver_all()
        assert committed, "rolled-back write did not re-execute"
        assert read_obj(backend, bus, "obj", STRIPE) == data2
        # version reuse is clean: log head advanced exactly once per write
        assert backend.pg_log.head == 2

    def test_rollback_restores_log_and_hinfo(self, cluster):
        backend, bus = cluster
        data1 = payload(STRIPE, seed=1)
        self._commit_initial(backend, bus, data1)
        head_before = backend.pg_log.head
        hinfo_version = backend._hinfo("obj").version
        committed = []
        backend.submit_transaction(
            PGTransaction().write("obj", 0, payload(STRIPE, seed=2)),
            on_commit=committed.append)
        while bus.deliver_one(1):
            pass
        bus.mark_down(3)
        bus.mark_down(4)
        bus.deliver_all()
        assert backend.pg_log.head == head_before
        assert backend._hinfo("obj").version == hinfo_version

    def test_roll_forward_drops_rollback_data(self, cluster):
        backend, bus = cluster
        self._commit_initial(backend, bus, payload(STRIPE))
        # commit + drain: the rollforward kick must reach every shard
        for s in range(N):
            assert not shard_obj(bus, backend, s).pending_rollbacks, \
                f"shard {s} still holds rollback data after drain"

    def test_rollback_spares_the_committed_write_whose_kick_was_deferred(
            self, cluster):
        """ISSUE 34: while other ops wait, a drained pipeline announces
        its roll-forward point with the PG's next sub-write instead of a
        kick.  A later write that falls below min_size unwinds itself
        alone: the committed write under it stays, kick or no kick."""
        backend, bus = cluster
        backend.defer_kick = lambda b: True          # somebody always waits
        data1 = payload(STRIPE, seed=1)
        self._commit_initial(backend, bus, data1)
        assert backend.perf.get("rollforward_deferred") == 1
        assert backend.perf.get("rollforward_kicks") == 0
        for s in range(N):                  # v1's undo record: still held
            assert list(shard_obj(bus, backend, s).pending_rollbacks) == [1]
        old_chunks = {s: store_of(bus, backend, s).read(GObject("obj", s))
                      for s in range(N)}
        committed = []
        backend.submit_transaction(
            PGTransaction().write("obj", 0, payload(STRIPE, seed=2)),
            on_commit=committed.append)
        while bus.deliver_one(1) or bus.deliver_one(2):
            pass
        # the sub-write carried the point: v1's record went inside its
        # transaction, v2's took its place
        for s in (1, 2):
            assert list(shard_obj(bus, backend, s).pending_rollbacks) == [2]
        bus.mark_down(3)
        bus.mark_down(4)
        bus.deliver_all()
        assert not committed, "write acked below min_size"
        for s in (0, 1, 2, 5):
            assert store_of(bus, backend, s).read(GObject("obj", s)) == \
                old_chunks[s], f"shard {s} lost the committed write"
            assert not shard_obj(bus, backend, s).pending_rollbacks
        assert read_obj(backend, bus, "obj", STRIPE) == data1
        assert backend.pg_log.head == 1 and backend.committed_to == 1
        assert len(backend.waiting_state) == 1      # parked, not lost
        bus.mark_up(3)
        bus.deliver_all()
        assert committed
        assert read_obj(backend, bus, "obj", STRIPE) == payload(STRIPE,
                                                                seed=2)

    def test_deep_scrub_clean_after_rollback_cycle(self, cluster):
        backend, bus = cluster
        data1 = payload(STRIPE, seed=1)
        data2 = payload(STRIPE, seed=2)
        self._commit_initial(backend, bus, data1)
        committed = []
        backend.submit_transaction(
            PGTransaction().write("obj", 0, data2),
            on_commit=committed.append)
        while bus.deliver_one(1) or bus.deliver_one(2):
            pass
        bus.mark_down(3)
        bus.mark_down(4)
        bus.deliver_all()              # rollback
        bus.mark_up(3)
        bus.deliver_all()              # repair + re-execute
        bus.mark_up(4)
        bus.deliver_all()              # repair shard 4 (missed data2)
        assert committed
        report = backend.be_deep_scrub("obj")
        bad = {c for c, clean in report.items() if not clean}
        assert not bad, f"inconsistent chunks after rollback cycle: {bad}"


class TestStaleShards:
    def test_revived_shard_excluded_until_repaired(self, cluster):
        backend, bus = cluster
        data = payload(STRIPE)
        done = []
        backend.submit_transaction(PGTransaction().write("obj", 0, data),
                                   on_commit=done.append)
        bus.deliver_all()
        bus.mark_down(5)
        # a write lands while 5 is down
        backend.submit_transaction(
            PGTransaction().write("obj", 0, payload(STRIPE, seed=9)))
        bus.deliver_all()
        bus.mark_up(5)
        assert 5 in backend.stale
        assert 5 not in backend.current_shards()
        bus.deliver_all()              # auto-repair replays the missed write
        assert 5 not in backend.stale
        assert 5 in backend.current_shards()
        report = backend.be_deep_scrub("obj")
        assert all(report.values())

    def test_stale_shard_not_in_write_fanout(self, cluster):
        backend, bus = cluster
        bus.mark_down(5)
        bus.mark_up(5)                 # up but stale (repair still queued)
        committed = []
        backend.submit_transaction(
            PGTransaction().write("obj", 0, payload(STRIPE)),
            on_commit=committed.append)
        # dispatch happened at submit; shard 5 must not have a sub-write
        from ceph_tpu.backend.messages import ECSubWrite
        assert not any(isinstance(m, ECSubWrite) and m.log_entries
                       for m in bus.queues.get(5, ())), \
            "stale shard received new-write fan-out"
        bus.deliver_all()
        assert committed
