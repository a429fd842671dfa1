"""Small objects on a k=8 m=4 pool at a small stripe unit (ISSUE 35).

A served put of one, two or three stripes (and of two stripes less a
tail) leaves every shard, its length and its HashInfo crc as the plain
reference (``benchmark/lib/reference.py``) gives them, and a get returns
the payload.  The stores count what the put cost them: one
``transactions`` a committed transaction (twelve sub-writes a put and
its roll-forward kicks), ``block_bytes`` as allocated, ``wal_bytes`` as
the journal files grew, ``txn_ops``; a failed transaction counts
nothing; the collection is in ``perf dump`` while its store lives.
Since ISSUE 37 they also say where a commit's wall time went: four
phases of one clock that tile the ``store.commit`` span, the thread's
CPU clock over the same stretch, and the checkpoints' time —
from one transaction in ``BlueStoreLite.TIMED_EVERY``, booked that many
times (the CPU clock is dear where the chip is).
CPU, tiny sizes: counts and correctness only.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.lib import reference  # noqa: E402
from ceph_tpu.backend.bluestore import BlueStoreLite  # noqa: E402
from ceph_tpu.backend.ecutil import HINFO_KEY  # noqa: E402
from ceph_tpu.backend.memstore import GObject, Transaction  # noqa: E402
from ceph_tpu.backend.pg_backend import shard_store  # noqa: E402
from ceph_tpu.cluster import MiniCluster  # noqa: E402
from ceph_tpu.net import ClusterServer, TcpRados  # noqa: E402

K, M = 8, 4
CHUNK = 512
WIDTH = K * CHUNK
MIN_ALLOC = 4096
PROFILE = {"plugin": "jax_rs", "k": str(K), "m": str(M),
           "technique": "cauchy", "device": "jax"}
SIZES = {"one_stripe": WIDTH, "two_stripes": 2 * WIDTH,
         "three_stripes": 3 * WIDTH, "two_stripes_less_100": 2 * WIDTH - 100}
PHASES = ("stage_us", "record_us", "block_io_us", "wal_io_us")
COUNTERS = ("transactions", "read_bytes", "read_block_bytes",
            "commits_queued", "txn_ops", "block_bytes",
            "wal_bytes", "checkpoints") + PHASES + ("commit_cpu_us",
                                                    "checkpoint_us")


def _payload(n, seed):
    return np.random.default_rng([seed, 35]).integers(
        0, 256, n, dtype=np.uint8).tobytes()


class Served:
    def __init__(self, tmp_path):
        self.dir = tmp_path
        self.c = MiniCluster(n_osds=K + M, osds_per_host=1, chunk_size=CHUNK,
                             data_dir=tmp_path, store_backend="bluestore")
        self.serving = self.c.enable_serving(start=True)
        self.server = ClusterServer(self.c)
        self.server.start()
        self.r = TcpRados("127.0.0.1", self.server.port,
                          tmp_path / "client.admin.keyring")
        self.pool = self.r.mkpool("p", profile=dict(PROFILE), pg_num=4)
        self.parity = reference.cauchy_parity_matrix(K, M)

    def close(self):
        self.r.close()
        self.server.stop()
        self.serving.stop()
        self.c.shutdown()

    def stores(self):
        """perf dump's `bluestore.*` collections of this cluster, summed."""
        prefix = f"bluestore.c{self.c.cluster_id}."
        dump = self.c.cct.perf.perf_dump()
        mine = [v for name, v in dump.items() if name.startswith(prefix)]
        assert len(mine) == K + M
        return {key: sum(v[key] for v in mine) for key in COUNTERS}

    def backends(self, key):
        prefix = f"ec_backend.c{self.c.cluster_id}."
        return sum(v[key] for name, v in self.c.cct.perf.perf_dump().items()
                   if name.startswith(prefix))

    def wal_files(self):
        return sum(f.stat().st_size for f in self.dir.glob("osd.*/store/kv.log"))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    s = Served(tmp_path_factory.mktemp("small"))
    for i, n in enumerate(SIZES.values()):      # compiles outside the tests
        s.r.put("p", f"warm.{i}", _payload(n, 100 + i))
    yield s
    s.close()


@pytest.mark.parametrize("size", SIZES)
def test_every_shard_its_length_and_its_crc_equal_the_reference(size, served):
    n = SIZES[size]
    data = _payload(n, n)
    oid = f"obj.{size}"
    served.r.put("p", oid, data)
    shards = reference.object_shards(np.frombuffer(data, dtype=np.uint8),
                                     K, served.parity, CHUNK)
    crcs = reference.crc32c_rows(shards)
    stripes = -(-n // WIDTH)
    assert shards.shape == (K + M, stripes * CHUNK)
    with served.server.lock:
        g = served.c.pg_group(served.pool, oid)
        assert len(g.acting) == K + M
        for chunk, osd in enumerate(g.acting):
            store = shard_store(g.bus, osd)
            stored = store.read(GObject(oid, osd))
            hinfo = store.getattr(GObject(oid, osd), HINFO_KEY)
            # exactly stripes x stripe unit: no padding beyond the stripe
            assert len(stored) == stripes * CHUNK
            assert hinfo["total_chunk_size"] == stripes * CHUNK
            assert bytes(stored) == shards[chunk].tobytes(), chunk
            assert int(hinfo["cumulative_shard_hashes"][chunk]) == \
                int(crcs[chunk]), chunk
    assert served.r.get("p", oid) == data


@pytest.mark.parametrize("size", SIZES)
def test_the_store_counters_rise_by_what_a_put_cost_the_stores(size, served):
    n = SIZES[size]
    data = _payload(n, n + 1)
    stripes = -(-n // WIDTH)
    before, kicks, written, wal = (served.stores(),
                                   served.backends("rollforward_kicks"),
                                   served.backends("write_bytes"),
                                   served.wal_files())
    served.r.put("p", f"counted.{size}", data)
    after = served.stores()
    rose = {key: after[key] - before[key] for key in COUNTERS}
    kicks = served.backends("rollforward_kicks") - kicks
    # a serial client's put leaves nobody waiting: every shard's
    # sub-write commits, then its standalone roll-forward kick
    assert kicks == K + M
    assert rose["transactions"] == (K + M) + kicks
    assert rose["txn_ops"] >= rose["transactions"]
    # the shards as the reference sizes them, in whole allocation units;
    # the kicks and the metadata write nothing to the block file
    shard = reference.object_shards(np.frombuffer(data, dtype=np.uint8),
                                    K, served.parity, CHUNK).shape[1]
    assert shard == stripes * CHUNK
    assert rose["block_bytes"] == (K + M) * -(-shard // MIN_ALLOC) * MIN_ALLOC
    # the journal's records, as the twelve kv.log files grew
    assert rose["wal_bytes"] == served.wal_files() - wal > 0
    # what store_bytes_per_put_byte divides by: the payload as the
    # client sent it, the tail stripe's padding not counted
    assert served.backends("write_bytes") - written == n


def _fifty_puts(served, tag, monkeypatch):
    """What fifty puts added: each committed transaction's
    ``store.commit`` span (us, in order), each timed transaction's
    phases as its clock booked them on the caller's thread (us, in
    order: all four for a synchronous commit, staging and record for a
    queued one), the I/O phases of the timed queued ones as the group
    commit booked them from the native call's clocks (us), the stores'
    counters."""
    from ceph_tpu.common.perf_counters import PerfCounters
    from ceph_tpu.common.tracer import default_tracer
    clock = PerfCounters._PhaseClock
    timed, real = [], clock.commit
    booked, real_booked = [], clock.commit_booked

    def spy(self, weight=1):
        if self.on:
            timed.append(sum(self.sums.values()) * 1e6)
        real(self, weight)

    def spy_booked(self, phases, cpu_ns, weight=1):
        booked.append(sum(phases.values()) * 1e6)
        real_booked(self, phases, cpu_ns, weight)
    monkeypatch.setattr(clock, "commit", spy)
    monkeypatch.setattr(clock, "commit_booked", spy_booked)
    tr = default_tracer()
    done = tr.histograms().get("store.commit", {"count": 0})["count"]
    before = served.stores()
    data = _payload(2 * WIDTH, 37)
    for i in range(50):
        served.r.put("p", f"{tag}.{i}", data)
    monkeypatch.setattr(clock, "commit", real)
    monkeypatch.setattr(clock, "commit_booked", real_booked)
    rose = {key: served.stores()[key] - before[key] for key in COUNTERS}
    n = tr.histograms()["store.commit"]["count"] - done
    # this cluster's alone (the tracer is the process's: no other store
    # commits meanwhile), serial under the cluster lock: by start time
    spans = [e["dur"] for e in sorted(
        (e for e in tr.dump()["traceEvents"] if e["name"] == "store.commit"),
        key=lambda e: e["ts"])][-n:]
    assert len(spans) == n == rose["transactions"] >= 50 * (K + M)
    # every sub-write's commit is queued, its reply sent from its callback
    assert rose["commits_queued"] == 50 * (K + M)
    return spans, timed, booked, rose


def _less_the_stalls(us):
    """The sum without its largest fiftieth: a busy machine takes the
    core away inside a few commits, for milliseconds."""
    kept = sorted(us)[:len(us) - max(1, len(us) // 50)]
    return sum(kept) / len(kept) * len(us)


def test_the_four_phases_tile_the_commit_spans_of_fifty_puts(served,
                                                             monkeypatch):
    monkeypatch.setattr(BlueStoreLite, "TIMED_EVERY", 1)
    spans, timed, booked, rose = _fifty_puts(served, "tiled", monkeypatch)
    assert len(timed) == len(spans)
    assert len(booked) == rose["commits_queued"]
    # what the clock and the group commit booked is what the counters
    # rose by (each phase rounds to a microsecond)
    phases = sum(rose[p] for p in PHASES)
    assert phases == pytest.approx(sum(timed) + sum(booked),
                                   abs=2.0 * (len(spans) + len(booked)))
    assert all(rose[p] > 0 for p in PHASES)
    # consecutive marks of one clock, from the span's first statement
    # to its last: a timed span is its phases and the two reads of the
    # CPU clock around them (a microsecond or two each here, of commits
    # of some fifty), which no phase holds.  A read is a system call,
    # where a busy machine takes the core away: the median commit is
    # held to it, and every commit to its phases lying inside its span
    around = [span - inside for span, inside in zip(spans, timed)]
    assert min(around) > -1.0
    assert sorted(around)[len(around) // 2] <= 25.0
    # the same stretch by the thread's CPU clock, read outside the wall
    # clock's reads: a part of the phases, or where the thread ran
    # throughout, all of them and the CPU of a read besides
    assert 0 < rose["commit_cpu_us"] <= phases + 25.0 * len(spans)
    # no checkpoint, no checkpoint time (a store's 512th record is far)
    assert rose["checkpoints"] == rose["checkpoint_us"] == 0


def test_one_transaction_in_many_is_timed_and_stands_for_them(served,
                                                              monkeypatch):
    every = BlueStoreLite.TIMED_EVERY
    assert every == 17
    spans, timed, booked, rose = _fifty_puts(served, "sampled",
                                             monkeypatch)
    # one in seventeen a store, and every counter of a timed transaction
    # booked seventeen-fold (each rounds to a microsecond before it is
    # multiplied)
    assert len(spans) - every * (K + M) < every * len(timed) \
        < len(spans) + every * (K + M)
    phases = sum(rose[p] for p in PHASES)
    assert phases % every == 0 and rose["commit_cpu_us"] % every == 0
    assert phases == pytest.approx(
        every * (sum(timed) + sum(booked)),
        abs=2.0 * every * (len(timed) + len(booked)))
    assert 0 < rose["commit_cpu_us"] <= phases + 25.0 * len(spans)
    # an estimate of all the spans from some seventy of them.  The
    # stride is odd, so a store's timed transactions are sub-writes and
    # roll-forward kicks by turns (each put here is both, on each
    # store): an even stride times one kind only and reads up to twice
    # the spans
    assert 0.6 * _less_the_stalls(spans) \
        <= every * _less_the_stalls(timed) \
        <= 1.6 * _less_the_stalls(spans)


def test_the_512th_record_checkpoints_and_its_time_is_counted(tmp_path):
    store = BlueStoreLite(tmp_path / "s")
    obj = GObject("o", 0)
    for i in range(511):
        store.queue_transaction(Transaction().setattr(obj, "a", i))
    counted = store.perf.dump()
    assert counted["checkpoints"] == counted["checkpoint_us"] == 0
    store.queue_transaction(Transaction().setattr(obj, "a", 511))
    counted = store.perf.dump()
    assert counted["checkpoints"] == 1 and counted["checkpoint_us"] > 0
    assert counted["transactions"] == 512
    # a transaction that writes no data makes no block call (blobs are
    # written unbuffered, at their offsets: nothing waits for a flush
    # since PR 38), and its journal record is timed (30 of the 512 were)
    assert counted["block_bytes"] == 0 and counted["wal_io_us"] > 0
    assert counted["block_io_us"] <= counted["wal_io_us"]
    store.close()


def test_a_failed_transaction_counts_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(BlueStoreLite, "TIMED_EVERY", 1)
    store = BlueStoreLite(tmp_path / "s")
    obj = GObject("o", 0)
    store.queue_transaction(Transaction().write(obj, 0, b"x" * 5000)
                            .setattr(obj, "a", 1))
    counted = store.perf.dump()
    assert counted["transactions"] == 1 and counted["txn_ops"] == 2
    assert counted["block_bytes"] == 2 * MIN_ALLOC
    assert counted["wal_bytes"] == (tmp_path / "s" / "kv.log").stat().st_size
    bad = Transaction().write(obj, 0, b"y" * 100)
    bad.ops.append(("no_such_op", obj))
    with pytest.raises(ValueError):
        store.queue_transaction(bad)
    assert store.perf.dump() == counted
    assert store.read(obj) == b"x" * 5000
    store.close()


def test_the_collection_is_in_perf_dump_while_its_store_lives(tmp_path):
    from ceph_tpu.mgr import prometheus
    c = MiniCluster(n_osds=3, osds_per_host=1, chunk_size=CHUNK,
                    data_dir=tmp_path, store_backend="bluestore")
    names = [f"bluestore.c{c.cluster_id}.osd{o}" for o in range(3)]
    try:
        dump = c.cct.perf.perf_dump()
        for name in names:
            assert set(dump[name]) == set(COUNTERS)
        scrape = prometheus.render(c.cct)
        for key in COUNTERS:
            assert f'ceph_tpu_{key}{{collection="{names[0]}"}} 0' in scrape
    finally:
        c.shutdown()
    assert not set(names) & set(c.cct.perf.perf_dump())
    # a store nobody gave a Context counts for itself and registers nothing
    store = BlueStoreLite(tmp_path / "alone")
    assert store.perf.name == "bluestore.alone"
    assert store.perf.name not in c.cct.perf.perf_dump()
    store.close()
