"""Small objects on a k=8 m=4 pool at a small stripe unit (ISSUE 35).

A served put of one, two or three stripes (and of two stripes less a
tail) leaves every shard, its length and its HashInfo crc as the plain
reference (``benchmark/lib/reference.py``) gives them, and a get returns
the payload.  The stores count what the put cost them: one
``transactions`` a committed transaction (twelve sub-writes a put and
its roll-forward kicks), ``block_bytes`` as allocated, ``wal_bytes`` as
the journal files grew, ``txn_ops``; a failed transaction counts
nothing; the collection is in ``perf dump`` while its store lives.
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
COUNTERS = ("transactions", "txn_ops", "block_bytes", "wal_bytes",
            "checkpoints")


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


def test_a_failed_transaction_counts_nothing(tmp_path):
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
