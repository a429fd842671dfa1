"""A put's journal does not grow with its PG's log (ISSUE 36).

A store transaction journals what it changed: the 300th put to a PG
appends the bytes the 1st did (whole-onode records read 15 / 345 /
1,357 KB for the 1st / 100th / 400th, ``PERF.md`` PR 35), and after
kill -9 — the stores dropped without ``close``, so the journals are all
there is — boot finds each acked put's ``log.`` key and the one ``rb.``
key a busy PG owes.  CPU, tiny sizes: counts and correctness only.
"""
import numpy as np
import pytest

from ceph_tpu.backend.bluestore import BlueStoreLite
from ceph_tpu.backend.collection import COLL_SEP
from ceph_tpu.backend.pg_backend import PG_META, OSDShard
from ceph_tpu.cluster import MiniCluster
from ceph_tpu.net import ClusterServer, TcpRados

K, M = 2, 1
N = K + M
PROFILE = {"plugin": "jax_rs", "k": str(K), "m": str(M),
           "technique": "cauchy", "device": "jax"}
SIZE = 4096
PUTS = 300


def _data(seed, n=SIZE):
    return np.random.default_rng([seed, 36]).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _wal_bytes(c):
    prefix = f"bluestore.c{c.cluster_id}."
    return sum(v["wal_bytes"] for name, v in c.cct.perf.perf_dump().items()
               if name.startswith(prefix))


def test_the_300th_put_to_a_pg_journals_what_the_first_did(tmp_path):
    c = MiniCluster(n_osds=N, osds_per_host=1, chunk_size=1024,
                    data_dir=tmp_path, store_backend="bluestore")
    serving = c.enable_serving(start=True)
    server = ClusterServer(c)
    server.start()
    r = TcpRados("127.0.0.1", server.port, tmp_path / "client.admin.keyring")
    try:
        r.mkpool("p", profile=dict(PROFILE), pg_num=1)
        r.put("p", "warm", _data(0))
        journaled = []
        for i in range(1, PUTS + 1):
            before = _wal_bytes(c)
            r.put("p", f"o{i:04d}", _data(i))
            journaled.append(_wal_bytes(c) - before)
        g = c.pools[c.pool_ids["p"]]["pgs"][0]
        assert g.backend.pg_log.head - g.backend.pg_log.tail == PUTS + 1
        first, last = journaled[0], journaled[-1]
        # a serial client's put: N sub-writes and N roll-forward kicks
        assert first > 0 and abs(last - first) <= 0.10 * first, (first, last)
        assert max(journaled) <= 1.10 * first
        assert last < 10 * 1024 < 345 * 1024
        assert r.get("p", f"o{PUTS:04d}") == _data(PUTS)
    finally:
        r.close()
        server.stop()
        serving.stop()
        c.shutdown()


def test_after_kill_9_boot_finds_every_log_key_and_the_one_owed_rb_key(
        tmp_path):
    puts = 40
    c = MiniCluster(n_osds=N, osds_per_host=1, chunk_size=1024,
                    data_dir=tmp_path, store_backend="bluestore")
    pid = c.create_ec_pool("p", dict(PROFILE), pg_num=1)
    c.others_waiting = lambda: True     # a busy pool: every drain defers
    want = {f"o{i:03d}": _data(100 + i) for i in range(puts)}
    for oid, data in want.items():
        c.put(pid, oid, data)           # acked
    g = c.pools[pid]["pgs"][0]
    head = g.backend.pg_log.head
    assert head == puts
    del c, g                            # kill -9: no shutdown, no checkpoint

    for osd in range(N):                # what is on disk, read raw
        store = BlueStoreLite(tmp_path / f"osd.{osd}" / "store")
        assert not (tmp_path / f"osd.{osd}" / "store" / "kv.snap").exists()
        meta = [o for o in store.list_objects()
                if o.oid.endswith(COLL_SEP + PG_META)]
        assert len(meta) == 1
        keys = list(store.get_omap(meta[0]))
        assert sorted(k for k in keys if k.startswith("log.")) == \
            [f"log.{v:016d}" for v in range(1, head + 1)]
        assert [k for k in keys if k.startswith("rb.")] == \
            [f"rb.{head:016d}"]
        store.close(checkpoint=False)

    c2 = MiniCluster.load(tmp_path)
    try:
        g = c2.pools[c2.pool_ids["p"]]["pgs"][0]
        shards = [h if isinstance(h, OSDShard) else h.local_shard
                  for h in g.bus.handlers.values()]
        assert len(shards) == N
        for shard in shards:
            assert shard.pg_log.head == head
            assert set(shard.pending_rollbacks) <= {head}
        for oid, data in want.items():
            assert c2.get(c2.pool_ids["p"], oid, SIZE) == data
            assert all(g.backend.be_deep_scrub(oid).values())
    finally:
        c2.shutdown()
