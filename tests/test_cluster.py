"""MiniCluster (vstart analog).

Mirrors the standalone EC suite flows (reference:
qa/standalone/erasure-code/test-erasure-code.sh:21-66 — EC pool create over
a cluster of OSDs, put/get, per-plugin rules), plus
ceph_str_hash_rjenkins golden vectors from the reference C
(src/common/ceph_hash.cc:21-78)."""
import numpy as np
import pytest

from ceph_tpu.cluster import MiniCluster
from ceph_tpu.osdmap.str_hash import ceph_str_hash_rjenkins

# golden vectors generated by compiling the reference's ceph_hash.cc
# (same scheme as tests/golden/crush_golden.json)
STR_HASH_GOLDEN = {
    "": 3175731469,
    "a": 703514648,
    "obj": 2864471585,
    "benchmark_data_12345": 1598052875,
    "rbd_data.123456789abcdef.0000000000000001": 3034846263,
    "exactly12byt": 1292985707,
    "thirteen-byte": 2947614770,
    "a-somewhat-longer-object-name-with-more-than-twentyfour-bytes":
        1150909686,
}


def test_str_hash_matches_reference():
    for s, want in STR_HASH_GOLDEN.items():
        assert ceph_str_hash_rjenkins(s) == want, s


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(n_osds=12, chunk_size=128)
    c.create_ec_pool("ecpool", {"plugin": "jax_rs", "k": "4", "m": "2",
                                "device": "numpy",
                                "technique": "reed_sol_van"}, pg_num=8)
    return c


class TestMiniCluster:
    def test_pool_created_with_crush_rule(self, cluster):
        pid = cluster.pool_ids["ecpool"]
        pool = cluster.pools[pid]["pool"]
        assert pool.size == 6
        assert pool.crush_rule in cluster.osdmap.crush.rules
        assert len(cluster.pools[pid]["pgs"]) == 8

    def test_acting_sets_respect_failure_domain(self, cluster):
        """6 chunks over only 4 hosts: create_ec_pool falls back to the osd
        failure domain, so the invariant is 6 distinct acting OSDs."""
        pid = cluster.pool_ids["ecpool"]
        for g in cluster.pools[pid]["pgs"].values():
            assert len(set(g.acting)) == 6

    def test_objects_spread_over_pgs(self, cluster):
        pid = cluster.pool_ids["ecpool"]
        pgs = {cluster.object_pg(pid, f"obj{i}") for i in range(64)}
        assert len(pgs) > 1

    def test_put_get_roundtrip(self, cluster):
        pid = cluster.pool_ids["ecpool"]
        rng = np.random.default_rng(1)
        for i in range(8):
            data = rng.integers(0, 256, size=2048, dtype=np.uint8).tobytes()
            cluster.put(pid, f"rt{i}", data)
            assert cluster.get(pid, f"rt{i}", len(data)) == data

    def test_degraded_read_after_osd_down(self, cluster):
        pid = cluster.pool_ids["ecpool"]
        data = bytes(range(256)) * 8
        cluster.put(pid, "victim", data)
        g = cluster.pg_group(pid, "victim")
        g.bus.mark_down(g.acting[1])
        try:
            assert cluster.get(pid, "victim", len(data)) == data
        finally:
            g.bus.mark_up(g.acting[1])

    def test_status_shape(self, cluster):
        s = cluster.status()
        assert s["osdmap"]["num_osds"] == 12
        assert s["pgmap"]["num_pgs"] == 8

    def test_too_few_osds_rejected(self):
        c = MiniCluster(n_osds=4, chunk_size=128)
        with pytest.raises(RuntimeError):
            c.create_ec_pool("toobig", {"plugin": "jax_rs", "k": "8",
                                        "m": "4", "device": "numpy"})

    def test_host_failure_domain_when_enough_hosts(self):
        c = MiniCluster(n_osds=12, osds_per_host=2, chunk_size=128)
        pid = c.create_ec_pool("hostec", {"plugin": "jax_rs", "k": "3",
                                          "m": "2", "device": "numpy"},
                               pg_num=4)
        # 6 hosts of 2, 5 chunks: every chunk on a different host
        for g in c.pools[pid]["pgs"].values():
            hosts = {osd // 2 for osd in g.acting}
            assert len(hosts) == 5


class TestPerfCounterNames:
    def test_per_pg_perf_counters_distinct(self):
        """PGs sharing a primary OSD must not clobber each other's
        observability registrations (review regression)."""
        from ceph_tpu.common import Context
        cct = Context()
        c = MiniCluster(n_osds=12, chunk_size=128, cct=cct)
        pid = c.create_ec_pool("obs", {"plugin": "jax_rs", "k": "4",
                                       "m": "2", "device": "numpy"},
                               pg_num=8)
        names = {g.backend.perf.name for g in c.pools[pid]["pgs"].values()}
        assert len(names) == 8
        dump = cct.perf.perf_dump()
        assert sum(1 for k in dump
                   if k.startswith(f"ec_backend.c{c.cluster_id}.pg")) == 8
        # a second cluster on the same context must not shadow the first
        c2 = MiniCluster(n_osds=12, chunk_size=128, cct=cct)
        c2.create_ec_pool("obs2", {"plugin": "jax_rs", "k": "4",
                                   "m": "2", "device": "numpy"}, pg_num=8)
        dump = cct.perf.perf_dump()
        assert sum(1 for k in dump
                   if k.startswith("ec_backend.c")) == 16
        # shutdown unhooks everything
        c2.shutdown()
        dump = cct.perf.perf_dump()
        assert sum(1 for k in dump
                   if k.startswith("ec_backend.c")) == 8


def test_status_pg_states():
    """ceph -s pgmap: per-state PG counts track shard availability."""
    from ceph_tpu.cluster import MiniCluster
    c = MiniCluster(n_osds=8, osds_per_host=2, chunk_size=512)
    pid = c.create_ec_pool("st", {"k": "2", "m": "2", "device": "numpy"},
                           pg_num=4)                 # min_size 3 of 4
    st = c.status()["pgmap"]
    assert st["pgs_by_state"] == {"active+clean": 4}
    g = c.pools[pid]["pgs"][0]
    peers = [o for o in g.acting if o != g.backend.whoami]
    g.bus.mark_down(peers[0])                 # 3/4 shards: degraded
    st = c.status()["pgmap"]["pgs_by_state"]
    assert st.get("active+degraded", 0) >= 1
    g.bus.mark_down(peers[1])                 # 2/4 < min_size: inactive
    st = c.status()["pgmap"]["pgs_by_state"]
    assert st.get("inactive", 0) >= 1
    c.shutdown()
