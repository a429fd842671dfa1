"""A served get on a degraded k=8 m=4 pool (ISSUE 33): two OSDs that are
primary of no PG are down and not out, so a get whose PG lost a data
chunk reconstructs it through the serving engine and the codec pipeline.

What a get returns is held to ``benchmark/lib/reference.py`` (numpy
only): ``decode_matrix`` and ``gf_apply`` over the k shards the backend
chose, laid out as the object.  The counters and the span tag the
deployment's metrics read are held to exact counts, and a clean get
leaves all of them, and ``device_attribution``'s ``batches``, alone.
CPU, small sizes.
"""
import itertools

import numpy as np
import pytest

from benchmark.lib import reference
from ceph_tpu.backend.ec_backend import ECBackend
from ceph_tpu.cluster import MiniCluster
from ceph_tpu.common import device_attribution
from ceph_tpu.common.tracer import default_tracer
from ceph_tpu.mgr.stats import StatsAggregator

K, M = 8, 4
CHUNK = 1024
SIZE = 3 * K * CHUNK - 100          # three stripes, the last one padded
PROFILE = {"plugin": "jax_rs", "k": str(K), "m": str(M),
           "technique": "cauchy", "device": "jax"}
PARITY = reference.cauchy_parity_matrix(K, M)
COUNTERS = ("reads", "reads_reconstructed", "chunks_reconstructed")


def _payload(i):
    return np.random.default_rng([33, i]).integers(
        0, 256, SIZE, dtype=np.uint8).tobytes()


class Pool:
    """A cluster with the serving engine started, one EC pool and 24
    seeded objects, put with every OSD up."""

    def __init__(self, data_dir):
        self.c = MiniCluster(n_osds=K + M, osds_per_host=1,
                             chunk_size=CHUNK, data_dir=data_dir)
        self.serving = self.c.enable_serving(start=True)
        self.pid = self.c.create_ec_pool("p", dict(PROFILE), pg_num=8)
        self.pgs = list(self.c.pools[self.pid]["pgs"].values())
        self.objects = {f"obj.{i:02d}": _payload(i) for i in range(24)}
        for oid, data in self.objects.items():
            self.c.put(self.pid, oid, data)
        self.down: tuple = ()

    def lost(self, g, down=None) -> list[int]:
        """The data chunks of PG ``g`` that live on down OSDs."""
        down = self.down if down is None else down
        return [chunk for chunk, osd in enumerate(g.acting)
                if osd in down and chunk < K]

    def mark_two_down(self) -> None:
        """Two OSDs that are primary of no PG, chosen so that the pool
        has PGs that lost two data chunks, one, and none."""
        primaries = {g.backend.whoami for g in self.pgs}
        spare = [o for o in range(K + M) if o not in primaries]
        for pair in itertools.combinations(spare, 2):
            if {len(self.lost(g, pair)) for g in self.pgs
                    if self.objects_of(g)} == {0, 1, 2}:
                self.down = pair
                break
        else:
            pytest.fail("no pair of non-primary OSDs gives all three kinds")
        for g in self.pgs:
            for osd in self.down:
                g.bus.mark_down(osd)

    def objects_of(self, g) -> list[str]:
        return [oid for oid in self.objects
                if self.c.pg_group(self.pid, oid) is g]

    def counters(self) -> dict:
        return {name: sum(g.backend.perf.get(name) for g in self.pgs)
                for name in COUNTERS}

    def get(self, oid) -> bytes:
        return self.c.get(self.pid, oid, SIZE)

    def close(self):
        self.serving.stop()
        self.c.shutdown()


@pytest.fixture
def pool(tmp_path):
    p = Pool(tmp_path)
    yield p
    p.close()


def _batches() -> int:
    return int(device_attribution.perf_counters().get("batches"))


def _reference_get(payload: bytes, chosen: list[int]) -> bytes:
    """The object as the reference rebuilds it from the shards
    ``chosen`` (chunk indices) alone."""
    shards = reference.object_shards(np.frombuffer(payload, dtype=np.uint8),
                                     K, PARITY, CHUNK)
    data = {c: shards[c] for c in chosen if c < K}
    erased = [c for c in range(K) if c not in data]
    if erased:
        mat, src = reference.decode_matrix(PARITY, erased, available=chosen)
        for c, row in zip(erased, reference.gf_apply(mat, shards[src])):
            data[c] = row
    rows = np.stack([data[c] for c in range(K)])
    stripes = rows.shape[1] // CHUNK
    return rows.reshape(K, stripes, CHUNK).transpose(1, 0, 2) \
        .tobytes()[:len(payload)]


def test_every_get_of_a_degraded_pool_equals_the_references_answer(
        pool, monkeypatch):
    pool.mark_two_down()
    chosen = []
    real = ECBackend._serving_decode
    monkeypatch.setattr(
        ECBackend, "_serving_decode",
        lambda self, by_chunk: (chosen.append(sorted(by_chunk)),
                                real(self, by_chunk))[1])
    kinds = set()
    for g in pool.pgs:
        lost = pool.lost(g)
        for oid in pool.objects_of(g):
            got = pool.get(oid)
            shards = chosen.pop()
            assert not chosen and len(shards) == K        # exactly k read
            assert not set(shards) & {c for c, osd in enumerate(g.acting)
                                      if osd in pool.down}
            assert [c for c in range(K) if c not in shards] == lost
            assert got == _reference_get(pool.objects[oid], shards)
            assert got == pool.objects[oid]
            kinds.add(len(lost))
    assert kinds == {0, 1, 2}
    assert pool.serving.pipeline.perf.get("host_fallbacks") == 0


def test_the_reconstruction_counters_rise_by_exactly_what_was_decoded(pool):
    before = pool.counters()
    for oid in pool.objects:
        pool.get(oid)
    clean = pool.counters()
    assert clean == {**before, "reads": before["reads"] + len(pool.objects)}

    pool.mark_two_down()
    lost = [len(pool.lost(pool.c.pg_group(pool.pid, oid)))
            for oid in pool.objects]
    for oid in pool.objects:
        pool.get(oid)
    after = pool.counters()
    assert after["reads"] - clean["reads"] == len(pool.objects)
    assert after["reads_reconstructed"] == sum(1 for n in lost if n) > 0
    assert after["chunks_reconstructed"] == sum(lost)
    assert sum(lost) > after["reads_reconstructed"]       # some lost two


def test_the_decode_span_of_a_client_read_carries_its_erasure_count(pool):
    pool.mark_two_down()
    want = {oid: len(pool.lost(pool.c.pg_group(pool.pid, oid)))
            for oid in pool.objects}
    for oid in pool.objects:
        pool.get(oid)
    default_tracer().flush()
    seen = {}
    for e in default_tracer().dump(stitched=False)["traceEvents"]:
        args = e.get("args", {})
        if e.get("name") == "ec.decode" and args.get("oid") in want \
                and args.get("kind") == "client_read":
            seen[args["oid"]] = args["erasures"]          # the latest read
    assert seen == want


def test_a_clean_get_records_no_device_batch_and_a_decoding_get_one(pool):
    perf = pool.serving.pipeline.perf
    oid = next(iter(pool.objects))
    marks = (_batches(), perf.get("completed"), perf.get("device_dispatches"))
    assert pool.get(oid) == pool.objects[oid]
    assert (_batches(), perf.get("completed") - 1,
            perf.get("device_dispatches")) == marks       # host-only item

    pool.mark_two_down()
    decoding = [o for o in pool.objects
                if pool.lost(pool.c.pg_group(pool.pid, o))]
    before = _batches()
    for n, o in enumerate(decoding, 1):
        assert pool.get(o) == pool.objects[o]
        assert _batches() == before + n
    clean = [o for o in pool.objects if o not in decoding]
    assert clean
    for o in clean:
        pool.get(o)
    assert _batches() == before + len(decoding)


def test_the_stats_digests_batch_rate_reads_device_batches_only(pool):
    agg = StatsAggregator(cct=pool.c.cct, name="t")
    try:
        agg.sample(now=0.0)
        for oid in pool.objects:
            pool.get(oid)
        agg.sample(now=1.0)
        # the coalescer ran a batch a get; the chip ran none
        assert agg.counter_delta("batches", ("serving.",)) == \
            len(pool.objects)
        assert agg.digest()["serving"]["batch_s"] == 0

        pool.mark_two_down()
        decoding = sum(1 for o in pool.objects
                       if pool.lost(pool.c.pg_group(pool.pid, o)))
        agg2 = StatsAggregator(cct=pool.c.cct, name="t2")
        try:
            agg2.sample(now=0.0)
            for oid in pool.objects:
                pool.get(oid)
            agg2.sample(now=1.0)
            assert agg2.digest()["serving"]["batch_s"] == decoding
        finally:
            agg2.close()
    finally:
        agg.close()
