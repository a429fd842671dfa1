"""ClayCodec, the device codec of the clay plugin, on the CPU: bit for bit
against the plain reference (``benchmark/lib/clay_reference.py``), the
plugin's host loop (device=numpy) and the committed corpus, at geometries
with and without shortened nodes and aloof helpers; one dispatch a call
whatever the stripe count; and a MiniCluster Clay pool with device=jax
whose put, degraded get and one-OSD recovery go through it."""
import hashlib
import itertools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.lib.clay_reference import Clay  # noqa: E402
from ceph_tpu.ops import clay_kernels  # noqa: E402
from ceph_tpu.plugins import ErasureCodePluginRegistry  # noqa: E402

# (k, m, d): the test plugin's default, the benchmark's deployment, one
# with aloof nodes in a repair (d < k+m-1), one with a shortened node
GEOMETRIES = [(4, 2, 5), (8, 4, 11), (8, 4, 9), (3, 2, 4)]
SC = 8              # bytes a sub-chunk
STRIPES = 3


def _plugin(k, m, d, device, technique="cauchy"):
    return ErasureCodePluginRegistry.instance().factory(
        "clay", "", {"k": str(k), "m": str(m), "d": str(d),
                     "scalar_mds": "jax_rs", "technique": technique,
                     "device": device})


def _dispatches():
    return clay_kernels.perf_counters().dump()["device_dispatches"]


@pytest.fixture(scope="module", params=GEOMETRIES,
                ids=lambda g: "k%d_m%d_d%d" % g)
def geometry(request):
    """(plugin with device=jax, reference, chunk bytes, data, codeword)."""
    k, m, d = request.param
    ec, ref = _plugin(k, m, d, "jax"), Clay(k, m, d)
    chunk = ref.planes * SC
    rng = np.random.default_rng(k * 100 + m * 10 + d)
    data = rng.integers(0, 256, (k, STRIPES * chunk), dtype=np.uint8)
    return ec, ref, chunk, data, ref.codeword(data, chunk)


def test_encode_equals_the_reference(geometry):
    ec, ref, chunk, data, full = geometry
    assert ec.codec is not None and ec.codec.planes == ref.planes
    np.testing.assert_array_equal(ec.codec.encode(data, chunk),
                                  full[ec.k:])


def test_encode_equals_the_host_loop(geometry):
    """The plugin's own entry point, device=jax against device=numpy: one
    stripe is one chunk buffer there."""
    ec, ref, chunk, data, _full = geometry
    host = _plugin(ec.k, ec.m, ec.d, "numpy")
    obj = data[:, :chunk].tobytes()
    want = host.encode(set(range(ec.k + ec.m)), obj)
    got = ec.encode(set(range(ec.k + ec.m)), obj)
    for i in want:
        np.testing.assert_array_equal(got[i], want[i], err_msg=f"chunk {i}")


def test_every_single_chunk_repair_from_its_minimum_to_decode_runs(geometry):
    """The helpers send only the runs minimum_to_decode names (d of them,
    C/q bytes a stripe each); the device repair rebuilds the chunk, and
    so does the host loop from the same bytes."""
    ec, ref, chunk, _data, full = geometry
    host = _plugin(ec.k, ec.m, ec.d, "numpy")
    n, planes = ec.k + ec.m, ref.planes
    for lost in range(n):
        runs = ec.minimum_to_decode({lost}, set(range(n)) - {lost})
        ids = list(runs)
        assert len(ids) == ec.d and lost not in ids
        picked = [z for off, cnt in runs[ids[0]]
                  for z in range(off, off + cnt)]
        assert picked == ref.repair_planes(lost)
        helpers = full[ids].reshape(ec.d, STRIPES, planes, SC)[:, :, picked]
        got = ec.codec.repair(helpers.reshape(ec.d, -1), lost, ids, chunk)
        np.testing.assert_array_equal(got, full[lost], err_msg=f"{lost}")
        one = {h: helpers[i, 0].reshape(-1) for i, h in enumerate(ids)}
        np.testing.assert_array_equal(
            host.decode({lost}, one, chunk)[lost], full[lost, :chunk])


def _erasure_sets():
    """Every m-erasure pattern of (4, 2, 5); a seeded sample of 1..4
    erasures of (8, 4, 11)."""
    out = [((4, 2, 5), e) for e in itertools.combinations(range(6), 2)]
    rng = np.random.default_rng(20261019)
    for size in (1, 2, 3, 4, 4, 4):
        out.append(((8, 4, 11), tuple(sorted(
            int(c) for c in rng.choice(12, size, replace=False)))))
    return out


@pytest.fixture(scope="module")
def codewords():
    out = {}
    for k, m, d in ((4, 2, 5), (8, 4, 11)):
        ref = Clay(k, m, d)
        chunk = ref.planes * SC
        data = np.random.default_rng(k).integers(
            0, 256, (k, STRIPES * chunk), dtype=np.uint8)
        out[k, m, d] = (_plugin(k, m, d, "jax"), chunk,
                        ref.codeword(data, chunk))
    return out


@pytest.mark.parametrize("geometry_,erasures", _erasure_sets(),
                         ids=lambda v: "_".join(map(str, v)))
def test_layered_decode_of_erasures(geometry_, erasures, codewords):
    ec, chunk, full = codewords[geometry_]
    src = ec.codec.decode_sources(erasures)
    assert len(src) == ec.k and not set(src) & set(erasures)
    got = ec.codec.decode(full[src], erasures, chunk)
    np.testing.assert_array_equal(got, full[list(erasures)])
    # the plugin's decode of one stripe routes through it too
    before = _dispatches()
    avail = {i: full[i, :chunk] for i in range(ec.k + ec.m)
             if i not in erasures}
    out = ec.decode(set(erasures), avail)
    assert _dispatches() == before + 1
    for e in erasures:
        np.testing.assert_array_equal(out[e], full[e, :chunk])


def test_the_corpus_digest_holds_on_the_device_path():
    corpus = json.loads((REPO / "tests/golden/ec_corpus.json").read_text())
    entry = corpus["entries"]["clay/d=5_k=4_m=2_scalar_mds=jax_rs"]
    ec = ErasureCodePluginRegistry.instance().factory(
        "clay", "", dict(entry["profile"], device="jax"))
    payload = np.random.default_rng(corpus["payload_seed"]).integers(
        0, 256, size=corpus["payload_size"], dtype=np.uint8).tobytes()
    before = _dispatches()
    encoded = ec.encode(set(range(ec.get_chunk_count())), payload)
    assert _dispatches() == before + 1
    for i, want in entry["chunk_sha256"].items():
        chunk = np.ascontiguousarray(encoded[int(i)])
        assert hashlib.sha256(chunk.tobytes()).hexdigest() == want, i


@pytest.mark.parametrize("kind", ["encode", "decode", "repair"])
def test_one_dispatch_a_call_for_one_stripe_and_for_sixteen(kind):
    ec, ref = _plugin(8, 4, 11, "jax"), Clay(8, 4, 11)
    codec, chunk = ec.codec, ref.planes * SC
    counted = []
    for stripes in (1, 16):
        rows = np.zeros((11 if kind == "repair" else 8, stripes * chunk
                         // (4 if kind == "repair" else 1)), np.uint8)
        before = _dispatches()
        if kind == "encode":
            codec.encode(rows, chunk)
        elif kind == "decode":
            codec.decode(rows, [0, 9], chunk)
        else:
            codec.repair(rows, 0, range(1, 12), chunk)
        counted.append(_dispatches() - before)
    assert counted == [1, 1]


# -- a MiniCluster Clay pool with device=jax ------------------------------------

K, M, D = 8, 4, 11
CHUNK = 256


@pytest.fixture
def pool():
    """A fresh cluster a test: marking OSDs down leaves them to recover."""
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.common import Context
    c = MiniCluster(n_osds=12, chunk_size=CHUNK, cct=Context())
    pid = c.create_ec_pool(
        "clay", {"plugin": "clay", "k": str(K), "m": str(M), "d": str(D),
                 "scalar_mds": "jax_rs", "technique": "cauchy",
                 "device": "jax"}, pg_num=1)
    data = np.random.default_rng(43).integers(
        0, 256, 3 * K * CHUNK, dtype=np.uint8).tobytes()
    before = _dispatches()
    c.put(pid, "o", data)
    put_calls = _dispatches() - before
    # the object's k + m shard streams are one codeword, 3 stripes high
    shards = np.frombuffer(data, np.uint8).reshape(3, K, CHUNK)
    shards = shards.transpose(1, 0, 2).reshape(K, -1)
    full = Clay(K, M, D).codeword(shards, 3 * CHUNK)
    yield c, pid, data, full, put_calls
    c.shutdown()


def _stored(c, pid, ci):
    from ceph_tpu.backend.memstore import GObject
    from ceph_tpu.backend.pg_backend import shard_store
    g = c.pg_group(pid, "o")
    osd = g.acting[ci]
    return g, osd, shard_store(g.bus, osd), GObject("o", osd)


def test_cluster_put_stores_the_references_chunks(pool):
    c, pid, _data, full, put_calls = pool
    assert put_calls >= 1
    for ci in range(K + M):
        _g, _osd, store, obj = _stored(c, pid, ci)
        assert store.read(obj) == full[ci].tobytes(), ci


def test_cluster_degraded_get_with_two_osds_down(pool):
    c, pid, data, _full, _ = pool
    g = c.pg_group(pid, "o")
    down = [g.acting[1], g.acting[9]]      # not the primary
    for osd in down:
        g.bus.mark_down(osd)
    try:
        before = _dispatches()
        assert c.get(pid, "o", len(data)) == data
        assert _dispatches() > before
    finally:
        for osd in down:
            g.bus.mark_up(osd)


def test_cluster_recovery_of_one_osd_reads_d_quarter_chunks(pool):
    c, pid, _data, full, _ = pool
    from ceph_tpu.backend.ec_backend import RecoveryState
    from ceph_tpu.backend.memstore import Transaction
    g, _osd, store, obj = _stored(c, pid, 2)
    store.queue_transaction(Transaction().remove(obj))
    assert not store.exists(obj)
    perf = clay_kernels.perf_counters()
    before = dict(perf.dump())
    rop = g.backend.recover_object("o", {2})
    g.bus.deliver_all()
    assert rop.state == RecoveryState.COMPLETE
    assert store.read(obj) == full[2].tobytes()
    after = perf.dump()
    read = after["repair_read_bytes"] - before["repair_read_bytes"]
    rebuilt = after["repair_bytes"] - before["repair_bytes"]
    assert rebuilt == full.shape[1]
    assert read == D * full.shape[1] // (D - K + 1)


def _kernel_case(kind, stripes):
    """(codec, signature, input rows, z_in, sub-chunk bytes, wanted
    output) of a kernel call at (8, 4, 11): the encode and a two-erasure
    decode at 512-byte sub-chunks, the repair of chunk 0 from its 11
    helpers' repair planes at 1 KiB ones (the least the kernel writes
    one output row at)."""
    ec, ref = _plugin(8, 4, 11, "jax"), Clay(8, 4, 11)
    codec = ec.codec
    sc = 1024 if kind == "repair" else 512
    chunk = ref.planes * sc
    data = np.random.default_rng(7 + stripes).integers(
        0, 256, (8, stripes * chunk), dtype=np.uint8)
    full = ref.codeword(data, chunk)
    if kind == "encode":
        return codec, ("encode",), data, 64, sc, full[8:]
    if kind == "decode":
        src = codec.decode_sources([0, 9])
        return codec, ("decode", (0, 9)), full[src], 64, sc, full[[0, 9]]
    ids = tuple(range(1, 12))
    x = full[list(ids)].reshape(11, stripes, 64, sc)[
        :, :, ref.repair_planes(0)].reshape(11, -1)
    return codec, ("repair", 0, ids), x, 16, sc, full[0]


@pytest.mark.parametrize("stripes", [1, 2, 3])
@pytest.mark.parametrize("kind", ["encode", "decode", "repair"])
def test_the_pallas_kernel_in_interpret_mode_equals_the_reference(kind,
                                                                  stripes):
    """The TPU path in the Pallas interpreter, bit for bit against the
    reference at 1, 2 and 3 stripes: the caller's rows read as they lie
    (eight rows as 128-byte slabs, the 11 helpers' rows as a 2-D block),
    regathered into words of four planes, the blocking (groups of output
    words, each reading only the input words it depends on), and the
    output written in the caller's layout (four rows to a word, or the
    repair's one row as the 1-D chunk)."""
    import jax
    codec, sig, x, z_in, sc, want = _kernel_case(kind, stripes)
    entry = codec.tables(sig)
    r_out = len(entry.out)
    assert clay_kernels._pallas_fits(x.shape[0], z_in, r_out, 64, sc)
    plan = clay_kernels._plan(entry.G, x.shape[0], z_in, r_out, 64)
    assert len(plan.groups) > 1                 # blocked, not the whole matrix
    bits = clay_kernels._plan_bits(entry.G, plan, z_in, r_out, 64)
    got = clay_kernels._APPLY[kind](
        jax.numpy.asarray(bits), jax.numpy.asarray(x), z_in=z_in, sc=sc,
        r_out=r_out, z_out=64, plan=plan, interpret=True)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), want)


def test_kernel_calls_counts_the_kernels_calls_and_not_xlas(monkeypatch):
    """``clay`` ``kernel_calls`` rises by one for a call that ran the
    kernel (a TPU host's path, here in the interpreter) and not for one
    that took ``_xla_apply``; ``calls`` counts both."""
    import functools
    from ceph_tpu.ops import rs_kernels
    codec, _sig, x, _z_in, sc, want = _kernel_case("repair", 1)
    ids = tuple(range(1, 12))
    perf = clay_kernels.perf_counters()

    def counted():
        before = dict(perf.dump())
        got = codec.repair(x, 0, ids, 64 * sc)
        np.testing.assert_array_equal(got, want)
        after = perf.dump()
        return (after["calls"] - before["calls"],
                after["kernel_calls"] - before["kernel_calls"])

    assert counted() == (1, 0)
    monkeypatch.setattr(rs_kernels, "_runs_on_tpu", lambda data: True)
    monkeypatch.setitem(clay_kernels._APPLY, "repair", functools.partial(
        clay_kernels._APPLY["repair"], interpret=True))
    assert counted() == (1, 1)
