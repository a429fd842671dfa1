"""A put's codec work before the cluster lock (ISSUE 29).

``ClusterServer._dispatch`` runs a full-object put's encode, HashInfo
crcs and payload copy in the worker that dequeued the call, BEFORE it
asks for ``ClusterServer.lock`` (``ECBackend.prepare_write_full``); the
locked section adopts the result only where the plan is the one it was
computed for.  What is stored, and what is raised, is what the path
under the lock alone stores and raises.  CPU, small sizes (and one
4 MiB object).
"""
import threading
import time

import numpy as np
import pytest

from ceph_tpu.backend import GObject, ecutil
from ceph_tpu.backend.ecutil import HINFO_KEY
from ceph_tpu.backend.pg_backend import shard_store
from ceph_tpu.cluster import MiniCluster
from ceph_tpu.common.tracer import default_tracer
from ceph_tpu.net import ClusterServer, TcpRados

K, M = 8, 4
CHUNK = 4096
STRIPE = K * CHUNK
PROFILE = {"plugin": "jax_rs", "k": str(K), "m": str(M),
           "technique": "cauchy", "device": "jax"}
ADOPTION = ("writes_prepared", "prepared_adopted")
# attribute fields that count the PG's earlier ops, or read the clock
PG_HISTORY = ("version", "user_version", "mtime")


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


class Served:
    """A served cluster with one EC pool ``p`` and one client."""

    def __init__(self, data_dir, prepare=True):
        self.c = MiniCluster(n_osds=K + M, osds_per_host=1,
                             chunk_size=CHUNK, data_dir=data_dir)
        self.serving = self.c.enable_serving(start=True)
        self.server = ClusterServer(self.c)
        if not prepare:
            # the path under the lock alone: what the parent commit runs
            self.server._prepare_put = lambda args: args
        self.server.start()
        self.keyring = data_dir / "client.admin.keyring"
        self.r = self.client()
        self.r.mkpool("p", profile=dict(PROFILE), pg_num=4)
        self.pid = self.c.pool_ids["p"]

    def client(self):
        return TcpRados("127.0.0.1", self.server.port, self.keyring)

    def counters(self, pool="p"):
        """(writes_prepared, prepared_adopted) summed over a pool's PGs."""
        pgs = self.c.pools[self.c.pool_ids[pool]]["pgs"].values()
        return tuple(sum(g.backend.perf.get(k) for g in pgs)
                     for k in ADOPTION)

    def close(self):
        self.r.close()
        self.server.stop()
        self.serving.stop()
        self.c.shutdown()


def _stored(c, pid, oid, attrs=False):
    """Every shard of ``oid`` by chunk index: bytes, HashInfo (size and
    crcs) and, with ``attrs``, every other attribute less PG_HISTORY."""
    g = c.pg_group(pid, oid)
    out = {}
    for chunk, shard in enumerate(g.acting):
        store, gobj = shard_store(g.bus, shard), GObject(oid, shard)
        if not store.exists(gobj):
            out[chunk] = None
            continue
        h = store.getattr(gobj, HINFO_KEY)
        row = [store.read(gobj), h["total_chunk_size"],
               list(h["cumulative_shard_hashes"])]
        if attrs:
            rest = {}
            for name, v in store.getattrs(gobj).items():
                if isinstance(v, dict):
                    v = {k: x for k, x in v.items() if k not in PG_HISTORY}
                rest[name] = v
            row.append(rest)
        out[chunk] = row
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    s = Served(tmp_path_factory.mktemp("prepared"))
    s.r.put("p", "warm", _data(STRIPE, 99))
    yield s
    s.close()


@pytest.fixture(scope="module")
def unprepared(tmp_path_factory):
    """The served twin whose puts take the whole path under the lock."""
    s = Served(tmp_path_factory.mktemp("unprepared"), prepare=False)
    yield s
    s.close()


# -- (a) the same twelve shards, crcs and sizes as Cluster.put -------------

@pytest.fixture(scope="module")
def twin():
    c = MiniCluster(n_osds=K + M, osds_per_host=1, chunk_size=CHUNK)
    pid = c.create_ec_pool("p", dict(PROFILE), pg_num=4)
    yield c, pid
    c.shutdown()


@pytest.mark.parametrize("size", [1, STRIPE + 4321, 32768, 4 << 20],
                         ids=["1B", "unaligned", "32KiB", "4MiB"])
def test_a_served_put_stores_what_cluster_put_stores(served, twin, size):
    c2, pid2 = twin
    oid, data = f"same-{size}", _data(size, size)
    before = served.counters()
    assert served.r.put("p", oid, data) == size
    assert served.counters() == (before[0] + 1, before[1] + 1)
    c2.put(pid2, oid, data)
    got = _stored(served.c, served.pid, oid)
    assert len(got) == K + M and all(got.values())
    assert got == _stored(c2, pid2, oid)
    # the crcs are the host's crc32c of the stored bytes, the size the
    # stripe-padded object's share
    shard_len = -(-size // STRIPE) * CHUNK
    for body, total, _hashes in got.values():
        assert len(body) == total == shard_len
    for chunk, (body, _t, hashes) in got.items():
        assert hashes[chunk] == ecutil.crc32c(0xFFFFFFFF, body)
    assert served.r.get("p", oid) == data
    assert served.r.stat("p", oid)[0] == size
    assert served.c.pg_group(served.pid, oid).backend.object_size(oid) \
        == -(-size // STRIPE) * STRIPE


# -- (b) the guard ---------------------------------------------------------

def _both(served, unprepared, fn):
    """Run ``fn(s)`` on the prepared server and on its twin; returns the
    prepared side's adoption counters' change."""
    before = served.counters()
    fn(served)
    fn(unprepared)
    after = served.counters()
    return after[0] - before[0], after[1] - before[1]


def test_overwrite_of_a_longer_object_is_not_adopted(served, unprepared):
    long, short = _data(3 * STRIPE + 100, 1), _data(STRIPE + 77, 2)

    def run(s):
        s.r.put("p", "shrink", long)
        s.r.put("p", "shrink", short)
    prepared, adopted = _both(served, unprepared, run)
    # both puts were prepared; the second's plan is an RMW over a
    # truncate, its hashes start over: nothing of it chains
    assert (prepared, adopted) == (2, 1)
    assert _stored(served.c, served.pid, "shrink", attrs=True) == \
        _stored(unprepared.c, unprepared.pid, "shrink", attrs=True)
    assert served.r.get("p", "shrink") == short
    g = served.c.pg_group(served.pid, "shrink")
    assert all(g.backend.be_deep_scrub("shrink").values())


def test_overwrite_of_an_equal_object_is_not_adopted(served, unprepared):
    first, second = _data(2 * STRIPE, 3), _data(2 * STRIPE, 4)

    def run(s):
        s.r.put("p", "again", first)
        s.r.put("p", "again", second)
    assert _both(served, unprepared, run) == (2, 1)
    assert _stored(served.c, served.pid, "again", attrs=True) == \
        _stored(unprepared.c, unprepared.pid, "again", attrs=True)
    assert served.r.get("p", "again") == second


def test_a_snapped_pools_cow_is_not_adopted(tmp_path):
    for d in "ab":
        (tmp_path / d).mkdir()
    a, b = Served(tmp_path / "a"), Served(tmp_path / "b", prepare=False)
    try:
        v1, v2 = _data(STRIPE + 5, 5), _data(2 * STRIPE + 9, 6)

        def run(s):
            s.r.put("p", "cow", v1)
            s.c.create_pool_snap(s.pid, "s1")
            s.r.put("p", "cow", v2)           # clones the head first
            s.r.put("p", "born-snapped", v1)  # a new object still appends
        assert _both(a, b, run) == (3, 2)
        for oid in ("cow", "born-snapped"):
            assert _stored(a.c, a.pid, oid, attrs=True) == \
                _stored(b.c, b.pid, oid, attrs=True), oid
        assert a.r.get("p", "cow") == v2
        snap = a.c.pools[a.pid]["pool"].snaps
        assert len(snap) == 1
    finally:
        a.close()
        b.close()


def test_a_replicated_pool_is_not_prepared(served, unprepared):
    data = _data(5000, 7)

    def run(s):
        if "rep" not in s.c.pool_ids:
            s.r.mkpool("rep", replicated=True, size=3, pg_num=4)
        s.r.put("rep", "r1", data)
    before = served.counters("p")
    _both(served, unprepared, run)
    assert served.counters("p") == before
    assert served.counters("rep") == (0, 0)
    assert served.r.get("rep", "r1") == data == unprepared.r.get("rep", "r1")


def _put_while_locked(s, pool, oid, data, then):
    """Start a put, let it stand at the cluster lock, run ``then()``
    under the lock, release; returns what the put returned or raised."""
    got = {}

    def put():
        r = s.client()
        try:
            got["value"] = r.put(pool, oid, data)
        except Exception as e:                # noqa: BLE001 — compared
            got["error"] = e
        finally:
            r.close()
    with s.server.lock:
        t = threading.Thread(target=put)
        t.start()
        time.sleep(0.5)                       # prepared, now at the lock
        then()
    t.join(20.0)
    assert not t.is_alive()
    return got


def test_a_pool_removed_between_prepare_and_lock(served, unprepared):
    data = _data(STRIPE, 8)
    errors = []
    for s in (served, unprepared):
        s.r.mkpool("doomed", profile=dict(PROFILE), pg_num=2)
        before = s.counters("doomed")
        pools = s.c.pool_ids
        got = _put_while_locked(s, "doomed", "o", data,
                                lambda: pools.pop("doomed"))
        assert isinstance(got.get("error"), IOError), got
        errors.append(str(got["error"]))
        assert not s.server._rpc_inflight
        if s is served:
            assert before == (0, 0)
    assert errors[0] == errors[1]
    assert "KeyError" in errors[0]


def test_a_pool_made_anew_under_the_name_drops_the_preparation(tmp_path):
    """The chunks were computed with the old pool's codec: the new
    pool's op engine does not stage them."""
    s = Served(tmp_path)
    try:
        data = _data(STRIPE, 9)
        s.r.mkpool("again", profile=dict(PROFILE), pg_num=2)

        def remake():
            s.c.pool_ids.pop("again")
            s.c.create_ec_pool("again", dict(PROFILE, k="4", m="2"),
                               pg_num=2)
        got = _put_while_locked(s, "again", "o", data, remake)
        assert got.get("value") == len(data), got
        assert s.counters("again") == (0, 0)
        assert s.r.get("again", "o") == data
        pid = s.c.pool_ids["again"]
        g = s.c.pg_group(pid, "o")
        assert len(g.acting) == 6
        assert all(g.backend.be_deep_scrub("o").values())
    finally:
        s.close()


# -- (c) a prepare that raises ---------------------------------------------

def test_a_prepare_that_raises_leaves_the_op_on_the_locked_path(
        served, twin, monkeypatch):
    c2, pid2 = twin
    calls = []

    def broken(chunks, ec_impl):
        # the first call is the prepare's; the locked path's own succeeds
        calls.append(threading.current_thread().name)
        if len(calls) == 1:
            raise RuntimeError("device error")
        return real(chunks, ec_impl)
    real = ecutil.device_shard_crcs
    monkeypatch.setattr(ecutil, "device_shard_crcs", broken)
    data = _data(STRIPE + 1, 10)
    before = served.counters()
    assert served.r.put("p", "survivor", data) == len(data)
    assert len(calls) == 2
    assert served.counters() == before
    assert not served.server._rpc_inflight
    c2.put(pid2, "survivor", data)
    assert _stored(served.c, served.pid, "survivor") == \
        _stored(c2, pid2, "survivor")


def test_a_failing_put_fails_as_before_and_leaks_nothing(served,
                                                          unprepared):
    errors = []
    for s in (served, unprepared):
        with pytest.raises(IOError) as e:
            s.r.put("nope", "o", _data(100, 11))
        errors.append(str(e.value))
        with pytest.raises(IOError) as e:
            s.r.call("put", pool="p", oid="o")     # malformed: no data
        errors.append(str(e.value))
        assert not s.server._rpc_inflight
    assert errors[:2] == errors[2:]


# -- (d) the codec work runs while the lock is held ------------------------

def _spans(names):
    return [e for e in default_tracer().dump(stitched=False)["traceEvents"]
            if e.get("ph") == "X" and e["name"] in names]


def test_two_puts_encode_and_checksum_while_the_lock_is_held(served):
    tr = default_tracer()
    clients = [served.client() for _ in range(2)]
    data = [_data(2 * STRIPE, 20 + i) for i in range(2)]
    tr.reset()
    threads = [threading.Thread(
        target=lambda i=i: clients[i].put("p", f"held-{i}", data[i]))
        for i in range(2)]
    try:
        with served.server.lock:
            for t in threads:
                t.start()
            deadline = time.monotonic() + 20.0
            while len(_spans({"ec.encode", "ec.hinfo_crc"})) < 4 and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            t_release = (time.perf_counter() - tr._t0) * 1e6
            held = _spans({"ec.encode", "ec.hinfo_crc", "rpc.put"})
        for t in threads:
            t.join(20.0)
    finally:
        for r in clients:
            r.close()
    # both puts' encode and crc spans had ENDED before the release ...
    assert sorted(e["name"] for e in held) == \
        ["ec.encode"] * 2 + ["ec.hinfo_crc"] * 2
    assert all(e["ts"] + e["dur"] <= t_release for e in held)
    # ... each inside a pg.generate_transactions span, inside rpc.prepare
    time.sleep(0.1)
    every = _spans({"ec.encode", "ec.hinfo_crc", "rpc.put", "rpc.prepare",
                    "pg.generate_transactions", "rpc.lock_wait"})

    def inside(e, outer):
        return e["tid"] == outer["tid"] and outer["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1.0
    of = {n: [e for e in every if e["name"] == n]
          for n in {e["name"] for e in every}}
    assert len(of["rpc.put"]) == len(of["rpc.prepare"]) == 2
    assert len(of["pg.generate_transactions"]) == 4    # two a put
    for e in of["ec.encode"] + of["ec.hinfo_crc"]:
        assert any(inside(e, g) for g in of["pg.generate_transactions"])
        assert any(inside(e, p) for p in of["rpc.prepare"])
        assert not any(inside(e, p) for p in of["rpc.put"])
    for w in of["rpc.lock_wait"]:
        # the lock is asked for where the prepare ends
        assert any(abs(p["ts"] + p["dur"] - w["ts"]) <= 1.0
                   and p["tid"] == w["tid"] for p in of["rpc.prepare"])
    for i in range(2):
        assert served.r.get("p", f"held-{i}") == data[i]


def test_a_holder_that_waits_for_the_interpreter_reads_off_cpu_time(served):
    """ISSUE 37: the hold reads the worker's CPU clock beside the wall
    clock.  Three spinning Python threads take the interpreter from the
    worker that holds the cluster lock for a put: the hold's wall time
    less its CPU time, ``span_cpu``'s ``rpc.put.offcpu_us``, says so."""
    from ceph_tpu.common.tracer import span_cpu_perf_counters
    tr = default_tracer()

    def held():
        h = tr.histograms().get("rpc.put", {"sum": 0.0, "count": 0})
        dump = span_cpu_perf_counters().dump()
        return (h["sum"] * 1e6, h["count"], dump.get("rpc.put.cpu_us", 0),
                dump.get("rpc.put.offcpu_us", 0))
    data = _data(2 * STRIPE, 37)
    served.r.put("p", "calm", data)
    before = held()
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass
    spinners = [threading.Thread(target=spin) for _ in range(3)]
    for t in spinners:
        t.start()
    try:
        for i in range(3):
            served.r.put("p", f"contended-{i}", data)
    finally:
        stop.set()
        for t in spinners:
            t.join(20.0)
    wall, puts, cpu, off = (a - b for a, b in zip(held(), before))
    assert puts == 3 and cpu > 0
    assert off > 0
    # the two are the holds' wall time, parted
    assert cpu + off == pytest.approx(wall, abs=3 * 2)
    for i in range(3):
        assert served.r.get("p", f"contended-{i}") == data


# -- (e) the engine from three threads --------------------------------------

def test_encode_from_three_threads_is_bit_equal_in_batches_of_1_2_3():
    """``submitters`` = 3 compiles, at the first op of a size, every
    bucket that three such ops can fuse into: the batches of 2 and 3
    that follow build no executable."""
    import jax.monitoring
    from ceph_tpu.backend import StripeInfo
    from ceph_tpu.exec import ServingEngine
    from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
    ec = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", dict(PROFILE))
    sinfo = StripeInfo(K, CHUNK)
    eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="prepared.three")
    eng.expect_submitters(3)
    want_depth = [1]
    drain = eng._drain_locked

    def drain_when_all_queued(limit, force=False):
        # called with the engine's lock held, as _cond.wait needs
        while eng._depth < want_depth[0] and not eng._stopping:
            eng._cond.wait(0.01)
        return drain(limit, force)
    eng._drain_locked = drain_when_all_queued
    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _d, **_kw: built.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    eng.start()
    try:
        for n in (1, 2, 3):
            bufs = [_data(3 * STRIPE, 30 + 10 * n + i) for i in range(n)]
            wants = [ecutil.encode(sinfo, ec, buf) for buf in bufs]
            got = [None] * n
            threads = [threading.Thread(
                target=lambda i=i: got.__setitem__(i, eng.encode(bufs[i])))
                for i in range(n)]
            before = (eng.perf.get("batches"), eng.perf.get("ops_coalesced"))
            n_built = len(built)
            want_depth[0] = n              # all n queue up: one batch
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
            assert (eng.perf.get("batches") - before[0],
                    eng.perf.get("ops_coalesced") - before[1]) == (1, n)
            for want, chunks in zip(wants, got):
                assert set(chunks) == set(want)
                for c in want:
                    assert np.array_equal(chunks[c], want[c]), (n, c)
            if n > 1:
                assert len(built) == n_built, "a batch compiled mid-traffic"
        assert eng._warm[(id(ec), K, CHUNK)] >= {4, 8, 16}
    finally:
        want_depth[0] = 0
        eng.stop()


# -- the copies that left the hold -----------------------------------------

def test_an_aligned_full_write_is_assembled_and_pinned_without_a_copy(twin):
    """``_assemble_extent`` hands back the op's one write where it is the
    whole extent, and the extent cache pins a fresh extent's bytes as
    they are: the guard's compare is then an identity."""
    from ceph_tpu.backend.extent_cache import ExtentCache
    from ceph_tpu.backend.transaction import ObjectOperation
    c2, pid2 = twin
    backend = c2.pg_group(pid2, "x").backend
    data = _data(2 * STRIPE, 50)

    class _Op:
        remote_reads = {}
    aligned = ObjectOperation().write(0, data)
    aligned.truncate = (len(data), len(data))
    assert backend._assemble_extent(_Op, "x", aligned, 0, len(data)) \
        is aligned.buffer_updates[0][1]
    ragged = ObjectOperation().write(0, data[:-5])
    got = backend._assemble_extent(_Op, "x", ragged, 0, len(data))
    assert got == data[:-5] + b"\0" * 5

    cache = ExtentCache()
    cache.claim("x", 1, 0, data)
    assert cache.read("x", 0, len(data)) == data
    assert cache._pinned["x"][0] is data
    # an adjoining and an overlapping claim still splice
    more = _data(STRIPE, 51)
    cache.claim("x", 2, len(data), more)
    assert cache.read("x", 0, len(data) + STRIPE) == data + more
    cache.claim("x", 3, STRIPE, more)
    assert cache.read("x", 0, 3 * STRIPE) == data[:STRIPE] + more + more
    # a disjoint one is a span of its own
    cache.claim("x", 4, 8 * STRIPE, more)
    assert cache.read("x", 8 * STRIPE, STRIPE) == more
    assert cache.read("x", 0, 3 * STRIPE) == data[:STRIPE] + more + more
