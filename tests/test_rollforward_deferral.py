"""The roll-forward rides the next sub-write (ISSUE 34).

A PG whose pipeline drains while another op waits for the cluster sends
no standalone ``RollForward``: its next sub-write carries the point and
each shard drops the rollback data inside that sub-write's transaction.
Once nobody waits, PGs that still owe a kick get it.  So a busy pool
holds at most one put's rollback data a PG and an idle one none.

"Another op waits" is what ``ClusterServer`` reads from its dispatch
queue and from the calls on their way to its lock; on the in-process
API nobody waits and every drain kicks, as it always did.  CPU, tiny
sizes.
"""
import threading
import time

import numpy as np
import pytest

from ceph_tpu.backend.pg_backend import PG_META, OSDShard, shard_store
from ceph_tpu.backend.memstore import GObject
from ceph_tpu.cluster import MiniCluster
from ceph_tpu.common.tracer import default_tracer
from ceph_tpu.net import ClusterServer, TcpRados

K, M = 2, 1
N = K + M
PROFILE = {"plugin": "jax_rs", "k": str(K), "m": str(M),
           "technique": "cauchy", "device": "jax"}
SIZE = 8192


def _data(seed, n=SIZE):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _shards(g):
    return [h if isinstance(h, OSDShard) else h.local_shard
            for h in g.bus.handlers.values()]


def _rb_keys(shard):
    return [k for k in shard.store.get_omap(GObject(PG_META, shard.shard))
            if k.startswith("rb.")]


def _counters(c):
    """(writes, rollforward_kicks, rollforward_deferred) over this
    cluster's EC backends, as ``perf dump`` shows them."""
    out = [0, 0, 0]
    for name, vals in c.cct.perf.perf_dump().items():
        if name.startswith(f"ec_backend.c{c.cluster_id}."):
            for i, key in enumerate(("writes", "rollforward_kicks",
                                     "rollforward_deferred")):
                out[i] += vals[key]
    return tuple(out)


def _wait(cond, what, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.005)


class Served:
    """A one-PG pool behind a ``ClusterServer`` with ONE dispatch worker,
    so that calls started while the test holds the cluster lock are
    served in the order they were started, and an ``rpc.nap`` that
    stops under the lock until the test lets it go."""

    def __init__(self, tmp_path, pg_num=1, workers=1):
        self.c = MiniCluster(n_osds=N, osds_per_host=1, chunk_size=1024,
                             data_dir=tmp_path, store_backend="bluestore")
        self.serving = self.c.enable_serving(start=True)
        self.server = ClusterServer(self.c)
        # the context is the process's: the option is read at start()
        # and put back at once, so that no later test inherits it
        conf = self.c.cct.conf
        default = conf.get("ms_async_op_threads")
        conf.set("ms_async_op_threads", workers)
        try:
            self.server.start()
        finally:
            conf.set("ms_async_op_threads", default)
        self.keyring = tmp_path / "client.admin.keyring"
        self.clients = []
        self.napping = threading.Event()
        self.wake = threading.Event()

        def nap(ch):
            self.napping.set()
            assert self.wake.wait(10.0)
        self.server._rpc_nap = nap
        r = self.client()
        r.mkpool("p", profile=dict(PROFILE), pg_num=pg_num)
        r.put("p", "warm", _data(99))        # compiles outside the tests
        self.pgs = list(self.c.pools[self.c.pool_ids["p"]]["pgs"].values())

    def client(self):
        r = TcpRados("127.0.0.1", self.server.port, self.keyring)
        self.clients.append(r)
        return r

    def held_in_order(self, calls):
        """Start each of ``calls`` on a thread of its own while the
        cluster lock is held, the first at the lock and the rest in the
        dispatch queue behind it; returns the threads."""
        threads = []
        disp = self.server._transport.dispatcher
        with self.server.lock:
            for i, fn in enumerate(calls):
                t = threading.Thread(target=fn)
                t.start()
                threads.append(t)
                if i == 0:
                    _wait(lambda: self.server._bound_for_lock == 1,
                          "the first call never reached the lock")
                else:
                    _wait(lambda: disp.depth == i,
                          f"call {i} never reached the dispatch queue")
        return threads

    def close(self):
        self.wake.set()
        for r in self.clients:
            r.close()
        self.server.stop()
        self.serving.stop()
        self.c.shutdown()


@pytest.fixture
def served(tmp_path):
    s = Served(tmp_path)
    yield s
    s.close()


def _traced_put(r, oid, data, out):
    """A put under a fresh client trace; its trace id lands in ``out``."""
    tr = default_tracer()
    ctx = tr.new_trace("client")
    out[oid] = ctx.trace_id
    with tr.activate(ctx):
        r.put("p", oid, data)


def _commit_parents(trace_id):
    """The names of the spans that enclose each ``store.commit`` of a
    trace."""
    evs = [e for e in default_tracer().dump(stitched=False)["traceEvents"]
           if e.get("ph") == "X"
           and e.get("args", {}).get("trace_id") == trace_id]
    by_id = {e["args"]["span_id"]: e for e in evs}
    return [by_id[e["args"]["parent_span_id"]]["name"]
            for e in evs if e["name"] == "store.commit"]


def test_a_put_that_another_call_waits_behind_sends_no_kick(served):
    s = served
    a, b = s.client(), s.client()
    traces = {}
    before = _counters(s.c)
    threads = s.held_in_order([
        lambda: _traced_put(a, "first", _data(1), traces),
        lambda: b.call("nap")])
    assert s.napping.wait(10.0)      # the put is acked, the nap holds the lock
    threads[0].join(10.0)
    writes, kicks, deferred = _counters(s.c)
    assert (writes, kicks, deferred) == (before[0] + 1, before[1],
                                         before[2] + 1)
    parents = _commit_parents(traces["first"])
    assert parents.count("osd.ECSubWrite") == N
    assert parents.count("osd.RollForward") == 0
    assert len(parents) == N
    # the put's rollback data waits on every shard, in RAM and on disk
    for shard in _shards(s.pgs[0]):
        assert len(shard.pending_rollbacks) == 1
        assert len(_rb_keys(shard)) == 1
    assert s.c.kicks_owed == {s.pgs[0].backend}
    # the nap leaves nobody waiting: it settles what the PG owed
    s.wake.set()
    threads[1].join(10.0)
    assert _counters(s.c) == (before[0] + 1, before[1] + N, before[2] + 1)
    assert not s.c.kicks_owed
    for shard in _shards(s.pgs[0]):
        assert not shard.pending_rollbacks
        assert not _rb_keys(shard)
    assert a.get("p", "first") == _data(1)


def test_the_next_put_of_the_pg_carries_the_point(served):
    s = served
    a, b, n = s.client(), s.client(), s.client()
    traces = {}
    before = _counters(s.c)
    threads = s.held_in_order([
        lambda: _traced_put(a, "one", _data(2), traces),
        lambda: _traced_put(b, "two", _data(3), traces),
        lambda: n.call("nap")])
    assert s.napping.wait(10.0)
    threads[0].join(10.0)
    threads[1].join(10.0)
    assert _counters(s.c) == (before[0] + 2, before[1], before[2] + 2)
    for oid in ("one", "two"):
        parents = _commit_parents(traces[oid])
        assert parents == ["osd.ECSubWrite"] * N, (oid, parents)
    # "two"'s sub-write dropped "one"'s rollback data inside its own
    # transaction and left its own: exactly one entry a shard
    head = s.pgs[0].backend.pg_log.head
    for shard in _shards(s.pgs[0]):
        assert list(shard.pending_rollbacks) == [head]
        assert _rb_keys(shard) == [f"rb.{head:016d}"]
    s.wake.set()
    threads[2].join(10.0)
    for shard in _shards(s.pgs[0]):
        assert not shard.pending_rollbacks and not _rb_keys(shard)
    assert _counters(s.c) == (before[0] + 2, before[1] + N, before[2] + 2)


def test_the_last_put_of_a_burst_kicks_and_the_counters_account_for_every_drain(
        served):
    s = served
    a, b = s.client(), s.client()
    before = _counters(s.c)
    threads = s.held_in_order([
        lambda: a.put("p", "head", _data(4)),
        lambda: b.put("p", "tail", _data(5))])
    for t in threads:
        t.join(10.0)
    writes, kicks, deferred = (x - y for x, y in zip(_counters(s.c), before))
    # two drains: the first deferred (the second put waited), the second
    # found nobody waiting and kicked, in its own hold
    assert (writes, kicks, deferred) == (2, N, 1)
    assert kicks // N + deferred == writes
    assert not s.c.kicks_owed
    for shard in _shards(s.pgs[0]):
        assert not shard.pending_rollbacks and not _rb_keys(shard)


def test_a_serial_client_kicks_at_every_put_as_before(served):
    s = served
    r = s.client()
    before = _counters(s.c)
    for i in range(3):
        r.put("p", f"serial{i}", _data(10 + i))
        for shard in _shards(s.pgs[0]):
            assert not shard.pending_rollbacks
    assert _counters(s.c) == (before[0] + 3, before[1] + 3 * N, before[2])


def test_a_call_that_never_takes_the_lock_still_leaves_the_pool_settled(
        served):
    """The call a put deferred for may never take the lock (a resend
    answered from the dedup cache, an unknown method).  The worker that
    served it settles the kick."""
    s = served
    a, b = s.client(), s.client()
    before = _counters(s.c)
    refused = []

    def unknown():
        try:
            b.call("no_such_method")
        except Exception as e:               # noqa: BLE001 — the refusal
            refused.append(e)
    threads = s.held_in_order([
        lambda: a.put("p", "behind", _data(7)), unknown])
    for t in threads:
        t.join(10.0)
    assert refused and "no_such_method" in str(refused[0])
    _wait(lambda: not s.c.kicks_owed, "the deferred kick was never settled")
    with s.server.lock:
        assert _counters(s.c) == (before[0] + 1, before[1] + N,
                                  before[2] + 1)
        for shard in _shards(s.pgs[0]):
            assert not shard.pending_rollbacks and not _rb_keys(shard)


def test_eight_clients_over_four_pgs_end_idle_with_nothing_owed(tmp_path):
    s = Served(tmp_path, pg_num=4, workers=3)
    try:
        clients = [s.client() for _ in range(8)]
        want = {}
        before = _counters(s.c)

        def run(i, r):
            for j in range(12):
                oid = f"c{i}.{j % 6}"            # overwrites too
                want[oid] = _data(1000 + 100 * i + j)
                r.put("p", oid, want[oid])
        threads = [threading.Thread(target=run, args=(i, r))
                   for i, r in enumerate(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        writes, kicks, deferred = (x - y for x, y in
                                   zip(_counters(s.c), before))
        assert writes == 8 * 12
        assert deferred > 0                      # the backlog was seen
        assert kicks % N == 0
        # every drain either kicked or deferred; a deferred one that no
        # later sub-write of its PG resolved was kicked once more at the
        # idle moment: at most one such kick a PG
        assert writes <= kicks // N + deferred <= writes + len(s.pgs)
        assert not s.c.kicks_owed
        for g in s.pgs:
            for shard in _shards(g):
                assert not shard.pending_rollbacks and not _rb_keys(shard)
            assert g.backend.committed_to == g.backend._rolled_forward_to
        r = clients[0]
        for oid, data in want.items():
            assert r.get("p", oid) == data
            g = s.c.pg_group(s.c.pool_ids["p"], oid)
            assert all(g.backend.be_deep_scrub(oid).values())
    finally:
        s.close()


# -- the in-process API, with the predicate set by hand ----------------------

@pytest.fixture
def busy(tmp_path):
    """An in-process cluster that is told somebody always waits, so
    every drain defers; ``idle()`` says nobody does and settles."""
    c = MiniCluster(n_osds=K + 2, osds_per_host=1, chunk_size=1024,
                    data_dir=tmp_path, store_backend="bluestore")
    # m = 2: one shard down leaves min_size (k + 1) current
    pid = c.create_ec_pool("p", dict(PROFILE, m="2", device="numpy"),
                           pg_num=1)
    c.others_waiting = lambda: True

    def idle():
        c.others_waiting = lambda: False
        c.settle_kicks()
    yield c, pid, c.pools[pid]["pgs"][0], idle
    c.shutdown()


def test_an_overwrites_old_chunk_is_gone_after_the_pgs_next_write(busy):
    c, pid, g, idle = busy
    old, new = _data(20), _data(21)
    c.put(pid, "x", old)
    was = {shard.shard: bytes(shard.store.read(GObject("x", shard.shard),
                                               0, None))
           for shard in _shards(g)}
    assert {len(b) for b in was.values()} == {SIZE // K}
    c.put(pid, "x", new)                 # the overwrite: kick deferred
    v = g.backend.pg_log.head
    for shard in _shards(g):
        assert list(shard.pending_rollbacks) == [v]
        # the undo record holds this shard's old chunk, whole
        written = [op[3] for op in shard.pending_rollbacks[v].ops
                   if op[0] == "write"]
        assert written == [was[shard.shard]]
        assert len(_rb_keys(shard)) == 1
    c.put(pid, "y", _data(22))           # the PG's next write carries it
    for shard in _shards(g):
        assert list(shard.pending_rollbacks) == [v + 1]
        inv = shard.pending_rollbacks[v + 1]
        assert [op[0] for op in inv.ops] == ["remove"]   # y was new
        assert len(_rb_keys(shard)) == 1
    assert c.get(pid, "x", SIZE) == new
    idle()
    for shard in _shards(g):
        assert not shard.pending_rollbacks and not _rb_keys(shard)
    assert c.get(pid, "x", SIZE) == new and c.get(pid, "y", SIZE) == _data(22)


def test_an_osd_marked_down_with_a_kick_owed_is_not_written(busy):
    c, pid, g, idle = busy
    c.put(pid, "x", _data(30))
    c.put(pid, "x", _data(31))           # kick owed, rollback data held
    down = next(o for o in g.acting if o != g.backend.whoami)
    seq = shard_store(g.bus, down).committed_seq
    g.bus.mark_down(down)
    g.bus.deliver_all()
    idle()                               # the kick goes to current shards
    assert shard_store(g.bus, down).committed_seq == seq
    assert not c.kicks_owed
    for shard in _shards(g):
        if shard.shard != down:
            assert not shard.pending_rollbacks and not _rb_keys(shard)
    c.put(pid, "z", _data(32))           # a write the down shard misses
    assert shard_store(g.bus, down).committed_seq == seq
    g.bus.mark_up(down)
    g.bus.deliver_all()                  # stale -> log repair -> current
    assert down in g.backend.current_shards()
    for oid in ("x", "z"):
        assert all(g.backend.be_deep_scrub(oid).values()), oid
    assert c.get(pid, "x", SIZE) == _data(31)
    # what it still held from before it went down goes with the PG's
    # next write, like anyone's
    c.put(pid, "z2", _data(33))
    for shard in _shards(g):
        assert not shard.pending_rollbacks and not _rb_keys(shard)


def test_peering_with_a_kick_owed_announces_the_point_itself(busy):
    c, pid, g, idle = busy
    c.put(pid, "x", _data(34))
    c.put(pid, "x", _data(35))
    assert g.backend in c.kicks_owed
    g.peering.advance_map(epoch=5)       # GetLog: adopt, roll forward
    g.bus.deliver_all()
    for shard in _shards(g):
        assert not shard.pending_rollbacks and not _rb_keys(shard)
    kicks = g.backend.perf.get("rollforward_kicks")
    idle()                               # nothing left to announce
    assert g.backend.perf.get("rollforward_kicks") == kicks
    assert c.get(pid, "x", SIZE) == _data(35)


def test_a_pg_remapped_with_a_kick_owed_takes_it_along(tmp_path):
    c = MiniCluster(n_osds=K + 3, osds_per_host=1, chunk_size=1024,
                    data_dir=tmp_path, store_backend="bluestore")
    try:
        pid = c.create_ec_pool("p", dict(PROFILE, m="2", device="numpy"),
                               pg_num=1)
        old = c.pools[pid]["pgs"][0]
        c.others_waiting = lambda: True
        c.put(pid, "x", _data(36))
        assert old.backend in c.kicks_owed
        spare = next(o for o in range(K + 3) if o not in old.acting)
        acting = list(old.acting[:-1]) + [spare]
        c._backfill_pg(pid, 0, acting, c.pools[pid]["ec"])
        new = c.pools[pid]["pgs"][0]
        assert new is not old and old.backend not in c.kicks_owed
        c.others_waiting = lambda: False
        c.settle_kicks()                 # the new group's own, if any
        assert not c.kicks_owed
        for shard in _shards(new):
            assert not shard.pending_rollbacks and not _rb_keys(shard)
        assert c.get(pid, "x", SIZE) == _data(36)
        assert all(new.backend.be_deep_scrub("x").values())
    finally:
        c.shutdown()


def test_nobody_waits_on_the_in_process_api(tmp_path):
    c = MiniCluster(n_osds=N, osds_per_host=1, chunk_size=1024)
    try:
        pid = c.create_ec_pool("p", dict(PROFILE, device="numpy"), pg_num=2)
        rid = c.create_replicated_pool("r", size=3, pg_num=1)
        assert c.others_waiting() is False
        for i in range(4):
            c.put(pid, f"o{i}", _data(40 + i))
        c.put(rid, "rep", _data(50))
        writes, kicks, deferred = _counters(c)
        assert (writes, kicks, deferred) == (4, 4 * N, 0)
        rep = c.pools[rid]["pgs"][0].backend.perf
        assert rep.get("rollforward_kicks") == 3
        assert rep.get("rollforward_deferred") == 0
        assert not c.kicks_owed
    finally:
        c.shutdown()


def test_a_replicated_pool_defers_the_same_way(tmp_path):
    c = MiniCluster(n_osds=3, osds_per_host=1, chunk_size=1024)
    try:
        rid = c.create_replicated_pool("r", size=3, pg_num=1)
        g = c.pools[rid]["pgs"][0]
        c.others_waiting = lambda: True
        c.put(rid, "a", _data(60))
        c.put(rid, "a", _data(61))
        assert g.backend.perf.get("rollforward_deferred") == 2
        assert g.backend.perf.get("rollforward_kicks") == 0
        for shard in _shards(g):
            assert len(shard.pending_rollbacks) == 1
        c.others_waiting = lambda: False
        c.settle_kicks()
        assert g.backend.perf.get("rollforward_kicks") == 3
        for shard in _shards(g):
            assert not shard.pending_rollbacks
        assert c.get(rid, "a", SIZE) == _data(61)
    finally:
        c.shutdown()
