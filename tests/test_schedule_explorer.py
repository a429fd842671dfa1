"""Schedule exploration: delivery-order race hunting (the TSAN analog).

The reference validates concurrency with TSAN/lockdep/valgrind builds
(CMakeLists.txt:585-607); this framework's nondeterminism is delivery
order, so the explorer drives scenarios through many interleavings and
asserts the EC pipeline's invariants hold in every one — and proves it
can catch a planted race by replaying its trace.
"""
import numpy as np
import pytest

from ceph_tpu.backend.ec_backend import ECBackend
from ceph_tpu.backend.ecutil import StripeInfo
from ceph_tpu.backend.transaction import PGTransaction
from ceph_tpu.plugins.plugin_xor import ErasureCodeXor
from ceph_tpu.utils.schedule_explorer import (
    explore_dfs, explore_random, replay,
)

K, M, CHUNK = 2, 1, 256
STRIPE = K * CHUNK


def _codec():
    ec = ErasureCodeXor()
    ec.init({"k": str(K), "m": str(M), "plugin": "xor"})
    return ec


def _payload(seed):
    return np.random.default_rng(seed).integers(
        0, 256, STRIPE, dtype=np.uint8).tobytes()


def _mk_backend(bus, stores=(None, None, None)):
    from ceph_tpu.backend.pg_backend import OSDShard
    backend = ECBackend(_codec(), StripeInfo(K, CHUNK), bus,
                        acting=[0, 1, 2], whoami=0, store=stores[0])
    for s in (1, 2):
        OSDShard(s, bus, store=stores[s])
    return backend


def _read(backend, bus, oid):
    out = {}
    backend.objects_read_and_reconstruct(
        {oid: [(0, STRIPE)]},
        lambda result, errors: out.update(result=result, errors=errors))
    bus.run_to_quiescence()
    if out.get("errors"):
        raise IOError(out["errors"])
    return bytes(out["result"][oid][0][2])


def scenario_concurrent_writes(bus, stores=(None, None, None)):
    """Two in-flight writes to one object + a concurrent write to
    another: in EVERY delivery order, acked writes are durable, the
    pipeline commits them in submission order, and all shards scrub
    clean."""
    backend = _mk_backend(bus, stores)
    a, b, c = _payload(1), _payload(2), _payload(3)
    commits = []
    backend.submit_transaction(PGTransaction().write("obj", 0, a),
                               on_commit=lambda t: commits.append("a"))
    backend.submit_transaction(PGTransaction().write("obj", 0, b),
                               on_commit=lambda t: commits.append("b"))
    backend.submit_transaction(PGTransaction().write("other", 0, c),
                               on_commit=lambda t: commits.append("c"))
    bus.run_to_quiescence()
    assert "a" in commits and "b" in commits and "c" in commits
    assert commits.index("a") < commits.index("b"), "pipeline order broken"
    assert _read(backend, bus, "obj") == b, "last write must win"
    assert _read(backend, bus, "other") == c
    scrub = {oid: backend.be_deep_scrub(oid) for oid in ("obj", "other")}
    for oid, per_shard in scrub.items():
        assert all(per_shard.values()), f"scrub inconsistency on {oid}"


def scenario_write_vs_recovery(bus):
    """A shard dies mid-write and revives: whatever the interleaving of
    sub-writes, repair reads and pushes, the acked write survives and
    the revived shard converges to the authority log."""
    backend = _mk_backend(bus)
    first, second = _payload(4), _payload(5)
    backend.submit_transaction(PGTransaction().write("obj", 0, first))
    bus.run_to_quiescence()
    bus.mark_down(2)
    committed = []
    backend.submit_transaction(PGTransaction().write("obj", 0, second),
                               on_commit=committed.append)
    bus.run_to_quiescence()
    assert committed, "write acked while 2/3 shards up (min_size k)"
    bus.mark_up(2)                      # auto-repair kicks
    bus.run_to_quiescence()
    assert _read(backend, bus, "obj") == second
    shard2 = bus.handlers[2]
    assert shard2.pg_log.head == backend.pg_log.head, "revived shard stale"


def test_concurrent_writes_random_schedules():
    res = explore_random(scenario_concurrent_writes, schedules=40)
    assert res.ok, f"trace {res.failure_trace}: {res.failure}"
    assert res.schedules_run == 40
    assert len(res.traces_seen) > 1, "exploration degenerated to one order"


def test_concurrent_writes_dfs():
    res = explore_dfs(scenario_concurrent_writes, max_runs=120)
    assert res.ok, f"trace {res.failure_trace}: {res.failure}"
    assert res.schedules_run == 120          # tree is larger than the bound
    assert len(res.traces_seen) == 120       # every schedule distinct


def test_concurrent_writes_on_durable_stores_random_schedules(tmp_path):
    """The same scenario with every shard on a BlueStoreLite: a sub-write's
    reply waits for its store's commit, and the explorer sends it once
    nothing else is deliverable, as MessageBus.deliver_all does."""
    from ceph_tpu.backend.bluestore import BlueStoreLite
    runs = iter(range(1000))

    def scenario(bus):
        run = next(runs)
        stores = [BlueStoreLite(tmp_path / f"r{run}.s{s}") for s in range(3)]
        try:
            scenario_concurrent_writes(bus, stores)
            assert not bus._acks
            assert all(st.perf.get("commits_queued") for st in stores)
        finally:
            for st in stores:
                st.close(checkpoint=False)
    res = explore_random(scenario, schedules=10)
    assert res.ok, f"trace {res.failure_trace}: {res.failure}"
    assert len(res.traces_seen) > 1


def test_write_vs_recovery_schedules():
    res = explore_random(scenario_write_vs_recovery, schedules=30)
    assert res.ok, f"trace {res.failure_trace}: {res.failure}"


def test_explorer_catches_planted_race():
    """Sanity: the tool finds a real ordering bug and its trace replays.
    The planted 'service' acks as soon as ANY reply arrives (quorum 1 of
    2) and claims the FIRST reply's payload is the quorum value — true
    only for schedules that deliver replica 1 first."""
    from ceph_tpu.backend.messages import PGLogInfo, PGLogQuery

    class Replica:
        def __init__(self, bus, shard, value):
            self.bus, self.shard, self.value = bus, shard, value
            bus.register(shard, self)

        def handle_message(self, m):
            if isinstance(m, PGLogQuery):
                self.bus.send(m.from_shard,
                              PGLogInfo(self.shard, self.value, 0))

    class BuggyQuorum:
        def __init__(self, bus):
            self.bus = bus
            self.first = None
            bus.register(0, self)
            bus.send(1, PGLogQuery(0))
            bus.send(2, PGLogQuery(0))

        def handle_message(self, m):
            if self.first is None:
                self.first = m.last_update     # BUG: first reply "wins"

    def scenario(bus):
        svc = BuggyQuorum(bus)
        Replica(bus, 1, value=10)
        Replica(bus, 2, value=20)
        bus.run_to_quiescence()
        assert svc.first == 10, "quorum raced: adopted the wrong reply"

    res = explore_dfs(scenario, max_runs=50)
    assert not res.ok, "explorer missed the planted race"
    with pytest.raises(AssertionError, match="quorum raced"):
        replay(scenario, res.failure_trace)


def scenario_peering_vs_writes(bus):
    """The peering statechart restarted mid-write-storm: in EVERY
    delivery interleaving of GetInfo replies, activation acks, sub-ops
    and repair traffic, the PG ends Active with all acked writes
    readable and the statechart history well-formed."""
    from ceph_tpu.osd.peering import PeeringCoordinator, PState
    backend = _mk_backend(bus)
    coord = PeeringCoordinator(backend)
    a, b = _payload(8), _payload(9)
    commits = []
    backend.submit_transaction(PGTransaction().write("obj", 0, a),
                               on_commit=lambda t: commits.append("a"))
    coord.advance_map(epoch=3)      # peer while the write is in flight
    backend.submit_transaction(PGTransaction().write("obj", 0, b),
                               on_commit=lambda t: commits.append("b"))
    bus.run_to_quiescence()
    assert coord.state is PState.ACTIVE, coord.state
    assert commits == ["a", "b"], commits
    assert _read(backend, bus, "obj") == b
    # the history never skips states within one epoch
    seq = [s for e, s in coord.history if e == 3]
    assert seq[0] == PState.GET_INFO.value
    assert seq[-1] == PState.ACTIVE.value


def test_peering_vs_writes_schedules():
    res = explore_random(scenario_peering_vs_writes, schedules=30)
    assert res.ok, f"trace {res.failure_trace}: {res.failure}"
    res = explore_dfs(scenario_peering_vs_writes, max_runs=60)
    assert res.ok, f"trace {res.failure_trace}: {res.failure}"
