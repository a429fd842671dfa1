"""A sub-write acks from its store's commit callback (ISSUE 38).

``BlueStoreLite.queue_transaction(t, on_commit)`` stages on the caller's
thread and queues the I/O; a group commit (``bluestore.commit_queued``)
writes the queued transactions of the stores a waiter names (the bus:
those its held replies wait for; a store: itself) in one native call and
calls each ``on_commit`` once its record is in.  ``OSDShard`` hands its
``ECSubWriteReply`` to the bus as that callback (``send_on_commit``), and
the bus sends the replies in the order their commits were queued, once
they are durable, before ``deliver_all`` (or ``deliver_one``) returns.
Each case fails on the parent or pins the new contract.  CPU, tiny
sizes: counts and correctness only.
"""
import os
import signal
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from ceph_tpu.backend import bluestore
from ceph_tpu.backend.bluestore import BlueStoreLite
from ceph_tpu.backend import ecutil
from ceph_tpu.backend.ecutil import SMALL_CRC_MAX, crc32c
from ceph_tpu.backend.memstore import GObject, Transaction
from ceph_tpu.backend.messages import (ECSubWrite, ECSubWriteReply,
                                       FaultConfig, MessageBus)
from ceph_tpu.backend.pg_backend import OSDShard
from ceph_tpu.cluster import MiniCluster
from ceph_tpu.common.tracer import default_tracer
from ceph_tpu.osd.pg_log import OP_MODIFY, PGLogEntry

REPO = Path(__file__).resolve().parents[1]
K, M = 2, 1
N = K + M
PROFILE = {"k": str(K), "m": str(M), "device": "numpy"}
SIZE = 4096


def _data(seed, n=SIZE):
    return np.random.default_rng([seed, 38]).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _cluster(path):
    c = MiniCluster(n_osds=N, osds_per_host=1, chunk_size=1024,
                    data_dir=path, store_backend="bluestore")
    return c, c.create_ec_pool("p", dict(PROFILE), pg_num=1)


def _state(c):
    """Every OSD store's objects (bytes, xattrs, omap) and each PG shard's
    log: what a put leaves behind, ``rb.`` keys included."""
    stores = {}
    for osd, d in c.osds.items():
        st = d.store
        stores[osd] = {g: (st.read(g), st.getattrs(g), st.get_omap(g))
                       for g in st.list_objects()}
    g = c.pools[c.pool_ids["p"]]["pgs"][0]
    logs = {s: [(e.version, e.oid) for e in
                (h.pg_log if isinstance(h, OSDShard)
                 else h.local_shard.pg_log).entries]
            for s, h in g.bus.handlers.items()}
    return stores, logs


def _replies_to(bus):
    """Record every ECSubWriteReply the bus sends."""
    sent = []
    real = bus.send

    def send(to, msg):
        inner = getattr(msg, "msg", msg)
        if isinstance(inner, ECSubWriteReply):
            sent.append(inner)
        real(to, msg)
    bus.send = send
    return sent


# -- the cluster --------------------------------------------------------------

def test_puts_through_deliver_all_leave_what_stepped_puts_leave(tmp_path):
    """Overlapped (deliver_all: every shard stages, then one group commit)
    and stepped (deliver_one: each delivery waits for its own commit)
    leave the stores, the PG logs and the rb keys bit-equal."""
    puts = {f"o{i:02d}": _data(i) for i in range(6)}
    a, pid_a = _cluster(tmp_path / "a")
    b, pid_b = _cluster(tmp_path / "b")
    try:
        a.others_waiting = b.others_waiting = lambda: True
        for oid, data in puts.items():
            a.put(pid_a, oid, data)
            g = b.put(pid_b, oid, data, deliver=False)
            while any(g.bus.deliver_one(s) for s in list(g.bus.queues)):
                pass
            assert not b.bus._acks
        assert _state(a) == _state(b)
        stores, _logs = _state(a)
        assert any(k.startswith("rb.") for objs in stores.values()
                   for _d, _x, omap in objs.values() for k in omap)
        for oid, data in puts.items():
            assert b.get(pid_b, oid, SIZE) == data
    finally:
        a.shutdown()
        b.shutdown()


def test_no_ack_leaves_before_the_record_is_written(tmp_path, monkeypatch):
    c, pid = _cluster(tmp_path)
    c.put(pid, "warm", _data(0))
    sent = _replies_to(c.bus)
    reached, gate = threading.Event(), threading.Event()
    real = bluestore._write_txns

    def held(txns):
        reached.set()
        assert gate.wait(30)
        return real(txns)
    monkeypatch.setattr(bluestore, "_write_txns", held)
    wal = [d.store.path / "kv.log" for d in c.osds.values()]
    sizes = [p.stat().st_size for p in wal]
    done = []
    t = threading.Thread(target=lambda: done.append(
        c.put(pid, "gated", _data(1))))
    t.start()
    try:
        assert reached.wait(30)
        t.join(0.2)
        assert t.is_alive() and not done          # put has not returned
        assert sent == []                         # no shard acked
        assert [p.stat().st_size for p in wal] == sizes
        assert len(c.bus._acks) == N
    finally:
        gate.set()
        t.join(30)
    assert done and not t.is_alive()
    assert sorted(r.from_shard for r in sent) == list(range(N))
    assert all(p.stat().st_size > s for p, s in zip(wal, sizes))
    assert c.get(pid, "gated", SIZE) == _data(1)
    c.shutdown()


_CHILD = r"""
import sys
import numpy as np
from ceph_tpu.cluster import MiniCluster
c = MiniCluster(n_osds=3, osds_per_host=1, chunk_size=1024,
                data_dir=sys.argv[1], store_backend="bluestore")
pid = c.create_ec_pool("p", {"k": "2", "m": "1", "device": "numpy"},
                       pg_num=1)
print("ready", flush=True)
i = 0
while True:
    data = np.random.default_rng([i, 38]).integers(
        0, 256, 4096, dtype=np.uint8).tobytes()
    c.put(pid, f"o{i:04d}", data)
    print(f"acked o{i:04d}", flush=True)
    i += 1
"""


def test_kill_9_keeps_every_acked_put(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(tmp_path)], cwd=REPO, env=env,
        stdout=subprocess.PIPE, text=True)
    acked = []
    try:
        for line in child.stdout:
            if line.startswith("acked "):
                acked.append(line.split()[1])
                if len(acked) == 25:
                    break
    finally:
        child.send_signal(signal.SIGKILL)
        child.wait(30)
        child.stdout.close()
    assert len(acked) == 25
    c = MiniCluster.load(tmp_path)
    try:
        pid = c.pool_ids["p"]
        for oid in acked:
            assert c.get(pid, oid, SIZE) == _data(int(oid[1:]))
    finally:
        c.shutdown()


# -- the store ----------------------------------------------------------------

def _write(g, data):
    return Transaction().write(g, 0, data).setattr(g, "v", len(data))


def test_a_read_sees_a_write_whose_io_is_still_queued(tmp_path):
    s = BlueStoreLite(tmp_path / "s")
    g = GObject("x", 0)
    s.queue_transaction(_write(g, _data(1)))
    called = []
    s.queue_transaction(_write(g, _data(2, 8192)),
                        on_commit=lambda: called.append(1))
    assert s._queue and not called
    assert s.read(g) == _data(2, 8192)          # the read commits first
    assert called == [1] and not s._queue
    s.close()


def test_a_checkpoint_taken_with_records_queued_replays_to_the_same_state(
        tmp_path):
    s = BlueStoreLite(tmp_path / "s")
    called = []
    for i in range(5):
        s.queue_transaction(
            _write(GObject(f"o{i}", 0), _data(i)).omap_setkeys(
                GObject("meta", 0), {f"k{i}": b"v" * i}),
            on_commit=lambda i=i: called.append(i))
    s.checkpoint()
    assert called == list(range(5))
    s.queue_transaction(_write(GObject("late", 0), _data(9)),
                        on_commit=lambda: called.append(9))
    s.wait_committed()
    again = BlueStoreLite(tmp_path / "s")       # snapshot + one record
    for obj in s.list_objects():
        assert again.read(obj) == s.read(obj)
        assert again.getattrs(obj) == s.getattrs(obj)
        assert again.get_omap(obj) == s.get_omap(obj)
    assert again.list_objects() == s.list_objects()
    again.close(checkpoint=False)
    s.close()


def test_a_synchronous_commit_is_durable_on_return_and_follows_the_queue(
        tmp_path):
    s = BlueStoreLite(tmp_path / "s")
    queued = GObject("queued", 0)
    s.queue_transaction(_write(queued, _data(3)), on_commit=lambda: None)
    s.queue_transaction(_write(GObject("sync", 0), _data(4)))
    other = BlueStoreLite(tmp_path / "s")       # what the files say now
    assert other.read(queued) == _data(3)
    assert other.read(GObject("sync", 0)) == _data(4)
    other.close(checkpoint=False)
    s.close()


def test_an_io_error_is_kept_raised_and_acks_nothing(tmp_path):
    bad = BlueStoreLite(tmp_path / "bad")
    good = BlueStoreLite(tmp_path / "good")
    called = []
    good.queue_transaction(_write(GObject("g", 0), _data(5)),
                           on_commit=lambda: called.append("good"))
    bad.queue_transaction(_write(GObject("b", 0), _data(6)),
                          on_commit=lambda: called.append("bad"))
    # the journal's descriptor turned read-only under the store
    ro = os.open(tmp_path / "bad" / "kv.snap.ro", os.O_RDONLY | os.O_CREAT)
    os.dup2(ro, bad._wal.fileno())
    os.close(ro)
    bluestore.commit_queued([good, bad])        # one group, as the bus's
    assert called == ["good"]                   # the other store landed
    with pytest.raises(OSError):                # kept: raised on the wait
        bad.wait_committed()
    with pytest.raises(OSError):                # and again
        bad.wait_committed()
    with pytest.raises(OSError):                # and no more work taken
        bad.queue_transaction(_write(GObject("c", 0), _data(7)))
    good.close()
    with pytest.raises(OSError):
        bad.close(checkpoint=False)


def test_a_store_waits_for_its_own_queue_only(tmp_path):
    """A read, a checkpoint or a synchronous commit on one store writes
    that store's queue, and leaves another store's to its own waiter."""
    a, b = BlueStoreLite(tmp_path / "a"), BlueStoreLite(tmp_path / "b")
    called = []
    for s, name in ((a, "a"), (b, "b")):
        s.queue_transaction(_write(GObject(name, 0), _data(len(name))),
                            on_commit=lambda name=name: called.append(name))
    assert a.read(GObject("a", 0)) == _data(1)
    assert called == ["a"] and b._queue
    b.wait_committed()
    assert called == ["a", "b"] and not b._queue
    a.close()
    b.close()


def test_without_the_native_library_the_calls_are_made_one_by_one(
        tmp_path, monkeypatch):
    """The path a checkout without a toolchain takes: the same bytes, the
    same order, an error kept the same way."""
    monkeypatch.setattr(bluestore, "_native_io", lambda: None)
    s = BlueStoreLite(tmp_path / "s")
    called = []
    for i in range(3):
        s.queue_transaction(_write(GObject(f"o{i}", 0), _data(i, 5000)),
                            on_commit=lambda i=i: called.append(i))
    s.queue_transaction(_write(GObject("sync", 0), _data(9)))
    assert called == [0, 1, 2]
    again = BlueStoreLite(tmp_path / "s")
    for i in range(3):
        assert again.read(GObject(f"o{i}", 0)) == _data(i, 5000)
    assert again.read(GObject("sync", 0)) == _data(9)
    again.close(checkpoint=False)
    s.queue_transaction(_write(GObject("lost", 0), _data(4)),
                        on_commit=lambda: called.append("lost"))
    ro = os.open(tmp_path / "ro", os.O_RDONLY | os.O_CREAT)
    os.dup2(ro, s._wal.fileno())
    os.close(ro)
    with pytest.raises(OSError):
        s.wait_committed()
    assert called == [0, 1, 2]
    with pytest.raises(OSError):
        s.close(checkpoint=False)


@pytest.mark.parametrize("n", [0, 1, 8192, SMALL_CRC_MAX,
                               SMALL_CRC_MAX + 1, 512 * 1024])
def test_the_crc_is_crc32c_on_either_side_of_the_small_bound(
        n, monkeypatch):
    """Up to SMALL_CRC_MAX the native call keeps the interpreter, above
    it lets it go: the same crc either way, bytes or array, as the
    pure-Python oracle's."""
    data = _data(n, n)
    native = (crc32c(0xFFFFFFFF, data),
              crc32c(0xFFFFFFFF, np.frombuffer(data, np.uint8)))
    monkeypatch.setattr(ecutil, "_native_crc", False)
    assert native == (crc32c(0xFFFFFFFF, data),) * 2


def test_the_store_starts_no_thread(tmp_path):
    before = set(threading.enumerate())
    s = BlueStoreLite(tmp_path / "s")
    for i in range(20):
        s.queue_transaction(_write(GObject(f"o{i}", 0), _data(i)),
                            on_commit=lambda: None)
    s.close()
    assert set(threading.enumerate()) <= before


def test_the_counter_and_the_span(tmp_path):
    tr = default_tracer()
    before = tr.histograms().get("bus.commit_wait", {"count": 0})["count"]
    c, pid = _cluster(tmp_path)
    for i in range(3):
        c.put(pid, f"o{i}", _data(i))
    dump = {name: v for name, v in c.cct.perf.perf_dump().items()
            if name.startswith(f"bluestore.c{c.cluster_id}.")}
    assert len(dump) == N
    queued = sum(v["commits_queued"] for v in dump.values())
    txns = sum(v["transactions"] for v in dump.values())
    assert queued >= 3 * N and queued <= txns
    after = tr.histograms()["bus.commit_wait"]["count"]
    assert after - before >= 3
    c.shutdown()


# -- the bus and the shard ----------------------------------------------------

class _Primary:
    """Shard 0's handler: records the replies it is handed, and what the
    replying shard's store still had queued at that moment."""

    def __init__(self, bus, store):
        self.got, self.store = [], store
        bus.register(0, self)

    def handle_message(self, msg):
        self.got.append((type(msg).__name__, msg.tid,
                         list(self.store._queue)))


def _shard(tmp_path, bus):
    return OSDShard(1, bus, store=BlueStoreLite(tmp_path / "osd1"))


def _sub_write(version, payload):
    return ECSubWrite(from_shard=0, tid=version,
                      t=Transaction().write(GObject("x", 1), 0, payload),
                      at_version=version,
                      log_entries=[PGLogEntry(version, "x", OP_MODIFY)])


def test_a_duplicate_is_re_acked_behind_the_first_ack(tmp_path):
    bus = MessageBus()
    shard = _shard(tmp_path, bus)
    primary = _Primary(bus, shard.store)
    bus.send(1, _sub_write(1, _data(1)))
    # the first delivery and its immediate redelivery (a resend after a
    # connection reset) before the first's commit is written
    bus.inject_faults(FaultConfig(dup_prob=1.0, seed=1))
    assert bus._deliver(1)
    bus.inject_faults(None)
    assert len(bus._acks) == 2 and not bus._acks[0].durable
    assert primary.got == []
    bus.deliver_all()
    # two acks, in order, each once nothing of the store was unwritten
    assert primary.got == [("ECSubWriteReply", 1, []),
                           ("ECSubWriteReply", 1, [])]
    shard.store.close()


def test_a_failed_commit_raises_from_deliver_all_and_sends_no_ack(tmp_path):
    bus = MessageBus()
    shard = _shard(tmp_path, bus)
    primary = _Primary(bus, shard.store)
    ro = os.open(tmp_path / "ro", os.O_RDONLY | os.O_CREAT)
    os.dup2(ro, shard.store._wal.fileno())
    os.close(ro)
    bus.send(1, _sub_write(1, _data(2)))
    with pytest.raises(OSError):
        bus.deliver_all()
    assert primary.got == [] and not bus._acks
    bus.deliver_all()                           # nothing held any more
    assert primary.got == []
    with pytest.raises(OSError):
        shard.store.close(checkpoint=False)


def test_memstore_shards_ack_inline_as_before():
    bus = MessageBus()
    OSDShard(1, bus)
    got = []
    bus.register(0, type("P", (), {"handle_message":
                                   lambda self, m: got.append(m)})())
    bus.send(1, _sub_write(1, _data(3)))
    assert bus.deliver_one(1)
    # sent from inside the delivery, as on the parent: nothing held
    assert not bus._acks and len(bus.queues[0]) == 1
    assert bus.deliver_one(0) and isinstance(got[0], ECSubWriteReply)
