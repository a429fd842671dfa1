"""A partial write served over the wire: ``TcpRados.write`` →
``ClusterServer._rpc_write`` → ``MiniCluster.operate`` → the op engine →
the EC backend's read-modify-write, on a k=8 m=4 pool at a 4 KiB and a
512 B stripe unit.

Every case holds the object to a plain model — a ``bytearray`` with the
acked writes applied in ack order — and its twelve stored shards to that
model's encode by ``gf/ref.py``: inside a chunk, unaligned, across a
chunk and a stripe boundary, past the object's end, with a data shard's
OSD down (the RMW read reconstructs), two clients writing different
blocks of one stripe at once, and a resent call.  Then the rollback a
shard keeps for an overwrite: the extents it covers and the old size,
not the whole shard object (``rollback_bytes``), and a rolled-back
overwrite leaves the old bytes exactly.

CPU, small sizes but one: counts and correctness only.
"""
import threading

import numpy as np
import pytest

from ceph_tpu.backend import ECBackend, MessageBus, PGTransaction, StripeInfo
from ceph_tpu.backend.bluestore import BlueStoreLite
from ceph_tpu.backend.ecutil import HINFO_KEY
from ceph_tpu.backend.memstore import GObject
from ceph_tpu.backend.pg_backend import OSDShard, shard_store
from ceph_tpu.cluster import MiniCluster
from ceph_tpu.gf import matrix as gfm
from ceph_tpu.gf import ref as gfref
from ceph_tpu.net import ClusterServer, RpcCall, TcpRados
from ceph_tpu.plugins.registry import ErasureCodePluginRegistry

K, M = 8, 4
PROFILE = {"plugin": "jax_rs", "k": str(K), "m": str(M),
           "technique": "cauchy", "device": "jax"}
PARITY = gfm.cauchy1(K, M)


def _bytes(n, *seed):
    return np.random.default_rng([*seed, 41]).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def model_shards(data: bytes, chunk: int) -> np.ndarray:
    """[k + m, stripes * chunk]: data shard i holds chunk i of every
    stripe, the tail stripe zero-padded, parity by ``gf/ref.py``."""
    width = K * chunk
    stripes = -(-len(data) // width)
    buf = np.zeros(stripes * width, dtype=np.uint8)
    buf[:len(data)] = np.frombuffer(data, dtype=np.uint8)
    shards = np.ascontiguousarray(
        buf.reshape(stripes, K, chunk).transpose(1, 0, 2)
        .reshape(K, stripes * chunk))
    return np.concatenate([shards, gfref.encode(PARITY, shards)])


def apply(model: bytearray, offset: int, data: bytes) -> None:
    end = offset + len(data)
    if len(model) < end:
        model.extend(b"\0" * (end - len(model)))
    model[offset:end] = data


class Served:
    """A 12-OSD bluestore cluster behind a ClusterServer, one EC pool."""

    def __init__(self, path, chunk, pg_num=4):
        self.chunk = chunk
        self.c = MiniCluster(n_osds=K + M, osds_per_host=1, chunk_size=chunk,
                             data_dir=path, store_backend="bluestore")
        self.serving = self.c.enable_serving(start=True)
        self.server = ClusterServer(self.c)
        self.server.start()
        self.keyring = path / "client.admin.keyring"
        self.r = self.client()
        self.pool = self.r.mkpool("p", profile=dict(PROFILE), pg_num=pg_num)

    def client(self) -> TcpRados:
        return TcpRados("127.0.0.1", self.server.port, self.keyring)

    def close(self):
        self.r.close()
        self.server.stop()
        self.serving.stop()
        self.c.shutdown()

    def counter(self, key):
        prefix = f"ec_backend.c{self.c.cluster_id}."
        return sum(v[key] for name, v in self.c.cct.perf.perf_dump().items()
                   if name.startswith(prefix))

    def check(self, oid, model: bytearray) -> None:
        """The object reads back as the model; every shard on an up OSD
        is the model's encode, and its HashInfo holds no crc at all or
        the crc of what the shard holds."""
        assert self.r.get("p", oid) == bytes(model)
        want = model_shards(bytes(model), self.chunk)
        with self.server.lock:
            g = self.c.pg_group(self.pool, oid)
            for chunk, osd in enumerate(g.acting):
                if osd in g.bus.down:
                    continue
                store = shard_store(g.bus, osd)
                stored = store.read(GObject(oid, osd))
                hinfo = store.getattr(GObject(oid, osd), HINFO_KEY)
                assert bytes(stored) == want[chunk].tobytes(), chunk
                assert hinfo["total_chunk_size"] == want.shape[1]
                assert hinfo["cumulative_shard_hashes"] == [], \
                    "an overwrite leaves no crc it did not compute"


@pytest.fixture(scope="module", params=[4096, 512], ids=["4k", "512"])
def served(request, tmp_path_factory):
    s = Served(tmp_path_factory.mktemp(f"ow{request.param}"), request.param)
    s.r.put("p", "warm", _bytes(4 * K * request.param, 0))  # compiles here
    s.r.write("p", "warm", _bytes(100, 1), 7)
    yield s
    s.close()


def _cases(chunk):
    width = K * chunk
    size = 4 * width
    return size, {
        "aligned_4k": (2 * width + 4096 * (chunk >= 4096), 4096),
        "aligned_one_chunk": (width + 3 * chunk, chunk),
        "unaligned": (width + 2 * chunk + 100, chunk // 2 + 37),
        "across_a_chunk": (3 * chunk - 300, 600),
        "across_a_stripe": (2 * width - 700, 1400),
        "past_the_end": (size - 1000, 3000 + chunk),
    }


CASES = list(_cases(4096)[1])


@pytest.mark.parametrize("case", CASES)
def test_a_served_write_equals_the_model_and_its_encode(case, served):
    size, cases = _cases(served.chunk)
    offset, n = cases[case]
    oid = f"obj.{case}"
    model = bytearray(_bytes(size, served.chunk, len(oid)))
    served.r.put("p", oid, bytes(model))
    rmw = served.counter("rmw_ops")
    data = _bytes(n, served.chunk, offset)
    assert served.r.write("p", oid, data, offset) == n
    apply(model, offset, data)
    served.check(oid, model)
    # a write that covers part of a stripe reads it first (an aligned
    # 4 KiB write at the 512 B unit is a whole stripe and reads nothing)
    whole = offset % (K * served.chunk) == 0 and n % (K * served.chunk) == 0
    assert served.counter("rmw_ops") == rmw + (not whole)
    assert served.r.stat("p", oid)[0] == len(model)


def test_a_resent_write_is_applied_once(served):
    """A resend of a write that a newer write has since covered is
    answered from the first execution's result: applying it again would
    undo the newer write."""
    oid = "obj.resend"
    chunk = served.chunk
    model = bytearray(_bytes(2 * K * chunk, 7))
    served.r.put("p", oid, bytes(model))
    first, second = _bytes(chunk, 8), _bytes(chunk, 9)
    call = RpcCall(1, "write", {"pool": "p", "oid": oid, "offset": chunk,
                                "data": first}, session="resend-session")
    assert served.server._dispatch(None, call).value == chunk
    served.r.write("p", oid, second, chunk)
    writes = served.counter("writes")
    hits = served.server.rpc_dedup_hits
    again = served.server._dispatch(None, call)
    assert again.ok and again.value == chunk
    assert served.server.rpc_dedup_hits == hits + 1
    assert served.counter("writes") == writes
    apply(model, chunk, second)
    served.check(oid, model)


def test_two_clients_writing_one_stripe_at_once_lose_no_update(served):
    """Each client writes its own chunk of the same stripe, round after
    round, started together: every write reads the stripe as the one
    before it left it, so both clients' bytes survive each round."""
    oid = "obj.two_writers"
    chunk = served.chunk
    model = bytearray(_bytes(2 * K * chunk, 10))
    served.r.put("p", oid, bytes(model))
    clients = [served.client(), served.client()]
    try:
        for rnd in range(4):
            go = threading.Barrier(2)
            writes = [(ci * chunk + K * chunk, _bytes(chunk, 11, rnd, ci))
                      for ci in range(2)]
            errors = []

            def run(ci):
                try:
                    go.wait()
                    off, data = writes[ci]
                    clients[ci].write("p", oid, data, off)
                except Exception as e:   # noqa: BLE001 — asserted below
                    errors.append(e)
            threads = [threading.Thread(target=run, args=(ci,))
                       for ci in range(2)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(60)
            assert not errors
            for off, data in writes:
                apply(model, off, data)
            served.check(oid, model)
    finally:
        for cl in clients:
            cl.close()


def test_a_write_with_a_data_shard_down_reconstructs_the_stripe(tmp_path):
    """One data shard's OSD down (not the primary): the RMW read decodes
    the stripe from k of the eleven others, the write lands on those
    eleven, and the object reads back as the model."""
    chunk = 512
    s = Served(tmp_path, chunk, pg_num=1)
    try:
        oid = "obj.degraded"
        model = bytearray(_bytes(4 * K * chunk, 12))
        s.r.put("p", oid, bytes(model))
        g = s.c.pg_group(s.pool, oid)
        down = g.acting[3]               # data chunk 3, not the primary
        with s.server.lock:
            g.bus.mark_down(down)
        read = s.counter("rmw_read_bytes")
        for i, (off, n) in enumerate([(3 * chunk + 10, 200),
                                      (K * chunk + 3 * chunk, chunk),
                                      (2 * K * chunk - 50, 100)]):
            data = _bytes(n, 13, i)
            s.r.write("p", oid, data, off)
            apply(model, off, data)
        s.check(oid, model)
        assert s.counter("rmw_read_bytes") > read
        with s.server.lock:
            assert g.bus.down == {down}
    finally:
        s.close()


# -- rollback sized to the extent ------------------------------------------------

def test_a_4k_overwrite_of_a_4m_object_captures_4k_a_shard(tmp_path):
    """At the deployment's own sizes: a 4 MiB object is a 512 KiB object
    on each of twelve shards, and a 4 KiB overwrite keeps 4 KiB a shard
    to undo it (12 x 4,096 bytes), not the shard objects (12 x 524,288);
    the overwrite's RMW read brings k chunks of one stripe."""
    chunk = 4096
    s = Served(tmp_path, chunk, pg_num=1)
    try:
        model = bytearray(_bytes(4 << 20, 14))
        s.r.put("p", "img.0", bytes(model))
        s.r.put("p", "img.1", bytes(model))         # its undo: a remove
        assert s.counter("rollback_bytes") == 0
        before = {key: s.counter(key) for key in
                  ("rollback_bytes", "rmw_ops", "rmw_read_bytes",
                   "overwrite_bytes")}
        data = _bytes(4096, 15)
        s.r.write("p", "img.0", data, 345 * 4096)
        apply(model, 345 * 4096, data)
        got = {key: s.counter(key) - v for key, v in before.items()}
        assert got == {"rollback_bytes": (K + M) * 4096, "rmw_ops": 1,
                       "rmw_read_bytes": K * 4096, "overwrite_bytes": 4096}
        s.check("img.0", model)
    finally:
        s.close()


def _store_read_bytes(s):
    """Each OSD store's ``read_block_bytes``: bytes it read from its
    block file and verified."""
    prefix = f"bluestore.c{s.c.cluster_id}.osd"
    return {name: v["read_block_bytes"]
            for name, v in s.c.cct.perf.perf_dump().items()
            if name.startswith(prefix)}


def test_a_4k_overwrite_reads_4k_blocks_of_the_512k_shards(tmp_path):
    """The RMW read (k data shards) and the undo (every shard) of a 4 KiB
    overwrite read the 4 KiB block they cover from each shard's 512 KiB
    blob, with its crc, not the blob: 8 KiB on a data shard, 4 KiB on a
    parity shard.  The object still reads back exactly."""
    chunk = 4096
    s = Served(tmp_path, chunk, pg_num=1)
    try:
        model = bytearray(_bytes(4 << 20, 23))
        s.r.put("p", "img.0", bytes(model))
        with s.server.lock:
            assert all(512 * 1024 in {b.raw_len for b in
                                      s.c.osds[osd].store.blobs.values()}
                       for osd in range(K + M))
        before = _store_read_bytes(s)
        data = _bytes(4096, 24)
        s.r.write("p", "img.0", data, 345 * 4096 + 8 * 4096 * 3)
        apply(model, 345 * 4096 + 8 * 4096 * 3, data)
        read = {name: n - before[name]
                for name, n in _store_read_bytes(s).items()}
        assert len(read) == K + M
        assert all(0 < n <= 2 * 4096 for n in read.values()), read
        assert sum(read.values()) == (K + K + M) * 4096
        s.check("img.0", model)
    finally:
        s.close()


CHUNK = 512
STRIPE = K * CHUNK


@pytest.fixture(params=["memstore", "bluestore"])
def pg(request, tmp_path):
    """One PG in process: a primary and eleven shards on a private bus,
    min_size k + 1, on in-RAM or bluestore shards."""
    ec = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", {**PROFILE, "device": "numpy"})
    stores = {}
    if request.param == "bluestore":
        stores = {s: BlueStoreLite(tmp_path / f"osd{s}") for s in range(K + M)}
    bus = MessageBus()
    backend = ECBackend(ec, StripeInfo(K, CHUNK), bus,
                        acting=list(range(K + M)), whoami=0,
                        min_size=K + 1, store=stores.get(0))
    for s in range(1, K + M):
        OSDShard(s, bus, store=stores.get(s))
    yield backend, bus
    for store in stores.values():
        store.close(checkpoint=False)


def _stored(bus, oid):
    return {s: bytes(shard_store(bus, s).read(GObject(oid, s)))
            for s in range(K + M)}


def shard_obj(bus, shard):
    h = bus.handlers[shard]
    return h if isinstance(h, OSDShard) else h.local_shard


def base_shard(data, s):
    return model_shards(data, CHUNK)[s].tobytes()


@pytest.mark.parametrize("where", ["inside", "growing"])
def test_a_rolled_back_overwrite_leaves_the_old_bytes_exactly(where, pg):
    """An overwrite reads its stripe, then reaches two shards and applies
    there; then m shards die, the PG falls below min_size and the write rolls back on
    the survivors: each holds byte for byte what it held, at its old
    size, with its old HashInfo — from an undo of the extents alone."""
    backend, bus = pg
    old = _bytes(3 * STRIPE, 16)
    backend.submit_transaction(PGTransaction().write("obj", 0, old))
    bus.deliver_all()
    was = _stored(bus, "obj")
    hinfo_was = {s: shard_store(bus, s).getattr(GObject("obj", s), HINFO_KEY)
                 for s in range(K + M)}
    off, n = (STRIPE + 3 * CHUNK + 5, 300) if where == "inside" \
        else (3 * STRIPE - 100, STRIPE)
    captured = backend.perf.get("rollback_bytes")
    committed = []
    backend.submit_transaction(
        PGTransaction().write("obj", off, _bytes(n, 17)),
        on_commit=committed.append)
    while not backend.waiting_commit:        # the RMW read, then fan-out
        assert any(bus.deliver_one(s) for s in range(K + M))
    while bus.deliver_one(1) or bus.deliver_one(2):
        pass
    for s in (1, 2):
        undo = shard_obj(bus, s).pending_rollbacks[backend.pg_log.head]
        kinds = [op[0] for op in undo.ops]
        assert "remove" not in kinds and kinds.count("write") == 1
        assert ("truncate" in kinds) == (where == "growing")
    assert backend.perf.get("rollback_bytes") > captured
    for s in range(K, K + M):
        bus.mark_down(s)
    bus.deliver_all()
    assert not committed, "acked below min_size"
    now = _stored(bus, "obj")
    for s in range(K):
        assert now[s] == was[s], f"shard {s} kept the rolled-back write"
        assert shard_store(bus, s).getattr(GObject("obj", s), HINFO_KEY) \
            == hinfo_was[s]
        assert not shard_obj(bus, s).pending_rollbacks


def test_an_overwrites_undo_is_its_extent_and_a_new_objects_a_remove(pg):
    """What a shard keeps to undo a write: the old bytes under an
    overwrite (and its old HashInfo), a remove for an object the write
    creates, the whole object where the write truncates it."""
    backend, bus = pg
    base = _bytes(2 * STRIPE, 18)
    backend.submit_transaction(PGTransaction().write("a", 0, base))
    bus.deliver_all()
    before = backend.perf.get("rollback_bytes")
    t = PGTransaction().write("a", STRIPE + 2 * CHUNK, _bytes(CHUNK, 19))
    t.write("b", 0, _bytes(STRIPE, 20))
    backend.defer_kick = lambda b: True       # keep the undo records
    backend.submit_transaction(t)
    bus.deliver_all()
    v = backend.pg_log.head
    for s in range(K + M):
        undo = shard_obj(bus, s).pending_rollbacks[v]
        by = {}
        for op in undo.ops:
            by.setdefault(op[1].oid, []).append(op)
        assert [op[0] for op in by["b"]] == ["remove"]
        writes = [op for op in by["a"] if op[0] == "write"]
        assert [(op[2], len(op[3])) for op in writes] == [(CHUNK, CHUNK)]
        assert writes[0][3] == base_shard(base, s)[CHUNK:2 * CHUNK]
        assert {op[2] for op in by["a"] if op[0] == "setattr"} >= {HINFO_KEY}
    assert backend.perf.get("rollback_bytes") - before == (K + M) * CHUNK
    backend.submit_transaction(PGTransaction().truncate_to("a", STRIPE))
    bus.deliver_all()
    undo = shard_obj(bus, 1).pending_rollbacks[backend.pg_log.head]
    assert undo.ops[0][0] == "remove" and \
        [len(op[3]) for op in undo.ops if op[0] == "write"] == [2 * CHUNK]


@pytest.mark.parametrize("store_backend", ["file", "bluestore"])
def test_an_overwrite_cut_by_a_crash_rolls_back_in_boot_peering(
        tmp_path, store_backend):
    """The overwrite reads its stripe and reaches the primary's own store
    alone when the process dies.  Boot peering counts fewer than
    min_size witnesses and unwinds it from the extent undo the store
    journaled: the old bytes read back exactly and scrub finds every
    shard consistent."""
    from ceph_tpu.osd.osd_ops import ObjectOperation
    c1 = MiniCluster(n_osds=K + M, chunk_size=CHUNK, data_dir=tmp_path,
                     store_backend=store_backend)
    pid = c1.create_ec_pool("pool", {**PROFILE, "device": "numpy"},
                            pg_num=1)
    old = _bytes(4 * STRIPE, 21)
    c1.put(pid, "x", old)
    g = c1.pools[pid]["pgs"][0]
    c1.operate(pid, "x", ObjectOperation().write(STRIPE + 5, _bytes(700, 22)),
               deliver=False)
    c1.osds[g.backend.whoami].drain()
    while not g.backend.waiting_commit:      # the RMW read completes
        assert any(g.bus.deliver_one(osd) for osd in g.acting)
    primary = g.backend.whoami
    while g.bus.deliver_one(primary):        # only its own sub-write applies
        pass
    undo = g.backend.local_shard.pending_rollbacks[g.backend.pg_log.head]
    assert "remove" not in [op[0] for op in undo.ops]
    c1.shutdown()                            # the process dies here

    c2 = MiniCluster.load(tmp_path)
    try:
        pid2 = c2.pool_ids["pool"]
        assert c2.get(pid2, "x", len(old)) == old
        gg = c2.pools[pid2]["pgs"][0]
        assert all(gg.backend.be_deep_scrub("x").values())
    finally:
        c2.shutdown()


def test_a_rolled_back_4k_overwrite_of_a_512k_shard_restores_it(tmp_path):
    """At the RBD pool's shape (4 KiB chunks, a 4 MiB object, 512 KiB a
    shard): the overwrite reaches two shards, which read only the block
    they undo, then rolls back below min_size to the old bytes."""
    chunk = 4096
    ec = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", {**PROFILE, "device": "numpy"})
    stores = {s: BlueStoreLite(tmp_path / f"osd{s}") for s in range(K + M)}
    bus = MessageBus()
    backend = ECBackend(ec, StripeInfo(K, chunk), bus,
                        acting=list(range(K + M)), whoami=0,
                        min_size=K + 1, store=stores[0])
    for s in range(1, K + M):
        OSDShard(s, bus, store=stores[s])
    try:
        old = _bytes(4 << 20, 25)
        backend.submit_transaction(PGTransaction().write("obj", 0, old))
        bus.deliver_all()
        was = _stored(bus, "obj")
        read = {s: st.perf.get("read_block_bytes") for s, st in stores.items()}
        committed = []
        backend.submit_transaction(
            PGTransaction().write("obj", 77 * K * chunk + 5 * chunk,
                                  _bytes(chunk, 26)),
            on_commit=committed.append)
        while not backend.waiting_commit:    # the RMW read, then fan-out
            assert any(bus.deliver_one(s) for s in range(K + M))
        while bus.deliver_one(1) or bus.deliver_one(2):
            pass
        for s in (1, 2):
            undo = shard_obj(bus, s).pending_rollbacks[backend.pg_log.head]
            assert [len(op[3]) for op in undo.ops if op[0] == "write"] \
                == [chunk]
        assert all(st.perf.get("read_block_bytes") - read[s] <= 2 * chunk
                   for s, st in stores.items())
        for s in range(K, K + M):
            bus.mark_down(s)
        bus.deliver_all()
        assert not committed, "acked below min_size"
        now = _stored(bus, "obj")
        for s in range(K):
            assert now[s] == was[s], f"shard {s} kept the rolled-back write"
    finally:
        for st in stores.values():
            st.close(checkpoint=False)
