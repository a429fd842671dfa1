"""Async messenger (ISSUE 14): reactor, zero-copy parser, session
multiplexing, write-queue backpressure, shed ladder, sharded front end.

The bounded-thread contract — the whole point of replacing the
thread-per-connection transport — is pinned here: a served cluster plus
thousands of logical sessions costs a FIXED set of threads (reactor +
dispatch pool + one sender), never one per connection or per client.
"""
import random
import socket
import threading
import time

import numpy as np
import pytest

from ceph_tpu.backend.wire import (BANNER, FrameParser, TAG_MESSAGE,
                                   WireError, frame_encode)
from ceph_tpu.msg import (AsyncConnection, MuxClient, Reactor, ShedPolicy,
                          ShardedFrontend, StreamParser)
from ceph_tpu.msg.frontend import FrontendBusy
from ceph_tpu.msg.shed import DEFAULT_SHED_FRACTIONS, EBUSY
from ceph_tpu.osd.mclock import BG_SCRUB, CLIENT_OP


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


# -- reactor -----------------------------------------------------------------

class TestReactor:
    def test_call_soon_crosses_threads(self):
        r = Reactor(name="t-soon").start()
        try:
            hits = []
            ev = threading.Event()
            r.call_soon(lambda: (hits.append(threading.current_thread()),
                                 ev.set()))
            assert ev.wait(5.0)
            # the callback ran ON the loop thread, not the caller's
            assert hits[0].name == "reactor.t-soon"
        finally:
            r.stop()

    def test_call_later_ordering_and_cancel(self):
        r = Reactor(name="t-timer").start()
        try:
            order = []
            done = threading.Event()
            r.call_later(0.05, lambda: order.append("b"))
            r.call_later(0.01, lambda: order.append("a"))
            t = r.call_later(0.02, lambda: order.append("cancelled"))
            t.cancel()
            r.call_later(0.08, lambda: (order.append("c"), done.set()))
            assert done.wait(5.0)
            assert order == ["a", "b", "c"]
        finally:
            r.stop()

    def test_stop_joins_loop_thread(self):
        r = Reactor(name="t-stop").start()
        assert r.running
        r.stop()
        assert not r.running
        assert not any(t.name == "reactor.t-stop"
                       for t in threading.enumerate())


# -- zero-copy stream parser -------------------------------------------------

def _rand_chunks(blob: bytes, rng: random.Random):
    i = 0
    while i < len(blob):
        n = rng.randint(1, 97)
        yield blob[i:i + n]
        i += n


class TestStreamParser:
    SECRETS = (None, b"k" * 32)

    def _frames(self, secret, n=12, seed=3):
        rng = random.Random(seed)
        out = []
        for i in range(n):
            segs = [bytes([65 + i]) * rng.randint(0, 5000)
                    for _ in range(rng.randint(1, 4))]
            out.append((TAG_MESSAGE,
                        [bytes(s) for s in segs],
                        frame_encode(TAG_MESSAGE, segs, secret=secret)))
        return out

    @pytest.mark.parametrize("secret", SECRETS,
                             ids=["crc", "secure"])
    def test_equivalent_to_frameparser_any_chunking(self, secret):
        """Same frames out of the same bytes, regardless of where recv
        boundaries fall — including 1-byte feeds mid-preamble/mid-MAC —
        and the same real on-wire sizes FrameParser.track_sizes reports."""
        frames = self._frames(secret)
        blob = b"".join(f[2] for f in frames)
        ref = FrameParser(secret)
        ref.track_sizes = True
        ref_out = ref.feed(blob)
        for seed in (1, 2, 7):
            sp = StreamParser(secret)
            got = []
            for chunk in _rand_chunks(blob, random.Random(seed)):
                for tag, segs in sp.feed(chunk):
                    got.append((tag, [bytes(s) for s in segs]))
            assert got == [(t, list(s)) for t, s in ref_out]
            assert sp.frame_sizes == ref.frame_sizes
            assert sp.pending() == 0

    def test_banner_is_stream_state(self):
        f = frame_encode(TAG_MESSAGE, [b"hello"])
        sp = StreamParser(expect_banner=True)
        blob = BANNER + f
        assert sp.feed(blob[:5]) == []
        out = sp.feed(blob[5:])
        assert [bytes(s) for _, s in out for s in s] == [b"hello"]
        with pytest.raises(WireError, match="banner"):
            StreamParser(expect_banner=True).feed(b"X" * len(BANNER))

    def test_corruption_raises_wire_error(self):
        good = frame_encode(TAG_MESSAGE, [b"payload" * 100])
        flipped = bytearray(good)
        flipped[len(good) // 2] ^= 0xFF
        with pytest.raises(WireError):
            StreamParser(None).feed(bytes(flipped))
        sec = frame_encode(TAG_MESSAGE, [b"payload"], secret=b"s" * 32)
        bad_mac = bytearray(sec)
        bad_mac[-1] ^= 0xFF
        with pytest.raises(WireError, match="MAC"):
            StreamParser(b"s" * 32).feed(bytes(bad_mac))

    def test_mid_stream_secret_switch(self):
        """The post-auth handoff: crc frames, then set_secret, then
        HMAC frames — one parser, one buffer."""
        key = b"q" * 32
        sp = StreamParser(None)
        a = sp.feed(frame_encode(TAG_MESSAGE, [b"clear"]))
        sp.set_secret(key)
        b = sp.feed(frame_encode(TAG_MESSAGE, [b"sealed"], secret=key))
        assert bytes(a[0][1][0]) == b"clear"
        assert bytes(b[0][1][0]) == b"sealed"

    def test_compaction_survives_long_streams(self):
        """Many frames through one parser: the consumed head compacts
        (no unbounded buffer growth) and every frame still parses."""
        sp = StreamParser(None)
        seen = 0
        payload = b"z" * 40_000
        for _ in range(16):
            for _, segs in sp.feed(
                    frame_encode(TAG_MESSAGE, [payload])):
                assert bytes(segs[0]) == payload
                seen += 1
        assert seen == 16
        assert len(sp._buf) < 3 * (len(payload) + 64)


# -- write-queue backpressure ------------------------------------------------

class TestBackpressure:
    def test_send_bounded_by_throttle_then_connection_error(self):
        """A peer that never drains exhausts the byte budget: send()
        blocks for its timeout, then fails AND closes the link — never
        an unbounded outbound buffer.  (register=False keeps the
        reactor from flushing, so the queue genuinely stalls.)"""
        import ceph_tpu.net as net
        a, b = socket.socketpair()
        r = Reactor(name="t-bp").start()
        try:
            conn = AsyncConnection(a, r, name="bp", secret=b"k" * 32,
                                   write_queue_bytes=8192,
                                   register=False)
            conn.send(net.RpcCall(1, "noop", {"blob": b"x" * 3000}),
                      timeout=0.5)
            conn.send(net.RpcCall(2, "noop", {"blob": b"x" * 3000}),
                      timeout=0.5)
            with pytest.raises(ConnectionError, match="write queue full"):
                conn.send(net.RpcCall(3, "noop", {"blob": b"x" * 3000}),
                          timeout=0.3)
            assert conn.closed
        finally:
            r.stop()
            a.close(), b.close()

    def test_budget_released_as_peer_drains(self):
        import ceph_tpu.net as net
        a, b = socket.socketpair()
        r = Reactor(name="t-drain").start()
        try:
            conn = AsyncConnection(a, r, name="drain", secret=b"k" * 32,
                                   write_queue_bytes=64 * 1024)
            for i in range(20):
                conn.send(net.RpcCall(i, "noop", {"blob": b"y" * 2048}),
                          timeout=2.0)
            b.setblocking(False)
            deadline = time.monotonic() + 10.0
            received = 0
            while time.monotonic() < deadline and (
                    conn.wthrottle.count > 0 or received < 20 * 2048):
                try:
                    received += len(b.recv(65536))
                except BlockingIOError:
                    time.sleep(0.01)
            assert conn.wthrottle.count == 0, "budget not fully released"
            assert received >= 20 * 2048
        finally:
            r.stop()
            a.close(), b.close()


# -- shed ladder -------------------------------------------------------------

class TestShedPolicy:
    def test_background_sheds_before_client(self):
        p = ShedPolicy(100)
        # at depth 60: scrub (threshold 50) sheds, client (100) admits
        assert p.should_shed(BG_SCRUB, 60)
        assert not p.should_shed(CLIENT_OP, 60)
        assert p.should_shed(CLIENT_OP, 100)
        snap = p.snapshot()
        assert snap["shed"][BG_SCRUB] == 1
        assert snap["shed"][CLIENT_OP] == 1 and snap["admitted"] == 1

    def test_depth_counts_logical_ops(self):
        """A mux batch sheds/admits as a unit but is COUNTED per op —
        shed_rate means the same thing batched and unbatched."""
        p = ShedPolicy(10)
        assert not p.should_shed(CLIENT_OP, 0, n=7)
        assert p.should_shed(CLIENT_OP, 10, n=3)
        assert p.snapshot()["admitted"] == 7
        assert p.shed_total == 3
        assert p.shed_rate() == pytest.approx(0.3)

    def test_ladder_ordering_matches_qos(self):
        p = ShedPolicy(1000)
        ths = {c: p.threshold(c) for c in DEFAULT_SHED_FRACTIONS}
        ordered = sorted(ths, key=ths.get)
        assert ordered[0] == BG_SCRUB and ordered[-1] == CLIENT_OP


# -- sharded front end -------------------------------------------------------

class _StubEngine:
    """depths()/submit shapes of ServingEngine, queue depth scripted."""

    def __init__(self, depth=0):
        self._depth = depth
        self.encodes = []

    def depths(self):
        return {"_total": self._depth}

    def submit_encode(self, buf, op_class, **kw):
        self.encodes.append((bytes(buf), op_class))
        return f"fut-{len(self.encodes)}"

    def submit_decode(self, chunks, op_class, **kw):
        return "dfut"

    def pressure(self):
        return self._depth / 100.0

    def start(self):
        return self

    def stop(self):
        pass

    def flush(self, timeout=None):
        pass


class TestShardedFrontend:
    def test_routing_is_stable_and_respects_locate(self):
        fe = ShardedFrontend({0: _StubEngine(), 1: _StubEngine(),
                              2: _StubEngine()})
        assert fe.shard_for("obj-a") == fe.shard_for("obj-a")
        assert {fe.shard_for(f"o{i}") for i in range(64)} == {0, 1, 2}
        placed = ShardedFrontend({0: _StubEngine(), 1: _StubEngine()},
                                 locate=lambda name: 1)
        assert placed.shard_for("anything") == 1

    def test_striped_encode_fans_pieces_across_shards(self):
        shards = {i: _StubEngine() for i in range(4)}
        fe = ShardedFrontend(shards)
        data = _data(300_000, 5)
        out = fe.submit_striped_encode("soid", data, stripe_unit=65536,
                                       stripe_count=4)
        assert len(out) >= 2                  # the object really striped
        assert len({sid for _, sid, _ in out}) >= 2
        total = sum(len(buf) for eng in shards.values()
                    for buf, _ in eng.encodes)
        assert total == len(data)             # every byte routed, once

    def test_striped_pieces_carry_the_right_bytes(self):
        """One shard so submit order == route order: each piece buffer's
        extents hold exactly the logical bytes the striper maps there."""
        eng = _StubEngine()
        fe = ShardedFrontend({0: eng})
        data = _data(300_000, 6)
        out = fe.submit_striped_encode("soid", data, stripe_unit=65536,
                                       stripe_count=4)
        routes = fe.stripe_routes("soid", len(data), stripe_unit=65536,
                                  stripe_count=4)
        assert [p for p, _, _ in routes] == [p for p, _, _ in out]
        for (pname, _sid, extents), (buf, _cls) in zip(routes,
                                                       eng.encodes):
            for p_off, l_off, n in extents:
                assert buf[p_off:p_off + n] == data[l_off:l_off + n], \
                    pname

    def test_shed_ladder_refuses_background_first(self):
        eng = _StubEngine(depth=60)
        fe = ShardedFrontend({0: eng}, queue_limit=100)
        with pytest.raises(FrontendBusy) as ei:
            fe.submit_encode("o", b"x", op_class=BG_SCRUB)
        assert ei.value.errno == EBUSY and ei.value.op_class == BG_SCRUB
        sid, fut = fe.submit_encode("o", b"x", op_class=CLIENT_OP)
        assert fut == "fut-1"
        eng._depth = 100
        with pytest.raises(FrontendBusy):
            fe.submit_encode("o", b"x", op_class=CLIENT_OP)
        stats = fe.stats()
        assert stats["routed"][0] == 1
        assert stats["shed"][0]["shed_total"] == 2

    def test_pressures_surface_engine_occupancy(self):
        fe = ShardedFrontend({0: _StubEngine(depth=50),
                              1: _StubEngine(depth=0)})
        p = fe.pressures()
        assert p[0] == pytest.approx(0.5) and p[1] == 0.0


# -- the full async stack ----------------------------------------------------

@pytest.fixture
def served(tmp_path):
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.net import ClusterServer
    c = MiniCluster(n_osds=3, osds_per_host=3, chunk_size=512,
                    data_dir=tmp_path)
    server = ClusterServer(c)
    server.start()
    yield server, tmp_path / "client.admin.keyring"
    server.stop()
    c.shutdown()


class TestMuxStack:
    def test_many_sessions_bounded_threads(self, served):
        """500 logical sessions over 2 sockets: every call lands, and
        the thread census stays FIXED — reactor threads + the dispatch
        pool + one mux sender, no per-connection or per-client spawns
        (satellite 1: the net.py thread leak is structurally gone)."""
        server, keyring = served
        before = threading.active_count()
        mux = MuxClient("127.0.0.1", server.port, keyring, n_conns=2)
        try:
            mux.connect()
            s0 = mux.session()
            s0.call("mkpool", {"name": "p", "replicated": True,
                               "size": 3})
            sessions = [mux.session() for _ in range(500)]
            calls = [s.call_async("put", {"pool": "p",
                                          "oid": f"o{i % 32}",
                                          "data": _data(256, i)})
                     for i, s in enumerate(sessions)]
            for c in calls:
                c.event.wait(30.0)
                assert c.done and c.value() == 256
            # thread count is independent of session count: allow only
            # the fixed transport threads over the baseline
            grown = threading.active_count() - before
            assert grown <= 6, \
                f"thread census grew by {grown} for 500 sessions"
            st = mux.stats()
            assert st["sessions"] == 501
            assert st["connections"] <= 2
            assert st["batches_sent"] < st["calls_sent"]  # mux coalesced
        finally:
            mux.close()

    def test_reqid_dedup_is_per_session(self, served):
        """(session, rid) is the dedup key: the same rid in two sessions
        executes twice; a resent (session, rid) executes once and both
        replies carry the first execution's result."""
        import ceph_tpu.net as net
        from ceph_tpu.msg.proto import RpcBatch
        from ceph_tpu.msg.reactor import client_reactor
        server, keyring = served
        hits = []
        server._rpc_bump = lambda ch, tag: hits.append(tag) or len(hits)
        import pickle
        with open(keyring, "rb") as f:
            key = pickle.load(f)["key"]
        sock, skey = net.dial_and_handshake("127.0.0.1", server.port, key)
        got = []
        ev = threading.Event()

        def on_msg(conn, msg):
            got.extend(msg.results)
            if len(got) >= 3:
                ev.set()
        conn = AsyncConnection(sock, client_reactor(), secret=skey,
                               name="dedup", on_message=on_msg)
        try:
            conn.send(RpcBatch([
                net.RpcCall(7, "bump", {"tag": "a"}, session="S1"),
                net.RpcCall(7, "bump", {"tag": "b"}, session="S2"),
                net.RpcCall(7, "bump", {"tag": "a"}, session="S1"),
            ]))
            assert ev.wait(20.0)
            assert hits == ["a", "b"]         # dup never re-executed
            assert server.rpc_dedup_hits >= 1
            by_order = [r.value for r in got]
            assert by_order[0] == by_order[2]  # cached first result
            assert all(r.ok for r in got)
        finally:
            conn.close()

    def test_shed_by_class_under_tiny_queue(self, served):
        """Dispatch queue clamped to 1 with every worker HELD on a gated
        rpc: background traffic bounces with EBUSY while the server
        stays up and client ops still complete.  (Holding the workers
        makes the shed deterministic — on an idle host a fast drain can
        otherwise serve the whole flood without ever filling a queue of
        one.)"""
        server, keyring = served
        server._transport.shed = ShedPolicy(1)
        server._transport.dispatcher.shed = server._transport.shed
        gate = threading.Event()
        running = threading.Semaphore(0)

        def _rpc_block(ch):
            running.release()
            gate.wait(30.0)
            return "unblocked"

        server._rpc_block = _rpc_block
        mux = MuxClient("127.0.0.1", server.port, keyring, n_conns=1)
        try:
            s = mux.session()
            s.call("mkpool", {"name": "p", "replicated": True, "size": 3})
            # ONE parked blocker stalls the whole pool: rpc dispatch
            # serializes handlers on the cluster lock, so the other
            # workers pop an op each and wait on the lock, and the flood
            # piles into the depth-1 queue
            blocker = mux.session().call_async("block", {}, timeout=30.0)
            assert running.acquire(timeout=10.0)
            outcomes = {"ok": 0, "shed": 0}
            calls = [s.call_async("ping", {"payload": i},
                                  op_class=BG_SCRUB, timeout=10.0)
                     for i in range(200)]
            gate.set()
            for c in calls:
                c.event.wait(30.0)
                try:
                    c.value()
                    outcomes["ok"] += 1
                except IOError as e:
                    assert e.errno == EBUSY
                    outcomes["shed"] += 1
            blocker.event.wait(30.0)
            assert blocker.value() == "unblocked"
            assert outcomes["shed"] > 0, "tiny queue never shed"
            assert mux.stats()["sheds_seen"] == outcomes["shed"]
            snap = server._transport.shed.snapshot()
            assert snap["shed"].get(BG_SCRUB, 0) == outcomes["shed"]
            # the link survived shedding: a client op still round-trips
            assert s.call("ping", {"payload": "after"}) == "after"
        finally:
            mux.close()

    def test_wire_accounting_partition_invariant(self, served):
        """Satellite 6: on the async transport every tx/rx byte lands in
        exactly one dmClock class — sum(class_bytes) == tx+rx totals —
        including the new RpcBatch/RpcResultBatch frames."""
        server, keyring = served
        mux = MuxClient("127.0.0.1", server.port, keyring, n_conns=2)
        try:
            s = mux.session()
            s.call("mkpool", {"name": "p", "replicated": True, "size": 3})
            calls = [s.call_async("put", {"pool": "p", "oid": f"w{i}",
                                          "data": _data(2048, i)})
                     for i in range(32)]
            for c in calls:
                c.event.wait(30.0)
                assert c.done and c.value() == 2048
            totals = server.wire.totals()
            cls = server.wire.class_bytes()
            assert totals["tx_bytes"] > 0 and totals["rx_bytes"] > 0
            assert sum(cls.values()) == \
                totals["tx_bytes"] + totals["rx_bytes"]
            per = server.wire.per_type()
            assert per.get("RpcBatch", {}).get("rx_msgs", 0) > 0, \
                "mux batches never reached the server's accountant"
        finally:
            mux.close()

    def test_batch_frame_stamps_each_riding_call(self, served):
        """A batch frame leaves, for EACH traced call riding it, one
        queue wait, one lock wait and one reply stamp (the coalesced
        reply is ``mux.batch_reply``), all on the tracer's one clock:
        inside the interval the test itself measured."""
        from ceph_tpu.common.tracer import default_tracer
        server, keyring = served
        tr = default_tracer()
        mux = MuxClient("127.0.0.1", server.port, keyring, n_conns=1)
        try:
            mux.connect()
            s = mux.session()
            s.call("mkpool", {"name": "p", "replicated": True, "size": 3})
            ctxs = [tr.new_trace("client") for _ in range(8)]
            t0 = time.perf_counter()
            with server.lock:            # the calls pile up into batches
                calls = [s.call_async("ping", {"payload": b"x"}, trace=c)
                         for c in ctxs]
                time.sleep(0.1)
            for c in calls:
                c.event.wait(30.0)
                assert c.done
            time.sleep(0.05)             # the reactor's last drain
            t1 = time.perf_counter()
            st = mux.stats()
            assert st["batches_sent"] < st["calls_sent"]
        finally:
            mux.close()
        evs = [e for e in tr.dump(stitched=False)["traceEvents"]
               if e.get("ph") == "X"]
        lo, hi = (t0 - tr._t0) * 1e6, (t1 - tr._t0) * 1e6
        batched = 0
        for ctx in ctxs:
            mine = [e for e in evs
                    if e.get("args", {}).get("trace_id") == ctx.trace_id]
            names = [e["name"] for e in mine]
            for name in ("msgr.frame_rx", "msgr.dispatch_queue_wait",
                         "rpc.lock_wait", "rpc.ping", "msgr.reply_drain"):
                assert names.count(name) == 1, (name, names)
            # the reply's send: alone, or riding the batch's
            assert names.count("msgr.reply_send") + \
                names.count("mux.batch_reply") == 1, names
            batched += names.count("mux.batch_reply")
            for e in mine:
                assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi, e
        assert batched >= 2

    def test_tcprados_interops_with_async_server(self, served):
        """The classic one-session client and the mux client share one
        server: same pools, same data, same watch/notify plumbing."""
        from ceph_tpu.net import TcpRados
        server, keyring = served
        r = TcpRados("127.0.0.1", server.port, keyring)
        mux = MuxClient("127.0.0.1", server.port, keyring)
        try:
            r.mkpool("p", replicated=True, size=3)
            r.put("p", "shared", b"from-tcprados")
            s = mux.session()
            assert s.call("get", {"pool": "p", "oid": "shared"}) == \
                b"from-tcprados"
            s.call("put", {"pool": "p", "oid": "back",
                           "data": b"from-mux"})
            assert r.get("p", "back") == b"from-mux"
        finally:
            mux.close()
            r.close()
