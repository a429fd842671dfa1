"""Serving engine: admission throttles, op coalescing, futures, QoS.

Reference analogs: src/common/Throttle.{h,cc} (FIFO bounded semaphore),
src/common/Finisher.{h,cc} (ordered completion thread), the mClock op
queues — fused here with inference-style dynamic batching through
``ecutil.encode_many``/``decode_many`` (ceph_tpu/exec/).
"""
import threading
import time

import numpy as np
import pytest

from ceph_tpu.backend import StripeInfo, ecutil
from ceph_tpu.common import Context
from ceph_tpu.exec import (BatchFuture, Finisher, ServingEngine, Throttle,
                           ThrottleFull, bucket_pad_stripes)
from ceph_tpu.osd.mclock import BG_SCRUB, CLIENT_OP
from ceph_tpu.plugins.registry import ErasureCodePluginRegistry

PROFILE = {"plugin": "jax_rs", "k": "4", "m": "2", "device": "numpy",
           "technique": "reed_sol_van"}
CHUNK = 256
STRIPE = 4 * CHUNK


def codec():
    ec = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", dict(PROFILE))
    return ec, StripeInfo(4, CHUNK)


def payload(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


def counting(ec):
    calls = {"n": 0}
    orig = ec.encode_chunks

    def wrapped(want, chunks):
        calls["n"] += 1
        return orig(want, chunks)
    ec.encode_chunks = wrapped
    return calls


class TestThrottle:
    def test_get_put_counts(self):
        t = Throttle("t", 10)
        assert t.get(4) and t.count == 4
        assert t.get(6) and t.count == 10
        t.put(10)
        assert t.count == 0

    def test_get_or_fail_backpressure(self):
        t = Throttle("t", 4)
        assert t.get_or_fail(3)
        assert not t.get_or_fail(2)        # would overshoot
        assert t.get_or_fail(1)
        assert not t.get_or_fail(1)
        assert t.perf.get("get_or_fail_fail") == 2

    def test_blocking_get_waits_for_put(self):
        t = Throttle("t", 2)
        t.get(2)
        order = []

        def taker():
            t.get(1)
            order.append("took")
        th = threading.Thread(target=taker, daemon=True)
        th.start()
        time.sleep(0.05)
        assert order == [] and t.waiters() == 1      # blocked, bounded
        t.put(1)
        th.join(2)
        assert order == ["took"]

    def test_fifo_large_request_not_starved(self):
        """A queued large take must not be starved by later small ones
        (Throttle.cc queues per-waiter conds for exactly this)."""
        t = Throttle("t", 4)
        t.get(4)
        got = []

        def take(n, tag):
            t.get(n)
            got.append(tag)
        big = threading.Thread(target=take, args=(4, "big"), daemon=True)
        big.start()
        time.sleep(0.02)
        small = threading.Thread(target=take, args=(1, "small"),
                                 daemon=True)
        small.start()
        time.sleep(0.02)
        # small could sneak in without FIFO; with it, nothing moves yet
        t.put(4)                   # big (head) takes all four
        big.join(2)
        assert got == ["big"]
        t.put(4)
        small.join(2)
        assert got == ["big", "small"]

    def test_get_timeout(self):
        t = Throttle("t", 1)
        t.get(1)
        assert t.get(1, timeout=0.02) is False
        assert t.waiters() == 0            # timed-out waiter left cleanly

    def test_oversized_singleton_admitted_when_empty(self):
        t = Throttle("t", 4)
        assert t.get_or_fail(100)          # would deadlock otherwise
        assert not t.get_or_fail(1)
        t.put(100)
        assert t.get_or_fail(1)


class TestFinisher:
    def test_inline_drain_preserves_order(self):
        f = Finisher("t")
        out = []
        for i in range(5):
            f.queue(out.append, i)
        assert f.drain() == 5
        assert out == list(range(5))

    def test_threaded_stop_drains_everything(self):
        f = Finisher("t").start()
        out = []
        for i in range(100):
            f.queue(out.append, i)
        f.stop()
        assert out == list(range(100))

    def test_crashing_callback_does_not_kill_the_rest(self):
        f = Finisher("t")
        out = []
        f.queue(lambda: 1 / 0)
        f.queue(out.append, "ok")
        f.drain()
        assert out == ["ok"]


class TestCoalescing:
    def test_many_ops_one_dispatch_results_exact(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.co")
        calls = counting(ec)
        bufs = [payload(STRIPE * (1 + i % 3), seed=i) for i in range(16)]
        futs = [eng.submit_encode(b) for b in bufs]
        eng.step()
        assert calls["n"] == 1, "concurrent submissions did not coalesce"
        for b, fut in zip(bufs, futs):
            want = ecutil.encode(sinfo, ec, b)
            got = fut.result(1)
            for c in want:
                assert np.array_equal(got[c], want[c]), f"chunk {c}"

    def test_batch_max_ops_splits_batches(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.max",
                            batch_max_ops=4)
        calls = counting(ec)
        futs = [eng.submit_encode(payload(STRIPE, seed=i))
                for i in range(10)]
        eng.flush()
        assert calls["n"] == 3             # 4 + 4 + 2
        assert all(f.done() for f in futs)
        assert eng.perf.get("batches") == 3

    def test_decode_ops_coalesce_and_match(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.dec")
        bufs = [payload(STRIPE * (1 + i % 2), seed=i) for i in range(8)]
        encoded = [ecutil.encode(sinfo, ec, b) for b in bufs]
        # same survivor signature for all -> one decode dispatch
        futs = [eng.submit_decode({c: e[c] for c in (0, 2, 3, 5)})
                for e in encoded]
        eng.flush()
        for b, fut in zip(bufs, futs):
            assert fut.result(1) == b

    def test_mixed_codecs_do_not_fuse(self):
        """Ops from pools with different codecs share the QUEUE but never
        a device dispatch."""
        ec1, sinfo1 = codec()
        ec2 = ErasureCodePluginRegistry.instance().factory(
            "jax_rs", "", {**PROFILE, "k": "2", "m": "1"})
        sinfo2 = StripeInfo(2, CHUNK)
        eng = ServingEngine(name="t.mix")
        c1, c2 = counting(ec1), counting(ec2)
        f1 = eng.submit_encode(payload(STRIPE), sinfo=sinfo1, ec_impl=ec1)
        f2 = eng.submit_encode(payload(2 * CHUNK, seed=1), sinfo=sinfo2,
                               ec_impl=ec2)
        eng.step()
        assert c1["n"] == 1 and c2["n"] == 1
        assert f1.result(1) is not None and f2.result(1) is not None

    def test_unaligned_op_padded_to_stripe(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.pad")
        raw = payload(STRIPE + 100, seed=3)    # non-stripe-aligned tail
        fut = eng.submit_encode(raw)
        eng.flush()
        want = ecutil.encode(
            sinfo, ec, raw + b"\0" * (STRIPE - 100))
        got = fut.result(1)
        for c in want:
            assert np.array_equal(got[c], want[c])

    def test_size_buckets_are_powers_of_two(self):
        assert [bucket_pad_stripes(n) for n in (0, 1, 2, 3, 5, 64, 65)] \
            == [1, 1, 2, 4, 8, 64, 128]

    def test_group_error_fails_futures_not_engine(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.err")

        def boom(want, chunks):
            raise RuntimeError("device fell over")
        orig = ec.encode_chunks
        ec.encode_chunks = boom
        try:
            fut = eng.submit_encode(payload(STRIPE))
            eng.flush()
            with pytest.raises(RuntimeError, match="fell over"):
                fut.result(1)
        finally:
            ec.encode_chunks = orig
        # the engine still serves (throttles were released)
        assert eng.op_throttle.count == 0
        fut2 = eng.submit_encode(payload(STRIPE))
        eng.flush()
        assert fut2.result(1)


class TestDeadline:
    def test_partial_batch_dispatches_at_deadline(self):
        """A lone op must not wait for batch_max_ops companions forever:
        the coalescer's deadline bounds its queue time."""
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.dl",
                            batch_max_ops=64,
                            batch_max_delay_ms=10.0).start()
        try:
            fut = eng.submit_encode(payload(STRIPE))
            got = fut.result(2.0)          # << 64 ops ever arrive
            assert got is not None
            assert fut.t_dispatch - fut.t_submit < 1.0
        finally:
            eng.stop()

    def test_sync_encode_cuts_through_deadline(self):
        """A BLOCKED sync caller (engine.encode) must not sit out the
        whole batching deadline when it is alone — eager submissions
        dispatch what has arrived (regression: serial cluster writes
        through a threaded engine paid ~deadline per op)."""
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.eager",
                            batch_max_ops=64,
                            batch_max_delay_ms=500.0).start()
        try:
            t0 = time.monotonic()
            for i in range(3):
                assert eng.encode(payload(STRIPE, seed=i), timeout=5.0)
            # 3 serial ops at a 500 ms deadline would take >= 1.5 s if
            # each waited it out; eager cut-through stays far under ONE
            assert time.monotonic() - t0 < 0.5
        finally:
            eng.stop()

    def test_full_batch_does_not_wait_for_deadline(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.full",
                            batch_max_ops=4,
                            batch_max_delay_ms=10_000.0).start()
        try:
            futs = [eng.submit_encode(payload(STRIPE, seed=i))
                    for i in range(4)]
            for f in futs:
                f.result(5.0)              # deadline is 10s: batch-size
        finally:                           # trigger fired, not the clock
            eng.stop()


class TestBackpressure:
    def test_fail_fast_bounds_queue(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.ff",
                            max_ops=4, fail_fast=True)
        for i in range(4):
            eng.submit_encode(payload(STRIPE, seed=i))
        with pytest.raises(ThrottleFull):
            eng.submit_encode(payload(STRIPE))
        d = eng.depths()
        assert d["_total"] == 4            # depth stays bounded
        assert eng.perf.get("ops_rejected") == 1
        assert eng.perf.get("queue_depth") == 4
        eng.flush()
        # completions released the throttle: admission works again
        assert eng.submit_encode(payload(STRIPE)) is not None
        eng.flush()

    def test_byte_throttle_bounds_queued_bytes(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.bytes",
                            max_bytes=4 * STRIPE, fail_fast=True)
        eng.submit_encode(payload(3 * STRIPE))
        with pytest.raises(ThrottleFull):
            eng.submit_encode(payload(2 * STRIPE))
        assert eng.depths()["_bytes"] <= 4 * STRIPE
        eng.flush()

    def test_blocking_submitter_parks_until_capacity(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.blk",
                            max_ops=2, fail_fast=False)
        eng.submit_encode(payload(STRIPE, seed=0))
        eng.submit_encode(payload(STRIPE, seed=1))
        submitted = []

        def third():
            f = eng.submit_encode(payload(STRIPE, seed=2))
            submitted.append(f)
        th = threading.Thread(target=third, daemon=True)
        th.start()
        time.sleep(0.05)
        assert not submitted               # blocked at the throttle
        assert eng.depths()["_total"] == 2  # queue depth stays bounded
        eng.step()                         # completes the two -> room
        th.join(2)
        assert submitted
        eng.flush()
        assert submitted[0].result(1)


class TestQoS:
    def test_client_ops_dequeue_ahead_of_scrub(self):
        """Admission is dmClock-ordered: with a backlog of both classes,
        the first batch carries every client op while the rate-limited
        scrub class (limit 0.001/s) gets AT MOST its one under-limit op
        — background work cannot crowd clients out of a batch."""
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.qos",
                            batch_max_ops=5)
        scrub = [eng.submit_encode(payload(STRIPE, seed=i),
                                   op_class=BG_SCRUB) for i in range(4)]
        client = [eng.submit_encode(payload(STRIPE, seed=10 + i),
                                    op_class=CLIENT_OP) for i in range(4)]
        eng.step()                         # ONE batch of 5, mClock order
        assert all(f.done() for f in client)
        assert sum(f.done() for f in scrub) <= 1
        eng.flush()
        assert all(f.done() for f in scrub)


class TestFutures:
    def test_add_done_callback_after_completion_runs_inline(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.fut")
        fut = eng.submit_encode(payload(STRIPE))
        eng.flush()
        seen = []
        fut.add_done_callback(seen.append)
        assert seen == [fut]

    def test_result_timeout(self):
        ec, sinfo = codec()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="t.to")
        fut = eng.submit_encode(payload(STRIPE))
        with pytest.raises(TimeoutError):
            fut.result(0.01)               # engine never stepped
        eng.flush()
        assert fut.result(1)


class TestClusterIntegration:
    def test_serving_cluster_matches_plain_cluster(self):
        """Writes routed through the engine land bit-identical to the
        direct encode path, and reads decode through the engine too."""
        from ceph_tpu.cluster import MiniCluster
        a = MiniCluster(n_osds=12, chunk_size=CHUNK)
        b = MiniCluster(n_osds=12, chunk_size=CHUNK)
        pa = a.create_ec_pool("p", PROFILE, pg_num=4)
        pb = b.create_ec_pool("p", PROFILE, pg_num=4)
        eng = b.enable_serving()
        objs = {f"o{i}": payload(STRIPE * (1 + i % 3), seed=i)
                for i in range(8)}
        for oid, data in objs.items():
            a.put(pa, oid, data)
            b.put(pb, oid, data)
        assert eng.perf.get("ops_completed") >= len(objs)
        for oid, data in objs.items():
            assert b.get(pb, oid, len(data)) == data, oid
            ga, gb = a.pg_group(pa, oid), b.pg_group(pb, oid)
            from ceph_tpu.backend import GObject
            for chunk, (sa, sb) in enumerate(zip(ga.acting, gb.acting)):
                from ceph_tpu.backend.pg_backend import shard_store
                assert shard_store(ga.bus, sa).read(GObject(oid, sa)) == \
                    shard_store(gb.bus, sb).read(GObject(oid, sb)), \
                    f"{oid} chunk {chunk}"
            assert all(gb.backend.be_deep_scrub(oid).values()), oid
        a.shutdown()
        b.shutdown()

    def test_scrub_and_recovery_survive_serving(self):
        from ceph_tpu.backend.memstore import GObject
        from ceph_tpu.backend.pg_backend import shard_store
        from ceph_tpu.cluster import MiniCluster
        c = MiniCluster(n_osds=12, chunk_size=CHUNK)
        pid = c.create_ec_pool("p", PROFILE, pg_num=4)
        c.enable_serving()
        data = payload(STRIPE * 2, seed=7)
        c.put(pid, "victim", data)
        g = c.pg_group(pid, "victim")
        rot = g.acting[1]
        st = shard_store(g.bus, rot)
        st.objects[GObject("victim", rot)].data[0] ^= 0xFF
        report = c.scrub_pool(pid, repair=True)
        assert any("victim" in bad for bad in report.values())
        assert c.scrub_pool(pid) == {}
        assert c.get(pid, "victim", len(data)) == data
        c.shutdown()


class TestDaemonThrottle:
    def test_ms_dispatch_throttled_past_bound(self):
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.osd.osd_ops import MOSDOp, ObjectOperation
        c = MiniCluster(n_osds=9, osds_per_host=3, chunk_size=512)
        pid = c.create_ec_pool("p", {"k": "2", "m": "1",
                                     "device": "numpy"}, pg_num=4)
        c.put(pid, "obj", payload(1024))
        g = c.pg_group(pid, "obj")
        d = c.osds[g.backend.whoami]
        d.op_throttle = Throttle("osd.q", 2)
        results = []
        for i in range(3):
            m = MOSDOp(oid="obj", ops=ObjectOperation().stat().ops,
                       epoch=g.epoch)
            results.append(d.ms_dispatch(g.pgid, m, lambda r: None))
        assert results[:2] == [None, None]
        assert results[2] == ("throttled", d.epoch)
        assert d.queue_stats["throttled_rejects"] == 1
        d.drain()                          # runs + releases the throttle
        g.bus.deliver_all()
        m = MOSDOp(oid="obj", ops=ObjectOperation().stat().ops,
                   epoch=g.epoch)
        assert d.ms_dispatch(g.pgid, m, lambda r: None) is None
        d.drain()
        c.shutdown()

    def test_osd_queue_throttle_ops_option_wires_daemons(self):
        from ceph_tpu.cluster import MiniCluster
        cct = Context(overrides={"osd_queue_throttle_ops": 3})
        c = MiniCluster(n_osds=9, osds_per_host=3, chunk_size=512,
                        cct=cct)
        assert all(d.op_throttle is not None and d.op_throttle.max == 3
                   for d in c.osds.values())
        # normal I/O drains within the bound (ops release on dequeue)
        pid = c.create_ec_pool("p", {"k": "2", "m": "1",
                                     "device": "numpy"}, pg_num=4)
        data = payload(1024, seed=5)
        c.put(pid, "obj", data)
        assert c.get(pid, "obj", len(data)) == data
        c.shutdown()

    def test_cluster_drains_and_resends_on_throttled_bounce(self):
        """A throttled dispatch is a TRANSIENT: the cluster drains the
        daemon (freeing its queue slots) and resends, so a batch far
        larger than the bound still completes — no mislabeled 'stale'
        failure (regression: the bounce surfaced as a stale-map
        IOError with no retry)."""
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.osd.osd_ops import ObjectOperation
        cct = Context(overrides={"osd_queue_throttle_ops": 1})
        c = MiniCluster(n_osds=9, osds_per_host=3, chunk_size=512,
                        cct=cct)
        pid = c.create_ec_pool("p", {"k": "2", "m": "1",
                                     "device": "numpy"}, pg_num=4)
        # deliver=False queues without draining: past op #1 every
        # dispatch to the same primary hits the full queue
        for i in range(6):
            c.operate(pid, "same-obj" if i else "same-obj",
                      ObjectOperation().write_full(payload(777, seed=i)),
                      deliver=False)
        c.deliver_all()
        assert c.get(pid, "same-obj", 777) == payload(777, seed=5)
        rejects = sum(d.queue_stats["throttled_rejects"]
                      for d in c.osds.values())
        assert rejects >= 1            # the bound actually bit
        c.shutdown()


class TestServingMetrics:
    def test_prometheus_carries_serving_and_mclock_metrics(self):
        from ceph_tpu.mgr.prometheus import render
        cct = Context()
        ec, sinfo = codec()
        eng = ServingEngine(cct=cct, ec_impl=ec, sinfo=sinfo,
                            name="promtest", max_ops=16, fail_fast=True)
        for i in range(3):
            eng.submit_encode(payload(STRIPE, seed=i))
        text = render(cct)                 # scrape WHILE queued: depth > 0
        assert 'ceph_tpu_queue_depth{collection="promtest"} 3' in text
        assert 'ceph_tpu_mclock_queue_depth{owner="serving.promtest",' \
               'shard="0",op_class="client_op"} 3' in text
        eng.flush()
        text = render(cct)
        assert 'ceph_tpu_queue_depth{collection="promtest"} 0' in text
        assert 'ceph_tpu_ops_coalesced{collection="promtest"} 3' in text
        # batch-size histogram with the full _bucket/_sum/_count set
        assert 'ceph_tpu_batch_size_bucket{collection="promtest",' \
               'le="+Inf"} 1' in text
        assert 'ceph_tpu_batch_size_sum{collection="promtest"}' in text
        # throttle counters registered under their own collections
        assert 'collection="throttle.promtest.ops"' in text

    def test_e2e_latency_histogram_counts_ops(self):
        cct = Context()
        ec, sinfo = codec()
        eng = ServingEngine(cct=cct, ec_impl=ec, sinfo=sinfo,
                            name="latm")
        for i in range(5):
            eng.submit_encode(payload(STRIPE, seed=i))
        eng.flush()
        dump = eng.perf.dump()
        assert dump["op_e2e_lat"]["count"] == 5
        assert dump["queue_wait_lat"]["count"] == 5
        assert dump["e2e_time"]["avgcount"] == 5
