"""Fault injection + self-healing (ISSUE 9).

The TCP ports of the bus fault scenarios (resets / black-holes /
truncation under load, healed by reconnect + resend + reqid dedup), the
circuit breaker's open/half-open/close machine, backoff jitter bounds,
mark-down flap damping, store-plane faults, injector determinism, and a
short seeded chaos soak driving ``tools/chaos_run.py`` end to end twice
to pin the same-seed event-digest guarantee.
"""
import threading
import time

import numpy as np
import pytest

from ceph_tpu.common import Context
from ceph_tpu.failure import (CLOSED, HALF_OPEN, OPEN, CircuitBreaker,
                              DeviceFaults, ExponentialBackoff,
                              FaultConfig, FaultInjector, FaultPlan,
                              FaultyStore, MarkDownLimiter,
                              RetriesExhausted, StoreFaults,
                              TransportFaults, live_breakers)


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


# -- backoff: full-jitter bounds + bounded budgets ---------------------------

class TestBackoff:
    def test_jitter_bounds(self):
        """Every draw for attempt n lies in [0, min(cap, base * 2^n)] —
        the full-jitter envelope."""
        import random
        bo = ExponentialBackoff(base=0.05, cap=2.0, max_attempts=10,
                                rng=random.Random(42))
        for attempt in range(10):
            ceiling = min(2.0, 0.05 * 2 ** attempt)
            for _ in range(200):
                d = bo.delay(attempt)
                assert 0.0 <= d <= ceiling, (attempt, d, ceiling)

    def test_attempt_budget_is_bounded(self):
        slept = []
        bo = ExponentialBackoff(base=0.01, cap=0.02, max_attempts=5,
                                sleep=slept.append)
        attempts = [a for a, _ in bo.delays()]
        assert attempts == [0, 1, 2, 3, 4]
        assert len(slept) == 4          # no sleep before the first try

    def test_run_raises_retries_exhausted(self):
        calls = []
        bo = ExponentialBackoff(base=0.0, cap=0.0, max_attempts=3)

        def always_fails():
            calls.append(1)
            raise ConnectionError("nope")
        with pytest.raises(RetriesExhausted):
            bo.run(always_fails)
        assert len(calls) == 3

    def test_deadline_cuts_schedule_short(self):
        t = {"now": 0.0}

        def clock():
            return t["now"]

        def sleep(d):
            t["now"] += d
        bo = ExponentialBackoff(base=1.0, cap=1.0, max_attempts=50,
                                deadline=2.5, clock=clock, sleep=sleep)
        attempts = [a for a, _ in bo.delays()]
        assert 1 <= len(attempts) < 50


# -- circuit breaker ---------------------------------------------------------

class TestCircuitBreaker:
    def _clocked(self, **kw):
        t = {"now": 0.0}
        b = CircuitBreaker("t.breaker", clock=lambda: t["now"], **kw)
        return b, t

    def test_opens_after_threshold_consecutive_failures(self):
        b, _ = self._clocked(threshold=3, cooldown=10.0)
        b.record_failure()
        b.record_failure()
        assert b.state == CLOSED
        b.record_failure()
        assert b.state == OPEN and b.opens == 1
        assert not b.allow()

    def test_success_resets_consecutive_count(self):
        b, _ = self._clocked(threshold=2, cooldown=10.0)
        b.record_failure()
        b.record_success()
        b.record_failure()
        assert b.state == CLOSED     # never two IN A ROW

    def test_half_open_probe_and_reclose(self):
        b, t = self._clocked(threshold=1, cooldown=5.0)
        b.record_failure()
        assert b.state == OPEN and not b.allow()
        t["now"] = 5.1
        assert b.allow()             # THE probe slot
        assert b.state == HALF_OPEN
        assert not b.allow()         # only one probe at a time
        b.record_success()
        assert b.state == CLOSED and b.allow()

    def test_half_open_probe_failure_reopens(self):
        b, t = self._clocked(threshold=1, cooldown=5.0)
        b.record_failure()
        t["now"] = 5.1
        assert b.allow()
        b.record_failure()
        assert b.state == OPEN and b.opens == 2
        t["now"] = 6.0               # cooldown restarts from the re-open
        assert not b.allow()
        t["now"] = 10.2
        assert b.allow()

    def test_live_registry_and_close(self):
        b, _ = self._clocked(threshold=1, cooldown=1.0)
        assert b in live_breakers()
        b.close()
        assert b not in live_breakers()

    def test_transition_hook_fires(self):
        seen = []
        t = {"now": 0.0}
        b = CircuitBreaker("hooked", threshold=1, cooldown=1.0,
                           clock=lambda: t["now"],
                           on_transition=lambda br, old, new:
                           seen.append((old, new)))
        b.record_failure()
        t["now"] = 1.1
        b.allow()
        b.record_success()
        assert seen == [(CLOSED, OPEN), (OPEN, HALF_OPEN),
                        (HALF_OPEN, CLOSED)]


# -- mark-down limiter (flap damping) ---------------------------------------

class TestMarkDownLimiter:
    def test_damps_after_count_within_window(self):
        lim = MarkDownLimiter(count=3, window=100.0)
        assert not lim.record_down(4, 10.0)
        assert not lim.record_down(4, 20.0)
        assert lim.record_down(4, 30.0)      # tripped
        assert not lim.allow_up(4)
        assert lim.allow_up(5)               # others unaffected

    def test_old_marks_age_out(self):
        lim = MarkDownLimiter(count=3, window=50.0)
        lim.record_down(1, 0.0)
        lim.record_down(1, 10.0)
        assert not lim.record_down(1, 90.0)  # first two aged out
        assert lim.allow_up(1)

    def test_clear_restores_boots(self):
        lim = MarkDownLimiter(count=2, window=100.0)
        lim.record_down(7, 1.0)
        lim.record_down(7, 2.0)
        assert not lim.allow_up(7)
        assert lim.clear(7)
        assert lim.allow_up(7)
        assert lim.dump().get(7) is None


# -- injector: determinism + event log ---------------------------------------

class TestFaultInjector:
    def test_same_seed_same_decisions_and_digest(self):
        plan = FaultPlan(seed=11, transport=TransportFaults(
            reset_prob=0.3, blackhole_prob=0.2))
        runs = []
        for _ in range(2):
            inj = FaultInjector(FaultPlan(**vars(plan)))
            decisions = [(inj.roll("transport", "reset", 0.3, target="x"),
                          inj.roll("transport", "blackhole", 0.2,
                                   target="y"))
                         for _ in range(200)]
            runs.append((decisions, inj.event_digest()))
        assert runs[0] == runs[1]
        assert any(a or b for a, b in runs[0][0])

    def test_streams_independent_per_kind(self):
        """Enabling a second fault kind must not shift the first kind's
        decision stream — the property that keeps soak repros stable."""
        a = FaultInjector(FaultPlan(seed=5))
        only = [a.roll("transport", "reset", 0.5) for _ in range(100)]
        b = FaultInjector(FaultPlan(seed=5))
        mixed = []
        for _ in range(100):
            mixed.append(b.roll("transport", "reset", 0.5))
            b.roll("store", "eio_read", 0.5)
        assert only == mixed

    def test_zero_prob_consumes_nothing(self):
        a = FaultInjector(FaultPlan(seed=9))
        for _ in range(50):
            a.roll("device", "oom", 0.0)
        first_live = a.roll("device", "oom", 1.0)
        b = FaultInjector(FaultPlan(seed=9))
        assert first_live == b.roll("device", "oom", 1.0)

    def test_events_counted_in_perf_collection(self):
        cct = Context()
        inj = FaultInjector(FaultPlan(seed=1), cct=cct, name="t1")
        try:
            inj.roll("store", "eio_read", 1.0, target="osd.0")
            snap = cct.perf.snapshot()["faults.t1"]
            assert snap.get("injected") == 1
            assert snap.get("store_events") == 1
        finally:
            inj.close()

    def test_bus_plane_unified_under_plan_seed(self):
        """MessageBus.inject_faults accepts a whole FaultPlan; its bus
        events land in the injector's log."""
        from ceph_tpu.backend import MessageBus
        plan = FaultPlan(seed=3, bus=FaultConfig(drop_prob=1.0))
        inj = FaultInjector(plan)
        bus = MessageBus()
        bus.register(0, type("S", (), {"handle_message":
                                       lambda self, m: None})())
        bus.inject_faults(plan)
        bus.fault_log = inj.record
        for i in range(5):
            bus.send(0, ("m", i))
        assert bus.dropped == 5
        assert inj.summary()["planes"]["bus"]["drop"] == 5


# -- store plane -------------------------------------------------------------

class TestStoreFaults:
    def _store(self, **faults):
        from ceph_tpu.backend.memstore import MemStore
        inj = FaultInjector(FaultPlan(seed=2,
                                      store=StoreFaults(**faults)))
        return FaultyStore(MemStore(), inj, target="osd.0"), inj

    def test_injected_eio_on_read(self):
        from ceph_tpu.backend.memstore import GObject, Transaction
        st, _ = self._store(eio_read_prob=1.0)
        obj = GObject("o", 0)
        st.queue_transaction(Transaction().write(obj, 0, b"abc"))
        with pytest.raises(IOError) as ei:
            st.read(obj)
        import errno
        assert ei.value.errno == errno.EIO

    def test_injected_eio_on_write_applies_nothing(self):
        from ceph_tpu.backend.memstore import GObject, Transaction
        st, _ = self._store(eio_write_prob=1.0)
        obj = GObject("o", 0)
        with pytest.raises(IOError):
            st.queue_transaction(Transaction().write(obj, 0, b"abc"))
        assert not st.exists(obj)

    def test_torn_write_applies_strict_prefix(self):
        from ceph_tpu.backend.memstore import GObject, Transaction
        st, inj = self._store(torn_write_prob=1.0)
        a, b = GObject("a", 0), GObject("b", 0)
        t = Transaction().write(a, 0, b"AA").write(b, 0, b"BB")
        with pytest.raises(IOError, match="torn"):
            st.queue_transaction(t)
        assert st.exists(a) and not st.exists(b)
        assert inj.summary()["planes"]["store"]["torn_write"] == 1

    def test_slow_read_stalls_then_returns(self):
        from ceph_tpu.backend.memstore import GObject, Transaction
        st, _ = self._store(slow_read_prob=1.0, slow_read_ms=10.0)
        obj = GObject("o", 0)
        st.queue_transaction(Transaction().write(obj, 0, b"xyz"))
        t0 = time.monotonic()
        assert st.read(obj) == b"xyz"
        assert time.monotonic() - t0 >= 0.009

    def test_delegation_and_unwrap(self):
        from ceph_tpu.failure import unwrap
        st, _ = self._store()
        assert st.list_objects() == []
        assert unwrap(st) is st._store


# -- TCP transport: the bus fault scenarios ported to real sockets -----------

def _served_cluster(tmp_path, plan, **overrides):
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.net import ClusterServer
    cct = Context(overrides={
        "ms_rpc_timeout": 4.0, "ms_rpc_retry_attempts": 4,
        "ms_reconnect_backoff_base": 0.01,
        "ms_reconnect_backoff_cap": 0.05, **overrides})
    c = MiniCluster(n_osds=6, osds_per_host=2, chunk_size=512,
                    cct=cct, data_dir=tmp_path)
    inj = c.inject_faults(plan)
    server = ClusterServer(c)
    server.inject_faults(inj)
    server.start()
    return c, server, inj, cct


class TestTcpTransportFaults:
    PROFILE = {"k": "2", "m": "1", "device": "numpy",
               "technique": "reed_sol_van"}

    def _client(self, server, tmp_path, cct):
        from ceph_tpu.net import TcpRados
        return TcpRados("127.0.0.1", server.port,
                        tmp_path / "client.admin.keyring", cct=cct)

    def test_resets_under_load_zero_acked_loss(self, tmp_path):
        """Connection resets on sends AND receipts: every acked write
        reads back (reconnect + resend + reqid dedup — the drop_prob
        data loss of the bus, healed on the TCP path)."""
        plan = FaultPlan(seed=5, transport=TransportFaults(
            reset_prob=0.15))
        c, server, inj, cct = _served_cluster(tmp_path, plan,
                                              ms_rpc_retry_attempts=8,
                                              ms_rpc_timeout=8.0)
        try:
            r = self._client(server, tmp_path, cct)
            r.mkpool("p", profile=dict(self.PROFILE))
            model = {}
            for i in range(25):
                data = _data(2048, seed=i)
                r.put("p", f"o{i % 8}", data)
                model[f"o{i % 8}"] = data
            for oid, want in sorted(model.items()):
                assert r.get("p", oid) == want, oid
            kinds = inj.summary()["planes"].get("transport", {})
            assert kinds.get("reset", 0) + kinds.get("recv_reset", 0) > 0
            assert r.reconnects > 0
            r.close()
        finally:
            server.stop()
            c.shutdown()

    def test_blackholed_requests_resend_and_dedup(self, tmp_path):
        """A swallowed request (no reply, connection alive) heals via
        the per-RPC deadline -> resend -> server-side reqid dedup: no
        double apply, no lost ack."""
        plan = FaultPlan(seed=9, transport=TransportFaults(
            blackhole_prob=0.12))
        c, server, inj, cct = _served_cluster(tmp_path, plan,
                                              ms_rpc_timeout=2.0)
        try:
            r = self._client(server, tmp_path, cct)
            r.mkpool("p", profile=dict(self.PROFILE))
            model = {}
            for i in range(15):
                data = _data(1536, seed=100 + i)
                r.put("p", f"b{i % 5}", data)
                model[f"b{i % 5}"] = data
            for oid, want in sorted(model.items()):
                assert r.get("p", oid) == want, oid
            assert inj.summary()["planes"][
                "transport"].get("blackhole", 0) > 0
            assert r.resends > 0
            r.close()
        finally:
            server.stop()
            c.shutdown()

    def test_truncated_frames_under_load(self, tmp_path):
        """Partial frames on the wire (mid-frame RST): the client's
        parser dies, reconnect + resend recover every op."""
        plan = FaultPlan(seed=4, transport=TransportFaults(
            truncate_prob=0.10, delay_prob=0.2, delay_ms=1.0))
        c, server, inj, cct = _served_cluster(tmp_path, plan)
        try:
            r = self._client(server, tmp_path, cct)
            r.mkpool("p", profile=dict(self.PROFILE))
            model = {}
            for i in range(20):
                data = _data(1024, seed=200 + i)
                r.put("p", f"t{i % 6}", data)
                model[f"t{i % 6}"] = data
            for oid, want in sorted(model.items()):
                assert r.get("p", oid) == want, oid
            assert inj.summary()["planes"][
                "transport"].get("truncate", 0) > 0
            r.close()
        finally:
            server.stop()
            c.shutdown()

    def test_ms_inject_socket_failures_option_auto_arms(self, tmp_path):
        """The reference's config surface: ms_inject_socket_failures=N
        arms a reset roughly every N post-auth messages with no code —
        and the self-healing client rides them out."""
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.net import ClusterServer
        cct = Context(overrides={
            "ms_inject_socket_failures": 6,
            "ms_rpc_retry_attempts": 8, "ms_rpc_timeout": 8.0,
            "ms_reconnect_backoff_base": 0.01,
            "ms_reconnect_backoff_cap": 0.05})
        c = MiniCluster(n_osds=6, osds_per_host=2, chunk_size=512,
                        cct=cct, data_dir=tmp_path)
        server = ClusterServer(c)
        server.start()
        try:
            assert server.fault_hooks is not None
            r = self._client(server, tmp_path, cct)
            r.mkpool("p", profile=dict(self.PROFILE))
            model = {}
            for i in range(20):
                data = _data(1024, seed=300 + i)
                r.put("p", f"a{i % 5}", data)
                model[f"a{i % 5}"] = data
            for oid, want in sorted(model.items()):
                assert r.get("p", oid) == want, oid
            assert server.fault_hooks.inj.summary()["total"] > 0
            r.close()
        finally:
            server.stop()
            c.shutdown()

    def test_handshake_never_faulted(self, tmp_path):
        """Even at reset_prob 1.0 a fresh client can connect and auth —
        injection arms only post-auth, so reconnects always succeed."""
        plan = FaultPlan(seed=1, transport=TransportFaults(
            reset_prob=1.0))
        c, server, inj, cct = _served_cluster(tmp_path, plan)
        try:
            r = self._client(server, tmp_path, cct)
            assert r.ch.secret is not None
            r.close()
        finally:
            server.stop()
            c.shutdown()


# -- device plane: pipeline breaker integration ------------------------------

class TestPipelineBreaker:
    K, M, CHUNK = 4, 2, 1024

    def _parts(self):
        from ceph_tpu.backend.ecutil import StripeInfo
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        ec = ErasureCodePluginRegistry.instance().factory(
            "jax_rs", "", {"plugin": "jax_rs", "k": str(self.K),
                           "m": str(self.M),
                           "technique": "reed_sol_van", "device": "jax"})
        return ec, StripeInfo(self.K, self.CHUNK)

    def test_injected_dispatch_failures_trip_breaker_and_heal(self):
        from ceph_tpu.backend import ecutil
        from ceph_tpu.mgr.health import device_degraded_check
        from ceph_tpu.ops.pipeline import CodecPipeline
        ec, sinfo = self._parts()
        cct = Context(overrides={"pipeline_breaker_threshold": 2,
                                 "pipeline_breaker_cooldown": 0.05})
        plan = FaultPlan(seed=8, device=DeviceFaults(
            dispatch_fail_prob=1.0))
        inj = FaultInjector(plan)
        pl = CodecPipeline(depth=2, name="chaos.bt", cct=cct)
        try:
            pl.inject_faults(inj)
            bufs = [_data(2 * self.K * self.CHUNK, seed=i)
                    for i in range(5)]
            futs = [ecutil.encode_many_pipelined(sinfo, ec, [b], pl)
                    for b in bufs]
            pl.flush()
            # every batch SUCCEEDS (host fallback), bitwise-identical
            for buf, fut in zip(bufs, futs):
                got = fut.result(30)[0]
                want = ecutil.encode(sinfo, ec, buf)
                assert {c: bytes(v) for c, v in got.items()} == \
                    {c: bytes(v) for c, v in want.items()}
            assert pl.breaker.state == OPEN
            assert pl.perf.get("host_fallbacks") >= 3
            # DEVICE_DEGRADED sees the open breaker...
            res = device_degraded_check()()
            assert res is not None and "degraded" in res.summary
            # ...heal the device; the half-open probe re-closes
            plan.device.dispatch_fail_prob = 0.0
            time.sleep(0.06)
            probe = ecutil.encode_many_pipelined(sinfo, ec, [bufs[0]],
                                                 pl)
            pl.flush()
            probe.result(30)
            assert pl.breaker.state == CLOSED
        finally:
            pl.close()
        assert device_degraded_check()() is None   # closed + unregistered

    def test_completion_failure_heals_via_fallback(self):
        from ceph_tpu.backend import ecutil
        from ceph_tpu.ops.pipeline import CodecPipeline
        ec, sinfo = self._parts()
        cct = Context(overrides={"pipeline_breaker_threshold": 3})
        plan = FaultPlan(seed=6, device=DeviceFaults(
            completion_fail_prob=1.0))
        inj = FaultInjector(plan)
        pl = CodecPipeline(depth=4, name="chaos.ct", cct=cct)
        try:
            pl.inject_faults(inj)
            buf = _data(2 * self.K * self.CHUNK, seed=3)
            fut = ecutil.encode_many_pipelined(sinfo, ec, [buf], pl)
            pl.flush()
            got = fut.result(30)[0]
            want = ecutil.encode(sinfo, ec, buf)
            assert {c: bytes(v) for c, v in got.items()} == \
                {c: bytes(v) for c, v in want.items()}
            assert fut.fallback
        finally:
            pl.close()

    def test_breaker_rejoins_live_registry_on_engine_restart(self):
        """stop() closes the pipeline (breaker leaves the registry);
        start() must bring it BACK, or DEVICE_DEGRADED goes blind after
        any engine restart."""
        from ceph_tpu.exec.engine import ServingEngine
        ec, sinfo = self._parts()
        eng = ServingEngine(ec_impl=ec, sinfo=sinfo, name="restart.brk")
        try:
            b = eng.pipeline.breaker
            assert b is not None and b in live_breakers()
            eng.stop()
            assert b not in live_breakers()
            eng.start()
            assert b in live_breakers()
        finally:
            eng.stop()

    def test_rados_shutdown_releases_objecter(self):
        from ceph_tpu.client.rados import Rados
        from ceph_tpu.cluster import MiniCluster
        c = MiniCluster(n_osds=6, osds_per_host=2, chunk_size=512,
                        cct=Context())
        try:
            with Rados(c) as r:
                name = r.objecter.perf.name
                assert name in c.cct.perf.snapshot()
            assert name not in c.cct.perf.snapshot()
        finally:
            c.shutdown()

    def test_injected_oom_without_fallback_surfaces(self):
        from ceph_tpu.failure import InjectedOOM
        from ceph_tpu.ops.pipeline import CodecPipeline
        cct = Context(overrides={"pipeline_breaker_threshold": 0})
        plan = FaultPlan(seed=2, device=DeviceFaults(oom_prob=1.0))
        pl = CodecPipeline(depth=2, name="chaos.oom", cct=cct)
        try:
            pl.inject_faults(FaultInjector(plan))
            fut = pl.submit(lambda: np.zeros(8, np.uint8),
                            lambda packed: packed, None)
            assert isinstance(fut.exception(5), InjectedOOM)
        finally:
            pl.close()


# -- mon: flap damping through heartbeats ------------------------------------

class TestFlapDamping:
    def _mon(self, **overrides):
        from ceph_tpu.crush import (CRUSH_BUCKET_STRAW2, CrushMap)
        from ceph_tpu.mon import Monitor
        from ceph_tpu.osdmap import OSDMap
        cmap = CrushMap()
        cmap.set_type_name(1, "host")
        cmap.set_type_name(2, "root")
        hosts = []
        for h0 in range(0, 9, 3):
            hb = cmap.add_bucket(CRUSH_BUCKET_STRAW2, 1,
                                 list(range(h0, h0 + 3)), [0x10000] * 3)
            cmap.set_item_name(hb, f"host{h0 // 3}")
            hosts.append(hb)
        root = cmap.add_bucket(CRUSH_BUCKET_STRAW2, 2, hosts,
                               [0x30000] * len(hosts))
        cmap.set_item_name(root, "default")
        cmap.finalize()
        m = OSDMap(crush=cmap)
        for o in range(9):
            m.create_osd(o)
        cct = Context(overrides={"osd_markdown_count": 3,
                                 "osd_markdown_window": 1000.0,
                                 **overrides})
        return Monitor(m, cct=cct)

    def _flap_once(self, mon, victim, now):
        mon.prepare_failure(victim, 3, failed_since=now - 25.0, now=now)
        mon.prepare_failure(victim, 6, failed_since=now - 25.0, now=now)
        mon.propose_pending(now)
        assert mon.osdmap.is_down(victim)

    def test_boot_refused_after_flapping_and_operator_clear(self):
        from ceph_tpu.common.clusterlog import ClusterLog
        mon = self._mon()
        mon.clog = ClusterLog(cct=mon.cct)
        victim, now = 1, 100.0
        for cycle in range(3):
            now += 30.0
            self._flap_once(mon, victim, now)
            booted = mon.osd_boot(victim, now=now + 1.0)
            mon.propose_pending(now + 1.0)
            if cycle < 2:
                assert booted and mon.osdmap.is_up(victim)
        assert not booted                 # third mark-down tripped damping
        assert mon.osdmap.is_down(victim)
        assert victim in mon.markdown.damped
        # repeated boot attempts stay refused, and log only once
        assert not mon.osd_boot(victim, now=now + 2.0)
        lines = [e["message"] for e in mon.clog.dump()
                 if "boot denied" in e["message"]]
        assert len(lines) == 1
        # operator clear -> boot allowed -> marked up, transitions logged
        assert mon.clear_markdown(victim)
        assert mon.osd_boot(victim, now=now + 3.0)
        mon.propose_pending(now + 3.0)
        assert mon.osdmap.is_up(victim)
        msgs = [e["message"] for e in mon.clog.dump()]
        assert any("marked down" in m for m in msgs)
        assert any("marked up" in m for m in msgs)
        assert any("flapping" in m for m in msgs)
        assert any("cleared by operator" in m for m in msgs)

    def test_heartbeat_reply_boots_downed_peer_with_damping(self):
        """The heartbeat hole: a post-grace reply used to re-mark the
        OSD up unconditionally.  Now the boot routes through the
        limiter: the flapping victim STAYS down."""
        from ceph_tpu.mon.heartbeat import (VirtualClock,
                                            build_heartbeat_mesh)
        mon = self._mon(osd_markdown_count=2, osd_heartbeat_grace=20)
        clock = VirtualClock()
        agents = build_heartbeat_mesh(mon, clock, 9)
        net = agents[0].network
        victim = 4

        def tick():
            clock.advance(6)
            for o, a in agents.items():
                if net.get(o) is not None:
                    a.tick()
            mon.tick(clock.now())

        def kill_until_down():
            net[victim] = None
            for _ in range(8):
                tick()
                if mon.osdmap.is_down(victim):
                    return
            raise AssertionError("victim never marked down")

        for _ in range(3):
            tick()                       # baselines
        # flap cycle 1: die -> down -> revive -> heartbeat boots it up
        kill_until_down()
        net[victim] = agents[victim]
        tick()
        tick()
        assert mon.osdmap.is_up(victim), \
            "heartbeat reply did not boot the revived peer"
        # flap cycle 2: second mark-down trips damping (count=2); the
        # revived peer keeps replying but STAYS down
        kill_until_down()
        net[victim] = agents[victim]
        for _ in range(4):
            tick()
        assert mon.osdmap.is_down(victim), \
            "flapping OSD was re-marked up without damping"
        assert victim in mon.markdown.damped
        # operator clear: the next reply boots it
        mon.clear_markdown(victim)
        tick()
        tick()
        assert mon.osdmap.is_up(victim)

    def test_osd_flapping_health_check(self):
        from ceph_tpu.mgr.health import osd_flapping_check
        mon = self._mon()
        check = osd_flapping_check(lambda: mon.markdown)
        assert check() is None
        now = 100.0
        for _ in range(3):
            now += 30.0
            self._flap_once(mon, 2, now)
            mon.osd_boot(2, now=now + 1.0)
            mon.propose_pending(now + 1.0)
        res = check()
        assert res is not None and "flapping" in res.summary
        mon.clear_markdown(2)
        assert check() is None


class TestRearmAndDisarm:
    def test_rearm_rebinds_store_plane_to_new_injector(self):
        """inject_faults(planB) while planA is armed must swap the store
        wrappers onto planB's injector (stale wrappers kept rolling the
        OLD plan) and release planA's perf collection first."""
        from ceph_tpu.cluster import MiniCluster
        cct = Context()
        c = MiniCluster(n_osds=6, osds_per_host=2, chunk_size=512,
                        cct=cct)
        try:
            pid = c.create_ec_pool(
                "p", {"k": "2", "m": "1", "device": "numpy",
                      "technique": "reed_sol_van"}, pg_num=2)
            inj_a = c.inject_faults(FaultPlan(
                seed=1, store=StoreFaults(eio_read_prob=1.0)))
            inj_b = c.inject_faults(FaultPlan(seed=2))   # store clean
            assert c.fault_injector is inj_b
            c.put(pid, "o", _data(1024))
            assert c.get(pid, "o", 1024) == _data(1024)  # no EIO rolls
            assert inj_b.summary()["planes"].get("store") is None
            assert inj_a.perf is None                    # closed
        finally:
            c.shutdown()

    def test_server_disarm_applies_to_live_connections(self, tmp_path):
        """ClusterServer.inject_faults(None) mid-run must stop send-
        plane faults on ALREADY-authenticated connections (the hooks
        are a provider, not a per-connection snapshot)."""
        plan = FaultPlan(seed=3, transport=TransportFaults(
            reset_prob=1.0))
        c, server, inj, cct = _served_cluster(tmp_path, plan,
                                              ms_rpc_retry_attempts=2,
                                              ms_rpc_timeout=2.0)
        try:
            from ceph_tpu.net import TcpRados
            r = TcpRados("127.0.0.1", server.port,
                         tmp_path / "client.admin.keyring", cct=cct)
            server.inject_faults(None)
            r.mkpool("p", profile={"k": "2", "m": "1",
                                   "device": "numpy",
                                   "technique": "reed_sol_van"})
            r.put("p", "o", _data(512))
            assert r.get("p", "o") == _data(512)
            assert r.reconnects == 0     # disarm reached the live conn
            r.close()
        finally:
            server.stop()
            c.shutdown()

    def test_quorum_clear_markdown_clears_every_replica(self):
        """Mark-downs replicate to every quorum member's limiter via
        apply_committed; the operator clear must too, or a leader
        failover resurrects the damping."""
        from ceph_tpu.crush import CRUSH_BUCKET_STRAW2, CrushMap
        from ceph_tpu.mon import MonCluster
        from ceph_tpu.osdmap import OSDMap
        cmap = CrushMap()
        cmap.set_type_name(1, "host")
        cmap.set_type_name(2, "root")
        hb = cmap.add_bucket(CRUSH_BUCKET_STRAW2, 1, [0, 1, 2],
                             [0x10000] * 3)
        cmap.set_item_name(hb, "host0")
        root = cmap.add_bucket(CRUSH_BUCKET_STRAW2, 2, [hb], [0x30000])
        cmap.set_item_name(root, "default")
        cmap.finalize()
        m = OSDMap(crush=cmap)
        for o in range(3):
            m.create_osd(o)
        mc = MonCluster(m, n_mons=3, cct=Context())
        for pm in mc.mons:          # what replicated apply_committed does
            for t in (10.0, 20.0, 30.0, 40.0, 50.0):
                pm.service.markdown.record_down(1, t)
            assert not pm.service.markdown.allow_up(1)
        assert mc.clear_markdown(1)
        for pm in mc.mons:
            assert pm.service.markdown.allow_up(1), \
                "a replica kept the damping after the operator clear"


# -- objecter op timeouts feed SLOW_OPS --------------------------------------

class TestObjecterTimeouts:
    def test_parked_op_flags_slow_and_feeds_slow_ops_check(self):
        from ceph_tpu.client.objecter import Objecter
        from ceph_tpu.cluster import MiniCluster
        cluster = MiniCluster(n_osds=6, osds_per_host=2, chunk_size=512,
                              cct=Context())
        obj = None
        try:
            pid = cluster.create_ec_pool(
                "p", {"k": "2", "m": "1", "device": "numpy",
                      "technique": "reed_sol_van"}, pg_num=4)
            obj = Objecter(cluster)
            oid = "stuck"
            g = cluster.pg_group(pid, oid)
            # drop the PG below min_size: the write PARKS (neither acked
            # nor lost) and sits in the objecter's inflight list
            for shard in g.acting[1:]:
                g.bus.mark_down(shard)
            cluster.status()                      # stats sample #1
            tid = obj.operate(pid, oid,
                              __import__("ceph_tpu.osd.osd_ops",
                                         fromlist=["ObjectOperation"])
                              .ObjectOperation().write_full(b"x" * 512),
                              drain=False)
            assert tid in obj.inflight
            flagged = obj.check_op_timeouts(
                now=time.monotonic() + 10_000.0)
            assert flagged == [tid]
            # idempotent: an op is a slow op once
            assert obj.check_op_timeouts(
                now=time.monotonic() + 20_000.0) == []
            assert obj.perf.get("slow_ops") == 1
            # ...and the cluster-level SLOW_OPS check sees the window
            # delta (the objecter collection feeds the same surface the
            # optracker does)
            cluster.status()                      # stats sample #2
            assert "SLOW_OPS" in cluster.health()["checks"]
            # revive the shards: the parked op completes and drains
            for shard in g.acting[1:]:
                g.bus.mark_up(shard)
            cluster.deliver_all()
            assert tid not in obj.inflight
        finally:
            if obj is not None:
                obj.close()
            cluster.shutdown()


# -- the seeded chaos soak (tools/chaos_run.py), twice ------------------------

class TestChaosSoak:
    def test_campaign_deterministic_and_invariants_hold(self):
        import sys
        from pathlib import Path
        tools = str(Path(__file__).resolve().parent.parent / "tools")
        sys.path.insert(0, tools)
        try:
            from chaos_run import run_campaign
        finally:
            sys.path.remove(tools)
        reports = [run_campaign(seed=13, ops=12) for _ in range(2)]
        for rep in reports:
            assert rep["ok"]
            assert rep["verified"] == rep["acked_writes"] > 0
            assert rep["breaker"]["opens"] >= 1
            assert rep["breaker"]["state"] == "closed"
            assert {"OSD_FLAPPING", "DEVICE_DEGRADED"} <= \
                set(rep["health_seen"])
            assert rep["events"]["total"] > 0
        assert reports[0]["event_digest"] == reports[1]["event_digest"], \
            "same seed produced different injected-event logs"


# -- the campaign's key streams (tools/chaos_run.py WorkloadKeys) -------------

class TestWorkloadKeys:
    @staticmethod
    def _keys(**kw):
        from tools.chaos_run import WorkloadKeys
        return WorkloadKeys(**kw)

    def _stream(self, n_ops, **kw):
        keys = self._keys(**kw)
        return [keys.key(i / n_ops) for i in range(n_ops)], keys

    def test_same_seed_same_stream_and_scale_free_coordinates(self):
        kw = dict(n_keys=64, dist="zipf", zipf_s=1.1,
                  flash=(0.5, 0.25, 0.5), hot_frac=0.1, seed=7)
        a, _ = self._stream(400, **kw)
        b, _ = self._stream(400, **kw)
        assert a == b
        c, _ = self._stream(400, **dict(kw, seed=8))
        assert a != c
        # progress is op-sequence position, not a count: with no flash
        # window the draw does not look at it, so any scale of run
        # reads the same first keys from one seed
        flat = dict(n_keys=64, dist="zipf", seed=7)
        short, _ = self._stream(50, **flat)
        long, _ = self._stream(5000, **flat)
        assert long[:50] == short

    def test_uniform_reaches_the_whole_keyspace_evenly(self):
        got, keys = self._stream(4000, n_keys=16, dist="uniform", seed=1,
                                 prefix="u")
        counts = {k: got.count(k) for k in set(got)}
        assert set(counts) == {f"u{r:08d}" for r in range(16)}
        assert min(counts.values()) > 0.6 * 4000 / 16
        assert max(counts.values()) < 1.4 * 4000 / 16
        assert keys.describe()["distinct_keys"] == 16

    def test_zipf_draws_rank_by_frequency(self):
        got, _ = self._stream(6000, n_keys=50, dist="zipf", zipf_s=1.2,
                              seed=2)
        by_rank = [got.count(f"obj{r:08d}") for r in range(50)]
        # P(r) ~ 1/r^s: the head dominates and the order of the first
        # ranks is the order of their frequencies
        assert by_rank[0] > by_rank[1] > by_rank[3] > by_rank[9] \
            > by_rank[40]
        h = sum(1.0 / r ** 1.2 for r in range(1, 51))
        assert by_rank[0] / 6000 == pytest.approx(1.0 / h, rel=0.15)

    def test_flash_window_collapses_the_stated_share_onto_the_hot_set(self):
        n_ops, frac = 8000, 0.75
        got, keys = self._stream(
            n_ops, n_keys=1000, dist="uniform", flash=(frac, 0.25, 0.5),
            hot_frac=0.01, seed=3)
        hot = {f"obj{r:08d}" for r in range(10)}         # 1% of 1000
        inside = got[n_ops // 4:3 * n_ops // 4]
        outside = got[:n_ops // 4] + got[3 * n_ops // 4:]
        share_in = sum(k in hot for k in inside) / len(inside)
        share_out = sum(k in hot for k in outside) / len(outside)
        # inside: frac + (1 - frac) x 1% of base draws; outside: 1%
        assert share_in == pytest.approx(frac + (1 - frac) * 0.01,
                                         abs=0.03)
        assert share_out < 0.03
        d = keys.describe()
        assert d["flash_draws"] == pytest.approx(frac * len(inside),
                                                 rel=0.05)
        assert d["hot_set"] == 10 and d["flash"] == [frac, 0.25, 0.5]

    def test_describe_names_what_was_drawn(self):
        got, keys = self._stream(300, n_keys=32, dist="zipf", zipf_s=0.9,
                                 seed=4)
        assert keys.describe() == {
            "dist": "zipf", "zipf_s": 0.9, "n_keys": 32, "hot_set": 1,
            "flash": None, "keys_drawn": 300, "flash_draws": 0,
            "distinct_keys": len(set(got))}
        assert self._keys(n_keys=8).describe()["zipf_s"] is None

    @pytest.mark.parametrize("kw", [
        {"dist": "pareto"}, {"flash": (1.5, 0.0, 0.5)},
        {"flash": (0.5, -0.1, 0.5)}, {"flash": (0.5, 0.0, 2.0)}])
    def test_refuses_what_it_cannot_draw(self, kw):
        with pytest.raises(ValueError):
            self._keys(**kw)

    def test_concurrent_draws_lose_no_call(self):
        import sys
        keys = self._keys(n_keys=128, dist="zipf", flash=(0.5, 0.0, 1.0),
                          hot_frac=0.05, seed=5)
        n_threads, per = 16, 2000
        drawn: list[list[str]] = [[] for _ in range(n_threads)]
        start = threading.Barrier(n_threads)

        def work(slot):
            start.wait(timeout=10)
            for i in range(per):
                drawn[slot].append(keys.key(i / per))
        prior = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work, args=(s,))
                       for s in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(prior)
        d = keys.describe()
        assert d["keys_drawn"] == n_threads * per
        assert sum(len(x) for x in drawn) == n_threads * per
        assert d["distinct_keys"] == len({k for x in drawn for k in x})
        assert 0 < d["flash_draws"] < d["keys_drawn"]
