"""mgr/slo.py + the ISSUE-10 acceptance criteria: multi-window burn
math, SLO_BURN/SLO_EXHAUSTED raise-and-clear with clusterlog receipts,
the loaded-cluster attribution table (fractions sum to 1, a
deliberately slowed phase dominates), retry-phase attribution under
transport faults, flight-bundle capture, and tools/slo_report.py
reproducing the table from artifacts alone.
"""
import importlib.util
import json
import time
from pathlib import Path

import numpy as np
import pytest

from ceph_tpu.common import Context
from ceph_tpu.common.critpath import CritPathLedger
from ceph_tpu.common.tracer import default_tracer
from ceph_tpu.mgr.slo import (
    SLOTracker, render_status, slo_burn_check, slo_exhausted_check,
    slo_objectives,
)

ROOT = Path(__file__).resolve().parent.parent

K4M2 = {"k": "4", "m": "2", "device": "numpy",
        "technique": "reed_sol_van"}


def _tracker(ledger, clock=None, **overrides):
    overrides.setdefault("slo_client_p99_ms", 10.0)
    overrides.setdefault("slo_client_target", 0.9)
    overrides.setdefault("slo_min_ops", 4)
    overrides.setdefault("slo_fast_window", 60.0)
    overrides.setdefault("slo_slow_window", 600.0)
    cct = Context(overrides=overrides)
    kw = {"clock": clock} if clock is not None else {}
    return SLOTracker(ledger, cct=cct, name="t", **kw)


class TestObjectives:
    def test_parsed_from_config(self):
        conf = Context(overrides={"slo_client_p99_ms": 40.0,
                                  "slo_recovery_p99_ms": 500.0,
                                  "slo_recovery_target": 0.99}).conf
        obj = slo_objectives(conf)
        assert set(obj) == {"client", "recovery"}
        assert obj["client"]["p99_ms"] == 40.0
        assert obj["client"]["budget"] == pytest.approx(0.001)
        assert obj["recovery"]["budget"] == pytest.approx(0.01)

    def test_zero_means_no_objective(self):
        assert slo_objectives(Context().conf) == {}


class TestBurnMath:
    def _ingest(self, led, n_good, n_bad, t):
        for _ in range(n_good):
            led.ingest("client", 0.001, {"device": 0.001}, t=t)
        for _ in range(n_bad):
            led.ingest("client", 0.050, {"device": 0.050}, t=t)

    def test_multi_window_agreement(self):
        """A fast-window blip alone stays silent; a burn present in
        BOTH windows raises; exhaustion needs the slow window past the
        exhausted threshold."""
        led = CritPathLedger(name="bm")
        try:
            now = 1000.0
            tr = _tracker(led, clock=lambda: now,
                          slo_fast_window=10.0, slo_slow_window=100.0,
                          slo_burn_rate_threshold=2.0,
                          slo_exhausted_burn_rate=8.0)
            # old clean traffic fills the slow window; a fresh blip of
            # bad ops lands only in the fast window
            self._ingest(led, 40, 0, t=920.0)        # slow window only
            self._ingest(led, 2, 6, t=995.0)         # both windows
            st = tr.class_status("client", slo_objectives(tr.cct.conf)
                                 ["client"], now=now)
            assert st["fast"]["burn"] >= 2.0
            assert st["slow"]["burn"] < 2.0
            assert not st["burning"] and not st["exhausted"]
            # sustained burn: bad ops throughout the slow window too —
            # slow = 78 ops / 36 bad -> burn 4.6x: burning, not yet
            # exhausted (threshold 8x)
            self._ingest(led, 0, 30, t=950.0)
            st = tr.class_status("client", slo_objectives(tr.cct.conf)
                                 ["client"], now=now)
            assert st["burning"]
            assert st["budget_remaining"] < 1.0
            assert not st["exhausted"]
            # pile on until bad_frac crosses 0.8 -> burn >= 8x: gone
            self._ingest(led, 0, 200, t=940.0)
            st = tr.class_status("client", slo_objectives(tr.cct.conf)
                                 ["client"], now=now)
            assert st["exhausted"]
            assert st["budget_remaining"] == 0.0
            tr.close()
        finally:
            led.close()

    def test_min_ops_gate(self):
        led = CritPathLedger(name="mo")
        try:
            now = 100.0
            tr = _tracker(led, clock=lambda: now, slo_min_ops=8)
            self._ingest(led, 0, 4, t=99.0)          # 100% bad, 4 ops
            st = tr.status(now=now)["objectives"]["client"]
            assert st["fast"]["burn"] > 2.0
            assert not st["burning"], "below min_ops must not page"
            tr.close()
        finally:
            led.close()

    def test_health_checks_raise_and_rank(self):
        led = CritPathLedger(name="hc")
        try:
            now = 50.0
            tr = _tracker(led, clock=lambda: now,
                          slo_exhausted_burn_rate=5.0)
            self._ingest(led, 0, 16, t=49.0)         # total burn
            burn = slo_burn_check(tr)()
            exhausted = slo_exhausted_check(tr)()
            # a class past the exhausted threshold reports THERE, not
            # twice (burn_check skips exhausted classes)
            assert burn is None
            assert exhausted is not None
            assert exhausted.severity == "HEALTH_ERR"
            assert "client" in exhausted.detail[0]
            tr.close()
        finally:
            led.close()

    def test_flat_series_and_render(self):
        led = CritPathLedger(name="fs")
        try:
            tr = _tracker(led)
            led.ingest("client", 0.004,
                       {"batch_delay": 0.003, "device": 0.001})
            flat = tr.flat_series()
            assert flat["client_budget_remaining"] == 1.0
            assert flat["client_p99_ms"] == pytest.approx(4.0)
            text = render_status(tr.status())
            assert "client p99 = 4.0 ms" in text
            assert "75% batch_delay" in text
            assert "ok" in text
            tr.close()
        finally:
            led.close()


@pytest.mark.filterwarnings("ignore")
class TestClusterAcceptance:
    """The ISSUE-10 acceptance: `ceph slo status` on a loaded
    MiniCluster prints per-class attribution whose fractions sum to
    1.0 (±1%), and a deliberately slowed phase dominates."""

    def _loaded_cluster(self, **overrides):
        from ceph_tpu.cluster import MiniCluster
        default_tracer().reset()
        cct = Context(overrides=overrides)
        c = MiniCluster(n_osds=6, chunk_size=1024, cct=cct)
        pid = c.create_ec_pool("slo", dict(K4M2), pg_num=4)
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, 8192, np.uint8).tobytes()
        for i in range(10):
            c.put(pid, f"o{i}", data)
        return c, pid, data

    def test_attribution_sums_to_one_and_slowed_phase_dominates(self):
        from ceph_tpu.failure import FaultPlan, StoreFaults
        c, pid, data = self._loaded_cluster(slo_client_p99_ms=30000.0)
        try:
            c.critpath.refresh()
            # now slow EVERY store read by 5 ms: the sub-read hops are
            # where that time lands, so `wire` must come to dominate
            # the client attribution for the faulted reads
            default_tracer().reset()
            c.inject_faults(FaultPlan(
                seed=2, store=StoreFaults(slow_read_prob=1.0,
                                          slow_read_ms=5.0)))
            for i in range(10):
                assert c.get(pid, f"o{i}", len(data)) == data
            out = c.cct.admin_socket.call("slo status")
            summary = out["attribution"]["client"]
            assert sum(summary["phases"].values()) == pytest.approx(
                1.0, abs=0.01)
            dominant = max(summary["phases"],
                           key=summary["phases"].get)
            assert dominant == "wire", summary["phases"]
            assert summary["phases"]["wire"] > 0.5
            # the rendered table carries the attribution line
            text = render_status(out)
            assert "client p99 =" in text and "% wire" in text
        finally:
            c.shutdown()

    def test_batch_delay_injection_dominates_serving_class(self):
        """The other acceptance arm: a serving submission that waits
        out a fat coalescer deadline attributes to batch_delay."""
        from ceph_tpu.backend import StripeInfo
        from ceph_tpu.exec import ServingEngine
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        tr = default_tracer()
        tr.reset()
        ec = ErasureCodePluginRegistry.instance().factory(
            "jax_rs", "", {"plugin": "jax_rs", **K4M2})
        eng = ServingEngine(cct=Context(), ec_impl=ec,
                            sinfo=StripeInfo(4, 1024),
                            name="slot", batch_max_delay_ms=50.0,
                            batch_max_ops=64,
                            pipeline_depth=0).start()
        led = CritPathLedger(name="bd")
        try:
            with tr.activate(tr.new_trace("serving")):
                fut = eng.submit_encode(
                    np.zeros(4096, np.uint8))   # non-eager: pays the
            fut.result(30)                      # full deadline
            led.refresh(tr)
            s = led.class_summary("serving")
            assert s is not None, led.snapshot()
            assert sum(s["phases"].values()) == pytest.approx(1.0,
                                                              abs=0.01)
            assert s["phases"]["batch_delay"] > 0.5, s["phases"]
            # the wait really was the deadline, not noise
            assert s["p99_ms"] >= 40.0
        finally:
            led.close()
            eng.stop()

    def test_queue_phase_attributed_through_daemon_dispatch(self):
        """Ops routed through the OSD daemon queue carry osd.queue_wait
        in their trace (the `queue` phase source)."""
        from ceph_tpu.osd.osd_ops import ObjectOperation
        c, pid, data = self._loaded_cluster()
        try:
            default_tracer().reset()
            c.operate(pid, "qq", ObjectOperation().write(0, data))
            c.critpath.refresh()
            snap = c.critpath.snapshot()
            # queue wait was stamped (near-zero in the cooperative
            # model, but PRESENT as an attributed phase event)
            evs = default_tracer().dump()["traceEvents"]
            assert any(e["name"] == "osd.queue_wait" and
                       e.get("args", {}).get("trace_id")
                       for e in evs)
            assert "client" in snap["classes"]
        finally:
            c.shutdown()


class TestBurnLifecycle:
    """SLO_BURN raises on a sustained burn and CLEARS after heal, with
    the transitions in the clusterlog — the in-tree arm of the
    chaos_run campaign check (satellite 6)."""

    def test_raise_then_clear_with_clusterlog_receipts(self):
        from ceph_tpu.cluster import MiniCluster
        default_tracer().reset()
        cct = Context(overrides={
            "slo_client_p99_ms": 0.0001,       # impossible: all ops bad
            "slo_client_target": 0.9,
            "slo_fast_window": 0.2, "slo_slow_window": 0.4,
            "slo_min_ops": 4,
        })
        c = MiniCluster(n_osds=6, chunk_size=1024, cct=cct)
        try:
            pid = c.create_ec_pool("b", dict(K4M2), pg_num=4)
            data = bytes(range(256)) * 16
            for i in range(8):
                c.put(pid, f"o{i}", data)
            c.critpath.refresh()
            checks = c.health()["checks"]
            assert "SLO_BURN" in checks or "SLO_EXHAUSTED" in checks, \
                checks
            # heal: no new bad ops; the windows drain and the burn
            # clears (idle windows below min_ops never page)
            time.sleep(0.5)
            checks = c.health()["checks"]
            assert "SLO_BURN" not in checks
            assert "SLO_EXHAUSTED" not in checks
            lines = [e["message"] for e in c.clusterlog.dump()]
            assert any("SLO_" in ln and "raised" in ln for ln in lines)
            assert any("SLO_" in ln and "cleared" in ln
                       for ln in lines), lines
        finally:
            c.shutdown()


class TestRetryPhaseUnderFaults:
    def test_tcp_blackholes_attribute_retry_time(self, tmp_path):
        """Transport faults -> bounded RPC resends -> `retry` phase
        time > 0 in the client attribution (the chaos_run receipt)."""
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.failure import FaultPlan, TransportFaults
        from ceph_tpu.net import ClusterServer, TcpRados
        default_tracer().reset()
        cct = Context(overrides={
            "ms_rpc_timeout": 2.0, "ms_rpc_retry_attempts": 5,
            "ms_reconnect_backoff_base": 0.005,
            "ms_reconnect_backoff_cap": 0.02,
        })
        c = MiniCluster(n_osds=6, chunk_size=256, cct=cct,
                        data_dir=tmp_path)
        server = ClusterServer(c)
        client = None
        try:
            # seeded: this schedule yields resends on every run without
            # ever exhausting the 5-attempt budget (decision streams are
            # per-(plane, kind), so other kinds never shift it)
            inj = c.inject_faults(FaultPlan(
                seed=11, transport=TransportFaults(blackhole_prob=0.15,
                                                   reset_prob=0.1)))
            server.inject_faults(inj)
            server.start()
            client = TcpRados("127.0.0.1", server.port,
                              tmp_path / "client.admin.keyring",
                              cct=cct)
            client.mkpool("r", profile={"plugin": "jax_rs", **K4M2},
                          pg_num=4)
            payload = bytes(range(256)) * 4
            for i in range(12):
                client.put("r", f"o{i}", payload)
            assert client.resends > 0, \
                "fault schedule produced no resends; bump probabilities"
            c.critpath.refresh()
            snap = c.critpath.snapshot()
            retry_s = sum(acc.get("retry", 0.0)
                          for acc in snap["phase_seconds"].values())
            assert retry_s > 0, snap["phase_seconds"]
        finally:
            if client is not None:
                client.close()
            server.stop()
            c.shutdown()


class TestFlightAndArtifacts:
    def _slo_report(self):
        spec = importlib.util.spec_from_file_location(
            "slo_report_t", ROOT / "tools" / "slo_report.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_flight_bundle_answers_which_phase(self, tmp_path):
        """Satellite 2: a WARN/ERR flight bundle carries the SLO +
        critical-path snapshot, and slo_report renders the attribution
        from the bundle file alone."""
        from ceph_tpu.cluster import MiniCluster
        default_tracer().reset()
        cct = Context(overrides={"slo_client_p99_ms": 20.0,
                                 "slo_client_target": 0.9})
        c = MiniCluster(n_osds=6, chunk_size=1024, cct=cct,
                        data_dir=tmp_path / "d")
        try:
            c.critpath.ingest("client", 0.050,
                              {"batch_delay": 0.040, "wire": 0.010})
            bundle = c.flight.dump(reason="test")
            assert "slo" in bundle and "critpath" in bundle["slo"]
            attribution = bundle["slo"]["slo"]["attribution"]["client"]
            assert attribution["phases"]["batch_delay"] == \
                pytest.approx(0.8)
            # the standalone tool reproduces the table from the file
            mod = self._slo_report()
            with open(bundle["path"]) as f:
                report = mod.build_report(json.load(f))
            assert report["source"] == "flight"
            text = mod.render(report)
            assert "client p99 = 50.0 ms" in text
            assert "80% batch_delay" in text
        finally:
            c.shutdown()

    def test_slo_report_main_renders_burn_table(self, tmp_path):
        """slo_report's CLI reproduces the attribution AND the error-
        budget table from a flight bundle file alone."""
        bundle = {"reason": "SLO_BURN", "slo": {"slo": {
            "attribution": {"client": {
                "p99_ms": 41.0, "ops": 64,
                "phases": {"batch_delay": 0.62, "device": 0.21,
                           "wire": 0.09, "other": 0.08}}},
            "objectives": {"client": {
                "objective_p99_ms": 100.0, "budget_remaining": 0.97,
                "fast": {"burn": 0.1}, "slow": {"burn": 0.2}}}}}}
        p = tmp_path / "flight.json"
        p.write_text(json.dumps(bundle))
        mod = self._slo_report()
        assert mod.main([str(p), "--json"]) == 0
        report = mod.build_report(bundle)
        text = mod.render(report)
        assert "client p99 = 41.0 ms (64 ops): 62% batch_delay, " \
               "21% device, 9% wire" in text
        assert "97%" in text
        # an artifact of neither shape is refused, not guessed at
        p.write_text(json.dumps({"metric": "m", "value": 1.0}))
        assert mod.main([str(p)]) == 1

    def test_slo_report_from_trace_dump(self, tmp_path):
        tr = default_tracer()
        tr.reset()
        with tr.activate(tr.new_trace("client")):
            with tr.span("client.op"):
                with tr.span("codec.encode"):
                    time.sleep(0.002)
        p = tmp_path / "trace.json"
        p.write_text(json.dumps(tr.dump()))
        mod = self._slo_report()
        with open(p) as f:
            report = mod.build_report(json.load(f))
        assert report["source"] == "trace"
        assert report["classes"]["client"]["ops"] == 1
        assert report["classes"]["client"]["phases"]["device"] > 0.5
