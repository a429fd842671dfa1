"""common/critpath.py: critical-path extraction over golden stitched
traces (the ISSUE-10 tentpole's correctness core), the span->phase
registry, the bounded ledger, and the unified nearest-rank percentile
(+ its AST guard: bench p99 and trace p99 can never drift apart again).
"""
import importlib.util
import time
from pathlib import Path

import pytest

from ceph_tpu.common import critpath
from ceph_tpu.common.critpath import (
    CritPathLedger, PHASES, decompose, group_traces, phase_for,
    render_attribution,
)
from ceph_tpu.common.percentile import nearest_rank, percentile
from ceph_tpu.common.tracer import Tracer

ROOT = Path(__file__).resolve().parent.parent


def ev(name, ts_ms, dur_ms, sid, parent=0, trace=1, **extra):
    args = {"trace_id": trace, "span_id": sid,
            "parent_span_id": parent}
    args.update(extra)
    return {"name": name, "ph": "X", "ts": ts_ms * 1000.0,
            "dur": dur_ms * 1000.0, "args": args}


class TestGoldenDecomposition:
    def test_known_per_phase_durations_attribute_exactly(self):
        """The fixture trace from the issue: queue 20, batch_delay 30,
        device 40 (two OVERLAPPING codec spans — union, never sum),
        wire 8, other 2 — summing to the 100 ms root exactly."""
        spans = [
            ev("client.op", 0, 100, 1, op_class="client"),
            ev("osd.queue_wait", 0, 20, 2, 1),
            ev("serving.batch_wait", 20, 30, 3, 1),
            ev("codec.encode", 50, 30, 4, 1),
            ev("codec.decode", 70, 20, 5, 1),   # overlaps encode 10 ms
            ev("osd.ECSubWrite", 90, 8, 6, 1),
        ]
        rec = decompose(spans)
        assert rec["op_class"] == "client"
        assert rec["total_s"] == pytest.approx(0.100)
        ph = rec["phases"]
        assert ph["queue"] == pytest.approx(0.020)
        assert ph["batch_delay"] == pytest.approx(0.030)
        # device overlap must not double-count: union [50,90] = 40 ms,
        # not 30+20 (the device_attribution clamping convention)
        assert ph["device"] == pytest.approx(0.040)
        assert ph["wire"] == pytest.approx(0.008)
        assert ph["other"] == pytest.approx(0.002)
        assert sum(ph.values()) == pytest.approx(rec["total_s"])

    def test_nested_children_charge_parents_self_time_down(self):
        spans = [
            ev("osd.op", 0, 50, 1, owner="client"),
            ev("ec.encode", 10, 30, 2, 1),
            ev("codec.encode", 15, 20, 3, 2),
        ]
        rec = decompose(spans)
        ph = rec["phases"]
        assert ph["other"] == pytest.approx(0.020)     # osd.op self
        assert ph["device"] == pytest.approx(0.030)    # ec + codec
        assert sum(ph.values()) == pytest.approx(rec["total_s"])

    def test_multiple_roots_union_not_sum(self):
        """Sibling roots (queue-wait event + daemon span, resent ops)
        contribute the UNION of their intervals; overlap clamps."""
        spans = [
            ev("osd.queue_wait", 0, 20, 1),
            ev("osd.op", 15, 35, 2, owner="client"),    # 5 ms overlap
        ]
        rec = decompose(spans)
        assert rec["total_s"] == pytest.approx(0.050)
        assert rec["phases"]["queue"] == pytest.approx(0.020)
        assert rec["phases"]["other"] == pytest.approx(0.030)

    def test_child_clipped_to_parent(self):
        """A child reaching past its parent's end (late async span)
        charges only the contained part — the invariant survives."""
        spans = [
            ev("client.op", 0, 40, 1, op_class="client"),
            ev("pipeline.complete", 30, 30, 2, 1),      # runs past root
        ]
        rec = decompose(spans)
        assert rec["total_s"] == pytest.approx(0.040)
        assert rec["phases"]["device"] == pytest.approx(0.010)
        assert rec["phases"]["other"] == pytest.approx(0.030)

    def test_explicit_phase_arg_wins_over_registry(self):
        spans = [ev("client.op", 0, 10, 1, phase="retry",
                    op_class="client")]
        rec = decompose(spans)
        assert rec["phases"]["retry"] == pytest.approx(0.010)

    def test_unknown_span_lands_in_other_and_is_counted(self):
        unmapped = {}
        rec = decompose([ev("mystery.span", 0, 5, 1)], unmapped=unmapped)
        assert rec["phases"]["other"] == pytest.approx(0.005)
        assert unmapped == {"mystery.span": 1}

    def test_empty_trace_is_none(self):
        assert decompose([]) is None


class TestPhaseRegistry:
    def test_bus_msgtype_prefix_is_wire_but_daemon_spans_are_not(self):
        assert phase_for("osd.ECSubWrite") == "wire"
        assert phase_for("osd.ECSubReadReply") == "wire"
        assert phase_for("rpc.put") == "wire"
        assert phase_for("osd.op") == "other"
        assert phase_for("osd.recovery") == "other"
        assert phase_for("osd.queue_wait") == "queue"

    def test_retry_family(self):
        for name in ("net.resend", "client.op_retry",
                     "pipeline.host_fallback", "client.backoff_resend"):
            assert phase_for(name) == "retry", name

    def test_declare_extends_registry(self):
        critpath.declare("my.new_span", "device")
        try:
            assert phase_for("my.new_span") == "device"
            assert critpath.is_declared("my.new_span")
        finally:
            del critpath.SPAN_PHASES["my.new_span"]
        with pytest.raises(ValueError):
            critpath.declare("bad", "not_a_phase")

    def test_every_registry_phase_is_canonical(self):
        assert set(critpath.SPAN_PHASES.values()) <= set(PHASES)


class TestLedger:
    def test_fold_dedup_and_summary(self):
        tr = Tracer()
        led = CritPathLedger(name="t", capacity=16)
        try:
            for i in range(3):
                with tr.activate(tr.new_trace("client")):
                    with tr.span("client.op"):
                        with tr.span("codec.encode"):
                            time.sleep(0.001)
            assert led.refresh(tr) == 3
            assert led.refresh(tr) == 0            # each trace folds ONCE
            s = led.class_summary("client")
            assert s["ops"] == 3
            assert sum(s["phases"].values()) == pytest.approx(1.0,
                                                              abs=0.01)
            assert s["phases"]["device"] > 0.5
            assert led.phase_seconds()["client"]["device"] > 0
        finally:
            led.close()

    def test_midflight_fold_amended_when_trace_grows(self):
        """A refresh that races an in-flight op (e.g. a prometheus
        scrape between the queue-wait event and the root span closing)
        folds the partial tree; the NEXT refresh after the root closes
        must amend the record in place — full wall time, no duplicate
        record, cumulative phase seconds corrected by delta."""
        tr = Tracer()
        led = CritPathLedger(name="amend")
        try:
            ctx = tr.new_trace("client")
            t0 = time.perf_counter()
            tr.observe("osd.queue_wait", t0, t0 + 0.002, ctx=ctx)
            assert led.refresh(tr) == 1          # truncated fold
            s = led.class_summary("client")
            assert s["ops"] == 1
            assert s["phase_ms"]["queue"] == pytest.approx(2.0, rel=0.2)
            # the op's root work completes afterwards
            with tr.activate(ctx):
                with tr.span("osd.op", owner="client"):
                    time.sleep(0.005)
            assert led.refresh(tr) == 1          # amended, not re-added
            s = led.class_summary("client")
            assert s["ops"] == 1, "amendment must not duplicate"
            assert s["phase_ms"]["other"] > 0    # osd.op self time now in
            assert led.phase_seconds()["client"]["other"] > 0
            assert led.refresh(tr) == 0          # settled: nothing new
        finally:
            led.close()

    def test_bounded_records(self):
        led = CritPathLedger(name="b", capacity=8)
        try:
            for i in range(50):
                led.ingest("client", 0.001 * (i + 1), {"device": 0.001})
            assert len(led.records("client")) == 8
            assert led.folded == 50
        finally:
            led.close()

    def test_background_class_attribution(self):
        tr = Tracer()
        led = CritPathLedger(name="bg")
        try:
            with tr.activate(tr.new_trace("bg_scrub")):
                with tr.span("osd.scrub", owner="scrub"):
                    time.sleep(0.001)
            led.refresh(tr)
            assert led.classes() == ["scrub"]
        finally:
            led.close()

    def test_render_attribution_shape(self):
        led = CritPathLedger(name="r")
        try:
            led.ingest("client", 0.040,
                       {"batch_delay": 0.025, "device": 0.010,
                        "wire": 0.005})
            lines = render_attribution(led.snapshot())
            assert len(lines) == 1
            assert lines[0].startswith("client p99 = 40.0 ms")
            assert "62% batch_delay" in lines[0] or \
                "63% batch_delay" in lines[0]
        finally:
            led.close()

    def test_group_traces_drops_untraced(self):
        events = [ev("a", 0, 1, 1, trace=7),
                  {"name": "b", "ph": "X", "ts": 0, "dur": 1}]
        grouped = group_traces(events)
        assert list(grouped) == [7]


class TestUnifiedPercentile:
    def test_nearest_rank_definition(self):
        s = [1.0, 2.0, 3.0, 4.0]
        assert nearest_rank(s, 50) == 2.0
        assert nearest_rank(s, 99) == 4.0
        assert nearest_rank(s, 100) == 4.0
        assert nearest_rank(s, 0) == 1.0
        assert nearest_rank([], 99) == 0.0
        assert percentile([3.0, 1.0, 2.0], 50) == 2.0

    def test_trace_report_shares_the_definition(self):
        """trace_report's once-duplicated copy now IS the shared
        helper: identical answers on an awkward distribution."""
        spec = importlib.util.spec_from_file_location(
            "trace_report_pctl", ROOT / "tools" / "trace_report.py")
        trace_report = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(trace_report)
        vals = [0.1, 5.0, 5.0, 7.5, 100.0, 0.2, 3.3]
        for q in (0, 1, 50, 95, 99, 100):
            assert nearest_rank(sorted(vals), q) == \
                trace_report.percentile_us(vals, q), q

    def test_ast_guard_no_local_percentile_redefinitions(self):
        """No file but common/percentile.py may define a function named
        percentile/percentile_us/nearest_rank — the drift that made
        ts_report's copy silently diverge to floor-index.  Thin wrapper
        over the ``percentile-redef`` rule (ISSUE 15)."""
        import ceph_tpu.analysis as A
        offenders = [f.render() for f in A.run_rules(
            A.default_index(), ("percentile-redef",))]
        assert not offenders, (
            "local percentile redefinitions (use "
            "ceph_tpu/common/percentile.py):\n" + "\n".join(offenders))
