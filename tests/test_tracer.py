"""Span tracer + traced_jit telemetry (common/tracer.py, ops/traced_jit.py).

Pins: span nesting on one tid, ring-buffer eviction, the Chrome
trace-event JSON schema (loads in chrome://tracing / Perfetto), traced_jit
compile accounting (one compilation per shape key, cache hits for repeats,
bypass under an enclosing jit), the slow-op threshold satellite, the
`trace dump`/`jit dump` admin commands after real EC backend traffic, and
the tools/trace_report.py self-time math.
"""
import importlib.util
import json
import pathlib
import time

import numpy as np
import pytest

from ceph_tpu.common import Context
from ceph_tpu.common.optracker import OpTracker
from ceph_tpu.common.perf_counters import PerfCountersBuilder
from ceph_tpu.common.tracer import (Tracer, default_tracer, jit_dump,
                                    jit_perf_counters, trace_span)


class TestSpans:
    def test_nesting_same_thread(self):
        t = Tracer()
        with t.span("outer") as outer:
            assert t.depth() == 1
            assert t.current() is outer
            with t.span("inner") as inner:
                assert t.depth() == 2
        assert t.depth() == 0
        ev = {e["name"]: e for e in t.dump()["traceEvents"]}
        o, i = ev["outer"], ev["inner"]
        assert o["tid"] == i["tid"]
        # child contained in parent on the shared timeline
        assert i["ts"] >= o["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
        assert inner.dur <= outer.dur

    def test_ring_buffer_eviction(self):
        t = Tracer(capacity=8)
        for n in range(20):
            with t.span(f"s{n}"):
                pass
        events = t.dump()["traceEvents"]
        assert len(events) == 8
        assert [e["name"] for e in events] == [f"s{n}" for n in range(12, 20)]

    def test_default_tracer_reads_its_capacity_when_it_is_made(
            self, monkeypatch):
        """CEPH_TPU_TRACE_CAPACITY is read when the process-wide tracer
        is made, not when the module is imported: a driver imported
        after it can still size the ring (ISSUE 35, ROADMAP B8)."""
        from ceph_tpu.common import tracer as mod
        assert mod.TRACE_CAPACITY == 16384
        monkeypatch.setattr(mod, "_default_tracer", None)
        monkeypatch.setenv("CEPH_TPU_TRACE_CAPACITY", "24")
        assert default_tracer()._events.maxlen == 24
        assert default_tracer() is default_tracer()
        monkeypatch.setattr(mod, "_default_tracer", None)
        monkeypatch.delenv("CEPH_TPU_TRACE_CAPACITY")
        assert default_tracer()._events.maxlen == 16384
        assert Tracer()._events.maxlen == 16384

    def test_chrome_trace_event_schema(self):
        t = Tracer()
        with t.span("work", cat="test", items=3):
            pass
        t.instant("tick", note="hi")
        doc = t.dump()
        text = json.dumps(doc)                 # must be JSON-serializable
        doc = json.loads(text)
        assert doc["displayTimeUnit"] == "ms"
        kinds = {e["ph"] for e in doc["traceEvents"]}
        assert kinds == {"X", "i"}
        for e in doc["traceEvents"]:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
            assert e["ts"] >= 0
            if e["ph"] == "X":
                assert e["dur"] >= 0
        ev = {e["name"]: e for e in doc["traceEvents"]}
        assert ev["work"]["args"]["items"] == 3
        assert ev["tick"]["s"] == "t"

    def test_reset_and_histograms(self):
        t = Tracer()
        with t.span("h"):
            pass
        h = t.histograms()["h"]
        assert h["count"] == 1
        assert sum(h["counts"]) == 1
        assert h["sum"] >= 0
        assert len(h["counts"]) == len(h["buckets"]) + 1
        t.reset()
        assert t.dump()["traceEvents"] == []
        assert t.histograms() == {}


class TestObserve:
    """The one after-the-fact path: ``observe(name, t0, t1)`` on
    ``time.perf_counter``, linked into a trace when given a context."""

    def test_bare_observe_is_the_lite_tuple_path(self):
        t = Tracer()
        t0 = time.perf_counter()
        t.observe("hot", t0, t0 + 0.002, cat="rpc")
        t.flush()
        assert type(t._events[-1]) is tuple
        (ev,) = t.dump()["traceEvents"]
        assert ev["name"] == "hot" and ev["cat"] == "rpc"
        assert "args" not in ev
        assert ev["dur"] == pytest.approx(2000.0)
        assert t.histograms()["hot"]["count"] == 1

    def test_linked_observe_stamps_what_a_live_span_stamps(self):
        t = Tracer()
        ctx = t.new_trace("recovery")
        with t.activate(ctx, track="osd.1"):
            with t.span("live") as live:
                t0 = time.perf_counter()
        t1 = time.perf_counter()
        t.observe("after", t0, t1, "rpc", live_ctx := ctx.child_of(7),
                  "server", shard=3)
        ev = {e["name"]: e for e in t.dump(stitched=False)["traceEvents"]}
        a, l = ev["after"]["args"], ev["live"]["args"]
        assert a["trace_id"] == l["trace_id"] == ctx.trace_id
        assert a["parent_span_id"] == live_ctx.span_id == 7
        assert a["span_id"] not in (0, l["span_id"])
        assert a["op_class"] == l["op_class"] == "recovery"
        assert a["shard"] == 3 and "sample_weight" not in a
        assert ev["after"]["ph"] == "X" and ev["after"]["cat"] == "rpc"
        # one clock: stamped from perf_counter inside the live span, the
        # event starts inside it
        assert ev["live"]["ts"] <= ev["after"]["ts"] \
            <= ev["live"]["ts"] + ev["live"]["dur"] + 1.0
        assert ev["after"]["dur"] == pytest.approx((t1 - t0) * 1e6)
        assert t.histograms()["after"]["count"] == 1
        # the track re-homes it like a live span's
        pids = {e["pid"] for e in t.dump()["traceEvents"]
                if e["name"] in ("after", "live")}
        assert len(pids) == 2 and all(p >= 1_000_000 for p in pids)

    def test_linked_observe_honours_the_head_sampling_decision(self):
        t = Tracer()
        t.sample_rate = 0.0
        t.slow_threshold_s = 0.05
        fast, slow = t.new_trace("client"), t.new_trace("client")
        assert not fast.sampled and len(t.micro_records()) == 2
        now = time.perf_counter()
        t.observe("fast", now - 0.001, now, ctx=fast)
        t.observe("slow", now - 0.2, now, ctx=slow)
        ev = {e["name"]: e for e in t.dump()["traceEvents"]}
        assert "fast" not in ev and "fast" not in t.histograms()
        assert ev["slow"]["args"]["promoted"] is True
        assert t.micro_records() == []           # both roots finished
        t.sample_rate = 0.5
        while True:
            ctx = t.new_trace("client")
            if ctx.sampled:
                break
        t.observe("weighted", now - 0.001, now, ctx=ctx)
        with t.activate(ctx):
            with t.span("weighted.live"):
                pass
        ev = {e["name"]: e for e in t.dump()["traceEvents"]}
        assert ev["weighted"]["args"]["sample_weight"] == \
            ev["weighted.live"]["args"]["sample_weight"] == 2.0


class TestTracedJit:
    def test_compile_per_shape_and_cache_hits(self):
        import jax.numpy as jnp
        from ceph_tpu.ops.traced_jit import traced_jit

        @traced_jit(name="tj_test_add")
        def add1(a):
            return a + jnp.uint8(1)

        x4 = np.zeros(4, dtype=np.uint8)
        for _ in range(3):
            np.testing.assert_array_equal(np.asarray(add1(x4)),
                                          np.ones(4, np.uint8))
        x8 = np.zeros(8, dtype=np.uint8)
        np.testing.assert_array_equal(np.asarray(add1(x8)),
                                      np.ones(8, np.uint8))
        entries = [e for e in jit_dump()["functions"]
                   if e["function"] == "tj_test_add"]
        assert len(entries) == 2               # one compilation per shape
        by_calls = sorted(e["calls"] for e in entries)
        assert by_calls == [1, 3]
        for e in entries:
            assert e["compiles"] == 1
            assert e["compile_s"] >= 0

    def test_bypass_under_enclosing_jit(self):
        import jax
        import jax.numpy as jnp
        from ceph_tpu.ops.traced_jit import traced_jit

        @traced_jit(name="tj_test_inner")
        def inner(a):
            return a * jnp.uint8(2)

        out = jax.jit(lambda a: inner(a) + jnp.uint8(1))(
            jnp.full((4,), 3, jnp.uint8))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full(4, 7, np.uint8))
        # the traced call inlined: no telemetry entry for it
        assert not [e for e in jit_dump()["functions"]
                    if e["function"] == "tj_test_inner"]

    def test_repeated_same_shape_encode_compiles_once(self):
        """The acceptance-criteria probe: repeated same-shape encodes show
        exactly ONE compilation for the kernel in the jit perf dump."""
        from ceph_tpu.ops import RSCodec

        codec = RSCodec(4, 2, technique="reed_sol_van", device="jax")
        rng = np.random.default_rng(0)
        data = rng.integers(0, 256, size=(4, 736), dtype=np.uint8)
        p1 = codec.encode(data)
        p2 = codec.encode(data)
        np.testing.assert_array_equal(p1, p2)
        entries = [e for e in jit_dump()["functions"]
                   if e["function"] == "gf_apply_bitslice"
                   and "(4, 736)" in e["key"]]
        assert len(entries) == 1               # exactly one compilation
        assert entries[0]["compiles"] == 1
        assert entries[0]["calls"] >= 2        # the repeat was a cache hit
        counters = jit_perf_counters().dump()
        assert counters["compilations"] >= 1
        assert counters["cache_hits"] >= 1
        assert counters["compile_time"]["avgcount"] >= 1


class TestSlowOps:
    def _perf(self):
        return (PerfCountersBuilder("slowtest")
                .add_u64_counter("slow_ops", "slow ops")
                .create_perf_counters())

    def test_threshold_marks_counts_and_dumps(self):
        perf = self._perf()
        tr = OpTracker(complaint_time=0.0, perf=perf)
        tr.create_request("write slowpoke").finish()
        assert perf.get("slow_ops") == 1
        hist = tr.dump_historic_ops()
        assert hist["ops"][0]["slow"] is True
        slow = tr.dump_historic_slow_ops()
        assert slow["num_ops"] == 1
        assert slow["ops"][0]["description"] == "write slowpoke"

    def test_fast_op_not_marked(self):
        perf = self._perf()
        tr = OpTracker(complaint_time=30.0, perf=perf)
        tr.create_request("write quick").finish()
        assert perf.get("slow_ops") == 0
        assert tr.dump_historic_ops()["ops"][0]["slow"] is False
        assert tr.dump_historic_slow_ops()["num_ops"] == 0

    def test_configured_via_options_with_live_update(self):
        cct = Context()
        tr = OpTracker(conf=cct.conf, perf=self._perf())
        assert tr.complaint_time == 30.0       # osd_op_complaint_time default
        cct.conf.set("osd_op_complaint_time", 0.25)
        assert tr.complaint_time == 0.25       # observer fired


class TestAdminSocketSurface:
    def test_trace_dump_contains_encode_decode_after_write_read(self):
        from ceph_tpu.backend import PGTransaction, make_cluster
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry

        default_tracer().reset()
        ec = ErasureCodePluginRegistry.instance().factory(
            "jax_rs", "", {"k": "2", "m": "1", "device": "numpy",
                           "technique": "reed_sol_van"})
        cct = Context()
        backend, bus = make_cluster(ec, chunk_size=128, cct=cct)
        data = np.arange(2 * 128, dtype=np.uint8).tobytes()
        backend.submit_transaction(PGTransaction().write("o", 0, data))
        bus.deliver_all()
        got = {}
        backend.objects_read_and_reconstruct(
            {"o": [(0, len(data))]},
            lambda result, errors: got.update(result))
        bus.deliver_all()
        assert got["o"][0][2] == data
        doc = json.loads(cct.admin_socket.call_json("trace dump"))
        names = {e["name"] for e in doc["traceEvents"]}
        assert "ec.encode" in names
        assert "ec.decode" in names
        assert "pg.generate_transactions" in names
        assert any(n.startswith("op.") for n in names)   # TrackedOp events
        # reset drops everything
        cct.admin_socket.call("trace reset")
        doc = json.loads(cct.admin_socket.call_json("trace dump"))
        assert doc["traceEvents"] == []

    def test_jit_dump_and_reset_commands(self):
        cct = Context()
        dump = cct.admin_socket.call("jit dump")
        assert set(dump) == {"functions", "num_keys", "counters"}
        assert dump["num_keys"] == len(dump["functions"])
        assert "success" in cct.admin_socket.call("jit reset")
        assert cct.admin_socket.call("jit dump")["num_keys"] == 0


class TestTraceReportTool:
    def _tool(self):
        path = pathlib.Path(__file__).resolve().parent.parent / \
            "tools" / "trace_report.py"
        spec = importlib.util.spec_from_file_location("trace_report", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_self_time_excludes_children(self, tmp_path):
        mod = self._tool()
        events = [
            {"name": "parent", "ph": "X", "ts": 0.0, "dur": 100.0,
             "pid": 1, "tid": 1},
            {"name": "child", "ph": "X", "ts": 10.0, "dur": 30.0,
             "pid": 1, "tid": 1},
            {"name": "child", "ph": "X", "ts": 50.0, "dur": 20.0,
             "pid": 1, "tid": 1},
            # another tid: independent track, no cross-charging
            {"name": "other", "ph": "X", "ts": 0.0, "dur": 5.0,
             "pid": 1, "tid": 2},
        ]
        f = tmp_path / "trace.json"
        f.write_text(json.dumps({"traceEvents": events}))
        agg = mod.self_times(mod.load_events(str(f)))
        assert agg["parent"]["total_us"] == 100.0
        assert agg["parent"]["self_us"] == 50.0       # minus both children
        assert agg["child"]["count"] == 2
        assert agg["child"]["self_us"] == 50.0
        assert agg["other"]["self_us"] == 5.0
        table = mod.render_table(agg)
        assert table.splitlines()[1].startswith(("parent", "child"))

    def test_cli_renders_a_real_dump(self, tmp_path, capsys):
        mod = self._tool()
        t = Tracer()
        with t.span("outer"):
            with t.span("inner"):
                pass
        f = tmp_path / "dump.json"
        f.write_text(json.dumps(t.dump()))
        assert mod.main([str(f)]) == 0
        out = capsys.readouterr().out
        assert "outer" in out and "inner" in out and "self ms" in out
        assert "p50 ms" in out and "p99 ms" in out

    def test_percentile_columns(self, tmp_path):
        """p50/p99 over each span name's per-occurrence durations (the
        serving-latency view): 100 spans of 1..100us -> p50=50, p99=99."""
        mod = self._tool()
        events = [{"name": "op", "ph": "X", "ts": float(i * 1000),
                   "dur": float(i + 1), "pid": 1, "tid": 1}
                  for i in range(100)]
        f = tmp_path / "trace.json"
        f.write_text(json.dumps({"traceEvents": events}))
        agg = mod.self_times(mod.load_events(str(f)))
        assert mod.percentile_us(agg["op"]["durs_us"], 50) == 50.0
        assert mod.percentile_us(agg["op"]["durs_us"], 99) == 99.0
        assert mod.percentile_us(agg["op"]["durs_us"], 100) == 100.0
        assert mod.percentile_us([], 50) == 0.0
        table = mod.render_table(agg)
        header, row = table.splitlines()[:2]
        assert "p50 ms" in header and "p99 ms" in header
        assert "0.050" in row and "0.099" in row
