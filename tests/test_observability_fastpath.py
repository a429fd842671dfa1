"""ISSUE 18: the observability fast path (sampled tracing, sharded
counters, kill-switch).

Pins the correctness surface that lets the instruments get cheap:

- head-based sampling decided once per trace root, atomic across the
  distributed trace (children inherit the decision + weight);
- slow-op promotion: an UNSAMPLED op that crosses the complaint
  threshold still lands in the ring (the acceptance test — slow ops are
  never lost, even at 1% sampling);
- sample-weight de-bias: weighted percentiles equal unweighted ones on
  unit weights and recover population percentiles from a thinned dump;
- the instruments kill-switch no-ops spans/instants/completes and wire
  accounting, and restores cleanly;
- sharded counter cells fold exactly under concurrent mutation, and the
  wire-class partition invariant survives multi-threaded accounting;
- per-thread tracer batching: pending events are visible to every read
  surface (dump/histograms/reset) and auto-flush at FLUSH_BATCH;
- the instrument-under-lock lint rule flags the PR 15 pattern and
  passes its clean twin;
- trace_report/slo_report label sampled artifacts and weight their
  percentile math.
"""
import importlib.util
import json
import pathlib
import threading
import time

import pytest

import ceph_tpu.analysis as A
from ceph_tpu.common import Context
from ceph_tpu.common import instruments
from ceph_tpu.common.perf_counters import PerfCountersBuilder
from ceph_tpu.common.percentile import percentile, weighted_nearest_rank
from ceph_tpu.common.tracer import FLUSH_BATCH, Tracer
from ceph_tpu.common.wire_accounting import WireAccounting

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_obs_t", ROOT / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- head-based sampling -----------------------------------------------------

class TestHeadSampling:
    def test_rate_one_samples_everything(self):
        t = Tracer()
        for _ in range(50):
            ctx = t.new_trace("client")
            assert ctx.sampled and ctx.weight == 1.0
        assert t.micro_records() == []

    def test_rate_zero_samples_nothing(self):
        t = Tracer()
        t.sample_rate = 0.0
        for _ in range(50):
            ctx = t.new_trace("client")
            assert not ctx.sampled and ctx.weight == 1.0
        assert len(t.micro_records()) == 50

    def test_quarter_rate_fraction_and_weight(self):
        t = Tracer()
        t.sample_rate = 0.25
        ctxs = [t.new_trace("client") for _ in range(1000)]
        sampled = [c for c in ctxs if c.sampled]
        # Knuth multiplicative hash over sequential ids is
        # low-discrepancy: the hit count stays near rate * n
        assert 200 <= len(sampled) <= 300
        assert all(c.weight == 4.0 for c in sampled)
        assert all(c.weight == 1.0 for c in ctxs if not c.sampled)

    def test_decision_is_per_trace_id_deterministic(self):
        t = Tracer()
        t.sample_rate = 0.25
        ctx = t.new_trace("client")
        assert t._sample(ctx.trace_id) == ctx.sampled
        assert t._sample(ctx.trace_id) == ctx.sampled

    def test_children_inherit_decision_and_weight(self):
        t = Tracer()
        t.sample_rate = 0.25
        ctxs = [t.new_trace("client") for _ in range(64)]
        assert any(c.sampled for c in ctxs)
        assert any(not c.sampled for c in ctxs)
        for ctx in ctxs:
            child = ctx.child_of(17)
            assert child.sampled == ctx.sampled
            assert child.weight == ctx.weight
            assert child.trace_id == ctx.trace_id

    def test_unsampled_instants_are_suppressed(self):
        t = Tracer()
        t.sample_rate = 0.0
        ctx = t.new_trace("client")
        with t.activate(ctx):
            t.instant("tick")
        assert t.dump()["traceEvents"] == []


# -- slow-op promotion (the acceptance pin) ----------------------------------

class TestSlowOpPromotion:
    def test_slow_ops_never_lost_at_one_percent_sampling(self):
        """THE acceptance test: at sample rate 0.01 every op that
        crosses osd_op_complaint_time reaches the ring — sampled ones
        as weighted events, unsampled ones promoted — and no fast
        unsampled op leaks in."""
        t = Tracer()
        t.sample_rate = 0.01
        t.slow_threshold_s = 0.05
        slow, fast = [], []
        for i in range(200):
            ctx = t.new_trace("client")
            name = f"op{i}"
            if i % 10 == 0:
                slow.append((name, ctx))
                dur = 0.2                      # over the complaint time
            else:
                fast.append((name, ctx))
                dur = 0.001
            now = time.perf_counter()
            t.observe(name, now - dur, now, ctx=ctx)
        ev = {e["name"]: e for e in t.dump()["traceEvents"]}
        for name, ctx in slow:
            assert name in ev, f"slow op {name} lost"
            args = ev[name]["args"]
            if ctx.sampled:
                assert args.get("sample_weight") == 100.0
                assert "promoted" not in args
            else:
                # promoted events represent only themselves: no weight
                assert args.get("promoted") is True
                assert "sample_weight" not in args
        for name, ctx in fast:
            if not ctx.sampled:
                assert name not in ev
        # every root completed: the micro-record table fully drained
        assert t.micro_records() == []

    def test_fast_unsampled_root_drops_micro_without_event(self):
        t = Tracer()
        t.sample_rate = 0.0
        ctx = t.new_trace("client")
        assert len(t.micro_records()) == 1
        now = time.perf_counter()
        t.observe("fast", now - 0.001, now, ctx=ctx)
        assert t.micro_records() == []
        assert t.dump()["traceEvents"] == []

    def test_span_path_promotes_on_threshold(self):
        t = Tracer()
        t.sample_rate = 0.0
        t.slow_threshold_s = 0.0               # everything counts as slow
        ctx = t.new_trace("client")
        with t.activate(ctx):
            with t.span("slow.work"):
                pass
        events = t.dump()["traceEvents"]
        assert len(events) == 1
        assert events[0]["args"].get("promoted") is True
        assert t.micro_records() == []

    def test_span_path_drops_fast_unsampled(self):
        t = Tracer()
        t.sample_rate = 0.0                    # threshold stays 30 s
        ctx = t.new_trace("client")
        with t.activate(ctx):
            with t.span("fast.work"):
                pass
        assert t.dump()["traceEvents"] == []
        assert t.micro_records() == []

    def test_micro_records_expose_inflight_unsampled_ops(self):
        t = Tracer()
        t.sample_rate = 0.0
        ctx = t.new_trace("recovery")
        recs = t.micro_records()
        assert len(recs) == 1
        assert recs[0]["trace_id"] == ctx.trace_id
        assert recs[0]["op_class"] == "recovery"
        assert recs[0]["start_wall"] <= time.time()
        t.reset()
        assert t.micro_records() == []


# -- weighted percentiles ----------------------------------------------------

class TestWeightedPercentiles:
    def test_unit_weights_match_unweighted_definition(self):
        vals = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
        pairs = sorted((v, 1.0) for v in vals)
        for q in (0, 1, 50, 90, 99, 100):
            assert weighted_nearest_rank(pairs, q) == percentile(vals, q)

    def test_thinned_sample_recovers_population_p99(self):
        population = [float(v) for v in range(1, 1001)]
        full_p99 = percentile(population, 99)
        # keep every 4th value, weight 4 — the head sampler's view
        pairs = [(v, 4.0) for v in population if v % 4 == 0]
        est = weighted_nearest_rank(pairs, 99)
        assert abs(est - full_p99) <= 0.012 * full_p99

    def test_heavy_weight_dominates(self):
        # 99 weighted units at 1.0, a single unit at 100.0: p50 is the
        # heavy value, p99.5 reaches the outlier
        pairs = [(1.0, 99.0), (100.0, 1.0)]
        assert weighted_nearest_rank(pairs, 50) == 1.0
        assert weighted_nearest_rank(pairs, 99.5) == 100.0


# -- the instruments kill-switch ---------------------------------------------

class TestKillSwitch:
    def test_tracer_noops_while_disabled_and_restores(self):
        t = Tracer()
        with instruments.disabled():
            assert not instruments.enabled()
            with t.span("gone") as s:
                s.set(note=1)                  # null span absorbs set()
            t.instant("gone.tick")
            t.observe("gone.op", time.perf_counter() - 0.01)
            t.observe("gone.linked", time.perf_counter() - 0.01,
                      ctx=t.new_trace("client"))
        assert instruments.enabled()
        assert t.dump()["traceEvents"] == []
        assert t.histograms() == {}
        with t.span("back"):
            pass
        assert [e["name"] for e in t.dump()["traceEvents"]] == ["back"]

    def test_wire_accounting_noops_while_disabled(self):
        cct = Context()
        acct = WireAccounting(cct=cct, name="ks")
        try:
            with instruments.disabled():
                acct.account_tx("T", 1000)
                acct.account_rx("T", 1000)
                acct.note_queue_depth(7)
                acct.observe_rpc("m", 0.5)
            totals = acct.totals()
            assert totals["tx_bytes"] == 0 and totals["rx_bytes"] == 0
            assert acct.rpc_methods() == {}
            acct.account_tx("T", 10)           # switch back on: counted
            assert acct.totals()["tx_bytes"] == 10
        finally:
            acct.close()

    def test_disabled_is_exception_safe(self):
        with pytest.raises(RuntimeError):
            with instruments.disabled():
                raise RuntimeError("boom")
        assert instruments.enabled()


# -- sharded counter cells ---------------------------------------------------

class TestShardedCounters:
    def _pc(self):
        return (PerfCountersBuilder("shard")
                .add_u64("gauge")
                .add_u64_counter("n")
                .add_u64_avg("bytes")
                .add_time_avg("lat")
                .add_histogram("h", [0.5, 2.0, 8.0])
                .create_perf_counters())

    def test_concurrent_mutation_folds_exactly(self):
        pc = self._pc()
        threads, per = 8, 500

        def work():
            for i in range(per):
                pc.inc("n")
                pc.inc("bytes", 10)
                pc.tinc("lat", 0.001)
                pc.hinc("h", float(i % 10))

        ts = [threading.Thread(target=work) for _ in range(threads)]
        for th in ts:
            th.start()
        for th in ts:
            th.join()
        total = threads * per
        assert pc.get("n") == total
        d = pc.dump()
        assert d["n"] == total
        assert d["bytes"]["avgcount"] == total
        assert d["bytes"]["sum"] == total * 10
        assert d["lat"]["avgcount"] == total
        assert abs(d["lat"]["sum"] - total * 0.001) < 1e-6
        assert d["h"]["count"] == total
        assert sum(d["h"]["buckets"].values()) == total

    def test_gauge_set_dec_keep_read_modify_write_semantics(self):
        pc = self._pc()
        pc.set("gauge", 10)
        pc.inc("gauge", 5)
        pc.dec("gauge", 3)
        assert pc.get("gauge") == 12
        pc.set("gauge", 0)
        assert pc.get("gauge") == 0

    def test_wire_partition_invariant_under_concurrency(self):
        """sum(class_bytes:*) == tx_bytes + rx_bytes even while eight
        threads account concurrently through the sharded cells."""
        cct = Context()
        acct = WireAccounting(cct=cct, name="part")
        classes = ["client", "recovery", "scrub", "rebalance"]

        class _Ctx:
            def __init__(self, op_class):
                self.op_class = op_class

        def work(seed):
            for i in range(400):
                cls = _Ctx(classes[(seed + i) % len(classes)])
                acct.account_tx("T", 10, ctx=cls)
                if i % 3 == 0:
                    acct.account_rx("T", 7, ctx=cls)

        try:
            ts = [threading.Thread(target=work, args=(k,))
                  for k in range(8)]
            for th in ts:
                th.start()
            for th in ts:
                th.join()
            totals = acct.totals()
            assert totals["tx_bytes"] == 8 * 400 * 10
            assert totals["rx_bytes"] == 8 * 134 * 7
            cls_bytes = acct.class_bytes()
            assert sum(cls_bytes.values()) == \
                totals["tx_bytes"] + totals["rx_bytes"]
        finally:
            acct.close()


# -- per-thread batching -----------------------------------------------------

class TestBatchedRingWrites:
    def test_pending_events_visible_to_every_read_surface(self):
        t = Tracer()
        with t.span("pending.a"):
            pass
        t.instant("pending.b")
        # below FLUSH_BATCH: still in the owner buffer, not the ring
        assert len(t._events) == 0
        names = {e["name"] for e in t.dump()["traceEvents"]}
        assert names == {"pending.a", "pending.b"}
        assert t.histograms()["pending.a"]["count"] == 1

    def test_flush_batch_folds_automatically(self):
        t = Tracer()
        for i in range(FLUSH_BATCH):
            t.instant(f"i{i}")
        assert len(t._events) == FLUSH_BATCH

    def test_explicit_flush_is_the_completion_boundary(self):
        t = Tracer()
        with t.span("done"):
            pass
        assert len(t._events) == 0
        t.flush()
        assert len(t._events) == 1

    def test_reset_drains_pending_before_counting(self):
        t = Tracer()
        with t.span("x"):
            pass
        out = t.reset()
        assert out["success"] == "dropped 1 events"
        assert t.dump()["traceEvents"] == []

    def test_cross_thread_pending_drained_by_dump(self):
        t = Tracer()

        def worker():
            with t.span("other.thread"):
                pass

        th = threading.Thread(target=worker)
        th.start()
        th.join()
        names = [e["name"] for e in t.dump()["traceEvents"]]
        assert names == ["other.thread"]


# -- lint rule: instrument-under-lock ----------------------------------------

_LINT_BAD = (
    "import threading\n"
    "class Sender:\n"
    "    def __init__(self, perf, acct):\n"
    "        self._lock = threading.Lock()\n"
    "        self.perf = perf\n"
    "        self.acct = acct\n"
    "        self.queue = []\n"
    "        self._thread = threading.Thread(target=self._loop,\n"
    "                                        daemon=True)\n"
    "    def _loop(self):\n"
    "        with self._lock:\n"
    "            self.queue.append(1)\n"
    "            self.perf.inc('msgs')\n"
    "            self.acct.account_tx('T', 10)\n"
)

_LINT_CLEAN = (
    "import threading\n"
    "class Sender:\n"
    "    def __init__(self, perf, acct):\n"
    "        self._lock = threading.Lock()\n"
    "        self.perf = perf\n"
    "        self.acct = acct\n"
    "        self.queue = []\n"
    "        self._thread = threading.Thread(target=self._loop,\n"
    "                                        daemon=True)\n"
    "    def _loop(self):\n"
    "        with self._lock:\n"
    "            self.queue.append(1)\n"
    "        self.perf.inc('msgs')\n"
    "        self.acct.account_tx('T', 10)\n"
)


class TestInstrumentUnderLockRule:
    def test_flags_instruments_inside_worker_critical_section(self):
        found = A.run_rule_on_sources("instrument-under-lock",
                                      {"sender.py": _LINT_BAD})
        assert len(found) == 2
        msgs = " | ".join(f.message for f in found)
        assert "self.perf.inc()" in msgs
        assert "self.acct.account_tx()" in msgs
        assert "Sender._loop" in msgs
        assert all(f.severity == "warning" for f in found)

    def test_clean_twin_passes(self):
        assert A.run_rule_on_sources("instrument-under-lock",
                                     {"sender.py": _LINT_CLEAN}) == []

    def test_live_tree_has_no_unbaselined_findings(self):
        findings = A.run_rules(A.default_index(),
                               rule_ids=("instrument-under-lock",))
        baseline = A.load_baseline(str(ROOT / ".ceph_lint_baseline.json"))
        new, _old, _stale = A.split_by_baseline(findings, baseline)
        assert new == [], [f.message for f in new]


# -- device-telemetry refresh TTL --------------------------------------------

class TestDeviceRefreshTTL:
    def test_scrapes_inside_ttl_reuse_the_snapshot(self):
        from ceph_tpu.mgr.prometheus import _device_refresh_due
        cct = Context()
        cct.conf.set("mgr_device_refresh_ttl", 5.0)
        assert _device_refresh_due(cct, 100.0)
        assert not _device_refresh_due(cct, 102.0)
        assert not _device_refresh_due(cct, 104.9)
        assert _device_refresh_due(cct, 105.1)

    def test_ttl_zero_refreshes_every_scrape(self):
        from ceph_tpu.mgr.prometheus import _device_refresh_due
        cct = Context()
        cct.conf.set("mgr_device_refresh_ttl", 0.0)
        assert _device_refresh_due(cct, 100.0)
        assert _device_refresh_due(cct, 100.0)

    def test_stamp_is_per_context(self):
        # one context's scrape must not starve a DIFFERENT context's
        # first scrape of its own device gauges
        from ceph_tpu.mgr.prometheus import _device_refresh_due
        a, b = Context(), Context()
        a.conf.set("mgr_device_refresh_ttl", 5.0)
        b.conf.set("mgr_device_refresh_ttl", 5.0)
        assert _device_refresh_due(a, 100.0)
        assert _device_refresh_due(b, 100.0)


# -- report tools on sampled dumps -------------------------------------------

class TestSampledReportTools:
    def _sampled_dump(self):
        """A dump where every recorded root carries weight 2 (rate 0.5),
        produced through the real tracer so args schemas stay honest."""
        t = Tracer()
        t.sample_rate = 0.5
        durs = []
        n = 0
        while n < 40:
            ctx = t.new_trace("client")
            if not ctx.sampled:
                continue
            dur = 0.001 * (n + 1)
            now = time.perf_counter()
            t.observe("client.op", now - dur, now, ctx=ctx)
            durs.append(dur)
            n += 1
        return t.dump(), durs

    def test_trace_report_weights_and_labels_sampled_dump(self, tmp_path):
        tr = _load_tool("trace_report")
        dump, durs = self._sampled_dump()
        events = [e for e in dump["traceEvents"] if e.get("ph") == "X"]
        agg = tr.self_times(events)
        assert tr.is_sampled(agg)
        row = agg["client.op"]
        assert row["count"] == 40
        assert row["weight"] == pytest.approx(80.0)
        doc = json.loads(tr.render_json(agg))
        assert doc["sampled"] is True
        assert doc["spans"][0]["est_count"] == pytest.approx(80.0)
        table = tr.render_table(agg)
        assert "sampled trace" in table.splitlines()[0]

    def test_trace_report_unsampled_dump_stays_unlabeled(self):
        tr = _load_tool("trace_report")
        t = Tracer()
        with t.span("plain"):
            pass
        agg = tr.self_times(
            [e for e in t.dump()["traceEvents"] if e.get("ph") == "X"])
        assert not tr.is_sampled(agg)
        assert json.loads(tr.render_json(agg))["sampled"] is False
        assert "sampled trace" not in tr.render_table(agg)

    def test_slo_report_debiases_sampled_trace_dump(self):
        slo = _load_tool("slo_report")
        dump, durs = self._sampled_dump()
        report = slo.build_report(dump)
        assert report["source"] == "trace"
        assert report["sampled"] is True
        cls = report["classes"]["client"]
        assert cls["ops"] == 40
        assert cls["weighted_ops"] == pytest.approx(80.0)
        # weighted p99 over the recorded ops matches the direct
        # computation on (dur, 2.0) pairs
        pairs = sorted((d, 2.0) for d in durs)
        want = weighted_nearest_rank(pairs, 99) * 1e3
        assert cls["p99_ms"] == pytest.approx(want, rel=1e-3)
        assert "head-sampled" in slo.render(report)
