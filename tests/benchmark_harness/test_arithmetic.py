"""The benchmark's own arithmetic, on the CPU: percentiles and the
sample-count rule, the drained-span rate, the capture reduction, the
bytes functions, the peaks table, the readers."""
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.lib import peaks, readers, stats, work, xplane  # noqa: E402
from benchmark.lib.loadgen import Reservoir, closed_loop  # noqa: E402
from benchmark.lib.stats import Op  # noqa: E402


# -- percentiles ---------------------------------------------------------------

@pytest.mark.parametrize("q,want", [(50, 50.0), (95, 95.0), (100, 100.0),
                                    (1, 1.0), (0.5, 1.0)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(range(1, 101), q) == want


def test_percentile_is_a_measured_value_not_an_interpolation():
    assert stats.percentile([1.0, 10.0], 50) == 1.0
    assert stats.percentile([1.0, 10.0], 51) == 10.0


@pytest.mark.parametrize("bad", [0, -1, 101])
def test_percentile_refuses_a_rank_outside_range(bad):
    with pytest.raises(ValueError):
        stats.percentile([1, 2, 3], bad)


def test_percentile_refuses_an_empty_sample():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,q,ok", [(200, 95, True), (199, 95, False),
                                    (20, 50, True), (19, 50, False),
                                    (0, 50, False)])
def test_sample_count_rule_wants_ten_beyond(n, q, ok):
    assert stats.supported(n, q) is ok


def test_samples_beyond_counts_the_tail():
    assert stats.samples_beyond(480, 95) == 24
    assert stats.samples_beyond(196, 95) == 9


# -- the drained-span rate -------------------------------------------------------

MIB = 1 << 20


def _timeline(stall_at=None, stall=0.0, n=100, clients=4, service=0.1):
    """``clients`` closed loops of ``n`` ops of 1 MiB, ``service`` seconds
    each; one client stalls once."""
    ops = []
    for c in range(clients):
        t = 0.0
        for i in range(n):
            dur = service + (stall if (c == 0 and i == stall_at) else 0.0)
            ops.append(Op(t, t + dur, MIB, True, c))
            t += dur
    return ops


def test_drained_rate_is_all_bytes_over_first_issue_to_last_ack():
    ops = _timeline()
    assert stats.drained_span(ops) == (0.0, pytest.approx(10.0))
    assert stats.drained_rate_mib_s(ops) == pytest.approx(400 / 10.0)


def test_a_stall_anywhere_in_the_span_shows_in_the_rate():
    steady = stats.drained_rate_mib_s(_timeline())
    stalled = stats.drained_rate_mib_s(_timeline(stall_at=50, stall=2.0))
    assert stalled == pytest.approx(400 / 12.0)
    assert stalled < steady * 0.85


def test_a_failed_op_adds_time_and_no_bytes_and_has_no_latency():
    ops = _timeline(n=10, clients=1)
    ops.append(Op(1.0, 3.0, 0, False, 0))
    assert stats.drained_rate_mib_s(ops) == pytest.approx(10 / 3.0)
    assert len(stats.latencies_ms(ops)) == 10


def test_drained_rate_refuses_no_ops_and_an_empty_span():
    with pytest.raises(ValueError):
        stats.drained_rate_mib_s([])
    with pytest.raises(ValueError):
        stats.drained_rate_mib_s([Op(1.0, 1.0, MIB, True)])


def test_quartile_spread_is_iqr_over_median():
    vals = [100, 101, 102, 103, 104, 105]
    import statistics
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.quartile_spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))


# -- the load generator ----------------------------------------------------------

def test_closed_loop_stops_issuing_at_the_close_and_drains():
    import time

    def op(ci, seq):
        time.sleep(0.02)
        return 10, (ci, seq)
    ops, errors, stuck, t0 = closed_loop(3, 0.2, op)
    assert not errors and not stuck
    assert all(o.ok and o.nbytes == 10 for o in ops)
    assert max(o.t0 for o in ops) < t0 + 0.2          # none issued late
    assert max(o.t1 for o in ops) >= t0 + 0.2 - 0.021  # the last drained
    assert {o.client for o in ops} == {0, 1, 2}


def test_closed_loop_counts_a_failed_op_and_goes_on():
    def op(ci, seq):
        if seq == 1:
            raise OSError("refused")
        return 1, None
    ops, errors, stuck, _ = closed_loop(1, 0.05, op)
    assert sum(1 for o in ops if not o.ok) == 1
    assert len(ops) > 2 and "refused" in errors[0]


def test_closed_loop_runs_the_schedule_meanwhile():
    import time
    seen = []
    ops, _e, _s, t0 = closed_loop(
        1, 0.2, lambda ci, seq: (time.sleep(0.01), (1, None))[1],
        schedule=[(0.1, lambda: seen.append(time.perf_counter()))])
    assert len(seen) == 1 and 0.09 < seen[0] - t0 < 0.19


def test_reservoir_keeps_a_uniform_sample_and_the_last():
    import random
    res = Reservoir(4, random.Random(7))
    for i in range(1000):
        res.offer(i)
    got = res.sample()
    assert 999 in got and 4 <= len(got) <= 5
    assert len(set(got)) == len(got)
    few = Reservoir(4, random.Random(7))
    for i in range(3):
        few.offer(i)
    assert few.sample() == [0, 1, 2]


# -- the capture reduction -------------------------------------------------------

EVENTS = [("%fusion = u32[6291456]{0} fusion(", 100.0, 50.0),
          ("%gf_apply_pallas.1 = u8[4,1024]{1,0} custom-call(", 140.0, 30.0),
          ("%copy.4 = u32[12,262144]", 300.0, 20.0),
          ("%gf_apply_pallas.1 = u8[2,1024]{1,0} custom-call(", 400.0, 10.0)]


def test_busy_union_counts_overlap_once():
    assert xplane.busy_union_ns(EVENTS) == (170 - 100) + 20 + 10


def test_clip_cuts_events_to_the_window():
    got = xplane.clip(EVENTS, 120.0, 305.0)
    assert [(s, d) for _n, s, d in got] == [(120.0, 30.0), (140.0, 30.0),
                                           (300.0, 5.0)]


def test_idle_gaps_are_the_window_less_the_union():
    gaps = xplane.idle_gaps(EVENTS, 0.0, 500.0)
    assert gaps == [(0.0, 100.0), (170.0, 300.0), (320.0, 400.0),
                    (410.0, 500.0)]
    idle = sum(b - a for a, b in gaps)
    assert idle + xplane.busy_union_ns(EVENTS) == 500.0


def test_gaps_go_to_the_innermost_host_span():
    gaps = [(170.0, 300.0), (320.0, 400.0), (410.0, 500.0)]
    spans = [("rpc.put", 150.0, 405.0), ("osd.ECSubWrite", 160.0, 310.0),
             ("pg.generate_transactions", 315.0, 404.0)]
    got = dict(xplane.attribute_gaps(gaps, spans, min_gap_ns=1.0))
    assert got == {"osd.ECSubWrite": 130e-9,
                   "pg.generate_transactions": 80e-9,
                   "unattributed": 90e-9}


def test_short_gaps_are_left_out_of_the_attribution():
    got = xplane.attribute_gaps([(0.0, 5.0), (10.0, 100.0)], [],
                                min_gap_ns=50.0)
    assert got == [["unattributed", 90e-9]]


def test_top_ops_sums_by_name_largest_first():
    twice = EVENTS + [EVENTS[2]]
    top = xplane.top_ops(twice, n=2)
    assert top[0][0].startswith("%fusion") and top[0][1] == 50e-9
    assert top[1] == ["%copy.4 = u32[12,262144]", 40e-9]


def test_reduce_averages_busy_over_devices_and_reports_the_window():
    cap = {"devices": {"/device:TPU:0": EVENTS, "/device:TPU:1": []}}
    red = xplane.reduce(cap, 0.0, 500.0)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(100e-9 / 2)
    assert red["window_s"] == pytest.approx(500e-9)
    assert sum(b - a for a, b in red["gaps"]) == 400.0


def test_matching_finds_events_by_the_metrics_own_pattern():
    found = xplane.matching(EVENTS, r"gf_apply_pallas[.\d]*[\W_]+u8[\W_]"
                                    r"(?P<r>\d+)[\W_](?P<n>\d+)[\W_]")
    assert [(m["r"], m["n"], d) for m, d in found] == [("4", "1024", 30.0),
                                                       ("2", "1024", 10.0)]


def test_device_plane_names():
    assert xplane.DEVICE_PLANE.match("/device:TPU:0")
    assert not xplane.DEVICE_PLANE.match("/host:CPU")
    assert not xplane.DEVICE_PLANE.match("/device:CUSTOM:Megascale Trace")


# -- work and peaks ----------------------------------------------------------------

def test_gf_apply_bytes_is_a_function_of_k_r_n_only():
    assert work.gf_apply_bytes(8, 4, 33554432) == 12 * 33554432
    assert work.gf_apply_bytes(8, 2, 33554432) == 10 * 33554432


@pytest.mark.parametrize("args", [(0, 4, 10), (8, 0, 10), (8, 4, 0)])
def test_gf_apply_bytes_refuses_an_empty_shape(args):
    with pytest.raises(ValueError):
        work.gf_apply_bytes(*args)


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert peaks.peak("TPU v5 lite", "bf16_flop_per_s") == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v9 ultra", "hbm_bytes_per_s")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("cpu", "hbm_bytes_per_s")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v5 lite", "fp4_flop_per_s")


# -- the readers -------------------------------------------------------------------

def _ctx(**over):
    ops = [Op(i * 0.1, i * 0.1 + 0.05 + 0.001 * i, MIB, True)
           for i in range(40)]
    ctx = {"ops": ops, "counted_ops": 30, "traced_ops": 10,
           "spans": {"rpc.put": (3.0, 30), "osd.ECSubWrite": (1.2, 360),
                     "pg.generate_transactions": (2.4, 30),
                     "ec.encode": (0.6, 30)},
           "rpc": {"put": (9.0, 30)},
           "counters": {"serving.c1": {"ops_coalesced": 30, "batches": 30},
                        "serving.c1.pipeline": {"submitted": 30}},
           "device": xplane.reduce({"devices": {"/device:TPU:0": EVENTS}},
                                   0.0, 500.0),
           "device_kind": "TPU v5 lite",
           "config": {"driver_params": {"profile": {"k": "8"}}}}
    ctx.update(over)
    return ctx


def test_span_mean_per_event_and_lock_wait_as_a_difference():
    ctx = _ctx()
    assert readers.span_mean_ms({"spans": ["span:rpc.put"]}, ctx) == \
        pytest.approx(100.0)
    wait = readers.span_mean_ms({"spans": ["rpc:put", "rpc:get"],
                                 "minus": ["span:rpc.put", "span:rpc.get"]},
                                ctx)
    assert wait == pytest.approx(300.0 - 100.0)


def test_span_mean_per_op_adds_spans_that_occur_several_times_an_op():
    got = readers.span_mean_ms(
        {"spans": ["span:osd.ECSubWrite", "span:pg.generate_transactions"],
         "minus": ["span:ec.encode"], "per": "op"}, _ctx())
    assert got == pytest.approx((1.2 + 2.4 - 0.6) / 30 * 1e3)


def test_a_reader_that_finds_nothing_returns_nothing():
    ctx = _ctx(device=None)
    assert readers.span_mean_ms({"spans": ["span:no.such"]}, ctx) is None
    assert readers.device_idle_pct({}, ctx) is None
    assert readers.device_op_ms_per_op({"pattern": "fusion"}, ctx) is None
    assert readers.roofline_pct({"pattern": "x", "bytes_fn": "gf_apply_bytes",
                                 "peak": "hbm_bytes_per_s"}, ctx) is None
    assert readers.counter_ratio({"num": ["nope.*:x"], "den": "client_ops"},
                                 ctx) is None
    assert readers.roofline_pct(
        {"pattern": "no_such_kernel", "bytes_fn": "gf_apply_bytes",
         "peak": "hbm_bytes_per_s"}, _ctx()) is None


def test_counter_ratio_by_collection_glob():
    ctx = _ctx()
    assert readers.counter_ratio(
        {"num": ["serving.c*:ops_coalesced"], "den": ["serving.c*:batches"]},
        ctx) == 1.0
    assert readers.counter_ratio(
        {"num": ["serving.c*.pipeline:submitted"], "den": "client_ops"},
        ctx) == 1.0


def test_a_count_that_is_nought_reads_nought_not_nothing():
    ctx = _ctx(counters={"serving.c1.pipeline": {"submitted": 0}})
    assert readers.counter_ratio(
        {"num": ["serving.c*.pipeline:submitted"], "den": "client_ops"},
        ctx) == 0.0


def test_device_op_ms_per_op_divides_by_the_ops_of_the_profiled_stretch():
    got = readers.device_op_ms_per_op(
        {"pattern": r"^[%_]?fusion(\.\d+)?[\W_]+u32[\W_]6291456[\W_]"},
        _ctx())
    assert got == pytest.approx(50.0 / 1e6 / 10)


def test_roofline_is_bytes_over_peak_over_device_time():
    got = readers.roofline_pct(
        {"pattern": r"gf_apply_pallas[.\d]*[\W_]+u8[\W_](?P<r>\d+)[\W_]"
                    r"(?P<n>\d+)[\W_]",
         "bytes_fn": "gf_apply_bytes", "fixed": {"k": "profile.k"},
         "peak": "hbm_bytes_per_s"}, _ctx())
    total = (8 + 4) * 1024 + (8 + 2) * 1024
    assert got == pytest.approx(total / 819e9 / 40e-9 * 100.0)


def test_roofline_refuses_a_chip_it_has_no_peaks_for():
    with pytest.raises(peaks.UnknownDevice):
        readers.roofline_pct(
            {"pattern": r"gf_apply_pallas\S* = u8\[(?P<r>\d+),(?P<n>\d+)\]",
             "bytes_fn": "gf_apply_bytes", "fixed": {"k": "profile.k"},
             "peak": "hbm_bytes_per_s"}, _ctx(device_kind="TPU v9"))


def test_idle_share_is_one_less_busy_over_window():
    assert readers.device_idle_pct({}, _ctx()) == pytest.approx(80.0)


def test_client_percentile_under_the_sample_count_rule():
    ctx = _ctx()
    assert readers.client_percentile_ms({"q": 50}, ctx) == pytest.approx(
        stats.percentile(stats.latencies_ms(ctx["ops"]), 50))
    assert readers.client_percentile_ms({"q": 95}, ctx) is None   # 2 beyond


def test_an_unknown_reader_is_an_error():
    with pytest.raises(ValueError):
        readers.read_metric({"name": "x", "reader": "guess"}, _ctx())


def test_every_pattern_in_a_metric_file_compiles():
    import json
    root = Path(__file__).resolve().parents[2] / "benchmark" / "metrics"
    for path in sorted(root.glob("*.json")):
        spec = json.loads(path.read_text())
        assert spec["reader"] in readers.READERS, path.name
        if "pattern" in spec.get("params", {}):
            re.compile(spec["params"]["pattern"])


# -- the allocator pin ---------------------------------------------------------------

def test_allocator_pin_sets_both_thresholds_on_glibc():
    from benchmark.lib import allocator
    import platform
    if platform.libc_ver()[0] != "glibc":
        pytest.skip("mallopt is glibc's")
    assert allocator.pin() is True
    assert allocator.MMAP_THRESHOLD == 32 << 20
