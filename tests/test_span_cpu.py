"""CPU time beside wall time (ISSUE 37): a span that asks reads its
thread's CPU clock at both ends, and the fold books the CPU microseconds
and the rest of the wall time under the span's name in the ``span_cpu``
collection; each service thread charges its own CPU to its role in
``thread_cpu``.  Counts and bookkeeping on the CPU: nothing here is a
time of the device.
"""
import threading
import time

import numpy as np
import pytest

from ceph_tpu.common import Context, instruments
from ceph_tpu.common import tracer as tracer_mod
from ceph_tpu.common.tracer import (THREAD_ROLES, Tracer, default_tracer,
                                    span_cpu_perf_counters,
                                    thread_cpu_perf_counters, trace_span)

MS30 = 0.030


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _span_cpu(name):
    """(cpu_us, offcpu_us) of ``name`` now, the tracer drained first as
    a reader of the counters does."""
    default_tracer().histograms()
    dump = span_cpu_perf_counters().dump()
    return (dump.get(f"{name}.cpu_us", 0), dump.get(f"{name}.offcpu_us", 0))


def _burn(cpu_seconds):
    """Spin until the calling thread's own CPU clock has advanced."""
    end = time.thread_time() + cpu_seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("how,work", [("sleeps", time.sleep),
                                      ("spins", _burn)])
def test_a_cpu_span_parts_the_time_it_ran_from_the_time_it_did_not(how, work):
    name = f"t37.{how}"
    with trace_span(name, cpu=True):
        # the same two clocks read beside the span's own reads: a busy
        # machine takes the core from a spin and wakes a sleeper late,
        # and both clocks say so alike
        w0, c0 = time.perf_counter(), time.thread_time_ns()
        work(MS30)
        c1, w1 = time.thread_time_ns(), time.perf_counter()
    cpu, off = _span_cpu(name)
    # the two together are the span's wall time
    hist = default_tracer().histograms()[name]
    assert cpu + off == pytest.approx(hist["sum"] * 1e6, abs=2)
    # and each is what the clocks read inside it, and the little the
    # span's own entry and exit take
    assert -2 <= cpu - (c1 - c0) * 1e-3 <= 5_000
    assert -2 <= off - ((w1 - w0) * 1e6 - (c1 - c0) * 1e-3) <= 5_000
    # a sleeper burns nothing and stands off the CPU for its sleep; a
    # spin that burns 30 ms reads them and no fifth more
    if how == "sleeps":
        assert cpu < 5_000 and off >= 0.8 * 30_000
    else:
        assert 30_000 <= cpu <= 1.2 * 30_000


def test_observe_with_cpu_seconds_lands_in_the_same_two_counters():
    name = "t37.observed"
    tr = default_tracer()
    t0 = time.perf_counter()
    tr.observe(name, t0, t0 + 0.010, cpu_s=0.004)
    # a CPU clock that ticks charges one span more than its wall time
    # (and the next nothing): the off-CPU sum never falls, and the
    # overshoot is owed to the spans after it, so that the two sums
    # stay the spans' wall time
    tr.observe(name, t0, t0 + 0.010, cat="rpc", cpu_s=0.0105)
    assert _span_cpu(name) == (4_000 + 10_500, 6_000)
    # through a trace context too: the dict path carries it in args
    tr.observe(name, t0, t0 + 0.002, ctx=tr.new_trace(), cpu_s=0.001)
    assert _span_cpu(name) == (15_500, 6_500)
    ev = [e for e in tr.dump()["traceEvents"] if e["name"] == name]
    assert [e["args"]["cpu_us"] for e in ev] == \
        pytest.approx([4_000, 10_500, 1_000])
    assert "trace_id" in ev[2]["args"] and "trace_id" not in ev[0]["args"]


def test_a_span_without_cpu_adds_no_counter_and_its_ring_entry_is_as_before():
    tr = Tracer()
    with tr.span("t37.plain"):
        pass
    tr.observe("t37.plain", time.perf_counter())
    with tr.span("t37.asked", cpu=True):
        pass
    # the lite path builds no dict: bare tuples ride the buffer and the
    # ring, five fields as before and six with the CPU time
    assert [type(e) for e in tr._local.pending] == [tuple] * 3
    assert [len(e) for e in tr._local.pending] == [5, 5, 6]
    tr.flush()
    assert [type(e) for e in tr._events] == [tuple] * 3
    plain, _observed, asked = tr.dump()["traceEvents"]
    assert set(plain) == {"name", "cat", "ph", "ts", "dur", "pid", "tid"}
    assert set(asked) == set(plain) | {"args"}
    assert set(asked["args"]) == {"cpu_us"}
    dump = span_cpu_perf_counters().dump()
    assert "t37.asked.cpu_us" in dump and "t37.asked.offcpu_us" in dump
    assert not [k for k in dump if k.startswith("t37.plain")]
    # histograms() is what it was
    assert set(tr.histograms()["t37.asked"]) == {"buckets", "counts", "sum",
                                                 "count"}


def test_a_phase_clock_tiles_a_transaction_and_parts_it_by_the_cpu_clock(
        monkeypatch):
    """``PerfCounters.phase_clock``: the store's transactions are timed
    through it (one in ``BlueStoreLite.TIMED_EVERY``: the CPU clock is a
    system call, dear on the chip's host, and ticks at 100 Hz there)."""
    from ceph_tpu.common.perf_counters import PerfCountersBuilder
    b = PerfCountersBuilder("t37.phases")
    for key in ("a_us", "b_us", "cpu_us"):
        b.add_u64_counter(key, key)
    pc = b.create_perf_counters()
    clk = pc.phase_clock("cpu_us")
    zero = pc.dump()
    # not started, started off, dropped by the next start: nothing is
    # read or booked
    clk.mark("a_us")
    clk.commit()
    clk.start(False)
    clk.mark("a_us")
    clk.commit()
    clk.start()
    clk.stop("a_us")
    clk.start(False)
    clk.commit()
    with instruments.disabled():
        clk.start()
        clk.stop("a_us")
        clk.commit()
    assert pc.dump() == zero
    # two marks under one key add up; the phases are the stretch
    t0 = time.perf_counter()
    clk.start()
    _spin(0.002)
    clk.mark("a_us")
    time.sleep(0.003)
    clk.mark("b_us")
    _spin(0.001)
    clk.stop("a_us")
    t1 = time.perf_counter()
    clk.commit(4)
    got = pc.dump()
    assert got["a_us"] % 4 == got["b_us"] % 4 == got["cpu_us"] % 4 == 0
    assert got["a_us"] >= 4 * 3_000 and got["b_us"] >= 4 * 3_000
    assert got["a_us"] + got["b_us"] <= 4 * (t1 - t0) * 1e6 + 8
    # the sleep stood off the CPU (and on a busy machine a spin may):
    # the CPU clock read the spins at most, and the reads' own CPU
    assert 0 < got["cpu_us"] <= got["a_us"] + 4 * 100
    # a CPU clock that ticks reads one transaction a whole tick and the
    # next ones nothing: both are booked as read, and only sums say
    # what ran
    ticks = iter([0, 10_000_000, 10_000_000, 10_000_000])
    monkeypatch.setattr(time, "thread_time_ns", lambda: next(ticks))
    before = pc.dump()
    for _ in range(2):
        clk.start()
        _spin(0.003)
        clk.stop("a_us")
        clk.commit()
    rose = {k: v - before[k] for k, v in pc.dump().items()}
    assert rose["cpu_us"] == 10_000 and rose["a_us"] >= 6_000


def test_switched_off_it_records_nothing_and_reads_no_clock(monkeypatch):
    reads = []
    real = time.thread_time_ns

    def counted():
        reads.append(1)
        return real()
    monkeypatch.setattr(time, "thread_time_ns", counted)
    before = (_span_cpu("t37.off"), thread_cpu_perf_counters().dump())
    with instruments.disabled():
        with trace_span("t37.off", cpu=True):
            pass
        default_tracer().observe("t37.off", time.perf_counter(), cpu_s=0.5)
        tracer_mod.charge_thread_cpu("dispatch")
        assert not reads
    roles = {r: v for r, v in thread_cpu_perf_counters().dump().items()
             if r != "process"}
    assert (_span_cpu("t37.off"), roles) == \
        (before[0], {r: before[1][r] for r in roles})
    with trace_span("t37.off", cpu=True):
        pass
    assert len(reads) == 2


def test_every_context_registers_the_two_collections_with_help_text():
    from ceph_tpu.mgr import prometheus
    with trace_span("t37.scraped", cpu=True):
        pass
    default_tracer().flush()
    cct = Context()
    dump = cct.perf.perf_dump()
    assert set(dump["thread_cpu"]) == set(THREAD_ROLES) | {"process"}
    assert "t37.scraped.cpu_us" in dump["span_cpu"]
    for pc in (span_cpu_perf_counters(), thread_cpu_perf_counters()):
        assert all(m.description for m in pc._metrics.values())
    # a counter name with dots in it exports as any other
    scrape = prometheus.render(cct)
    assert 'ceph_tpu_t37_scraped_cpu_us{collection="span_cpu"}' in scrape
    assert 'ceph_tpu_process{collection="thread_cpu"}' in scrape


def test_a_thread_charges_its_own_cpu_once():
    pc = thread_cpu_perf_counters()
    every = tracer_mod.CHARGE_EVERY_S
    assert every == 0.1

    def body(got):
        before = pc.get("finisher")
        _burn(MS30)
        t0 = time.perf_counter()
        tracer_mod.charge_thread_cpu("finisher")
        first = pc.get("finisher") - before
        _burn(every / 20)
        tracer_mod.charge_thread_cpu("finisher")    # too soon: no read
        soon = time.perf_counter() - t0 < every
        second = pc.get("finisher") - before - first
        while time.perf_counter() - t0 < every:
            _burn(every / 20)
        tracer_mod.charge_thread_cpu("finisher")    # and nothing is lost
        got.update(first=first, second=second, soon=soon,
                   third=pc.get("finisher") - before - first)
    # a machine busy enough to stretch 5 ms of CPU over 100 ms of wall
    # time fails a try, never the code: three are allowed
    for _attempt in range(3):
        got = {}
        t = threading.Thread(target=body, args=(got,))
        t.start()
        t.join()
        if got["soon"]:
            break
    assert got["soon"]
    # the thread's clock runs from its start: a little more than it burnt
    assert 30_000 <= got["first"] <= 30_000 + 10_000
    assert got["second"] == 0
    assert got["third"] >= every / 20 * 1e6


def test_after_served_puts_the_roles_are_charged_and_process_holds_them(
        tmp_path):
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.net import ClusterServer, TcpRados
    pc = thread_cpu_perf_counters()
    before = pc.dump()
    c = MiniCluster(n_osds=12, osds_per_host=1, chunk_size=512,
                    data_dir=tmp_path, store_backend="bluestore")
    serving = c.enable_serving(start=True)
    server = ClusterServer(c)
    server.start()
    r = TcpRados("127.0.0.1", server.port, tmp_path / "client.admin.keyring")
    try:
        r.mkpool("p", profile={"plugin": "jax_rs", "k": "8", "m": "4",
                               "technique": "cauchy", "device": "jax"},
                 pg_num=4)
        data = np.random.default_rng(37).integers(
            0, 256, 2 * 8 * 512, dtype=np.uint8).tobytes()
        for i in range(64):
            r.put("p", f"o{i}", data)
        # ``process`` is as of a reactor's last charge: one more put
        # after the charges fall due brings it up to this moment
        time.sleep(1.5 * tracer_mod.CHARGE_EVERY_S)
        r.put("p", "o64", data)
        dump = c.cct.perf.perf_dump()["thread_cpu"]
        beside = time.process_time() * 1e6
    finally:
        r.close()
        server.stop()
        serving.stop()
        c.shutdown()
    rose = {k: dump[k] - before[k] for k in dump}
    for role in THREAD_ROLES:
        assert rose[role] > 0, role
    # each charge is a thread's own clock, so together they stay under
    # the process's, which counts JAX's and the runtime's threads too
    assert sum(rose[r] for r in THREAD_ROLES) <= rose["process"]
    assert sum(dump[r] for r in THREAD_ROLES) <= dump["process"]
    assert dump["process"] == pytest.approx(beside, rel=0.05)


def test_trace_report_prints_cpu_and_off_cpu_beside_the_wall_time(tmp_path):
    import importlib.util
    import json
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "trace_report_t37",
        Path(__file__).resolve().parents[1] / "tools" / "trace_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tr = Tracer()
    t0 = time.perf_counter()
    # a CPU clock that ticks reads one span more than its wall time:
    # the name's off-CPU time is the sums' difference, not the spans'
    tr.observe("held", t0, t0 + 0.010, cpu_s=0.003)
    tr.observe("held", t0 + 0.020, t0 + 0.030, cpu_s=0.011)
    tr.observe("plain", t0 + 0.040, t0 + 0.050)
    f = tmp_path / "dump.json"
    f.write_text(json.dumps(tr.dump()))
    agg = mod.self_times(mod.load_events(str(f)))
    assert agg["held"]["cpu_us"] == pytest.approx(14_000)
    assert agg["held"]["offcpu_us"] == pytest.approx(6_000)
    assert "cpu_us" not in agg["plain"]
    header, *rows = mod.render_table(agg).splitlines()
    assert header.endswith("cpu ms  off-cpu ms")
    held = next(r for r in rows if r.startswith("held"))
    assert held.split()[-2:] == ["14.000", "6.000"]
    assert next(r for r in rows if r.startswith("plain")).split()[-1] \
        == "10.000"                                  # its p99, no more
    spans = {s["name"]: s for s in
             json.loads(mod.render_json(agg))["spans"]}
    assert (spans["held"]["cpu_ms"], spans["held"]["offcpu_ms"]) == \
        (pytest.approx(14.0), pytest.approx(6.0))
    assert "cpu_ms" not in spans["plain"]
    # a dump without the field renders the table it always did
    del agg["held"]
    assert "cpu ms" not in mod.render_table(agg)
