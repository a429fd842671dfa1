"""The twelve per-layer metrics ISSUE 37 added, all read from counters
the program keeps beside its wall-clock spans: the hold's thread CPU
time and the rest of its wall time (``span_cpu``), the store commit's
phases and its CPU time and the checkpoints' time (six adders on each
``bluestore.*``), each service thread's CPU by role (``thread_cpu``).
(The issue listed fourteen.  The I/O calls' own off-CPU time wanted
four more reads of a CPU clock that is dear on the chip's host inside
the stretch it measured, and a commit's off-CPU time is its phases
less its CPU time: a counter of its own, kept from falling while the
clock ticks at 100 Hz, read a third long.  PERF.md, PR 37.)
Each is a file that loads by name and matches its ``BENCHMARK.json``
entry, a traced REHEARSAL of each cell it lists (CPU, tiny sizes, the
look for a chip skipped) reports it as a number, and a program without
the counters leaves all of them out and is still ``correct``.

``BENCHMARK.json``'s entry alone says which cells report a metric; a
later PR may append a cell: nothing here pins a list beyond the cells
named below.

Counts and bookkeeping only: nothing here is a time of the device."""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import rados  # noqa: E402
from benchmark.lib import manifest, readers  # noqa: E402

WRITE, SMALL = "rados_write_4m_qd16", "rados_write_64k_qd64"
READ, DEGRADED = "rados_seqread_4m_qd16", "rados_degraded_read_4m_qd16"
WRITES, RADOS = [WRITE, SMALL], [WRITE, READ, DEGRADED, SMALL]
SEED = 2147483937
HOLD = ["hold_cpu_us", "hold_offcpu_us"]
STORE = ["store_commit_cpu_us", "store_stage_us", "store_record_us",
         "store_io_us", "store_checkpoint_us"]
THREADS = ["cpu_us_per_op." + role for role in
           ("reactor", "dispatch", "coalescer", "finisher", "process")]
CELLS_OF = {**{m: RADOS for m in HOLD + THREADS},
            **{m: WRITES for m in STORE}}
LAYER_OF = {**{m: "transport" for m in HOLD + THREADS[:2]},
            **{m: "store" for m in STORE},
            **{m: "serving coalescer" for m in THREADS[2:4]},
            "cpu_us_per_op.process": "process"}
# the collections and adders the parent commit has not
COLLECTIONS = ("span_cpu", "thread_cpu")
ADDERS = ("stage_us", "record_us", "block_io_us", "wal_io_us",
          "commit_cpu_us", "checkpoint_us")


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def traced():
    """One traced rehearsal of each rados cell, with what its readers
    were handed (the spans and counters of the counted stretch)."""
    seen, out = [], {}
    real = readers.read_metric

    def spy(spec, ctx):
        seen.append(ctx)
        return real(spec, ctx)
    readers.read_metric = spy
    try:
        for cell in RADOS:
            res = bench_run.run_cell(cell, SEED, 2.0, True, rehearsal=True)
            out[cell] = (res, seen[-1])
    finally:
        readers.read_metric = real
    return out


def test_the_twelve_are_each_listed_exactly_once(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert len(CELLS_OF) == 12
    for name in CELLS_OF:
        assert names.count(name) == 1, name
    # appended: nothing that was there moved
    assert names[-12:] == HOLD + STORE + THREADS


@pytest.mark.parametrize("name", sorted(CELLS_OF))
def test_the_metric_file_loads_by_name_and_matches_its_entry(name, bench):
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert set(entry["workloads"]) >= set(CELLS_OF[name])
    assert "ec_resident_b256" not in entry["workloads"]
    assert (entry["source"], entry["unit"], entry["better"],
            entry["moves"], entry["layer"]) == \
        ("program_counter", "us/op", "lower", "client_bw", LAYER_OF[name])
    for cell in CELLS_OF[name]:
        spec = next(m for m in manifest.load_cell(cell)["per_layer"]
                    if m["name"] == name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "workloads" not in spec
        assert spec["reader"] == "counter_ratio"
        assert spec["params"]["den"] == "client_ops" and spec["what"]


@pytest.mark.parametrize("name,cell", [(name, cell)
                                       for name in sorted(CELLS_OF)
                                       for cell in CELLS_OF[name]])
def test_a_traced_rehearsal_reports_the_metric_as_a_number(name, cell,
                                                           traced):
    res, _ctx = traced[cell]
    assert res["correct"] is True
    assert name in res["metrics"], sorted(res["metrics"])
    assert res["metrics"][name]["value"] >= 0


@pytest.mark.parametrize("cell", RADOS)
def test_the_holds_two_sides_are_its_wall_time(cell, traced):
    res, ctx = traced[cell]
    got = {n: m["value"] for n, m in res["metrics"].items()}
    held = sum(ctx["spans"].get(s, (0.0, 0))[1]
               for s in ("rpc.put", "rpc.get"))
    assert held > 0 and ctx["counted_ops"] > 0
    # rpc_work_ms is a mean over the holds counted, the two new ones are
    # sums over the client ops acked meanwhile: a hold that ended as the
    # stretch did may be counted with its ack still on the way
    assert (got["hold_cpu_us"] + got["hold_offcpu_us"]) * ctx["counted_ops"] \
        == pytest.approx(1e3 * got["rpc_work_ms"] * held, rel=0.02)
    assert got["hold_cpu_us"] > 0
    assert got["cpu_us_per_op.dispatch"] > 0
    assert got["cpu_us_per_op.reactor"] > 0
    roles = sum(got[n] for n in THREADS[:4])
    assert roles <= got["cpu_us_per_op.process"]


@pytest.mark.parametrize("cell", WRITES)
def test_the_commits_phases_tile_it_and_nest_in_the_hold(cell, traced):
    res, _ctx = traced[cell]
    got = {n: m["value"] for n, m in res["metrics"].items()}
    phases = got["store_stage_us"] + got["store_record_us"] \
        + got["store_io_us"]
    # the phases and the CPU clock are of the same transactions, one
    # in seventeen a store, booked seventeen-fold: an estimate of them
    # all that a second's rehearsal (some hundred timed) holds loosely
    # and 40 s on the chip to a few per cent (the CPU clock is read
    # outside the wall clock's reads: where the thread ran throughout
    # it reads the CPU of a read more)
    assert 0 < got["store_commit_cpu_us"] <= 1.2 * phases
    assert 0.3 * phases <= 1e3 * got["store_commit_ms"] <= 3.0 * phases
    # a store checkpoints at its 512th record: not in a rehearsal
    assert got["store_checkpoint_us"] == 0


def test_a_program_without_the_counters_leaves_them_out_and_is_correct(
        monkeypatch):
    """The parent commit keeps neither collection nor the six adders:
    its ``perf dump`` lacks them, each reader returns None and the line
    leaves the twelve out, with everything else as it was."""
    real = rados.Driver.snapshot

    def as_the_parent(self):
        snap = real(self)
        snap["counters"] = {
            name: {k: v for k, v in vals.items() if k not in ADDERS}
            for name, vals in snap["counters"].items()
            if name not in COLLECTIONS}
        return snap
    monkeypatch.setattr(rados.Driver, "snapshot", as_the_parent)
    res = bench_run.run_cell(SMALL, SEED + 1, 2.0, True, rehearsal=True)
    assert res["correct"] is True
    assert not set(CELLS_OF) & set(res["metrics"])
    for name in ("rpc_work_ms", "store_commit_ms", "wal_bytes_per_put"):
        assert name in res["metrics"]


def test_each_reader_finds_nothing_in_a_parents_counters():
    ctx = {"spans": {"rpc.put": (1.0, 100), "store.commit": (0.5, 1200)},
           "rpc": {}, "counted_ops": 100, "ops": [], "device": None,
           "traced_ops": 0, "device_kind": "cpu", "config": {},
           "counters": {"bluestore.c1.osd0": {"transactions": 100,
                                              "wal_bytes": 9000},
                        "jit": {"compilations": 0}}}
    for name in CELLS_OF:
        spec = json.loads((REPO / "benchmark" / "metrics"
                           / f"{name}.json").read_text())
        assert readers.read_metric(spec, ctx) is None, name
