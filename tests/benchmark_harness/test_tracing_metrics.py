"""The per-layer metrics ISSUE 25 added, and the two ISSUE 32 added as a
file and an entry each: each is a file that loads by name and matches
its ``BENCHMARK.json`` entry, and a traced REHEARSAL of each rados cell
(CPU, tiny sizes, the look for a chip skipped) reports it from a span or
counter recorded inside the program.

``BENCHMARK.json``'s entry alone says which cells report a metric (the
file has no ``workloads`` key), and a later PR may append a cell to it
or add a metric: nothing here pins the list's length, a metric's place
in it, or an entry's ``workloads`` beyond the cells named below.

Counts and presence only: nothing here is a rate of the device."""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import manifest, readers  # noqa: E402

WRITE, READ = "rados_write_4m_qd16", "rados_seqread_4m_qd16"
TRANSPORT = ["frame_rx_ms", "dispatch_queue_wait_ms", "lock_wait_ms",
             "reply_send_ms", "reply_drain_ms"]
# the spans the rehearsal's 32 KiB objects reach on the write cell: all
# of them (the shards go to the device whatever their size under
# device=jax, so ec.hinfo_crc is stamped too)
WRITE_SPANS = ["ec_encode_ms", "hinfo_crc_ms", "hinfo_crc_wait_ms",
               "pg_txn_self_ms", "pg_subwrite_ms", "store_commit_ms",
               "queue_wait_ms", "pipeline_pack_ms",
               "pipeline_device_wait_ms", "pipeline_fetch_ms"]
COUNTER = "device_dispatches_per_op"
# read from the device's plane, which a CPU capture has not: left out of
# a rehearsal's line, never reported as 0
DEVICE = "put_kernel_ms"
CELLS_OF = {**{m: [WRITE, READ] for m in TRANSPORT + [COUNTER]},
            **{m: [WRITE] for m in WRITE_SPANS + [DEVICE]}}
# ISSUE 32's two: PR 29's span of a put's work before the lock, and the
# host-only relayout that is all the pipeline does for a clean read
LATER = {"rpc_prepare_ms": [WRITE], "pipeline_unpack_ms": [READ]}
ALL = {**CELLS_OF, **LATER}
SOURCE_OF = {**{m: "program_span"
                for m in TRANSPORT + WRITE_SPANS + list(LATER)},
             COUNTER: "program_counter", DEVICE: "device_trace"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def traced():
    """One traced rehearsal of each rados cell, shared by the cases."""
    return {cell: bench_run.run_cell(cell, 2147483693, 2.0, True,
                                     rehearsal=True)
            for cell in (WRITE, READ)}


def test_seventeen_metrics_are_each_listed_exactly_once(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert len(CELLS_OF) == 17
    for name in ALL:
        assert names.count(name) == 1, name


@pytest.mark.parametrize("name", sorted(ALL))
def test_the_metric_file_loads_by_name_and_matches_its_entry(name, bench):
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert set(entry["workloads"]) >= set(ALL[name])
    assert entry["source"] == SOURCE_OF[name]
    assert entry["moves"] == "client_bw"
    for cell in ALL[name]:
        spec = next(m for m in manifest.load_cell(cell)["per_layer"]
                    if m["name"] == name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "workloads" not in spec
        assert spec["reader"] in readers.READERS
        assert spec["what"]


@pytest.mark.parametrize("name,cell", [
    (name, cell)
    for name in TRANSPORT + [COUNTER] + WRITE_SPANS + list(LATER)
    for cell in ALL[name]])
def test_a_traced_rehearsal_reports_the_metric(name, cell, traced):
    res = traced[cell]
    assert res["correct"] is True
    assert name in res["metrics"], sorted(res["metrics"])
    assert res["metrics"][name]["value"] >= 0


def test_device_dispatches_per_op_parts_a_put_from_a_clean_get(traced):
    assert traced[READ]["metrics"][COUNTER]["value"] == 0
    assert traced[WRITE]["metrics"][COUNTER]["value"] == \
        pytest.approx(1.0, abs=0.1)
    # the counter it stands beside cannot tell them apart
    assert traced[READ]["metrics"]["dispatches_per_op"]["value"] > 0.9


def test_a_cpu_rehearsal_leaves_the_device_metric_out(traced):
    assert DEVICE not in traced[WRITE]["metrics"]


def test_a_program_without_the_spans_reads_nothing_and_does_not_raise():
    """The parent commit records none of this PR's spans or its counter:
    each reader then returns None (the line leaves the metric out) or,
    where the spans it subtracts are all that is missing, a number."""
    ctx = {"spans": {"pg.generate_transactions": (2.0, 10),
                     "ec.encode": (0.5, 10),
                     "osd.ECSubWrite": (1.0, 120)},
           "rpc": {}, "counted_ops": 10, "ops": [], "device": None,
           "traced_ops": 0, "device_kind": "cpu", "config": {},
           "counters": {"serving.c1.pipeline": {"submitted": 10}}}
    got = {}
    for name in ALL:
        spec = json.loads((REPO / "benchmark" / "metrics"
                           / f"{name}.json").read_text())
        got[name] = readers.read_metric(spec, ctx)
    present = {n for n, v in got.items() if v is not None}
    assert present == {"ec_encode_ms", "pg_txn_self_ms", "pg_subwrite_ms"}
    assert got["pg_txn_self_ms"] == pytest.approx(150.0)
