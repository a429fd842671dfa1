"""``store_commit_overlap_share`` and ``commit_wait_ms``, a file and an
entry each: what the group commit of the sub-writes' stores leaves to be
read.  The share of store transactions whose writes were queued for the
bus's group commit (``bluestore.*:commits_queued`` over
``bluestore.*:transactions``), and the lock holder's wait for that commit
a put (``bus.commit_wait``, one span a settle of the bus).  A traced
REHEARSAL of each write cell (CPU, tiny sizes, the look for a chip
skipped) prints each as a number; a program without the counter or the
span (the parent of the group commit) leaves that metric out of its
line and is still `correct`.

Counts and bookkeeping only: nothing here is a time of the device."""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import rehearsals  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import rados  # noqa: E402
from benchmark.lib import manifest, readers  # noqa: E402

WRITES = ["rados_write_4m_qd16", "rados_write_64k_qd64"]
OVERLAP, WAIT = "store_commit_overlap_share", "commit_wait_ms"
ENTRIES = {
    OVERLAP: {"unit": "1/1", "better": "higher", "source": "program_counter",
              "layer": "store", "moves": "client_bw"},
    WAIT: {"unit": "ms", "better": "lower", "source": "program_span",
           "layer": "PG backend", "moves": "client_bw"}}
READS = {OVERLAP: ("counter_ratio",
                   {"num": ["bluestore.*:commits_queued"],
                    "den": ["bluestore.*:transactions"]}),
         WAIT: ("span_mean_ms", {"spans": ["span:bus.commit_wait"],
                                 "per": "op"})}
SEED = 2147484041


def _spec(name):
    return json.loads((REPO / "benchmark" / "metrics" / f"{name}.json")
                      .read_text())


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [OVERLAP, WAIT])
def test_the_entry_and_the_file_name_the_counter_and_the_span(name, bench):
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    assert {k: v for k, v in entry.items()
            if k not in ("name", "workloads")} == ENTRIES[name]
    # both write cells, and only cells whose traffic puts: a get neither
    # queues a commit nor settles a held reply
    assert set(WRITES) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert manifest.load_cell(cell)["traffic"]["op"] == "put", cell
    spec = _spec(name)
    assert spec["name"] == name and spec["what"] and "workloads" not in spec
    for key, value in ENTRIES[name].items():
        assert spec[key] == value, key
    assert (spec["reader"], spec["params"]) == READS[name]
    for cell in WRITES:
        assert name in [m["name"] for m in manifest.load_cell(cell)
                        ["per_layer"]]


@pytest.mark.parametrize("cell", WRITES)
def test_a_traced_rehearsal_prints_both_as_numbers(cell):
    res = rehearsals.traced(cell)
    assert res["correct"] is True, res["checks"]
    got = {n: m["value"] for n, m in res["metrics"].items()}
    assert res["metrics"][OVERLAP]["unit"] == "1/1"
    # every sub-write's commit is queued; a kick's, where one is sent,
    # is not
    assert 0 < got[OVERLAP] <= 1
    # a put's holder settles the bus at least once
    assert got[WAIT] > 0


def test_each_reader_reads_zero_as_zero_and_absence_as_absence():
    ctx = {"counted_ops": 10, "spans": {"bus.commit_wait": (0.0, 10)},
           "rpc": {}, "counters": {
               "bluestore.c1.osd0": {"transactions": 60, "commits_queued": 0},
               "bluestore.c1.osd1": {"transactions": 60,
                                     "commits_queued": 0}}}
    assert readers.read_metric(_spec(OVERLAP), ctx) == 0.0
    assert readers.read_metric(_spec(WAIT), ctx) == 0.0
    ctx["counters"]["bluestore.c1.osd1"]["commits_queued"] = 30
    ctx["spans"]["bus.commit_wait"] = (0.025, 10)
    assert readers.read_metric(_spec(OVERLAP), ctx) == pytest.approx(0.25)
    assert readers.read_metric(_spec(WAIT), ctx) == pytest.approx(2.5)
    bare = {"counted_ops": 10, "spans": {"rpc.put": (0.1, 10)}, "rpc": {},
            "counters": {"bluestore.c1.osd0": {"transactions": 120}}}
    assert readers.read_metric(_spec(OVERLAP), bare) is None
    assert readers.read_metric(_spec(WAIT), bare) is None


@pytest.mark.parametrize("missing", [OVERLAP, WAIT])
def test_a_program_without_the_counter_or_the_span_leaves_it_out(
        missing, monkeypatch):
    """What a tree without the group commit does with these files laid
    over it: its ``perf dump`` has no ``commits_queued``, or its tracer
    no ``bus.commit_wait``."""
    real = rados.Driver.snapshot

    def as_the_parent(self):
        snap = real(self)
        if missing == OVERLAP:
            for vals in snap["counters"].values():
                vals.pop("commits_queued", None)
        else:
            snap["spans"].pop("bus.commit_wait", None)
        return snap
    monkeypatch.setattr(rados.Driver, "snapshot", as_the_parent)
    res = bench_run.run_cell(WRITES[1], SEED, 2.0, True, rehearsal=True)
    assert res["correct"] is True, res["checks"]
    assert missing not in res["metrics"]
    kept = ({OVERLAP, WAIT} - {missing}).pop()
    for name in (kept, "store_commit_ms", "store_txns_per_put"):
        assert name in res["metrics"], name
