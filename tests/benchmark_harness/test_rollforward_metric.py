"""``rollforward_kicks_per_put``, a file and an entry: a traced REHEARSAL
of each write cell it lists (CPU, tiny sizes, the look for a chip
skipped) prints it as a number, 0 included, from the counter the program
keeps beside each ``ec_backend.*`` collection's ``writes``; a program
without the counter (the parent commit) leaves it out of its line and
is still `correct`.

Counts only: nothing here is a rate of the device."""
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

CELL = "rados_write_4m_qd16"
NAME = "rollforward_kicks_per_put"
SEED = 2147483801
COUNTERS = ("rollforward_kicks", "rollforward_deferred")


def rehearse():
    return bench_run.run_cell(CELL, SEED, 2.0, True, rehearsal=True)


@pytest.fixture(scope="module")
def spec():
    return json.loads((REPO / "benchmark" / "metrics" / f"{NAME}.json")
                      .read_text())


def check_the_entry_and_its_file(bench, repo):
    """The entry's fields and its file's; the write cell among the cells
    it lists, and every cell it lists one whose traffic puts (a kick
    follows a sub-write).  The list itself is not pinned: a later write
    cell appends its name."""
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1
    entry = entries[0]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "1/op", "better": "lower",
        "source": "program_counter", "layer": "PG backend",
        "moves": "client_bw"}
    assert CELL in entry["workloads"]
    for cell in entry["workloads"]:
        assert manifest.load_cell(cell, repo)["traffic"]["op"] == "put", cell
    spec = json.loads((repo / "benchmark" / "metrics" / f"{NAME}.json")
                      .read_text())
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert "workloads" not in spec and spec["what"]
    assert spec["reader"] == "counter_ratio"
    assert spec["params"] == {"num": ["ec_backend.*:rollforward_kicks"],
                              "den": "client_ops"}
    listed = {c: [m["name"] for m in manifest.load_cell(c, repo)["per_layer"]]
              for c in (w["name"] for w in bench["workloads"])}
    assert {c for c, names in listed.items() if NAME in names} == \
        set(entry["workloads"])


def check_every_listed_cell_prints_it(bench, repo):
    """A traced rehearsal of each cell the entry lists prints the metric
    as a number: between no kick at all and k + m a put."""
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    for cell in entry["workloads"]:
        res = rehearsals.traced(cell, repo)
        assert res["correct"] is True, (cell, res["checks"])
        assert NAME in res["metrics"], (cell, sorted(res["metrics"]))
        assert 0 <= res["metrics"][NAME]["value"] <= 12, cell


# checks of (bench, repo): tests/benchmark_harness/test_manifest.py runs
# them again on a copy of the benchmark that a later PR's cells grew
INVARIANTS = [check_the_entry_and_its_file, check_every_listed_cell_prints_it]


def test_the_entry_and_the_file_are_what_the_issue_names(spec):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    check_the_entry_and_its_file(bench, REPO)
    assert spec["name"] == NAME


def test_every_cell_the_entry_lists_prints_it_as_a_number():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    check_every_listed_cell_prints_it(bench, REPO)


def test_a_traced_rehearsal_prints_it_as_a_number():
    seen = []
    real = rados.Driver.snapshot

    def snapshot(self):
        snap = real(self)
        seen.append({k: sum(v.get(k, 0) for n, v in snap["counters"].items()
                            if n.startswith("ec_backend."))
                     for k in COUNTERS + ("writes",)})
        return snap
    rados.Driver.snapshot = snapshot
    try:
        res = rehearse()
    finally:
        rados.Driver.snapshot = real
    assert res["correct"] is True, res["checks"]
    value = res["metrics"][NAME]
    assert value["unit"] == "1/op"
    # between no kick at all and k + m a put; four clients back to back
    # defer most drains
    assert 0 <= value["value"] <= 12
    # the stretch's drains are accounted for: each kicked (12 messages)
    # or deferred; a deferred one settled at an idle moment kicks late
    first, last = seen[0], seen[-1]
    writes, kicks, deferred = (last[k] - first[k] for k in
                               ("writes",) + COUNTERS)
    assert writes > 0 and kicks % 12 == 0
    assert kicks // 12 + deferred >= writes
    assert deferred > 0


def test_a_program_without_the_counter_leaves_it_out_and_is_still_correct():
    """What the parent commit does with this PR's benchmark files laid
    over it: its ``perf dump`` has neither counter."""
    real = rados.Driver.snapshot

    def snapshot(self):
        snap = real(self)
        for vals in snap["counters"].values():
            for key in COUNTERS:
                vals.pop(key, None)
        return snap
    rados.Driver.snapshot = snapshot
    try:
        res = rehearse()
    finally:
        rados.Driver.snapshot = real
    assert res["correct"] is True, res["checks"]
    assert NAME not in res["metrics"]
    assert "pg_subwrite_ms" in res["metrics"]        # the rest is there


def test_the_reader_reads_zero_as_zero_and_nothing_as_nothing(spec):
    ctx = {"counted_ops": 10, "counters": {
        "ec_backend.c1.pg1.0": {"writes": 6, "rollforward_kicks": 0},
        "ec_backend.c1.pg1.1": {"writes": 4, "rollforward_kicks": 0}}}
    assert readers.read_metric(spec, ctx) == 0.0
    ctx["counters"]["ec_backend.c1.pg1.1"]["rollforward_kicks"] = 24
    assert readers.read_metric(spec, ctx) == pytest.approx(2.4)
    bare = {"counted_ops": 10,
            "counters": {"ec_backend.c1.pg1.0": {"writes": 10}}}
    assert readers.read_metric(spec, bare) is None
