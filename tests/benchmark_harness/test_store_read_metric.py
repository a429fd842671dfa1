"""``store_read_bytes_per_byte``, a file and an entry: a traced REHEARSAL
of ``rbd_randwrite_4k_qd16`` (CPU, tiny sizes, the look for a chip
skipped) prints it as a number from the two counters each
``bluestore.*`` collection keeps beside ``transactions``; a program
without the counters (one from before them) leaves it out of its line
and is still `correct`.

Counts only: nothing here is a rate of the device."""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import rados_overwrite  # noqa: E402
from benchmark.lib import readers  # noqa: E402

CELL = "rbd_randwrite_4k_qd16"
NAME = "store_read_bytes_per_byte"
SEED = 2147484229
COUNTERS = ("read_bytes", "read_block_bytes")


def rehearse():
    return bench_run.run_cell(CELL, SEED, 2.0, True, rehearsal=True)


@pytest.fixture(scope="module")
def spec():
    return json.loads((REPO / "benchmark" / "metrics" / f"{NAME}.json")
                      .read_text())


def test_the_entry_and_the_file_are_what_the_metric_names(spec):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1
    assert entries[0] == {
        "name": NAME, "unit": "B/B", "better": "lower",
        "source": "program_counter", "layer": "store",
        "moves": "client_bw", "workloads": [CELL]}
    # appended: the last entry of its list
    assert bench["per_layer"][-1]["name"] == NAME
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entries[0][key], key
    assert "workloads" not in spec and spec["what"]
    assert spec["reader"] == "counter_ratio"
    assert spec["params"] == {"num": ["bluestore.*:read_block_bytes"],
                              "den": ["bluestore.*:read_bytes"]}


def test_a_traced_rehearsal_reads_the_stores_block_reads():
    """A store reads the csum blocks (min_alloc, 4 KiB) its reads cover,
    never a whole blob: the rehearsal's 512 B chunks read within one
    block, at most 8 for 1, where the cell's 4 KiB chunks read 1 for 1."""
    res = rehearse()
    assert res["correct"] is True, res["checks"]
    value = res["metrics"][NAME]
    assert value["unit"] == "B/B"
    assert 1.0 <= value["value"] <= 4096 / 512


def test_a_program_without_the_counters_leaves_it_out_and_is_still_correct(
        monkeypatch):
    """What a program from before the counters does with these benchmark
    files laid over it: its ``perf dump`` has neither counter."""
    real = rados_overwrite.Driver.snapshot

    def snapshot(self):
        snap = real(self)
        for vals in snap["counters"].values():
            for key in COUNTERS:
                vals.pop(key, None)
        return snap
    monkeypatch.setattr(rados_overwrite.Driver, "snapshot", snapshot)
    res = rehearse()
    assert res["correct"] is True, res["checks"]
    assert NAME not in res["metrics"]
    assert "rmw_read_ms" in res["metrics"]           # the rest is there


def test_the_reader_reads_a_ratio_and_nothing_as_nothing(spec):
    ctx = {"counted_ops": 10, "counters": {
        "bluestore.osd.0": {"read_bytes": 4096, "read_block_bytes": 4096},
        "bluestore.osd.1": {"read_bytes": 4096, "read_block_bytes": 12288}}}
    assert readers.read_metric(spec, ctx) == pytest.approx(2.0)
    bare = {"counted_ops": 10,
            "counters": {"bluestore.osd.0": {"transactions": 3}}}
    assert readers.read_metric(spec, bare) is None
