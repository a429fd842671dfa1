"""``rados_degraded_read_4m_qd16``: a REHEARSAL at tiny sizes on the CPU
(the same code the chip runs, the look for a chip skipped), its control,
and the faults planted under its timed path.  `correct` has to come out
true for the program as it is and false for each of the others.

Counts and correctness only: nothing here is a rate of the device."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import rados_degraded  # noqa: E402

CELL = "rados_degraded_read_4m_qd16"
READ = "rados_seqread_4m_qd16"
SEED = 2147483777
# what the deployment adds to the read cell's line, device numbers apart
ADDED = ["get_decode_ms", "pg_subread_ms", "pipeline_dispatch_ms",
         "reconstructed_chunks_per_get", "queue_wait_ms", "pipeline_pack_ms",
         "pipeline_device_wait_ms", "pipeline_fetch_ms", "ops_per_batch"]


def rehearse(trace=False, control=None, seed=SEED):
    return bench_run.run_cell(CELL, seed, 2.0 if trace else 1.0, trace,
                              rehearsal=True, control=control)


def failed_checks(result):
    return sorted(n for n, c in result["checks"].items() if not c["ok"])


@pytest.fixture(scope="module")
def runs():
    """One untraced and one traced rehearsal on one seed, and what each
    run's driver marked down and named (kept as it closes)."""
    seen = []
    real = rados_degraded.Driver.close

    def close(self):
        seen.append((list(self.down), list(self.object_set)))
        real(self)
    rados_degraded.Driver.close = close
    try:
        return {"untraced": rehearse(), "traced": rehearse(trace=True),
                "seen": seen}
    finally:
        rados_degraded.Driver.close = real


@pytest.mark.parametrize("kind", ["untraced", "traced"])
def test_rehearsal_is_correct_and_compiles_nothing_in_the_window(kind, runs):
    res = runs[kind]
    assert res["correct"] is True, failed_checks(res)
    assert res["failed"] == 0 and res["attempted"] > 0
    checks = {n: c["value"] for n, c in res["checks"].items()}
    assert checks["compiles_in_window"] == 0
    objects = checks["shards_compared"] // 12
    assert objects > 0 and checks["shards_unreachable"] == 2 * objects
    assert checks["shards_reachable"] == 10 * objects
    assert checks["shards_missing"] == checks["reads_wrong"] == 0
    assert checks["gets_decoded_on_device"] * 2 >= res["attempted"]


def test_untraced_rehearsal_reports_the_end_to_end_metrics(runs):
    assert set(runs["untraced"]["metrics"]) == {"client_bw", "op_lat_p95",
                                                "setup_s"}


def test_traced_rehearsal_reports_the_read_cells_metrics_and_its_own(runs):
    got = runs["traced"]["metrics"]
    host_side = {m["name"] for m in
                 bench_run.manifest.load_cell(READ)["per_layer"]
                 if m["source"] != "device_trace"}
    assert set(got) == host_side | set(ADDED)
    for name in ADDED + ["pipeline_unpack_ms", "device_dispatches_per_op"]:
        assert got[name]["value"] > 0, name
    # a CPU capture has no device plane: no device number is invented
    assert "busy_s" not in runs["traced"]["device"]


def test_the_same_seed_marks_the_same_osds_down_and_names_the_same_objects(
        runs):
    (down_a, names_a), (down_b, names_b) = runs["seen"]
    assert down_a == down_b and len(down_a) == 2
    assert names_a == names_b and len(names_a) == 16
    from benchmark.drivers import rados_clients
    traffic = bench_run.merged(bench_run.manifest.load_cell(CELL)["traffic"],
                               True)
    prefixes = {s: rados_clients.make_payloads(s, traffic)[1]
                for s in (SEED, SEED + 1)}
    assert names_a[0][0].startswith(prefixes[SEED] + ".")
    assert prefixes[SEED] != prefixes[SEED + 1]


def test_the_control_with_every_osd_up_decodes_nothing_and_is_not_correct():
    res = rehearse(control="osds_up")
    assert res["correct"] is False
    bad = failed_checks(res)
    assert "gets_decoded_on_device" in bad
    assert res["checks"]["gets_decoded_on_device"]["value"] == 0
    assert res["checks"]["reads_wrong"]["ok"]


# -- faults planted under the timed path -------------------------------------------

def _in_the_window(monkeypatch, before):
    """Run ``before(driver)`` as the window opens (set-up's warm passes
    are answered truly); returns a flag that is set while it is open."""
    state = {"open": False}
    real = rados_degraded.Driver.window

    def window(self, seconds, schedule=()):
        before(self)
        state["open"] = True
        try:
            return real(self, seconds, schedule)
        finally:
            state["open"] = False
    monkeypatch.setattr(rados_degraded.Driver, "window", window)
    return state


def test_fault_a_recovered_row_altered_where_the_served_path_produces_it(
        monkeypatch):
    import numpy as np
    from ceph_tpu.ops.pipeline import CodecPipeline
    state = _in_the_window(monkeypatch, lambda driver: None)
    real = CodecPipeline.dispatch_decode

    def dispatch_decode(self, codec, stack, erasures, available):
        out = real(self, codec, stack, erasures, available)
        if state["open"]:
            out = np.array(out)       # on the host: the fault compiles nothing
            out[0, 0] ^= 1
        return out
    monkeypatch.setattr(CodecPipeline, "dispatch_decode", dispatch_decode)
    res = rehearse()
    assert res["correct"] is False
    assert failed_checks(res) == ["reads_wrong"]


def test_fault_a_down_osds_store_written_after_the_down_mark(monkeypatch):
    from ceph_tpu.backend.memstore import GObject, Transaction
    from ceph_tpu.backend.pg_backend import shard_store

    def write(driver):
        g = driver._pgs()[0]
        osd = driver.down[0]
        with driver.server.lock:
            shard_store(g.bus, osd).queue_transaction(
                Transaction().touch(GObject("written.while.down", osd)))
    _in_the_window(monkeypatch, write)
    res = rehearse()
    assert res["correct"] is False
    assert failed_checks(res) == ["down_stores_written"]
