"""``rados_write_64k_qd64``: a REHEARSAL at tiny sizes on the CPU (the
same code the chip runs, the look for a chip skipped), its control, and
the write cell's two faults planted under its timed path, on objects of
two stripes.  `correct` has to come out true for the program as it is
and false for each of the others.

Counts and correctness only: nothing here is a rate of the device."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import rehearsals  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import rados, rados_clients  # noqa: E402
from benchmark.lib import readers  # noqa: E402

CELL = "rados_write_64k_qd64"
WRITE = "rados_write_4m_qd16"
SEED = 2147483869
# what the stores' counters add to the write path's metrics; this cell
# lists them, the 4 MiB write cell does not
ADDED = ["store_txns_per_put", "store_bytes_per_put_byte",
         "wal_bytes_per_put"]


def rehearse(trace=False, control=None, seed=SEED):
    return bench_run.run_cell(CELL, seed, 2.0 if trace else 1.0, trace,
                              rehearsal=True, control=control)


def failed_checks(result):
    return sorted(n for n, c in result["checks"].items() if not c["ok"])


def _parts(rehearsal):
    cell = bench_run.manifest.load_cell(CELL)
    return (bench_run.merged(cell["config"], rehearsal)["driver_params"],
            bench_run.merged(cell["traffic"], rehearsal))


@pytest.fixture(scope="module")
def runs():
    """One untraced and one traced rehearsal on one seed, what each
    run's driver named and was served by (kept as it closes), and what
    the traced run's readers were handed with the instant its counters
    were read for the last time."""
    seen, ctxs, snapped = [], [], []
    real_close = rados.Driver.close
    real_read = readers.read_metric
    real_start = bench_run.Profiling.start

    def close(self):
        seen.append((self.prefix, list(self.acked[:self.t["clients"]]),
                     self.cluster.cct.conf.get("ms_async_op_threads")))
        real_close(self)

    def read_metric(spec, ctx):
        ctxs.append(ctx)
        return real_read(spec, ctx)

    def start(self):
        snapped.append(time.perf_counter())   # before its snapshot
        real_start(self)
    rados.Driver.close = close
    readers.read_metric = read_metric
    bench_run.Profiling.start = start
    try:
        return {"untraced": rehearse(), "traced": rehearse(trace=True),
                "seen": seen, "ctx": ctxs[-1], "snapped": snapped[-1]}
    finally:
        rados.Driver.close = real_close
        readers.read_metric = real_read
        bench_run.Profiling.start = real_start


@pytest.mark.parametrize("kind", ["untraced", "traced"])
def test_rehearsal_is_correct_and_compiles_nothing_in_the_window(kind, runs):
    res = runs[kind]
    assert res["correct"] is True, failed_checks(res)
    assert res["failed"] == 0 and res["attempted"] > 0
    checks = {n: c["value"] for n, c in res["checks"].items()}
    assert checks["compiles_in_window"] == 0
    assert checks["pipeline_host_fallbacks"] == 0
    assert checks["shards_compared"] >= 12 * 32
    assert checks["shards_missing"] == checks["shard_bytes_wrong"] == 0
    assert checks["shard_sizes_wrong"] == checks["stored_crcs_wrong"] == 0
    assert checks["reads_compared"] == 8 and checks["reads_wrong"] == 0


def test_untraced_rehearsal_reports_the_end_to_end_metrics(runs):
    got = runs["untraced"]["metrics"]
    assert set(got) == {"client_bw", "op_lat_p95", "setup_s"}
    assert all(m["value"] > 0 for m in got.values())


def check_a_traced_rehearsal_prints_the_cells_own_list(bench, repo):
    """Exactly the metrics the cell's own entries list, those of the
    device's plane apart: no expected set is built from a sibling
    cell's list, so a metric appended to another cell fails nothing."""
    res = rehearsals.traced(CELL, repo)
    assert res["correct"] is True, failed_checks(res)
    assert set(res["metrics"]) == rehearsals.host_side(CELL, repo)


# checks of (bench, repo): tests/benchmark_harness/test_manifest.py runs
# them again on a copy of the benchmark that a later PR's cells grew
INVARIANTS = [check_a_traced_rehearsal_prints_the_cells_own_list]


def test_traced_rehearsal_reports_the_write_cells_metrics_and_the_stores(
        runs):
    got = runs["traced"]["metrics"]
    assert set(got) == rehearsals.host_side(CELL)
    assert set(ADDED) <= set(got)
    # twelve sub-write transactions a put that others wait behind, up to
    # twelve kicks more for one that nobody does.  Counted exactly: the
    # counters stop where the profiler's start reads them, the client
    # ops they are divided by where its first clock anchor falls, a few
    # ms later; a put acked between the two is in the divisor and not
    # in the counters (a bound of 12 on the ratio itself failed so, now
    # and then: 11.934 = 12 x 179 / 180 on the CPU)
    ctx, snapped = runs["ctx"], runs["snapped"]
    txns = sum(v.get("transactions", 0) for n, v in ctx["counters"].items()
               if n.startswith("bluestore."))
    assert got["store_txns_per_put"]["value"] == \
        pytest.approx(txns / ctx["counted_ops"])
    acked = sum(1 for o in ctx["ops"] if o.ok and o.t1 <= snapped)
    in_flight = bench_run.merged(
        bench_run.manifest.load_cell(CELL)["traffic"], True)["clients"]
    assert acked > 0
    assert 12 * acked <= txns <= 24 * (ctx["counted_ops"] + in_flight)
    # two stripes of 8 x 512: a shard is 1 KiB in a 4 KiB allocation
    # unit, so the stores write at least 12 x 4 KiB for the 8 KiB put
    assert got["store_bytes_per_put_byte"]["value"] > 6.0
    assert got["wal_bytes_per_put"]["value"] > 12 * 8
    for name in ("rpc_prepare_ms", "ec_encode_ms", "hinfo_crc_ms",
                 "store_commit_ms", "pg_subwrite_ms",
                 "dispatch_queue_wait_ms", "device_dispatches_per_op"):
        assert got[name]["value"] > 0, name
    assert got["ops_per_batch"]["value"] >= 1.0
    # a CPU capture has no device plane: no device number is invented
    assert "busy_s" not in runs["traced"]["device"]


@pytest.mark.parametrize("rehearsal", [True, False],
                         ids=["rehearsal", "timed"])
def test_an_object_is_two_full_stripes_at_the_pools_stripe_unit(rehearsal):
    params, traffic = _parts(rehearsal)
    k = int(params["profile"]["k"])
    assert traffic["object_bytes"] == 2 * k * params["chunk_size"]
    assert traffic["op"] == "put" and traffic["loop"] == "closed"
    if not rehearsal:
        assert (traffic["clients"], traffic["object_bytes"],
                params["chunk_size"]) == (64, 65536, 4096)
        write = bench_run.manifest.load_cell(WRITE)
        # the write cell's deployment with one value changed
        assert {**write["config"]["driver_params"], "chunk_size": 4096} \
            == params
        assert set(traffic) | {"rehearsal"} == set(write["traffic"])


def test_the_same_seed_names_the_same_objects_and_three_workers_serve(runs):
    (prefix_a, warm_a, workers_a), (prefix_b, warm_b, workers_b) = \
        runs["seen"]
    assert prefix_a == prefix_b and warm_a == warm_b and len(warm_a) == 8
    assert all(oid.startswith(prefix_a + ".warm.") for oid, _pi in warm_a)
    # no earlier test file on this worker left its one dispatch worker
    # on the process-wide context (tests/test_served_op_timeline.py did)
    assert workers_a == workers_b == 3
    _params, traffic = _parts(True)
    made = {s: rados_clients.make_payloads(s, traffic)
            for s in (SEED, SEED + 1)}
    assert made[SEED][1] == prefix_a != made[SEED + 1][1]
    assert made[SEED][0] == rados_clients.make_payloads(SEED, traffic)[0]
    assert made[SEED][0] != made[SEED + 1][0]


def test_the_control_with_an_osd_down_comes_out_not_correct():
    res = rehearse(control="osd_down")
    assert res["correct"] is False
    assert failed_checks(res) == ["shards_missing"]


# -- faults planted under the timed path -------------------------------------------

def _altered(fn, alter):
    def wrapped(*a, **kw):
        return alter(fn(*a, **kw))
    return wrapped


def test_fault_parity_altered_where_the_served_path_produces_it(monkeypatch):
    from ceph_tpu.ops.codec import RSCodec

    def flip_first(out):
        import jax.numpy as jnp
        return jnp.asarray(out).at[0, 0].set(out[0, 0] ^ 1)
    monkeypatch.setattr(RSCodec, "encode_device",
                        _altered(RSCodec.encode_device, flip_first))
    res = rehearse()
    assert res["correct"] is False
    assert "shard_bytes_wrong" in failed_checks(res)


def test_fault_stored_crc_altered_where_it_is_produced(monkeypatch):
    from ceph_tpu.ops import rs_kernels

    def bump(crcs):
        return np.asarray(crcs) + np.eye(1, 12, 9, dtype=np.uint32)[0]
    monkeypatch.setattr(rs_kernels, "crc32c_rows",
                        _altered(rs_kernels.crc32c_rows, bump))
    res = rehearse()
    assert res["correct"] is False
    assert failed_checks(res) == ["stored_crcs_wrong"]
