"""``rbd_randwrite_4k_qd16``: a REHEARSAL at tiny sizes on the CPU (the
same code the chip runs, the look for a chip skipped), its control, and
three faults planted under its timed path: a data shard written without
its parity, a stale crc left in HashInfo, and a read-back of the bytes
from before the overwrite.  `correct` has to come out true for the
program as it is and false for each of the others.

Counts and correctness only: nothing here is a rate of the device."""
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import rehearsals  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import rados_overwrite  # noqa: E402

CELL = "rbd_randwrite_4k_qd16"
SMALL = "rados_write_64k_qd64"
SEED = 2147484101
# what the read-modify-write adds to the write path's metrics
ADDED = ["overwrite_hold_ms", "rmw_read_ms", "rmw_read_bytes_per_write_byte"]


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
    """One untraced and one traced rehearsal on one seed, and the writes
    each run's driver recorded (kept as it closes)."""
    seen = []
    real = rados_overwrite.Driver.close

    def close(self):
        seen.append(list(getattr(self, "writes", [])))
        real(self)
    rados_overwrite.Driver.close = close
    try:
        return {"untraced": rehearse(), "traced": rehearse(trace=True),
                "seen": seen}
    finally:
        rados_overwrite.Driver.close = real


@pytest.mark.parametrize("kind", ["untraced", "traced"])
def test_rehearsal_is_correct_and_compiles_nothing_in_the_window(kind, runs):
    res = runs[kind]
    assert res["correct"] is True, failed_checks(res)
    assert res["failed"] == 0 and res["attempted"] > 0
    checks = {n: c["value"] for n, c in res["checks"].items()}
    assert checks["compiles_in_window"] == 0
    assert checks["pipeline_host_fallbacks"] == 0
    _params, traffic = _parts(True)
    # the rehearsal's image is small enough to be compared whole
    assert checks["objects_read_back"] == traffic["object_set"]
    assert checks["shards_compared"] == 12 * traffic["object_set"]
    assert checks["written_blocks_compared"] >= traffic["clients"]
    assert checks["written_blocks_wrong"] == checks["shard_bytes_wrong"] == 0
    assert checks["stored_crcs_wrong"] == checks["shards_missing"] == 0
    # one device encode a write: none fell to the host
    assert checks["overwrites_encoded_on_device"] >= res["attempted"]


def test_untraced_rehearsal_reports_the_end_to_end_metrics(runs):
    """``client_bw`` and ``setup_s``: at depth 16 the lock is held nearly
    all the time and the tail swings with the stores' checkpoints, so
    the cell reports the rate (its latency quantiles go to stderr)."""
    got = runs["untraced"]["metrics"]
    assert set(got) == {"client_bw", "setup_s"}
    assert all(m["value"] > 0 for m in got.values())


def check_a_traced_rehearsal_prints_the_cells_own_list(bench, repo):
    """Exactly the metrics the cell's own entries list, those of the
    device's plane apart: no expected set is built from a sibling
    cell's list, so a metric appended to another cell fails nothing."""
    res = rehearsals.traced(CELL, repo)
    assert res["correct"] is True, failed_checks(res)
    assert set(res["metrics"]) == rehearsals.host_side(CELL, repo)


def check_the_cells_entries_read_no_put_or_get_span(bench, repo):
    """The entries that list the cell read what an overwrite leaves: no
    span or counter of a put's prepare, a put's or a get's hold."""
    for spec in bench_run.manifest.load_cell(CELL, repo)["per_layer"]:
        sources = spec.get("params", {}).get("spans", [])
        assert not {"span:rpc.put", "span:rpc.get", "span:rpc.prepare"} \
            & set(sources), spec["name"]


# checks of (bench, repo) that a later PR's cells could break
INVARIANTS = [check_a_traced_rehearsal_prints_the_cells_own_list,
              check_the_cells_entries_read_no_put_or_get_span]


@pytest.mark.parametrize("check", INVARIANTS, ids=lambda f: f.__name__[6:])
def test_the_cells_own_invariants_hold(check):
    check(bench_run.manifest.load_benchmark(), REPO)


def test_traced_rehearsal_reads_the_read_modify_write(runs):
    got = runs["traced"]["metrics"]
    assert set(ADDED) <= set(got)
    # a one-chunk write reads the k data chunks of its stripe
    assert got["rmw_read_bytes_per_write_byte"]["value"] == 8.0
    assert got["overwrite_hold_ms"]["value"] > got["rmw_read_ms"]["value"] > 0
    for name in ("ec_encode_ms", "pg_subread_ms", "pg_subwrite_ms",
                 "store_commit_ms", "lock_wait_ms", "pipeline_pack_ms"):
        assert got[name]["value"] > 0, name
    # twelve sub-write transactions an overwrite, nothing rolled forward
    # on its own while writes queue
    assert 12.0 <= got["store_txns_per_put"]["value"] <= 24.0
    # one encode a write; the counters stop at the profiler's start and
    # the writes they are divided by at its first clock anchor, so a
    # write in flight between the two moves the ratio by 1 / writes
    assert got["device_dispatches_per_op"]["value"] == \
        pytest.approx(1.0, rel=0.05)
    # a CPU capture has no device plane: no device number is invented
    assert "busy_s" not in runs["traced"]["device"]


@pytest.mark.parametrize("rehearsal", [True, False],
                         ids=["rehearsal", "timed"])
def test_the_deployment_is_the_small_object_pool_and_rbd_benchs_defaults(
        rehearsal):
    params, traffic = _parts(rehearsal)
    small = bench_run.manifest.load_cell(SMALL)
    assert params == bench_run.merged(small["config"],
                                      rehearsal)["driver_params"]
    assert traffic["op"] == "overwrite" and traffic["loop"] == "closed"
    # a write covers part of one stripe: the k data chunks are read
    k = int(params["profile"]["k"])
    assert traffic["io_bytes"] == params["chunk_size"]
    assert traffic["object_bytes"] % (k * params["chunk_size"]) == 0
    if not rehearsal:
        assert (traffic["clients"], traffic["io_bytes"],
                traffic["object_bytes"], traffic["object_set"]) == \
            (16, 4096, 4 << 20, 256)
        assert traffic["object_set"] * traffic["object_bytes"] == 1 << 30


def test_the_same_seed_writes_the_same_blocks(runs):
    """Both runs drew their blocks and bytes from one seed: the warm-up's
    writes, made before any timing, are the same."""
    warm_a, warm_b = ([w[0] for w in seen[:_parts(True)[1]["clients"]]]
                      for seen in runs["seen"])
    assert warm_a == warm_b and {w[3] for w in warm_a} == {0}
    assert rados_overwrite.block_bytes(SEED, 1, 2, 512) == \
        rados_overwrite.block_bytes(SEED, 1, 2, 512) != \
        rados_overwrite.block_bytes(SEED, 1, 3, 512)


def test_the_control_with_an_osd_down_comes_out_not_correct():
    res = rehearse(control="osd_down")
    assert res["correct"] is False
    assert failed_checks(res) == ["shards_missing"]


def test_a_program_without_a_served_write_is_refused_at_once(monkeypatch):
    from ceph_tpu.net import TcpRados
    monkeypatch.delattr(TcpRados, "write")
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="no write"):
        rehearse()
    assert time.perf_counter() - t0 < 60


# -- faults planted under the timed path -------------------------------------------

def test_fault_a_data_shard_written_without_its_parity(monkeypatch):
    """An overwrite's parity shards keep their attrs and lose the write."""
    from ceph_tpu.backend.ec_backend import ECBackend
    real = ECBackend._generate_transactions

    def generate(self, op):
        txns, log = real(self, op)
        if op.plan.to_read:
            k = self.ec_impl.get_data_chunk_count()
            for shard in self.acting[k:]:
                txns[shard].ops = [o for o in txns[shard].ops
                                   if o[0] != "write"]
        return txns, log
    monkeypatch.setattr(ECBackend, "_generate_transactions", generate)
    res = rehearse()
    assert res["correct"] is False
    assert failed_checks(res) == ["shard_bytes_wrong"]


def test_fault_a_stale_crc_left_in_hashinfo(monkeypatch):
    """An overwrite sets the shards' size and keeps their crcs."""
    from ceph_tpu.backend.ecutil import HashInfo

    def keep_hash(self, new_chunk_size):
        self.total_chunk_size = new_chunk_size
    monkeypatch.setattr(HashInfo, "set_total_chunk_size_clear_hash",
                        keep_hash)
    res = rehearse()
    assert res["correct"] is False
    assert "stored_crcs_wrong" in failed_checks(res)


def test_fault_a_read_back_of_the_bytes_before_the_overwrite(monkeypatch):
    """The stores take every write; a get answers with what the object
    held before its first one."""
    from ceph_tpu.net import ClusterServer
    real_write, real_get = ClusterServer._rpc_write, ClusterServer._rpc_get
    before: dict = {}

    def write(self, ch, pool, oid, offset, data):
        if oid not in before:
            before[oid] = real_get(self, ch, pool, oid)
        return real_write(self, ch, pool, oid, offset, data)

    def get(self, ch, pool, oid):
        return before.get(oid) or real_get(self, ch, pool, oid)
    monkeypatch.setattr(ClusterServer, "_rpc_write", write)
    monkeypatch.setattr(ClusterServer, "_rpc_get", get)
    res = rehearse()
    assert res["correct"] is False
    # the shards hold what was written (their check also fails only
    # where two writes to one block were acked in another order than
    # they were applied, which a rehearsal's few writes rarely meet)
    assert {"objects_read_wrong", "written_blocks_wrong"} <= \
        set(failed_checks(res)) <= {"objects_read_wrong",
                                    "written_blocks_wrong",
                                    "shard_bytes_wrong"}
