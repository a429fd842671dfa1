"""``ec_clay_repair_b256``: a REHEARSAL at tiny sizes on the CPU (the same
code the chip runs, the look for a chip skipped), traced and untraced,
its control, and a wrong helper byte planted under its timed path:
`correct` has to come out true for the program as it is and false for
the others.

Counts and correctness only: nothing here is a rate of the device."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import rehearsals  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL = "ec_clay_repair_b256"
SEED = 2147484203


def rehearse(trace=False, control=None, seed=SEED):
    return bench_run.run_cell(CELL, seed, 2.0 if trace else 1.0, trace,
                              rehearsal=True, control=control)


def failed_checks(result):
    return sorted(n for n, c in result["checks"].items() if not c["ok"])


def _clay_state():
    """(the clay perf counters, {span: count}) of this process, now."""
    from ceph_tpu.common.context import default_context
    from ceph_tpu.common.tracer import default_tracer
    perf = default_context().perf.perf_dump().get("clay", {})
    spans = {n: h["count"] for n, h in default_tracer().histograms().items()
             if n.startswith("clay.")}
    return perf, spans


@pytest.fixture(scope="module")
def runs():
    """An untraced and a traced rehearsal, and what the traced one left in
    the clay counters and spans."""
    untraced = rehearse()
    before = _clay_state()
    traced = rehearse(trace=True)
    return {"untraced": untraced, "traced": traced,
            "clay": (before, _clay_state())}


@pytest.mark.parametrize("kind", ["untraced", "traced"])
def test_rehearsal_is_correct_and_compiles_nothing_in_the_window(kind, runs):
    res = runs[kind]
    assert res["correct"] is True, failed_checks(res)
    assert res["failed"] == 0 and res["attempted"] > 0
    checks = {n: c["value"] for n, c in res["checks"].items()}
    assert checks["compiles_in_window"] == 0
    assert checks["parity_outputs_compared"] >= 1
    assert checks["recovered_outputs_compared"] >= 1
    assert checks["helper_runs_wrong"] == checks["helpers_not_d"] == 0
    assert checks["helper_bytes_not_d_C_over_q"] == 0


def test_untraced_rehearsal_reports_codec_bw_and_setup(runs):
    got = runs["untraced"]["metrics"]
    assert set(got) == {"codec_bw", "setup_s"}
    assert all(m["value"] > 0 for m in got.values())


def test_every_call_of_the_window_is_one_dispatch_and_one_span(runs):
    """What a metric of the codec layer would read: the clay counters move
    by one dispatch a call, and each call leaves a clay.encode or a
    clay.repair span (set-up's own calls are in the deltas too)."""
    (perf0, spans0), (perf1, spans1) = runs["clay"]
    calls = perf1["calls"] - perf0.get("calls", 0)
    dispatches = perf1["device_dispatches"] - perf0.get(
        "device_dispatches", 0)
    spans = {n: spans1[n] - spans0.get(n, 0)
             for n in ("clay.encode", "clay.repair")}
    res = runs["traced"]
    assert calls == dispatches >= res["attempted"]
    assert spans["clay.encode"] + spans["clay.repair"] == calls
    assert abs(spans["clay.encode"] - spans["clay.repair"]) <= 2
    # d quarter-chunks read a chunk rebuilt
    read = perf1["repair_read_bytes"] - perf0.get("repair_read_bytes", 0)
    built = perf1["repair_bytes"] - perf0.get("repair_bytes", 0)
    assert read * 4 == built * 11
    assert "busy_s" not in res["device"]


def test_the_control_comes_out_not_correct():
    res = rehearse(control="technique")
    assert res["correct"] is False
    assert failed_checks(res) == ["parity_bytes_wrong"]


def test_a_wrong_helper_byte_comes_out_not_correct(monkeypatch):
    from ceph_tpu.ops.clay_kernels import ClayCodec
    real = ClayCodec.repair_device

    def planted(self, helpers, lost, helper_ids=None, chunk_size=None):
        import jax.numpy as jnp
        helpers = jnp.asarray(helpers)
        return real(self, helpers.at[3, 5].set(helpers[3, 5] ^ 0x40), lost,
                    helper_ids, chunk_size)
    monkeypatch.setattr(ClayCodec, "repair_device", planted)
    res = rehearse()
    assert res["correct"] is False
    assert failed_checks(res) == ["recovered_bytes_wrong"]


def test_the_reference_repair_planes_are_the_plugins_runs():
    from benchmark.lib.clay_reference import Clay
    from ceph_tpu.plugins import ErasureCodePluginRegistry
    cell = bench_run.manifest.load_cell(CELL)
    profile = dict(cell["config"]["driver_params"]["profile"], device="numpy")
    ec = ErasureCodePluginRegistry.instance().factory("clay", "", profile)
    ref = Clay(int(profile["k"]), int(profile["m"]), int(profile["d"]))
    for lost in range(ec.get_chunk_count()):
        runs = ec.get_repair_subchunks(lost if lost < ec.k else lost + ec.nu)
        assert [z for off, cnt in runs for z in range(off, off + cnt)] == \
            ref.repair_planes(lost)
    assert len(ref.repair_planes(0)) * ref.q == ref.planes == 64


def check_a_traced_rehearsal_prints_the_cells_own_list(bench, repo):
    """Exactly the metrics the cell's own entries list, those of the
    device's plane apart."""
    res = rehearsals.traced(CELL, repo)
    assert res["correct"] is True, failed_checks(res)
    assert set(res["metrics"]) == rehearsals.host_side(CELL, repo)


def check_the_cell_reports_codec_bw_and_the_devices_idle_share(bench, repo):
    """``codec_bw`` and ``setup_s``, and of the accepted per-layer metrics
    the idle share; the RS kernel's roofline reads nothing here."""
    cell = bench_run.manifest.load_cell(CELL, repo)
    assert {m["name"] for m in cell["end_to_end"]} == {"codec_bw", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert "device_idle_pct.codec" in names
    assert "gf_apply_roofline" not in names
    assert cell["chips"] == 1 and cell["traffic_name"] == "resident_b256"


# checks of (bench, repo) that a later PR's cells could break
INVARIANTS = [check_a_traced_rehearsal_prints_the_cells_own_list,
              check_the_cell_reports_codec_bw_and_the_devices_idle_share]


@pytest.mark.parametrize("check", INVARIANTS, ids=lambda f: f.__name__[6:])
def test_the_cells_own_invariants_hold(check):
    check(bench_run.manifest.load_benchmark(), REPO)
