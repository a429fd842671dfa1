"""tools/perf_gate.py: verdicts on pass / regress / platform-fallback
artifacts (the ISSUE-6 gate acceptance: nonzero exit on a synthetic 20%
regression and on a TPU->CPU fallback)."""
import importlib.util
import json
from pathlib import Path

import pytest

_PATH = Path(__file__).resolve().parent.parent / "tools" / "perf_gate.py"
spec = importlib.util.spec_from_file_location("perf_gate_t", _PATH)
perf_gate = importlib.util.module_from_spec(spec)
spec.loader.exec_module(perf_gate)


def _line(value=1000.0, device="tpu", serving=500.0, recovery=80.0,
          pipeline=120.0, p99=2.0, wire_per_byte=6.0, wire_per_op=9000.0,
          pct_of_peak=42.0, slo_p99=5.0, budget=1.0):
    return {
        "metric": "rs_k8m4_1MiB_encode_decode_device_resident",
        "value": value, "unit": "MiB/s", "device": device,
        "serving": {"device": device,
                    "batched": {"ops_s": serving, "p99_ms": p99},
                    "wire": {"per_op": wire_per_op}},
        "recovery": {"device": device, "batched": {"mib_s": recovery},
                     "wire": {"per_byte_repaired": wire_per_byte}},
        "pipeline": {"device": device, "async": {"mib_s": pipeline}},
        "efficiency": {"device": device, "pct_of_peak": pct_of_peak},
        "slo": {"device": device,
                "client": {"p99_ms": slo_p99, "ops": 48,
                           "budget_remaining": budget,
                           "phases": {"device": 0.6, "wire": 0.4}}},
    }


class TestEvaluate:
    def test_pass_within_thresholds(self):
        res = perf_gate.evaluate(_line(value=980.0), _line(),
                                 expect_platform="tpu")
        assert res["ok"] and res["verdict"].startswith("PERF GATE: PASS")
        assert len(res["compared"]) == 10

    def test_twenty_percent_regression_fails(self):
        res = perf_gate.evaluate(_line(value=800.0), _line(value=1000.0))
        assert not res["ok"]
        assert any("core.mib_s" in f for f in res["failures"])
        assert res["verdict"].startswith("PERF GATE: FAIL")

    def test_block_regression_fails_independently(self):
        res = perf_gate.evaluate(_line(recovery=50.0), _line())
        assert not res["ok"]
        assert any("recovery.mib_s" in f for f in res["failures"])

    def test_resilience_block_gated(self):
        """ISSUE 9: the `resilience` block participates — a goodput-
        ratio collapse or a breaker-fallback-throughput cliff past the
        (loose, wall-clock-noisy) 30% threshold fails the round; a
        within-threshold wiggle passes."""
        def rline(ratio=0.8, fallback=200.0):
            line = _line()
            line["resilience"] = {
                "device": "tpu", "goodput_ratio": ratio,
                "breaker": {"fallback_mib_s": fallback, "opens": 1}}
            return line
        res = perf_gate.evaluate(rline(), rline())
        assert res["ok"] and len(res["compared"]) == 12
        res = perf_gate.evaluate(rline(ratio=0.4), rline(ratio=0.8))
        assert not res["ok"]
        assert any("resilience.goodput_ratio" in f
                   for f in res["failures"])
        res = perf_gate.evaluate(rline(fallback=100.0),
                                 rline(fallback=200.0))
        assert not res["ok"]
        assert any("resilience.fallback_mib_s" in f
                   for f in res["failures"])
        # 20% off is inside the loose 30% band for this metric
        res = perf_gate.evaluate(rline(ratio=0.65), rline(ratio=0.8))
        assert res["ok"]

    def test_slo_block_gated(self):
        """ISSUE 10: the `slo` block participates — a client-p99 cliff
        (past the loose 50% band: per-op p99 on a shared host is
        tail-of-the-tail noisy) or a budget burn (budget_remaining
        drop past 30%) fails the round; within-band wiggles pass."""
        res = perf_gate.evaluate(_line(slo_p99=20.0),
                                 _line(slo_p99=5.0))
        assert not res["ok"]
        assert any("slo.client_p99_ms" in f for f in res["failures"])
        # a 40% p99 rise is inside the loose band
        res = perf_gate.evaluate(_line(slo_p99=7.0), _line(slo_p99=5.0))
        assert res["ok"]
        # budget burn: remaining budget dropped 50% -> fail
        res = perf_gate.evaluate(_line(budget=0.5), _line(budget=1.0))
        assert not res["ok"]
        assert any("slo.budget_remaining" in f for f in res["failures"])
        res = perf_gate.evaluate(_line(budget=0.9), _line(budget=1.0))
        assert res["ok"]
        # a latency IMPROVEMENT never fails
        res = perf_gate.evaluate(_line(slo_p99=1.0), _line(slo_p99=5.0))
        assert res["ok"]

    def test_wire_efficiency_regression_direction_is_up(self):
        """Wire metrics gate on INCREASE: repair moving more bytes on
        the wire per byte repaired (or serving per op) is the
        regression, even with throughput unchanged."""
        res = perf_gate.evaluate(_line(wire_per_byte=8.0),
                                 _line(wire_per_byte=6.0))
        assert not res["ok"]
        assert any("recovery.wire_per_byte" in f for f in res["failures"])
        res = perf_gate.evaluate(_line(wire_per_op=12000.0),
                                 _line(wire_per_op=9000.0))
        assert any("serving.wire_per_op" in f for f in res["failures"])
        # a wire-efficiency IMPROVEMENT (fewer bytes moved) passes
        res = perf_gate.evaluate(_line(wire_per_byte=2.0,
                                       wire_per_op=5000.0), _line())
        assert res["ok"]

    def test_pct_of_peak_regression_fails_loose_threshold(self):
        """The ISSUE-8 acceptance pin: a synthetic %-of-peak cliff flips
        the verdict to FAIL.  The metric carries a LOOSE default
        threshold (30%: dispatch wall-clock on a shared host is noisy),
        so a 50% drop fails while ordinary jitter passes."""
        res = perf_gate.evaluate(_line(pct_of_peak=20.0),
                                 _line(pct_of_peak=42.0))
        assert not res["ok"]
        assert any("efficiency.pct_of_peak" in f for f in res["failures"])
        # 20% down is inside the loose threshold: jitter, not a cliff
        res = perf_gate.evaluate(_line(pct_of_peak=34.0),
                                 _line(pct_of_peak=42.0))
        assert res["ok"]
        # an explicit --threshold still tightens it
        res = perf_gate.evaluate(
            _line(pct_of_peak=34.0), _line(pct_of_peak=42.0),
            thresholds={"efficiency.pct_of_peak": 0.10})
        assert not res["ok"]

    def test_efficiency_platform_fallback_not_compared(self):
        # a cpu efficiency block never diffs against a tpu reference —
        # and the fallback itself already hard-fails the gate
        res = perf_gate.evaluate(_line(device="cpu", pct_of_peak=90.0),
                                 _line(device="tpu", pct_of_peak=42.0),
                                 expect_platform="tpu")
        assert not res["ok"]
        assert not any("efficiency.pct_of_peak" in c["metric"]
                       for c in res["compared"])
        assert any("platform fallback" in f for f in res["failures"])

    def test_latency_regression_direction_is_up(self):
        res = perf_gate.evaluate(_line(p99=3.0), _line(p99=2.0))
        assert any("serving.p99_ms" in f for f in res["failures"])
        # a latency DROP is an improvement, never a failure
        res = perf_gate.evaluate(_line(p99=1.0), _line(p99=2.0))
        assert res["ok"]

    def test_platform_fallback_hard_fails(self):
        # the r05 failure mode: expected tpu, measured cpu — the numbers
        # themselves look "fine" (cpu vs cpu is not even compared)
        new = _line(value=7500.0, device="cpu")
        res = perf_gate.evaluate(new, _line(), expect_platform="tpu")
        assert not res["ok"]
        assert any("platform fallback" in f for f in res["failures"])

    def test_tpu_reference_cpu_new_fails_per_block(self):
        res = perf_gate.evaluate(_line(device="cpu"), _line(device="tpu"))
        assert not res["ok"]
        assert any("platform fallback" in f for f in res["failures"])

    def test_cpu_vs_cpu_compares_normally(self):
        res = perf_gate.evaluate(_line(device="cpu"),
                                 _line(device="cpu"),
                                 expect_platform="cpu")
        assert res["ok"] and len(res["compared"]) == 10

    def test_custom_threshold(self):
        ref, new = _line(value=1000.0), _line(value=900.0)
        assert perf_gate.evaluate(new, ref)["ok"]          # 10% default
        res = perf_gate.evaluate(new, ref,
                                 thresholds={"core.mib_s": 0.05})
        assert not res["ok"]

    def test_no_reference_checks_platform_only(self):
        res = perf_gate.evaluate(_line(), None, expect_platform="tpu")
        assert res["ok"]
        res = perf_gate.evaluate(_line(device="cpu"), None,
                                 expect_platform="tpu")
        assert not res["ok"]

    def test_bench_wrapper_normalizes(self):
        wrapped = {"n": 7, "rc": 0, "parsed": _line()}
        res = perf_gate.evaluate(wrapped, {"parsed": _line()},
                                 expect_platform="tpu")
        assert res["ok"]

    def test_legacy_tpu_line_infers_platform(self):
        # BENCH_r03's shape: no device field, no error -> tpu success
        legacy = {"metric": "m", "value": 32222.3, "unit": "MiB/s",
                  "vs_baseline": 4.0}
        assert perf_gate.artifact_platform(legacy) == "tpu"
        fallback = dict(legacy, error="tpu unavailable", device="cpu")
        assert perf_gate.artifact_platform(fallback) == "cpu"


class TestMainAndHistory:
    def _write(self, tmp_path, name, doc):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return p

    def test_cli_exit_codes(self, tmp_path, capsys):
        ref = self._write(tmp_path, "BENCH_r06.json",
                          {"n": 6, "parsed": _line()})
        good = self._write(tmp_path, "good.json", _line(value=990.0))
        bad = self._write(tmp_path, "bad.json", _line(value=700.0))
        cpu = self._write(tmp_path, "cpu.json",
                          _line(value=9000.0, device="cpu"))
        rd = str(tmp_path)
        assert perf_gate.main([str(good), "--repo-dir", rd,
                               "--check"]) == 0
        assert "PERF GATE: PASS" in capsys.readouterr().out
        assert perf_gate.main([str(bad), "--repo-dir", rd,
                               "--check"]) == 1
        assert "PERF GATE: FAIL" in capsys.readouterr().out
        # TPU->CPU fallback: nonzero even though the number is higher
        assert perf_gate.main([str(cpu), "--repo-dir", rd,
                               "--check"]) == 1
        out = capsys.readouterr().out
        assert "platform fallback" in out
        assert ref.exists()

    def test_legacy_reference_still_gates_tpu_regressions(self):
        # a pre-r04 TPU record (no device markers anywhere) must still
        # participate in per-metric comparison via platform inference —
        # not be skipped as device-unknown
        legacy_ref = {"metric": "m", "value": 32000.0, "unit": "MiB/s",
                      "vs_baseline": 4.0}
        res = perf_gate.evaluate(_line(value=16000.0), legacy_ref,
                                 expect_platform="tpu")
        assert not res["ok"]
        assert any("core.mib_s" in f for f in res["failures"])
        res = perf_gate.evaluate(_line(value=31500.0), legacy_ref,
                                 expect_platform="tpu")
        assert res["ok"] and res["compared"]

    def test_find_reference_skips_errored_artifacts(self, tmp_path):
        # the r05 shape (newest round, but an errored cpu fallback) must
        # not become the baseline while a clean round exists
        self._write(tmp_path, "BENCH_r03.json", {"parsed": _line()})
        self._write(tmp_path, "BENCH_r05.json",
                    {"parsed": dict(_line(device="cpu"),
                                    error="tpu unavailable")})
        _doc, path = perf_gate.find_reference(str(tmp_path))
        assert path.endswith("BENCH_r03.json")
        # ...unless EVERY round errored (cpu-only history still compares)
        (tmp_path / "BENCH_r03.json").unlink()
        _doc, path = perf_gate.find_reference(str(tmp_path))
        assert path.endswith("BENCH_r05.json")

    def test_find_reference_picks_newest_round(self, tmp_path):
        self._write(tmp_path, "BENCH_r02.json",
                    {"parsed": _line(value=1.0)})
        self._write(tmp_path, "BENCH_r09.json",
                    {"parsed": _line(value=9.0)})
        self._write(tmp_path, "BENCH_r08.json", {"parsed": _line(8.0)})
        doc, path = perf_gate.find_reference(str(tmp_path))
        assert path.endswith("BENCH_r09.json")
        assert doc["parsed"]["value"] == 9.0

    def test_expected_platform_from_history(self, tmp_path):
        self._write(tmp_path, "BENCH_r01.json",
                    {"parsed": _line(device="cpu")})
        assert perf_gate.expected_platform(str(tmp_path)) is None
        self._write(tmp_path, "BENCH_r02.json", {"parsed": _line()})
        assert perf_gate.expected_platform(str(tmp_path)) == "tpu"

    def test_gate_for_bench_attaches_verdict(self, tmp_path):
        self._write(tmp_path, "BENCH_r03.json", {"parsed": _line()})
        res = perf_gate.gate_for_bench(_line(value=995.0), str(tmp_path))
        assert res["ok"] and res["reference"] == "BENCH_r03.json"
        assert res["expected_platform"] == "tpu"
        res = perf_gate.gate_for_bench(_line(device="cpu"),
                                       str(tmp_path))
        assert not res["ok"]

    def test_legacy_history_gates_a_cpu_fallback_artifact(self, tmp_path):
        """A history in the shape the first five rounds left — device
        lines with no ``device`` field, then an rc=1 round with nothing
        parsed, then a host number written under the device metric's
        name with ``device: cpu`` and an ``error`` — must FAIL that last
        artifact as a platform fallback, not accept it as a slower
        device number.  (The real files recorded a set-up that is gone
        and were deleted in PR 21; this keeps their shapes.)"""
        legacy = {"metric": "rs_k8m4_1MiB_encode_decode_device_resident",
                  "unit": "MiB/s"}
        for n, value in ((1, 32686.1), (2, 24437.0), (3, 32222.3)):
            self._write(tmp_path, f"BENCH_r0{n}.json",
                        {"n": n, "rc": 0,
                         "parsed": dict(legacy, value=value,
                                        vs_baseline=4.0)})
        self._write(tmp_path, "BENCH_r04.json",
                    {"n": 4, "rc": 1, "parsed": None})
        r05 = {"n": 5, "rc": 0,
               "parsed": dict(legacy, value=7532.2, vs_baseline=1.0,
                              device="cpu", cpu_kind="simd",
                              error="tpu backend unavailable after "
                                    "bounded init retries")}
        self._write(tmp_path, "BENCH_r05.json", r05)
        assert perf_gate.expected_platform(str(tmp_path)) == "tpu"
        res = perf_gate.evaluate(
            r05, None, expect_platform=perf_gate.expected_platform(
                str(tmp_path)))
        assert not res["ok"]
        assert any("platform fallback" in x for x in res["failures"])
