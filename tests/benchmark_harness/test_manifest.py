"""BENCHMARK.json against the contract it is written to, and the
loading of a cell's files by name."""
import json
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark.lib import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert len(bench["command"]) <= 32 and all(map(_line, bench["command"]))
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    # a full check with all 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_command_names_only_files_under_paths(bench):
    for word in bench["command"]:
        if (REPO / word).exists():
            assert any(word.startswith(p + "/") for p in bench["paths"])


def test_configs(bench):
    files = set()
    assert 1 <= len(bench["configs"]) <= 24
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(r)
                                               for r in c["reduced"])
        spec = json.loads((REPO / c["file"]).read_text())
        assert spec["name"] == c["name"] and spec["source"] == c["source"]
        assert sorted(spec["reduced"]) == sorted(c["reduced"])
        assert spec["guarantees"], "a deployment states its guarantees"
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    assert len({c["name"] for c in bench["configs"]}) == len(bench["configs"])


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def test_end_to_end_metrics(bench):
    e2e = bench["end_to_end"]
    assert 1 <= len(e2e) <= 16
    names = [m["name"] for m in e2e]
    assert "setup_s" in names and len(set(names)) == len(names)
    cells = {w["name"] for w in bench["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = [m for m in e2e if cell in m.get("workloads", cells)]
        assert any(m["name"] == "setup_s" for m in mine)
        assert any(m["name"] != "setup_s" for m in mine), cell


def test_per_layer_metrics(bench):
    per = bench["per_layer"]
    assert 1 <= len(per) <= 128
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert len({m["name"] for m in per}) == len(per)
    assert not {m["name"] for m in per} & set(e2e)
    for m in per:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in per), cell


def test_files_under_paths_have_plain_names(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        for f in (REPO / p).rglob("*"):
            rel = f.relative_to(REPO).as_posix()
            if "__pycache__" in rel or rel.endswith(".pyc"):
                continue
            assert ok.match(rel), rel


def test_traffic_mixes_are_data_files_one_generator_reads():
    for path in sorted((REPO / "benchmark" / "traffic").glob("*.json")):
        spec = json.loads(path.read_text())
        assert spec["name"] == path.stem and spec["loop"] == "closed"
        assert spec["end_to_end"], "the mix says how its metrics are reckoned"


@pytest.mark.parametrize("cell", ["rados_write_4m_qd16", "ec_resident_b256",
                                  "rados_seqread_4m_qd16"])
def test_load_cell_finds_config_traffic_and_metric_files_by_name(cell, bench):
    got = manifest.load_cell(cell)
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    assert got["config"]["name"] == entry["config"]
    assert got["traffic"]["name"] == entry["traffic"]
    assert {m["name"] for m in got["end_to_end"]} >= {"setup_s"}
    want = {m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])}
    assert {m["name"] for m in got["per_layer"]} == want
    for spec in got["per_layer"]:
        listed = next(m for m in bench["per_layer"]
                      if m["name"] == spec["name"])
        for key in ("unit", "layer", "moves", "source", "better"):
            assert spec[key] == listed[key], (spec["name"], key)
        assert spec["workloads"] == listed["workloads"]


def test_an_unknown_cell_is_refused_by_name():
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.load_cell("no_such_cell")


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A later PR's cell: copy the tree, ADD a configuration file, a
    traffic file, a metric file and their entries; edit no file that was
    there.  The harness finds all three by name."""
    import shutil
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "benchmark/configs/ec_bench_k8m4_1m.json")
                     .read_text())
    cfg["name"] = "ec_bench_k4m2_64k"
    cfg["driver_params"]["size"] = 65536
    (tmp_path / "benchmark/configs/ec_bench_k4m2_64k.json").write_text(
        json.dumps(cfg))
    mix = json.loads((REPO / "benchmark/traffic/single_1m.json").read_text())
    mix["name"] = "single_64k"
    (tmp_path / "benchmark/traffic/single_64k.json").write_text(
        json.dumps(mix))
    metric = {"name": "codec_encode_ms", "layer": "codec", "unit": "ms",
              "better": "lower", "source": "program_span",
              "moves": "codec_bw", "workloads": ["ec_single_64k"],
              "reader": "span_mean_ms",
              "params": {"spans": ["span:codec.encode"]}}
    (tmp_path / "benchmark/metrics/codec_encode_ms.json").write_text(
        json.dumps(metric))
    bench["configs"].append({"name": "ec_bench_k4m2_64k", "source": "x",
                             "file": "benchmark/configs/ec_bench_k4m2_64k.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "ec_single_64k",
                               "config": "ec_bench_k4m2_64k",
                               "traffic": "single_64k", "chips": 1,
                               "why": "x"})
    bench["end_to_end"][2]["workloads"].append("ec_single_64k")
    bench["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    got = manifest.load_cell("ec_single_64k", repo=tmp_path)
    assert got["config"]["driver_params"]["size"] == 65536
    assert got["traffic"]["name"] == "single_64k"
    assert [m["name"] for m in got["per_layer"]] == ["codec_encode_ms"]
    assert {m["name"] for m in got["end_to_end"]} == {"codec_bw", "setup_s"}
