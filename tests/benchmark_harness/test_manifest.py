"""BENCHMARK.json against the contract it is written to, and the
loading of a cell's files by name.

The contract checks are functions of ``(bench, repo)``: they run on this
tree under the test names they always had, and again on a copy of the
benchmark that a later PR's cell was added to (the last tests here).
No check pins how many cells or metrics there are, or where in a list
one stands: adding one must not fail a test."""
import hashlib
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark.lib import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# read at collection: one case of test_load_cell_finds_... for every cell
CELLS = [w["name"] for w in manifest.load_benchmark()["workloads"]]


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def check_top_level_keys_and_limits(bench, repo):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (repo / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert len(bench["command"]) <= 32 and all(map(_line, bench["command"]))
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    # a full check with all 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def check_command_names_only_files_under_paths(bench, repo):
    for word in bench["command"]:
        if (repo / word).exists():
            assert any(word.startswith(p + "/") for p in bench["paths"])


def check_configs(bench, repo):
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
        spec = json.loads((repo / c["file"]).read_text())
        assert spec["name"] == c["name"] and spec["source"] == c["source"]
        assert sorted(spec["reduced"]) == sorted(c["reduced"])
        assert spec["guarantees"], "a deployment states its guarantees"
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    assert len({c["name"] for c in bench["configs"]}) == len(bench["configs"])


def check_workloads(bench, repo):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def check_end_to_end_metrics(bench, repo):
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


def check_per_layer_metrics(bench, repo):
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


def check_files_under_paths_have_plain_names(bench, repo):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        for f in (repo / p).rglob("*"):
            rel = f.relative_to(repo).as_posix()
            if "__pycache__" in rel or rel.endswith(".pyc"):
                continue
            assert ok.match(rel), rel


def check_traffic_mixes_are_data_files_one_generator_reads(bench, repo):
    for path in sorted((repo / "benchmark" / "traffic").glob("*.json")):
        spec = json.loads(path.read_text())
        assert spec["name"] == path.stem and spec["loop"] == "closed"
        assert spec["end_to_end"], "the mix says how its metrics are reckoned"


def check_metric_files_and_entries_pair_up(bench, repo):
    """``BENCHMARK.json`` alone says which cell reports which metric: no
    file under ``metrics/`` carries a ``workloads`` key (a second copy
    that nothing reads made every new cell edit files that were there),
    every file has an entry and every entry a file (an orphan of either
    kind is how a metric that reads nothing lingers)."""
    files = {}
    for path in sorted((repo / "benchmark" / "metrics").iterdir()):
        assert path.suffix == ".json", path.name
        files[path.stem] = json.loads(path.read_text())
    for stem, spec in files.items():
        assert "workloads" not in spec, stem
        assert spec["name"] == stem
    assert set(files) == {m["name"] for m in bench["per_layer"]}


CONTRACT = [check_top_level_keys_and_limits,
            check_command_names_only_files_under_paths,
            check_configs, check_workloads, check_end_to_end_metrics,
            check_per_layer_metrics, check_files_under_paths_have_plain_names,
            check_traffic_mixes_are_data_files_one_generator_reads,
            check_metric_files_and_entries_pair_up]


def test_top_level_keys_and_limits(bench):
    check_top_level_keys_and_limits(bench, REPO)


def test_command_names_only_files_under_paths(bench):
    check_command_names_only_files_under_paths(bench, REPO)


def test_configs(bench):
    check_configs(bench, REPO)


def test_workloads(bench):
    check_workloads(bench, REPO)


def test_end_to_end_metrics(bench):
    check_end_to_end_metrics(bench, REPO)


def test_per_layer_metrics(bench):
    check_per_layer_metrics(bench, REPO)


def test_files_under_paths_have_plain_names(bench):
    check_files_under_paths_have_plain_names(bench, REPO)


def test_traffic_mixes_are_data_files_one_generator_reads(bench):
    check_traffic_mixes_are_data_files_one_generator_reads(bench, REPO)


def test_metric_files_carry_no_workloads_and_pair_up_with_entries(bench):
    check_metric_files_and_entries_pair_up(bench, REPO)


def check_load_cell(cell, bench, repo):
    got = manifest.load_cell(cell, repo)
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


@pytest.mark.parametrize("cell", CELLS)
def test_load_cell_finds_config_traffic_and_metric_files_by_name(cell, bench):
    check_load_cell(cell, bench, REPO)


def test_an_unknown_cell_is_refused_by_name():
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.load_cell("no_such_cell")


# -- a later PR's cells, added to a copy of the benchmark ---------------------

READ = "rados_seqread_4m_qd16"
SIBLING = "rados_sibling_read_4m_qd16"     # what the degraded-pool PR adds
SIBLING_METRIC = "ec_decode_ms"


def _hashes(root):
    return {f.relative_to(root).as_posix():
            hashlib.sha256(f.read_bytes()).hexdigest()
            for f in sorted(root.rglob("*")) if f.is_file()}


def _entry(metric, cells):
    return {**{k: metric[k] for k in ("name", "unit", "better", "source",
                                      "layer", "moves")},
            "workloads": cells}


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of ``benchmark/`` and ``BENCHMARK.json`` with two cells
    ADDED the way a ``model_config`` PR may: new files, new entries, and
    a name appended to the ``workloads`` of entries that are there.

    - a rados sibling: a new configuration file (the k=8 m=4 pool under
      another name and source), a workload on the EXISTING traffic
      ``seqread_4m_qd16``, its name appended to ``client_bw``,
      ``op_lat_p95`` and every per-layer entry that lists the read cell,
      and one new metric (a file and an entry);
    - an ec cell with a configuration, a traffic mix and a metric of its
      own.

    Returns ``(root, hashes of the files before the additions)``."""
    root = tmp_path_factory.mktemp("grown")
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(root / "benchmark")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())

    cfg = json.loads((REPO / "benchmark/configs/rados_bench_ec84.json")
                     .read_text())
    cfg["name"] = "rados_bench_ec84_sibling"
    cfg["source"] = cfg["source"].replace("(BASELINE.json config 4)",
                                          "as a later PR's deployment")
    (root / "benchmark/configs/rados_bench_ec84_sibling.json").write_text(
        json.dumps(cfg, indent=1))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "rados_bench_ec84")
    bench["configs"].append({**entry, "name": cfg["name"],
                             "source": cfg["source"],
                             "file": "benchmark/configs/"
                                     "rados_bench_ec84_sibling.json"})
    bench["workloads"].append({"name": SIBLING, "config": cfg["name"],
                               "traffic": "seqread_4m_qd16", "chips": 1,
                               "why": "the read cell's traffic on a sibling "
                                      "deployment"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if READ in m.get("workloads", []):
            m["workloads"].append(SIBLING)
    metric = {"name": SIBLING_METRIC, "layer": "codec", "unit": "ms",
              "better": "lower", "source": "program_span",
              "moves": "client_bw",
              "what": "mean of ec.decode: a get's decode of its k shards",
              "reader": "span_mean_ms",
              "params": {"spans": ["span:ec.decode"], "per": "event"}}
    (root / f"benchmark/metrics/{SIBLING_METRIC}.json").write_text(
        json.dumps(metric, indent=1))
    bench["per_layer"].append(_entry(metric, [SIBLING]))

    cfg = json.loads((REPO / "benchmark/configs/ec_bench_k8m4_1m.json")
                     .read_text())
    cfg["name"] = "ec_bench_k4m2_64k"
    cfg["source"] = cfg["source"].replace("--size 1048576", "--size 65536")
    cfg["driver_params"]["size"] = 65536
    (root / "benchmark/configs/ec_bench_k4m2_64k.json").write_text(
        json.dumps(cfg))
    mix = json.loads((REPO / "benchmark/traffic/single_1m.json").read_text())
    mix["name"] = "single_64k"
    (root / "benchmark/traffic/single_64k.json").write_text(json.dumps(mix))
    metric = {"name": "codec_encode_ms", "layer": "codec", "unit": "ms",
              "better": "lower", "source": "program_span",
              "moves": "codec_bw", "what": "mean of codec.encode",
              "reader": "span_mean_ms",
              "params": {"spans": ["span:codec.encode"]}}
    (root / "benchmark/metrics/codec_encode_ms.json").write_text(
        json.dumps(metric))
    bench["configs"].append({"name": "ec_bench_k4m2_64k",
                             "source": cfg["source"],
                             "file": "benchmark/configs/ec_bench_k4m2_64k.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "ec_single_64k",
                               "config": "ec_bench_k4m2_64k",
                               "traffic": "single_64k", "chips": 1,
                               "why": "x"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "codec_bw")["workloads"].append("ec_single_64k")
    bench["per_layer"].append(_entry(metric, ["ec_single_64k"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root, before


def test_a_new_cell_is_files_and_entries_only(grown):
    """Additions only: every file the benchmark had is byte for byte what
    it was, every entry ``BENCHMARK.json`` had is what it was but for
    names appended to ``workloads``, and the harness finds the new cells'
    files by name."""
    root, before = grown
    after = _hashes(root / "benchmark")
    assert {f: after.get(f) for f in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/ec_bench_k4m2_64k.json",
        "configs/rados_bench_ec84_sibling.json",
        "metrics/codec_encode_ms.json", f"metrics/{SIBLING_METRIC}.json",
        "traffic/single_64k.json"]
    was = manifest.load_benchmark()
    now = manifest.load_benchmark(root)
    for key, value in was.items():
        if not isinstance(value, list) or key in ("command", "paths"):
            assert now[key] == value, key
            continue
        for old, new in zip(value, now[key]):     # the old entries lead
            cells = old.get("workloads", [])
            assert new.get("workloads", [])[:len(cells)] == cells
            assert {**new, "workloads": cells} == {**old, "workloads": cells}
    got = manifest.load_cell("ec_single_64k", repo=root)
    assert got["config"]["driver_params"]["size"] == 65536
    assert got["traffic"]["name"] == "single_64k"
    assert [m["name"] for m in got["per_layer"]] == ["codec_encode_ms"]
    assert {m["name"] for m in got["end_to_end"]} == {"codec_bw", "setup_s"}
    read = manifest.load_cell(READ, repo=root)
    sib = manifest.load_cell(SIBLING, repo=root)
    assert sib["config"]["name"] == "rados_bench_ec84_sibling"
    assert sib["traffic"] == read["traffic"]
    assert [m["name"] for m in sib["per_layer"]] == \
        [m["name"] for m in read["per_layer"]] + [SIBLING_METRIC]
    assert sib["end_to_end"] == read["end_to_end"]


@pytest.mark.parametrize("check", CONTRACT, ids=lambda f: f.__name__[6:])
def test_the_whole_contract_holds_with_the_new_cells(check, grown):
    root, _ = grown
    check(manifest.load_benchmark(root), root)


def test_load_cell_finds_every_cell_of_the_grown_tree(grown):
    root, _ = grown
    bench = manifest.load_benchmark(root)
    assert len(bench["workloads"]) == len(CELLS) + 2
    for w in bench["workloads"]:
        check_load_cell(w["name"], bench, root)


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "untraced"])
def test_the_new_rados_cell_rehearses_correct_and_reports_its_metrics(
        trace, grown):
    """The sibling runs through the harness as it stands (CPU, tiny
    sizes, the look for a chip skipped): traced, it reports what the
    read cell's traced rehearsal reports plus its own new metric;
    untraced, ``client_bw``, ``op_lat_p95`` and ``setup_s``."""
    from benchmark import run as bench_run
    root, _ = grown
    res = bench_run.run_cell(SIBLING, 2147483743, 2.0 if trace else 1.0,
                             trace, rehearsal=True, repo=root)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    if not trace:
        assert set(res["metrics"]) == {"client_bw", "op_lat_p95", "setup_s"}
        return
    spec = manifest.load_cell(READ, repo=root)
    host_side = {m["name"] for m in spec["per_layer"]
                 if m["source"] != "device_trace"}
    assert set(res["metrics"]) == host_side | {SIBLING_METRIC}
    assert res["metrics"][SIBLING_METRIC]["value"] > 0
    assert res["metrics"]["pipeline_unpack_ms"]["value"] > 0
