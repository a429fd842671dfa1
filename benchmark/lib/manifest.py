"""Find a cell's files by the names ``BENCHMARK.json`` gives: a cell is
a configuration and a traffic mix, a per-layer metric is a file of its
own.  Nothing here names a cell."""
from __future__ import annotations

import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parent


class ManifestError(ValueError):
    pass


def _load(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"{path.relative_to(REPO)} is missing") from None


def load_benchmark(repo: Path = REPO) -> dict:
    return _load(repo / "BENCHMARK.json")


def load_cell(name: str, repo: Path = REPO) -> dict:
    """``{name, chips, config, traffic, end_to_end, per_layer}`` of one
    workload: the configuration's and the traffic's own files, the
    end-to-end metrics the cell reports, and the files of the per-layer
    metrics whose ``BENCHMARK.json`` entries list it under ``workloads``
    (the entry is the one list; a metric's file has no such key)."""
    bench = load_benchmark(repo)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (it has "
            f"{[w['name'] for w in bench['workloads']]})")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == cell["config"]), None)
    if cfg_entry is None:
        raise ManifestError(f"workload {name!r} names configuration "
                            f"{cell['config']!r}, which is not listed")
    config = _load(repo / cfg_entry["file"])
    traffic = _load(repo / Path(cfg_entry["file"]).parents[1] / "traffic"
                    / f"{cell['traffic']}.json")
    metrics_dir = repo / Path(cfg_entry["file"]).parents[1] / "metrics"
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    per_layer = []
    for entry in bench["per_layer"]:
        if name not in entry.get("workloads", [name]):
            continue
        spec = _load(metrics_dir / f"{entry['name']}.json")
        if spec.get("name") != entry["name"]:
            raise ManifestError(
                f"metrics/{entry['name']}.json names {spec.get('name')!r}")
        per_layer.append(spec)
    return {"name": name, "chips": cell["chips"], "config": config,
            "config_name": cell["config"], "traffic": traffic,
            "traffic_name": cell["traffic"], "end_to_end": end_to_end,
            "per_layer": per_layer}
