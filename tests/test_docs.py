"""The documents name only what exists.

Every back-ticked repo path in ``README.md``, ``COMPONENTS.md``,
``BASELINE.md``, ``.claude/skills/verify/SKILL.md`` and the module column
of ``PERF.md`` §3 must be a file or directory of this tree, and every
back-ticked option name in ``README.md`` must be in ``OPTIONS``.  A
document that sends a reader to a harness, a gate or a switch that was
deleted fails here, not in the reader's shell.

What counts as a repo path: an inline code span (or a word of a fenced
block) made of path characters only that carries a file extension, ends
in ``/``, or starts with one of the tree's top-level directories or a
``ceph_tpu`` subpackage.  ``:line`` and ``::test`` suffixes are dropped.
Paths of the upstream project (``src/...``, ``qa/...``) are references
to another tree and are not checked.  A path may be written relative to
the root, to ``ceph_tpu/`` or as the tail of a longer path
(``lib/readers.py``), as the documents do.
"""
from __future__ import annotations

import functools
import json
import os
import re
from pathlib import Path

import pytest

from ceph_tpu.common.options import OPTIONS

ROOT = Path(__file__).resolve().parent.parent
_PRUNE = {".git", "chiprun_out", ".bench_checkout", ".bench_runs",
          ".scratch", ".jax_cache", "__pycache__", ".pytest_cache", "build"}
_EXTS = {"py", "md", "json", "jsonl", "cc", "h", "sh", "so", "txt"}
_UPSTREAM = ("src/", "qa/", "doc/")
_PATH_CHARS = re.compile(r"^[A-Za-z0-9_.\-/]+$")
_SUFFIX = re.compile(r"(::[\w\[\]-]+|:\d+(-\d+)?(,\d+(-\d+)?)*)+$")


@functools.cache
def _tree() -> tuple[frozenset[str], frozenset[str], frozenset[str]]:
    """(files, dirs, heads): the tree's paths, and the first components
    a repo path may start with — a top-level entry or a ``ceph_tpu``
    subpackage."""
    files, dirs = set(), set()
    for base, dnames, fnames in os.walk(ROOT):
        dnames[:] = [d for d in dnames if d not in _PRUNE]
        rel = Path(base).relative_to(ROOT)
        dirs.update((rel / d).as_posix() for d in dnames)
        files.update((rel / f).as_posix() for f in fnames)
    heads = {p.split("/")[0] for p in files | dirs} \
        | {d.split("/")[1] for d in dirs
           if d.startswith("ceph_tpu/") and d.count("/") == 1}
    return frozenset(files), frozenset(dirs), frozenset(heads)


def _spans(text: str) -> list[str]:
    """Inline code spans, plus the words of fenced blocks that start
    with a top-level directory (the commands a reader would paste)."""
    out: list[str] = []
    fenced = False
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
            continue
        if fenced:
            out += [w for w in line.split()
                    if "/" in w and w.split("/")[0] in _tree()[2]]
        else:
            out += re.findall(r"`([^`\n]+)`", line)
    return out


def _repo_path(span: str) -> str | None:
    """The span as a path to look up, or None if it is not one."""
    tok = _SUFFIX.sub("", span.strip().rstrip(".,;:"))
    if not tok or not _PATH_CHARS.match(tok) or tok[0] in "/-" \
            or ".." in tok or tok.startswith(_UPSTREAM):
        return None
    head = tok.split("/")[0]
    ext = tok.rsplit(".", 1)[-1] if "." in tok.rsplit("/", 1)[-1] else ""
    if ext in _EXTS or tok.endswith("/"):
        return tok
    if "/" in tok and head in _tree()[2]:
        return tok
    return None


def _exists(tok: str) -> bool:
    files, dirs, _heads = _tree()
    t = tok.rstrip("/")
    for cand in (t, f"ceph_tpu/{t}"):
        if cand in dirs or (not tok.endswith("/") and cand in files):
            return True
    pool = dirs if tok.endswith("/") else files | dirs
    if any(p.endswith("/" + t) for p in pool):
        return True
    # `ops/pallas_kernels.gf_apply_pallas`: a module path with a name
    # in it — the module must exist
    leaf = t.rsplit("/", 1)[-1]
    if "/" in t and "." in leaf and leaf.rsplit(".", 1)[-1] not in _EXTS:
        return _exists(t[:len(t) - len(leaf)] + leaf.split(".")[0] + ".py")
    return False


def _missing_paths(text: str) -> list[str]:
    seen, missing = set(), []
    for span in _spans(text):
        tok = _repo_path(span)
        if tok is None or tok in seen:
            continue
        seen.add(tok)
        if not _exists(tok):
            missing.append(tok)
    return missing


def _perf_layers_modules() -> str:
    """PERF.md §3, the tables' module / site columns only."""
    text = (ROOT / "PERF.md").read_text()
    sec = text[text.index("## 3. Layers"):text.index("## 4. Cells")]
    cells: list[str] = []
    for line in sec.splitlines():
        cols = [c.strip() for c in line.strip().strip("|").split("|")]
        if not line.startswith("|") or len(cols) < 4 \
                or set(cols[0]) <= {"-"}:
            continue
        # first table: layer | module | ...; second: ... | site | cells
        cells.append(cols[1])
        cells.append(cols[-2])
    return "\n".join(cells)


DOCUMENTS = {
    "README.md": lambda: (ROOT / "README.md").read_text(),
    "COMPONENTS.md": lambda: (ROOT / "COMPONENTS.md").read_text(),
    "BASELINE.md": lambda: (ROOT / "BASELINE.md").read_text(),
    "PERF.md-layers": _perf_layers_modules,
    "verify-SKILL.md": lambda: (
        ROOT / ".claude/skills/verify/SKILL.md").read_text(),
}


@pytest.mark.parametrize("doc", sorted(DOCUMENTS))
def test_every_backticked_repo_path_exists(doc):
    text = DOCUMENTS[doc]()
    assert len([s for s in _spans(text) if _repo_path(s)]) >= 3, \
        f"{doc}: the reader found no paths to check"
    missing = _missing_paths(text)
    assert not missing, f"{doc} names paths that do not exist: {missing}"


def _other_names() -> set[str]:
    """What else a snake_case span may name: the benchmark's metrics,
    cells and deployments, a module, a plugin."""
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = {e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in b[key]}
    stems = {Path(f).stem for f in _tree()[0]}
    return names | {n.split(".")[0] for n in names} | stems \
        | {s.removeprefix("plugin_") for s in stems}


def test_every_backticked_option_in_readme_is_registered():
    """A snake_case span in an option namespace (``ms_``, ``osd_``,
    ``slo_`` ...) that names nothing else must be an option."""
    options = {o.name for o in OPTIONS}
    prefixes = {name.split("_")[0] + "_" for name in options}
    known = _other_names()
    text = (ROOT / "README.md").read_text()
    named = [s for s in re.findall(r"`([a-z][a-z0-9]*(?:_[a-z0-9]+)+)`",
                                   text)
             if s.split("_")[0] + "_" in prefixes and s not in known]
    assert len(named) >= 5, "README.md names no options to check"
    unknown = sorted({s for s in named if s not in options})
    assert not unknown, f"README.md names options not in OPTIONS: {unknown}"


@pytest.mark.parametrize("span, want", [
    ("tools/no_such_gate.py", False),        # a top-level dir, no file
    ("NO_SUCH_RECORD_r08.json", False),      # a bare name with an extension
    ("smoke.py", False),                     # not the tail of chip_smoke.py
    ("ceph_tpu/bench/ec_bench.py", True),
    ("net.py:294-313", True),                # relative to ceph_tpu/
    ("lib/readers.py", True),                # tail of benchmark/lib/...
    ("ops/pallas_kernels.gf_apply_pallas", True),
    ("tests/test_zero_copy.py::TestEndToEnd", True),
    ("benchmark/", True),
    ("osd/no_such_module.py", False),
])
def test_the_checker_itself(span, want):
    tok = _repo_path(span)
    assert tok is not None, span
    assert _exists(tok) is want


@pytest.mark.parametrize("span", [
    "k/m", "put/get", "src/common/obj_bencher.cc", "/tmp/d",
    "<data_dir>/clusterlog", "client_bw", "python3 benchmark/run.py",
    "rs_kernels.crc32c_rows", "flight-*.json"])
def test_what_is_not_a_repo_path(span):
    assert _repo_path(span) is None
