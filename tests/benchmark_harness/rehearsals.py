"""Traced REHEARSALS (CPU, tiny sizes, the look for a chip skipped) shared
by the harness's invariant checks: one run a cell and tree in a process,
on one seed, so that the checks of several test files that read the same
cell of the same tree do not each pay for a run.

A check that spies on the run (a patched driver method, a reader) makes
its own run instead: a shared one may have been made before the spy."""
import functools
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import manifest  # noqa: E402

SEED = 2147484011


@functools.cache
def traced(cell: str, repo: Path = REPO) -> dict:
    """The result of one 2 s traced rehearsal of ``cell`` in the tree at
    ``repo`` (not to be changed by the caller)."""
    return bench_run.run_cell(cell, SEED, 2.0, True, rehearsal=True,
                              repo=repo)


def host_side(cell: str, repo: Path = REPO) -> set[str]:
    """The per-layer metrics a traced rehearsal of ``cell`` prints: all
    that the cell's own entries list but those read from the device's
    plane, which a CPU capture has not."""
    return {m["name"] for m in manifest.load_cell(cell, repo)["per_layer"]
            if m["source"] != "device_trace"}
