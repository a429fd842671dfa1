"""Tier-1 entry point for the static-analysis engine (ISSUE 15).

Three layers:

- the CLI gate — ``python -m tools.ceph_lint --baseline
  .ceph_lint_baseline.json`` must run clean over the repo (in-process
  so the already-imported runtime registries are reused);
- fixture proof for every deep rule — each must flag its seeded-bad
  fixture package (``tests/lint_fixtures/``) and pass the clean twin,
  so the rules are tested against known ground truth, not just
  self-hosted;
- engine internals — index resolution tiers, the baseline round trip,
  and the rule registry the wrapper tests lean on.
"""
import json
from pathlib import Path

import pytest

import ceph_tpu.analysis as A
from tools import ceph_lint

ROOT = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"


def _fixture(name: str) -> str:
    return (FIXTURES / name).read_text()


# -- the CI gate -------------------------------------------------------------

def test_cli_runs_clean_with_baseline():
    rc = ceph_lint.main(["--baseline",
                         str(ROOT / ".ceph_lint_baseline.json")])
    assert rc == 0, "new (non-baselined) lint findings — run " \
        "python -m tools.ceph_lint --baseline .ceph_lint_baseline.json"


def test_cli_fails_without_baseline_iff_findings_exist():
    findings = A.run_rules(A.default_index())
    rc = ceph_lint.main([])
    assert rc == (1 if findings else 0)


def test_cli_list_rules_and_unknown_rule():
    assert ceph_lint.main(["--list-rules"]) == 0
    assert ceph_lint.main(["--rules", "no-such-rule"]) == 2


def test_baseline_entries_all_carry_justifications():
    base = A.load_baseline(ROOT / ".ceph_lint_baseline.json")
    assert all(j and len(j) > 20 for j in base.values()), \
        "every baseline suppression needs a real justification"


def test_json_summary_has_no_new_and_no_stale_entries(capsys):
    """``--json`` over the live tree: every finding is baselined and
    every baseline entry still fires (the file stays honest)."""
    rc = ceph_lint.main(["--json", "--baseline",
                         str(ROOT / ".ceph_lint_baseline.json")])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    s = doc["summary"]
    assert s["new"] == 0 and s["stale_baseline"] == 0
    assert s["total"] == s["baselined"] == len(doc["findings"])
    assert all(f["baselined"] for f in doc["findings"])


# -- fixture proof: lock-order ----------------------------------------------

def test_lock_order_rule_flags_seeded_cycle():
    found = A.run_rule_on_sources(
        "lock-order-cycle", {"cycle.py": _fixture("lock_cycle_bad.py")})
    assert len(found) == 1
    assert "Alpha._lock" in found[0].message
    assert "Beta._lock" in found[0].message


def test_lock_order_rule_passes_clean_twin():
    assert A.run_rule_on_sources(
        "lock-order-cycle",
        {"cycle.py": _fixture("lock_cycle_clean.py")}) == []


def test_callback_under_lock_flags_send_and_stored_callback():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self, on_done):\n"
        "        self._lock = threading.Lock()\n"
        "        self.on_done = on_done\n"
        "    def finish(self, conn):\n"
        "        with self._lock:\n"
        "            self.on_done(self)\n"
        "            conn.send(b'x')\n"
        "    def ok(self, conn):\n"
        "        with self._lock:\n"
        "            n = 1\n"
        "        self.on_done(self)\n"
        "        conn.send(b'x')\n")
    found = A.run_rule_on_sources("callback-under-lock",
                                  {"cb.py": src})
    assert len(found) == 2
    kinds = {f.message.split()[0] for f in found}
    assert kinds == {"callback", "send"}


# -- fixture proof: thread contexts ------------------------------------------

def test_cross_thread_rule_flags_unlocked_mutation():
    found = A.run_rule_on_sources(
        "cross-thread-unlocked",
        {"w.py": _fixture("cross_thread_bad.py")})
    assert len(found) == 1
    f = found[0]
    assert "Worker.count" in f.message
    assert "caller" in f.message and "thread:Worker._loop" in f.message


def test_cross_thread_rule_passes_locked_twin():
    assert A.run_rule_on_sources(
        "cross-thread-unlocked",
        {"w.py": _fixture("cross_thread_clean.py")}) == []


# -- fixture proof: hot-path copies ------------------------------------------

def test_hot_path_copy_flags_all_three_shapes():
    found = A.run_rule_on_sources(
        "hot-path-copy", {"relay.py": _fixture("hot_copy_bad.py")})
    msgs = sorted(f.message for f in found)
    assert len(found) == 3, msgs
    assert any("bytes(view)" in m for m in msgs)
    assert any("payload.tobytes()" in m for m in msgs)
    assert any("pickle.dumps" in m for m in msgs)


def test_hot_path_copy_passes_ids_and_boundaries():
    assert A.run_rule_on_sources(
        "hot-path-copy",
        {"relay.py": _fixture("hot_copy_clean.py")}) == []


# -- fixture proof: jax dispatch purity --------------------------------------

def test_jit_host_sync_flags_direct_and_transitive():
    found = A.run_rule_on_sources(
        "jit-host-sync", {"bad.py": _fixture("jit_sync_bad.py")})
    msgs = " | ".join(f.message for f in found)
    assert "device_get" in msgs and "direct_sync" in msgs
    assert "block_until_ready" in msgs and "transitive_sync" in msgs


def test_jit_donated_reuse_flags_read_after_dispatch():
    found = A.run_rule_on_sources(
        "jit-donated-reuse", {"bad.py": _fixture("jit_sync_bad.py")})
    assert len(found) == 1
    assert "'buf'" in found[0].message


def test_jit_rules_pass_clean_twin():
    clean = {"clean.py": _fixture("jit_sync_clean.py")}
    for rid in ("jit-host-sync", "jit-donated-reuse",
                "jit-nonstatic-shape", "jit-traced-control-flow"):
        assert A.run_rule_on_sources(rid, dict(clean)) == [], rid


def test_jit_recompile_rules_flag_nonstatic_params():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import functools\n"
        "@functools.partial(jax.jit, static_argnames=('k',))\n"
        "def f(x, n, k):\n"
        "    pad = jnp.zeros(n)\n"
        "    if n > 0:\n"
        "        x = x + pad\n"
        "    for _ in range(k):\n"
        "        x = x * 2\n"
        "    return x\n")
    shape = A.run_rule_on_sources("jit-nonstatic-shape",
                                  {"f.py": src})
    assert [f.message for f in shape] and "'n'" in shape[0].message
    flow = A.run_rule_on_sources("jit-traced-control-flow",
                                 {"f.py": src})
    assert len(flow) == 1 and "'n'" in flow[0].message  # k is static


# -- engine internals --------------------------------------------------------

def test_index_resolution_tiers():
    idx = A.default_index()
    conn = idx.modules["ceph_tpu/msg/connection.py"]
    send = conn.functions["AsyncConnection.send"]
    # self-method tier
    import ast as _ast
    calls = [n for n in _ast.walk(send.node)
             if isinstance(n, _ast.Call)
             and isinstance(n.func, _ast.Attribute)
             and n.func.attr == "_account_tx"]
    assert calls
    hit = idx.resolve_call(send, calls[0])
    assert [h.qualname for h in hit] == ["AsyncConnection._account_tx"]
    # callback-binding tier: AsyncConnection.on_message was bound at
    # construction sites to the server/mux handlers
    handlers = idx.callback_bindings.get(("AsyncConnection",
                                         "on_message"), set())
    assert any("_on_message" in r for r in handlers)


def test_baseline_round_trip(tmp_path):
    f = A.Finding("lock-order-cycle", "x.py", 3, "error", "msg")
    p = tmp_path / "base.json"
    A.write_baseline([f], "known benign because reasons", p)
    base = A.load_baseline(p)
    assert base[f.key] == "known benign because reasons"
    new, suppressed, stale = A.split_by_baseline([f], base)
    assert (new, suppressed) == ([], [f])
    assert stale == []
    g = A.Finding("lock-order-cycle", "y.py", 1, "error", "other")
    new2, _, stale2 = A.split_by_baseline([g], base)
    assert new2 == [g] and stale2 == [f.key]


def test_rule_registry_complete():
    rules = A.all_rules()
    for rid in ("lock-order-cycle", "callback-under-lock",
                "cross-thread-unlocked", "jit-host-sync",
                "jit-nonstatic-shape", "jit-traced-control-flow",
                "jit-donated-reuse", "no-host-sync", "unbounded-queue",
                "blocking-socket", "thread-spawn-site", "bounded-retry",
                "span-owner", "span-phase", "profiler-confinement",
                "bare-clock", "counter-help", "percentile-redef",
                "wire-sizer", "hot-path-copy"):
        assert rid in rules, rid
        assert rules[rid].severity in ("error", "warning")
        assert rules[rid].description


def test_findings_render_path_line_severity_rule():
    f = A.Finding("counter-help", "ceph_tpu/x.py", 12, "error", "boom")
    assert f.render() == "ceph_tpu/x.py:12: error [counter-help] boom"


def test_analysis_import_stays_jax_free():
    import subprocess
    import sys
    code = ("import sys; import ceph_tpu.analysis; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT)
    assert proc.returncode == 0, "ceph_tpu.analysis must import " \
        "without dragging in jax (rules import registries lazily)"
