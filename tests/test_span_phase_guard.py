"""Guard: spans in the serving/recovery/pipeline layers must map to a
DECLARED critical-path phase.

Thin wrapper over the ``span-phase`` rule in
:mod:`ceph_tpu.analysis.rules_guards` (ISSUE 15); semantics unchanged —
an undeclared span silently files its self-time under ``other`` in the
latency decomposition, so every span opened (or
``tracer.observe()``-stamped) in ``exec/``, ``recovery/`` and
``ops/pipeline.py`` must be declared in ``critpath.SPAN_PHASES`` or
carry an explicit constant ``phase=``.
"""
import ceph_tpu.analysis as A
from ceph_tpu.common.critpath import is_declared


def test_spans_in_serving_recovery_pipeline_declare_a_phase():
    offenders = [f.render() for f in A.run_rules(
        A.default_index(), ("span-phase",))]
    assert not offenders, (
        "undeclared span phases (attribution would file these under "
        "'other'):\n" + "\n".join(offenders))


def test_scan_targets_still_exist():
    idx = A.default_index()
    for sub in ("ceph_tpu/exec", "ceph_tpu/recovery",
                "ceph_tpu/ops/pipeline.py"):
        assert idx.iter_modules((sub,)), f"stale scan target: {sub}"


def test_guard_catches_an_undeclared_span():
    bad = ("def f(tr):\n"
           "    with tr.span('totally.new.span'):\n"
           "        pass\n"
           "    with tr.span('ec.encode'):\n"       # declared: fine
           "        pass\n"
           "    with tr.span('x.y', phase='device'):\n"  # explicit: fine
           "        pass\n")
    found = A.run_rule_on_sources("span-phase", {"bad.py": bad})
    assert len(found) == 1
    assert "totally.new.span" in found[0].message


def test_registry_covers_the_process_wide_span_inventory():
    """The spans the rest of the codebase emits on the client-op path
    must stay declared too — this is the list the decomposition's
    fixtures and docs are written against."""
    for name in ("client.op", "osd.op", "osd.queue_wait", "ec.encode",
                 "ec.decode", "codec.encode", "codec.decode",
                 "serving.batch_wait", "serving.admission",
                 "pipeline.complete", "pipeline.host_fallback",
                 "net.resend", "client.op_retry", "recovery.wave",
                 "osd.ECSubWrite", "rpc.put"):
        assert is_declared(name), name
