"""Process-wide instrumentation kill-switch.

ISSUE 18's measurement lever: every hot-path instrument (tracer spans/
instants/completes, wire accounting, rpc latency observation) checks
:func:`enabled` before doing any work, so ``instruments_enabled=false``
turns the whole instrumentation plane into cheap no-op guards.  One
workload run twice — instruments on, then off — gives what they cost
(PERF.md §6, PR 25).

The flag is deliberately a bare module global read without a lock: the
hot paths pay one attribute load + truth test per instrument call, and
a torn read is impossible under the GIL (the value is a bool).  Flips
are rare (``config set instruments_enabled``) and take
effect on the next instrument call.

What the switch does NOT stub: perf-counter math that the control plane
*acts on* (throttle gauges, shed ladders, health inputs) keeps running —
observability must be free to drop, behavior must not change with it.
"""
from __future__ import annotations

from contextlib import contextmanager

_enabled = True


def enabled() -> bool:
    """The hot-path guard: True when the instruments should record."""
    return _enabled


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


@contextmanager
def disabled():
    """Scoped kill-switch: instruments off inside
    the block, restored to the PRIOR state on exit."""
    prior = _enabled
    set_enabled(False)
    try:
        yield
    finally:
        set_enabled(prior)


def wire_config(conf) -> None:
    """Adopt ``instruments_enabled`` from a ConfigProxy and follow live
    updates (``config set instruments_enabled false`` on a running
    cluster flips the process-wide switch, like every other option)."""
    if "instruments_enabled" not in conf.schema:
        return
    set_enabled(bool(conf.get("instruments_enabled")))
    conf.add_observer("instruments_enabled",
                      lambda _name, v: set_enabled(bool(v)))
