"""Context: the per-process service bundle (CephContext analog).

Mirror of the reference's ``CephContext`` (reference:
src/common/ceph_context.cc, ~950 LoC): owns the config store, the log, the
perf-counter collection, and the admin socket, and pre-registers the
standard admin commands (``perf dump``, ``config show``, ``config set``,
``log dump``).  Daemon-ish objects (ECBackend, shards) take a Context and
hang their counters/commands off it.
"""
from __future__ import annotations

from . import tracer as tracer_mod
from .admin_socket import AdminSocket
from .log import Log
from .options import ConfigProxy
from .perf_counters import PerfCountersCollection


class Context:
    def __init__(self, overrides: dict | None = None):
        self.conf = ConfigProxy(overrides)
        self.log = Log(self.conf)
        self.perf = PerfCountersCollection()
        self.admin_socket = AdminSocket()
        # observability fast path (ISSUE 18): adopt the kill-switch and
        # the tracer's sampling/slow-promotion knobs from this conf and
        # follow live updates.  Both targets are process-wide (there is
        # ONE default tracer), matching the reference's md_config
        # observers feeding process singletons.
        from . import instruments
        instruments.wire_config(self.conf)
        tracer_mod.wire_config(self.conf)
        # the process-wide jit telemetry collection: shared by every
        # Context so any `perf dump` / prometheus render carries it
        self.perf.add(tracer_mod.jit_perf_counters())
        # CPU time beside wall time, process-wide too: by span name for
        # the spans that read their thread's CPU clock, and by role for
        # the service threads
        self.perf.add(tracer_mod.span_cpu_perf_counters())
        self.perf.add(tracer_mod.thread_cpu_perf_counters())
        # the device-time attribution ledger (who occupies the chip, by
        # owner class) — process-wide for the same reason
        from . import device_attribution
        self.perf.add(device_attribution.perf_counters())

        self.admin_socket.register(
            "perf dump", lambda **kw: self.perf.perf_dump(),
            "dump all perf counters")
        self.admin_socket.register(
            "config show", lambda **kw: self.conf.show_config(),
            "show all config values")
        self.admin_socket.register(
            "config diff", lambda **kw: self.conf.diff(),
            "show non-default config values")

        def _config_set(name: str = "", value: str = "", **kw):
            self.conf.set(name, value)
            return {"success": f"{name} = {value}"}
        self.admin_socket.register("config set", _config_set,
                                   "set a config option")
        self.admin_socket.register(
            "log dump", lambda **kw: self.log.dump_recent(),
            "dump recent log entries")
        self.admin_socket.register(
            "trace dump",
            lambda **kw: tracer_mod.default_tracer().dump(),
            "dump the span tracer as Chrome trace-event JSON")
        self.admin_socket.register(
            "trace reset",
            lambda **kw: tracer_mod.default_tracer().reset(),
            "clear the span tracer ring buffer and histograms")
        self.admin_socket.register(
            "jit dump", lambda **kw: tracer_mod.jit_dump(),
            "per-(function, shape) JIT compile/dispatch telemetry")

        def _device_dump(initialize: str = "", **kw):
            from . import device_telemetry
            # SAFE by default: initializing a backend from an admin call
            # takes the chip, which fails or hangs when another process
            # holds it (what device_telemetry exists to avoid).
            # Operators opt in with initialize=true when this process
            # is meant to own the device.
            return device_telemetry.refresh(
                self, initialize=str(initialize).lower()
                in ("1", "true", "yes"))
        self.admin_socket.register(
            "device dump", _device_dump,
            "JAX/XLA device inventory + memory/compile-cache telemetry "
            "(pass initialize=true to force backend init — takes the "
            "chip, and hangs if another process holds it)")
        self.admin_socket.register(
            "jit reset", lambda **kw: tracer_mod.jit_reset(),
            "clear the per-(function, shape) JIT telemetry records")

        def _device_top(limit: str = "10", **kw):
            return device_attribution.device_top(int(limit))
        self.admin_socket.register(
            "device top", _device_top,
            "device occupancy by owner class (client/serving/recovery/"
            "scrub/rebalance) + costliest compiled executables")

        def _device_roofline(limit: str = "20", **kw):
            from . import roofline
            return roofline.report(int(limit), cct=self)
        self.admin_socket.register(
            "device roofline", _device_roofline,
            "per-executable roofline ledger: achieved vs peak FLOP/s "
            "and HBM B/s, arithmetic intensity, memory/compute-bound "
            "classification")

    def dout(self, subsys: str, level: int, message: str) -> None:
        self.log.dout(subsys, level, message)


_default: Context | None = None


def default_context() -> Context:
    global _default
    if _default is None:
        _default = Context()
    return _default
