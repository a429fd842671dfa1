"""StatsAggregator: rolling perf-counter windows -> PGMap-style rates.

Analog of the reference's MgrStatMonitor/PGMap digest (reference:
src/mon/MgrStatMonitor.cc + src/mon/PGMap.cc ``overall_recovery_summary``
/ ``overall_client_io_rate_summary`` — the 'client: 12 MiB/s wr, 3 op/s'
lines in ``ceph -s``): daemons report counters, the mgr differentiates
them over time, and status renders RATES, not lifetime totals.

Here the source is the process-wide :class:`PerfCountersCollection`: each
``sample()`` flattens every registered collection into a
``(collection, key) -> value`` snapshot appended to a bounded window;
rates are computed between the window's endpoints, summed across the
collections that carry a key (one ``ec_backend.<pg>`` collection per PG —
the cluster rate is their sum, exactly how PGMap sums per-PG deltas).
Counter resets (a collection removed and re-registered) clamp to zero
rather than going negative.

Driving: ``sample()`` is explicit (``Cluster.status()`` ticks it — the
deterministic single-thread design), the prometheus exporter ticks it on
scrape, and ``start()`` runs an optional background sampler at
``mgr_stats_period`` for live `top` output.
"""
from __future__ import annotations

import threading
import time
import weakref
from collections import deque

from ..common import default_context

# live aggregators, for the prometheus rate-gauge export
_AGGREGATORS: "weakref.WeakSet[StatsAggregator]" = weakref.WeakSet()

# collection prefixes whose counters are CLIENT/RECOVERY io (the PG
# backends; one collection per PG instance)
PG_PREFIXES = ("ec_backend.", "replicated_backend.", "pg_backend.")

# collection prefix of the wire accountants (common/wire_accounting.py):
# bus + TCP messenger byte/op counters, per-op-class rollups
WIRE_PREFIXES = ("wire.",)

# the device-occupancy ledger (common/device_attribution.py): its
# `batches` counts work the chip did, never a host-only pipeline item
DEVICE_PREFIXES = ("device_attribution",)


def live_aggregators() -> list["StatsAggregator"]:
    return list(_AGGREGATORS)


def _flatten(perf_dump: dict) -> dict[tuple[str, str], float]:
    """One numeric value per (collection, key): counters/gauges as-is,
    averages and histograms as ``key:count``/``key:sum`` pairs (their
    monotone components — rates over them are ops/s and seconds/s)."""
    flat: dict[tuple[str, str], float] = {}
    for coll, metrics in perf_dump.items():
        for key, v in metrics.items():
            if isinstance(v, dict):
                if "avgcount" in v:                  # avg / time_avg
                    flat[(coll, f"{key}:count")] = float(v["avgcount"])
                    flat[(coll, f"{key}:sum")] = float(v["sum"])
                elif "buckets" in v:                 # histogram
                    flat[(coll, f"{key}:count")] = float(v["count"])
                    flat[(coll, f"{key}:sum")] = float(v["sum"])
            else:
                flat[(coll, key)] = float(v)
    return flat


class StatsAggregator:
    """Bounded time-series of perf snapshots + rate/digest math."""

    def __init__(self, cct=None, name: str = "stats",
                 window: int | None = None, clock=time.monotonic):
        self.cct = cct if cct is not None else default_context()
        self.name = name
        self.clock = clock
        n = int(self.cct.conf.get("mgr_stats_window")
                if window is None else window)
        self._samples: deque[tuple[float, dict]] = deque(maxlen=max(2, n))
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        _AGGREGATORS.add(self)

    # -- sampling ----------------------------------------------------------

    def sample(self, now: float | None = None) -> dict:
        """Scrape every registered collection into the window."""
        flat = _flatten(self.cct.perf.perf_dump())
        t = self.clock() if now is None else now
        with self._lock:
            self._samples.append((t, flat))
        return flat

    def start(self, period: float | None = None) -> "StatsAggregator":
        """Background sampler (live ``ceph_tpu top``); bounded by the
        window deque.  Explicit ``sample()`` calls still work alongside."""
        if self._thread is None:
            p = float(self.cct.conf.get("mgr_stats_period")
                      if period is None else period)
            self._stop.clear()

            def loop():
                while not self._stop.wait(p):
                    self.sample()
            self._thread = threading.Thread(
                target=loop, name=f"stats-{self.name}", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join()
            self._thread = None

    def close(self) -> None:
        self.stop()
        _AGGREGATORS.discard(self)

    # -- window math -------------------------------------------------------

    def _ends(self) -> tuple[tuple[float, dict], tuple[float, dict]] | None:
        with self._lock:
            if len(self._samples) < 2:
                return None
            return self._samples[0], self._samples[-1]

    def span(self) -> float:
        """Seconds covered by the window (0.0 below two samples)."""
        ends = self._ends()
        return ends[1][0] - ends[0][0] if ends else 0.0

    def counter_delta(self, key: str,
                      coll_prefix: tuple[str, ...] | None = None) -> float:
        """Summed increase of counter ``key`` across matching collections
        between the window's endpoints.  A collection that appeared
        mid-window contributes its full value (its counters started at
        zero inside the window); a reset clamps to zero."""
        ends = self._ends()
        if ends is None:
            return 0.0
        (_, first), (_, last) = ends
        total = 0.0
        for (coll, k), v in last.items():
            if k != key:
                continue
            if coll_prefix is not None and \
                    not any(coll.startswith(p) for p in coll_prefix):
                continue
            total += max(0.0, v - first.get((coll, k), 0.0))
        return total

    def rate(self, key: str,
             coll_prefix: tuple[str, ...] | None = None) -> float:
        """``counter_delta / span`` — per-second rate over the window."""
        dt = self.span()
        return self.counter_delta(key, coll_prefix) / dt if dt > 0 else 0.0

    def per_collection_delta(self, key: str,
                             coll_prefix: tuple[str, ...] | None = None
                             ) -> dict[str, float]:
        """Window increase of counter ``key`` PER collection (the heat
        tracker's input: one PG backend collection per PG, so per-
        collection deltas ARE per-PG deltas).  Same born-mid-window and
        reset-clamp semantics as :meth:`counter_delta`."""
        ends = self._ends()
        if ends is None:
            return {}
        (_, first), (_, last) = ends
        out: dict[str, float] = {}
        for (coll, k), v in last.items():
            if k != key:
                continue
            if coll_prefix is not None and \
                    not any(coll.startswith(p) for p in coll_prefix):
                continue
            out[coll] = max(0.0, v - first.get((coll, k), 0.0))
        return out

    def gauge_sum(self, key: str,
                  coll_prefix: tuple[str, ...] | None = None) -> float:
        """Summed CURRENT value across matching collections (for gauges
        and lifetime totals)."""
        with self._lock:
            if not self._samples:
                return 0.0
            last = self._samples[-1][1]
        return sum(v for (coll, k), v in last.items()
                   if k == key and (coll_prefix is None or
                                    any(coll.startswith(p)
                                        for p in coll_prefix)))

    # -- the PGMap-style digest --------------------------------------------

    def _wire_class_delta(self, cls: str) -> float:
        return self.counter_delta(f"class_bytes:{cls}", WIRE_PREFIXES)

    def wire_bytes_per_byte_repaired(self) -> float:
        """ROADMAP item 3's success metric: wire bytes attributed to
        recovery-class ops over the window, per byte of repaired data
        pushed — ~k for centralized repair (k-1 survivor chunk reads +
        one reconstructed chunk push per chunk repaired), ~1 for a
        pipelined repair chain.  0.0 while nothing repaired."""
        repaired = self.counter_delta("recovery_bytes", PG_PREFIXES)
        if repaired <= 0:
            return 0.0
        return self._wire_class_delta("recovery") / repaired

    def wire_bytes_per_op(self) -> float:
        """ROADMAP item 4's companion metric: wire bytes of client- and
        serving-class traffic per completed client op over the window."""
        ops = (self.counter_delta("writes", PG_PREFIXES)
               + self.counter_delta("reads", PG_PREFIXES))
        if ops <= 0:
            return 0.0
        return (self._wire_class_delta("client")
                + self._wire_class_delta("serving")) / ops

    @staticmethod
    def bytes_copied_per_byte_served() -> float:
        """ROADMAP item 2's success metric: host payload copies per
        payload byte consumed, from the process-global copy ledger —
        ~3 on the legacy pickle path, ~1 on the sideband path.  0.0
        while nothing served (or the ledger is unavailable)."""
        try:
            from ..common.copy_ledger import ledger
        except Exception:                   # pragma: no cover
            return 0.0
        return ledger().copies_per_byte()

    def digest(self) -> dict:
        """The rate digest ``Cluster.status()`` / `ceph_tpu top` render:
        client IO, recovery, serving-batch throughput, wire traffic,
        jit churn."""
        return {
            "window_s": round(self.span(), 3),
            "samples": len(self._samples),
            "client_io": {
                "wr_bytes_s": self.rate("write_bytes", PG_PREFIXES),
                "rd_bytes_s": self.rate("read_bytes", PG_PREFIXES),
                "wr_op_s": self.rate("writes", PG_PREFIXES),
                "rd_op_s": self.rate("reads", PG_PREFIXES),
            },
            "recovery": {
                "bytes_s": self.rate("recovery_bytes", PG_PREFIXES),
                # objects-recovered/s: batched waves and the per-object
                # machine both land on the backends' `recoveries` counter
                "op_s": self.rate("recoveries", PG_PREFIXES),
                # scheduler occupancy (0 when no scheduler is attached):
                # queued/active PG jobs from the live recovery schedulers
                "queued_pgs": self.gauge_sum("jobs_queued",
                                             ("recovery.",)),
                "active_pgs": self.gauge_sum("jobs_active",
                                             ("recovery.",)),
                # bytes-on-wire per byte repaired (ROADMAP item 3's
                # success metric — ~k centralized, ~1 pipelined)
                "wire_bytes_per_byte_repaired":
                    self.wire_bytes_per_byte_repaired(),
            },
            "serving": {
                # device batches only: the coalescer's own `batches`
                # also counts a clean read's host-only batch
                "batch_s": self.rate("batches", DEVICE_PREFIXES),
                "op_s": self.rate("ops_completed"),
                "bytes_s": self.rate("bytes_in"),
                # client+serving wire bytes per completed client op
                "wire_bytes_per_op": self.wire_bytes_per_op(),
                # host copies per payload byte consumed — the zero-copy
                # data path's success metric (common/copy_ledger.py);
                # cumulative since process start, not windowed
                "bytes_copied_per_byte_served":
                    self.bytes_copied_per_byte_served(),
            },
            "wire": {
                "tx_bytes_s": self.rate("tx_bytes", WIRE_PREFIXES),
                "tx_msgs_s": self.rate("tx_msgs", WIRE_PREFIXES),
                "class_bytes_s": {
                    cls: (self._wire_class_delta(cls) / self.span()
                          if self.span() > 0 else 0.0)
                    for cls in ("client", "serving", "recovery",
                                "scrub", "rebalance", "other")},
            },
            "jit": {
                "compiles": self.counter_delta("compilations", ("jit",)),
                "cache_hits": self.counter_delta("cache_hits", ("jit",)),
            },
        }

    def digest_flat(self) -> dict[str, float]:
        """The digest flattened to ``stat -> value`` (the prometheus
        ``ceph_tpu_stats_rate`` gauge label set)."""
        d = self.digest()
        return {
            "client_wr_bytes_s": d["client_io"]["wr_bytes_s"],
            "client_rd_bytes_s": d["client_io"]["rd_bytes_s"],
            "client_wr_op_s": d["client_io"]["wr_op_s"],
            "client_rd_op_s": d["client_io"]["rd_op_s"],
            "recovery_bytes_s": d["recovery"]["bytes_s"],
            "recovery_op_s": d["recovery"]["op_s"],
            "recovery_queued_pgs": d["recovery"]["queued_pgs"],
            "recovery_active_pgs": d["recovery"]["active_pgs"],
            "recovery_wire_per_byte":
                d["recovery"]["wire_bytes_per_byte_repaired"],
            "serving_batch_s": d["serving"]["batch_s"],
            "serving_op_s": d["serving"]["op_s"],
            "serving_bytes_s": d["serving"]["bytes_s"],
            "serving_wire_per_op": d["serving"]["wire_bytes_per_op"],
            "serving_copies_per_byte":
                d["serving"]["bytes_copied_per_byte_served"],
            "wire_tx_bytes_s": d["wire"]["tx_bytes_s"],
            "wire_tx_msgs_s": d["wire"]["tx_msgs_s"],
            "jit_compiles": d["jit"]["compiles"],
            "jit_cache_hits": d["jit"]["cache_hits"],
        }
