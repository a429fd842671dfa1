"""Roofline ledger: per-executable achieved vs peak FLOP/s and HBM B/s.

ROADMAP item 2's missing compass: the repo can say how long a kernel ran
(``device_attribution``) and what XLA modeled it to cost
(``cost_analysis()`` folded by ``ops/traced_jit.py``), but nothing joins
the two — so "chase the next tier" has no instrument that says how close
any executable runs to what the hardware allows.  This module is that
join:

- a **peak-spec registry** (device kind -> peak FLOP/s and HBM B/s, the
  public TPU generation specs; overridable via the
  ``device_peak_flops`` / ``device_peak_hbm_bytes_per_sec`` options for
  hosts the registry does not know);
- a **per-executable ledger**: ``ops/traced_jit.py`` records each
  compiled (function, shape) key's modeled FLOPs/bytes at compile time
  and its measured dispatch seconds on every call, and :func:`snapshot`
  computes achieved FLOP/s, achieved B/s, arithmetic intensity,
  memory-vs-compute-bound classification and %-of-peak per executable;
- surfaces: the ``device_efficiency`` PerfCounters collection
  (:func:`refresh`), the ``ceph_tpu_device_efficiency{executable,stat}``
  prometheus family, the ``device roofline`` admin command
  (:func:`report`), :func:`flat_series` for the time-series ring, and
  ``tools/roofline_report.py`` post-hoc.

Honesty note on the occupancy clock: per-call seconds are the WALL time
of the dispatch on the calling thread.  The first dispatch of every key
is synced (``traced_jit`` waits it out), so those samples are true
end-to-end; steady-state dispatches on an async backend can return
before the device finishes, under-counting time and producing
impossible >100%-of-peak rates.  :func:`_estimated_seconds` therefore
compares the synced-sample per-call mean against the overall mean and,
when async under-counting is evident, extrapolates the synced mean over
every call (conservative — first dispatches run cold; each derived row
carries ``estimator`` saying which clock it used, and ``synced_calls``
says how much of the sample was sync-timed).

Stdlib-only (the device_attribution discipline): importable before any
JAX backend initializes; jax facts arrive as plain numbers from callers.
"""
from __future__ import annotations

import os
import threading

# -- peak-spec registry -------------------------------------------------------

#: (device-kind substring, peak FLOP/s, peak HBM bytes/s) — public specs,
#: bf16 peak (the bitslice/pallas GF kernels ride the MXU as bf16/int8
#: matmuls).  First substring match on the lowercased device kind wins.
PEAK_SPECS: tuple[tuple[str, float, float], ...] = (
    ("v6e", 918e12, 1640e9),       # Trillium
    ("trillium", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),    # what a v5e's device_kind says
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 46e12, 700e9),
)

#: nominal per-core CPU peaks (an AVX2-class core's fma throughput and a
#: share of one DDR channel) — rough on purpose: on CPU the roofline's
#: job is the memory-vs-compute CLASSIFICATION and round-over-round
#: comparison, not an absolute hardware claim (``source`` says nominal).
CPU_NOMINAL_FLOPS_PER_CORE = 5e10
CPU_NOMINAL_DRAM_BPS = 3e10


def lookup_peaks(cct=None, device_kind: str | None = None,
                 platform: str | None = None) -> dict:
    """Resolve peak FLOP/s and HBM B/s for the current (or named)
    device.  Config overrides win; then the device-kind registry.  A TPU
    kind the registry does not know has NO peaks (zeros, ``source`` says
    so) — a share of some other chip's roofline is not a measurement;
    anything else gets a nominal CPU spec (classification still works,
    ``source`` marks it).  Never initializes a backend."""
    if device_kind is None and platform is None:
        from . import device_telemetry
        inv = device_telemetry.device_inventory()
        device_kind, platform = inv["device_kind"], inv["platform"]
    flops = hbm = 0.0
    source = None
    kind_l = (device_kind or "").lower()
    for sub, f, b in PEAK_SPECS:
        if sub in kind_l:
            flops, hbm, source = f, b, f"registry:{sub}"
            break
    if source is None and platform == "tpu":
        source = f"unknown-tpu({device_kind})"
    if source is None:
        cores = os.cpu_count() or 1
        flops = CPU_NOMINAL_FLOPS_PER_CORE * cores
        hbm = CPU_NOMINAL_DRAM_BPS
        source = f"nominal-cpu({cores} cores)"
    if cct is not None:
        conf_f = float(cct.conf.get("device_peak_flops") or 0.0)
        conf_b = float(cct.conf.get("device_peak_hbm_bytes_per_sec") or 0)
        if conf_f > 0:
            flops, source = conf_f, "config"
        if conf_b > 0:
            hbm = conf_b
            source = "config" if conf_f > 0 else f"{source}+config-hbm"
    return {"flops": flops, "hbm_bytes_s": hbm, "source": source,
            "device_kind": device_kind, "platform": platform,
            "ridge_flops_per_byte": (flops / hbm) if hbm else 0.0}


# -- the per-executable ledger ------------------------------------------------

_lock = threading.Lock()
_execs: dict[str, dict] = {}
_perf = None


def executable_id(label: str, key) -> str:
    """A readable executable name from traced_jit's (label, shape key):
    ``gf_apply_bitslice[4x8:uint8,8x131072:uint8]`` — one ledger row per
    compiled XLA executable, not per python function."""
    parts = []
    for p in key if isinstance(key, tuple) else (key,):
        if isinstance(p, tuple) and len(p) == 2 and isinstance(p[0], tuple):
            shape, dtype = p
            parts.append("x".join(str(d) for d in shape) + f":{dtype}")
        else:
            parts.append(str(p)[:24])
    return f"{label}[{','.join(parts)}]"


def record_compile(label: str, key, flops_per_call: float,
                   bytes_per_call: float, input_bytes: int = 0) -> None:
    """Register one compiled executable's modeled per-call cost (from
    ``cost_analysis()``).  When the backend models no byte traffic,
    the summed input-operand bytes stand in as the mandatory-traffic
    floor (``modeled_source`` records which)."""
    eid = executable_id(label, key)
    src = "cost_analysis"
    if bytes_per_call <= 0 and input_bytes > 0:
        bytes_per_call, src = float(input_bytes), "input_shapes"
    with _lock:
        rec = _execs.get(eid)
        if rec is None:
            _execs[eid] = {
                "function": label, "compiles": 1,
                "flops_per_call": float(flops_per_call),
                "bytes_per_call": float(bytes_per_call),
                "modeled_source": src,
                "calls": 0, "seconds": 0.0,
                "synced_calls": 0, "synced_s": 0.0,
                "flops": 0.0, "bytes": 0.0,
            }
        else:           # a recompile of the same key (e.g. after reset)
            rec["compiles"] += 1
            rec["flops_per_call"] = float(flops_per_call)
            rec["bytes_per_call"] = float(bytes_per_call)
            rec["modeled_source"] = src


def record_call(label: str, key, seconds: float, synced: bool = False,
                cost: tuple | None = None) -> None:
    """Account one dispatch of a compiled executable: ``seconds`` is the
    caller-measured wall time (``synced`` when it waited out the device
    — the first dispatch of every key is).  ``cost`` is the caller's
    cached ``(flops_per_call, bytes_per_call, input_bytes)`` so a ledger
    reset mid-run re-seeds the row on the next dispatch instead of going
    dark until a recompile (traced_jit passes it on every call)."""
    eid = executable_id(label, key)
    with _lock:
        rec = _execs.get(eid)
        if rec is None:
            if cost is None:     # no cost model at all: drop rather
                return           # than invent a zero-cost row
            flops, nbytes, input_bytes = cost
            src = "cost_analysis"
            if nbytes <= 0 and input_bytes > 0:
                nbytes, src = float(input_bytes), "input_shapes"
            rec = _execs[eid] = {
                "function": label, "compiles": 0,
                "flops_per_call": float(flops),
                "bytes_per_call": float(nbytes),
                "modeled_source": src,
                "calls": 0, "seconds": 0.0,
                "synced_calls": 0, "synced_s": 0.0,
                "flops": 0.0, "bytes": 0.0,
            }
        rec["calls"] += 1
        rec["seconds"] += float(seconds)
        rec["flops"] += rec["flops_per_call"]
        rec["bytes"] += rec["bytes_per_call"]
        if synced:
            rec["synced_calls"] += 1
            rec["synced_s"] += float(seconds)


def reset() -> dict:
    with _lock:
        n = len(_execs)
        _execs.clear()
    return {"success": f"dropped {n} executable records"}


# -- derived views ------------------------------------------------------------

#: when the sync-timed per-call mean exceeds the overall per-call mean by
#: this factor, the async dispatches are evidently returning before the
#: device finishes — rates are then computed over the synced mean
#: extrapolated to every call (conservative: first dispatches run cold)
_ASYNC_UNDERCOUNT_RATIO = 1.5


def _estimated_seconds(rec: dict) -> tuple[float, str]:
    """The seconds the rates divide by.  Measured wall seconds when they
    look end-to-end; the synced-sample mean extrapolated over all calls
    when async dispatch evidently under-measured (a 1-core host cannot
    run 16x its peak — better a conservative cold-sample estimate than
    an impossible achieved rate)."""
    secs, calls = rec["seconds"], rec["calls"]
    if calls and rec["synced_calls"]:
        sync_mean = rec["synced_s"] / rec["synced_calls"]
        if sync_mean > (secs / calls) * _ASYNC_UNDERCOUNT_RATIO:
            return sync_mean * calls, "synced-extrapolated"
    return secs, "measured"


def _derive(rec: dict, peaks: dict) -> dict:
    """One executable's roofline stats from its raw ledger record."""
    secs, estimator = _estimated_seconds(rec)
    out = dict(rec)
    out["est_seconds"] = round(secs, 6)
    out["estimator"] = estimator
    ach_f = (rec["flops"] / secs) if secs > 0 else 0.0
    ach_b = (rec["bytes"] / secs) if secs > 0 else 0.0
    ai = (rec["flops"] / rec["bytes"]) if rec["bytes"] > 0 else 0.0
    ridge = peaks["ridge_flops_per_byte"]
    # under the ridge the op cannot reach peak FLOP/s even at perfect
    # bandwidth: HBM is the binding resource (the roofline's knee)
    bound = "memory" if (ai < ridge or not rec["flops"]) else "compute"
    if bound == "memory":
        pct = 100.0 * ach_b / peaks["hbm_bytes_s"] \
            if peaks["hbm_bytes_s"] else 0.0
    else:
        pct = 100.0 * ach_f / peaks["flops"] if peaks["flops"] else 0.0
    out.update(
        achieved_flops_s=round(ach_f, 1),
        achieved_bytes_s=round(ach_b, 1),
        arithmetic_intensity=round(ai, 4),
        bound=bound,
        pct_of_peak=round(pct, 4),
    )
    return out


def snapshot(cct=None) -> dict:
    """The full ledger view: peaks + per-executable roofline stats +
    aggregate totals + the attribution ledger's busy-time context."""
    peaks = lookup_peaks(cct)
    with _lock:
        raw = {eid: dict(rec) for eid, rec in _execs.items()}
    execs = {eid: _derive(rec, peaks) for eid, rec in sorted(raw.items())}
    # the aggregate divides by the per-executable ESTIMATED seconds, so
    # an async-undercounted executable cannot inflate the total rate
    t_calls = sum(r["calls"] for r in raw.values())
    t_secs = sum(r["est_seconds"] for r in execs.values())
    t_flops = sum(r["flops"] for r in raw.values())
    t_bytes = sum(r["bytes"] for r in raw.values())
    agg = _derive({"calls": t_calls, "seconds": t_secs, "flops": t_flops,
                   "bytes": t_bytes, "synced_calls": 0, "synced_s": 0.0},
                  peaks)
    totals = {k: agg[k] for k in
              ("calls", "seconds", "flops", "bytes", "achieved_flops_s",
               "achieved_bytes_s", "arithmetic_intensity", "bound",
               "pct_of_peak")}
    from . import device_attribution
    busy = device_attribution.snapshot()["busy_s"]
    return {"peaks": peaks, "executables": execs, "totals": totals,
            "device_busy_s": round(busy, 6)}


def flat_series() -> dict[str, float]:
    """The time-series-ring source: aggregate efficiency as flat
    name -> value series."""
    snap = snapshot()
    t = snap["totals"]
    return {"achieved_flops_s": t["achieved_flops_s"],
            "achieved_bytes_s": t["achieved_bytes_s"],
            "pct_of_peak": t["pct_of_peak"],
            "executables": float(len(snap["executables"])),
            "device_busy_s": snap["device_busy_s"]}


def report(limit: int = 20, cct=None) -> dict:
    """The ``device roofline`` admin command: executables ranked by
    measured seconds, peaks and totals alongside."""
    snap = snapshot(cct)
    rows = sorted(snap["executables"].items(),
                  key=lambda kv: kv[1]["seconds"], reverse=True)
    return {
        "peaks": snap["peaks"],
        "totals": snap["totals"],
        "device_busy_s": snap["device_busy_s"],
        "executables": [dict(rec, executable=eid)
                        for eid, rec in rows[:max(0, int(limit))]],
    }


def render_table(snap_or_report: dict, limit: int = 20) -> str:
    """Human table over a :func:`snapshot`/:func:`report` shape (the
    ``ceph device roofline`` CLI rendering; tools/roofline_report.py
    carries its own standalone copy of this logic)."""
    execs = snap_or_report.get("executables")
    if isinstance(execs, dict):
        rows = [dict(rec, executable=eid) for eid, rec in execs.items()]
    else:
        rows = list(execs or [])
    rows.sort(key=lambda r: r.get("seconds", 0.0), reverse=True)
    peaks = snap_or_report.get("peaks") or {}
    lines = []
    if peaks:
        lines.append(
            f"peaks: {peaks.get('flops', 0) / 1e12:.1f} TFLOP/s, "
            f"{peaks.get('hbm_bytes_s', 0) / 1e9:.0f} GB/s "
            f"({peaks.get('source')})")
    lines.append(f"{'EXECUTABLE':<44} {'CALLS':>6} {'AI':>8} "
                 f"{'GB/S':>8} {'GF/S':>8} {'%PEAK':>7} BOUND")
    for r in rows[:limit]:
        lines.append(
            f"{r['executable'][:44]:<44} {r['calls']:>6} "
            f"{r['arithmetic_intensity']:>8.2f} "
            f"{r['achieved_bytes_s'] / 1e9:>8.3f} "
            f"{r['achieved_flops_s'] / 1e9:>8.3f} "
            f"{r['pct_of_peak']:>7.2f} {r['bound']}")
    return "\n".join(lines)


# -- perf-counter surface -----------------------------------------------------

EFFICIENCY_COLLECTION = "device_efficiency"


def _efficiency_perf(cct):
    pc = cct.perf.get(EFFICIENCY_COLLECTION)
    if pc is None:
        from .perf_counters import PerfCountersBuilder
        pc = (PerfCountersBuilder(EFFICIENCY_COLLECTION)
              .add_u64("executables",
                       "compiled executables in the roofline ledger")
              .add_u64("calls", "dispatches accounted by the ledger")
              .add_u64("achieved_flops_s",
                       "aggregate achieved FLOP/s over accounted "
                       "dispatch time")
              .add_u64("achieved_bytes_s",
                       "aggregate achieved bytes/s over accounted "
                       "dispatch time")
              .add_u64("pct_of_peak_x100",
                       "aggregate percent of the binding roofline peak, "
                       "x100 (4212 = 42.12%)")
              .add_u64("memory_bound",
                       "executables classified memory-bound (arithmetic "
                       "intensity under the ridge point)")
              .create_perf_counters())
        cct.perf.add(pc)
    return pc


def refresh(cct) -> dict:
    """Push the aggregate ledger view into the Context's
    ``device_efficiency`` collection (the prometheus render / perf dump
    hook).  Returns the full snapshot."""
    snap = snapshot(cct)
    pc = _efficiency_perf(cct)
    t = snap["totals"]
    pc.set("executables", len(snap["executables"]))
    pc.set("calls", t["calls"])
    pc.set("achieved_flops_s", int(t["achieved_flops_s"]))
    pc.set("achieved_bytes_s", int(t["achieved_bytes_s"]))
    pc.set("pct_of_peak_x100", int(round(t["pct_of_peak"] * 100)))
    pc.set("memory_bound",
           sum(1 for r in snap["executables"].values()
               if r["bound"] == "memory"))
    return snap
