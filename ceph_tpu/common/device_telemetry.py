"""Device telemetry: JAX/XLA backend introspection as a perf collection.

The observability gap this closes: a perf number is meaningless
without knowing WHAT hardware produced it, and a serving
process needs live device-memory pressure the way the reference watches
BlueStore utilization.  This module exposes:

- :func:`device_inventory` — platform / device kind / count / jax
  version.  ``initialize=False`` (the default) never triggers a backend
  init: until an XLA backend has ACTUALLY initialized in this process
  (:func:`backend_ready` — importing jax alone is not enough, the first
  ``jax.devices()`` call is what starts init), the inventory degrades
  to version-only.  That discipline matters because backend init TAKES
  the chip, and a chip belongs to one process at a time: a scrape or a
  status command that initialised a backend would fail or hang against
  the server that holds it — telemetry must never be what does that.
- :func:`memory_stats` / :func:`live_buffer_bytes` — per-device memory
  stats where the backend exposes them (``Device.memory_stats()``; TPU
  backends report bytes_in_use/peak, CPU usually returns nothing) and the
  total bytes pinned by live jax arrays.
- :func:`compile_cache_stats` — size of the traced_jit AOT key registry
  (the compile-cache the RECOMPILE_STORM health check watches).
- :func:`refresh` — pushes all of the above into a ``device``
  PerfCounters collection on a Context, so ``perf dump`` and the
  prometheus exporter carry device gauges with zero extra wiring.

Stdlib-importable: jax is only touched inside functions, and only when
already loaded (or when ``initialize=True`` is explicit).
"""
from __future__ import annotations

import sys

from . import tracer as tracer_mod

DEVICE_COLLECTION = "device"


def jax_version() -> str | None:
    """The installed jax version WITHOUT importing jax (importlib
    metadata only — safe before any backend probe)."""
    try:
        from importlib.metadata import version
        return version("jax")
    except Exception:
        return None


def backend_ready() -> bool:
    """True only when an XLA backend has ALREADY initialized in this
    process.  ``"jax" in sys.modules`` is not enough: merely importing
    jax (which the codec does at module scope) leaves the backend
    uninitialized, and the first ``jax.devices()`` call would START init
    — the chip grab this module must never cause.  Reads the bridge's backend
    cache; if that private surface moves in a future jax, degrade to
    False (telemetry goes dark rather than wedging a scrape)."""
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:
        return False


def device_inventory(initialize: bool = False) -> dict:
    """Platform/device summary.  Never initializes a backend unless
    ``initialize=True``; errors degrade to an ``error`` field rather than
    raising (telemetry must not take the process down)."""
    info: dict = {"jax_version": jax_version(), "platform": None,
                  "device_kind": None, "num_devices": 0}
    if not initialize and not backend_ready():
        return info
    try:
        import jax
        devs = jax.devices()
        info.update(platform=devs[0].platform,
                    device_kind=getattr(devs[0], "device_kind", None),
                    num_devices=len(devs))
        info["devices"] = [
            {"id": d.id, "platform": d.platform,
             "kind": getattr(d, "device_kind", None)} for d in devs]
    except Exception as e:                       # backend down / wedged
        info["error"] = repr(e)[:200]
    return info


def memory_stats(initialize: bool = False) -> dict[str, dict]:
    """Per-device memory stats where the backend exposes them (the PJRT
    ``memory_stats()`` surface: bytes_in_use, peak_bytes_in_use,
    bytes_limit on TPU/GPU).  Guarded per device AND per field: a CPU
    backend may lack the method entirely, return ``None``, or return a
    non-dict — every shape degrades to that device being absent from the
    snapshot (partial data, never a raise)."""
    if not initialize and not backend_ready():
        return {}
    out: dict[str, dict] = {}
    try:
        import jax
        for d in jax.devices():
            st = None
            try:
                if hasattr(d, "memory_stats"):
                    st = d.memory_stats()
            except Exception:
                st = None
            if isinstance(st, dict) and st:
                out[f"{d.platform}:{d.id}"] = dict(st)
    except Exception:
        pass
    return out


# session high-water marks per device: the backend's own
# peak_bytes_in_use can reset (client restart, stats clear); the module
# keeps the max ever observed in THIS process so HBM_PRESSURE sees the
# true watermark even between samples
_hbm_high_water: dict[str, int] = {}


def hbm_watermarks(initialize: bool = False) -> dict[str, dict]:
    """Per-device HBM watermark sample: bytes in use, backend peak,
    bytes limit, and the session high-water mark (max observed across
    samples).  Devices whose backend lacks memory stats (CPU) simply
    don't appear — the HBM_PRESSURE health check reads this and stays
    silent on such platforms."""
    out: dict[str, dict] = {}
    for dev, st in memory_stats(initialize).items():
        try:
            in_use = int(st.get("bytes_in_use", 0) or 0)
            peak = int(st.get("peak_bytes_in_use", 0) or 0)
            limit = int(st.get("bytes_limit", 0) or 0)
        except (TypeError, ValueError):     # backend-specific field shapes
            continue
        hw = max(_hbm_high_water.get(dev, 0), peak, in_use)
        _hbm_high_water[dev] = hw
        rec = {"bytes_in_use": in_use, "peak_bytes_in_use": peak,
               "bytes_limit": limit, "high_water_bytes": hw}
        if limit > 0:
            rec["high_water_ratio"] = round(hw / limit, 4)
        out[dev] = rec
    return out


def live_buffer_bytes(initialize: bool = False) -> int:
    """Total bytes held by live jax arrays in this process (the
    device-resident working set; ``jax.live_arrays``)."""
    if not initialize and not backend_ready():
        return 0
    try:
        import jax
        return int(sum(getattr(a, "nbytes", 0) or 0
                       for a in jax.live_arrays()))
    except Exception:
        return 0


def compile_cache_stats() -> dict:
    """traced_jit registry size + aggregate compile counters (the
    process compile-cache view; no jax import needed — the registry
    lives in common.tracer)."""
    jd = tracer_mod.jit_dump()
    counters = jd["counters"]
    return {"keys": jd["num_keys"],
            "compilations": counters.get("compilations", 0),
            "cache_hits": counters.get("cache_hits", 0)}


def _device_perf(cct):
    """The Context's ``device`` collection, built lazily on first
    refresh (a jax-free process never grows one)."""
    pc = cct.perf.get(DEVICE_COLLECTION)
    if pc is None:
        from .perf_counters import PerfCountersBuilder
        pc = (PerfCountersBuilder(DEVICE_COLLECTION)
              .add_u64("num_devices", "accelerator devices visible to jax")
              .add_u64("live_buffer_bytes",
                       "bytes held by live jax arrays (device-resident "
                       "working set)")
              .add_u64("mem_bytes_in_use",
                       "backend-reported bytes in use, summed over devices")
              .add_u64("mem_peak_bytes_in_use",
                       "backend-reported peak bytes in use, summed over "
                       "devices")
              .add_u64("mem_bytes_limit",
                       "backend-reported memory capacity, summed over "
                       "devices (0 where the backend lacks it)")
              .add_u64("hbm_high_water_bytes",
                       "session high-water device-memory mark, summed "
                       "over devices (feeds HBM_PRESSURE)")
              .add_u64("compile_cache_keys",
                       "distinct (function, shape) keys in the traced_jit "
                       "compile cache")
              .create_perf_counters())
        cct.perf.add(pc)
    return pc


def refresh(cct, initialize: bool = False) -> dict:
    """Take one telemetry snapshot and push it into the Context's
    ``device`` perf collection.  Returns the full snapshot (the
    ``device dump`` admin command / flight-recorder source)."""
    inv = device_inventory(initialize)
    mem = memory_stats(initialize)
    marks = hbm_watermarks(initialize)
    live = live_buffer_bytes(initialize)
    cache = compile_cache_stats()
    pc = _device_perf(cct)
    pc.set("num_devices", inv["num_devices"])
    pc.set("live_buffer_bytes", live)
    # guarded field folds: a backend may report partial stat sets
    pc.set("mem_bytes_in_use",
           sum(int(s.get("bytes_in_use", 0) or 0) for s in mem.values()))
    pc.set("mem_peak_bytes_in_use",
           sum(int(s.get("peak_bytes_in_use", 0) or 0)
               for s in mem.values()))
    pc.set("mem_bytes_limit",
           sum(m["bytes_limit"] for m in marks.values()))
    pc.set("hbm_high_water_bytes",
           sum(m["high_water_bytes"] for m in marks.values()))
    pc.set("compile_cache_keys", cache["keys"])
    return {"inventory": inv, "memory": mem, "watermarks": marks,
            "live_buffer_bytes": live, "compile_cache": cache}
