"""The small fixed set of generic readers a per-layer metric's file
chooses from.  Each takes the metric's ``params`` and the run's
``ctx`` and returns a number, or None where it finds nothing to read;
the harness then leaves the metric out of the line.  A share of a
roofline or of the window is never returned as 0 for "not found".

``ctx`` (built by ``run.py`` in a ``--trace 1`` run):
  ops         every client op of the window (``stats.Op``)
  counted_ops ops acked while spans and counters were being counted
  spans       {span name: (sum seconds, count)} over that same stretch
  rpc         {rpc method: (sum seconds, count)}, server side, same stretch
  counters    {perf collection: {counter: delta}}, same stretch
  device      ``xplane.reduce`` of the profiled stretch, or None
  traced_ops  ops acked inside the profiled stretch
  device_kind as JAX names the chip
  config      the configuration's file
"""
from __future__ import annotations

import fnmatch

from . import peaks, stats, work, xplane


def _source(ctx: dict, key: str):
    """``span:<name>`` or ``rpc:<method>`` -> (sum seconds, count)."""
    kind, _, name = key.partition(":")
    table = {"span": ctx["spans"], "rpc": ctx["rpc"]}.get(kind)
    if table is None:
        raise ValueError(f"source {key!r}: want span:<name> or rpc:<method>")
    return table.get(name)


def _sum_sources(ctx: dict, keys) -> tuple[float, int]:
    total, count = 0.0, 0
    for key in keys:
        found = _source(ctx, key)
        if found is not None:
            total += found[0]
            count += found[1]
    return total, count


def client_percentile_ms(params: dict, ctx: dict):
    """A percentile of the client's own op latencies, under the
    sample-count rule."""
    lat = stats.latencies_ms(ctx["ops"])
    if not stats.supported(len(lat), params["q"]):
        return None
    return stats.percentile(lat, params["q"])


def span_mean_ms(params: dict, ctx: dict):
    """Mean milliseconds of ``spans`` less those of ``minus``.
    ``per: "event"`` takes each side's own mean (sum / its count);
    ``per: "op"`` divides both sums by the client ops counted, so spans
    that occur several times an op (12 sub-writes a put) add up."""
    plus, n_plus = _sum_sources(ctx, params["spans"])
    minus, n_minus = _sum_sources(ctx, params.get("minus", []))
    if n_plus == 0 or (params.get("minus") and n_minus == 0):
        return None
    if params.get("per", "event") == "op":
        if not ctx["counted_ops"]:
            return None
        return (plus - minus) / ctx["counted_ops"] * 1e3
    return (plus / n_plus - (minus / n_minus if n_minus else 0.0)) * 1e3


def _counter_sum(ctx: dict, paths):
    """Sum of ``<collection glob>:<counter>`` deltas; None if no
    collection matches any path."""
    total, seen = 0.0, False
    for path in paths:
        glob, _, counter = path.rpartition(":")
        for name, values in ctx["counters"].items():
            if fnmatch.fnmatchcase(name, glob) and counter in values:
                total += values[counter]
                seen = True
    return total if seen else None


def counter_ratio(params: dict, ctx: dict):
    """``num`` counters over ``den`` counters, or over the client ops
    counted where ``den`` is "client_ops"."""
    num = _counter_sum(ctx, params["num"])
    den = ctx["counted_ops"] if params["den"] == "client_ops" \
        else _counter_sum(ctx, params["den"])
    if num is None or not den:
        return None
    return num / den


def device_op_ms_per_op(params: dict, ctx: dict):
    """Device milliseconds of the ops a name pattern finds, per client
    op acked inside the profiled stretch."""
    dev = ctx.get("device")
    if not dev or not ctx.get("traced_ops"):
        return None
    found = xplane.matching(dev["events"], params["pattern"])
    if not found:
        return None
    return sum(d for _m, d in found) / 1e6 / ctx["traced_ops"]


def _dotted(table: dict, path: str):
    for part in path.split("."):
        table = table[part]
    return table


def roofline_pct(params: dict, ctx: dict):
    """The least time the chip could take for the work asked of the
    matching ops (bytes from a function of the benchmark's own, over
    the published peak) as a share of the device time they took.  The
    pattern's named groups give each op's sizes; ``fixed`` adds sizes
    from the configuration's ``driver_params``, by dotted path."""
    dev = ctx.get("device")
    if not dev:
        return None
    found = xplane.matching(dev["events"], params["pattern"])
    if not found:
        return None
    fn = work.BYTES_FUNCTIONS[params["bytes_fn"]]
    fixed = {arg: int(_dotted(ctx["config"]["driver_params"], key))
             for arg, key in params.get("fixed", {}).items()}
    total_bytes = sum(
        fn(**fixed, **{g: int(v) for g, v in m.groupdict().items()})
        for m, _d in found)
    seconds = sum(d for _m, d in found) / 1e9
    if seconds <= 0:
        return None
    least = total_bytes / peaks.peak(ctx["device_kind"], params["peak"])
    return least / seconds * 100.0


def device_idle_pct(params: dict, ctx: dict):
    """1 - union of the device-op intervals over the profiled stretch."""
    dev = ctx.get("device")
    if not dev or dev["window_s"] <= 0 or dev["devices"] == 0:
        return None
    return (1.0 - dev["busy_s"] / dev["window_s"]) * 100.0


READERS = {f.__name__: f for f in (
    client_percentile_ms, span_mean_ms, counter_ratio,
    device_op_ms_per_op, roofline_pct, device_idle_pct)}


def read_metric(spec: dict, ctx: dict):
    reader = READERS.get(spec["reader"])
    if reader is None:
        raise ValueError(f"metric {spec['name']!r} names reader "
                         f"{spec['reader']!r}; there are {sorted(READERS)}")
    return reader(spec.get("params", {}), ctx)
