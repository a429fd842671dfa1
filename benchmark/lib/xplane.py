"""From a profiler capture to numbers: device busy time, the ops that
took it, per-pattern device time, and the idle gaps by what the host was
doing.  Works on plain ``(name, start_ns, dur_ns)`` tuples so the
arithmetic is testable without a capture; :func:`load` is the only part
that touches a file."""
from __future__ import annotations

import re
import time
from pathlib import Path

ANCHOR = "bench_clock_anchor"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
OPS_LINE = "XLA Ops"


def clock_anchor(profiler) -> float:
    """Stamp ``time.perf_counter()`` inside a host annotation of a known
    name: its event in the capture pairs the capture's clock with the
    host's, so tracer spans can be laid over device gaps."""
    with profiler.TraceAnnotation(ANCHOR):
        return time.perf_counter()


def load(capture_dir) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns), ...]}, "anchors":
    [start_ns, ...], "lines": {plane: [line names]}}`` of the newest
    ``*.xplane.pb`` under ``capture_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(capture_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {capture_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, anchors, lines = {}, [], {}
    for plane in data.planes:
        names = []
        if DEVICE_PLANE.match(plane.name):
            plane_lines = list(plane.lines)
            names = [ln.name for ln in plane_lines]
            picked = [ln for ln in plane_lines if ln.name == OPS_LINE]
            devices[plane.name] = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ln in picked for ev in ln.events]
        else:
            for ln in plane.lines:
                names.append(ln.name)
                anchors.extend(float(ev.start_ns) for ev in ln.events
                               if ev.name == ANCHOR)
        lines[plane.name] = names
    return {"devices": devices, "anchors": sorted(anchors), "lines": lines,
            "file": str(files[-1])}


def clip(events, lo_ns: float, hi_ns: float) -> list:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo_ns), min(start + dur, hi_ns)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_union_ns(events) -> float:
    """Length of the union of the events' intervals: overlapping ops
    (two cores, an async copy under a kernel) count once."""
    total, edge = 0.0, float("-inf")
    for start, end in sorted((s, s + d) for _n, s, d in events):
        if end > edge:
            total += end - max(start, edge)
            edge = end
    return total


def idle_gaps(events, lo_ns: float, hi_ns: float) -> list:
    """The intervals of [lo, hi] in which no event runs: [(start, end)]."""
    gaps, edge = [], lo_ns
    for start, end in sorted((s, s + d) for _n, s, d in events):
        if start > edge:
            gaps.append((edge, min(start, hi_ns)))
        edge = max(edge, end)
        if edge >= hi_ns:
            break
    if edge < hi_ns:
        gaps.append((edge, hi_ns))
    return [(a, b) for a, b in gaps if b > a]


def top_ops(events, n: int = 10) -> list:
    """[[name, seconds], ...]: device time summed by op name, largest
    first."""
    by_name: dict[str, float] = {}
    for name, _s, dur in events:
        by_name[name] = by_name.get(name, 0.0) + dur
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:64], dur / 1e9] for name, dur in ranked]


def matching(events, pattern: str) -> list:
    """[(match, dur_ns)] of the events whose name the pattern finds."""
    rx = re.compile(pattern)
    out = []
    for name, _s, dur in events:
        m = rx.search(name)
        if m:
            out.append((m, dur))
    return out


def attribute_gaps(gaps, spans, min_gap_ns: float = 20e3, n: int = 10) -> list:
    """[[span name, seconds], ...]: every idle gap of ``min_gap_ns`` or
    more goes to the innermost (shortest) host span that covers its
    midpoint, "unattributed" where none does; summed by name, largest
    first.  ``spans`` are (name, start_ns, end_ns) on the gaps' clock."""
    spans = sorted(spans, key=lambda s: s[1])
    mids = sorted(((a + b) / 2.0, b - a) for a, b in gaps
                  if b - a >= min_gap_ns)
    by_name: dict[str, float] = {}
    active, nxt = [], 0
    for mid, length in mids:
        while nxt < len(spans) and spans[nxt][1] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [s for s in active if s[2] >= mid]
        owner = min(active, key=lambda s: s[2] - s[1])[0] if active \
            else "unattributed"
        by_name[owner] = by_name.get(owner, 0.0) + length
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, dur / 1e9] for name, dur in ranked]


def reduce(capture: dict, lo_ns: float, hi_ns: float) -> dict:
    """The traced window [lo, hi] of a loaded capture: per-device clipped
    events, busy seconds averaged over the devices, the window's
    length, the top ops and the idle gaps of the busiest device."""
    per_device = {plane: clip(evs, lo_ns, hi_ns)
                  for plane, evs in capture["devices"].items()}
    busy_of = {plane: busy_union_ns(evs) for plane, evs in per_device.items()}
    busy = list(busy_of.values())
    fullest = max(busy_of, key=busy_of.get, default=None)
    events = [ev for evs in per_device.values() for ev in evs]
    return {
        "events": events,
        "busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
        "window_s": (hi_ns - lo_ns) / 1e9,
        "devices": len(per_device),
        "device_ops": top_ops(events),
        "gaps": idle_gaps(per_device[fullest], lo_ns, hi_ns)
        if fullest is not None else [],
    }
