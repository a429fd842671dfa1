"""The benchmark's arithmetic on client timings: percentiles, the
drained-span rate, the quartile spread the bounds are set from."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile ``q`` (0 < q <= 100) of ``values``:
    the smallest value with at least q% of the sample at or below it.
    No interpolation, so the number is one that was measured."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond percentile ``q``."""
    return n - max(1, math.ceil(q / 100.0 * n))


def supported(n: int, q: float, beyond: int = 10) -> bool:
    """The sample-count rule: a percentile is reported only where at
    least ``beyond`` samples lie beyond it (p95 wants 200 ops)."""
    return n > 0 and samples_beyond(n, q) >= beyond


class Op:
    """One client operation: issued at ``t0``, acked (or failed) at
    ``t1`` on ``time.perf_counter``, ``nbytes`` of user data."""
    __slots__ = ("t0", "t1", "nbytes", "ok", "client", "key")

    def __init__(self, t0, t1, nbytes, ok, client=0, key=None):
        self.t0, self.t1, self.nbytes = t0, t1, nbytes
        self.ok, self.client, self.key = ok, client, key


def drained_span(ops) -> tuple[float, float]:
    """(first issue, last ack or failure) over every op attempted."""
    if not ops:
        raise ValueError("no op was attempted")
    return min(o.t0 for o in ops), max(o.t1 for o in ops)


def drained_rate_mib_s(ops) -> float:
    """All bytes of acked ops over the seconds from the first issue to
    the last ack: issuing stopped earlier, the in-flight ops drained, so
    no op is cut at an edge and a stall anywhere in the span shows
    (``ObjBencher``'s bandwidth).  Failed ops add time and no bytes."""
    first, last = drained_span(ops)
    if last <= first:
        raise ValueError("the span has no length")
    done = sum(o.nbytes for o in ops if o.ok)
    return done / float(1 << 20) / (last - first)


def latencies_ms(ops) -> list[float]:
    """Issue -> ack of every acked op; a failed op has no latency."""
    return [(o.t1 - o.t0) * 1e3 for o in ops if o.ok]


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median with ``statistics.quantiles(n=4)``: the spread
    the builder's bounds and the driver's check are reckoned in."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
