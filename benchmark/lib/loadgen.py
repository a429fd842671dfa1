"""The one general load generator: a closed loop of N clients, each
sending its next operation when the last one is answered.  What an
operation is, and what it sends, comes from the driver and the traffic
mix's file; the generator only issues, times and drains."""
from __future__ import annotations

import threading
import time

from .stats import Op


class Reservoir:
    """A uniform sample of ``size`` of the items offered, whatever their
    number turns out to be (algorithm R), drawn from a seeded
    ``random.Random``; the last item offered is kept beside it."""

    def __init__(self, size: int, rng):
        self.size, self.rng = size, rng
        self.items: list = []
        self.last = None
        self.offered = 0

    def offer(self, item) -> None:
        n = self.offered
        self.offered += 1
        self.last = item
        if n < self.size:
            self.items.append(item)
            return
        j = self.rng.randrange(n + 1)
        if j < self.size:
            self.items[j] = item

    def sample(self) -> list:
        out = list(self.items)
        if self.last is not None and all(self.last is not it for it in out):
            out.append(self.last)
        return out


def run_schedule(t_start: float, schedule) -> None:
    """Run each ``(at_seconds, callback)`` of ``schedule`` at its offset
    from ``t_start``, on the calling thread."""
    for at, callback in sorted(schedule, key=lambda s: s[0]):
        delay = t_start + at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        callback()


def closed_loop(n_clients: int, seconds: float, op, schedule=(),
                drain_timeout: float = 120.0, on_start=None):
    """Run ``op(client, seq) -> (nbytes, key)`` back to back from
    ``n_clients`` threads.  Issuing stops ``seconds`` after the common
    start; every op in flight then drains.  ``schedule`` is a list of
    ``(at_seconds, callback)`` the calling thread runs meanwhile (the
    traced run's profiler window); ``on_start(t_start)`` is told the
    common start as the clients leave the barrier.

    Returns ``(ops, errors, stuck, t_start)``: every op attempted as a
    ``stats.Op``, the first failures' text, the clients that had not
    returned ``drain_timeout`` seconds after the close (their op in
    flight never came: it counts as failed), and the start instant."""
    per_client: list[list[Op]] = [[] for _ in range(n_clients)]
    errors: list[str] = []
    inflight: list = [None] * n_clients
    go = threading.Barrier(n_clients + 1)
    t_start_box: list[float] = []

    def loop(ci: int) -> None:
        mine = per_client[ci]
        go.wait()
        deadline = t_start_box[0] + seconds
        seq = 0
        clock = time.perf_counter
        while True:
            t0 = clock()
            if t0 >= deadline:
                return
            inflight[ci] = t0
            try:
                nbytes, key = op(ci, seq)
            except Exception as e:          # noqa: BLE001 — an op failed
                mine.append(Op(t0, clock(), 0, False, ci, None))
                if len(errors) < 8:
                    errors.append(f"client {ci} op {seq}: "
                                  f"{type(e).__name__}: {e}"[:300])
            else:
                mine.append(Op(t0, clock(), nbytes, True, ci, key))
            inflight[ci] = None
            seq += 1

    threads = [threading.Thread(target=loop, args=(i,), daemon=True,
                                name=f"bench-client-{i}")
               for i in range(n_clients)]
    for t in threads:
        t.start()
    t_start_box.append(time.perf_counter())
    go.wait()
    t_start = t_start_box[0]
    if on_start is not None:
        on_start(t_start)
    run_schedule(t_start, schedule)
    close = t_start + seconds
    stuck = []
    for i, t in enumerate(threads):
        t.join(timeout=max(0.0, close + drain_timeout - time.perf_counter()))
        if t.is_alive():
            stuck.append(i)
    ops = [o for mine in per_client for o in mine]
    now = time.perf_counter()
    for i in stuck:                 # an answer that never came
        ops.append(Op(inflight[i] or now, now, 0, False, i, None))
        errors.append(f"client {i}: op in flight never returned")
    return ops, errors, stuck, t_start
