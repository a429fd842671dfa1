"""Span tracer + JIT telemetry: the process-wide timing backbone.

The reference ships three observability mechanisms — OpTracker event
timelines (src/common/TrackedOp.h), PerfCounters (src/common/perf_counters.h)
and the blkin/opentracing span hooks (src/common/zipkin_trace.h) — but the
span layer is the one this TPU-first framework needs most: a single MiB/s
number cannot tell trace time from compile time from device-resident time
from host<->device transfer (an earlier chip run spent 570s in opaque
backend probing).  This module provides:

- :class:`Span` / :class:`Tracer`: nested spans with a thread-safe bounded
  ring buffer, exported as Chrome trace-event JSON (``chrome://tracing`` /
  Perfetto load ``trace dump`` output directly).
- per-span-name latency histograms (log-spaced bounds) that
  ``ceph_tpu.mgr.prometheus`` renders as real histogram series.
- the JIT telemetry registry behind ``ceph_tpu.ops.traced_jit``: per
  (function, shape-key) compile counts and trace/compile/first-dispatch
  wall times, plus the process-wide ``jit`` PerfCounters collection.

Everything here is stdlib-only so the bench driver can import it before
any JAX backend initializes.

Distributed tracing (the PR-6 tentpole): a :class:`TraceContext`
(trace id, parent span id, owner op class) rides every client op across
daemon boundaries — Objecter ops, net.py RPC frames, the OSD daemon's
queued dispatch, and the PG bus's ECSubRead/ECSubWrite envelopes.  Each
daemon ``activate()``s the inbound context and stamps its spans with a
per-daemon *track* (``osd.3``, ``client``), so :meth:`Tracer.dump` can
stitch the per-daemon span trees into ONE Chrome trace with one process
row per daemon, and ``tools/trace_report.py --trace`` can answer "where
did this 1 MiB write spend its 4 ms".
"""
from __future__ import annotations

import itertools
import os
import random
import threading
import time
from bisect import bisect_left
from collections import deque
from dataclasses import dataclass

from . import instruments

# log-spaced span-latency bounds (seconds); one overflow bucket follows
LATENCY_BUCKETS_S = (1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0)

# events the ring holds; the process-wide tracer takes
# CEPH_TPU_TRACE_CAPACITY instead where it is set when default_tracer()
# first makes it (not when this module is imported: a driver that is
# imported after it can still size the ring for a busy cell)
TRACE_CAPACITY = 16384

# finished events buffered per thread before the batch folds into the
# shared ring: the owning thread touches the ring lock once per batch
# (or at an explicit completion-boundary flush()) instead of per span —
# the reactor-thread contention class behind the PR 15 races
FLUSH_BATCH = 64

# unsampled-trace micro-records kept for slow-op promotion (one small
# dict entry per in-flight unsampled op; FIFO eviction past the bound)
MICRO_CAPACITY = 4096

# process-wide id allocators: ids must stay unique across every Tracer
# instance (cross-daemon stitching joins on them).  The high word is a
# per-process random salt: in multi-process mode (rados serve +
# --connect) each client process allocates its own ids, and sequential
# small ints would collide in the server's stitched dump, silently
# merging unrelated ops into one tree.
_id_salt = random.getrandbits(31) << 32
_trace_ids = itertools.count(_id_salt + 1)
_span_ids = itertools.count(_id_salt + 1)


@dataclass
class TraceContext:
    """What rides the wire: enough to stitch a child daemon's spans
    under the caller's (trace id + parent span id) and to attribute the
    work to an owner class (client/serving/recovery/scrub/rebalance).
    Picklable on purpose — net.py RPC frames and wire-mode bus envelopes
    serialize it.

    ``sampled``/``weight`` are the head-based sampling decision, made
    ONCE at :meth:`Tracer.new_trace` and carried here so the whole
    distributed trace samples atomically across daemons: an unsampled
    context suppresses every span it touches (locally and remotely)
    except slow-op promotions, and a sampled one stamps its 1/rate
    weight on every event so downstream rate math stays unbiased."""
    trace_id: int
    span_id: int          # the span new children hang under (0 = root)
    op_class: str = "client"
    sampled: bool = True
    weight: float = 1.0   # 1/sample_rate, decided at the root

    def child_of(self, span_id: int) -> "TraceContext":
        return TraceContext(self.trace_id, span_id, self.op_class,
                            self.sampled, self.weight)


class _Activation:
    """Context manager pushing a TraceContext (and optional track) onto
    the calling thread's stacks.  ``ctx=None`` is a no-op so call sites
    need no branching for untraced messages."""

    __slots__ = ("tracer", "ctx", "track", "_pushed")

    def __init__(self, tracer: "Tracer", ctx: TraceContext | None,
                 track: str | None = None):
        self.tracer = tracer
        self.ctx = ctx
        self.track = track
        self._pushed = False

    def __enter__(self) -> TraceContext | None:
        if self.ctx is not None or self.track is not None:
            self.tracer._ctx_stack().append((self.ctx, self.track))
            self._pushed = True
        return self.ctx

    def __exit__(self, *exc) -> bool:
        if self._pushed:
            self.tracer._ctx_stack().pop()
        return False


class Span:
    """One timed region; use as a context manager.  ``dur`` (seconds) is
    valid after ``__exit__``; the Chrome event is emitted on exit so the
    ring buffer holds only finished spans."""

    __slots__ = ("tracer", "name", "cat", "args", "ts_us", "dur",
                 "_t0", "trace_id", "span_id", "parent_id", "track",
                 "op_class", "sampled", "weight", "cpu_us", "_c0")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict,
                 cpu: bool = False):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.dur = 0.0
        # the thread's CPU microseconds inside the span, for a span that
        # asked (``cpu``): None until the exit, and for every other
        self.cpu_us: float | None = None
        self._c0: int | None = 0 if cpu else None
        # distributed-trace linkage (span_id/parent/class/weight) is
        # filled on __enter__ only when a TraceContext is active; a
        # nonzero trace_id is the "linked" flag (_trace_ids starts at 1)
        self.trace_id = 0
        self.track: str | None = None

    def set(self, **args) -> "Span":
        """Attach results discovered mid-span (e.g. bytes moved)."""
        self.args.update(args)
        return self

    def __enter__(self) -> "Span":
        tracer = self.tracer
        tracer._push(self)
        # one fused walk for the innermost ctx AND track (two separate
        # current_ctx()/current_track() sweeps cost real time per op)
        ctx = track = None
        for c, t in reversed(tracer._ctx_stack()):
            if ctx is None and c is not None:
                ctx = c
            if track is None and t is not None:
                track = t
            if ctx is not None and track is not None:
                break
        if ctx is not None:
            self.trace_id = ctx.trace_id
            self.span_id = next(_span_ids)
            self.parent_id = ctx.span_id
            self.op_class = ctx.op_class
            self.sampled = getattr(ctx, "sampled", True)
            self.weight = getattr(ctx, "weight", 1.0)
            # nested spans (this thread, while we are open) chain under
            # us — even when unsampled, so child daemons inherit the
            # head decision through child_of()
            tracer._ctx_stack().append((ctx.child_of(self.span_id),
                                        None))
        self.track = track
        self._t0 = time.perf_counter()
        self.ts_us = (self._t0 - tracer._t0) * 1e6
        if self._c0 is not None:
            # read inside the wall clock's two reads, so that wall - CPU
            # is the time the thread did not run and never below zero
            self._c0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        if self._c0 is not None:
            self.cpu_us = (time.thread_time_ns() - self._c0) * 1e-3
        self.dur = time.perf_counter() - self._t0
        tracer = self.tracer
        if self.trace_id:
            tracer._ctx_stack().pop()
        tracer._pop(self)
        tracer._finish_span(self)
        return False


class _NullSpan:
    """The kill-switch span: context-manager compatible, records
    nothing.  One shared instance serves every call site — no per-op
    allocation when ``instruments_enabled=false``."""

    __slots__ = ()
    dur = 0.0
    ts_us = 0.0
    args: dict = {}

    def set(self, **args) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Thread-safe span recorder with a bounded ring of Chrome events.

    Finished events buffer per thread and fold into the shared ring in
    batches (``FLUSH_BATCH``, or an explicit completion-boundary
    :meth:`flush`), so hot threads touch the ring lock ~1/64th as often
    as they emit.  Read surfaces (:meth:`dump`, :meth:`histograms`)
    drain every thread's pending batch first, so nothing observable
    changes except the lock traffic."""

    def __init__(self, capacity: int = TRACE_CAPACITY):
        # finished events: dicts, or lite tuples (name, cat, ts_us,
        # dur_us, tid[, cpu_us]) from the untraced fast path —
        # materialized by dump()
        self._events: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        # per-thread pending-event buffers (thread ident -> list); the
        # owner appends without the lock (single writer + GIL), batches
        # fold under the ring lock
        self._pending: dict[int, list] = {}
        # the one clock: every event is stamped from perf_counter
        self._t0 = time.perf_counter()
        self.pid = os.getpid()
        # span-name -> [bucket_counts..., overflow] plus (sum, count)
        self._hist: dict[str, list] = {}
        # head-based sampling (ISSUE 18): decided once per root context
        # in new_trace(); unsampled traces keep only a micro-record here
        # until they finish fast (dropped) or cross slow_threshold_s
        # (promoted into the ring)
        self.sample_rate = 1.0
        self.slow_threshold_s = 30.0
        self._micro: dict[int, dict] = {}
        self._micro_lock = threading.Lock()

    # -- span stack (per thread, for nesting introspection) ----------------

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()

    def current(self) -> Span | None:
        st = self._stack()
        return st[-1] if st else None

    def depth(self) -> int:
        return len(self._stack())

    # -- distributed trace contexts (per thread) ----------------------------

    def _ctx_stack(self) -> list:
        st = getattr(self._local, "ctx_stack", None)
        if st is None:
            st = self._local.ctx_stack = []
        return st

    def new_trace(self, op_class: str = "client") -> TraceContext:
        """A fresh root context (span_id 0): the client edge of an op.

        The head-based sampling decision happens HERE, once per trace:
        the result rides the context (and every child_of() derived from
        it, across daemons), so a distributed trace is all-in or
        all-out.  Unsampled roots leave a micro-record (start, class,
        id) for retroactive slow-op promotion; sampled roots carry a
        1/rate weight so dump consumers can de-bias rate math."""
        tid = next(_trace_ids)
        if self._sample(tid):
            rate = self.sample_rate
            w = 1.0 / rate if 0.0 < rate < 1.0 else 1.0
            return TraceContext(tid, 0, op_class, True, w)
        self._note_micro(tid, op_class)
        return TraceContext(tid, 0, op_class, False, 1.0)

    def _sample(self, trace_id: int) -> bool:
        """Deterministic per-trace-id decision (Knuth multiplicative
        hash): equidistributed over sequential ids, reproducible for a
        given id, and free of shared RNG state on the hot path."""
        rate = self.sample_rate
        if rate >= 1.0:
            return True
        if rate <= 0.0:
            return False
        return ((trace_id * 2654435761) & 0xFFFFFFFF) < rate * 4294967296.0

    # -- unsampled-op micro-records (slow-op promotion) ---------------------

    def _note_micro(self, trace_id: int, op_class: str) -> None:
        with self._micro_lock:
            self._micro[trace_id] = {"trace_id": trace_id,
                                     "start_wall": time.time(),
                                     "op_class": op_class}
            while len(self._micro) > MICRO_CAPACITY:
                self._micro.pop(next(iter(self._micro)))

    def _drop_micro(self, trace_id: int) -> None:
        if trace_id in self._micro:          # cheap pre-check, racy is fine
            with self._micro_lock:
                self._micro.pop(trace_id, None)

    def micro_records(self) -> list[dict]:
        """The in-flight unsampled ops (start wall time, op class, trace
        id) — what SLOW_OPS triage sees for ops the sampler skipped that
        have not completed yet."""
        with self._micro_lock:
            return [dict(r) for r in self._micro.values()]

    def current_ctx(self) -> TraceContext | None:
        """The innermost active TraceContext on this thread (None when
        the current work is untraced)."""
        for ctx, _track in reversed(self._ctx_stack()):
            if ctx is not None:
                return ctx
        return None

    def current_track(self) -> str | None:
        """The innermost daemon track ('osd.3', 'client', ...) active on
        this thread; spans default their track from it."""
        for _ctx, track in reversed(self._ctx_stack()):
            if track is not None:
                return track
        return None

    def activate(self, ctx: TraceContext | None,
                 track: str | None = None) -> _Activation:
        """Adopt an inbound trace context (and optionally name the local
        daemon track) for the duration of a ``with`` block.  ``ctx=None``
        activates only the track; both None is a no-op."""
        return _Activation(self, ctx, track)

    def track_scope(self, track: str) -> _Activation:
        """Name the local daemon track without touching the context."""
        return _Activation(self, None, track)

    # -- recording ----------------------------------------------------------

    def span(self, name: str, cat: str = "", cpu: bool = False,
             **args) -> Span:
        """``cpu=True`` also reads the thread's CPU clock at both ends:
        the event carries ``cpu_us`` and the fold books it, and the
        wall time less it, under the span's name in ``span_cpu``.  The
        clock is a system call, and dear on some hosts (5.6 us and more
        where ``perf_counter`` is 0.07; PERF.md, PR 37)."""
        if not instruments.enabled():
            return _NULL_SPAN
        return Span(self, name, cat, args, cpu)

    def instant(self, name: str, cat: str = "", **args) -> None:
        if not instruments.enabled():
            return
        ctx = self.current_ctx()
        if ctx is not None and not getattr(ctx, "sampled", True):
            return                   # unsampled trace: no per-event record
        ev = {"name": name, "cat": cat or "instant", "ph": "i", "s": "t",
              "ts": (time.perf_counter() - self._t0) * 1e6,
              "pid": self.pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def stamp(self) -> float:
        """The tracer's clock now (``time.perf_counter``), for a region
        that opens in one call and closes in another — a fan-out and its
        last reply — and is recorded where it closes, by :meth:`observe`."""
        return time.perf_counter()

    def observe(self, name: str, t0: float, t1: float | None = None,
                cat: str = "", ctx: TraceContext | None = None,
                track: str | None = None, cpu_s: float | None = None,
                **args) -> None:
        """Record a finished region measured with ``time.perf_counter()``
        (``t1`` defaults to now) — the ONE after-the-fact path: waits
        measured at dequeue, sends measured at return, TrackedOp ops.
        ``cpu_s`` is the thread's CPU seconds inside the region where
        the caller read ``time.thread_time_ns()`` at both ends: booked
        as a ``cpu=True`` span's is.

        Bare (no ``ctx``/``track``/args) it is the allocation-light fast
        path for hot untraced regions: no Span object, no event dict, a
        lite tuple rides the pending buffer and the ring, and
        :meth:`dump` materializes whatever survived eviction.  With
        ``ctx`` the event joins that distributed trace as a child span —
        trace/span/parent ids, ``op_class`` and the head-sampling
        decision stamped exactly as :meth:`_finish_span` stamps a live
        span (an unsampled context drops the event unless it crossed
        ``slow_threshold_s``).  Linkage is EXPLICIT opt-in, never
        ambient, so an event recorded under somebody else's active
        context does not become a node of that tree."""
        if not instruments.enabled():
            return
        if t1 is None:
            t1 = time.perf_counter()
        if ctx is None and track is None and not args:
            # inlined _emit_lite: this is the single hottest instrument
            # call (one per RPC dispatch), so it pays for zero extra frames
            buf = getattr(self._local, "pending", None)
            if buf is None:
                buf = self._pending_buf()
            ev = (name, cat, (t0 - self._t0) * 1e6, (t1 - t0) * 1e6,
                  threading.get_ident())
            buf.append(ev if cpu_s is None else ev + (cpu_s * 1e6,))
            if len(buf) >= FLUSH_BATCH:
                self._flush_buf(buf)
            return
        dur_s = t1 - t0
        promoted = False
        if ctx is not None and not getattr(ctx, "sampled", True):
            if dur_s < self.slow_threshold_s:
                if ctx.span_id == 0:         # the trace's root completed fast
                    self._drop_micro(ctx.trace_id)
                return
            promoted = True                  # slow op: into the ring anyway
            self._drop_micro(ctx.trace_id)
        ev = {"name": name, "cat": cat or "span", "ph": "X",
              "ts": (t0 - self._t0) * 1e6, "dur": dur_s * 1e6,
              "pid": self.pid, "tid": threading.get_ident()}
        if ctx is not None:
            args["trace_id"] = ctx.trace_id
            args["span_id"] = next(_span_ids)
            args["parent_span_id"] = ctx.span_id
            args.setdefault("op_class", ctx.op_class)
            if promoted:
                # promoted events represent only themselves: weight 1
                args["promoted"] = True
            elif getattr(ctx, "weight", 1.0) != 1.0:
                args["sample_weight"] = ctx.weight
        cpu_us = None
        if cpu_s is not None:
            args["cpu_us"] = cpu_us = cpu_s * 1e6
        if args:
            ev["args"] = args
        if track is not None:
            ev["track"] = track
        self._emit(ev, name, dur_s, cpu_us)

    def _finish_span(self, span: Span) -> None:
        promoted = False
        if span.trace_id and not span.sampled:
            # unsampled trace: the span vanishes unless it crossed the
            # complaint time — then it is promoted into the ring so
            # SLOW_OPS / flight bundles / slo_report never go dark
            if span.dur < self.slow_threshold_s:
                if span.parent_id == 0:      # the root finished fast
                    self._drop_micro(span.trace_id)
                return
            promoted = True
            self._drop_micro(span.trace_id)
        if not span.trace_id and not span.args and span.track is None:
            # the hot shape (untraced, no args, no track): defer the
            # event-dict build to dump() — evicted events never pay it
            ev = (span.name, span.cat, span.ts_us, span.dur * 1e6,
                  threading.get_ident())
            if span.cpu_us is not None:
                ev += (span.cpu_us,)
            self._emit_lite(ev)
            return
        ev = {"name": span.name, "cat": span.cat or "span", "ph": "X",
              "ts": span.ts_us, "dur": span.dur * 1e6,
              "pid": self.pid, "tid": threading.get_ident()}
        args = dict(span.args) if span.args else {}
        if span.trace_id:
            args["trace_id"] = span.trace_id
            args["span_id"] = span.span_id
            args["parent_span_id"] = span.parent_id
            # the owner class rides every traced span so the critical-
            # path ledger (common/critpath.py) can classify a trace
            # without re-deriving it from span-name heuristics
            args.setdefault("op_class", span.op_class)
            if promoted:
                args["promoted"] = True
            elif span.weight != 1.0:
                args["sample_weight"] = span.weight
        if span.cpu_us is not None:
            args["cpu_us"] = span.cpu_us
        if args:
            ev["args"] = args
        if span.track is not None:
            ev["track"] = span.track
        self._emit(ev, span.name, span.dur, span.cpu_us)

    # -- per-thread batching -------------------------------------------------

    def _pending_buf(self) -> list:
        buf = getattr(self._local, "pending", None)
        if buf is None:
            buf = self._local.pending = []
            with self._lock:
                old = self._pending.get(threading.get_ident())
                if old:
                    # a dead thread's ident was reused: fold its
                    # leftovers before the new owner takes the slot
                    self._fold_locked(old)
                self._pending[threading.get_ident()] = buf
        return buf

    def _emit(self, ev: dict, name: str | None = None,
              dur_s: float = 0.0, cpu_us: float | None = None) -> None:
        buf = self._pending_buf()
        buf.append((ev, name, dur_s, cpu_us))
        if len(buf) >= FLUSH_BATCH:
            self._flush_buf(buf)

    def _emit_lite(self, ev: tuple) -> None:
        # a lite event rides the buffer BARE (no wrapper triple): the
        # fold recognizes it by its first field, the name, and derives
        # name/duration from it, so the hot path allocates one tuple per
        # op, not two
        buf = getattr(self._local, "pending", None)
        if buf is None:
            buf = self._pending_buf()
        buf.append(ev)
        if len(buf) >= FLUSH_BATCH:
            self._flush_buf(buf)

    def _flush_buf(self, buf: list) -> None:
        with self._lock:
            self._fold_locked(buf)

    def _fold_locked(self, buf: list) -> None:
        # under self._lock.  The owner may append concurrently (without
        # the lock): capture len first, drain exactly that prefix — the
        # append lands at the tail and survives for the next flush.
        n = len(buf)
        if not n:
            return
        items = buf[:n]
        del buf[:n]
        for item in items:
            if type(item[0]) is str:
                # bare lite event: (name, cat, ts_us, dur_us, tid) and,
                # from a span that read the CPU clock, cpu_us
                self._events.append(item)
                self._hist_add_locked(item[0], item[3] * 1e-6)
                if len(item) > 5:
                    _span_cpu_add(item[0], item[3], item[5])
            else:
                ev, name, dur_s, cpu_us = item
                self._events.append(ev)
                if name is not None:
                    self._hist_add_locked(name, dur_s)
                if cpu_us is not None:
                    _span_cpu_add(name, dur_s * 1e6, cpu_us)

    def flush(self) -> None:
        """Fold the CALLING thread's pending batch into the ring — the
        completion-boundary hook (pipeline complete, dispatcher worker
        loop, serving finisher, mux sender loop)."""
        buf = getattr(self._local, "pending", None)
        if buf:
            self._flush_buf(buf)

    def _drain_all_locked(self) -> None:
        for buf in list(self._pending.values()):
            self._fold_locked(buf)

    def _hist_add_locked(self, name: str, dur_s: float) -> None:
        # cells are flat lists [counts, sum, count] and the bucket scan
        # is a C-level bisect: this runs once per event inside the fold
        # critical section, so it is the floor of the batched ring cost
        h = self._hist.get(name)
        if h is None:
            h = self._hist[name] = [[0] * (len(LATENCY_BUCKETS_S) + 1),
                                    0.0, 0]
        h[0][bisect_left(LATENCY_BUCKETS_S, dur_s)] += 1
        h[1] += dur_s
        h[2] += 1

    # -- export --------------------------------------------------------------

    def _materialize(self, ev) -> dict:
        """A ring entry as a Chrome event dict.  Lite tuples (the
        untraced span/observe fast path) build their dict HERE, once
        per surviving event, instead of once per op."""
        if type(ev) is tuple:
            name, cat, ts, dur, tid = ev[:5]
            out = {"name": name, "cat": cat or "span", "ph": "X",
                   "ts": ts, "dur": dur, "pid": self.pid, "tid": tid}
            if len(ev) > 5:
                out["args"] = {"cpu_us": ev[5]}
            return out
        return dict(ev)

    def dump(self, stitched: bool = True) -> dict:
        """Chrome trace-event JSON (the ``trace dump`` admin command):
        load in chrome://tracing or ui.perfetto.dev as-is.

        ``stitched`` (default) renders the cross-daemon view: events
        whose span carried a daemon *track* ('osd.3', 'client') are
        re-homed onto a synthetic pid per track — one process row per
        daemon — with ``process_name`` metadata events naming the rows,
        so one client op's spans across N daemons line up on one shared
        timeline (all tracks stamp from this tracer's clock pair)."""
        with self._lock:
            self._drain_all_locked()
            events = [self._materialize(ev) for ev in self._events]
        if stitched:
            track_pids: dict[str, int] = {}
            meta: list[dict] = []
            for ev in events:
                track = ev.pop("track", None)
                if track is None:
                    continue
                pid = track_pids.get(track)
                if pid is None:
                    # deterministic synthetic pids, far from real ones
                    pid = track_pids[track] = 1_000_000 + len(track_pids)
                    meta.append({"name": "process_name", "ph": "M",
                                 "pid": pid, "tid": 0,
                                 "args": {"name": track}})
                ev["pid"] = pid
            events = meta + events
        else:
            for ev in events:
                ev.pop("track", None)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def reset(self) -> dict:
        with self._lock:
            self._drain_all_locked()
            n = len(self._events)
            self._events.clear()
            self._hist.clear()
        with self._micro_lock:
            self._micro.clear()
        return {"success": f"dropped {n} events"}

    def histograms(self) -> dict:
        """Per-span-name latency histograms: {name: {buckets (bounds, s),
        counts (len+1, last = overflow), sum, count}}."""
        with self._lock:
            self._drain_all_locked()
            return {name: {"buckets": list(LATENCY_BUCKETS_S),
                           "counts": list(h[0]),
                           "sum": h[1], "count": h[2]}
                    for name, h in self._hist.items()}


_default_tracer: Tracer | None = None
_default_lock = threading.Lock()


def default_tracer() -> Tracer:
    global _default_tracer
    if _default_tracer is None:
        with _default_lock:
            if _default_tracer is None:
                _default_tracer = Tracer(int(os.environ.get(
                    "CEPH_TPU_TRACE_CAPACITY", TRACE_CAPACITY)))
    return _default_tracer


def wire_config(conf) -> None:
    """Adopt the default tracer's sampling knobs from a ConfigProxy and
    follow live updates: ``tracer_sample_rate`` sets the head-based
    sampling probability, ``osd_op_complaint_time`` doubles as the
    slow-op promotion threshold (the same bound SLOW_OPS health uses, so
    'promoted into the ring' and 'flagged slow' agree by construction)."""
    tr = default_tracer()
    if "tracer_sample_rate" in conf.schema:
        tr.sample_rate = float(conf.get("tracer_sample_rate"))

        def _on_rate(_name, v, _tr=tr):
            _tr.sample_rate = float(v)
        conf.add_observer("tracer_sample_rate", _on_rate)
    if "osd_op_complaint_time" in conf.schema:
        tr.slow_threshold_s = float(conf.get("osd_op_complaint_time"))

        def _on_complaint(_name, v, _tr=tr):
            _tr.slow_threshold_s = float(v)
        conf.add_observer("osd_op_complaint_time", _on_complaint)


def trace_span(name: str, cat: str = "", cpu: bool = False,
               **args) -> Span:
    """Convenience: a span on the process-default tracer."""
    return default_tracer().span(name, cat, cpu, **args)


def trace_instant(name: str, cat: str = "", **args) -> None:
    default_tracer().instant(name, cat, **args)


def new_trace(op_class: str = "client") -> TraceContext:
    """A fresh root trace context on the process-default tracer."""
    return default_tracer().new_trace(op_class)


def current_trace() -> TraceContext | None:
    """The calling thread's active TraceContext, if any."""
    return default_tracer().current_ctx()


def activate_trace(ctx: TraceContext | None,
                   track: str | None = None) -> _Activation:
    """Adopt an inbound context / daemon track on the default tracer."""
    return default_tracer().activate(ctx, track)


def root_or_ambient(op_class: str) -> _Activation:
    """Activate the calling thread's ambient trace context — or root a
    fresh ``op_class`` trace when none is active — so the sub-ops a call
    fans out attribute their wire bytes and device time to the right
    owner class (an enclosing scrub-repair/scheduler-wave context wins
    over the default)."""
    tr = default_tracer()
    return tr.activate(tr.current_ctx() or tr.new_trace(op_class))


# -- CPU time beside wall time ----------------------------------------------
#
# Two process-wide collections every Context registers beside ``jit``.
# ``span_cpu``: for each span name that read its thread's CPU clock
# (``cpu=True`` / ``observe(cpu_s=)``), the CPU microseconds inside its
# spans and the rest of their wall time: what the thread stood blocked
# in a call or waiting for the interpreter.  Booked in the fold, beside
# the name's histogram and from the same events, so a reader that drains
# (``histograms()``) and then dumps sees sums of one set of spans;
# ``perf dump`` alone lags a thread's unfolded batch, as the histograms
# do.  ``thread_cpu``: each service thread's own CPU, by role.

_cpu_lock = threading.Lock()
_span_cpu_perf = None
# span name -> [cpu key, off-cpu key, what the off-cpu sum is owed (<= 0)]
_span_cpu_keys: dict[str, list] = {}
_thread_cpu_perf = None
_thread_cpu_local = threading.local()

#: the roles a service thread charges its CPU to (``charge_thread_cpu``)
THREAD_ROLES = ("reactor", "dispatch", "coalescer", "finisher")
# a thread reads its CPU clock at most this often: the read is a system
# call that keeps the interpreter (5.6 us on the chip's host, some 17
# in a busy server) and a reactor runs thousands of rounds a second;
# what a role lags by is under this a thread
CHARGE_EVERY_S = 0.1


def span_cpu_perf_counters():
    """The process-wide ``span_cpu`` PerfCounters: ``<span>.cpu_us`` and
    ``<span>.offcpu_us``, declared when a name's first event folds."""
    global _span_cpu_perf
    with _cpu_lock:
        if _span_cpu_perf is None:
            from .perf_counters import PerfCounters
            _span_cpu_perf = PerfCounters("span_cpu")
        return _span_cpu_perf


def _span_cpu_add(name: str, dur_us: float, cpu_us: float) -> None:
    # in a fold, under the folding tracer's lock
    pc = _span_cpu_perf or span_cpu_perf_counters()
    st = _span_cpu_keys.get(name)
    if st is None:
        st = [f"{name}.cpu_us", f"{name}.offcpu_us", 0]
        pc.declare_counter(
            st[0], f"microseconds of their thread's CPU clock inside "
                   f"{name} spans")
        pc.declare_counter(
            st[1], f"microseconds of {name} spans' wall time their "
                   f"thread did not run: blocked in a call or waiting "
                   f"for the interpreter (wall - CPU, never below 0)")
        _span_cpu_keys[name] = st
    cpu = int(cpu_us + 0.5)
    pc.inc(st[0], cpu)
    # wall - CPU, summed: a CPU clock that ticks (100 Hz on the chip's
    # host) charges one short span a whole tick and the next ninety
    # nothing, so a span that reads more CPU than wall time is owed to
    # the ones after it and not dropped.  The sum is what is never
    # below 0, and cpu + off-cpu stays the spans' wall time
    off = int(dur_us + 0.5) - cpu + st[2]
    if off > 0:
        pc.inc(st[1], off)
        off = 0
    st[2] = off


def thread_cpu_perf_counters():
    """The process-wide ``thread_cpu`` PerfCounters: microseconds of CPU
    by service-thread role, and ``process`` — every thread of the
    process, JAX's and the runtime's too — read from the process's own
    clock at a reactor's charge, so as current as the roles are.  What
    no role claims is ``process`` less the roles."""
    global _thread_cpu_perf
    with _cpu_lock:
        if _thread_cpu_perf is None:
            from .perf_counters import PerfCountersBuilder
            b = PerfCountersBuilder("thread_cpu")
            for role in THREAD_ROLES:
                b.add_u64_counter(
                    role, f"CPU microseconds of the {role} threads, "
                          f"each charging its own thread clock at the "
                          f"end of a round of its loop")
            b.add_u64("process",
                      "CPU microseconds of the whole process (user + "
                      "system, every thread), as of a reactor thread's "
                      "last charge")
            _thread_cpu_perf = b.create_perf_counters()
        return _thread_cpu_perf


def charge_thread_cpu(role: str) -> None:
    """Charge the calling thread's CPU since its last charge (since its
    start, the first time) to ``role``: a read of the thread's CPU clock
    and one sharded ``inc``, the state thread-local.  For a service
    thread's loop, once a round, outside its locks; a round inside
    ``CHARGE_EVERY_S`` of the thread's last charge reads the wall clock
    only and leaves its CPU to the next.  A reactor's charge also
    brings ``process`` up to date."""
    if not instruments.enabled():
        return
    local = _thread_cpu_local
    t = time.perf_counter()
    if t < getattr(local, "due", 0.0):
        return
    local.due = t + CHARGE_EVERY_S
    now = time.thread_time_ns()
    us, rem = divmod(now - getattr(local, "ns", 0), 1000)
    local.ns = now - rem
    pc = _thread_cpu_perf or thread_cpu_perf_counters()
    pc.inc(role, us)
    if role == "reactor":
        pc.set("process", time.process_time_ns() // 1000)


# -- JIT telemetry registry (fed by ceph_tpu.ops.traced_jit) ----------------
#
# Keyed by (function label, shape key).  Each entry exists because exactly
# one compilation happened for that key; re-dispatches bump ``calls``.  The
# ``jit`` PerfCounters collection aggregates across keys and is registered
# into every Context's collection so `perf dump` / prometheus carry it.

_jit_lock = threading.Lock()
_jit_stats: dict[tuple, dict] = {}
_jit_perf = None


def jit_perf_counters():
    """The process-wide ``jit`` PerfCounters (built lazily: tracer must
    stay importable before perf_counters in partial environments)."""
    global _jit_perf
    with _jit_lock:
        if _jit_perf is None:
            from .perf_counters import PerfCountersBuilder
            _jit_perf = (
                PerfCountersBuilder("jit")
                .add_u64_counter("compilations",
                                 "distinct (function, shape) compiles")
                .add_u64_counter("cache_hits",
                                 "dispatches served by a compiled cache key")
                .add_time_avg("trace_time", "jaxpr trace wall time")
                .add_time_avg("compile_time", "XLA compile wall time")
                .add_time_avg("first_dispatch_time",
                              "first execution incl. completion wait")
                .create_perf_counters())
        return _jit_perf


def record_compilation(fn_label: str, key, trace_s: float, compile_s: float,
                       dispatch_s: float) -> None:
    pc = jit_perf_counters()
    pc.inc("compilations")
    pc.tinc("trace_time", trace_s)
    pc.tinc("compile_time", compile_s)
    pc.tinc("first_dispatch_time", dispatch_s)
    with _jit_lock:
        entry = _jit_stats.get((fn_label, key))
        if entry is None:
            _jit_stats[(fn_label, key)] = {
                "function": fn_label, "key": repr(key), "compiles": 1,
                "trace_s": trace_s, "compile_s": compile_s,
                "first_dispatch_s": dispatch_s, "calls": 1}
        else:
            # distinct jitted closures can share a label (e.g. one
            # BulkMapper kernel per CRUSH rule): accumulate, don't clobber
            entry["compiles"] += 1
            entry["calls"] += 1
            entry["trace_s"] += trace_s
            entry["compile_s"] += compile_s
            entry["first_dispatch_s"] += dispatch_s


def record_cache_hit(fn_label: str, key) -> None:
    jit_perf_counters().inc("cache_hits")
    with _jit_lock:
        entry = _jit_stats.get((fn_label, key))
        if entry is not None:
            entry["calls"] += 1


def jit_dump() -> dict:
    """The ``jit dump`` admin command: per-key stats + the aggregate
    counters, compile-cost-sorted so the expensive kernels lead."""
    with _jit_lock:
        entries = [dict(e) for e in _jit_stats.values()]
    entries.sort(key=lambda e: e["compile_s"], reverse=True)
    return {"functions": entries,
            "num_keys": len(entries),
            "counters": jit_perf_counters().dump()}


def jit_reset() -> dict:
    with _jit_lock:
        n = len(_jit_stats)
        _jit_stats.clear()
    return {"success": f"dropped {n} jit cache-key records"}
