"""CodecPipeline: depth-limited async device dispatch for codec batches.

The transfer-stall fix the ISSUE-5 tentpole names: every synchronous
``RSCodec.encode``/``decode`` call blocks on ``np.asarray(jax.device_get)``
right after dispatch, so host-side pack/unpack (``np.stack``, transposes,
``ascontiguousarray``) and device compute run SERIALLY.  JAX dispatch is
asynchronous on every backend (a dispatched computation runs in the XLA
runtime while Python continues), so the pipeline keeps up to ``depth``
dispatched batches in flight and defers ``block_until_ready`` to an
explicit completion boundary:

    submit(pack, dispatch, unpack):
        pack()              host: build the folded uint8 block      [overlaps
        dispatch(packed)    device: async kernel launch              previous
        -> PipelineFuture                                            batches'
    completion (oldest-first once depth is exceeded, or flush(),     device
    or an out-of-order ``result()``):                                compute]
        block_until_ready + device_get                 <- the ONLY host sync
        unpack(packed, host) -> future's result

This module IS the completion boundary: ``tests/test_no_host_sync.py``
guards that ``exec/`` and ``recovery/`` never call ``jax.device_get`` /
``block_until_ready`` (or import jax at all) — batch N+1's host prep in
those layers can therefore never accidentally serialise against batch N's
device work.

Every stage lands on the PR-1 tracer (``pipeline.pack``/``dispatch``/
``complete`` spans) plus an in-flight-depth perf collection.

Multi-chip: when ``jax_rs_mesh_devices`` names >= 2 devices, encode and
decode dispatches split the coalesced batch across the ``dp`` axis of a
``parallel.mesh`` device mesh (``sharded_batch_encode_step`` — the
parity-only serving variant of the dryrun-validated encode step — and
``sharded_decode_step``), so the serving path rides the same shard_map
machinery the MULTICHIP dryruns validate.
"""
from __future__ import annotations

import collections
import threading
import weakref

import jax
import jax.numpy as jnp

from ..common import default_context
from ..common import device_attribution
from ..common.perf_counters import PerfCountersBuilder
from ..common.tracer import (activate_trace, current_trace,
                             default_tracer, trace_span)
from ..failure.breaker import CircuitBreaker, state_rank
from ..failure.injector import InjectedFault, InjectedOOM

DEPTH_BUCKETS = [0, 1, 2, 4, 8, 16, 32]

_MISSING = object()


class PipelineFuture:
    """Completion handle for one in-flight device batch.

    ``result()``/``exception()`` FORCE completion when the item is still
    in flight (out-of-order completion is legal: forcing item 3 before
    item 1 completes 3 alone; 1 stays dispatched).  Device-side failures
    (anything ``block_until_ready`` or the unpack stage raises) surface
    here, never on the dispatching thread.

    ``timeout`` bounds only the wait for ANOTHER thread to finish the
    item: the forcing path runs the completion itself, and JAX has no
    timed sync — ``block_until_ready`` waits on the device unboundedly.
    """

    __slots__ = ("kind", "meta", "owner", "fallback", "trace",
                 "_pipeline", "_packed", "_dev", "_unpack",
                 "_host_fallback", "_dispatched_at", "_event", "_result",
                 "_error", "_callbacks", "_cb_lock")

    def __init__(self, pipeline: "CodecPipeline", kind: str, meta: dict,
                 owner: str = "client", trace=None):
        self.kind = kind
        self.meta = meta
        # the owner class this batch's device occupancy is charged to
        # (common/device_attribution), resolved on the SUBMITTING thread
        # where the trace context is active
        self.owner = owner
        # the submitter's TraceContext: completion/fallback spans run on
        # whatever thread forces the boundary, and activating this keeps
        # them in the op's trace (critical-path `device`/`retry` phases)
        self.trace = trace
        # True when the sync host codec served this batch (breaker open
        # or a device failure healed by the fallback)
        self.fallback = False
        self._pipeline = weakref.ref(pipeline)
        self._packed = None
        self._dev = None
        self._unpack = None
        self._host_fallback = None
        self._dispatched_at = 0.0
        self._event = threading.Event()
        self._result = None
        self._error: BaseException | None = None
        self._callbacks: list = []
        self._cb_lock = threading.Lock()

    # -- consumer side -----------------------------------------------------

    def done(self) -> bool:
        return self._event.is_set()

    @property
    def value(self):
        """The result, valid once done (for done-callbacks)."""
        return self._result

    @property
    def error(self) -> BaseException | None:
        """The failure, valid once done (for done-callbacks)."""
        return self._error

    def _force(self) -> None:
        if not self._event.is_set():
            pl = self._pipeline()
            if pl is not None:
                pl.complete(self)

    def result(self, timeout: float | None = None):
        self._force()
        if not self._event.wait(timeout):
            raise TimeoutError(f"pipeline item not complete within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result

    def exception(self, timeout: float | None = None):
        self._force()
        if not self._event.wait(timeout):
            raise TimeoutError(f"pipeline item not complete within {timeout}s")
        return self._error

    def add_done_callback(self, fn) -> None:
        """``fn(future)`` on completion; immediate when already done."""
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    # -- pipeline side -----------------------------------------------------

    def _finish(self, result, error: BaseException | None) -> None:
        with self._cb_lock:
            self._result = result
            self._error = error
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            fn(self)


def _build_perf(name: str):
    return (PerfCountersBuilder(name)
            .add_u64("in_flight", "dispatched device batches not yet "
                                  "completed (the pipeline's depth gauge)")
            .add_u64_counter("submitted", "batches submitted to the pipeline")
            .add_u64_counter("device_dispatches",
                             "submitted batches whose dispatch launched "
                             "work on the device (a host-only decode and "
                             "a host fallback launch none)")
            .add_u64_counter("completed", "batches completed (fetch + unpack)")
            .add_u64_counter("errors", "batches that failed in pack, "
                                       "dispatch, device compute, or unpack")
            .add_u64_counter("mesh_dispatches",
                             "batches split across the device mesh's dp "
                             "axis (jax_rs_mesh_devices engaged)")
            .add_u64_counter("host_fallbacks",
                             "batches served by the sync host codec "
                             "because the device breaker was open or "
                             "the device failed with a fallback in hand")
            .add_u64("breaker_state",
                     "circuit breaker state (0 closed, 1 half-open "
                     "probe in flight, 2 open: device path bypassed)")
            .add_histogram("inflight_depth", DEPTH_BUCKETS,
                           "in-flight depth observed at each dispatch")
            .add_time_avg("pack_time", "host pack stage (overlaps in-flight "
                                       "device compute)")
            .add_time_avg("dispatch_time", "async device dispatch stage")
            .add_time_avg("complete_time", "completion boundary: device "
                                           "sync + host unpack")
            .create_perf_counters())


class CodecPipeline:
    """Depth-limited async dispatch queue over the device codec.

    ``depth`` bounds in-flight device batches (0 = synchronous: every
    submit completes before returning — the comparison baseline).  When a
    submit exceeds the bound, the OLDEST item completes first: that is
    the pipeline's backpressure AND its completion boundary on the
    steady-state path.
    """

    def __init__(self, depth: int | None = None,
                 name: str = "codec_pipeline", cct=None,
                 mesh_devices: int | None = None):
        self.cct = cct if cct is not None else default_context()
        conf = self.cct.conf
        self.name = name
        self.depth = int(conf.get("jax_rs_pipeline_depth")
                         if depth is None else depth)
        self.mesh_devices = int(conf.get("jax_rs_mesh_devices")
                                if mesh_devices is None else mesh_devices)
        self.perf = _build_perf(name)
        self.cct.perf.add(self.perf)
        self._lock = threading.Lock()
        self._queue: collections.OrderedDict = collections.OrderedDict()
        # circuit breaker on the device path (failure/breaker.py):
        # pipeline_breaker_threshold consecutive device failures open it
        # and fallback-capable submits run the sync host codec until a
        # half-open probe (after pipeline_breaker_cooldown) re-closes.
        # Threshold 0 disables (no breaker, pre-ISSUE-9 behavior).
        thresh = int(conf.get("pipeline_breaker_threshold"))
        self.breaker = CircuitBreaker(
            f"{name}.breaker", threshold=thresh,
            cooldown=float(conf.get("pipeline_breaker_cooldown"))) \
            if thresh > 0 else None
        # device-plane fault injection (failure/injector.py): when set,
        # dispatch/completion rolls may raise InjectedFault/InjectedOOM
        self.fault_injector = None
        # the text of the newest device failure (dispatch or completion)
        # and of the mesh probe's, if it failed: a healed batch or a
        # latched-off mesh must still say why the device was not used
        self.last_device_error: str | None = None
        self.mesh_error: str | None = None
        self._mesh = None
        self._enc_steps: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._dec_step = None

    # -- lifecycle ---------------------------------------------------------

    def close(self) -> None:
        """Drain and unhook the perf collection (the repo's discipline:
        a discarded component must not leave frozen gauges behind); the
        breaker leaves the live registry so it stops raising
        DEVICE_DEGRADED."""
        self.flush()
        self.cct.perf.remove(self.perf.name)
        if self.breaker is not None:
            self.breaker.close()

    def reopen(self) -> None:
        """Re-register the perf collection AND the breaker after a close
        (engine restart) — a reopened pipeline's breaker must be visible
        to DEVICE_DEGRADED again."""
        self.cct.perf.add(self.perf)
        if self.breaker is not None:
            self.breaker.reopen()

    # -- fault injection (device plane) ------------------------------------

    def inject_faults(self, injector) -> None:
        """Attach (or, with None, detach) a FaultInjector whose device
        plane rolls dispatch/completion failures and simulated OOM into
        this pipeline — the chaos harness hook."""
        self.fault_injector = injector

    def _roll_device_fault(self, stage: str) -> None:
        inj = self.fault_injector
        if inj is None:
            return
        f = inj.plan.device
        if stage == "dispatch":
            if inj.roll("device", "oom", f.oom_prob, target=self.name):
                raise InjectedOOM("RESOURCE_EXHAUSTED: injected device "
                                  "OOM at dispatch")
            if inj.roll("device", "dispatch_fail", f.dispatch_fail_prob,
                        target=self.name):
                raise InjectedFault("injected device dispatch failure")
        elif inj.roll("device", "completion_fail",
                      f.completion_fail_prob, target=self.name):
            raise InjectedFault("injected device completion failure")

    # -- breaker bookkeeping -----------------------------------------------

    def _device_failure(self, stage: str, exc: BaseException) -> None:
        with self._lock:    # completions run on whichever thread forces
            self.last_device_error = \
                f"{stage}: {type(exc).__name__}: {exc}"
        if self.breaker is not None:
            self.breaker.record_failure()
            self.perf.set("breaker_state", state_rank(self.breaker.state))

    def _device_success(self) -> None:
        if self.breaker is not None:
            self.breaker.record_success()
            self.perf.set("breaker_state", 0)

    def _serve_host(self, fut: PipelineFuture, host_fallback,
                    unpack) -> PipelineFuture:
        """Serve one batch entirely on the host codec (breaker open, or
        a device failure with a fallback in hand).  The batch is marked
        degraded in device attribution so `device top` shows how much
        work the chip is NOT doing."""
        fut.fallback = True
        self.perf.inc("host_fallbacks")
        if self.breaker is not None:
            self.breaker.note_fallback()
        try:
            # re-activate the submitter's trace: the fallback is the
            # op's RETRY time (critical-path phase registry), and it may
            # run on a different thread than the submit
            with activate_trace(fut.trace), \
                    trace_span("pipeline.host_fallback", kind=fut.kind,
                               owner=fut.owner), \
                    self.perf.time("complete_time"):
                host = host_fallback(fut._packed)
                result = unpack(fut._packed, host) \
                    if unpack is not None else host
            device_attribution.record_host_fallback(
                fut.owner, getattr(host, "nbytes", 0) or 0)
            self.perf.inc("completed")
            fut._packed = fut._host_fallback = None
            fut._finish(result, None)
        except BaseException as e:              # noqa: BLE001 — the future
            self.perf.inc("errors")             # carries the failure
            fut._packed = fut._host_fallback = None
            fut._finish(None, e)
        return fut

    @property
    def in_flight(self) -> int:
        with self._lock:
            return len(self._queue)

    # -- submission --------------------------------------------------------

    def submit(self, pack, dispatch, unpack, kind: str = "op",
               owner: str | None = None, host_fallback=None,
               **meta) -> PipelineFuture:
        """Run ``pack()`` (host) and ``dispatch(packed)`` (async device
        launch) NOW; defer ``unpack(packed, host_arrays)`` to the
        completion boundary.  Returns the future; errors in any stage
        land on it.  ``owner`` tags the batch's device occupancy
        (client/serving/recovery/scrub/rebalance); when omitted it
        resolves from the active TraceContext's op class.

        ``host_fallback(packed)`` — when provided — is the sync host
        codec's answer to the same batch: it serves the batch when the
        circuit breaker is open (skipping the doomed dispatch entirely)
        and HEALS a batch whose dispatch or device compute fails, so a
        dying device degrades throughput instead of failing ops."""
        fut = PipelineFuture(self, kind, meta,
                             owner=device_attribution.resolve_owner(owner),
                             trace=current_trace())
        self.perf.inc("submitted")
        # pack is host work: its failures are the caller's bug, never
        # breaker evidence — keep it outside the device try
        try:
            with trace_span("pipeline.pack", kind=kind, owner=fut.owner), \
                    self.perf.time("pack_time"):
                packed = pack() if pack is not None else None
            fut._packed = packed
        except BaseException as e:              # noqa: BLE001 — the future
            self.perf.inc("errors")             # carries the failure
            fut._finish(None, e)
            return fut
        if host_fallback is not None and self.breaker is not None \
                and not self.breaker.allow():
            return self._serve_host(fut, host_fallback, unpack)
        try:
            self._roll_device_fault("dispatch")
            with trace_span("pipeline.dispatch", kind=kind,
                            owner=fut.owner), \
                    self.perf.time("dispatch_time"):
                fut._dev = dispatch(packed)
            if fut._dev is not None:            # None: a host-only item
                self.perf.inc("device_dispatches")
            fut._dispatched_at = device_attribution.dispatch_mark()
            fut._unpack = unpack
            fut._host_fallback = host_fallback
        except BaseException as e:              # noqa: BLE001 — the future
            self._device_failure("dispatch", e)  # carries the failure ...
            if host_fallback is not None:        # ... unless the host can
                return self._serve_host(fut, host_fallback, unpack)
            self.perf.inc("errors")
            fut._finish(None, e)
            return fut
        with self._lock:
            self._queue[fut] = True
            depth = len(self._queue)
        self.perf.hinc("inflight_depth", depth)
        self.perf.set("in_flight", depth)
        if self.depth <= 0:
            self.complete(fut)                  # synchronous mode
        else:
            while True:
                with self._lock:
                    if len(self._queue) <= self.depth:
                        break
                    oldest = next(iter(self._queue))
                self.complete(oldest)
        return fut

    # -- completion boundary -----------------------------------------------

    def complete(self, fut: PipelineFuture) -> PipelineFuture:
        """Complete ONE item (possibly out of order): the only place the
        serving/recovery data path waits on the device."""
        with self._lock:
            present = self._queue.pop(fut, _MISSING) is not _MISSING
            self.perf.set("in_flight", len(self._queue))
        if not present:
            # already completed (or another thread is completing it now)
            fut._event.wait()
            return fut
        result, error = None, None
        # a host-only item (a clean read's relayout) occupies no device:
        # it records no device batch, here or on a failure below
        recorded = fut._dev is None
        device_ok = False
        try:
            with activate_trace(fut.trace), \
                    trace_span("pipeline.complete", kind=fut.kind,
                               owner=fut.owner), \
                    self.perf.time("complete_time"):
                self._roll_device_fault("completion")
                dev = fut._dev
                if dev is not None:             # None: a host-only item
                    with trace_span("pipeline.device_wait"):
                        dev = jax.block_until_ready(dev)
                device_ok = True
                self._device_success()
                nbytes = getattr(dev, "nbytes", 0) or 0
                # device occupancy ends at block_until_ready: the
                # device_get transfer and the host-side unpack below are
                # HOST time — charging them would inflate busy_s and the
                # owner's share while the chip sits idle
                if not recorded:
                    device_attribution.record_batch(
                        fut.owner, fut._dispatched_at, nbytes)
                    recorded = True
                host = None
                if dev is not None:
                    with trace_span("pipeline.fetch", bytes=nbytes):
                        host = jax.device_get(dev)
                if fut._unpack is not None:
                    with trace_span("pipeline.unpack"):
                        result = fut._unpack(fut._packed, host)
                else:
                    result = host
        except BaseException as e:              # noqa: BLE001 — device-side
            error = e                           # failures surface on the
            if not recorded:                    # future, not the completer
                # the chip was busy up to the failure either way
                device_attribution.record_batch(fut.owner,
                                                fut._dispatched_at, 0)
            if not device_ok:
                self._device_failure("complete", e)
                if fut._host_fallback is not None:
                    # a completion-boundary device failure with the host
                    # answer in hand: heal the batch instead of failing it
                    fallback, unpack = fut._host_fallback, fut._unpack
                    fut._dev = fut._unpack = None
                    return self._serve_host(fut, fallback, unpack)
            self.perf.inc("errors")
        self.perf.inc("completed")
        # free buffers promptly
        fut._packed = fut._dev = fut._unpack = fut._host_fallback = None
        fut._finish(result, error)
        # pipeline completion boundary: fold this thread's pending span
        # batch into the tracer ring once per completed item
        default_tracer().flush()
        return fut

    def complete_one(self) -> bool:
        """Complete the oldest in-flight item; False when empty."""
        with self._lock:
            if not self._queue:
                return False
            oldest = next(iter(self._queue))
        self.complete(oldest)
        return True

    def flush(self) -> None:
        """Complete everything in flight (oldest first)."""
        while self.complete_one():
            pass

    # -- device dispatch helpers (single-chip or mesh-sharded) -------------

    def _mesh_ctx(self):
        """The (cached) device mesh when ``jax_rs_mesh_devices`` engages:
        >= 2 devices requested AND present.  A failed probe latches off —
        the serving path must not re-raise per batch — and leaves its
        reason in ``mesh_error``."""
        if self.mesh_devices < 2 or self.mesh_error is not None:
            return None
        if self._mesh is None:
            try:
                from ..parallel import mesh as meshmod
                self._mesh = meshmod.make_mesh(self.mesh_devices)
            except Exception as e:              # noqa: BLE001 — latched,
                self.mesh_error = f"{type(e).__name__}: {e}"   # text kept
                return None
        return self._mesh

    def dispatch_encode(self, codec, data_shards, chunk_size: int):
        """``data_shards`` [k, S*chunk] host uint8 (logical row order) ->
        device parity [m, S*chunk], dispatched async.  Splits the stripe
        batch over the mesh's dp axis when the mesh engages and the
        shapes divide; single-chip dispatch otherwise."""
        mesh = self._mesh_ctx()
        if mesh is not None:
            out = self._mesh_encode(codec, data_shards, int(chunk_size),
                                    mesh)
            if out is not None:
                return out
        return codec.encode_device(jnp.asarray(data_shards))

    def _mesh_encode(self, codec, data_shards, c: int, mesh):
        k, total = data_shards.shape
        if c <= 0 or total % c:
            return None
        stripes = total // c
        dp, sp = mesh.shape["dp"], mesh.shape["sp"]
        if c % sp:
            return None
        step = self._enc_steps.get(codec)
        if step is None:
            from ..parallel import mesh as meshmod
            step = meshmod.sharded_batch_encode_step(mesh, codec.parity_mat)
            self._enc_steps[codec] = step
        # [k, S*c] -> [S, k, c] (+ zero stripes up to a dp multiple: RS is
        # positionwise-linear, zero stripes encode to zero parity)
        data = jnp.asarray(data_shards).reshape(k, stripes, c)
        data = jnp.swapaxes(data, 0, 1)
        pad = (-stripes) % dp
        if pad:
            data = jnp.pad(data, ((0, pad), (0, 0), (0, 0)))
        parity = step(data)
        self.perf.inc("mesh_dispatches")
        parity = jnp.swapaxes(parity[:stripes], 0, 1)
        return parity.reshape(codec.m, total)

    def host_encode(self, codec, data_shards, chunk_size: int):
        """The sync-host mirror of :meth:`dispatch_encode` — the
        ``host_fallback`` the ecutil pipelined entries hand to submit."""
        return codec.encode_host(data_shards)

    def host_decode(self, codec, stack, erasures, available):
        """The sync-host mirror of :meth:`dispatch_decode`."""
        return codec.decode_host(stack, erasures, available)

    def dispatch_decode(self, codec, stack, erasures, available):
        """``stack`` [k', S*chunk] host uint8 survivors in the sorted-src
        order ``codec.decode_matrix(erasures, available)`` returns ->
        device recovered rows [len(erasures), S*chunk], async.  Mesh
        path: survivors shard over dp, partial GF products psum over ICI
        (``sharded_decode_step``)."""
        mesh = self._mesh_ctx()
        if mesh is not None:
            out = self._mesh_decode(codec, stack, erasures, available, mesh)
            if out is not None:
                return out
        return codec.decode_device(jnp.asarray(stack), erasures,
                                   available)

    def _mesh_decode(self, codec, stack, erasures, available, mesh):
        # the DEVICE-resident matrix from the signature LRU: an LRU hit
        # must cost zero host->device transfers on the mesh path too
        # (the step's jnp.asarray is a no-op on a device array)
        D, src = codec.decode_matrix_device(erasures, available)
        kk, total = stack.shape
        if kk != len(src):
            return None
        sp = mesh.shape["sp"]
        pad = (-total) % sp
        if self._dec_step is None:
            from ..parallel import mesh as meshmod
            self._dec_step = meshmod.sharded_decode_step(mesh)
        chunks = jnp.asarray(stack)
        if pad:
            chunks = jnp.pad(chunks, ((0, 0), (0, pad)))
        out = self._dec_step(D, chunks)
        self.perf.inc("mesh_dispatches")
        return out[:, :total] if pad else out
