"""Driver benchmark: north-star metric as ONE JSON line.

Metric (BASELINE.json): encode+decode MiB/s at k=8, m=4, 1 MiB stripes,
device-resident buffers.  It times the VERTICAL kernel
(``rs_kernels.gf_apply_stripes``); every codec entry the cluster serves
through calls the horizontal ``gf_apply`` instead, which this does not
time — ``chip_smoke.py`` runs both, and ROADMAP S1 replaces this metric
with a cell table.

Methodology: each kernel is timed as a jitted fori_loop chain of R
dependent applications ending in a scalar reduction (the 4-byte fetch
forces real completion); per-op time is the difference between an R-rep
and a lo-rep chain divided by R-lo, so the fixed dispatch cost cancels.
The chain XORs the output back into the carry, so no iteration can be
elided.

vs_baseline: ratio against the native SIMD CPU codec (cpp_rs,
gf8_simd.cc: GFNI/AVX-512 where the host supports it, AVX2 pshufb
otherwise — the same kernel families the reference's isa-l uses, so the
denominator is an honest AVX2-class number, not numpy).  Falls back to
the numpy codec only if the native build is unavailable, and says so in
``cpu_kind``.

One process, one chip.  The device metric comes from a TPU or not at
all: when JAX finds no TPU the run exits non-zero and prints no metric
line, and a section that raises fails the run.

The JSON line also reports pct_hbm_roofline: the combined number as a
percentage of what v5e HBM bandwidth (819 GB/s) allows for this op's
mandatory traffic (in + out bytes).
"""
from __future__ import annotations

import json
import os
import sys
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

from ceph_tpu.common.device_telemetry import jax_version
from ceph_tpu.common.tracer import default_tracer

HBM_BYTES_PER_S = 819e9          # TPU v5e HBM bandwidth (public spec)


_chain_cache: dict = {}


class PlatformMismatchError(RuntimeError):
    """The measured JAX platform is not the one the run requested — the
    r05 failure mode (a silent CPU fallback recorded as if it were a
    slower TPU number).  Raised BEFORE the suite runs so the artifact
    names the abort instead of carrying a different experiment's data."""


def requested_platform() -> str | None:
    """The platform this run was ASKED to measure on: the explicit
    ``BENCH_EXPECT_PLATFORM`` override, else ``JAX_PLATFORMS`` when it
    names exactly one platform (a comma list is jax's own documented
    fallback chain — the operator opted into degradation there)."""
    expect = os.environ.get("BENCH_EXPECT_PLATFORM", "").strip().lower()
    if expect:
        return expect
    env = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if env and "," not in env:
        return env
    return None


def preflight_platform(measured: str | None) -> None:
    """Abort the suite with a NAMED error when the measured platform is
    not the requested one (kills the silent-fallback mode at the source;
    tools/perf_gate.py still gates it after the fact)."""
    requested = requested_platform()
    if requested is not None and measured != requested:
        raise PlatformMismatchError(
            f"requested platform {requested!r} but measured "
            f"{measured or 'none'} — refusing to run the suite on the "
            f"wrong device (set BENCH_EXPECT_PLATFORM/JAX_PLATFORMS to "
            f"what you mean, or unset them to accept fallback)")

# Hardware attribution for the emitted line: without it the BENCH
# trajectory is unattributable — a regression could be a slower kernel or
# a different device and the artifact alone could not tell.  main() fills
# the device fields from jax.devices() before anything is measured.
_DEVICE_INFO: dict = {"platform": None, "device_kind": None,
                      "num_devices": 0, "jax_version": jax_version()}

# -- per-phase accounting -----------------------------------------------------
# Every phase lands in the bench JSON (`phases`: name -> seconds) AND on the
# process span tracer.
_PHASES: dict[str, float] = {}


@contextmanager
def phase(name):
    with default_tracer().span(f"bench.{name}"):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            _PHASES[name] = round(
                _PHASES.get(name, 0.0) + time.perf_counter() - t0, 3)


def chain_fn(apply_fn, mat, data, reps):
    """The cached jitted chain of `reps` applications (build only; the
    first execution compiles)."""
    import jax
    import jax.numpy as jnp

    # On TPU the kernel is an opaque pallas call, so a 2-row tap is enough
    # to chain iterations — XLA cannot slice an opaque call down to the
    # used rows, and the glue adds only ~2 rows of extra HBM traffic.  On
    # the XLA fallback path (plain dot_general) a narrow tap WOULD let the
    # compiler elide most of the matmul, so consume every output row there.
    on_tpu = jax.devices()[0].platform == "tpu"

    key = (id(apply_fn), reps, mat.shape, data.shape)
    run = _chain_cache.get(key)
    if run is None:
        @jax.jit
        def run(M, D):
            def body(i, carry):
                out = apply_fn(M, carry)                   # [R, N]
                dep_rows = min(2, out.shape[0]) if on_tpu else out.shape[0]
                head = jax.lax.dynamic_slice(
                    carry, (0, 0), (dep_rows, carry.shape[1]))
                tap = jax.lax.dynamic_slice(
                    out, (0, 0), (dep_rows, out.shape[1]))
                return jax.lax.dynamic_update_slice(
                    carry, jax.lax.bitwise_xor(head, tap), (0, 0))
            final = jax.lax.fori_loop(0, reps, body, D)
            return final.astype(jnp.int32).sum()
        _chain_cache[key] = run
    return run


def chain_timer(apply_fn, mat, data, reps, rounds=5):
    """Best-of-rounds wall time of a jitted chain of `reps` applications."""
    run = chain_fn(apply_fn, mat, data, reps)
    _ = int(run(mat, data))                                # compile+sync
    best = 1e9
    for _ in range(rounds):
        t0 = time.perf_counter()
        _ = int(run(mat, data))                            # 4-byte fetch
        best = min(best, time.perf_counter() - t0)
    return best


def per_op_seconds(apply_fn, mat, data, lo=4, hi=52):
    """Per-op seconds from the (hi-reps − lo-reps) chain difference.

    Host-clock jitter is comparable to small kernels; a wide rep spread
    plus best-of-rounds keeps the difference positive.  If jitter still
    swallows it, retry once, then fall back to the hi-chain mean
    (conservative: includes the fixed dispatch overhead, so it can only
    understate throughput).
    """
    for _ in range(2):
        t_lo = chain_timer(apply_fn, mat, data, lo, rounds=7)
        t_hi = chain_timer(apply_fn, mat, data, hi, rounds=7)
        if t_hi > t_lo * 1.05:
            return (t_hi - t_lo) / (hi - lo)
    return t_hi / hi


def measure_cpu(fn, iters=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def cpu_baseline(data, k, m, erasures):
    """(combined MiB/s, kind, encode MiB/s, decode MiB/s) for the host
    codec: native SIMD if the toolchain built, else the numpy path."""
    from ceph_tpu.ops import RSCodec

    stripe_bytes = data.shape[1] * k
    cdata = np.ascontiguousarray(data[:k])
    kind = "numpy"
    try:
        from ceph_tpu.native import NativeRegistry
        native = NativeRegistry().factory(
            "cpp_rs", {"k": str(k), "m": str(m), "technique": "cauchy"})
        enc_t = measure_cpu(lambda: native.encode(cdata), iters=20)
        parity = native.encode(cdata)
        avail = {i: cdata[i] for i in range(k) if i not in erasures}
        avail |= {k + j: parity[j] for j in range(m)
                  if k + j not in erasures}
        dec_t = measure_cpu(
            lambda: native.decode(avail, erasures, data.shape[1]), iters=20)
        kind = "simd"                          # only after timings succeed
    except Exception as e:                     # no native toolchain
        print(f"# native baseline unavailable ({e}); using numpy",
              file=sys.stderr)
        from ceph_tpu.gf import ref
        cpu = RSCodec(k, m, technique="cauchy", device="numpy")
        D, src = cpu.decode_matrix(erasures)
        enc_t = measure_cpu(lambda: cpu.encode(cdata))
        csurv = np.concatenate([cdata, cpu.encode(cdata)], axis=0)[src]
        dec_t = measure_cpu(lambda: ref.apply_matrix(D, csurv))
    enc = (stripe_bytes / 2**20) / enc_t
    dec = (stripe_bytes / 2**20) / dec_t
    return 2.0 / (1.0 / enc + 1.0 / dec), kind, enc, dec


def _pipeline_pass(sinfo, ec, batches, degraded, depth: int,
                   mesh_devices: int = 0, rounds: int = 3) -> dict:
    """One sync-vs-async measurement arm: encode then decode every batch
    through the codec pipeline at ``depth`` (0 = the synchronous
    per-batch path: every submit completes before returning — exactly
    the pre-pipeline coalescer dispatch).  Best-of-rounds MiB/s over the
    logical payload, encode/decode combined harmonically."""
    from ceph_tpu.backend import ecutil
    from ceph_tpu.ops.pipeline import CodecPipeline

    total = sum(len(b) for bb in batches for b in bb)
    pipe = CodecPipeline(depth=depth, name=f"bench.pipe.d{depth}",
                         mesh_devices=mesh_devices)
    try:
        # warm the jit shape caches out of the timed region
        ecutil.encode_many_pipelined(sinfo, ec, batches[0], pipe).result()
        for _i, f in ecutil.decode_many_pipelined(
                sinfo, ec, degraded[0], pipe,
                chunk_size=sinfo.chunk_size):
            f.result()
        enc_t = dec_t = 1e9
        for _ in range(rounds):
            t0 = time.perf_counter()
            futs = [ecutil.encode_many_pipelined(sinfo, ec, bb, pipe)
                    for bb in batches]
            pipe.flush()
            for f in futs:
                f.result()
            enc_t = min(enc_t, time.perf_counter() - t0)
            t0 = time.perf_counter()
            pend = [ecutil.decode_many_pipelined(
                sinfo, ec, bb, pipe, chunk_size=sinfo.chunk_size)
                for bb in degraded]
            pipe.flush()
            for groups in pend:
                for _i, f in groups:
                    f.result()
            dec_t = min(dec_t, time.perf_counter() - t0)
        mesh_hits = int(pipe.perf.get("mesh_dispatches"))
    finally:
        pipe.close()
    enc = total / 2**20 / enc_t
    dec = total / 2**20 / dec_t
    out = {"depth": depth,
           "encode_mibs": round(enc, 1), "decode_mibs": round(dec, 1),
           "mib_s": round(2.0 / (1.0 / enc + 1.0 / dec), 1)}
    if mesh_devices:
        out["mesh_devices"] = mesh_devices
        out["mesh_dispatches"] = mesh_hits
    return out


def pipeline_section(platform: str | None) -> dict:
    """Codec-pipeline comparison for the JSON artifact's `pipeline`
    block: synchronous per-batch dispatch (depth 0: pack | compute |
    fetch serial, the pre-pipeline serving path) vs async depth-4
    (batch N+1's host pack overlaps batch N's in-flight device compute),
    plus a mesh-sharded arm when >1 device is up."""
    import jax
    from ceph_tpu.backend.ecutil import StripeInfo
    from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
    k, m, chunk = 8, 4, 16384           # 128 KiB stripes
    n_batches, ops_per_batch = 12, 8    # 1 MiB coalesced batches
    ec = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", {"plugin": "jax_rs", "k": str(k), "m": str(m),
                       "technique": "reed_sol_van", "device": "jax"})
    sinfo = StripeInfo(k, chunk)
    rng = np.random.default_rng(2)
    with phase("pipeline"):
        batches = [[rng.integers(0, 256, sinfo.stripe_width,
                                 np.uint8).tobytes()
                    for _ in range(ops_per_batch)]
                   for _ in range(n_batches)]
        from ceph_tpu.backend import ecutil
        degraded = [[{c: v for c, v in chunks.items() if c != 0}
                     for chunks in ecutil.encode_many(sinfo, ec, bb)]
                    for bb in batches]
        sync = _pipeline_pass(sinfo, ec, batches, degraded, depth=0)
        asynch = _pipeline_pass(sinfo, ec, batches, degraded, depth=4)
        n_dev = len(jax.devices())
        mesh = None
        if n_dev > 1:
            mesh = _pipeline_pass(sinfo, ec, batches, degraded,
                                  depth=4, mesh_devices=n_dev)
    res = {
        "device": platform,
        "host_cpus": os.cpu_count(),
        "sync": sync,
        "async": asynch,
        "speedup": round(asynch["mib_s"] / max(sync["mib_s"], 1e-9),
                         2),
    }
    if mesh is not None:
        res["mesh"] = mesh
    print(f"# pipeline: async depth-4 {asynch['mib_s']:.1f} MiB/s vs "
          f"sync {sync['mib_s']:.1f} MiB/s -> {res['speedup']}x on "
          f"{res['device']} ({res['host_cpus']} cpus)",
          file=sys.stderr)
    return res


def _recovery_repair_pass(device: str, batched: bool, n_objects: int,
                          obj_bytes: int, chain: bool = False) -> dict:
    """One degraded-cluster repair: write, kill a shard, overwrite
    everything while it is down, revive, and time the drain to clean.
    ``batched`` routes repair through the recovery scheduler (waves
    fused into decode_shards_many dispatches); otherwise the per-object
    inline path runs.  ``chain`` (batched only) lets the scheduler plan
    partial-sum chains over the survivors instead of centralizing k
    chunks at the primary.  Returns MiB/s over the chunk bytes pushed
    plus the wire decomposition (total / coordinator-ingress /
    newcomer-ingress per repaired byte)."""
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.common import Context
    # fresh Context: the conf knobs below must not leak into the rest
    # of the bench through the process-global default context
    c = MiniCluster(n_osds=8, osds_per_host=2, chunk_size=4096,
                    cct=Context())
    try:
        # chains default ON cluster-wide, so the CENTRALIZED arms must
        # pin them off explicitly to measure what they claim to measure
        c.cct.conf.set("osd_recovery_chain_enable", bool(chain))
        if batched:
            c.cct.conf.set("osd_recovery_max_active", 16)
            c.enable_recovery_scheduler()
        pid = c.create_ec_pool(
            "r", {"k": "4", "m": "2", "device": device,
                  "technique": "reed_sol_van"}, pg_num=1)
        g = c.pools[pid]["pgs"][0]
        victim = g.acting[1]
        rng = np.random.default_rng(0)
        objs = {f"o{i}": rng.integers(0, 256, obj_bytes,
                                      np.uint8).tobytes()
                for i in range(n_objects)}
        for oid, d in objs.items():
            c.put(pid, oid, d)
        # two kill-overwrite-revive cycles: the first warms the jit
        # shape caches (both paths pay a cold compile on their decode
        # shapes), the second is the steady-state measurement — same
        # warm-vs-cold discipline as the chain timer above
        dt = pushed = wire = 0
        tdelta: dict = {}
        chain_objects = chain_fallbacks = 0
        for payload in (b"\x01", b"\x02"):
            g.bus.mark_down(victim)
            for oid in objs:              # the writes the victim misses
                c.put(pid, oid, payload + objs[oid][1:])
            before = g.backend.perf.get("recovery_bytes")
            co_before = g.backend.perf.get("chain_objects")
            cf_before = g.backend.perf.get("chain_fallbacks")
            wire_before = c.wire.class_bytes()["recovery"]
            types_before = {t: v["tx_bytes"]
                            for t, v in c.wire.per_type().items()}
            t0 = time.perf_counter()
            g.bus.mark_up(victim)
            c.deliver_all()
            dt = time.perf_counter() - t0
            pushed = g.backend.perf.get("recovery_bytes") - before
            chain_objects = g.backend.perf.get("chain_objects") - co_before
            chain_fallbacks = (g.backend.perf.get("chain_fallbacks")
                               - cf_before)
            wire = c.wire.class_bytes()["recovery"] - wire_before
            tdelta = {t: v["tx_bytes"] - types_before.get(t, 0)
                      for t, v in c.wire.per_type().items()}
            assert not g.backend.stale, "repair did not drain"
        report = c.scrub_pool(pid, repair=False)
        assert report == {}, f"repair left scrub findings: {report}"
        # wire decomposition from per-type deltas: the message types
        # below flow to exactly one role in a repair (read replies +
        # chain acks/aborts land on the coordinating primary; pushes +
        # chain applies land on the repair target)
        coord_in = sum(tdelta.get(t, 0) for t in
                       ("ECSubReadReply", "ECPartialSumApplied",
                        "ECPartialSumAbort"))
        newcomer_in = sum(tdelta.get(t, 0) for t in
                          ("PushOp", "ECPartialSumApply"))
        return {"mib_s": round(pushed / 2**20 / dt, 2),
                "objects": n_objects, "pushed_bytes": pushed,
                "elapsed_s": round(dt, 3),
                # bytes-on-wire per byte repaired (ROADMAP item 3's
                # success metric): recovery-class wire traffic of the
                # measured cycle over the chunk bytes pushed — ~k for
                # centralized repair.  The k-transfer information floor
                # means NO repair scheme gets total wire below ~k-1;
                # what chains eliminate is the COORDINATOR ingress
                # (~k+m-1 chunks per object centralized, ~0 chained)
                # while the newcomer keeps receiving ~1 byte per byte
                # repaired
                "wire_bytes": int(wire),
                "wire_per_byte": round(wire / max(pushed, 1), 3),
                "coordinator_ingress_per_byte": round(
                    coord_in / max(pushed, 1), 3),
                "newcomer_ingress_per_byte": round(
                    newcomer_in / max(pushed, 1), 3),
                "chain_objects": int(chain_objects),
                "chain_fallbacks": int(chain_fallbacks)}
    finally:
        c.shutdown()


def _recovery_regen_pass(device: str, mode: str, k: int, m: int, d: int,
                         chunk: int, n_objects: int, stripes: int,
                         regen: bool = True) -> dict:
    """One degraded repair on a REGENERATING pool (pm_regen MSR/MBR):
    write, kill a shard, overwrite while down, revive, time the drain.
    ``regen=False`` pins the option off so the same pool repairs through
    the centralized verified wave — the comparison arm.  Repaired bytes
    are counted in STORED units (MBR chunks are expanded alpha*k/B on
    disk); wire is the recovery-class delta over the measured cycle."""
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.common import Context
    c = MiniCluster(n_osds=9, osds_per_host=3, chunk_size=chunk,
                    cct=Context())
    try:
        c.cct.conf.set("osd_recovery_regen_enable", bool(regen))
        c.cct.conf.set("osd_recovery_max_active", 16)
        c.enable_recovery_scheduler()
        pid = c.create_ec_pool(
            "rg", {"plugin": "pm_regen", "k": str(k), "m": str(m),
                   "d": str(d), "mode": mode, "device": device},
            pg_num=1)
        g = c.pools[pid]["pgs"][0]
        victim = g.acting[1]
        obj_bytes = stripes * chunk * k
        rng = np.random.default_rng(0)
        objs = {f"o{i}": rng.integers(0, 256, obj_bytes,
                                      np.uint8).tobytes()
                for i in range(n_objects)}
        for oid, data in objs.items():
            c.put(pid, oid, data)
        stored = g.backend.ec_impl.get_stored_chunk_size(chunk)
        repaired = stripes * stored * n_objects
        dt = wire = helper_tx = 0
        ro = rf = 0
        # warm cycle then measured cycle (same discipline as the chain
        # pass: both arms pay their cold jit/compile in cycle one)
        for payload in (b"\x01", b"\x02"):
            g.bus.mark_down(victim)
            for oid in objs:
                c.put(pid, oid, payload + objs[oid][1:])
            ro_before = g.backend.perf.get("regen_objects")
            rf_before = g.backend.perf.get("regen_fallbacks")
            wire_before = c.wire.class_bytes()["recovery"]
            helper_before = c.wire.per_type().get(
                "ECRegenHelper", {}).get("tx_bytes", 0)
            t0 = time.perf_counter()
            g.bus.mark_up(victim)
            c.deliver_all()
            dt = time.perf_counter() - t0
            ro = g.backend.perf.get("regen_objects") - ro_before
            rf = g.backend.perf.get("regen_fallbacks") - rf_before
            wire = c.wire.class_bytes()["recovery"] - wire_before
            helper_tx = c.wire.per_type().get(
                "ECRegenHelper", {}).get("tx_bytes", 0) - helper_before
            assert not g.backend.stale, "regen repair did not drain"
        report = c.scrub_pool(pid, repair=False)
        assert report == {}, f"repair left scrub findings: {report}"
        return {"mib_s": round(repaired / 2**20 / dt, 2),
                "objects": n_objects, "repaired_bytes": repaired,
                "stored_chunk": stored, "elapsed_s": round(dt, 3),
                "wire_bytes": int(wire),
                # total recovery wire per STORED byte repaired — the
                # ROADMAP item-3 metric on the regenerating pool.  The
                # beta-stream floor is 1.0 B/B at the MBR point and
                # d/alpha at MSR; control legs (plan + acks) amortize
                # over payload
                "wire_per_byte": round(wire / max(repaired, 1), 3),
                # the helper beta-streams alone: what the newcomer
                # ingests beyond its own combine matrix
                "helper_stream_per_byte": round(
                    helper_tx / max(repaired, 1), 3),
                "regen_objects": int(ro),
                "regen_fallbacks": int(rf)}
    finally:
        c.shutdown()


def recovery_section(platform: str | None) -> dict:
    """Degraded-cluster repair throughput for the JSON artifact's
    `recovery` block: kill-one-shard repair MiB/s, batch-fused
    (scheduler waves through decode_shards_many) vs per-object, on the
    SAME device."""
    device = "jax"
    with phase("recovery"):
        per_object = _recovery_repair_pass(device, batched=False,
                                           n_objects=48,
                                           obj_bytes=64 * 1024)
        batched = _recovery_repair_pass(device, batched=True,
                                        n_objects=48,
                                        obj_bytes=64 * 1024)
        chained = _recovery_repair_pass(device, batched=True,
                                        n_objects=48,
                                        obj_bytes=64 * 1024,
                                        chain=True)
        # regenerating-code repair (pm_regen): MBR at the ~1 B/B
        # repair-bandwidth point, MSR at d/alpha, vs the same pool
        # repaired through the centralized wave
        regen_mbr = _recovery_regen_pass(device, "mbr", 3, 2, 4,
                                         chunk=1536, n_objects=24,
                                         stripes=8)
        regen_mbr_cent = _recovery_regen_pass(device, "mbr", 3, 2,
                                              4, chunk=1536,
                                              n_objects=24,
                                              stripes=8,
                                              regen=False)
        regen_msr = _recovery_regen_pass(device, "msr", 3, 2, 4,
                                         chunk=4096, n_objects=24,
                                         stripes=8)
    res = {
        "device": platform,
        "codec": device,
        "per_object": per_object,
        "batched": batched,
        "speedup": round(batched["mib_s"] /
                         max(per_object["mib_s"], 1e-9), 2),
        # the wire sub-block tools/perf_gate.py gates on: repair
        # efficiency regresses when this number rises
        "wire": {"per_byte_repaired": batched["wire_per_byte"],
                 "per_object_arm": per_object["wire_per_byte"]},
        # chained streaming repair vs the centralized wave on the
        # SAME cluster shape (k=4/m=2, one victim).  Total wire
        # cannot beat the k-transfer information floor; the honest
        # wins the gate holds are (a) total wire well under the
        # centralized arm, (b) coordinator ingress ~0, (c) newcomer
        # ingress ~1x bytes repaired (<= 1.5 gated absolutely in
        # tools/perf_gate.py)
        "chain": {
            "mib_s": chained["mib_s"],
            "pushed_bytes": chained["pushed_bytes"],
            "wire_per_byte": chained["wire_per_byte"],
            "centralized_wire_per_byte": batched["wire_per_byte"],
            "wire_reduction": round(
                batched["wire_per_byte"] /
                max(chained["wire_per_byte"], 1e-9), 2),
            "speedup_vs_centralized": round(
                chained["mib_s"] / max(batched["mib_s"], 1e-9), 2),
            "coordinator_ingress_per_byte":
                chained["coordinator_ingress_per_byte"],
            "centralized_coordinator_ingress_per_byte":
                batched["coordinator_ingress_per_byte"],
            "newcomer_ingress_per_byte":
                chained["newcomer_ingress_per_byte"],
            "chain_objects": chained["chain_objects"],
            "chain_fallbacks": chained["chain_fallbacks"],
        },
        # regenerating repair vs centralized on the SAME pm_regen
        # pool.  MBR's total wire is gated absolutely at 1.5 B/B
        # (tools/perf_gate.py) — below the k-transfer floor any
        # decode-based repair pays; MSR sits at d/alpha and is
        # gated under the 4.0 regenerating-pool ceiling
        "regen": {
            "mbr": {
                **regen_mbr,
                "centralized_wire_per_byte":
                    regen_mbr_cent["wire_per_byte"],
                "wire_reduction": round(
                    regen_mbr_cent["wire_per_byte"] /
                    max(regen_mbr["wire_per_byte"], 1e-9), 2),
            },
            "msr": regen_msr,
        },
    }
    print(f"# recovery: batched {batched['mib_s']:.1f} MiB/s vs "
          f"per-object {per_object['mib_s']:.1f} MiB/s -> "
          f"{res['speedup']}x on {res['device']}; chain wire "
          f"{chained['wire_per_byte']:.2f}/B vs centralized "
          f"{batched['wire_per_byte']:.2f}/B, newcomer ingress "
          f"{chained['newcomer_ingress_per_byte']:.2f}/B",
          file=sys.stderr)
    print(f"# recovery.regen: mbr {regen_mbr['wire_per_byte']:.2f}/B"
          f" (centralized {regen_mbr_cent['wire_per_byte']:.2f}/B, "
          f"{res['regen']['mbr']['wire_reduction']}x less wire) at "
          f"{regen_mbr['mib_s']:.1f} MiB/s; msr "
          f"{regen_msr['wire_per_byte']:.2f}/B at "
          f"{regen_msr['mib_s']:.1f} MiB/s",
          file=sys.stderr)
    return res


def _serving_wire_pass(device: str, n_ops: int = 64) -> dict:
    """Bytes-on-wire per client op over a short cluster pass (put+get
    through the PG fan-out).  compare_batched_unbatched drives the
    ServingEngine directly — no bus — so the wire cost of a served op
    is measured here, on the path that actually frames messages."""
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.common import Context
    c = MiniCluster(n_osds=6, chunk_size=1024, cct=Context())
    try:
        pid = c.create_ec_pool(
            "sw", {"k": "4", "m": "2", "device": device,
                   "technique": "reed_sol_van"}, pg_num=4)
        rng = np.random.default_rng(1)
        payload = rng.integers(0, 256, 4096, np.uint8).tobytes()
        before = c.wire.class_bytes()
        for i in range(n_ops // 2):
            c.put(pid, f"w{i}", payload)
        for i in range(n_ops // 2):
            c.get(pid, f"w{i}", len(payload))
        after = c.wire.class_bytes()
        moved = sum(after[k] - before[k] for k in ("client", "serving"))
        return {"per_op": round(moved / n_ops, 1), "ops": n_ops,
                "bytes": int(moved), "op_bytes": len(payload)}
    finally:
        c.shutdown()


def _serving_async_pass() -> dict:
    """The async-messenger block (`serving.async`): 10k logical
    closed-loop clients multiplexed over 8 TCP connections to an async
    ClusterServer (tools/rados_bench.run_mux_bench) — goodput + p99 at
    clean capacity, and goodput + shed-rate with the dispatch queue
    pinned tiny (the overload arm: the shed ladder must refuse work by
    class while completed work keeps flowing)."""
    from tools.rados_bench import run_mux_overload_pair
    return run_mux_overload_pair(n_clients=10000, ops_per_client=2,
                                 n_conns=8)


def serving_section(platform: str | None) -> dict:
    """Closed-loop serving comparison (coalesced vs op-at-a-time on the
    SAME device) for the JSON artifact's `serving` block: throughput +
    p50/p99 at fixed concurrency through ceph_tpu.exec.ServingEngine."""
    from ceph_tpu.backend import StripeInfo
    from ceph_tpu.exec.workload import compare_batched_unbatched
    from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
    device = "jax"
    ec = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", {"plugin": "jax_rs", "k": "4", "m": "2",
                       "technique": "reed_sol_van", "device": device})
    with phase("serving"):
        res = compare_batched_unbatched(
            ec, StripeInfo(4, 1024), n_ops=256, concurrency=64,
            op_bytes=4096, warmup_ops=64, timeout=240.0)
    res["device"] = platform
    res["wire"] = _serving_wire_pass(device)
    print(f"# serving: batched {res['batched']['ops_s']:.0f} ops/s "
          f"(p99 {res['batched']['p99_ms']:.2f} ms) vs unbatched "
          f"{res['unbatched']['ops_s']:.0f} ops/s (p99 "
          f"{res['unbatched']['p99_ms']:.2f} ms) -> "
          f"{res['speedup']}x on {res['device']}", file=sys.stderr)
    # async-messenger concurrency
    with phase("serving.async"):
        res["async"] = _serving_async_pass()
    a = res["async"]
    print(f"# serving.async: {a['clients']} clients "
          f"{a['ops_s']:.0f} ops/s p99 {a['p99_ms']:.1f} ms "
          f"({a['threads']} threads); overload shed-rate "
          f"{a['overload']['shed_rate']:.0%} with "
          f"{a['overload']['ops_s']:.0f} ops/s goodput",
          file=sys.stderr)
    # zero-copy data-path arms
    from tools.rados_bench import run_zero_copy_pair
    with phase("serving.zero_copy"):
        res["zero_copy"] = run_zero_copy_pair()
    z = res["zero_copy"]
    print(f"# serving.zero_copy: fused "
          f"{z['copies_per_byte']:.2f} copies/B at "
          f"{z['fused']['ops_s']:.0f} ops/s (p99 "
          f"{z['fused']['p99_ms']:.1f} ms) vs legacy "
          f"{z['legacy_copies_per_byte']:.2f} copies/B at "
          f"{z['legacy']['ops_s']:.0f} ops/s — "
          f"{z['goodput_ratio']}x goodput on "
          f"{z['payload_bytes']}B payloads", file=sys.stderr)
    return res


def observability_section(platform: str | None) -> dict:
    """The instrumentation-tax block (`observability`): the serving.async
    mux workload with full instruments vs the ``instruments_enabled``
    kill-switch — reporting both arms' goodput/p99 and the overhead
    percentage the perf gate caps absolutely (ISSUE 18).  The A/B runs
    as paired on/off CPU-time segments against ONE warmed server
    (tools.rados_bench.run_mux_overhead_bench), overhead = median of the
    per-round paired deltas: wall-clock goodput on a shared host swings
    2x run-to-run from scheduler noise and per-process setup, and that
    noise must not masquerade as instrument tax."""
    from ceph_tpu.common.tracer import default_tracer
    from tools.rados_bench import run_mux_overhead_bench
    with phase("observability"):
        ab = run_mux_overhead_bench()
    on = ab["instruments_on"]
    off = ab["instruments_off"]
    res = {
        "device": platform,
        "sample_rate": default_tracer().sample_rate,
        "overhead_pct": ab["overhead_pct"],
        "rounds": ab["rounds"],
        "deltas_pct": ab["deltas_pct"],
        "instruments_on": dict(on),
        "instruments_off": dict(off),
        "p99_delta_ms": round(on["p99_ms"] - off["p99_ms"], 3),
    }
    print(f"# observability: instruments on {on['cpu_us_per_op']:.1f} "
          f"us/op CPU ({on['ops_s']:.0f} ops/s) vs off "
          f"{off['cpu_us_per_op']:.1f} us/op ({off['ops_s']:.0f} ops/s)"
          f" -> {res['overhead_pct']:.1f}% overhead at sample_rate "
          f"{res['sample_rate']}", file=sys.stderr)
    return res


def _resilience_cluster_pass(device: str, faulted: bool,
                             n_objects: int = 24) -> dict:
    """One put+verify-get pass over a MiniCluster — clean, or under a
    FIXED seeded fault schedule (bus reorder+dup, slow store reads) —
    returning latency percentiles and acked-goodput MiB/s."""
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.common import Context
    c = MiniCluster(n_osds=6, chunk_size=1024, cct=Context())
    try:
        pid = c.create_ec_pool(
            "rz", {"k": "4", "m": "2", "device": device,
                   "technique": "reed_sol_van"}, pg_num=4)
        rng = np.random.default_rng(5)
        payload = rng.integers(0, 256, 8192, np.uint8).tobytes()
        if faulted:
            from ceph_tpu.failure import (FaultConfig, FaultPlan,
                                          StoreFaults)
            c.inject_faults(FaultPlan(
                seed=23, bus=FaultConfig(reorder=True, dup_prob=0.2),
                store=StoreFaults(slow_read_prob=0.10,
                                  slow_read_ms=0.5)))
        for i in range(2):            # codec warmup outside the window
            c.put(pid, f"warm{i}", payload)
            c.get(pid, f"warm{i}", len(payload))
        lat: list[float] = []
        t_all = time.perf_counter()
        for i in range(n_objects):
            t0 = time.perf_counter()
            c.put(pid, f"r{i}", payload)
            lat.append(time.perf_counter() - t0)
        for i in range(n_objects):
            t0 = time.perf_counter()
            got = c.get(pid, f"r{i}", len(payload))
            lat.append(time.perf_counter() - t0)
            assert got == payload, f"read diverged under faults: r{i}"
        wall = time.perf_counter() - t_all
        moved = 2 * n_objects * len(payload)
        lat_ms = sorted(x * 1e3 for x in lat)
        return {"ops": 2 * n_objects,
                "goodput_mib_s": round(moved / 2**20 / wall, 2),
                "p50_ms": round(lat_ms[len(lat_ms) // 2], 3),
                "p99_ms": round(lat_ms[int(len(lat_ms) * 0.99)], 3)}
    finally:
        c.shutdown()


def _breaker_fallback_pass(n_batches: int = 12) -> dict:
    """Encode throughput with the device path FORCED open (dispatch
    failures at probability 1): every batch serves through the breaker's
    sync host fallback — the floor the cluster keeps serving at when the
    device dies."""
    from ceph_tpu.backend import StripeInfo, ecutil
    from ceph_tpu.common import Context
    from ceph_tpu.failure import DeviceFaults, FaultInjector, FaultPlan
    from ceph_tpu.ops.pipeline import CodecPipeline
    from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
    ec = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", {"plugin": "jax_rs", "k": "4", "m": "2",
                       "technique": "reed_sol_van", "device": "jax"})
    sinfo = StripeInfo(4, 1024)
    cct = Context(overrides={"pipeline_breaker_threshold": 2,
                             "pipeline_breaker_cooldown": 60.0})
    pl = CodecPipeline(depth=2, name="bench.resilience", cct=cct)
    try:
        pl.inject_faults(FaultInjector(FaultPlan(
            seed=31, device=DeviceFaults(dispatch_fail_prob=1.0))))
        rng = np.random.default_rng(7)
        bufs = [rng.integers(0, 256, 64 * 4096, np.uint8).tobytes()
                for _ in range(n_batches)]
        t0 = time.perf_counter()
        futs = [ecutil.encode_many_pipelined(sinfo, ec, [b], pl)
                for b in bufs]
        pl.flush()
        for f in futs:
            f.result(120)
        wall = time.perf_counter() - t0
        moved = sum(len(b) for b in bufs)
        return {"fallback_mib_s": round(moved / 2**20 / wall, 2),
                "batches": n_batches,
                "opens": pl.breaker.opens if pl.breaker else 0,
                "fallbacks": pl.perf.get("host_fallbacks")}
    finally:
        pl.close()


def resilience_section(platform: str | None) -> dict:
    """The `resilience` block (ISSUE 9): p99 + goodput with a fixed
    seeded fault schedule vs a clean run (the self-healing tax), and
    breaker-fallback throughput (the floor when the device path dies).
    Gated by tools/perf_gate.py: a goodput-ratio or fallback-throughput
    drop past threshold fails the round."""
    device = "jax"
    with phase("resilience"):
        clean = _resilience_cluster_pass(device, faulted=False)
        faulted = _resilience_cluster_pass(device, faulted=True)
        res = {
            "device": platform,
            "clean": clean, "faulted": faulted,
            "goodput_ratio": round(
                faulted["goodput_mib_s"]
                / max(clean["goodput_mib_s"], 1e-9), 3),
        }
        res["breaker"] = _breaker_fallback_pass()
    brk = res.get("breaker", {})
    print(f"# resilience: goodput x{res['goodput_ratio']} under "
          f"faults (clean {clean['goodput_mib_s']} -> faulted "
          f"{faulted['goodput_mib_s']} MiB/s, p99 "
          f"{clean['p99_ms']} -> {faulted['p99_ms']} ms)"
          + (f"; breaker fallback {brk['fallback_mib_s']} MiB/s"
             if brk else ""), file=sys.stderr)
    return res


def slo_section(platform: str | None) -> dict:
    """The `slo` block (ISSUE 10): a short loaded MiniCluster pass whose
    completed traces fold through the critical-path ledger into
    per-class p99 + phase attribution, judged against a generous bench
    objective so the artifact carries budget state too.
    tools/perf_gate.py gates `slo.client_p99_ms` (regression = p99 rise)
    and `slo.budget_remaining` (regression = budget burned);
    tools/slo_report.py reproduces the attribution table from the block
    alone."""
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.common import Context
    device = "jax"
    cct = Context(overrides={
        # a generous objective: steady-state ops pass it easily, so
        # budget_remaining ~1.0 and any real latency cliff shows as
        # a burned budget in the gate
        "slo_client_p99_ms": 250.0,
        "slo_client_target": 0.9,
        "slo_min_ops": 4,
    })
    with phase("slo"):
        # the ledger folds the PROCESS tracer ring: drop the traces
        # the earlier sections left there (resilience deliberately
        # ran faulted traffic) so the gated p99/budget numbers
        # measure THIS pass, not the chaos before it
        from ceph_tpu.common.tracer import default_tracer
        default_tracer().reset()
        c = MiniCluster(n_osds=6, chunk_size=1024, cct=cct)
        try:
            pid = c.create_ec_pool(
                "slo", {"k": "4", "m": "2", "device": device,
                        "technique": "reed_sol_van"}, pg_num=4)
            rng = np.random.default_rng(11)
            payload = rng.integers(0, 256, 8192, np.uint8).tobytes()
            for i in range(24):
                c.put(pid, f"s{i}", payload)
            for i in range(24):
                c.get(pid, f"s{i}", len(payload))
            c.status()                      # fold + tick
            c.critpath.refresh()
            res = c.slo.bench_block(
                platform)
        finally:
            c.shutdown()
    cl = res.get("client") or {}
    if cl:
        from ceph_tpu.common.critpath import format_phase_mix
        print(f"# slo: client p99 {cl['p99_ms']:.2f} ms over "
              f"{cl['ops']} ops ({format_phase_mix(cl['phases'])}); "
              f"budget {100 * cl.get('budget_remaining', 0):.0f}% "
              f"left", file=sys.stderr)
    return res


def tiering_section(platform: str | None) -> dict:
    """The `tiering` block (ROADMAP 7): a flash crowd — 90% of arrivals
    collapsing onto 0.1% of the keyspace — of mixed reads/writes from
    10k mux clients, served cold (straight off the EC base pool) and
    then warm (through a writeback cache tier, after one warmup pass of
    the identical stream).  tools/perf_gate.py gates the warm hit rate
    (>= 0.8), warm-over-cold p99 (<= 1.0) and warm-over-cold
    device-time-per-op: the tier must actually absorb the crowd, not
    just sit in the path."""
    from tools.rados_bench import run_tier_mux_bench
    device = "jax"
    with phase("tiering"):
        # the run resets the process tracer ring (its device
        # seconds are per-segment critpath deltas) — safe here:
        # slo_section already folded and captured its own block
        res = run_tier_mux_bench(
            n_clients=int(os.environ.get("BENCH_TIER_CLIENTS",
                                         10000)),
            ops_per_client=1, n_objects=1000, object_bytes=2048,
            device=device, timeout_s=240.0)
    # the gate compares like-for-like devices across artifacts:
    # carry the codec arg separately and mark the block with the
    # platform vocabulary every other block uses
    res["codec_device"] = res.pop("device")
    res["device"] = platform
    return res


def efficiency_section(platform: str | None) -> dict:
    """The roofline ledger the sections above populated (every
    traced_jit dispatch recorded its measured seconds next to its
    XLA-modeled FLOPs/bytes), rendered as the JSON artifact's
    `efficiency` block: aggregate %-of-peak + the per-executable table
    tools/roofline_report.py renders.  tools/perf_gate.py gates
    `efficiency.pct_of_peak` regressions against the BENCH history."""
    from ceph_tpu.common import roofline
    block = roofline.bench_block(platform)
    if "error" not in block:
        print(f"# efficiency: {block['pct_of_peak']:.2f}% of "
              f"{block['peaks']['source']} peak "
              f"({block['bound']}-bound aggregate, "
              f"{len(block['executables'])} executables)",
              file=sys.stderr)
    return block


def lint_section() -> dict:
    """ceph-lint over the tree with the committed baseline applied
    (ISSUE 15): carried in the artifact so the perf-gate history tracks
    the finding trajectory — ``lint.new`` must stay 0, and a growing
    ``lint.baselined`` count shows debt accumulating even while the
    gate is green."""
    from tools.ceph_lint import lint_summary
    block = lint_summary(Path(__file__).resolve().parent
                         / ".ceph_lint_baseline.json")
    print(f"# lint: {block['new']} new, {block['baselined']} "
          f"baselined, {block['rules_run']} rules",
          file=sys.stderr)
    return block


def emit(value, vs_baseline, extra):
    """Print the one driver JSON line."""
    line = {
        "metric": "rs_k8m4_1MiB_encode_decode_device_resident",
        "value": round(value, 1),
        "unit": "MiB/s",
        "vs_baseline": round(vs_baseline, 3),
        # hardware attribution: platform + device kind/count as
        # jax.devices() reports them, jax version from package metadata
        "device_info": dict(_DEVICE_INFO),
    }
    line.update(extra)
    line["phases"] = dict(_PHASES)
    # perf-regression gate (tools/perf_gate.py): the artifact carries
    # its own verdict vs whatever BENCH_r history sits next to this script
    gate = _run_perf_gate(line)
    if gate is not None:
        line["gate"] = gate
        print(f"# {gate['verdict']}", file=sys.stderr)
    print(json.dumps(line), flush=True)


def _run_perf_gate(line: dict) -> dict | None:
    """Load tools/perf_gate.py (stdlib-only, not a package) and evaluate
    this line against the BENCH_r history next to this script."""
    import importlib.util
    repo_dir = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(repo_dir, "tools", "perf_gate.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("ceph_tpu_perf_gate",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.gate_for_bench(line, repo_dir)


def measure_device(data, k, m, erasures, batch):
    """The TPU measurement proper: (combined MiB/s, extra-keys dict)."""
    import jax
    import jax.numpy as jnp
    from ceph_tpu.ops import RSCodec, rs_kernels

    stripe_bytes = data.shape[1] * k
    codec = RSCodec(k, m, technique="cauchy", device="jax")
    with phase("table_upload"):
        dev = jax.device_put(jnp.asarray(data))
        pmat = jax.device_put(jnp.asarray(codec.parity_mat))
        D, _src = codec.decode_matrix(erasures)
        dmat = jax.device_put(jnp.asarray(D))
        jax.block_until_ready(dev)

    def apply_auto(M, Dd):
        return rs_kernels.gf_apply_stripes(M, Dd, batch)

    # the chains per_op_seconds will time (lo=4, hi=52 reps over the
    # encode and decode matrices): compile them all first, then warm them
    # once more, so the measure phase is pure steady-state dispatch
    with phase("compile"):
        for mt in (pmat, dmat):
            for reps in (4, 52):
                _ = int(chain_fn(apply_auto, mt, dev, reps)(mt, dev))
    with phase("warmup"):
        for mt in (pmat, dmat):
            _ = int(chain_fn(apply_auto, mt, dev, 4)(mt, dev))

    with phase("measure"):
        enc_t = per_op_seconds(apply_auto, pmat, dev)       # [B*k]->[B*m]
        enc_mibs = batch * (stripe_bytes / 2**20) / enc_t
        # decode: 2 erasures (1 data + 1 parity) — the same apply
        # primitive over the decode matrix; the chain keeps the
        # [B*k, N] carry so per-op traffic matches a real reconstruct
        # over k survivors
        dec_t = per_op_seconds(apply_auto, dmat, dev)
        dec_mibs = batch * (stripe_bytes / 2**20) / dec_t

    combined = 2.0 / (1.0 / enc_mibs + 1.0 / dec_mibs)

    # HBM roofline for the measured ops: mandatory traffic per op is the
    # uint8 input block plus the uint8 output block (the fused kernel's
    # whole point is that bit-plane inflation never touches HBM).  Convert
    # the roofline to "stripe-payload MiB/s" so it is directly comparable
    # to enc/dec_mibs, then take the combined-metric ratio.
    n = data.shape[1]
    payload = batch * stripe_bytes
    roof_enc = HBM_BYTES_PER_S * payload / (batch * (k + m) * n) / 2**20
    r_dec = int(D.shape[0])
    roof_dec = HBM_BYTES_PER_S * payload / (batch * (k + r_dec) * n) / 2**20
    roof_combined = 2.0 / (1.0 / roof_enc + 1.0 / roof_dec)

    return combined, {
        "device": "tpu",
        "encode_mibs": round(enc_mibs, 1),
        "decode_mibs": round(dec_mibs, 1),
        "pct_hbm_roofline": round(100.0 * combined / roof_combined, 1),
    }


def main() -> int:
    from ceph_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    _DEVICE_INFO.update(platform=platform,
                        device_kind=devices[0].device_kind,
                        num_devices=len(devices))
    # the measured platform must BE the requested one before any suite
    # runs, and the device metric comes from a TPU or not at all: no
    # metric line is printed for any other platform
    preflight_platform(platform)
    if platform != "tpu":
        print(f"# no tpu (platform={platform}, "
              f"device_kind={devices[0].device_kind}): the device "
              f"metric is not measured anywhere else", file=sys.stderr)
        return 1

    k, m = 8, 4
    stripe_bytes = 1024 * 1024
    n = stripe_bytes // k                      # 128 KiB chunks
    batch = 64                                 # stripes per dispatch
    rng = np.random.default_rng(0)
    # device-native VERTICAL batch layout: stripe s = rows [s*k, (s+1)*k)
    # (tall blocks feed full MXU tiles; see rs_kernels.gf_apply_stripes)
    data = rng.integers(0, 256, size=(batch * k, n), dtype=np.uint8)
    erasures = [0, 9]

    with phase("cpu_baseline"):
        cpu_combined, cpu_kind, cpu_enc, cpu_dec = cpu_baseline(
            data, k, m, erasures)
    print(f"# cpu-{cpu_kind} encode {cpu_enc:.0f} decode {cpu_dec:.0f} "
          f"MiB/s", file=sys.stderr)

    blocks = {
        # static-analysis trajectory: pure AST work, no device needed
        "lint": lint_section(),
        # serving comparison (coalesced vs op-at-a-time)
        "serving": serving_section(platform),
        # instrumentation tax (instruments on vs off over the same mux
        # workload) right after the serving block it compares against
        "observability": observability_section(platform),
        # repair-throughput comparison (batched waves vs per-object)
        "recovery": recovery_section(platform),
        # codec-pipeline comparison (sync per-batch vs async depth-4,
        # mesh when >1 device)
        "pipeline": pipeline_section(platform),
        # goodput under a fixed fault schedule + breaker-fallback floor
        "resilience": resilience_section(platform),
        # critical-path attribution + SLO budget over a loaded cluster
        "slo": slo_section(platform),
        # hot-tier flash crowd, cold vs warm, at mux-client scale (after
        # slo: the run resets the tracer ring slo folds from)
        "tiering": tiering_section(platform),
    }
    # the roofline efficiency block reads the ledger the sections above
    # populated
    blocks["efficiency"] = efficiency_section(platform)
    combined, extra = measure_device(data, k, m, erasures, batch)
    print(f"# encode {extra['encode_mibs']:.0f} MiB/s, decode "
          f"{extra['decode_mibs']:.0f} MiB/s "
          f"({extra['pct_hbm_roofline']:.0f}% of HBM roofline)",
          file=sys.stderr)
    emit(combined, combined / cpu_combined,
         {**extra, "cpu_kind": cpu_kind, **blocks})
    return 0


if __name__ == "__main__":
    sys.exit(main())
