"""Fill BASELINE.md's run matrix: the five measured configs.

The reference publishes no absolute EC numbers (BASELINE.md), so every
number here is measured on the host/device this script runs on, with the
methodology of the reference harnesses it mirrors:

  1. CPU baseline          ceph_erasure_code_benchmark --plugin jerasure/isa
                           (src/test/erasure-code/ceph_erasure_code_benchmark.cc:151-181)
                           -> native cpp_rs plugin (gf8_simd: GFNI/AVX-512
                           or AVX2 pshufb), RS(4,2) and RS(8,4), 1 MiB.
  2. single-stripe jax_rs  same harness, --plugin jax_rs, one 1 MiB stripe
                           per call INCLUDING host->device transfer, plus
                           the plugin's auto-routed path (which sends
                           sub-threshold calls to the SIMD CPU codec —
                           the framework's answer to dispatch economics).
  3. batched device path   C++ BatchQueue -> coalesce -> one JAX dispatch
                           (the sidecar product path): throughput vs batch
                           size curve.
  4. cluster-level         rados bench on a MiniCluster EC pool
                           (qa/standalone/erasure-code/test-erasure-code.sh:21-66).
  5. bulk placement        osdmaptool --test-map-pgs analog: all PGs of a
                           pool through the vmapped JAX mapper vs the
                           scalar host interpreter, with bit-equality.

Writes BASELINE_RESULTS.json and prints a markdown table for BASELINE.md.

Usage: python tools/baseline_matrix.py [--quick] [--only N[,N...]]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

MIB = 2**20


def timeit(fn, iters, warmup=2):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def config1_cpu_baseline(quick: bool) -> dict:
    """Native SIMD CPU codec through the plugin path, 1 MiB buffers."""
    from ceph_tpu.native import NativeRegistry, registry_lib
    reg = NativeRegistry()
    level = registry_lib().ec_simd_level()
    out = {"simd_level": level,
           "simd_name": {0: "scalar", 1: "avx2", 2: "gfni+avx2",
                         3: "gfni+avx512"}[level]}
    iters = 10 if quick else 50
    for k, m in ((4, 2), (8, 4)):
        ec = reg.factory("cpp_rs", {"k": str(k), "m": str(m),
                                    "technique": "reed_sol_van"})
        chunk = MIB // k
        rng = np.random.default_rng(0)
        data = np.ascontiguousarray(
            rng.integers(0, 256, size=(k, chunk), dtype=np.uint8))
        t_enc = timeit(lambda: ec.encode(data), iters)
        parity = ec.encode(data)
        erased = [0, k]                      # 1 data + 1 parity
        avail = {i: data[i] for i in range(1, k)}
        avail |= {k + j: parity[j] for j in range(1, m)}
        t_dec = timeit(lambda: ec.decode(avail, erased, chunk), iters)
        out[f"rs_k{k}m{m}"] = {
            "encode_mibs": round(1.0 / t_enc, 1),
            "decode_mibs": round(1.0 / t_dec, 1),
        }
    return out


def config2_single_stripe(quick: bool) -> dict:
    """One 1 MiB stripe per call: device path incl. transfer, and the
    plugin's auto route."""
    import jax
    from ceph_tpu.ops import RSCodec
    k, m = 8, 4
    chunk = MIB // k
    rng = np.random.default_rng(1)
    data = np.ascontiguousarray(
        rng.integers(0, 256, size=(k, chunk), dtype=np.uint8))
    iters = 3 if quick else 10

    dev = RSCodec(k, m, technique="reed_sol_van", device="jax")
    t_dev = timeit(lambda: np.asarray(dev.encode(data)), iters, warmup=1)

    from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
    auto = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", {"k": str(k), "m": str(m), "device": "auto"})
    bufs = {i: (data[i].copy() if i < k else np.zeros(chunk, np.uint8))
            for i in range(k + m)}
    t_auto = timeit(
        lambda: auto.encode_chunks(set(range(k + m)), bufs), iters)

    cpu = RSCodec(k, m, technique="reed_sol_van", device="numpy")
    t_cpu = timeit(lambda: cpu.encode(data), iters)
    return {
        "platform": jax.devices()[0].platform,
        "device_incl_transfer_mibs": round(1.0 / t_dev, 1),
        "auto_routed_mibs": round(1.0 / t_auto, 1),
        "cpu_forced_mibs": round(1.0 / t_cpu, 1),
        "note": "device path moves k+m chunks across the host<->device "
                "link per call; the auto route "
                "compares against ec_device_threshold_bytes; cpu_forced "
                "is the SIMD host codec on the same call shape",
    }


def config3_batch_queue(quick: bool) -> dict:
    """C++ batch queue -> JAX dispatch: throughput vs batch size."""
    import jax
    import jax.numpy as jnp
    from ceph_tpu.native import BatchQueue
    from ceph_tpu.ops import RSCodec
    k, m, chunk = 8, 4, 4096
    codec = RSCodec(k, m, technique="reed_sol_van", device="jax")
    pmat = jax.device_put(jnp.asarray(codec.parity_mat))

    from ceph_tpu.ops import rs_kernels

    @jax.jit
    def kernel(batch):                       # [n, k, chunk] -> [n, m, chunk]
        flat = batch.transpose(1, 0, 2).reshape(k, -1)
        par = rs_kernels.gf_apply(pmat, flat, "auto")
        return par.reshape(m, -1, chunk).transpose(1, 0, 2)

    rng = np.random.default_rng(2)
    stripes_total = 256 if quick else 1024
    curve = []
    for max_batch in (1, 4, 16, 64, 256):
        def fn(data, n, c, _mb=max_batch):
            # pad partial batches to the coalescing cap: one static shape
            # per queue, so nothing recompiles inside the timed region
            if n < _mb:
                data = np.concatenate(
                    [data, np.zeros((_mb - n, k, c), np.uint8)])
            return np.asarray(kernel(jnp.asarray(data)))[:n]

        q = BatchQueue(k, m, chunk, fn, max_batch=max_batch)
        data = [np.ascontiguousarray(
            rng.integers(0, 256, size=(k, chunk), dtype=np.uint8))
            for _ in range(stripes_total)]
        q.submit(data[0]); q.flush()         # warm compile
        t0 = time.perf_counter()
        for d in data:
            q.submit(d)
        q.flush()
        dt = time.perf_counter() - t0
        batches = q.batches
        q.close()
        curve.append({
            "max_batch": max_batch,
            "stripes_per_s": round(stripes_total / dt, 1),
            "mibs": round(stripes_total * k * chunk / MIB / dt, 1),
            "dispatches": batches,
        })
    return {"k": k, "m": m, "chunk": chunk, "curve": curve}


def config4_rados_bench(quick: bool) -> dict:
    """Cluster-level write/read bench on a MiniCluster EC pool."""
    import io
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.bench.rados_bench import write_bench, seq_read_bench
    secs = 3 if quick else 10
    mc = MiniCluster(n_osds=12, osds_per_host=3)
    pid = mc.create_ec_pool("bench", {"plugin": "jax_rs", "k": "4",
                                      "m": "2"}, pg_num=8)
    sink = io.StringIO()
    w = write_bench(mc, pid, secs, 4 * MIB, concurrency=16, out=sink)
    r = seq_read_bench(mc, pid, w["ops"], 4 * MIB, out=sink)
    return {
        "write_mb_s": round(w["bandwidth_mb_s"], 1),
        "write_iops": round(w["iops"], 1),
        "read_mb_s": round(r["bandwidth_mb_s"], 1),
        "read_iops": round(r["iops"], 1),
        "seconds": secs,
    }


def config5_bulk_placement(quick: bool) -> dict:
    """All PGs of a pool: vmapped JAX mapper vs scalar host interpreter."""
    import jax
    jax.config.update("jax_enable_x64", True)   # exact straw2 draws
    from ceph_tpu.crush.map import (CRUSH_BUCKET_STRAW2,
                                    CRUSH_RULE_CHOOSELEAF_INDEP,
                                    CRUSH_RULE_EMIT, CRUSH_RULE_TAKE,
                                    CrushMap)
    from ceph_tpu.osdmap.osdmap import OSDMap
    from ceph_tpu.osdmap.types import PG, Pool, POOL_TYPE_ERASURE
    from ceph_tpu.osdmap.bulk import BulkPGMapper

    n_osds = 256
    pg_num = 4096 if quick else 32768
    cmap = CrushMap()
    cmap.set_type_name(1, "host")
    cmap.set_type_name(2, "root")
    hosts = []
    for h0 in range(0, n_osds, 8):
        items = list(range(h0, h0 + 8))
        hosts.append(cmap.add_bucket(
            CRUSH_BUCKET_STRAW2, 1, items, [0x10000] * len(items)))
    root = cmap.add_bucket(CRUSH_BUCKET_STRAW2, 2, hosts,
                           [sum(cmap.buckets[h].item_weights)
                            for h in hosts])
    cmap.finalize()
    ruleno = cmap.add_rule([(CRUSH_RULE_TAKE, root, 0),
                            (CRUSH_RULE_CHOOSELEAF_INDEP, 6, 1),
                            (CRUSH_RULE_EMIT, 0, 0)])
    m = OSDMap(crush=cmap)
    for o in range(n_osds):
        m.create_osd(o)
    pool = Pool(pool_id=1, type=POOL_TYPE_ERASURE, size=6, min_size=5,
                pg_num=pg_num, crush_rule=ruleno, name="bulk")
    m.add_pool(pool)

    t0 = time.perf_counter()
    host = [m.pg_to_up_acting_osds(PG(1, ps))[2] for ps in range(pg_num)]
    t_host = time.perf_counter() - t0

    mapper = BulkPGMapper(m)
    mapping = mapper.map_pool(1)             # includes jit compile
    t0 = time.perf_counter()
    mapping = mapper.map_pool(1)
    t_jax = time.perf_counter() - t0

    mismatch = sum(
        1 for ps in range(pg_num)
        if list(mapping.acting[ps][:len(host[ps])]) != list(host[ps]))
    return {
        "pg_num": pg_num, "n_osds": n_osds,
        "host_pgs_per_s": round(pg_num / t_host, 1),
        "jax_pgs_per_s": round(pg_num / t_jax, 1),
        "speedup": round(t_host / t_jax, 1),
        "mismatches": mismatch,
    }


CONFIGS = {
    1: ("cpu_baseline_simd", config1_cpu_baseline),
    2: ("single_stripe_incl_transfer", config2_single_stripe),
    3: ("batch_queue_curve", config3_batch_queue),
    4: ("rados_bench_minicluster", config4_rados_bench),
    5: ("bulk_placement", config5_bulk_placement),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--out", default="BASELINE_RESULTS.json")
    args = ap.parse_args()
    only = {int(x) for x in args.only.split(",") if x} or set(CONFIGS)

    results = {}
    try:
        with open(args.out) as f:
            results = json.load(f)
    except (OSError, ValueError):
        pass
    for n, (name, fn) in sorted(CONFIGS.items()):
        if n not in only:
            continue
        print(f"# config {n}: {name} ...", file=sys.stderr, flush=True)
        try:
            results[name] = fn(args.quick)
        except Exception as e:               # record the failure honestly
            results[name] = {"error": f"{type(e).__name__}: {e}"}
        print(json.dumps({name: results[name]}), flush=True)
    results["_meta"] = {"ts": time.time(), "quick": args.quick}
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    print(f"# wrote {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
