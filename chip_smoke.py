#!/usr/bin/env python3
"""chip_smoke.py: does the system still start, serve and place on the chip?

One process, one chip, the entry points a user calls, data from
``--seed``.  Six phases, each compared against the repo's plain
references (``gf/ref.py`` in pure numpy, the scalar ``crush_do_rule``
path, the bytes a client wrote) outside any timed region:

  kernel      RS(8,4) cauchy, 64 stripes x 1 MiB resident in HBM, through
              the vertical and the horizontal kernel selectors, and the
              benchmark's 256 stripes through the horizontal one; then the
              HashInfo checksum (crc32c_rows) at the served shard shapes
  served      MiniCluster + serving engine + ClusterServer + TcpRados:
              put, read, degraded read, repair, kill -9 and reload
  placement   BulkPGMapper.map_pool over 32,768 PGs x 256 OSDs under x64,
              then one more Pallas encode in the same x64 process
  entry       ``__graft_entry__``: entry() jitted, dryrun_multichip()
  ec_bench    the metric of record's command, routed to the device
  clay        Clay k=8 m=4 d=11 through its plugin's device codec: the
              encode, the layered decode of chunks 0 and 9, and the
              repair of every one of the 12 chunks from its 11 helpers'
              repair runs, against ``benchmark/lib/clay_reference.py``

No phase's failure is caught: the first failed check or exception ends
the run non-zero.  The bare command refuses anything but a TPU;
``--rehearsal`` is the same code at tiny sizes on whatever JAX finds,
prints ``REHEARSAL platform=...`` and never prints the result line.

Timings printed here are smoke timings, not metrics: set-up is the time
JAX spent tracing, lowering and compiling (or fetching from the compile
cache) inside the phase, run is the rest of the phase's wall time.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

# the sizes a deployment would run (ISSUE 21 tentpole §1) and the tiny
# ones a --rehearsal runs on the CPU
REAL = dict(stripes=64, resident_stripes=256, chunk=131072, n_osds=12,
            objects=64,
            object_bytes=4 << 20, clients=16, overwrite=8,
            bulk_osds=256, bulk_pgs=32768, bulk_sample=1024,
            ec_size=1048576,
            # the served shard of a 4 MiB and of a 64 KiB object, and an
            # odd one: PR 21's wrong crc was at the first, only on the chip
            crc_shapes=((12, 524288), (12, 8192), (5, 777)))
TINY = dict(stripes=8, resident_stripes=24, chunk=1024, n_osds=12, objects=8,
            object_bytes=4 * 8 * 1024, clients=4, overwrite=2,
            bulk_osds=32, bulk_pgs=256, bulk_sample=32,
            ec_size=65536, crc_shapes=((12, 8192), (5, 777)))

K, M = 8, 4
ERASURES_TWO = [0, 9]
ERASURES_ONE = [3]


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    """A failed comparison ends the run (``assert`` would vanish under
    ``python -O``)."""
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileMeter:
    """What JAX built in this process, from its own monitoring events:
    every executable it obtained (compiled OR fetched from the persistent
    cache), the persistent-cache hits among them, and the wall-clock
    intervals it spent tracing, lowering and compiling.  The intervals
    nest (an outer trace contains its inner jits') and overlap across
    threads, so set-up time is the length of their union."""

    SETUP_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration",
                    "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self.executables = 0
        self.cache_hits = 0
        self._spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_kw) -> None:
        if event in self.SETUP_EVENTS:
            end = time.perf_counter()       # the event fires as it ends
            with self._lock:
                self._spans.append((end - duration, end))
                if event == self.SETUP_EVENTS[2]:
                    self.executables += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def setup_seconds(self, since: float = 0.0) -> float:
        """Length of the union of the set-up intervals after ``since``."""
        with self._lock:
            spans = sorted((max(a, since), b) for a, b in self._spans
                           if b > since)
        total, edge = 0.0, since
        for a, b in spans:
            if b > edge:
                total += b - max(a, edge)
                edge = b
        return total


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter):
    say(f"--- phase {name}")
    ex0, hit0 = meter.executables, meter.cache_hits
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    setup = meter.setup_seconds(since=t0)
    say(f"phase {name}: ok  setup_s={setup:.2f} run_s={wall - setup:.2f} "
        f"executables={meter.executables - ex0} "
        f"cache_hits={meter.cache_hits - hit0}")


# -- header -------------------------------------------------------------------

def rebuild_native() -> str:
    """``native/build/`` is git-ignored yet rides along in a copy of the
    disk: drop it and rebuild from ``native/src`` so the host codec under
    test is this checkout's.  A missing toolchain fails the run here —
    it must not continue on the Python crc and numpy GF."""
    import subprocess
    from ceph_tpu import native
    shutil.rmtree(native.BUILD_DIR, ignore_errors=True)
    try:
        native.build(force=True)
    except (OSError, subprocess.CalledProcessError) as e:
        err = getattr(e, "stderr", b"") or b""
        raise SystemExit(
            f"chip_smoke: FAILED: native toolchain missing or broken "
            f"(make -C native: {e}) {err.decode(errors='replace')[-400:]}")
    level = native.registry_lib().ec_simd_level()
    return {0: "scalar", 1: "avx2", 2: "gfni+avx2",
            3: "gfni+avx512"}.get(level, str(level))


def header(cache_dir, cache_entries: int) -> None:
    import jax
    import jaxlib
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    simd = rebuild_native()
    d = jax.devices()[0]
    say(f"device: platform={d.platform} device_kind={d.device_kind} "
        f"count={len(jax.devices())}")
    say(f"versions: python={sys.version.split()[0]} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say(f"compile cache: dir={cache_dir} entries_before={cache_entries}")
    say(f"host codec: native simd={simd} "
        f"(native/build rebuilt from native/src)")


# -- phase 1: the kernels, resident --------------------------------------------

def phase_kernels(cfg: dict, rng, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ceph_tpu.gf import cauchy1, decode_matrix
    from ceph_tpu.gf import ref as gfref
    from ceph_tpu.ops import rs_kernels

    stripes, n = cfg["stripes"], cfg["chunk"]
    vert = rng.integers(0, 256, size=(stripes * K, n), dtype=np.uint8)
    # the same bytes in the horizontal layout: [k, stripes * n]
    horiz = np.ascontiguousarray(
        vert.reshape(stripes, K, n).transpose(1, 0, 2).reshape(K, -1))
    pm = cauchy1(K, M)
    parity = gfref.apply_matrix(pm, horiz)        # pure numpy, not the .so
    full = np.concatenate([horiz, parity], axis=0)
    cases = [("encode", pm, horiz, parity)]
    for name, erasures in (("decode2", ERASURES_TWO),
                           ("decode1", ERASURES_ONE)):
        D, src = decode_matrix(pm, erasures)
        cases.append((name, D, full[src], full[erasures]))

    def to_vert(h):           # [r, stripes*n] -> [stripes*r, n]
        r = h.shape[0]
        return h.reshape(r, stripes, n).transpose(1, 0, 2).reshape(-1, n)

    def apply_vert(Mt, Dd):
        return rs_kernels.gf_apply_stripes(Mt, Dd, stripes, "auto")

    def apply_horiz(Mt, Dd):
        return rs_kernels.gf_apply(Mt, Dd, "auto")

    def check_apply(layout, fn, name, mat, data, want):
        mat_d = jax.device_put(jnp.asarray(mat))
        data_d = jax.device_put(jnp.asarray(data))
        jfn = jax.jit(fn)
        if on_tpu:
            # positive proof of WHICH kernel the selector picked
            check("tpu_custom_call" in jfn.lower(mat_d, data_d).as_text(),
                  f"{layout} {name}: selector under 'auto' did not "
                  f"lower to the pallas kernel (no tpu_custom_call)")
        t0 = time.perf_counter()
        got = jax.block_until_ready(jfn(mat_d, data_d))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = jax.block_until_ready(jfn(mat_d, data_d))
        steady = time.perf_counter() - t0
        check(np.array_equal(np.asarray(got), want),
              f"{layout} {name} {tuple(data.shape)} differs from gf/ref")
        say(f"  {layout:10s} {name:8s} {tuple(mat.shape)} x "
            f"{tuple(data.shape)}: bit-equal; first_call_s={first:.3f} "
            f"steady_call_s={steady:.5f}")

    for name, mat, src_h, want_h in cases:
        check_apply("vertical", apply_vert, name, mat,
                    to_vert(src_h), to_vert(want_h))
        check_apply("horizontal", apply_horiz, name, mat, src_h, want_h)

    # the benchmark's resident row (ec_resident_b256: 256 stripes x 1 MiB
    # as one [8, 33554432] array), fresh bytes: four times the grid steps
    # of the row above, the shape whose rate the ledger records
    wide = rng.integers(0, 256, size=(K, cfg["resident_stripes"] * n),
                        dtype=np.uint8)
    wide_parity = gfref.apply_matrix(pm, wide)
    check_apply("horizontal", apply_horiz, "encode", pm, wide, wide_parity)
    D, src = decode_matrix(pm, ERASURES_TWO)
    wide_full = np.concatenate([wide, wide_parity], axis=0)
    check_apply("horizontal", apply_horiz, "decode2", D, wide_full[src],
                wide_full[ERASURES_TWO])
    del wide, wide_parity, wide_full

    # the HashInfo checksum, bit for bit against the host's crc32c: rows
    # given from the host (words before the upload), rows on the device
    # (made words there), and the fused encode + checksum dispatch
    from ceph_tpu.backend import ecutil

    def host_crcs(rows):
        return [ecutil.crc32c(0, np.ascontiguousarray(row)) for row in rows]

    for r, width in cfg["crc_shapes"]:
        fills = (("random", rng.integers(0, 256, size=(r, width),
                                         dtype=np.uint8)),
                 ("zeros", np.zeros((r, width), dtype=np.uint8)),
                 ("0xff", np.full((r, width), 0xFF, dtype=np.uint8)))
        for fill, rows in fills:
            want = host_crcs(rows)
            for given, arg in (("host", rows),
                               ("device", jax.device_put(rows))):
                got = np.asarray(rs_kernels.crc32c_rows(arg))
                check([int(c) for c in got] == want,
                      f"crc32c_rows [{r}, {width}] {fill} rows given from "
                      f"the {given} differs from ecutil.crc32c")
        say(f"  crc32c_rows [{r}, {width}]: bit-equal (random, zeros, 0xff; "
            f"host and device rows)")
    width = cfg["crc_shapes"][0][1]
    data = rng.integers(0, 256, size=(K, width), dtype=np.uint8)
    got_parity, got_crcs = rs_kernels.gf_encode_with_crc(pm, data)
    want_parity = gfref.apply_matrix(pm, data)
    check(np.array_equal(np.asarray(got_parity), want_parity),
          f"gf_encode_with_crc [{K}, {width}]: parity differs from gf/ref")
    check([int(c) for c in np.asarray(got_crcs)]
          == host_crcs(np.concatenate([data, want_parity], axis=0)),
          f"gf_encode_with_crc [{K}, {width}]: crcs differ from ecutil.crc32c")
    say(f"  gf_encode_with_crc [{K}, {width}]: parity and crcs bit-equal")


# -- phase 2: the served path, over the wire -----------------------------------

def _run_clients(n_clients: int, port: int, keyring, work: list, fn) -> list:
    """``fn(client, item)`` over ``work`` from ``n_clients`` threads, one
    TcpRados connection each; the first failure is re-raised here."""
    from ceph_tpu.net import TcpRados
    it = iter(work)
    lock = threading.Lock()
    results, errors = [], []

    def loop():
        r = TcpRados("127.0.0.1", port, keyring)
        try:
            while not errors:
                with lock:
                    item = next(it, None)
                if item is None:
                    return
                out = fn(r, item)
                with lock:
                    results.append(out)
        except BaseException as e:          # noqa: BLE001 — re-raised below
            errors.append(e)
        finally:
            r.close()

    threads = [threading.Thread(target=loop, name=f"smoke-client-{i}")
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
        check(not t.is_alive(), f"{t.name} still running after 900 s")
    if errors:
        raise errors[0]
    return results


def _pipeline_report(serving, label: str) -> dict:
    pl = serving.pipeline
    perf = {k: int(pl.perf.get(k)) for k in
            ("submitted", "completed", "errors", "host_fallbacks",
             "breaker_state", "mesh_dispatches")}
    say(f"  pipeline[{label}]: {perf} last_device_error="
        f"{pl.last_device_error!r} mesh_error={pl.mesh_error!r}")
    check(pl.last_device_error is None and pl.mesh_error is None,
          f"device error behind a fallback: {pl.last_device_error} / "
          f"{pl.mesh_error}")
    check(perf["submitted"] > 0, "pipeline saw no submissions")
    check(perf["host_fallbacks"] == 0 and perf["errors"] == 0
          and perf["breaker_state"] == 0,
          f"pipeline not clean: {perf}")
    return perf


def phase_served(cfg: dict, rng, out_dir: Path, meter: CompileMeter) -> None:
    import jax
    import numpy as np
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.net import ClusterServer, TcpRados

    profile = {"plugin": "jax_rs", "k": str(K), "m": str(M),
               "technique": "cauchy", "device": "jax"}
    data_dir = out_dir / "cluster"
    objects = {f"obj{i:03d}": rng.integers(
        0, 256, cfg["object_bytes"], dtype=np.uint8).tobytes()
        for i in range(cfg["objects"])}

    c = MiniCluster(n_osds=cfg["n_osds"], osds_per_host=1,
                    chunk_size=cfg["chunk"], data_dir=data_dir,
                    store_backend="bluestore")
    serving = c.enable_serving(start=True)
    server = ClusterServer(c)
    server.start()
    keyring = data_dir / "client.admin.keyring"
    n_clients = cfg["clients"]

    def put(r, oid):
        return r.put("smoke", oid, objects[oid])

    def get_equal(r, oid):
        check(r.get("smoke", oid) == objects[oid],
              f"{oid} read back differs from what was written")
        return oid

    def read_all():
        got = _run_clients(n_clients, server.port, keyring,
                           sorted(objects), get_equal)
        check(len(got) == len(objects), "not every object was read")

    try:
        admin = TcpRados("127.0.0.1", server.port, keyring)
        pid = admin.mkpool("smoke", profile=profile, pg_num=8)
        admin.close()

        t0 = time.perf_counter()
        acked = _run_clients(n_clients, server.port, keyring,
                             sorted(objects), put)
        check(len(acked) == len(objects), "not every put was acked")
        say(f"  put {len(objects)} x {cfg['object_bytes']} B by "
            f"{n_clients} clients: {time.perf_counter() - t0:.2f} s "
            f"(compiles included)")
        read_all()
        say("  read back: all equal")
        healthy = _pipeline_report(serving, "healthy")

        # two OSDs down, the way tests/test_thrash.py kills them (never a
        # primary: the per-PG group models no primary takeover)
        pgs = list(c.pools[pid]["pgs"].values())
        primaries = {g.backend.whoami for g in pgs}
        down = [o for o in range(cfg["n_osds"]) if o not in primaries][:2]
        check(len(down) == 2, "no two non-primary OSDs to mark down")
        with server.lock:
            for osd in down:
                for g in pgs:
                    if osd in g.acting:
                        g.bus.mark_down(osd)
        read_all()
        degraded = _pipeline_report(serving, f"osd.{down} down")
        check(degraded["submitted"] > healthy["submitted"],
              "degraded reads dispatched nothing to the device decode")
        say(f"  degraded read with osd.{down} down: all equal")
        # overwrite a few while degraded, so the revived shards really
        # are stale and the repair below has something to rebuild
        for oid in sorted(objects)[:cfg["overwrite"]]:
            objects[oid] = rng.integers(
                0, 256, cfg["object_bytes"], dtype=np.uint8).tobytes()
        acked = _run_clients(n_clients, server.port, keyring,
                             sorted(objects)[:cfg["overwrite"]], put)
        check(len(acked) == cfg["overwrite"], "degraded overwrite not acked")

        with server.lock:
            for osd in down:
                for g in pgs:
                    if osd in g.acting:
                        g.bus.mark_up(osd)
                        g.bus.deliver_all()
            for _ in range(20):
                for g in pgs:
                    g.bus.deliver_all()
                if not any(g.backend.stale or g.backend.shard_repairs
                           for g in pgs):
                    break
            stale = {str(g.pgid): sorted(g.backend.stale)
                     for g in pgs if g.backend.stale}
            check(not stale, f"shards never repaired: {stale}")
            report = c.scrub_pool(pid, repair=False)
        check(report == {}, f"deep scrub after repair found {report}")
        read_all()
        say("  revived, repaired, deep scrub clean, read back: all equal")

        # a second pass over the same shapes must compile nothing
        ex0 = meter.executables
        read_all()
        _run_clients(n_clients, server.port, keyring, sorted(objects), put)
        check(meter.executables == ex0,
              f"second pass over the same shapes built "
              f"{meter.executables - ex0} new executables")
        say("  second pass (read all, rewrite all): 0 executables built")
        _pipeline_report(serving, "final")
        stats = jax.devices()[0].memory_stats() or {}
        say(f"  device memory: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')} "
            f"bytes_limit={stats.get('bytes_limit', 'not reported')}")
    finally:
        server.stop()
    # kill -9: the cluster is dropped WITHOUT shutdown() — no checkpoint,
    # no store close; only its threads are stopped so the process can go
    # on.  Every acked write must come back from the WAL.
    serving.stop()
    del server, serving, c
    c2 = MiniCluster.load(data_dir)
    try:
        pid2 = c2.pool_ids["smoke"]
        for oid, want in sorted(objects.items()):
            check(c2.get(pid2, oid, len(want)) == want,
                  f"acked object {oid} lost across kill -9 + reload")
        say(f"  dropped without shutdown, MiniCluster.load: all "
            f"{len(objects)} acked objects read back equal")
    finally:
        c2.shutdown()


# -- phase 3: bulk placement under x64, then pallas again ----------------------

def phase_placement(cfg: dict, rng, on_tpu: bool) -> None:
    import jax
    import numpy as np
    jax.config.update("jax_enable_x64", True)    # exact straw2 draws
    from ceph_tpu.crush.map import (CRUSH_BUCKET_STRAW2,
                                    CRUSH_RULE_CHOOSELEAF_INDEP,
                                    CRUSH_RULE_EMIT, CRUSH_RULE_TAKE,
                                    CrushMap)
    from ceph_tpu.osdmap.bulk import BulkPGMapper
    from ceph_tpu.osdmap.osdmap import OSDMap
    from ceph_tpu.osdmap.types import PG, POOL_TYPE_ERASURE, Pool

    # the map of BASELINE.json config 5: hosts of 8 OSDs under one
    # straw2 root, chooseleaf indep 6
    n_osds, pg_num = cfg["bulk_osds"], cfg["bulk_pgs"]
    cmap = CrushMap()
    cmap.set_type_name(1, "host")
    cmap.set_type_name(2, "root")
    hosts = [cmap.add_bucket(CRUSH_BUCKET_STRAW2, 1,
                             list(range(h0, h0 + 8)), [0x10000] * 8)
             for h0 in range(0, n_osds, 8)]
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
    m.add_pool(Pool(pool_id=1, type=POOL_TYPE_ERASURE, size=6, min_size=5,
                    pg_num=pg_num, crush_rule=ruleno, name="bulk"))

    mapper = BulkPGMapper(m)
    t0 = time.perf_counter()
    mapping = mapper.map_pool(1)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    mapping = mapper.map_pool(1)
    steady = time.perf_counter() - t0
    sample = rng.choice(pg_num, size=cfg["bulk_sample"], replace=False)
    for ps in (int(x) for x in sample):
        # the scalar host path: OSDMap -> crush.mapper.crush_do_rule
        want = list(m.pg_to_up_acting_osds(PG(1, ps))[2])
        check(list(mapping.acting[ps][:len(want)]) == want,
              f"pg 1.{ps}: bulk {list(mapping.acting[ps])} != scalar {want}")
    say(f"  map_pool {pg_num} PGs x {n_osds} OSDs (x64): "
        f"{len(sample)} seeded PGs bit-equal to crush_do_rule; "
        f"first_call_s={first:.2f} steady_call_s={steady:.4f}")

    # x64 is still on: Pallas and CRUSH must coexist in one process
    import jax.numpy as jnp
    from ceph_tpu.gf import cauchy1
    from ceph_tpu.gf import ref as gfref
    from ceph_tpu.ops import rs_kernels
    pm = cauchy1(K, M)
    data = rng.integers(0, 256, size=(K, 8 * cfg["chunk"]), dtype=np.uint8)
    data_d = jax.device_put(jnp.asarray(data))
    jfn = jax.jit(lambda Mt, Dd: rs_kernels.gf_apply(Mt, Dd, "auto"))
    if on_tpu:
        check("tpu_custom_call" in jfn.lower(jnp.asarray(pm), data_d).as_text(),
              "x64 encode did not lower to the pallas kernel")
    got = np.asarray(jfn(jnp.asarray(pm), data_d))
    check(np.array_equal(got, gfref.apply_matrix(pm, data)),
          "pallas encode under x64 differs from gf/ref")
    say(f"  pallas encode {tuple(data.shape)} with x64 on: bit-equal")


# -- phase 4: the driver's entry points ----------------------------------------

def phase_entry() -> None:
    import jax
    sys.path.insert(0, str(REPO))
    import __graft_entry__ as graft
    fn, args = graft.entry()
    out = jax.block_until_ready(jax.jit(fn)(*args))
    say(f"  jit(entry()[0]): {[tuple(o.shape) for o in out]}")
    graft.dryrun_multichip(len(jax.devices()))
    say(f"  dryrun_multichip({len(jax.devices())}): ok")


# -- phase 5: the metric of record's command -----------------------------------

def phase_ec_bench(cfg: dict) -> None:
    from ceph_tpu.bench import ec_bench
    base = ["--plugin", "jax_rs", "--parameter", f"k={K}",
            "--parameter", f"m={M}", "--parameter", "device=jax",
            "--size", str(cfg["ec_size"])]
    for workload in (["--workload", "encode"],
                     ["--workload", "decode", "--erased", "0",
                      "--erased", "9"]):
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = ec_bench.main(base + workload + ["--iterations", "3"])
        say(f"  ec_bench {' '.join(workload)}: rc={rc} "
            f"stdout={out.getvalue().strip()!r}")
        say(f"    {err.getvalue().strip()}")
        check(rc == 0, f"ec_bench {workload} exited {rc}")
        secs, kib = out.getvalue().split()
        check(float(secs) > 0 and int(kib) == 3 * (cfg["ec_size"] // 1024),
              f"ec_bench {workload} printed {out.getvalue()!r}")
        check("route=device" in err.getvalue(),
              f"ec_bench {workload} was not routed to the device")


# -- phase 6: Clay through its plugin's device codec --------------------------

def phase_clay(cfg: dict, rng, on_tpu: bool) -> None:
    import numpy as np
    from benchmark.lib.clay_reference import Clay
    from ceph_tpu.ops import clay_kernels
    from ceph_tpu.plugins.registry import ErasureCodePluginRegistry

    d = K + M - 1
    ec = ErasureCodePluginRegistry.instance().factory(
        "clay", "", {"k": str(K), "m": str(M), "d": str(d),
                     "scalar_mds": "jax_rs", "technique": "cauchy",
                     "device": "jax"})
    codec, ref = ec.codec, Clay(K, M, d)
    check(codec is not None, "the clay plugin built no device codec")
    stripes, chunk = cfg["stripes"], cfg["chunk"]
    planes = ec.get_sub_chunk_count()
    sc = chunk // planes
    data = rng.integers(0, 256, size=(K, stripes * chunk), dtype=np.uint8)
    full = ref.codeword(data, chunk)               # pure numpy
    perf = clay_kernels.perf_counters()
    calls = perf.dump()["device_dispatches"]
    kernel_calls = perf.dump()["kernel_calls"]

    def same(got, want, what):
        got = np.asarray(got)
        bad = int(np.count_nonzero(got != want)) if got.shape == want.shape \
            else want.size
        say(f"  clay {what}: {got.shape} {bad} bytes differ")
        check(bad == 0, f"clay {what} differs from the reference")

    same(codec.encode_device(data, chunk), full[K:], "encode")
    erasures = ERASURES_TWO
    src = codec.decode_sources(erasures)
    same(codec.decode_device(full[src], erasures, chunk), full[erasures],
         f"decode {erasures}")
    for lost in range(K + M):
        runs = ec.minimum_to_decode({lost}, set(range(K + M)) - {lost})
        ids = list(runs)
        picked = [z for off, cnt in runs[ids[0]]
                  for z in range(off, off + cnt)]
        check(picked == ref.repair_planes(lost),
              f"repair of {lost} reads other planes than the reference's")
        helpers = full[ids].reshape(d, stripes, planes, sc)[:, :, picked]
        same(codec.repair_device(helpers.reshape(d, -1), lost, ids, chunk),
             full[lost], f"repair of {lost} from {ids}")
    made = perf.dump()["device_dispatches"] - calls
    check(made == 2 + K + M, f"{made} dispatches for {2 + K + M} calls")
    ran = perf.dump()["kernel_calls"] - kernel_calls
    check(ran == (made if on_tpu else 0),
          f"{ran} of {made} calls counted as kernel calls")
    # on the chip every call above ran the Pallas kernel, not XLA's path
    for sig in (("encode",), ("decode", tuple(erasures)),
                ("repair", K + M - 1, tuple(ids))):
        _mat, plan = codec.tables(sig).dev[on_tpu]
        check((plan is not None) == on_tpu,
              f"clay {sig[0]} took the {'XLA' if plan is None else 'kernel'}"
              f" path on {'a TPU' if on_tpu else 'the CPU'}")
    # the plugin's own entry points, one object of one stripe
    size = cfg["ec_size"]
    cp = ec.get_chunk_size(size)
    obj = rng.integers(0, 256, size=K * cp, dtype=np.uint8)
    full = ref.codeword(obj.reshape(K, cp), cp)
    enc = ec.encode(set(range(K + M)), obj.tobytes())
    same(np.stack([enc[i] for i in range(K + M)]), full, "plugin encode")
    dec = ec.decode(set(erasures), {i: enc[i] for i in range(K + M)
                                    if i not in erasures})
    same(np.stack([dec[e] for e in erasures]), full[erasures],
         "plugin decode")
    runs = ec.minimum_to_decode({0}, set(range(1, K + M)))
    helpers = {h: enc[h].reshape(planes, -1)[
        [z for off, cnt in r for z in range(off, off + cnt)]].reshape(-1)
        for h, r in runs.items()}
    same(ec.decode({0}, helpers, cp)[0], full[0], "plugin repair")


# -- main ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever platform JAX finds; "
                         "prints REHEARSAL and never the result line")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    on_tpu = platform == "tpu"
    if args.rehearsal:
        say(f"REHEARSAL platform={platform}")
    elif not on_tpu:
        print(f"chip_smoke: no accelerator: jax.devices()[0].platform is "
              f"{platform!r}, not 'tpu' (a CPU rehearsal is "
              f"--rehearsal, and proves nothing about the chip)",
              file=sys.stderr)
        return 1
    cfg = TINY if args.rehearsal else REAL

    import numpy as np
    from ceph_tpu.common import compile_cache
    cache_dir = compile_cache.enable_compile_cache()
    entries_before = compile_cache.entry_count(cache_dir)
    header(cache_dir, entries_before)
    meter = CompileMeter()
    rng = np.random.default_rng(args.seed)
    out_dir = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    t0 = time.perf_counter()
    try:
        with phase("kernel", meter):
            phase_kernels(cfg, rng, on_tpu)
        with phase("served", meter):
            phase_served(cfg, rng, out_dir, meter)
        with phase("placement", meter):
            phase_placement(cfg, rng, on_tpu)
        with phase("entry", meter):
            phase_entry()
        with phase("ec_bench", meter):
            phase_ec_bench(cfg)
        with phase("clay", meter):
            phase_clay(cfg, rng, on_tpu)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    setup = meter.setup_seconds(since=t0)
    entries_after = compile_cache.entry_count(cache_dir)
    say(f"compile cache: dir={cache_dir} entries_before={entries_before} "
        f"entries_after={entries_after} added="
        f"{entries_after - entries_before}")
    say(f"total: wall_s={time.perf_counter() - t0:.1f} setup_s={setup:.1f} "
        f"executables={meter.executables} cache_hits={meter.cache_hits}")
    if args.rehearsal:
        say(f"REHEARSAL ok platform={platform}")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
