"""osdmaptool equivalent: bulk PG mapping tests and histograms.

Mirror of the reference tool's --test-map-pgs family (reference:
src/tools/osdmaptool.cc:38-40 usage, :491-610 the mapping loop, histogram
table and stddev summary) driven by the vmapped bulk mapper instead of a
per-PG loop.  Output format matches the reference line-for-line so existing
tooling can parse it:

    pool 1 pg_num 64
    #osd   count  first  primary  c wt   wt
    osd.0  12     4      4        1.0    1.0
    ...
     in 9
     avg 21 stddev 2.1 (0.1x) (expected 4.3 0.2x))
     min osd.3 18
     max osd.7 25

CLI:  python -m ceph_tpu.tools.osdmaptool MAP.json --test-map-pgs
      [--pool N] [--test-map-pgs-dump] [--test-map-pgs-dump-all]
"""
from __future__ import annotations

import argparse
import json
import math
import sys

from ..crush.map import CRUSH_ITEM_NONE
from ..osdmap import OSDMap, PG
from ..osdmap.bulk import BulkPGMapper


def device_crush_weights(crush) -> dict[int, int]:
    """Leaf item -> 16.16 weight (delegates to CrushMap.device_weights)."""
    return crush.device_weights()


def test_map_pgs(m: OSDMap, pool: int = -1, dump: bool = False,
                 dump_all: bool = False, out=None) -> dict:
    """The --test-map-pgs[-dump[-all]] loop (osdmaptool.cc:491-610).
    Returns the stats dict; prints the reference-format report to ``out``."""
    w = out.write if out is not None else (lambda s: None)
    n = m.max_osd
    count = [0] * n
    first_count = [0] * n
    primary_count = [0] * n
    size_hist: dict[int, int] = {}
    mapper = BulkPGMapper(m)

    for pid in sorted(m.pools):
        if pool != -1 and pid != pool:
            continue
        p = m.pools[pid]
        w(f"pool {pid} pg_num {p.pg_num}\n")
        pm = mapper.map_pool(pid)
        for ps in range(p.pg_num):
            acting = [int(o) for o in pm.acting[ps] if o != CRUSH_ITEM_NONE]
            primary = int(pm.acting_primary[ps])
            size_hist[len(acting)] = size_hist.get(len(acting), 0) + 1
            if dump:
                w(f"{pid}.{ps:x}\t{acting}\t{primary}\n")
            elif dump_all:
                raw, rawp = m.pg_to_raw_osds(PG(pid, ps))
                up = [int(o) for o in pm.up[ps] if o != CRUSH_ITEM_NONE]
                upp = int(pm.up_primary[ps])
                w(f"{pid}.{ps:x} raw ({raw}, p{rawp}) up ({up}, p{upp}) "
                  f"acting ({acting}, p{primary})\n")
            for o in acting:
                count[o] += 1
            if acting:
                first_count[acting[0]] += 1
            if primary >= 0:
                primary_count[primary] += 1

    cw = device_crush_weights(m.crush)
    total = 0
    n_in = 0
    min_osd = max_osd = -1
    w("#osd\tcount\tfirst\tprimary\tc wt\twt\n")
    for i in range(n):
        if not m.is_in(i) or cw.get(i, 0) <= 0:
            continue
        n_in += 1
        w(f"osd.{i}\t{count[i]}\t{first_count[i]}\t{primary_count[i]}"
          f"\t{cw.get(i, 0) / 0x10000:g}\t{m.osd_weight[i] / 0x10000:g}\n")
        total += count[i]
        if count[i] and (min_osd < 0 or count[i] < count[min_osd]):
            min_osd = i
        if count[i] and (max_osd < 0 or count[i] > count[max_osd]):
            max_osd = i
    avg = total // n_in if n_in else 0
    dev = 0.0
    for i in range(n):
        if not m.is_in(i) or cw.get(i, 0) <= 0:
            continue
        dev += (avg - count[i]) ** 2
    dev = math.sqrt(dev / n_in) if n_in else 0.0
    edev = math.sqrt(total / n_in * (1.0 - 1.0 / n_in)) if n_in else 0.0
    w(f" in {n_in}\n")
    w(f" avg {avg} stddev {dev:g} ({dev / avg if avg else 0:g}x) "
      f"(expected {edev:g} {edev / avg if avg else 0:g}x))\n")
    if min_osd >= 0:
        w(f" min osd.{min_osd} {count[min_osd]}\n")
    if max_osd >= 0:
        w(f" max osd.{max_osd} {count[max_osd]}\n")
    w(f"size {json.dumps(dict(sorted(size_hist.items())))}\n")
    return {"count": count, "first": first_count, "primary": primary_count,
            "size_hist": size_hist, "in": n_in, "avg": avg, "stddev": dev,
            "min_osd": min_osd, "max_osd": max_osd, "total": total}


def main(argv=None) -> int:
    from ..common.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        prog="osdmaptool", description=__doc__.splitlines()[0])
    ap.add_argument("mapfile", help="OSDMap as JSON (OSDMap.to_dict)")
    ap.add_argument("--test-map-pgs", action="store_true")
    ap.add_argument("--test-map-pgs-dump", action="store_true")
    ap.add_argument("--test-map-pgs-dump-all", action="store_true")
    ap.add_argument("--test-map-pg", metavar="PGID",
                    help="map one pg, e.g. 1.7")
    ap.add_argument("--pool", type=int, default=-1)
    ap.add_argument("--print", dest="do_print", action="store_true",
                    help="summarize the map")
    ap.add_argument("--upmap", metavar="OUT",
                    help="calculate pg upmap entries to balance pg layout "
                         "and write them as JSON (osdmaptool --upmap)")
    ap.add_argument("--upmap-deviation", type=float, default=1.0)
    ap.add_argument("--upmap-max", type=int, default=32,
                    help="max optimization iterations")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_enable_x64", True)   # exact straw2 draws

    with open(args.mapfile) as f:
        m = OSDMap.from_dict(json.load(f))

    if args.do_print:
        print(f"epoch {m.epoch}")
        print(f"max_osd {m.max_osd}")
        for pid in sorted(m.pools):
            p = m.pools[pid]
            kind = "replicated" if p.type == 1 else "erasure"
            print(f"pool {pid} '{p.name}' {kind} size {p.size} "
                  f"pg_num {p.pg_num} crush_rule {p.crush_rule}")
    if args.test_map_pg:
        pool_s, ps_s = args.test_map_pg.split(".")
        pg = PG(int(pool_s), int(ps_s, 16))
        print(f" parsed '{args.test_map_pg}' -> {pg}")
        raw, rawp = m.pg_to_raw_osds(pg)
        up, upp, acting, actingp = m.pg_to_up_acting_osds(pg)
        print(f"{pg} raw ({raw}, p{rawp}) up ({up}, p{upp}) "
              f"acting ({acting}, p{actingp})")
    if args.test_map_pgs or args.test_map_pgs_dump or args.test_map_pgs_dump_all:
        test_map_pgs(m, pool=args.pool, dump=args.test_map_pgs_dump,
                     dump_all=args.test_map_pgs_dump_all, out=sys.stdout)
    if args.upmap:
        from ..mgr import calc_pg_upmaps
        inc = calc_pg_upmaps(
            m, max_iterations=args.upmap_max,
            max_deviation=args.upmap_deviation,
            pools=None if args.pool == -1 else [args.pool])
        entries = {f"{pg.pool}.{pg.ps}": items
                   for pg, items in inc.new_pg_upmap_items.items()}
        with open(args.upmap, "w") as f:
            json.dump({"pg_upmap_items": entries}, f, indent=1)
            f.write("\n")
        print(f"wrote {len(entries)} pg_upmap_items to {args.upmap}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
