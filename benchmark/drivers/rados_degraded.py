"""Driver ``rados_degraded``: the served read path of ``rados`` on a pool
with ``osds_down`` OSDs down and not out.

The object set is put and read once with every OSD up (the parent's
set-up, unchanged).  Then the lowest-numbered OSDs that are primary of
no PG are marked down on every PG that holds them, under the cluster
lock, the way ``chip_smoke.py`` and ``tests/test_thrash.py`` stop an
OSD; they stay down for the whole run and nothing is recovered.  A pass
over the whole set follows, so the window compiles nothing: the first
degraded get of each erasure count builds its decode program, and what
that costs is printed beside the same pass over the clean pool.

``correct`` holds what ``rados`` holds, with a stored-shard check that
knows the down OSDs' shards are unreachable but intact, and besides:
the window's gets decoded on the device, every PG is active+degraded
before and after, the down set is what it was and the down OSDs' stores
were not written.  No check reads a counter newer than the pipeline's
``device_dispatches``.
"""
from __future__ import annotations

import sys
import time

from ..lib.compile_meter import CompileMeter
from . import rados


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Driver(rados.Driver):
    def __init__(self, config, traffic, seed, run_dir, control=None):
        super().__init__(config, traffic, seed, run_dir, control)
        # osds_up: nothing is marked down, so nothing is decoded: a run
        # that decodes nothing is not a run of this cell
        self.osds_up = control == "osds_up"
        if self.osds_up:
            self.control = None               # the parent knows it not
        self.down: list[int] = []
        self.down_seqs: dict[int, int] = {}
        self.decoded = self.gets = 0

    # -- set-up ----------------------------------------------------------------

    def setup(self) -> None:
        super().setup()
        meter = CompileMeter()
        t0 = time.perf_counter()
        self._warm_pass("clean")
        t1 = time.perf_counter()
        if not self.osds_up:
            self._mark_down(int(self.p["osds_down"]))
        built = meter.executables
        t2 = time.perf_counter()
        self._warm_pass("degraded")
        t3 = time.perf_counter()
        say(f"warm pass over {len(self.object_set)} objects, one get at a "
            f"time: clean {t1 - t0:.3f} s, then with OSDs {self.down} down "
            f"{t3 - t2:.3f} s with {meter.executables - built} executables "
            f"built in it ({meter.cache_hits} from the compile cache)")
        self._say_mix()

    def _warm_pass(self, what: str) -> None:
        compared, wrong = self.clients.readback(self.object_set)
        if wrong or compared != len(self.object_set):
            raise RuntimeError(f"{what} warm pass: {wrong} of {compared} "
                               f"gets differ from what was put")

    def _pgs(self) -> list:
        return list(self.cluster.pools[self.pool_id]["pgs"].values())

    def _mark_down(self, n: int) -> None:
        pgs = self._pgs()
        primaries = {g.backend.whoami for g in pgs}
        self.down = [o for o in range(self.p["n_osds"])
                     if o not in primaries][:n]
        if len(self.down) != n:
            raise RuntimeError(f"no {n} OSDs that are primary of no PG")
        with self.server.lock:
            for g in pgs:
                for osd in self.down:
                    if osd in g.acting:
                        g.bus.mark_down(osd)
            self.down_seqs = self._down_store_seqs()
            states = self._pg_states()
        if set(states) != {"active+degraded"}:
            raise RuntimeError(f"after the down-mark the PGs are {states}")

    def _pg_states(self) -> list[str]:
        return [self.cluster.pg_state(g) for g in self._pgs()]

    def _down_store_seqs(self) -> dict[int, int]:
        """The committed transaction count of each down OSD's store."""
        from ceph_tpu.backend.pg_backend import shard_store
        seqs = {}
        for g in self._pgs():
            for osd in self.down:
                if osd in g.acting:
                    seqs[osd] = shard_store(g.bus, osd).committed_seq
        return seqs

    def _say_mix(self) -> None:
        """What the window will read, by data chunks lost: the share of
        gets that decode and the chunks a get recovers follow from it."""
        lost_of = {}
        for g in self._pgs():
            ec = g.backend.ec_impl
            data = {ec.chunk_index(i) for i in range(self.k)}
            lost_of[id(g)] = sum(1 for chunk, osd in enumerate(g.acting)
                                 if osd in self.down and chunk in data)
        lost: dict[int, int] = {}
        for oid, _pi in self.object_set:
            n = lost_of[id(self.cluster.pg_group(self.pool_id, oid))]
            lost[n] = lost.get(n, 0) + 1
        total = len(self.object_set) or 1
        say(f"object set by data chunks lost: {dict(sorted(lost.items()))}; "
            f"{sum(c for n, c in lost.items() if n) / total:.4f} of the gets "
            f"decode, {sum(n * c for n, c in lost.items()) / total:.4f} "
            f"chunks reconstructed a get")

    # -- the window --------------------------------------------------------------

    def window(self, seconds: float, schedule=()):
        perf = self.serving.pipeline.perf
        before = int(perf.get("device_dispatches"))
        ops, errors, t_start = super().window(seconds, schedule)
        self.decoded = int(perf.get("device_dispatches")) - before
        self.gets = sum(1 for o in ops if o.ok)
        return ops, errors, t_start

    def host_spans(self) -> list:
        spans = super().host_spans()
        if spans:
            say(f"  host spans: {len(spans)} in the tracer's ring, the "
                f"oldest {time.perf_counter() - min(s[1] for s in spans):.2f}"
                f" s old")
        return spans

    # -- the comparison ------------------------------------------------------------

    def _check_stored(self, objects, numbers: dict) -> None:
        """All k+m shards of ``objects`` against the reference: the
        reachable ones as ``rados`` reads them, those on down OSDs from
        their intact stores."""
        from ceph_tpu.backend.ecutil import HINFO_KEY
        from ceph_tpu.backend.memstore import GObject
        from ceph_tpu.backend.pg_backend import shard_store
        missing = wrong = crc_wrong = size_wrong = unreachable = 0
        with self.server.lock:
            for oid, pi in objects:
                shards, crcs = self._reference_of(pi)
                g = self.cluster.pg_group(self.pool_id, oid)
                for chunk, osd in enumerate(g.acting):
                    unreachable += osd in g.bus.down
                    try:
                        store = shard_store(g.bus, osd)
                        stored = store.read(GObject(oid, osd))
                        hinfo = store.getattr(GObject(oid, osd), HINFO_KEY)
                    except (KeyError, FileNotFoundError, OSError):
                        missing += 1
                        continue
                    want = shards[chunk]
                    if len(stored) != len(want) or \
                            hinfo.get("total_chunk_size") != len(want):
                        size_wrong += 1
                    if bytes(stored) != want.tobytes():
                        wrong += 1
                    hashes = hinfo.get("cumulative_shard_hashes") or []
                    if len(hashes) != len(crcs) or \
                            int(hashes[chunk]) != int(crcs[chunk]):
                        crc_wrong += 1
        n = len(objects)
        width = self.k + self.m
        n_down = int(self.p["osds_down"])
        numbers["shards_compared"] = (n * width, ">=", width)
        # together exact: every shard is one or the other
        numbers["shards_unreachable"] = (unreachable, ">=", n * n_down)
        numbers["shards_reachable"] = (n * width - unreachable, ">=",
                                       n * (width - n_down))
        numbers["shards_missing"] = (missing, "<=", 0)
        numbers["shard_bytes_wrong"] = (wrong, "<=", 0)
        numbers["shard_sizes_wrong"] = (size_wrong, "<=", 0)
        numbers["stored_crcs_wrong"] = (crc_wrong, "<=", 0)

    def after_window(self) -> dict:
        numbers = super().after_window()
        numbers["gets_decoded_on_device"] = (self.decoded, ">=",
                                             max(1, self.gets // 2))
        with self.server.lock:
            states = self._pg_states()
            down_now = set().union(*(g.bus.down for g in self._pgs()))
            seqs = self._down_store_seqs()
        numbers["pgs_not_active_degraded"] = (
            sum(1 for s in states if s != "active+degraded"), "<=", 0)
        numbers["down_set_changed"] = (int(down_now != set(self.down)),
                                       "<=", 0)
        numbers["down_stores_written"] = (
            sum(1 for osd, seq in seqs.items()
                if seq != self.down_seqs.get(osd)), "<=", 0)
        return numbers
