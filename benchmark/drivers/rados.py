"""Driver ``rados``: the served path over the wire.

One process holds the chip and the whole deployment: a ``MiniCluster``
on bluestore under the run's own fresh directory, the serving engine and
a ``ClusterServer`` on loopback.  The N ``TcpRados`` clients run in a
process of their own (``rados_clients.py``).  The set-up and the client
fan-out are ``chip_smoke.py``'s ``phase_served`` (PR 21), copied so no
later PR can change them.

What the window drives is ``TcpRados.put`` / ``TcpRados.get``.  What
``correct`` compares is what those ops left in the stores and returned:
every shard of a seeded sample of the window's objects (data AND
parity, byte for byte) and the crcs in their HashInfo, against
``lib/reference.py``; bytes read back over the wire; the pipeline's
fallback counters.
"""
from __future__ import annotations

import random
import time
from pathlib import Path

import numpy as np

from ..lib import reference
from ..lib.stats import Op
from . import rados_clients
from .rados_clients import POOL


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 run_dir: Path, control: str | None = None):
        self.p = dict(config["driver_params"])
        self.t = dict(traffic)
        self.seed = seed
        self.run_dir = Path(run_dir)
        self.control = control
        self.clients = None
        self.cluster = self.serving = self.server = None
        self.acked: list[tuple[str, int]] = []     # (oid, payload index)
        self.stuck: list[int] = []
        self.k = int(self.p["profile"]["k"])
        self.m = int(self.p["profile"]["m"])

    # -- set-up ----------------------------------------------------------------

    def setup(self) -> None:
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.net import ClusterServer, TcpRados
        p, t = self.p, self.t
        data_dir = self.run_dir / "cluster"
        self.cluster = c = MiniCluster(
            n_osds=p["n_osds"], osds_per_host=p["osds_per_host"],
            chunk_size=p["chunk_size"], data_dir=data_dir,
            store_backend=p["store_backend"])
        self.serving = c.enable_serving(start=True)
        self.server = ClusterServer(c)
        self.server.start()
        self.keyring = data_dir / "client.admin.keyring"
        admin = TcpRados("127.0.0.1", self.server.port, self.keyring)
        try:
            self.pool_id = admin.mkpool(POOL, profile=dict(p["profile"]),
                                        pg_num=p["pg_num"])
        finally:
            admin.close()
        if self.control == "osd_down":
            self._mark_one_osd_down()
        elif self.control is not None:
            raise ValueError(f"driver rados has no control {self.control!r}")

        self.payloads, self.prefix = rados_clients.make_payloads(self.seed, t)
        self._expected: dict[int, tuple] = {}
        self.parity = reference.cauchy_parity_matrix(self.k, self.m)
        if p["profile"]["technique"] != "cauchy":
            raise ValueError("the reference knows technique=cauchy only")
        self.clients = rados_clients.ClientProcess(
            self.server.port, str(self.keyring), t, self.seed)
        made = self.clients.setup()
        self.object_set = [tuple(o) for o in made["object_set"]]
        self.acked = [tuple(o) for o in made["acked"]]
        self.reads = (0, 0)

    def _mark_one_osd_down(self) -> None:
        """The control: the program's own degraded path.  With one
        non-primary OSD down a put is acked with k+m-1 shards stored,
        which breaks the guarantee the configuration states (acked after
        all k+m shards are committed)."""
        pgs = list(self.cluster.pools[self.pool_id]["pgs"].values())
        primaries = {g.backend.whoami for g in pgs}
        osd = next(o for o in range(self.p["n_osds"]) if o not in primaries)
        with self.server.lock:
            for g in pgs:
                if osd in g.acting:
                    g.bus.mark_down(osd)

    # -- the window --------------------------------------------------------------

    def window(self, seconds: float, schedule=()):
        got = self.clients.window(seconds, schedule)
        ops = [Op(*row[:5], tuple(row[5]) if row[5] is not None else None)
               for row in got["ops"]]
        self.acked.extend(o.key for o in ops if o.ok and o.key is not None)
        self.stuck = got["stuck"]
        self.reads = (got["reads_compared"], got["reads_wrong"])
        return ops, got["errors"], got["t_start"]

    # -- what the traced run reads -------------------------------------------------

    def snapshot(self) -> dict:
        """Span sums, server-side rpc sums and perf counters, now."""
        from ceph_tpu.common.tracer import default_tracer
        hist = default_tracer().histograms()
        spans = {name: (h["sum"], h["count"]) for name, h in hist.items()}
        rpc = {m: (row["sum_s"], row["count"])
               for m, row in self.server.wire.rpc_methods().items()}
        counters = {
            name: {k: v for k, v in vals.items()
                   if isinstance(v, (int, float))}
            for name, vals in self.cluster.cct.perf.perf_dump().items()}
        return {"spans": spans, "rpc": rpc, "counters": counters}

    def host_spans(self) -> list:
        """The tracer's finished spans as (name, start, end) on
        ``time.perf_counter``'s clock, client-side ones left out."""
        from ceph_tpu.common.tracer import default_tracer
        tr = default_tracer()
        mark = time.perf_counter()
        tr.observe("bench.clock_anchor", mark, mark)
        events = tr.dump(stitched=False)["traceEvents"]
        anchor = next((e for e in reversed(events)
                       if e.get("name") == "bench.clock_anchor"), None)
        if anchor is None:
            return []
        origin = mark - anchor["ts"] / 1e6
        return [(e["name"], origin + e["ts"] / 1e6,
                 origin + (e["ts"] + e["dur"]) / 1e6)
                for e in events
                if e.get("ph") == "X" and e.get("cat") != "client"
                and e["name"] != "bench.clock_anchor"]

    # -- the comparison ------------------------------------------------------------

    def _reference_of(self, pi: int):
        """(shards [k+m, L], crcs [k+m]) the reference gives payload
        ``pi``: computed once per distinct payload."""
        got = self._expected.get(pi)
        if got is None:
            shards = reference.object_shards(
                np.frombuffer(self.payloads[pi], dtype=np.uint8),
                self.k, self.parity, self.p["chunk_size"])
            got = self._expected[pi] = (shards,
                                        reference.crc32c_rows(shards))
        return got

    def _check_stored(self, objects, numbers: dict) -> None:
        """Every shard of ``objects`` as the stores hold it, and the
        HashInfo beside it, against the reference."""
        from ceph_tpu.backend.ecutil import HINFO_KEY
        from ceph_tpu.backend.memstore import GObject
        from ceph_tpu.backend.pg_backend import shard_store
        missing = wrong = crc_wrong = size_wrong = 0
        with self.server.lock:
            for oid, pi in objects:
                shards, crcs = self._reference_of(pi)
                g = self.cluster.pg_group(self.pool_id, oid)
                for chunk, osd in enumerate(g.acting):
                    try:
                        if osd in g.bus.down:
                            raise KeyError(osd)
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
        numbers["shards_compared"] = (len(objects) * (self.k + self.m),
                                      ">=", self.k + self.m)
        numbers["shards_missing"] = (missing, "<=", 0)
        numbers["shard_bytes_wrong"] = (wrong, "<=", 0)
        numbers["shard_sizes_wrong"] = (size_wrong, "<=", 0)
        numbers["stored_crcs_wrong"] = (crc_wrong, "<=", 0)

    def after_window(self) -> dict:
        """{name: (value, comparison, limit)} of everything compared."""
        t = self.t
        numbers: dict = {}
        rng = random.Random(self.seed * 7919 + 17)
        stored = list(self.object_set) + list(self.acked)
        cap = t["check_objects"]
        sample = stored if len(stored) <= cap else rng.sample(stored, cap)
        if self.acked and self.acked[-1] not in sample:
            sample.append(self.acked[-1])
        self._check_stored(sample, numbers)

        # bytes back over the wire: the reads the window itself returned
        # (compared by the clients, which hold them), and a seeded sample
        # of its puts read back now
        compared, wrong = self.reads
        n_back = t["check_readback"]
        back = self.acked if len(self.acked) <= n_back \
            else rng.sample(self.acked, n_back)
        if back:
            more, bad = self.clients.readback(back)
            compared, wrong = compared + more, wrong + bad
        numbers["reads_compared"] = (compared, ">=", 1)
        numbers["reads_wrong"] = (wrong, "<=", 0)

        perf = self.serving.pipeline.perf
        for name in ("host_fallbacks", "errors", "breaker_state"):
            numbers[f"pipeline_{name}"] = (int(perf.get(name)), "<=", 0)
        numbers["pipeline_submitted"] = (int(perf.get("submitted")), ">=", 1)
        dev_err = self.serving.pipeline.last_device_error is not None or \
            self.serving.pipeline.mesh_error is not None
        numbers["pipeline_device_errors"] = (int(dev_err), "<=", 0)
        numbers["ops_never_answered"] = (len(self.stuck), "<=", 0)
        return numbers

    def close(self) -> None:
        if self.clients is not None:
            self.clients.close()
        self.clients = None
        if self.server is not None:
            self.server.stop()
        if self.serving is not None:
            self.serving.stop()
        if self.cluster is not None:
            self.cluster.shutdown()
        self.server = self.serving = self.cluster = None
