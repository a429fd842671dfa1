"""Driver ``rados_overwrite``: ``rbd bench --io-type write --io-pattern
rand`` on the served path of ``rados``.

The set-up puts the image — ``object_set`` objects of ``object_bytes``,
the payloads of ``rados`` — through ``TcpRados.put``, then every client
writes one block (the warm-up: the read-modify-write's encode compiles
there and not in the window).  The window's op writes ``io_bytes``
seeded bytes at an ``io_bytes``-aligned offset drawn uniformly, with
replacement, over the image's blocks, through ``TcpRados.write``: each
a partial-stripe write that the EC backend reads, merges, encodes and
writes to all k+m shards, under the cluster lock.

The clients record every acked write: the object, the offset, the
client and its op number (from which the bytes follow, as the payloads
follow from the seed), and its send and ack times.  ``correct`` holds a
plain model to them, outside the timed window: a seeded sample of the
image's objects is read back whole over the wire, and each block must
hold the bytes of an acked write that nothing sent after its ack could
have covered (the last acked, or one in flight beside it), the set-up's
bytes where nothing was written; every shard of those objects, data and
parity, must be the reference encode of what was read, and a crc left in
its HashInfo must be that shard's.
"""
from __future__ import annotations

import random
import sys
import time

import numpy as np

from ..lib import reference, stats
from . import rados, rados_clients
from .rados_clients import POOL


def block_bytes(seed: int, client: int, n: int, size: int) -> bytes:
    """The bytes client ``client`` writes with its ``n``-th write (0 is
    its warm-up): the same in the clients, which send them, and in the
    parent, which holds the model."""
    return np.random.default_rng([seed, 0x0e7e, client, n]).bytes(size)


class Clients(rados_clients.Clients):
    OPS = {**rados_clients.Clients.OPS,
           "overwrite": ("warm_overwrites", "overwrite_op")}

    def __init__(self, port, keyring, traffic, seed):
        super().__init__(port, keyring, traffic, seed)
        self.io = traffic["io_bytes"]
        self.blocks = traffic["object_bytes"] // self.io
        # one block stream a client, drawn in its own order
        self._draws = [random.Random(seed * 1000003 + ci)
                       for ci in range(traffic["clients"])]

    def _write(self, ci: int, n: int) -> tuple:
        """Client ``ci``'s ``n``-th write: (object, offset, client, n)."""
        block = self._draws[ci].randrange(len(self.object_set) * self.blocks)
        oid = self.object_set[block // self.blocks][0]
        offset = block % self.blocks * self.io
        self.clients[ci].write(POOL, oid, block_bytes(self.seed, ci, n,
                                                      self.io), offset)
        return oid, offset, ci, n

    def warm_overwrites(self) -> list[tuple]:
        """One write a client, in turn: (object, offset, client, 0, sent,
        acked)."""
        done = []
        for ci in range(len(self.clients)):
            t0 = time.perf_counter()
            key = self._write(ci, 0)
            done.append(key + (t0, time.perf_counter()))
        return done

    def overwrite_op(self):
        io = self.io

        def overwrite(ci, seq):
            return io, self._write(ci, seq + 1)
        return overwrite


class Driver(rados.Driver):
    clients_class = f"{__name__}.Clients"

    def setup(self) -> None:
        # a program without the served partial write refuses the cell
        # here, before the image is put, and not at the first write
        from ceph_tpu.net import TcpRados
        if not callable(getattr(TcpRados, "write", None)):
            raise RuntimeError("this program's TcpRados has no write(): "
                               "it serves no partial write")
        super().setup()
        # the warm-up's writes, then the window's: (key, sent, acked)
        self.writes = [(tuple(w[:4]), w[4], w[5]) for w in self.acked]

    def window(self, seconds: float, schedule=()):
        perf = self.serving.pipeline.perf
        before = int(perf.get("device_dispatches"))
        ops, errors, t_start = super().window(seconds, schedule)
        self.dispatched = int(perf.get("device_dispatches")) - before
        self.writes.extend((o.key, o.t0, o.t1) for o in ops if o.ok)
        self.window_writes = sum(1 for o in ops if o.ok)
        lat = stats.latencies_ms(ops)
        if lat:
            # the tail's shape, beside the one percentile the line reports
            print("  latency ms p50 %.1f, p90 %.1f, p95 %.1f, p99 %.1f, "
                  "max %.1f" % tuple(stats.percentile(lat, q)
                                     for q in (50, 90, 95, 99, 100)),
                  file=sys.stderr, flush=True)
        return ops, errors, t_start

    # -- the comparison ------------------------------------------------------------

    def _model(self, oid: str, pi: int, got: bytes):
        """The bytes ``oid`` may hold by the acked writes, and how many of
        its written blocks ``got`` holds none of the allowed bytes of.
        A block may hold the bytes of any write to it that was acked
        after the last one sent to it was sent: nothing was sure to
        follow it.  Where ``got`` holds one of those, the model takes
        it; elsewhere the last acked."""
        io = self.t["io_bytes"]
        model = bytearray(self.payloads[pi])
        by_block: dict[int, list] = {}
        for (w_oid, off, ci, n), t0, t1 in self.writes:
            if w_oid == oid:
                by_block.setdefault(off, []).append((t1, t0, ci, n))
        wrong = 0
        for off, ws in by_block.items():
            last_sent = max(t0 for _t1, t0, _ci, _n in ws)
            allowed = [block_bytes(self.seed, ci, n, io)
                       for t1, _t0, ci, n in sorted(ws) if t1 >= last_sent]
            have = got[off:off + io]
            if have in allowed:
                model[off:off + io] = have
            else:
                wrong += 1
                model[off:off + io] = allowed[-1]
        return bytes(model), len(by_block), wrong

    def _check_objects(self, objects, numbers: dict) -> None:
        from ceph_tpu.backend.ecutil import HINFO_KEY
        from ceph_tpu.backend.memstore import GObject
        from ceph_tpu.backend.pg_backend import shard_store
        from ceph_tpu.net import TcpRados
        reader = TcpRados("127.0.0.1", self.server.port, self.keyring)
        try:
            back = [(oid, pi, reader.get(POOL, oid)) for oid, pi in objects]
        finally:
            reader.close()
        width = self.k + self.m
        blocks = wrong_blocks = read_wrong = 0
        missing = wrong = crc_wrong = size_wrong = 0
        for oid, pi, got in back:
            model, written, bad = self._model(oid, pi, got)
            blocks += written
            wrong_blocks += bad
            read_wrong += got != model
            shards = reference.object_shards(
                np.frombuffer(model, dtype=np.uint8), self.k, self.parity,
                self.p["chunk_size"])
            crcs = reference.crc32c_rows(shards)
            with self.server.lock:
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
                    # cleared by an overwrite, or the crc of the shard
                    if hashes and (len(hashes) != width or
                                   int(hashes[chunk]) != int(crcs[chunk])):
                        crc_wrong += 1
        numbers["objects_read_back"] = (len(back), ">=", 1)
        numbers["written_blocks_compared"] = (blocks, ">=", 1)
        numbers["written_blocks_wrong"] = (wrong_blocks, "<=", 0)
        numbers["objects_read_wrong"] = (read_wrong, "<=", 0)
        numbers["shards_compared"] = (len(back) * width, ">=", width)
        numbers["shards_missing"] = (missing, "<=", 0)
        numbers["shard_bytes_wrong"] = (wrong, "<=", 0)
        numbers["shard_sizes_wrong"] = (size_wrong, "<=", 0)
        numbers["stored_crcs_wrong"] = (crc_wrong, "<=", 0)

    def after_window(self) -> dict:
        """{name: (value, comparison, limit)} of everything compared."""
        numbers: dict = {}
        rng = random.Random(self.seed * 7919 + 17)
        image = list(self.object_set)
        cap = self.t["check_objects"]
        sample = image if len(image) <= cap else rng.sample(image, cap)
        self._check_objects(sample, numbers)
        # one device encode an overwrite: each runs alone under the lock
        numbers["overwrites_encoded_on_device"] = (
            self.dispatched, ">=", max(1, self.window_writes))
        perf = self.serving.pipeline.perf
        for name in ("host_fallbacks", "errors", "breaker_state"):
            numbers[f"pipeline_{name}"] = (int(perf.get(name)), "<=", 0)
        dev_err = self.serving.pipeline.last_device_error is not None or \
            self.serving.pipeline.mesh_error is not None
        numbers["pipeline_device_errors"] = (int(dev_err), "<=", 0)
        numbers["ops_never_answered"] = (len(self.stuck), "<=", 0)
        return numbers
