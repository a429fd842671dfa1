"""The client side of driver ``rados``: N ``TcpRados`` connections, the
seeded payload pool, the put and get operations of a traffic mix, and
the closed loop over them.

It runs in a process of its own (``spawn``), which never initialises a
JAX backend: the parent holds the chip, the cluster and the server, and
the load generator does not share the server's interpreter lock.  With
the clients as threads of the server's process the read cell measured
the generator as much as the server: 300 MiB/s against 450 with the
clients apart (PERF.md, findings of PR 24).

Times are ``time.perf_counter`` (CLOCK_MONOTONIC), one clock for both
processes of a machine.
"""
from __future__ import annotations

import itertools
import os
import random
import threading

POOL = "bench"


def make_payloads(seed: int, traffic: dict):
    """(payloads, name prefix) a seed gives a mix: the same in the
    parent, which needs them for the reference, and in the clients."""
    import numpy as np
    rng = np.random.default_rng([seed, 0x0b1ec7])
    n = traffic["clients"] * traffic["payloads_per_client"]
    payloads = [rng.integers(0, 256, traffic["object_bytes"],
                             dtype=np.uint8).tobytes() for _ in range(n)]
    return payloads, f"s{int(rng.integers(0, 1 << 32)):08x}"


class Clients:
    def __init__(self, port: int, keyring: str, traffic: dict, seed: int):
        from ceph_tpu.net import TcpRados
        from ..lib.loadgen import Reservoir
        self.t = traffic
        self.seed = seed
        self.payloads, self.prefix = make_payloads(seed, traffic)
        self.clients = [TcpRados("127.0.0.1", port, keyring)
                        for _ in range(traffic["clients"])]
        self.object_set: list[tuple[str, int]] = []
        self._read_seq = itertools.count()
        # a seeded sample of what the window's gets return, per client
        self._kept = [Reservoir(traffic["keep_reads_per_client"],
                                random.Random(seed * 1000003 + ci))
                      for ci in range(traffic["clients"])] \
            if traffic["op"] == "get" else []

    def setup(self) -> dict:
        """The object set a read mix reads, put through the same path,
        then one warm op per client through every shape the window
        uses."""
        t, payloads = self.t, self.payloads
        n_set = t.get("object_set", 0)
        if n_set:
            names = [(f"{self.prefix}.set.{j:05d}", j % len(payloads))
                     for j in range(n_set)]
            nxt = itertools.count()

            def put_set(ci):
                while True:
                    j = next(nxt)
                    if j >= len(names):
                        return
                    oid, pi = names[j]
                    self.clients[ci].put(POOL, oid, payloads[pi])
            self._all_clients(put_set)
            self.object_set = names
        acked = []
        for ci, r in enumerate(self.clients):
            pi = ci % len(payloads)
            if t["op"] == "put":
                oid = f"{self.prefix}.warm.{ci:02d}"
                r.put(POOL, oid, payloads[pi])
                acked.append((oid, pi))
        if t["op"] == "get":
            # one pass over the whole object set, shared by the clients:
            # the window re-reads the set many times over, so whatever
            # the first read of an object fills (the stores' caches)
            # fills here and not in the window's first second
            self._all_clients(lambda ci: self._warm_reads(ci))
        return {"acked": acked, "object_set": self.object_set}

    def _warm_reads(self, ci: int) -> None:
        for oid, pi in self.object_set[ci::len(self.clients)]:
            if self.clients[ci].get(POOL, oid) != self.payloads[pi]:
                raise RuntimeError(f"warm read of {oid} differs")

    def _all_clients(self, fn) -> None:
        """Set-up work: ``fn(client index)`` on a thread per client; the
        first failure is re-raised here."""
        errors: list = []

        def run(ci):
            try:
                fn(ci)
            except Exception as e:            # noqa: BLE001 — re-raised below
                errors.append(e)
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(self.clients))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
            if th.is_alive():
                raise RuntimeError("set-up still running after 600 s")
        if errors:
            raise errors[0]

    def _op(self):
        t = self.t
        payloads, clients, prefix = self.payloads, self.clients, self.prefix
        per = t["payloads_per_client"]
        if t["op"] == "put":
            def put(ci, seq):
                pi = ci * per + seq % per
                oid = f"{prefix}.{ci:02d}.{seq:07d}"
                clients[ci].put(POOL, oid, payloads[pi])
                return len(payloads[pi]), (oid, pi)
            return put
        if t["op"] == "get":
            objects, nxt, kept = self.object_set, self._read_seq, self._kept

            def get(ci, _seq):
                oid, pi = objects[next(nxt) % len(objects)]
                data = clients[ci].get(POOL, oid)
                kept[ci].offer((oid, pi, data))
                return len(data), None
            return get
        raise ValueError(f"driver rados has no op {t['op']!r}")

    def window(self, seconds: float, on_start=None) -> dict:
        from ..lib.loadgen import closed_loop
        ops, errors, stuck, t_start = closed_loop(
            self.t["clients"], seconds, self._op(), on_start=on_start)
        # the reads the window itself returned, a seeded sample of them
        compared = wrong = 0
        for res in self._kept:
            for _oid, pi, data in res.sample():
                compared += 1
                wrong += data != self.payloads[pi]
        return {"ops": [(o.t0, o.t1, o.nbytes, o.ok, o.client, o.key)
                        for o in ops],
                "errors": errors, "stuck": stuck, "t_start": t_start,
                "reads_compared": compared, "reads_wrong": wrong}

    def readback(self, objects) -> tuple[int, int]:
        """Read ``objects`` back over the wire now: (compared, wrong);
        an acked object that cannot be read is wrong."""
        wrong = 0
        for i, (oid, pi) in enumerate(objects):
            try:
                wrong += self.clients[i % len(self.clients)].get(
                    POOL, oid) != self.payloads[pi]
            except Exception:                 # noqa: BLE001 — acked, unreadable
                wrong += 1
        return len(objects), wrong

    def close(self) -> None:
        for r in self.clients:
            try:
                r.close()
            except Exception:                 # noqa: BLE001 — closing anyway
                pass
        self.clients = []


def serve(conn, port: int, keyring: str, traffic: dict, seed: int) -> None:
    """The child's main: build the clients, then answer the parent's
    requests over ``conn`` until told to close."""
    os.environ["JAX_PLATFORMS"] = "cpu"       # never the parent's chip
    import gc
    from ..lib import allocator
    allocator.pin()
    clients = None
    try:
        clients = Clients(port, keyring, traffic, seed)
        while True:
            msg = conn.recv()
            what = msg[0]
            if what == "setup":
                out = clients.setup()
                gc.collect()
                gc.freeze()
                conn.send(("ok", out))
            elif what == "window":
                conn.send(("ok", clients.window(
                    msg[1], on_start=lambda t0: conn.send(("started", t0)))))
            elif what == "readback":
                conn.send(("ok", clients.readback(msg[1])))
            elif what == "close":
                conn.send(("ok", None))
                return
            else:
                conn.send(("error", f"unknown request {what!r}"))
    except EOFError:
        pass
    except Exception as e:                    # noqa: BLE001 — told to the parent
        import traceback
        try:
            conn.send(("error", f"{type(e).__name__}: {e}\n"
                                f"{traceback.format_exc()[-1500:]}"))
        except OSError:
            pass
    finally:
        if clients is not None:
            clients.close()
        conn.close()


class ClientProcess:
    """The parent's handle on the clients' process: the same three calls
    as :class:`Clients`."""

    def __init__(self, port: int, keyring: str, traffic: dict, seed: int):
        import multiprocessing
        ctx = multiprocessing.get_context("spawn")
        self.conn, child = ctx.Pipe()
        self.proc = ctx.Process(target=serve, name="bench-clients",
                                args=(child, port, keyring, traffic, seed))
        self.proc.start()
        child.close()

    def _answer(self, timeout: float):
        if not self.conn.poll(timeout):
            raise RuntimeError(f"the clients' process did not answer in "
                               f"{timeout:.0f} s")
        try:
            kind, body = self.conn.recv()
        except EOFError:
            raise RuntimeError("the clients' process ended "
                               f"(exit code {self.proc.exitcode})") from None
        if kind == "error":
            raise RuntimeError(f"the clients' process failed: {body}")
        return kind, body

    def setup(self) -> dict:
        self.conn.send(("setup",))
        return self._answer(900)[1]

    def window(self, seconds: float, schedule=()) -> dict:
        """The clients' window; ``schedule`` runs here, in the parent,
        at its offsets from the clients' common start."""
        from ..lib.loadgen import run_schedule
        self.conn.send(("window", seconds))
        kind, t_start = self._answer(120)
        if kind != "started":
            raise RuntimeError(f"expected 'started', got {kind!r}")
        run_schedule(t_start, schedule)
        return self._answer(seconds + 400)[1]

    def readback(self, objects) -> tuple[int, int]:
        self.conn.send(("readback", list(objects)))
        return tuple(self._answer(600)[1])

    def close(self) -> None:
        try:
            if self.proc.is_alive():
                self.conn.send(("close",))
                self._answer(30)
        except (RuntimeError, OSError):
            pass
        self.proc.join(timeout=30)
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(timeout=30)
        self.conn.close()
