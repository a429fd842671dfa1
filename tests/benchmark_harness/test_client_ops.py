"""A driver module brings a client op of its own, with no file that is
there edited: the clients take the window's op (and its warm-up) from a
table keyed by the traffic's ``op``, and ``rados.Driver`` hands the
clients' process the class to build by a dotted path.  The ``put`` and
``get`` the cells run issue the same calls on the same names and
payloads, in the same order, as before the table.

Counts and correctness only: nothing here is a rate of the device."""
import hashlib
import itertools
import json
import os
import random
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark.drivers import rados, rados_clients, rados_degraded  # noqa: E402
from benchmark.lib.loadgen import Reservoir  # noqa: E402

SEED = 2147484077


class _Recorder:
    """A ``TcpRados`` that records the calls made on it."""

    def __init__(self, calls, stored):
        self.calls, self.stored = calls, stored

    def put(self, pool, oid, data):
        self.calls.append(("put", pool, oid, data))
        self.stored[oid] = data

    def get(self, pool, oid):
        self.calls.append(("get", pool, oid))
        return self.stored[oid]


def _clients(op, n=3, object_set=()):
    """A :class:`rados_clients.Clients` of ``n`` recording connections,
    made as ``__init__`` makes it but for the sockets."""
    t = {"op": op, "clients": n, "payloads_per_client": 2,
         "object_bytes": 64, "keep_reads_per_client": 4}
    c = rados_clients.Clients.__new__(rados_clients.Clients)
    c.t, c.seed = t, SEED
    c.payloads, c.prefix = rados_clients.make_payloads(SEED, t)
    c.calls, stored = [], {}
    c.clients = [_Recorder(c.calls, stored) for _ in range(n)]
    c.object_set = list(object_set)
    for oid, pi in c.object_set:
        stored[oid] = c.payloads[pi]
    c._read_seq = itertools.count()
    c._kept = [Reservoir(4, random.Random(ci))
               for ci in range(n)] if op == "get" else []
    return c


def test_the_put_op_names_and_payloads_are_what_they_were():
    c = _clients("put")
    warm = c.setup()["acked"]
    assert warm == [(f"{c.prefix}.warm.{ci:02d}", ci) for ci in range(3)]
    op = c._op()
    assert op(1, 3) == (64, (f"{c.prefix}.01.0000003", 3))
    assert op(2, 4) == (64, (f"{c.prefix}.02.0000004", 4))
    assert c.calls == [
        ("put", "bench", f"{c.prefix}.warm.00", c.payloads[0]),
        ("put", "bench", f"{c.prefix}.warm.01", c.payloads[1]),
        ("put", "bench", f"{c.prefix}.warm.02", c.payloads[2]),
        ("put", "bench", f"{c.prefix}.01.0000003", c.payloads[3]),
        ("put", "bench", f"{c.prefix}.02.0000004", c.payloads[4])]


def test_the_get_op_reads_the_object_set_in_order():
    objects = [(f"x.set.{j:05d}", j % 6) for j in range(4)]
    c = _clients("get", object_set=objects)
    assert c.setup()["acked"] == []
    # the warm pass read every object once, each client its share
    assert sorted(call[2] for call in c.calls) == [o for o, _ in objects]
    del c.calls[:]
    op = c._op()
    assert [op(ci, 0)[0] for ci in (0, 1, 2, 0, 1)] == [64] * 5
    assert [call[2] for call in c.calls] == \
        [objects[j % 4][0] for j in range(5)]


def test_an_op_the_table_lacks_is_refused():
    c = _clients("nope")
    with pytest.raises(ValueError, match="no op 'nope'"):
        c._op()
    with pytest.raises(ValueError, match="no op 'nope'"):
        c.setup()
    assert c.calls == []


def test_the_clients_class_is_handed_by_a_dotted_path():
    assert rados_clients.CLIENTS == "benchmark.drivers.rados_clients.Clients"
    assert rados_clients.load_class(rados_clients.CLIENTS) \
        is rados_clients.Clients
    assert rados.Driver.clients_class == rados_clients.CLIENTS
    assert rados_degraded.Driver.clients_class == rados_clients.CLIENTS
    assert set(rados_clients.Clients.OPS) == {"put", "get"}


# -- a driver module of a later PR, in a copy of the benchmark ----------------

DRIVER = '''"""Driver ``rados_put_stat``: the write path of ``rados``, each put
followed by a stat of the object it stored."""
import sys
import threading

from . import rados, rados_clients
from .rados_clients import POOL


class Clients(rados_clients.Clients):
    OPS = {**rados_clients.Clients.OPS,
           "put_stat": ("warm_puts", "put_stat_op")}

    def put_stat_op(self):
        put, clients = self.put_op(), self.clients
        self.stats, lock = [0], threading.Lock()

        def put_stat(ci, seq):
            nbytes, key = put(ci, seq)
            size = clients[ci].stat(POOL, key[0])[0]
            if size != nbytes:
                raise RuntimeError(f"stat of {key[0]}: {size} for {nbytes}")
            with lock:
                self.stats[0] += 1
            return nbytes, key
        return put_stat

    def window(self, seconds, on_start=None):
        got = super().window(seconds, on_start)
        print(f"put_stat: {self.stats[0]} stats", file=sys.stderr,
              flush=True)
        return got


class Driver(rados.Driver):
    clients_class = f"{__name__}.Clients"
'''
CELL = "rados_put_stat_64k"


def _hashes(root):
    return {f.relative_to(root).as_posix():
            hashlib.sha256(f.read_bytes()).hexdigest()
            for f in sorted(root.rglob("*"))
            if f.is_file() and "__pycache__" not in f.parts}


def test_a_driver_module_in_a_copy_brings_a_client_op(tmp_path):
    """A copy of ``benchmark/`` and ``BENCHMARK.json`` gains a driver
    module whose clients add ``put_stat``, a configuration naming it, a
    traffic mix whose op it is and the entries; the copy's own
    ``run.py`` rehearses the cell to `correct`, every op a put and a
    stat, and no file the copy had is changed."""
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(tmp_path / "benchmark")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp_path / "benchmark/drivers/rados_put_stat.py").write_text(DRIVER)
    cfg = json.loads((REPO / "benchmark/configs/rados_bench_ec84_64k.json")
                     .read_text())
    cfg.update(name="rados_bench_ec84_64k_stat", driver="rados_put_stat")
    (tmp_path / "benchmark/configs/rados_bench_ec84_64k_stat.json"
     ).write_text(json.dumps(cfg))
    mix = json.loads((REPO / "benchmark/traffic/write_64k_qd64.json")
                     .read_text())
    mix.update(name="write_64k_stat", op="put_stat")
    (tmp_path / "benchmark/traffic/write_64k_stat.json").write_text(
        json.dumps(mix))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "rados_bench_ec84_64k")
    bench["configs"].append({**entry, "name": cfg["name"],
                             "file": "benchmark/configs/"
                                     "rados_bench_ec84_64k_stat.json"})
    bench["workloads"].append({"name": CELL, "config": cfg["name"],
                               "traffic": "write_64k_stat", "chips": 1,
                               "why": "a put and a stat of it"})
    for m in bench["end_to_end"]:
        if "rados_write_64k_qd64" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))

    env = {**os.environ, "PYTHONPATH": str(REPO)}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(SEED), "--seconds", "1", "--trace", "0", "--rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL {"), done.stdout[-2000:]
    res = json.loads(last[len("REHEARSAL "):])
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"client_bw", "op_lat_p95", "setup_s"}
    # the module the copy's run loaded is the copy's: every window op
    # was a put and a stat, as the subclass counted in its own process
    stats = [int(n) for n in re.findall(r"put_stat: (\d+) stats",
                                        done.stderr)]
    assert stats == [res["attempted"]]
    after = _hashes(tmp_path / "benchmark")
    assert {f: after.get(f) for f in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/rados_bench_ec84_64k_stat.json",
        "drivers/rados_put_stat.py", "traffic/write_64k_stat.json"]
