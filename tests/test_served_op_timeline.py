"""A served op's timeline from inside the program (ISSUE 25).

A call that reaches ``ClusterServer`` over loopback leaves, under the
client's trace id and on ``time.perf_counter``'s clock, six adjacent
spans — ``msgr.frame_rx``, ``msgr.dispatch_queue_wait``,
``rpc.lock_wait``, ``rpc.<method>``, ``msgr.reply_send``,
``msgr.reply_drain`` — a put a seventh, ``rpc.prepare``, before the
lock wait (ISSUE 29): its ``ec.encode`` and ``ec.hinfo_crc`` (with its
``.wait``) lie there; the lock hold breaks down into ``store.commit``
and the rest, and the ``pipeline.*`` parts are the coalescer's.  CPU,
tiny sizes.
"""
import threading
import time

import numpy as np
import pytest

from ceph_tpu.cluster import MiniCluster
from ceph_tpu.common import instruments
from ceph_tpu.common.tracer import default_tracer
from ceph_tpu.net import ClusterServer, TcpRados

K, M = 2, 1
PROFILE = {"plugin": "jax_rs", "k": str(K), "m": str(M),
           "technique": "cauchy", "device": "jax"}
TRANSPORT = ("msgr.frame_rx", "msgr.dispatch_queue_wait", "rpc.lock_wait",
             "msgr.reply_send", "msgr.reply_drain")
NEW_NAMES = TRANSPORT + ("ec.hinfo_crc", "ec.hinfo_crc.wait", "store.commit",
                         "pipeline.device_wait", "pipeline.fetch",
                         "pipeline.unpack")


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _served(tmp_path, workers=None):
    c = MiniCluster(n_osds=K + M, osds_per_host=1, chunk_size=1024,
                    data_dir=tmp_path, store_backend="bluestore")
    serving = c.enable_serving(start=True)
    server = ClusterServer(c)
    # the context is the process's: the option is read at start() and
    # put back at once, so that no later test file inherits it
    conf = c.cct.conf
    default = conf.get("ms_async_op_threads")
    if workers is not None:
        conf.set("ms_async_op_threads", workers)
    try:
        server.start()
    finally:
        conf.set("ms_async_op_threads", default)
    return c, serving, server


@pytest.fixture
def served(tmp_path):
    c, serving, server = _served(tmp_path)
    r = TcpRados("127.0.0.1", server.port,
                 tmp_path / "client.admin.keyring")
    r.mkpool("p", profile=dict(PROFILE), pg_num=4)
    r.put("p", "warm", _data(8192, 9))       # compiles outside the tests
    r.get("p", "warm")
    yield c, serving, server, r
    r.close()
    server.stop()
    serving.stop()
    c.shutdown()


def _traced(fn):
    """Run ``fn`` under a fresh client trace; returns the trace's ``X``
    events by start time once the reply's send and drain have both been
    recorded (the worker and the reactor stamp them as the client
    wakes)."""
    tr = default_tracer()
    ctx = tr.new_trace("client")
    with tr.activate(ctx):
        fn()
    deadline = time.monotonic() + 5.0
    while True:
        evs = [e for e in tr.dump(stitched=False)["traceEvents"]
               if e.get("ph") == "X"
               and e.get("args", {}).get("trace_id") == ctx.trace_id]
        names = {e["name"] for e in evs}
        if {"msgr.reply_send", "msgr.reply_drain"} <= names \
                or time.monotonic() > deadline:
            return sorted(evs, key=lambda e: e["ts"])
        time.sleep(0.01)


def _one(evs, name):
    found = [e for e in evs if e["name"] == name]
    assert len(found) == 1, (name, [e["name"] for e in evs])
    return found[0]


@pytest.mark.parametrize("method", ["put", "get"])
def test_a_call_leaves_six_adjacent_spans_of_its_trace(served, method):
    _c, _serving, _server, r = served
    payload = _data(8192, 1)
    r.put("p", "obj", payload)
    if method == "put":
        evs = _traced(lambda: r.put("p", "obj2", payload))
    else:
        evs = _traced(lambda: r.get("p", "obj"))
    order = list(TRANSPORT)
    order.insert(3, f"rpc.{method}")
    if method == "put":
        # the codec work ahead of the lock (ISSUE 29): dequeue -> the
        # lock is asked for
        order.insert(2, "rpc.prepare")
    else:
        assert not [e for e in evs if e["name"] == "rpc.prepare"]
    line = [_one(evs, name) for name in order]
    client = _one(evs, "client.rpc")
    end = client["ts"]
    for e in line:
        # on the client's clock, after the one before it (1 us of float
        # rounding), inside the client's own span
        assert e["ts"] >= end - 1.0, (e["name"], e["ts"], end)
        end = e["ts"] + e["dur"]
        assert e["cat"] != "client"
        assert e["args"]["parent_span_id"] == client["args"]["span_id"]
    # the server's spans lie inside the client's own; the reactor stamps
    # the drain's end as the client already wakes, so in one process the
    # two threads' hand-over of the interpreter may run past it
    assert sum(e["dur"] for e in line[:-1]) <= client["dur"]
    assert line[-1]["ts"] <= client["ts"] + client["dur"]
    assert sum(e["dur"] for e in line) <= client["dur"] + 20e3


def test_holding_the_cluster_lock_shows_as_lock_wait_alone(served):
    _c, _serving, server, r = served
    payload = _data(8192, 2)
    got = {}
    with server.lock:
        t = threading.Thread(
            target=lambda: got.setdefault(
                "evs", _traced(lambda: r.put("p", "held", payload))))
        t.start()
        time.sleep(0.3)                  # the call is at the lock by now
    t.join(10.0)
    evs = got["evs"]
    assert _one(evs, "rpc.lock_wait")["dur"] >= 50e3
    assert _one(evs, "msgr.dispatch_queue_wait")["dur"] < 50e3


def test_one_worker_and_a_slow_call_show_as_queue_wait(tmp_path):
    c, serving, server = _served(tmp_path, workers=1)
    server._rpc_nap = lambda ch, s: time.sleep(s)
    keyring = tmp_path / "client.admin.keyring"
    a = TcpRados("127.0.0.1", server.port, keyring)
    b = TcpRados("127.0.0.1", server.port, keyring)
    got = {}
    try:
        a.call("ping")
        b.call("ping")
        with server.lock:                # the one worker stops at the lock
            ta = threading.Thread(target=lambda: got.setdefault(
                "a", _traced(lambda: a.call("nap", s=0.1))))
            ta.start()
            time.sleep(0.2)
            tb = threading.Thread(target=lambda: got.setdefault(
                "b", _traced(lambda: b.call("ping"))))
            tb.start()
            time.sleep(0.2)              # b's call is queued behind a's
        ta.join(10.0)
        tb.join(10.0)
    finally:
        a.close()
        b.close()
        server.stop()
        serving.stop()
        c.shutdown()
    hold = _one(got["a"], "rpc.nap")["dur"]
    assert hold >= 100e3
    assert _one(got["b"], "msgr.dispatch_queue_wait")["dur"] >= hold
    assert _one(got["b"], "rpc.lock_wait")["dur"] < 50e3


def test_a_put_breaks_down_into_crc_and_store_commits(served):
    _c, _serving, _server, r = served
    evs = _traced(lambda: r.put("p", "parts", _data(8192, 3)))
    crc = _one(evs, "ec.hinfo_crc")
    wait = _one(evs, "ec.hinfo_crc.wait")
    assert crc["args"]["rows"] == K + M
    assert crc["args"]["bytes"] == 8192 // K * (K + M)
    assert crc["ts"] <= wait["ts"]
    assert wait["ts"] + wait["dur"] <= crc["ts"] + crc["dur"] + 1.0
    assert wait["args"]["parent_span_id"] == crc["args"]["span_id"]
    hold = _one(evs, "rpc.put")
    # every shard's store commits twice a put: the sub-write's
    # transaction, then the roll-forward's
    by_id = {e["args"]["span_id"]: e for e in evs}
    commits = [e for e in evs if e["name"] == "store.commit"]
    parents = [by_id[e["args"]["parent_span_id"]]["name"] for e in commits]
    assert parents.count("osd.ECSubWrite") == K + M
    assert parents.count("osd.RollForward") == K + M
    assert len(commits) == 2 * (K + M)
    for e in commits:
        assert hold["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= hold["ts"] + hold["dur"] + 1.0
    # the crc (and the encode) ran BEFORE the hold (ISSUE 29), in the
    # prepare, under the first of the put's two pg.generate_transactions
    prepare = _one(evs, "rpc.prepare")
    assert prepare["ts"] + prepare["dur"] <= hold["ts"] + 1.0
    txns = [e for e in evs if e["name"] == "pg.generate_transactions"]
    assert len(txns) == 2
    assert by_id[crc["args"]["parent_span_id"]] is txns[0]
    assert by_id[_one(evs, "ec.encode")["args"]["parent_span_id"]] \
        is txns[0]
    assert prepare["ts"] <= txns[0]["ts"]
    assert txns[0]["ts"] + txns[0]["dur"] <= \
        prepare["ts"] + prepare["dur"] + 1.0
    assert hold["ts"] <= txns[1]["ts"]


def test_pipeline_complete_breaks_down_into_wait_fetch_unpack(served):
    """The coalescer's thread completes the batch, outside the op's
    trace: the parts are found by thread and time inside their
    ``pipeline.complete``."""
    _c, _serving, _server, r = served
    tr = default_tracer()
    tr.reset()
    r.put("p", "piped", _data(8192, 7))
    evs = [e for e in tr.dump(stitched=False)["traceEvents"]
           if e.get("ph") == "X"]
    done = _one(evs, "pipeline.complete")
    end = done["ts"]
    for name in ("pipeline.device_wait", "pipeline.fetch",
                 "pipeline.unpack"):
        part = _one(evs, name)
        assert part["tid"] == done["tid"]
        assert part["ts"] >= end - 1.0
        end = part["ts"] + part["dur"]
    assert end <= done["ts"] + done["dur"] + 1.0


def test_device_dispatches_counts_what_reached_the_device(served):
    _c, serving, _server, r = served
    perf = serving.pipeline.perf
    r.put("p", "counted", _data(8192, 4))
    before = perf.get("device_dispatches"), perf.get("submitted")
    r.get("p", "counted")                # clean read: host-only decode
    assert perf.get("device_dispatches") == before[0]
    assert perf.get("submitted") == before[1] + 1
    r.put("p", "counted2", _data(8192, 5))
    assert perf.get("device_dispatches") == before[0] + 1


def test_no_new_span_while_the_instruments_are_off(served):
    _c, _serving, _server, r = served
    tr = default_tracer()
    payload = _data(8192, 6)
    with instruments.disabled():
        tr.reset()
        r.put("p", "quiet", payload)
        assert r.get("p", "quiet") == payload
        time.sleep(0.05)                 # the reactor's last drain
        names = set(tr.histograms())
    assert not names & set(NEW_NAMES), names
    r.put("p", "loud", payload)
    time.sleep(0.05)
    assert set(TRANSPORT) <= set(tr.histograms())
