"""What the cluster reads as "another op is waiting" (ISSUE 38).

A drained PG defers its roll-forward kick while another call waits for
the cluster, and the host settles the kicks owed once nobody does
(ISSUE 34).  With a short hold the lock is free more often while calls
are still on their way, so the predicate has to see them there too: a
call a worker took off the dispatch queue before it reaches the lock, a
frame the reactor is still reading, and bytes in a socket that the
reactor has not read yet.  Each case reads False on the parent.  CPU,
tiny sizes.
"""
import socket
import threading
import time

import pytest

from ceph_tpu.cluster import MiniCluster
from ceph_tpu.net import ClusterServer, TcpRados


def _wait(cond, what, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.005)


@pytest.fixture
def served(tmp_path):
    c = MiniCluster(n_osds=3, osds_per_host=1, chunk_size=1024,
                    data_dir=tmp_path)
    serving = c.enable_serving(start=True)
    server = ClusterServer(c)
    server.start()
    r = TcpRados("127.0.0.1", server.port,
                 tmp_path / "client.admin.keyring")
    r.mkpool("p", profile={"k": "2", "m": "1", "device": "numpy"},
             pg_num=1)
    yield server, r
    r.close()
    server.stop()
    serving.stop()
    c.shutdown()


def test_nobody_waits_on_an_idle_server(served):
    server, _r = served
    assert not server._others_waiting()


def test_a_call_between_the_dispatch_queue_and_the_lock_waits(served):
    """The worker has taken the put off the queue and stops in the resend
    check, before the lock: the queue is empty, yet the call waits."""
    server, r = served
    disp = server._transport.dispatcher
    done = threading.Event()
    with server._rpc_cache_lock:
        t = threading.Thread(target=lambda: (r.put("p", "o", b"x" * 100),
                                             done.set()))
        t.start()
        _wait(lambda: server._bound_for_lock == 1,
              "the put never left the dispatch queue")
        assert disp.depth == 0
        assert server._others_waiting()
    t.join(10.0)
    assert done.is_set()
    assert not server._others_waiting()


def test_a_frame_partly_read_waits(served):
    """Part of a frame (here, of the banner) sits in the parser."""
    server, _r = served
    s = socket.create_connection(("127.0.0.1", server.port))
    try:
        s.sendall(b"cep")
        _wait(lambda: any(c.parser.pending()
                          for c in list(server._transport._conns)),
              "the reactor never read the bytes")
        assert server._others_waiting()
    finally:
        s.close()


def test_bytes_the_reactor_has_not_read_wait(served):
    """The reactor is busy elsewhere: what reached the socket waits."""
    server, _r = served
    busy, free = threading.Event(), threading.Event()
    s = socket.create_connection(("127.0.0.1", server.port))
    try:
        _wait(lambda: server._transport.connections() == 2,
              "the connection was never accepted")
        server._transport.reactor.call_soon(
            lambda: (busy.set(), free.wait(10.0)))
        assert busy.wait(10.0)
        assert not server._others_waiting()
        s.sendall(b"cep")
        _wait(server._others_waiting, "the unread bytes were not seen")
    finally:
        free.set()
        s.close()
