"""Common runtime services (SURVEY.md §5): typed config + observers, perf
counters, ring log, admin socket, op tracker, and their wiring into the EC
backend.  Mirrors the reference's config/perf behaviors
(src/common/options.cc schema typing, src/common/config.cc observers,
src/common/perf_counters.h avg dumps, src/log/Log.cc recent-ring dump)."""
import io
import json

import numpy as np
import pytest

from ceph_tpu.common import (AdminSocket, ConfigProxy, Context, Log, Option,
                             OpTracker, PerfCountersBuilder,
                             PerfCountersCollection, parse_size, SCHEMA,
                             TYPE_BOOL, TYPE_SIZE, TYPE_UINT)


class TestOptions:
    def test_typed_defaults(self):
        conf = ConfigProxy()
        assert conf.get("osd_pool_default_size") == 3
        assert conf.get("osd_recovery_max_chunk") == 8 << 20
        assert isinstance(conf.get("osd_erasure_code_plugins"), str)

    def test_size_parsing(self):
        assert parse_size("4K") == 4096
        assert parse_size("1m") == 1 << 20
        assert parse_size("2G") == 2 << 30
        assert parse_size(512) == 512
        conf = ConfigProxy()
        conf.set("osd_recovery_max_chunk", "16M")
        assert conf.get("osd_recovery_max_chunk") == 16 << 20

    def test_bounds_and_unknown_rejected(self):
        conf = ConfigProxy()
        with pytest.raises(ValueError):
            conf.set("osd_heartbeat_interval", 0)       # min=1
        with pytest.raises(ValueError):
            conf.set("debug_osd", 99)                   # max=20
        with pytest.raises(KeyError):
            conf.set("no_such_option", 1)
        with pytest.raises(ValueError):
            conf.set("osd_pool_default_size", -1)       # uint

    def test_startup_flag_blocks_runtime_update(self):
        conf = ConfigProxy()
        with pytest.raises(ValueError):
            conf.set("erasure_code_dir", "/elsewhere")
        conf2 = ConfigProxy({"erasure_code_dir": "/plugins"})  # startup ok
        assert conf2.get("erasure_code_dir") == "/plugins"

    def test_observers_fire_on_set(self):
        conf = ConfigProxy()
        seen = []
        conf.add_observer("osd_recovery_max_active",
                          lambda k, v: seen.append((k, v)))
        conf.set("osd_recovery_max_active", 7)
        assert seen == [("osd_recovery_max_active", 7)]

    def test_diff_shows_only_overrides(self):
        conf = ConfigProxy()
        conf.set("debug_ec", 10)
        assert conf.diff() == {"debug_ec": 10}
        assert len(conf.show_config()) == len(SCHEMA)


class TestPerfCounters:
    def build(self):
        return (PerfCountersBuilder("osd")
                .add_u64_counter("ops", "client operations")
                .add_u64("queue_depth")
                .add_time_avg("op_latency")
                .add_u64_avg("batch_size")
                .add_histogram("sizes", [128, 1024, 65536])
                .create_perf_counters())

    def test_counter_and_gauge(self):
        pc = self.build()
        pc.inc("ops")
        pc.inc("ops", 4)
        pc.set("queue_depth", 17)
        d = pc.dump()
        assert d["ops"] == 5 and d["queue_depth"] == 17

    def test_time_avg_dump_shape(self):
        pc = self.build()
        pc.tinc("op_latency", 0.5)
        pc.tinc("op_latency", 1.5)
        d = pc.dump()["op_latency"]
        assert d == {"avgcount": 2, "sum": 2.0, "avgtime": 1.0}

    def test_timer_context(self):
        pc = self.build()
        with pc.time("op_latency"):
            pass
        assert pc.dump()["op_latency"]["avgcount"] == 1

    def test_histogram_buckets(self):
        pc = self.build()
        for v in (64, 512, 4096, 1 << 20):
            pc.hinc("sizes", v)
        b = pc.dump()["sizes"]["buckets"]
        assert b["128"] == 1 and b["1024"] == 1 and b["65536"] == 1
        assert b["inf"] == 1

    def test_collection_dump(self):
        coll = PerfCountersCollection()
        coll.add(self.build())
        out = coll.perf_dump()
        assert "osd" in out and "ops" in out["osd"]


class TestLog:
    def test_gather_levels_gate(self):
        conf = ConfigProxy()
        log = Log(conf)
        log.dout("osd", 1, "kept")
        log.dout("osd", 5, "dropped (debug_osd default 1)")
        assert [e.message for e in log.recent()] == ["kept"]
        conf.set("debug_osd", 10)
        log.dout("osd", 5, "now kept")
        assert len(log.recent()) == 2

    def test_ring_bounded_and_dump(self):
        log = Log(max_recent=3)
        for i in range(10):
            log.dout("ec", 1, f"msg{i}")
        buf = io.StringIO()
        lines = log.dump_recent(file=buf)
        assert len(lines) == 3
        assert "msg9" in lines[-1]
        assert "begin dump of recent" in buf.getvalue()


class TestAdminSocket:
    def test_register_call_json(self):
        sock = AdminSocket()
        sock.register("status", lambda **kw: {"ok": True}, "health")
        assert sock.call("status") == {"ok": True}
        assert json.loads(sock.call_json("status")) == {"ok": True}
        assert "status" in sock.call("help")
        with pytest.raises(ValueError):
            sock.register("status", lambda **kw: None)
        with pytest.raises(KeyError):
            sock.call("nope")


class TestOpTracker:
    def test_lifecycle_and_dumps(self):
        tr = OpTracker()
        op = tr.create_request("write obj1")
        op.mark_event("queued")
        assert tr.dump_ops_in_flight()["num_ops"] == 1
        op.finish()
        assert tr.dump_ops_in_flight()["num_ops"] == 0
        hist = tr.dump_historic_ops()
        assert hist["num_ops"] == 1
        events = [e["event"] for e in hist["ops"][0]["type_data"]["events"]]
        assert events == ["initiated", "queued", "done"]

    def test_context_manager(self):
        tr = OpTracker()
        with tr.create_request("read obj2") as op:
            op.mark_event("dispatched")
        assert tr.dump_ops_in_flight()["num_ops"] == 0

    def test_history_bounded(self):
        tr = OpTracker(history_size=2)
        for i in range(5):
            tr.create_request(f"op{i}").finish()
        assert tr.dump_historic_ops()["num_ops"] == 2


class TestContextAndBackendWiring:
    def test_context_admin_commands(self):
        cct = Context()
        assert "perf dump" in cct.admin_socket.call("help")
        cct.conf.set("debug_ec", 5)
        assert cct.admin_socket.call("config diff") == {"debug_ec": 5}
        cct.admin_socket.call("config set", name="debug_ec", value="7")
        assert cct.conf.get("debug_ec") == 7

    def test_backend_counters_and_optracker(self):
        from ceph_tpu.backend import PGTransaction, make_cluster
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        ec = ErasureCodePluginRegistry.instance().factory(
            "jax_rs", "", {"k": "4", "m": "2", "device": "numpy",
                           "technique": "reed_sol_van"})
        cct = Context()
        backend, bus = make_cluster(ec, chunk_size=128, cct=cct)
        data = np.arange(4 * 128, dtype=np.uint8).tobytes()
        backend.submit_transaction(PGTransaction().write("o", 0, data))
        bus.deliver_all()
        out = {}
        backend.objects_read_and_reconstruct(
            {"o": [(0, len(data))]},
            lambda result, errors: out.update(result))
        bus.deliver_all()
        dump = cct.perf.perf_dump()["ec_backend.0"]
        assert dump["writes"] == 1
        assert dump["write_bytes"] == len(data)
        assert dump["reads"] == 1
        assert dump["read_bytes"] == len(data)
        assert dump["encode_time"]["avgcount"] == 1
        # small RMW write: client bytes counted, the padded stripe encoded
        backend.submit_transaction(PGTransaction().write("o", 3, b"xy"))
        bus.deliver_all()
        dump = cct.perf.perf_dump()["ec_backend.0"]
        assert dump["write_bytes"] == len(data) + 2
        assert dump["encode_time"]["avgcount"] == 2
        assert dump["pipeline_depth"] == 0
        # read of a missing object is an error, not a completed read
        out2 = {}
        backend.objects_read_and_reconstruct(
            {"nope": [(0, 16)]},
            lambda result, errors: out2.update(errors=errors))
        bus.deliver_all()
        dump = cct.perf.perf_dump()["ec_backend.0"]
        assert dump["reads"] == 1 and dump["read_errors"] == 1
        hist = backend.op_tracker.dump_historic_ops()
        assert hist["num_ops"] == 2            # full-stripe write + RMW patch
        events = [e["event"]
                  for e in hist["ops"][0]["type_data"]["events"]]
        assert events == ["initiated", "queued_for_pg", "encoded",
                          "commit_sent", "done"]


def test_backend_shutdown_unhooks_context_and_bus():
    """shutdown() must remove every registration the constructor added
    (review regression: leaked closures pinned dead backends)."""
    from ceph_tpu.backend import make_cluster
    from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
    ec = ErasureCodePluginRegistry.instance().factory(
        "jax_rs", "", {"k": "4", "m": "2", "device": "numpy",
                       "technique": "reed_sol_van"})
    cct = Context()
    backend, bus = make_cluster(ec, chunk_size=128, cct=cct)
    assert "dump_ops_in_flight.0" in cct.admin_socket.call("help")
    assert backend.on_shard_down in bus.down_listeners
    backend.shutdown()
    assert "dump_ops_in_flight.0" not in cct.admin_socket.call("help")
    assert "ec_backend.0" not in cct.perf.perf_dump()
    assert backend.on_shard_down not in bus.down_listeners
    assert backend.on_shard_up not in bus.up_listeners


def test_log_timestamp_no_rounding_carry():
    from ceph_tpu.common.log import Entry
    e = Entry(stamp=1000000.9999996, subsys="osd", level=1, message="x")
    # truncation: fraction stays within the same second
    assert ".999999" in e.format()
