"""ISSUE 20: the zero-copy device-direct data path.

Bitwise equivalence of the sideband wire format against the legacy
pickle path (any chunking, 1-byte partial reads, reordered frame
bursts), memoryview-lifetime safety under the stream parser's
compaction and BufferError fallback, the fused encode+checksum kernel
against the host crc loop, and the copy ledger's end-to-end
copies-per-byte contrast over a real mux stack.
"""
import os
import random
import threading

import numpy as np
import pytest

import ceph_tpu.net as net
from ceph_tpu.backend import ecutil, wire
from ceph_tpu.common import copy_ledger
from ceph_tpu.msg import proto  # noqa: F401 — registers batch codecs
from ceph_tpu.msg.parser import StreamParser
from ceph_tpu.msg.staging import StagingPool

SECRET = bytes(range(32))


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(seed)


def _flatten(parts: list) -> bytes:
    return b"".join(bytes(p) if isinstance(p, memoryview) else p
                    for p in parts)


def _parse_one(blob: bytes, secret, staging=None):
    p = StreamParser(secret)
    frames = p.feed(blob)
    assert len(frames) == 1 and p.pending() == 0
    tag, segs = frames[0]
    return net._decode(tag, segs, authed=True, staging=staging)


# -- the frame splice: frame_encode_parts == frame_encode --------------------

class TestFramePartsEquality:
    @pytest.mark.parametrize("secret", [None, SECRET],
                             ids=["crc", "secure"])
    def test_scattered_segment_bitwise_equal(self, secret):
        """A scattered third segment (length table + spliced payload
        views) wires byte-for-byte identically to the joined frame, in
        BOTH integrity modes — the device->wire splice never changes
        what the peer verifies."""
        pieces = [b"\x07" * 12, memoryview(bytes(range(256)) * 17),
                  b"xy", memoryview(b"z" * 4096),
                  memoryview(b"s" * 8)]          # small view: coalesces
        segs_joined = [b"RpcBatch", b"header", _flatten(pieces)]
        segs_parts = [b"RpcBatch", b"header", pieces]
        joined = wire.frame_encode(wire.TAG_MESSAGE, segs_joined,
                                   secret=secret)
        parts = wire.frame_encode_parts(wire.TAG_MESSAGE, segs_parts,
                                        secret=secret)
        assert _flatten(parts) == joined
        # the bulk views really splice unjoined (no hidden join copy)
        spliced = [p for p in parts if isinstance(p, memoryview)]
        assert len(spliced) == 2
        assert spliced[0].obj is pieces[1].obj
        assert spliced[1].obj is pieces[3].obj


# -- the sideband codec: _encode_parts == _encode ----------------------------

class TestSidebandCodec:
    @pytest.mark.parametrize("n", [1024, 4096, 65536, 1 << 20])
    def test_rpc_call_roundtrips_match_legacy(self, n):
        payload = os.urandom(n)
        msg = net.RpcCall(3, "put", {"pool": "p", "data": payload},
                          session="S1")
        parts = net._encode_parts(msg, SECRET)
        assert parts is not None
        legacy = net._encode(msg, SECRET)
        got_sb = _parse_one(_flatten(parts), SECRET)
        got_legacy = _parse_one(legacy, SECRET)
        assert bytes(got_sb.args["data"]) == payload \
            == bytes(got_legacy.args["data"])
        assert got_sb.args["pool"] == "p" and got_sb.rid == 3
        assert got_sb.session == "S1"
        # extraction never mutates the original (retries resend it)
        assert msg.args["data"] is payload

    def test_result_batch_staged_landing(self):
        from ceph_tpu.msg.proto import RpcResultBatch
        payloads = [os.urandom(2048), os.urandom(5000), os.urandom(1024)]
        msg = RpcResultBatch([net.RpcResult(i, True, p)
                              for i, p in enumerate(payloads)])
        parts = net._encode_parts(msg, SECRET)
        assert parts is not None
        pool = StagingPool("test")
        base = copy_ledger.ledger().snapshot()["copied"]["staging"]
        got = _parse_one(_flatten(parts), SECRET, staging=pool)
        for r, p in zip(got.results, payloads):
            assert isinstance(r.value, memoryview)   # staged slice
            assert bytes(r.value) == p
        # all three landed with ONE staged copy of the whole sideband
        assert pool.stats["staged_buffers"] == 1
        led = copy_ledger.ledger().snapshot()["copied"]["staging"]
        assert led >= base + sum(len(p) for p in payloads)

    def test_small_payloads_stay_pickled_but_weigh_in_ledger(self):
        """Eligible-but-small values (>= PAYLOAD_MIN, < splice
        threshold) do not lift — the header rewrite would cost more
        than the copy — but their bytes still count as legacy copies,
        so the ratio cannot flatter the small-op path."""
        small = os.urandom(net._SB_SPLICE_MIN - 1)
        msg = net.RpcCall(1, "put", {"data": small}, session="S")
        assert net._encode_parts(msg, SECRET) is None
        base = copy_ledger.ledger().snapshot()["copied"]["pickle"]
        blob = net._encode(msg, SECRET)
        assert bytes(_parse_one(blob, SECRET).args["data"]) == small
        assert copy_ledger.ledger().snapshot()["copied"]["pickle"] \
            >= base + len(small)
        # sub-PAYLOAD_MIN values are invisible to the whole machinery
        tiny = net.RpcCall(2, "put", {"data": os.urandom(8)}, session="S")
        assert net._encode_parts(tiny, SECRET) is None


# -- the stream parser: chunking, reordering, lifetime -----------------------

class TestStreamParserZeroCopy:
    def _frames(self, seed: int, sizes) -> list[tuple[bytes, bytes]]:
        """(wire_blob, payload) per frame: a mix of sideband and legacy
        encodings of the same call shape."""
        rng = random.Random(seed)
        out = []
        for i, n in enumerate(sizes):
            payload = os.urandom(n)
            msg = net.RpcCall(i, "put", {"data": payload},
                              session=f"S{i}")
            if rng.random() < 0.5:
                parts = net._encode_parts(msg, SECRET)
                blob = _flatten(parts) if parts is not None \
                    else net._encode(msg, SECRET)
            else:
                blob = net._encode(msg, SECRET)
            out.append((blob, payload))
        return out

    @pytest.mark.parametrize("chunk", [1, 7, 4096])
    def test_partial_reads_any_chunking(self, chunk):
        """1-byte and odd-size partial reads across frame boundaries
        decode bitwise-identically to whole-frame feeds — including
        sideband frames whose payload segment spans many feeds."""
        frames = self._frames(chunk, [40, 1024, 9000, 64, 2048])
        stream = b"".join(b for b, _ in frames)
        p = StreamParser(SECRET)
        got = []
        for off in range(0, len(stream), chunk):
            for tag, segs in p.feed(stream[off:off + chunk]):
                got.append(net._decode(tag, segs, authed=True))
        assert [bytes(m.args["data"]) for m in got] \
            == [pl for _, pl in frames]
        assert p.pending() == 0

    def test_reordered_bursts_decode_in_arrival_order(self):
        """Frames delivered in a different burst order (the coalescer
        re-queues under backpressure) decode to exactly the payloads in
        arrival order — no cross-frame buffer state leaks."""
        frames = self._frames(99, [2048, 1024, 70000, 31, 4096])
        order = [2, 0, 4, 1, 3]
        rng = random.Random(7)
        p = StreamParser(SECRET)
        got = []
        for i in order:
            blob = frames[i][0]
            off = 0
            while off < len(blob):      # bursts misaligned with frames
                step = rng.randrange(1, 1 + len(blob) - off)
                for tag, segs in p.feed(blob[off:off + step]):
                    got.append(net._decode(tag, segs, authed=True))
                off += step
        assert [bytes(m.args["data"]) for m in got] \
            == [frames[i][1] for i in order]

    def test_staged_payloads_survive_parser_reuse(self):
        """A staged payload stays intact after the parser buffer that
        produced it is overwritten by later feeds — the staging copy is
        what makes handing views across threads safe."""
        pool = StagingPool("lifetime")
        payload = os.urandom(8192)
        msg = net.RpcCall(1, "put", {"data": payload}, session="S")
        blob = _flatten(net._encode_parts(msg, SECRET))
        p = StreamParser(SECRET)
        (tag, segs), = p.feed(blob)
        got = net._decode(tag, segs, authed=True, staging=pool)
        staged = got.args["data"]
        for i in range(2, 6):           # stomp the parser buffer
            m2 = net.RpcCall(i, "put", {"data": os.urandom(8192)},
                             session="S")
            p.feed(_flatten(net._encode_parts(m2, SECRET)))
        assert bytes(staged) == payload

    def test_retained_view_fallback_counted_and_safe(self):
        """A caller that (wrongly) retains a segment view across feeds
        pins the buffer: the next feed's BufferError fallback rebuilds
        it, COUNTS the copied bytes in the ledger, and the retained
        view still reads the original bytes."""
        p = StreamParser(SECRET)
        m1 = net.RpcCall(1, "put", {"data": os.urandom(2000)},
                         session="S")
        (tag, segs), = p.feed(net._encode(m1, SECRET))
        retained = segs[1]              # memoryview into p's buffer
        header_bytes = bytes(retained)
        base = copy_ledger.ledger().snapshot()["copied"]["fallback"]
        m2 = net.RpcCall(2, "put", {"data": os.urandom(3000)},
                         session="S")
        blob2 = net._encode(m2, SECRET)
        (tag2, segs2), = p.feed(blob2)
        got2 = net._decode(tag2, segs2, authed=True)
        assert bytes(got2.args["data"]) == m2.args["data"]
        assert copy_ledger.ledger().snapshot()["copied"]["fallback"] \
            >= base + len(blob2)
        assert bytes(retained) == header_bytes

    def test_compaction_tail_move_is_counted(self):
        """The amortized head-trim's tail move reports to the ledger:
        park a partial frame behind >64 KiB of consumed stream, then
        let the next feed compact — the moved tail bytes appear under
        ``compaction``."""
        p = StreamParser(SECRET)
        big = net._encode(net.RpcCall(1, "put",
                                      {"data": os.urandom(80000)},
                                      session="S"), SECRET)
        tail_msg = net.RpcCall(2, "put", {"data": os.urandom(4000)},
                               session="S")
        tail = net._encode(tail_msg, SECRET)
        half = len(tail) // 2
        frames = p.feed(big + tail[:half])
        assert len(frames) == 1 and p.pending() == half
        del frames                       # sever the views: buffer free
        base = copy_ledger.ledger().snapshot()["copied"]["compaction"]
        (tag, segs), = p.feed(tail[half:])
        assert bytes(net._decode(tag, segs, authed=True)
                     .args["data"]) == tail_msg.args["data"]
        assert copy_ledger.ledger().snapshot()["copied"]["compaction"] \
            >= base + half


# -- the fused encode + checksum kernel --------------------------------------

# every width class of the word fold: under a word, a word, odd, a lane
# row and more, no power of two, and the served shard (12 x 512 KiB);
# fill None is seeded random bytes, else every byte that value
_CRC_CASES = [(r, n, None)
              for n in (1, 2, 3, 4, 5, 63, 64, 777, 4096, 131072, 524288)
              for r in (1, 5, 12)] \
    + [(r, n, fill) for r, n in ((5, 777), (12, 524288))
       for fill in (0x00, 0xFF)]


class TestFusedChecksum:
    @pytest.mark.parametrize("r,n,fill", _CRC_CASES)
    def test_crc32c_rows_matches_host(self, r, n, fill):
        """Bit-equal to the host crc whether the rows come from the host
        (viewed as words before the upload) or lie on the device (made
        words there, as the fused encode dispatch does; at the two
        largest widths for r = 12 only, a CPU compile being seconds)."""
        import jax.numpy as jnp
        from ceph_tpu.ops import rs_kernels
        rows = np.full((r, n), fill, dtype=np.uint8) if fill is not None \
            else _rng(n).integers(0, 256, size=(r, n), dtype=np.uint8)
        host = [ecutil.crc32c(0, bytes(row)) for row in rows]
        given = [rows] + [jnp.asarray(rows)] * (n < 131072 or r == 12)
        for arg in given:
            dev = np.asarray(rs_kernels.crc32c_rows(arg))
            assert dev.dtype == np.uint32 and dev.shape == (r,)
            assert [int(x) for x in dev] == host

    def test_crc32c_rows_has_no_gather(self):
        """The served shape lowers to word arithmetic alone: a TPU has
        no fast per-element gather, and the table lookup this kernel
        replaced cost 57 ms a put there."""
        import jax
        import jax.numpy as jnp
        from ceph_tpu.ops import rs_kernels
        for text in (
                rs_kernels._crc32c_rows_jit.lower(
                    jax.ShapeDtypeStruct((12, 524288), jnp.uint8),
                    524288).as_text(),
                rs_kernels._crc32c_words_jit.lower(
                    jax.ShapeDtypeStruct((12, 1024, 128),
                                         jnp.uint32)).as_text()):
            assert "xor" in text
            for op in ("gather", "dynamic-slice", "dynamic_slice"):
                assert op not in text, op

    @pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (6, 3)])
    @pytest.mark.parametrize("n", [64, 1000, 4096])
    def test_encode_with_crc_bitwise(self, k, m, n):
        """The fused dispatch returns the SAME parity as the host
        reference and the SAME seed-free row crcs as a host loop over
        concat(data, parity) — across geometries and non-pow2 widths."""
        from ceph_tpu.ops.codec import RSCodec
        codec = RSCodec(k, m)
        data = _rng(k * 1000 + n).integers(0, 256, size=(k, n),
                                           dtype=np.uint8)
        parity, crcs = codec.encode_with_crc(data)
        ref = codec.encode_host(data)
        assert np.array_equal(parity, ref)
        rows = np.concatenate([data, ref], axis=0)
        assert [int(c) for c in crcs] \
            == [ecutil.crc32c(0, bytes(r)) for r in rows]

    def test_append_crcs_matches_append(self):
        """Chaining device crcs through the crc32_combine identity is
        bitwise-identical to the host running-seed append, across
        multiple uneven-length appends."""
        rng = _rng(17)
        h_ref, h_dev = ecutil.HashInfo(3), ecutil.HashInfo(3)
        old = 0
        for nbytes in (512, 64, 1 << 14, 33):
            chunks = {s: rng.integers(0, 256, size=nbytes,
                                      dtype=np.uint8)
                      for s in range(3)}
            h_ref.append(old, chunks)
            h_dev.append_crcs(
                old, {s: ecutil.crc32c(0, bytes(c))
                      for s, c in chunks.items()}, nbytes)
            old += nbytes
        assert h_ref.cumulative_shard_hashes \
            == h_dev.cumulative_shard_hashes
        assert h_ref.total_chunk_size == h_dev.total_chunk_size

    def test_hinfo_append_device_path_matches_host(self):
        """``hinfo_append`` with a device-codec plugin fuses the shard
        crcs into one kernel call and lands the same running hashes as
        the pure host append."""
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        ec_impl = ErasureCodePluginRegistry.instance().factory(
            "jax_rs", "", {"k": "4", "m": "2", "device": "jax",
                           "technique": "reed_sol_van"})
        assert ec_impl.device_codec(4096 * 6) is not None
        rng = _rng(23)
        h_ref, h_dev = ecutil.HashInfo(6), ecutil.HashInfo(6)
        old = 0
        for nbytes in (4096, 512):
            chunks = {s: rng.integers(0, 256, size=nbytes,
                                      dtype=np.uint8)
                      for s in range(6)}
            h_ref.append(old, chunks)
            ecutil.hinfo_append(h_dev, old, chunks, ec_impl=ec_impl)
            old += nbytes
        assert h_ref.cumulative_shard_hashes \
            == h_dev.cumulative_shard_hashes

    def test_pack_shard_major_matches_reference(self):
        """The single-allocation batched relayout equals per-buffer
        ``_to_shard_major`` + concatenate, for mixed stripe counts."""
        k, c = 4, 32
        rng = _rng(5)
        arrs = [rng.integers(0, 256, size=k * c * s, dtype=np.uint8)
                for s in (1, 3, 2, 7)]
        packed = ecutil._pack_shard_major(arrs, k, c)
        ref = np.concatenate(
            [ecutil._to_shard_major(a, k, c) for a in arrs], axis=1)
        assert np.array_equal(packed, ref)


# -- the whole stack: mux on/off equivalence + the ledger contrast -----------

@pytest.fixture
def served(tmp_path):
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.net import ClusterServer
    c = MiniCluster(n_osds=3, osds_per_host=3, chunk_size=512,
                    data_dir=tmp_path)
    server = ClusterServer(c)
    server.start()
    yield server, tmp_path / "client.admin.keyring"
    server.stop()
    c.shutdown()


class TestEndToEnd:
    def _mux(self, server, keyring):
        from ceph_tpu.msg import MuxClient
        mux = MuxClient("127.0.0.1", server.port, keyring, n_conns=1)
        mux.connect()
        return mux

    def test_sideband_echo_copies_each_byte_at_most_1p5_times(
            self, served):
        """Bulk payloads echo bitwise through the mux transport and the
        ledger counts each served byte moved at most ~1.5 times, all of
        it the sanctioned landing copies (staging / materialize), none
        of it codec copies."""
        server, keyring = served
        led = copy_ledger.ledger()
        led.reset()
        mux = self._mux(server, keyring)
        rng = _rng(1)
        try:
            s = mux.session()
            for _ in range(8):
                payload = bytes(rng.integers(0, 256, size=65536,
                                             dtype=np.uint8))
                assert bytes(s.call("ping", {"payload": payload},
                                    timeout=30.0)) == payload
        finally:
            mux.close()
        fused = led.snapshot()
        assert fused["served"] >= 8 * 2 * 65536
        assert fused["copies_per_byte"] <= 1.5, fused
        sanctioned = fused["copied"]["staging"] \
            + fused["copied"]["materialize"]
        assert sanctioned >= 0.9 * fused["copied_total"], fused

    def test_all_pickle_frames_decode_beside_sideband_on_one_connection(
            self, served):
        """A peer that frames a bulk payload all-pickle (two segments,
        as ``net.Channel`` does for every send) is served on the same
        connection as sideband frames: ``net._decode`` dispatches on
        the segment count, and every echo is bit-equal."""
        server, keyring = served
        led = copy_ledger.ledger()
        mux = self._mux(server, keyring)
        rng = _rng(3)
        size = 4 * net._SB_SPLICE_MIN
        try:
            s = mux.session()
            conn, = [c for c in mux._conns if c is not None]
            for i in range(6):
                payload = bytes(rng.integers(0, 256, size=size,
                                             dtype=np.uint8))
                if i % 2:       # this request leaves all-pickle
                    conn._encode_parts = lambda msg: None
                base = led.snapshot()["copied"]
                try:
                    echoed = s.call("ping", {"payload": payload},
                                    timeout=30.0)
                finally:
                    conn.__dict__.pop("_encode_parts", None)
                assert bytes(echoed) == payload
                now = led.snapshot()["copied"]
                if i % 2:
                    assert now["unpickle"] - base["unpickle"] >= size
                else:
                    assert now["unpickle"] == base["unpickle"]
                    assert now["staging"] - base["staging"] >= size
        finally:
            mux.close()
