"""Stripe offset algebra, per-shard hashes, and batched stripe codecs.

Analog of the reference's ``ECUtil`` (reference: src/osd/ECUtil.{h,cc}) with
the one deliberate TPU-first restructuring called out in SURVEY.md §2.2: the
reference encodes **per stripe** (one plugin call per stripe_width bytes,
ECUtil.cc:136-148); here :func:`encode`/:func:`decode` make ONE plugin call
for the whole multi-stripe buffer by laying stripes out as contiguous
per-shard chunk streams.  RS parity is positionwise, so batching across
stripes is a pure relayout — bit-identical output, MXU-sized launches.
"""
from __future__ import annotations

import functools

import numpy as np

from ..common import copy_ledger
from ..common.tracer import trace_span

# -- crc32c (Castagnoli), seed-chained like ceph_crc32c ----------------------
# HashInfo chains bufferlist::crc32c(seed) per shard with initial seed -1
# (reference: src/osd/ECUtil.h:110-112, ECUtil.cc:161-177).

_CRC32C_POLY = 0x82F63B78


def _make_crc_tables(n_tables: int = 16) -> list[list[int]]:
    """Slice-by-N tables: T[j][b] advances byte b through j+1 zero bytes."""
    t0 = []
    for n in range(256):
        c = n
        for _ in range(8):
            c = (c >> 1) ^ _CRC32C_POLY if c & 1 else c >> 1
        t0.append(c)
    tables = [t0]
    for _ in range(n_tables - 1):
        prev = tables[-1]
        tables.append([(prev[b] >> 8) ^ t0[prev[b] & 0xFF] for b in range(256)])
    return tables


_CRC_TABLES = _make_crc_tables()

_native_crc = None
# up to this many bytes the native crc keeps the interpreter (some 2 us at
# 8 KiB): letting it go means waiting to win it back from the threads
# that took it, for longer than the crc takes (the store's blob and record
# crcs inside the cluster lock's hold); a 512 KiB shard's (75 us) lets it
# go, for the threads that wait for it
SMALL_CRC_MAX = 64 * 1024


def _load_native_crc():
    """SSE4.2 CRC32C from the native lib (gf8_simd.cc ec_crc32c) as two
    calls of one symbol: (lets the interpreter go, keeps it).  The pure
    Python path below stays as the oracle and no-toolchain fallback."""
    global _native_crc
    if _native_crc is not None:
        return _native_crc or None
    try:
        import ctypes
        from ..native import registry_lib
        lib = registry_lib()
        held = ctypes.PYFUNCTYPE(ctypes.c_uint32, ctypes.c_uint32,
                                 ctypes.c_void_p, ctypes.c_size_t)(
            ("ec_crc32c", lib))
        _native_crc = (lib.ec_crc32c, held)
    except Exception:
        _native_crc = False
    return _native_crc or None


def crc32c(seed: int, data: bytes | np.ndarray) -> int:
    """ceph_crc32c semantics: raw reflected CRC-32C update, no final xor —
    the caller chains seeds (standard crc32c(x) = crc32c(0xffffffff, x) ^ 0xffffffff).

    Dispatches to the native SSE4.2/table kernel when built (keeping the
    interpreter up to ``SMALL_CRC_MAX`` bytes); pure-Python slice-by-16
    otherwise (one iteration consumes 16 bytes).
    """
    fns = _load_native_crc()
    if fns is not None and isinstance(data, np.ndarray):
        # zero-copy for contiguous arrays: the kernel needs pointer+length
        arr = np.ascontiguousarray(data).reshape(-1)
        return fns[arr.nbytes <= SMALL_CRC_MAX](
            seed & 0xFFFFFFFF, arr.ctypes.data, arr.nbytes)
    if isinstance(data, np.ndarray):
        buf = np.ascontiguousarray(data.ravel()).tobytes()
    else:
        buf = bytes(data)
    if fns is not None:
        return fns[len(buf) <= SMALL_CRC_MAX](
            seed & 0xFFFFFFFF, buf, len(buf))
    crc = seed & 0xFFFFFFFF
    t = _CRC_TABLES
    (t15, t14, t13, t12, t11, t10, t9, t8,
     t7, t6, t5, t4, t3, t2, t1, t0) = t[15], t[14], t[13], t[12], t[11], \
        t[10], t[9], t[8], t[7], t[6], t[5], t[4], t[3], t[2], t[1], t[0]
    n16 = len(buf) & ~15
    for i in range(0, n16, 16):
        b = buf[i:i + 16]
        crc ^= b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)
        crc = (t15[crc & 0xFF] ^ t14[(crc >> 8) & 0xFF] ^
               t13[(crc >> 16) & 0xFF] ^ t12[crc >> 24] ^
               t11[b[4]] ^ t10[b[5]] ^ t9[b[6]] ^ t8[b[7]] ^
               t7[b[8]] ^ t6[b[9]] ^ t5[b[10]] ^ t4[b[11]] ^
               t3[b[12]] ^ t2[b[13]] ^ t1[b[14]] ^ t0[b[15]])
    for i in range(n16, len(buf)):
        crc = t0[(crc ^ buf[i]) & 0xFF] ^ (crc >> 8)
    return crc


# -- crc32c combine algebra (the fused-checksum kernel's host half) ---------
#
# The crc32c register update is GF(2)-linear in (seed, data bits), so
#     crc32c(seed, D) == crc32c(seed, zeros(len(D))) ^ crc32c(0, D)
# (zlib's crc32_combine identity).  That factorization is what lets the
# device compute seed-FREE per-row crcs inside the encode dispatch
# (ops/rs_kernels.crc32c_rows) while HashInfo's seed-chained ceph
# semantics are restored exactly on the host with one 32x32 GF(2)
# matrix application per append: advance the previous cumulative crc
# through n zero bytes, then xor the device's crc32c(0, chunk).

def _gf2_times(op: list[int], vec: int) -> int:
    out = 0
    i = 0
    while vec:
        if vec & 1:
            out ^= op[i]
        vec >>= 1
        i += 1
    return out


def _gf2_square(op: list[int]) -> list[int]:
    return [_gf2_times(op, op[i]) for i in range(32)]


@functools.lru_cache(maxsize=None)
def crc32c_zeros_op(nbytes: int) -> tuple:
    """The 32x32 GF(2) operator advancing a crc32c register through
    ``nbytes`` zero bytes, as bit-image columns (entry i = image of
    register bit i).  Square-and-multiply over the one-zero-byte
    operator: O(log n) squarings, lru-cached per length."""
    assert nbytes >= 0
    t0 = _CRC_TABLES[0]
    # one zero byte: crc' = (crc >> 8) ^ T0[crc & 0xFF]
    byte_op = [t0[1 << i] if i < 8 else (1 << (i - 8)) for i in range(32)]
    result = [1 << i for i in range(32)]          # identity
    while nbytes:
        if nbytes & 1:
            result = [_gf2_times(byte_op, result[i]) for i in range(32)]
        byte_op = _gf2_square(byte_op)
        nbytes >>= 1
    return tuple(result)


def crc32c_zeros(crc: int, nbytes: int) -> int:
    """``crc32c(crc, b"\\x00" * nbytes)`` in O(log n) (no zero buffer)."""
    return _gf2_times(list(crc32c_zeros_op(nbytes)), crc & 0xFFFFFFFF)


class StripeInfo:
    """stripe_info_t: logical<->chunk offset algebra (ECUtil.h:27-80).

    ``stripe_width = k * chunk_size``; logical offsets live in object space,
    chunk offsets in per-shard space.
    """

    def __init__(self, k: int, chunk_size: int,
                 stored_chunk_size: int | None = None):
        self.k = k
        self.chunk_size = chunk_size
        self.stripe_width = k * chunk_size
        # On-disk bytes per chunk_size logical share bytes.  Equal for
        # every classic code; regenerating MBR chunks expand (plugin
        # get_stored_chunk_size), so shard extents, hinfo sizes and
        # transaction offsets all live in STORED units while logical
        # offset algebra stays in share units.
        self.stored_chunk_size = (chunk_size if stored_chunk_size is None
                                  else stored_chunk_size)

    def chunk_to_stored(self, chunk_off: int) -> int:
        """Share-space chunk offset/length -> stored (on-disk) units."""
        if self.stored_chunk_size == self.chunk_size:
            return chunk_off
        scaled = chunk_off * self.stored_chunk_size
        assert scaled % self.chunk_size == 0, \
            f"chunk offset {chunk_off} not stored-convertible"
        return scaled // self.chunk_size

    def stored_to_chunk(self, stored_off: int) -> int:
        """Stored (on-disk) offset/length -> share-space chunk units."""
        if self.stored_chunk_size == self.chunk_size:
            return stored_off
        scaled = stored_off * self.chunk_size
        assert scaled % self.stored_chunk_size == 0, \
            f"stored offset {stored_off} not share-convertible"
        return scaled // self.stored_chunk_size

    def logical_offset_is_stripe_aligned(self, logical: int) -> bool:
        return logical % self.stripe_width == 0

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return ((offset + self.stripe_width - 1) // self.stripe_width) * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        rem = offset % self.stripe_width
        return offset + (self.stripe_width - rem) if rem else offset

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return (offset // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return (offset // self.chunk_size) * self.stripe_width

    def aligned_offset_len_to_chunk(self, off: int, length: int) -> tuple[int, int]:
        return (self.aligned_logical_offset_to_chunk_offset(off),
                self.aligned_logical_offset_to_chunk_offset(length))

    def offset_len_to_stripe_bounds(self, off: int, length: int) -> tuple[int, int]:
        start = self.logical_to_prev_stripe_offset(off)
        end_len = self.logical_to_next_stripe_offset((off - start) + length)
        return start, end_len


class HashInfo:
    """Per-shard cumulative crc32c of appended chunk bytes (ECUtil.h:101-168).

    Appends must be contiguous with the current size; out-of-order appends
    clear the hashes the way the reference asserts them away.
    """

    def __init__(self, num_chunks: int):
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [0xFFFFFFFF] * num_chunks
        self.projected_total_chunk_size = 0
        # per-object write version, bumped on every committed transaction
        # and persisted with each shard: a shard that missed writes while
        # down is detectably stale even after overwrites cleared the chunk
        # hashes (the role the reference's PG log versions play,
        # src/osd/PGLog.cc divergence detection)
        self.version = 0

    def append(self, old_size: int, to_append: dict[int, np.ndarray]) -> None:
        assert old_size == self.total_chunk_size
        if not to_append:
            return
        sizes = {len(v) for v in to_append.values()}
        assert len(sizes) == 1, "uneven shard appends"
        if self.has_chunk_hash():
            for shard, buf in to_append.items():
                self.cumulative_shard_hashes[shard] = crc32c(
                    self.cumulative_shard_hashes[shard], buf)
        self.total_chunk_size += sizes.pop()

    def append_crcs(self, old_size: int, crc0s: dict[int, int],
                    nbytes: int) -> None:
        """Append with PRE-computed seed-free crcs — the fused device
        checksum path.  ``crc0s[shard] = crc32c(0, chunk_bytes)`` (what
        ``ops.rs_kernels.crc32c_rows`` returns); each running hash
        advances by the crc32_combine identity

            crc32c(seed, D) == crc32c_zeros(seed, len(D)) ^ crc32c(0, D)

        so the device never needs the host's running seed.  Bitwise
        identical to :meth:`append` on the same bytes."""
        assert old_size == self.total_chunk_size
        if not crc0s:
            return
        if self.has_chunk_hash():
            for shard, c0 in crc0s.items():
                self.cumulative_shard_hashes[shard] = crc32c_zeros(
                    self.cumulative_shard_hashes[shard], nbytes) ^ c0
        self.total_chunk_size += nbytes

    def clear(self) -> None:
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [0xFFFFFFFF] * len(self.cumulative_shard_hashes)

    def get_chunk_hash(self, shard: int) -> int:
        return self.cumulative_shard_hashes[shard]

    def get_total_chunk_size(self) -> int:
        return self.total_chunk_size

    def get_projected_total_chunk_size(self) -> int:
        return self.projected_total_chunk_size

    def get_total_logical_size(self, sinfo: StripeInfo) -> int:
        # chunk sizes are STORED units; convert back to share space
        # before multiplying out to logical bytes
        return sinfo.stored_to_chunk(self.total_chunk_size) * \
            (sinfo.stripe_width // sinfo.chunk_size)

    def get_projected_total_logical_size(self, sinfo: StripeInfo) -> int:
        return sinfo.stored_to_chunk(self.projected_total_chunk_size) * \
            (sinfo.stripe_width // sinfo.chunk_size)

    def set_projected_total_logical_size(self, sinfo: StripeInfo, logical: int) -> None:
        assert sinfo.logical_offset_is_stripe_aligned(logical)
        self.projected_total_chunk_size = sinfo.chunk_to_stored(
            sinfo.aligned_logical_offset_to_chunk_offset(logical))

    def set_total_chunk_size_clear_hash(self, new_chunk_size: int) -> None:
        self.cumulative_shard_hashes = []
        self.total_chunk_size = new_chunk_size

    def has_chunk_hash(self) -> bool:
        return bool(self.cumulative_shard_hashes)

    def to_dict(self) -> dict:
        return {"total_chunk_size": self.total_chunk_size,
                "cumulative_shard_hashes": list(self.cumulative_shard_hashes),
                "version": self.version}


# -- batched stripe codec ----------------------------------------------------

def _to_shard_major(buf: np.ndarray, k: int, chunk_size: int) -> np.ndarray:
    """[S * stripe_width] logical bytes -> [k, S * chunk_size] shard streams.

    Stripe s contributes bytes [s*W + i*c, s*W + (i+1)*c) to shard i at chunk
    offset s*c (doc/dev/osd_internals/erasure_coding.rst:55-75 layout).
    """
    stripes = buf.reshape(-1, k, chunk_size)          # [S, k, c]
    return np.ascontiguousarray(stripes.transpose(1, 0, 2)).reshape(k, -1)


def _from_shard_major(shards: np.ndarray, chunk_size: int) -> np.ndarray:
    """[k, S * chunk_size] shard streams -> [S * stripe_width] logical bytes."""
    k = shards.shape[0]
    stripes = shards.reshape(k, -1, chunk_size).transpose(1, 0, 2)  # [S, k, c]
    return np.ascontiguousarray(stripes).reshape(-1)


def _pack_shard_major(arrs: list[np.ndarray], k: int,
                      chunk_size: int) -> np.ndarray:
    """Single-copy shard-major pack of MANY logical buffers: each
    buffer's [S, k, c] stripe view lands transposed DIRECTLY into one
    contiguous [k, total] output — one strided ``copyto`` per buffer —
    replacing the two-copy ``_to_shard_major``-then-``concatenate``
    relayout.  The surviving copy is the data path's host relayout
    floor (until staging buffers land shard-major), reported to the
    copy ledger as ``relayout``."""
    total = sum(len(b) for b in arrs) // k
    out = np.empty((k, total), dtype=np.uint8)
    off = 0
    for b in arrs:
        ln = len(b) // k
        s = ln // chunk_size
        # out[:, off:off+ln].reshape splits the row extent into chunk
        # cells without copying (strides stay expressible), so copyto
        # streams straight from the stripe view into the packed output
        np.copyto(out[:, off:off + ln].reshape(k, s, chunk_size),
                  b.reshape(s, k, chunk_size).swapaxes(0, 1))
        off += ln
    copy_ledger.count_copy("relayout", out.nbytes)
    return out


def encode(sinfo: StripeInfo, ec_impl, data: bytes | np.ndarray,
           want: set | None = None) -> dict[int, np.ndarray]:
    """Encode a stripe-aligned logical buffer into per-shard chunk buffers.

    One ``encode_chunks`` call for ALL stripes (vs the reference's per-stripe
    loop at ECUtil.cc:136-148); returns {shard: concatenated chunk bytes}.
    """
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) \
        else np.asarray(data, dtype=np.uint8)
    assert len(buf) % sinfo.stripe_width == 0, \
        f"len {len(buf)} not stripe aligned ({sinfo.stripe_width})"
    k = ec_impl.get_data_chunk_count()
    n = ec_impl.get_chunk_count()
    assert k == sinfo.k
    if want is None:
        want = set(range(n))
    shard_len = (len(buf) // sinfo.stripe_width) * sinfo.chunk_size
    data_shards = _to_shard_major(buf, k, sinfo.chunk_size)
    encoded = {ec_impl.chunk_index(i): data_shards[i].copy() for i in range(k)}
    for i in range(k, n):
        encoded[ec_impl.chunk_index(i)] = np.zeros(shard_len, dtype=np.uint8)
    ec_impl.encode_chunks(set(range(n)), encoded)
    return {i: encoded[i] for i in want}


def encode_many(sinfo: StripeInfo, ec_impl,
                bufs: list[bytes | np.ndarray]) -> list[dict[int, np.ndarray]]:
    """Encode MANY stripe-aligned buffers (different objects, different
    PGs) in ONE ``encode_chunks`` call — the cross-op/cross-PG coalescing
    the per-op :func:`encode` cannot do.  All buffers share the codec, so
    their shard streams concatenate along the byte axis and one device
    dispatch covers the lot; results split back per buffer.

    Returns one ``{chunk: bytes}`` dict per input buffer, identical to
    calling :func:`encode` per buffer.  An empty batch is a no-op (the
    coalescer's drain can race a flush to zero ops)."""
    if not bufs:
        return []
    k = ec_impl.get_data_chunk_count()
    n = ec_impl.get_chunk_count()
    arrs = []
    for data in bufs:
        buf = np.frombuffer(data, dtype=np.uint8) \
            if isinstance(data, (bytes, bytearray)) \
            else np.asarray(data, dtype=np.uint8)
        assert len(buf) % sinfo.stripe_width == 0, \
            f"len {len(buf)} not stripe aligned"
        arrs.append(buf)
    shard_lens = [(len(b) // sinfo.stripe_width) * sinfo.chunk_size
                  for b in arrs]
    data_shards = _pack_shard_major(arrs, k, sinfo.chunk_size)
    total = data_shards.shape[1]
    encoded = {ec_impl.chunk_index(i): data_shards[i].copy()
               for i in range(k)}
    for i in range(k, n):
        encoded[ec_impl.chunk_index(i)] = np.zeros(total, dtype=np.uint8)
    ec_impl.encode_chunks(set(range(n)), encoded)
    out: list[dict[int, np.ndarray]] = []
    off = 0
    for ln in shard_lens:
        out.append({c: encoded[c][off:off + ln] for c in range(n)})
        off += ln
    return out


def _as_u8(v) -> np.ndarray:
    if isinstance(v, (bytes, bytearray, memoryview)):
        return np.frombuffer(v, dtype=np.uint8)
    return np.asarray(v, dtype=np.uint8)


# -- device-resident pipelined variants ---------------------------------------
# These route the SAME batched relayouts through ops.pipeline.CodecPipeline:
# the host pack (the `_to_shard_major` transposes and concatenations below)
# runs while earlier batches' device kernels are still in flight, and the
# `device_get` happens only at the pipeline's completion boundary.  They
# engage only when the plugin exposes a device codec (`device_codec`, the
# jax_rs capability hook) for a call of this size — everything else (numpy
# routing, sub-chunk codes, non-RS plugins) returns None and the caller
# keeps the verified synchronous path.

def _device_codec(ec_impl, nbytes: int):
    probe = getattr(ec_impl, "device_codec", None)
    if probe is None or ec_impl.get_sub_chunk_count() != 1:
        return None
    return probe(int(nbytes))


def device_shard_crcs(chunks: dict[int, np.ndarray],
                      ec_impl) -> dict[int, int] | None:
    """The seed-free crc32c of each of ``chunks``' equal-length rows in
    ONE ``crc32c_rows`` dispatch, or None where the plugin has no device
    codec for them (numpy routing, uneven or empty rows).  Reads nothing
    but its arguments, so a full-object write computes it before the
    cluster lock (:meth:`ECBackend.prepare_write_full`) and
    :func:`hinfo_append` under it — same call, same bits."""
    lens = {len(v) for v in chunks.values()}
    if len(lens) != 1 or ec_impl is None:
        return None
    nbytes = lens.pop()
    if not nbytes or _device_codec(ec_impl, nbytes * len(chunks)) is None:
        return None
    shards = sorted(chunks)
    from ..ops import rs_kernels
    with trace_span("ec.hinfo_crc", rows=len(shards),
                    bytes=nbytes * len(shards)):
        rows = np.stack([_as_u8(chunks[s]) for s in shards])
        crc_dev = rs_kernels.crc32c_rows(rows)
        # the fetch is where the host blocks on the device
        with trace_span("ec.hinfo_crc.wait"):
            crc0 = np.asarray(crc_dev)
    return {s: int(c) for s, c in zip(shards, crc0)}


def hinfo_append(hinfo: HashInfo, old_size: int,
                 chunks: dict[int, np.ndarray], ec_impl=None,
                 crcs: dict[int, int] | None = None) -> None:
    """HashInfo maintenance with the checksum fused into a device
    dispatch: when the plugin has a device codec and the hashes are
    live, the appended chunk rows stack into ONE ``crc32c_rows`` call
    (:func:`device_shard_crcs`) and the seed-free results chain through
    :meth:`HashInfo.append_crcs` — no host crc loop over the shards.
    ``crcs`` are that call's results for exactly these ``chunks``,
    computed ahead of time; they are chained only where this function
    would have computed them itself.  Everything else (numpy routing,
    hash-less objects, uneven appends) falls through to the
    bitwise-identical :meth:`HashInfo.append`."""
    if not chunks:
        return
    if hinfo.has_chunk_hash():
        if crcs is None or crcs.keys() != chunks.keys():
            crcs = device_shard_crcs(chunks, ec_impl)
        if crcs is not None:
            hinfo.append_crcs(old_size, crcs,
                              len(next(iter(chunks.values()))))
            return
    hinfo.append(old_size, chunks)


def encode_many_pipelined(sinfo: StripeInfo, ec_impl,
                          bufs: list[bytes | np.ndarray], pipeline,
                          owner: str | None = None):
    """Async :func:`encode_many`: returns a ``PipelineFuture`` resolving
    to the identical per-buffer ``{chunk: bytes}`` list, or None when the
    codec has no device path.  Pack (shard-major relayout) runs now and
    overlaps in-flight device work; parity lands at the completion
    boundary."""
    if not bufs:
        return None
    k = ec_impl.get_data_chunk_count()
    n = ec_impl.get_chunk_count()
    arrs = []
    for data in bufs:
        buf = np.frombuffer(data, dtype=np.uint8) \
            if isinstance(data, (bytes, bytearray)) \
            else np.asarray(data, dtype=np.uint8)
        assert len(buf) % sinfo.stripe_width == 0, \
            f"len {len(buf)} not stripe aligned"
        arrs.append(buf)
    codec = _device_codec(ec_impl, sum(len(b) for b in arrs))
    if codec is None:
        return None
    shard_lens = [(len(b) // sinfo.stripe_width) * sinfo.chunk_size
                  for b in arrs]

    def pack():
        return _pack_shard_major(arrs, k, sinfo.chunk_size)

    def dispatch(data_shards):
        return pipeline.dispatch_encode(codec, data_shards,
                                        sinfo.chunk_size)

    def unpack(data_shards, parity):
        out: list[dict[int, np.ndarray]] = []
        off = 0
        for ln in shard_lens:
            chunks = {ec_impl.chunk_index(i): data_shards[i, off:off + ln]
                      for i in range(k)}
            for j in range(n - k):
                chunks[ec_impl.chunk_index(k + j)] = parity[j, off:off + ln]
            out.append(chunks)
            off += ln
        return out

    def host_fallback(data_shards):
        # breaker-open / device-failure path: same parity, host codec
        return pipeline.host_encode(codec, data_shards, sinfo.chunk_size)

    return pipeline.submit(pack, dispatch, unpack, kind="encode",
                           owner=owner, host_fallback=host_fallback,
                           ops=len(bufs))


def decode_many_pipelined(sinfo: StripeInfo, ec_impl,
                          batches: list[dict[int, np.ndarray]],
                          pipeline, pad_chunks=None,
                          chunk_size: int | None = None,
                          owner: str | None = None):
    """Async :func:`decode_many`: one pipeline item per distinct
    available-chunk signature.  Returns ``[(idxs, future), ...]`` where
    each future resolves to the logical bytes for those batch indices, or
    None when the codec has no device path."""
    if not batches:
        return None
    total_bytes = sum(sum(_as_u8(v).nbytes for v in chunks.values())
                      for chunks in batches)
    codec = _device_codec(ec_impl, total_bytes)
    if codec is None:
        return None
    by_sig: dict[frozenset, list[int]] = {}
    for i, chunks in enumerate(batches):
        by_sig.setdefault(frozenset(chunks), []).append(i)
    pending = []
    for sig, idxs in sorted(by_sig.items(), key=lambda kv: kv[1][0]):
        pending.append((list(idxs),
                        _submit_decode_group(sinfo, ec_impl, codec, batches,
                                             sig, idxs, pipeline, pad_chunks,
                                             chunk_size, owner)))
    return pending


def _submit_decode_group(sinfo, ec_impl, codec, batches, sig, idxs,
                         pipeline, pad_chunks, chunk_size,
                         owner: str | None = None):
    """One signature group's pack/dispatch/unpack trio, submitted."""
    k = ec_impl.get_data_chunk_count()

    def pack():
        concat, lens = _group_streams(
            [batches[i] for i in idxs], sig, pad_chunks=pad_chunks,
            quantum=chunk_size if chunk_size else sinfo.chunk_size)
        # wire ids are PHYSICAL; the codec's rows are LOGICAL
        avail_l, _ = ec_impl.remap_for_decode(concat, [])
        erasures_l = [i for i in range(k) if i not in avail_l]
        stack = None
        if erasures_l:
            _D, src = codec.decode_matrix(erasures_l,
                                          available=list(avail_l))
            stack = np.stack([avail_l[s] for s in src])
        return avail_l, erasures_l, stack, lens

    def dispatch(packed):
        avail_l, erasures_l, stack, _lens = packed
        if not erasures_l:
            return None                  # all data rows survived: host-only
        return pipeline.dispatch_decode(codec, stack, erasures_l,
                                        list(avail_l))

    def unpack(packed, rec):
        avail_l, erasures_l, _stack, lens = packed
        rows = {e: rec[i] for i, e in enumerate(erasures_l)} \
            if erasures_l else {}
        data = np.stack([avail_l[i] if i in avail_l else rows[i]
                         for i in range(k)])
        out: list[bytes] = []
        off = 0
        for ln in lens:
            out.append(_from_shard_major(
                np.ascontiguousarray(data[:, off:off + ln]),
                sinfo.chunk_size).tobytes())
            off += ln
        return out

    def host_fallback(packed):
        avail_l, erasures_l, stack, _lens = packed
        if not erasures_l:
            return None                  # host-only group either way
        return pipeline.host_decode(codec, stack, erasures_l,
                                    list(avail_l))

    return pipeline.submit(pack, dispatch, unpack, kind="decode",
                           owner=owner, host_fallback=host_fallback,
                           ops=len(idxs))


def decode(sinfo: StripeInfo, ec_impl,
           to_decode: dict[int, np.ndarray]) -> bytes:
    """Reconstruct the logical buffer from >=k shard chunk streams
    (ECUtil.cc:9-45), batched across all stripes in one decode call."""
    chunks = {i: _as_u8(v) for i, v in to_decode.items()}
    total = {len(v) for v in chunks.values()}
    assert len(total) == 1, "uneven shard buffers"
    decoded = ec_impl.decode_concat(chunks)
    k = ec_impl.get_data_chunk_count()
    total.pop()
    # reshape by row count, not input length: expanded (MBR) stored
    # chunks decode to SHORTER share streams than the stored input
    logical = _from_shard_major(
        np.frombuffer(decoded, dtype=np.uint8).reshape(k, -1),
        sinfo.chunk_size)
    return logical.tobytes()


def _group_streams(chunk_dicts: list[dict], sig,
                   pad_chunks=None, quantum: int | None = None
                   ) -> tuple[dict[int, np.ndarray], list[int]]:
    """Assemble one signature group's per-op shard streams into
    ``({chunk: concatenated [total] bytes}, per-op lens)`` — the ONE copy
    of the stacking/validation/size-bucket-padding logic shared by the
    sync and pipelined decode paths (they are asserted bitwise-identical,
    so they must assemble identically by construction).  ``pad_chunks``
    optionally rounds the group's total chunk count up (zero chunks
    decode to zero bytes — linear code — and the pad slices off)."""
    streams: dict[int, list[np.ndarray]] = {c: [] for c in sig}
    lens: list[int] = []
    for chunks in chunk_dicts:
        chunks = {c: _as_u8(v) for c, v in chunks.items()}
        sizes = {len(v) for v in chunks.values()}
        assert len(sizes) == 1, "uneven shard buffers"
        lens.append(sizes.pop())
        for c in sig:
            streams[c].append(chunks[c])
    total = sum(lens)
    if pad_chunks is not None and quantum and total % quantum == 0:
        padded = pad_chunks(total // quantum) * quantum
        if padded > total:
            pad = np.zeros(padded - total, dtype=np.uint8)
            for c in sig:
                streams[c].append(pad)
    return ({c: (np.concatenate(v) if len(v) > 1 else v[0])
             for c, v in streams.items()}, lens)


def decode_many(sinfo: StripeInfo, ec_impl,
                batches: list[dict[int, np.ndarray]],
                pad_chunks=None, chunk_size: int | None = None
                ) -> list[bytes]:
    """Decode MANY ops' shard chunk-dicts with ONE ``decode_concat`` per
    distinct available-chunk signature — the decode-side sibling of
    :func:`encode_many`.  Ops sharing a survivor set share a decode
    matrix, so their shard streams concatenate along the byte axis into
    one device dispatch; results split back per op, bit-identical to
    calling :func:`decode` per dict.

    ``pad_chunks(stripes) -> padded_stripes`` optionally rounds each
    group's total stripe count up (size bucketing: zero chunks decode to
    zero bytes — linear code — and the pad slices off exactly), keeping
    the jitted device path's shape set bounded."""
    if not batches:
        return []
    results: list[bytes | None] = [None] * len(batches)
    by_sig: dict[frozenset, list[int]] = {}
    for i, chunks in enumerate(batches):
        by_sig.setdefault(frozenset(chunks), []).append(i)
    k = ec_impl.get_data_chunk_count()
    for sig, idxs in by_sig.items():
        concat, lens = _group_streams(
            [batches[i] for i in idxs], sig, pad_chunks=pad_chunks,
            quantum=chunk_size if chunk_size else sinfo.chunk_size)
        decoded = np.frombuffer(
            ec_impl.decode_concat(concat), dtype=np.uint8).reshape(k, -1)
        off = 0
        for i, ln in zip(idxs, lens):
            logical = _from_shard_major(
                np.ascontiguousarray(decoded[:, off:off + ln]),
                sinfo.chunk_size)
            results[i] = logical.tobytes()
            off += ln
    return results


def decode_shards_many(sinfo: StripeInfo, ec_impl,
                       batches: list[tuple[dict[int, np.ndarray], set]],
                       pipeline=None, owner: str | None = "recovery"
                       ) -> list[dict[int, np.ndarray]]:
    """Reconstruct specific shards for MANY objects with ONE
    ``ec_impl.decode`` per distinct (survivor signature, want set) — the
    recovery-side sibling of :func:`decode_many`.  Parity is positionwise,
    so objects sharing both signatures share a decode matrix and their
    chunk streams concatenate along the byte axis into one device
    dispatch; results split back per object, bit-identical to calling
    :func:`decode_shards` per object.

    ``batches`` is ``[(available {chunk: bytes}, want set), ...]``.  Only
    valid for whole-chunk codes (``get_sub_chunk_count() == 1``) — clay's
    fractional repair reads are not positionwise across objects; callers
    gate on that and fall back to per-object :func:`decode_shards`.

    With a ``pipeline``, each (signature, want) group dispatches async
    through the device pipeline: group i+1's host pack overlaps group i's
    in-flight device reconstruct, and results fetch at the end — the
    repair-wave overlap the recovery scheduler rides."""
    if not batches:
        return []
    results: list[dict[int, np.ndarray] | None] = [None] * len(batches)
    by_sig: dict[tuple[frozenset, frozenset], list[int]] = {}
    for i, (available, want) in enumerate(batches):
        by_sig.setdefault((frozenset(available), frozenset(want)),
                          []).append(i)
    if pipeline is not None:
        pending = _decode_shards_groups_pipelined(sinfo, ec_impl, batches,
                                                  by_sig, pipeline, owner)
        if pending is not None:
            # every group is dispatched before the first fetch: the host
            # pack of later groups overlapped earlier device compute
            for idxs, fut in pending:
                for i, rec in zip(idxs, fut.result()):
                    results[i] = rec
            return results
    for (sig, want_sig), idxs in by_sig.items():
        want = set(want_sig)
        concat, lens = _group_streams([batches[i][0] for i in idxs], sig)
        decoded = ec_impl.decode(want, concat, 0)
        off = 0
        for i, ln in zip(idxs, lens):
            results[i] = {c: np.asarray(decoded[c], dtype=np.uint8)
                          [off:off + ln] for c in want}
            off += ln
    return results


def _decode_shards_groups_pipelined(sinfo, ec_impl, batches, by_sig,
                                    pipeline, owner: str | None = "recovery"):
    """Submit every (signature, want) recovery group through the device
    pipeline; ``[(idxs, future), ...]`` or None when no device path."""
    total_bytes = sum(sum(_as_u8(v).nbytes for v in avail.values())
                      for avail, _want in batches)
    codec = _device_codec(ec_impl, total_bytes)
    if codec is None:
        return None
    n = ec_impl.get_chunk_count()
    pending = []
    for (sig, want_sig), idxs in sorted(by_sig.items(),
                                        key=lambda kv: kv[1][0]):
        want = sorted(want_sig)

        def pack(sig=sig, want=want, idxs=idxs):
            concat, lens = _group_streams([batches[i][0] for i in idxs],
                                          sig)
            avail_l, want_l = ec_impl.remap_for_decode(concat, want)
            erasures_l = [i for i in range(n) if i not in avail_l]
            _D, src = codec.decode_matrix(erasures_l,
                                          available=list(avail_l))
            stack = np.stack([avail_l[s] for s in src])
            return erasures_l, want_l, list(avail_l), stack, lens

        def dispatch(packed):
            erasures_l, _want_l, avail_ids, stack, _lens = packed
            return pipeline.dispatch_decode(codec, stack, erasures_l,
                                            avail_ids)

        def unpack(packed, rec):
            erasures_l, want_l, _avail_ids, _stack, lens = packed
            rows = {e: rec[i] for i, e in enumerate(erasures_l)}
            out: list[dict[int, np.ndarray]] = []
            off = 0
            for ln in lens:
                out.append({ec_impl.chunk_index(w): rows[w][off:off + ln]
                            for w in want_l})
                off += ln
            return out

        def host_fallback(packed):
            erasures_l, _want_l, avail_ids, stack, _lens = packed
            return pipeline.host_decode(codec, stack, erasures_l,
                                        avail_ids)

        pending.append((list(idxs),
                        pipeline.submit(pack, dispatch, unpack,
                                        kind="recover", owner=owner,
                                        host_fallback=host_fallback,
                                        ops=len(idxs))))
    return pending


def decode_shards(sinfo: StripeInfo, ec_impl, available: dict[int, np.ndarray],
                  want: set, chunk_size: int = 0) -> dict[int, np.ndarray]:
    """Reconstruct specific shards (recovery path, ECUtil.cc:47-118 shape).

    ``chunk_size`` is the full per-shard size; when the available buffers are
    smaller, sub-chunk-aware codes (clay) route through their fractional
    repair path (ErasureCodeClay.cc:107-122)."""
    chunks = {i: _as_u8(v) for i, v in available.items()}
    return ec_impl.decode(set(want), chunks, chunk_size)


def partial_sum_accumulate(coeffs, stream, acc, pipeline=None,
                           owner: str | None = "recovery",
                           use_device: bool = False) -> list[bytes]:
    """One streaming-repair hop's partial-sum update: scale the hop's
    local chunk ``stream`` (every plan object concatenated) by its
    per-erased-row decode ``coeffs`` and XOR into ``acc``.

    ``acc`` is ``None`` on the first hop, else one running buffer per
    erased row.  Returns one bytes buffer per row.  With a ``pipeline``
    and ``use_device`` the single fused scale-accumulate dispatch rides
    the shared CodecPipeline — breaker, host fallback, and device-time
    attribution for free; otherwise (or when the breaker trips) the
    exact host GF math runs."""
    from ..gf import ref as gfref                       # noqa: F401 (host path)
    from ..ops import codec as _codec
    data = _as_u8(stream).reshape(1, -1)
    mat = np.asarray([[int(c) & 0xFF] for c in coeffs], dtype=np.uint8)
    acc_stack = None if acc is None \
        else np.stack([_as_u8(a) for a in acc])

    def _rows(out) -> list[bytes]:
        out = np.asarray(out, dtype=np.uint8)
        return [out[i].tobytes() for i in range(out.shape[0])]

    if pipeline is None or not use_device:
        return _rows(_codec.scale_accumulate_host(mat, data, acc_stack))

    def pack():
        return mat, data, acc_stack

    def dispatch(packed):
        m, d, a = packed
        return _codec.scale_accumulate_device(m, d, a)

    def unpack(packed, host):
        return _rows(host)

    def host_fallback(packed):
        m, d, a = packed
        return _codec.scale_accumulate_host(m, d, a)

    fut = pipeline.submit(pack, dispatch, unpack, kind="partial_sum",
                          owner=owner, host_fallback=host_fallback, ops=1)
    return fut.result()


def _gf_matmul_routed(mat: np.ndarray, data: np.ndarray, pipeline=None,
                      owner: str | None = "recovery",
                      use_device: bool = False) -> np.ndarray:
    """One GF(2^8) matrix product routed through the recovery
    CodecPipeline (breaker / host-fallback / attribution) when present,
    host otherwise — the shared engine under the regenerating-repair
    legs."""
    from ..ops import codec as _codec
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    if pipeline is None or not use_device:
        return _codec.gf_inner_product_host(mat, data)

    def pack():
        return mat, data

    def dispatch(packed):
        m, d = packed
        return _codec.gf_inner_product_device(m, d)

    def unpack(packed, host):
        return np.asarray(host, dtype=np.uint8)

    def host_fallback(packed):
        m, d = packed
        return _codec.gf_inner_product_host(m, d)

    fut = pipeline.submit(pack, dispatch, unpack, kind="regen",
                          owner=owner, host_fallback=host_fallback, ops=1)
    return fut.result()


def regen_project(coeffs: bytes | np.ndarray, stream, sub_count: int,
                  pipeline=None, owner: str | None = "recovery",
                  use_device: bool = False) -> bytes:
    """One helper's regenerating-repair leg: project the stored chunk's
    ``sub_count`` symbol rows down to the single beta-stream
    ``psi_f . chunk`` it ships to the newcomer (len(stream)/sub_count
    bytes — the d-fold wire saving the product-matrix code exists
    for)."""
    data = _as_u8(stream)
    assert data.size % sub_count == 0, "chunk not sub-chunk aligned"
    mat = np.frombuffer(bytes(coeffs), dtype=np.uint8).reshape(1, sub_count)
    out = _gf_matmul_routed(mat, data.reshape(sub_count, -1),
                            pipeline=pipeline, owner=owner,
                            use_device=use_device)
    return out.reshape(-1).tobytes()


def regen_combine(mat: bytes | np.ndarray, streams: list, sub_count: int,
                  pipeline=None, owner: str | None = "recovery",
                  use_device: bool = False) -> bytes:
    """The newcomer's regenerating-repair leg: combine the d stacked
    helper beta-streams into the lost chunk's ``sub_count`` symbol rows
    (bitwise-exact repair)."""
    stack = np.stack([_as_u8(s) for s in streams])
    m = np.frombuffer(bytes(mat), dtype=np.uint8).reshape(sub_count,
                                                          len(streams))
    out = _gf_matmul_routed(m, stack, pipeline=pipeline, owner=owner,
                            use_device=use_device)
    return out.reshape(-1).tobytes()


HINFO_KEY = "hinfo_key"  # xattr name (ECUtil.cc:235, get_hinfo_key)
