"""RSCodec: the device-resident Reed-Solomon codec.

Combines host-side matrix algebra (construction + erasure-signature-cached
inversion, mirroring the isa plugin's table cache,
reference: src/erasure-code/isa/ErasureCodeIsaTableCache.h:35-65) with the
jit'd device kernels from rs_kernels.  Shapes are static per (k, m, N);
matrices are traced, so one compilation covers all erasure signatures.
"""
from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..common import device_attribution
from ..common.tracer import trace_span
from ..gf import matrix as gfm
from ..gf import ref as gfref
from . import rs_kernels

TECHNIQUES = {
    "reed_sol_van": gfm.rs_vandermonde_jerasure,
    "vandermonde": gfm.rs_vandermonde_isa,
    "cauchy": gfm.cauchy1,
}

# Matches the isa decode-table LRU capacity, "sufficient up to (12,4)"
# (reference: src/erasure-code/isa/ErasureCodeIsaTableCache.h:46-48).
DECODE_CACHE_SIZE = 2516


class _DecodeTables:
    """One signature's cached decode state: the host matrix, the source
    chunk order, and — uploaded lazily, then pinned for the LRU entry's
    lifetime — the device-resident copy.  The device copy is what keeps
    an LRU *hit* from paying a host->device matrix transfer per call."""

    __slots__ = ("D", "src", "dev")

    def __init__(self, D: np.ndarray, src: list[int]):
        self.D = D
        self.src = src
        self.dev: jax.Array | None = None


@functools.partial(jax.jit, static_argnames=("variant",))
def _gf_scale_accumulate(mat, data, acc, variant):
    """One chained-repair hop's partial-sum update: ``mat @ data XOR acc``
    over GF(2^8) — the survivor scales its local chunk by its decode
    coefficients and folds it into the running sum in a single fused
    dispatch (no intermediate host round-trip)."""
    return jnp.bitwise_xor(rs_kernels.gf_apply(mat, data, variant), acc)


def scale_accumulate_device(mat, data, acc, variant: str = "auto"):
    """Device scale-accumulate for a chain hop: ``mat`` [r, 1] decode
    coefficients, ``data`` [1, N] the hop's local chunk stream, ``acc``
    [r, N] running partial sums (or None on the first hop) -> [r, N] on
    device.  One jitted dispatch either way; the shapes are static per
    (r, N) so chains over a wave share a single compilation."""
    if acc is None:
        return rs_kernels.gf_apply(jnp.asarray(mat), jnp.asarray(data),
                                   variant)
    return _gf_scale_accumulate(jnp.asarray(mat), jnp.asarray(data),
                                jnp.asarray(acc), variant)


def scale_accumulate_host(mat: np.ndarray, data: np.ndarray,
                          acc: np.ndarray | None) -> np.ndarray:
    """Exact host sibling of :func:`scale_accumulate_device` (breaker
    fallback and the no-pipeline path)."""
    out = gfref.apply_matrix_fast(
        np.ascontiguousarray(mat, dtype=np.uint8),
        np.ascontiguousarray(data, dtype=np.uint8))
    if acc is not None:
        np.bitwise_xor(out, acc, out=out)
    return out


@functools.partial(jax.jit, static_argnames=("variant",))
def _gf_inner_product(mat, data, variant):
    """Regenerating-repair inner product: ``mat @ data`` over GF(2^8) in
    one fused dispatch.  ``mat`` is a helper's projection row (1 x alpha)
    or the newcomer's combine matrix (alpha x d); ``data`` is the stored
    chunk's symbol rows (alpha x N) or the stacked helper beta-streams
    (d x N).  Shapes are static per (rows, N), so every helper in a wave
    shares one compilation."""
    return rs_kernels.gf_apply(mat, data, variant)


def gf_inner_product_device(mat, data, variant: str = "auto"):
    """Device GF matrix-vector product for the product-matrix repair legs
    (helper projection and newcomer combine) -> jax.Array [rows, N]."""
    return _gf_inner_product(jnp.asarray(mat), jnp.asarray(data), variant)


def gf_inner_product_host(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Exact host sibling of :func:`gf_inner_product_device` (breaker
    fallback and the no-pipeline path)."""
    return gfref.apply_matrix_fast(
        np.ascontiguousarray(mat, dtype=np.uint8),
        np.ascontiguousarray(data, dtype=np.uint8))


class RSCodec:
    """Systematic RS(k, m) over GF(2^8), poly 0x11D.

    device='jax' runs the jit'd TPU kernels; device='numpy' is the exact CPU
    fallback used for latency-bound single small stripes.
    """

    def __init__(self, k: int, m: int, technique: str = "reed_sol_van",
                 device: str = "jax", variant: str = "auto"):
        if k < 2 or m < 1 or k + m > 256:
            raise ValueError(f"bad RS parameters k={k} m={m}")
        if technique not in TECHNIQUES:
            raise ValueError(f"unknown technique {technique!r}")
        if technique == "vandermonde":
            # ISA-L's geometric-progression matrix is only MDS inside this
            # envelope (reference: src/erasure-code/isa/ErasureCodeIsa.cc:323-364).
            if k > 32 or m > 4 or (m == 4 and k > 21):
                raise ValueError(
                    f"technique 'vandermonde' requires k<=32, m<=4 "
                    f"(m=4 => k<=21); got k={k} m={m}")
        self.k, self.m, self.technique = k, m, technique
        self.device, self.variant = device, variant
        self.parity_mat = TECHNIQUES[technique](k, m)          # [m, k] uint8
        self._parity_dev = None
        self._decode_cache: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()
        # host->device table-transfer counters: the pipeline tests assert
        # an LRU hit costs ZERO uploads (the serving/recovery hot paths
        # must never re-upload a decode matrix per call)
        self.parity_uploads = 0
        self.decode_table_uploads = 0

    # -- encode ------------------------------------------------------------

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data [k, N] (or [B, k, N]) uint8 -> parity [m, N] (or [B, m, N])."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        if data.ndim == 3:
            b, k, n = data.shape
            out = self.encode(np.swapaxes(data, 0, 1).reshape(k, b * n))
            return np.swapaxes(out.reshape(self.m, b, n), 0, 1)
        with trace_span("codec.encode", k=self.k, m=self.m,
                        n=int(data.shape[1]), device=self.device):
            if self.device == "numpy":
                return gfref.apply_matrix_fast(self.parity_mat, data)
            self._upload_parity()
            # synchronous dispatch: the launch-return -> fetch interval is
            # device occupancy, charged to the caller's owner class (the
            # pipeline path accounts at its own completion boundary).  The
            # mark is taken AFTER the launch returns: a first-call launch
            # runs trace+XLA compile synchronously, and that host-side
            # interval must not inflate device busy time.
            out = rs_kernels.gf_apply(self._parity_dev, data, self.variant)
            t0 = device_attribution.dispatch_mark()
            host = np.asarray(jax.device_get(out))
            device_attribution.record_batch(None, t0, host.nbytes)
            return host

    def encode_with_crc(self, data: np.ndarray):
        """Fused encode + checksum: parity [m, N] uint8 AND the
        crc32c(0, row) of every row of concat(data, parity) as a
        [k + m] uint32 array, ONE jitted dispatch (the checksum pass
        rides the rows the encode just produced instead of a host
        crc loop over fetched shards).  Seed-free crcs: callers chain
        them into ceph's running HashInfo semantics with
        ``ecutil.crc32c_zeros`` (see :meth:`HashInfo.append_crcs`)."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        with trace_span("codec.encode_with_crc", k=self.k, m=self.m,
                        n=int(data.shape[1]), device=self.device):
            if self.device == "numpy":
                from ..backend import ecutil
                parity = gfref.apply_matrix_fast(self.parity_mat, data)
                crcs = np.array(
                    [ecutil.crc32c(0, bytes(r))
                     for r in np.concatenate([data, parity], axis=0)],
                    dtype=np.uint32)
                return parity, crcs
            self._upload_parity()
            parity, crcs = rs_kernels.gf_encode_with_crc(
                self._parity_dev, data, self.variant)
            t0 = device_attribution.dispatch_mark()
            parity_h = np.asarray(jax.device_get(parity))
            crcs_h = np.asarray(jax.device_get(crcs))
            device_attribution.record_batch(None, t0, parity_h.nbytes)
            return parity_h, crcs_h

    def encode_host(self, data: np.ndarray) -> np.ndarray:
        """Pure-host parity (the exact CPU reference path) REGARDLESS of
        ``self.device`` — the circuit breaker's fallback when the device
        side is failing: data [k, N] uint8 -> parity [m, N]."""
        with trace_span("codec.encode_host", k=self.k, m=self.m,
                        n=int(data.shape[-1])):
            return gfref.apply_matrix_fast(
                self.parity_mat, np.ascontiguousarray(data,
                                                      dtype=np.uint8))

    def decode_host(self, stack: np.ndarray, erasures: list[int],
                    available: list[int]) -> np.ndarray:
        """Pure-host recovery, device never touched: ``stack`` [k', N]
        survivors already in the ``src`` order ``decode_matrix(erasures,
        available)`` returns -> recovered rows [len(erasures), N].  The
        host sibling of :meth:`decode_device` for breaker fallback."""
        entry = self._decode_entry(sorted(int(e) for e in erasures),
                                   available=list(available))
        with trace_span("codec.decode_host", k=self.k, m=self.m,
                        n=int(stack.shape[-1]), erasures=len(erasures)):
            return gfref.apply_matrix_fast(
                entry.D, np.ascontiguousarray(stack, dtype=np.uint8))

    def _upload_parity(self) -> None:
        if self._parity_dev is None:
            with trace_span("codec.table_upload",
                            bytes=int(self.parity_mat.nbytes)):
                self._parity_dev = jnp.asarray(self.parity_mat)
                self.parity_uploads += 1

    def encode_device(self, data: jax.Array) -> jax.Array:
        """Device-to-device encode (no host transfer), for pipeline use."""
        self._upload_parity()
        return rs_kernels.gf_apply(self._parity_dev, data, self.variant)

    # -- decode ------------------------------------------------------------

    def _decode_entry(self, erasures, available=None) -> _DecodeTables:
        """Signature-LRU lookup/build of the shared decode state."""
        sig = (tuple(sorted(int(e) for e in erasures)),
               None if available is None else tuple(sorted(int(a) for a in available)))
        with self._lock:
            hit = self._decode_cache.get(sig)
            if hit is not None:
                self._decode_cache.move_to_end(sig)
                return hit
        with trace_span("codec.decode_matrix_build", k=self.k, m=self.m,
                        erasures=len(sig[0])):
            D, src = gfm.decode_matrix(self.parity_mat, list(erasures),
                                       available)
        entry = _DecodeTables(D, src)
        with self._lock:
            entry = self._decode_cache.setdefault(sig, entry)
            self._decode_cache.move_to_end(sig)
            if len(self._decode_cache) > DECODE_CACHE_SIZE:
                self._decode_cache.popitem(last=False)
        return entry

    def decode_matrix(self, erasures, available=None):
        """Signature-LRU-cached (decode matrix, source chunk list)."""
        entry = self._decode_entry(erasures, available)
        return entry.D, entry.src

    def decode_matrix_device(self, erasures, available=None):
        """Like :meth:`decode_matrix` but the matrix is the DEVICE-resident
        copy, uploaded once per LRU entry: an LRU hit costs zero
        host->device transfers (the re-upload-per-call bug the pipeline
        tests pin via ``decode_table_uploads``)."""
        entry = self._decode_entry(erasures, available)
        return self._entry_device(entry), entry.src

    def _entry_device(self, entry: _DecodeTables) -> jax.Array:
        """Pin (lazily uploading) an already-fetched entry's device copy —
        one LRU lookup per decode call, not two."""
        if entry.dev is None:
            # upload outside the lock (it can be slow), publish under it:
            # two threads racing a fresh signature upload twice but count
            # once, and the pinned copy is whichever published first
            with trace_span("codec.table_upload", bytes=int(entry.D.nbytes)):
                dev = jnp.asarray(entry.D)
            with self._lock:
                if entry.dev is None:
                    entry.dev = dev
                    self.decode_table_uploads += 1
        return entry.dev

    def decode(self, chunks: dict[int, np.ndarray],
               erasures: list[int]) -> dict[int, np.ndarray]:
        """Recover the erased chunk indices from surviving chunks.

        chunks: {index: [N] uint8} (>= k survivors), erasures: lost indices.
        """
        erasures = sorted(int(e) for e in erasures)
        if not erasures:
            return {}
        entry = self._decode_entry(erasures, available=list(chunks))
        stack = np.stack([np.asarray(chunks[i], dtype=np.uint8)
                          for i in entry.src])
        with trace_span("codec.decode", k=self.k, m=self.m,
                        n=int(stack.shape[1]), erasures=len(erasures),
                        device=self.device):
            if self.device == "numpy":
                rec = gfref.apply_matrix_fast(entry.D, stack)
            else:
                # mark after the launch returns (compile time is host time)
                out = rs_kernels.gf_apply(self._entry_device(entry), stack,
                                          self.variant)
                t0 = device_attribution.dispatch_mark()
                rec = np.asarray(jax.device_get(out))
                device_attribution.record_batch(None, t0, rec.nbytes)
        return {e: rec[i] for i, e in enumerate(erasures)}

    @staticmethod
    def _src_index_map(src: list[int],
                       src_expected: list[int]) -> list[int] | None:
        """Row gather mapping caller order -> decode_matrix order, or None
        when it is the identity over a prefix (precomputed in O(k) — the
        per-element ``src.index(s)`` scan was O(k^2) per batch)."""
        if src == src_expected:
            return None
        pos = {s: i for i, s in enumerate(src)}
        idx = [pos[s] for s in src_expected]
        if idx == list(range(len(idx))):
            return None          # identity after dropping extras: slice, no gather
        return idx

    def decode_batch(self, stack: np.ndarray, src: list[int],
                     erasures: list[int]) -> np.ndarray:
        """Batched decode with one shared erasure signature.

        stack: [B, k, N] survivors in ``src`` order -> [B, len(erasures), N].
        """
        src = [int(s) for s in src]
        entry = self._decode_entry(erasures, available=src)
        idx = self._src_index_map(src, entry.src)
        if idx is not None:
            stack = stack[:, idx, :]
        elif len(entry.src) != stack.shape[1]:
            stack = stack[:, :len(entry.src), :]     # drop extras: a view
        b, k, n = stack.shape
        folded = np.ascontiguousarray(
            np.swapaxes(stack, 0, 1).reshape(k, b * n), dtype=np.uint8)
        with trace_span("codec.decode_batch", k=self.k, m=self.m,
                        batch=int(b), n=int(n), erasures=len(erasures),
                        device=self.device):
            if self.device == "numpy":
                rec = gfref.apply_matrix_fast(entry.D, folded)
            else:
                # mark after the launch returns (compile time is host time)
                out = rs_kernels.gf_apply(self._entry_device(entry), folded,
                                          self.variant)
                t0 = device_attribution.dispatch_mark()
                rec = np.asarray(jax.device_get(out))
                device_attribution.record_batch(None, t0, rec.nbytes)
        return np.swapaxes(rec.reshape(len(erasures), b, n), 0, 1)

    # -- device-resident decode (no host round-trip; pipeline path) --------

    def decode_device(self, stack: jax.Array, erasures: list[int],
                      available: list[int] | None = None) -> jax.Array:
        """Device-to-device decode: ``stack`` [k, N] survivors already in
        the sorted-src order ``decode_matrix(erasures, available)``
        returns -> recovered rows [len(erasures), N], still on device.
        No ``device_get`` and no matrix re-upload — the decode matrix
        rides the signature LRU's device copy."""
        erasures = sorted(int(e) for e in erasures)
        D_dev, src = self.decode_matrix_device(erasures, available)
        if int(stack.shape[0]) != len(src):
            raise ValueError(
                f"stack has {stack.shape[0]} rows for {len(src)} sources")
        return rs_kernels.gf_apply(D_dev, stack, self.variant)

    def decode_batch_device(self, stack: jax.Array, src: list[int],
                            erasures: list[int]) -> jax.Array:
        """Device-to-device batched decode: ``stack`` [B, k', N] survivors
        in ``src`` order -> [B, len(erasures), N] on device.  The row
        permutation, fold and unfold all run as device ops, so nothing
        touches the host."""
        src = [int(s) for s in src]
        erasures = sorted(int(e) for e in erasures)
        D_dev, src_expected = self.decode_matrix_device(erasures,
                                                        available=src)
        idx = self._src_index_map(src, src_expected)
        if idx is not None:
            stack = jnp.take(stack, jnp.asarray(idx), axis=1)
        elif len(src_expected) != int(stack.shape[1]):
            stack = stack[:, :len(src_expected), :]
        b, k, n = (int(s) for s in stack.shape)
        folded = jnp.swapaxes(stack, 0, 1).reshape(k, b * n)
        rec = rs_kernels.gf_apply(D_dev, folded, self.variant)
        return jnp.swapaxes(rec.reshape(len(erasures), b, n), 0, 1)
