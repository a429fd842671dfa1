"""TPU device kernels for GF(2^8) matrix application (RS encode/decode).

The one primitive both encode and decode need is

    out[i, :] = XOR_j  mat[i, j] * data[j, :]     (GF(2^8))

with ``mat`` tiny ([m, k] for encode, [n_lost, k] for decode) and ``data``
huge ([k, N] bytes).  Two TPU-first realisations, both jit'd with the matrix
as a *traced* argument so a single compilation per (r, k, N) shape serves
every coefficient matrix and every erasure signature (the reference instead
caches per-signature CPU decode tables, src/erasure-code/isa/ErasureCodeIsa.cc:227-304):

- ``bitslice``: expand the GF(2^8) matrix to its GF(2) bit-matrix [8r, 8k]
  (each coefficient becomes the 8x8 binary matrix of "multiply by c"), unpack
  data bytes to bit-planes, and compute the GF(2) product as a bf16 matmul on
  the MXU with f32 accumulation (exact: 0/1 values, <=2^8 terms), then mod-2
  and repack.  This turns erasure coding into the MXU's native operation.
- ``lookup``: gather-based VPU path: per-coefficient 256-entry product tables
  (rows of the global 256x256 table) indexed by the data bytes, XOR-reduced
  over j.  Fewer memory blowups, no MXU; wins for small r*k.

Data layout convention everywhere: uint8 arrays [chunks, chunk_bytes]; a
batch of stripes is folded into the byte axis (the matrix is the same for
every stripe, so [k, B*N] == B stripes of [k, N]).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..gf.tables import MUL_TABLE
from .traced_jit import traced_jit

def _mul_dev():
    """The 256x256 GF(2^8) product table as a trace-time constant (64 KiB)."""
    return jnp.asarray(MUL_TABLE)


def expand_bits_raw(mat: jax.Array) -> jax.Array:
    """Traced GF(2^8) matrix [r, k] -> GF(2) bits [r, bi, k, bj] (uint8 0/1):
    bit bi of (mat[i,j] * 2^bj).  Shared by the interleaved (XLA bitslice)
    and plane-major (pallas) layouts, which differ only in the final
    reshape."""
    powers = jnp.asarray([1 << j for j in range(8)], dtype=jnp.uint8)
    # mv[i, j, bj] = mat[i,j] * 2^bj in GF(2^8)
    mv = _mul_dev()[mat.astype(jnp.int32)[:, :, None],
                    powers.astype(jnp.int32)[None, None, :]]
    bi = jnp.arange(8, dtype=jnp.uint8)[None, :, None, None]
    return (mv[:, None, :, :] >> bi) & 1          # [r, bi, k, bj]


def _expand_bits_device(mat: jax.Array) -> jax.Array:
    """Interleaved layout [8r, 8k]: B[8i+bi, 8j+bj]."""
    r, k = mat.shape
    return expand_bits_raw(mat).reshape(8 * r, 8 * k)


def _unpack_bits(data: jax.Array) -> jax.Array:
    """uint8 [k, N] -> bit-planes [8k, N] (row 8j+bj = bit bj of chunk j)."""
    k, n = data.shape
    bj = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
    bits = (data[:, None, :] >> bj) & 1           # [k, 8, N]
    return bits.reshape(8 * k, n)


def _pack_bits(bits: jax.Array) -> jax.Array:
    """int32 bit-planes [8r, N] -> uint8 [r, N]."""
    rr, n = bits.shape
    r = rr // 8
    w = jnp.asarray([1 << i for i in range(8)], dtype=jnp.int32)[None, :, None]
    return (bits.reshape(r, 8, n) * w).sum(axis=1).astype(jnp.uint8)


@traced_jit
def gf_apply_bitslice(mat: jax.Array, data: jax.Array) -> jax.Array:
    """MXU path: out = mat @GF data via GF(2) bf16 matmul."""
    B = _expand_bits_device(mat).astype(jnp.bfloat16)      # [8r, 8k]
    x = _unpack_bits(data).astype(jnp.bfloat16)            # [8k, N]
    acc = jax.lax.dot_general(
        B, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # exact integer sums
    bits = acc.astype(jnp.int32) & 1                       # mod 2
    return _pack_bits(bits)


@traced_jit
def gf_apply_lookup(mat: jax.Array, data: jax.Array) -> jax.Array:
    """VPU path: per-coefficient 256-entry product-table gathers, XOR-reduced."""
    tables = _mul_dev()[mat.astype(jnp.int32)]             # [r, k, 256]

    def one(tab_j, d_j):                                   # [r,256], [N] -> [r,N]
        return jnp.take(tab_j, d_j.astype(jnp.int32), axis=1)

    terms = jax.vmap(one, in_axes=(1, 0))(tables, data)    # [k, r, N]
    return jax.lax.reduce(terms, np.uint8(0), jax.lax.bitwise_xor, [0])


@traced_jit
def xor_reduce(data: jax.Array) -> jax.Array:
    """XOR of all chunk rows: [k, N] -> [1, N] (m=1 / parity-row-of-ones path,
    cf. the isa plugin's region_xor short-circuit, ErasureCodeIsa.cc:119-131)."""
    return jax.lax.reduce(data, np.uint8(0), jax.lax.bitwise_xor, [0])[None, :]


def _runs_on_tpu(data) -> bool:
    """Where will this op execute?  For concrete arrays the committed
    device wins (a CPU-committed array on a TPU host runs on CPU, where
    the Mosaic kernel cannot lower).  A traced array has no committed
    device to inspect (``Tracer.devices()`` raises), so under jit the
    runtime's default device decides — every jitted caller, the codec
    pipeline included, reaches the pallas kernel this way.  Jitting over
    a CPU-committed array on a TPU host is unsupported (pass
    variant='bitslice' explicitly for that).  A backend that fails to
    initialise raises here: there is no pretending to be a CPU."""
    if not isinstance(data, jax.core.Tracer):
        devices = getattr(data, "devices", None)
        devs = devices() if callable(devices) else None
        if devs:
            return all(d.platform == "tpu" for d in devs)
    return jax.devices()[0].platform == "tpu"


def gf_apply_stripes(mat, data, stripes: int, variant: str = "auto"):
    """Batched GF apply over the VERTICAL stripe layout: data
    [stripes*k, Nc] -> [stripes*r, Nc] (stripe s = rows [s*k, (s+1)*k)).

    This is the codec's device-native batch layout (stripes stack as rows,
    a no-copy append for the IO path) and the fast path on TPU: tall
    blocks + block-diagonal int8 MXU matmuls (see
    pallas_kernels.gf_apply_stripes_pallas).  Off-TPU it folds back to the
    horizontal layout and reuses the XLA paths.
    """
    mat = jnp.asarray(mat, dtype=jnp.uint8)
    data = jnp.asarray(data, dtype=jnp.uint8)
    r, k = mat.shape
    rows, n = data.shape
    assert rows == stripes * k
    if variant in ("auto", "pallas") and _runs_on_tpu(data) and n >= 1024:
        from .pallas_kernels import gf_apply_stripes_pallas
        return gf_apply_stripes_pallas(mat, data, stripes)
    # fallback: [S*k, N] -> [k, S*N] -> gf_apply -> [S*r, N]
    folded = data.reshape(stripes, k, n).transpose(1, 0, 2).reshape(k, -1)
    out = gf_apply(mat, folded, variant)
    return out.reshape(r, stripes, n).transpose(1, 0, 2).reshape(
        stripes * r, n)


def gf_apply(mat, data, variant: str = "auto"):
    """Apply a GF(2^8) matrix to chunk data on the device.

    mat: [r, k] uint8 (numpy or jax), data: [k, N] uint8 -> [r, N] uint8.
    variant: 'pallas' (fused TPU kernel), 'bitslice' (MXU via XLA),
    'lookup' (VPU), or 'auto'.
    """
    mat = jnp.asarray(mat, dtype=jnp.uint8)
    data = jnp.asarray(data, dtype=jnp.uint8)
    if variant == "auto":
        # Fused pallas pipeline on TPU (unpacked bit-planes never
        # round-trip HBM); XLA paths elsewhere.  Tiny matrices with short
        # rows stay on the VPU lookup path where the MXU can't amortise
        # its unpack.
        if mat.shape[0] * mat.shape[1] < 8:
            variant = "lookup"
        elif _runs_on_tpu(data) and data.shape[1] >= 1024:
            variant = "pallas"
        else:
            variant = "bitslice"
    if variant == "pallas":
        from .pallas_kernels import gf_apply_pallas
        # trace-time metadata only, and OUTSIDE the kernel's own jit: the
        # TPU compiler names the Mosaic custom call after the innermost
        # scope, so a scope inside gf_apply_pallas would rename the
        # instruction profile readers match on (ISSUE 25)
        with jax.named_scope("ceph.gf_apply"):
            return gf_apply_pallas(mat, data)
    if variant == "bitslice":
        return gf_apply_bitslice(mat, data)
    if variant == "lookup":
        return gf_apply_lookup(mat, data)
    raise ValueError(f"unknown variant {variant!r}")


def xor_apply(W, packets, variant: str = "auto"):
    """GF(2) XOR-matmul on the MXU: out[r] = XOR over i with W[r,i]==1 of
    packets[i], bytewise.  variant: 'pallas' (fused kernel — honoured
    unconditionally, like gf_apply), 'xla', or 'auto' (pallas on TPU for
    wide rows, XLA elsewhere).

    W: [R, K] 0/1 uint8, packets: [K, P] uint8 -> [R, P] uint8.  The device
    path for bitmatrix codes (liberation/blaum_roth/liber8tion — see
    gf/bitmatrix.py): a byte XOR is 8 independent GF(2) sums, so unpack the
    bit-planes along the column axis, run ONE int8 matmul (exact: 0/1
    values, <= K terms in int32), take mod 2, and repack.
    """
    W = jnp.asarray(W, dtype=jnp.int8)
    packets = jnp.asarray(packets, dtype=jnp.uint8)
    if variant == "pallas" or (variant == "auto" and _runs_on_tpu(packets)
                               and packets.shape[1] >= 1024):
        from .pallas_kernels import xor_apply_pallas
        return xor_apply_pallas(W, packets)
    if variant not in ("auto", "xla"):
        raise ValueError(f"unknown variant {variant!r}")
    return _xor_apply_xla(W, packets)


def bitplane_xor_matmul(W, d):
    """The shared core: uint8 columns -> 8 bit-planes -> ONE int8 matmul
    -> mod 2 -> repacked bytes.  Used by the jitted XLA path AND the
    pallas kernel body (both operate on plain jnp values)."""
    p = d.shape[1]
    planes = jnp.concatenate(
        [(d >> b) & 1 for b in range(8)], axis=1).astype(jnp.int8)
    acc = jax.lax.dot_general(
        W, planes, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32) & 1            # [R, 8P]
    out = acc[:, :p]
    for b in range(1, 8):
        out = out | (acc[:, b * p:(b + 1) * p] << b)
    return out.astype(jnp.uint8)


@traced_jit
def _xor_apply_xla(W, packets):
    return bitplane_xor_matmul(W, packets)


# -- crc32c of rows: the HashInfo checksum as word arithmetic -----------------
#
# crc32c is GF(2)-linear in the data bits once the seed is factored out
# (backend/ecutil.crc32c_zeros).  With Z_L the 32x32 GF(2) matrix that
# advances a register through L zero bytes, four little-endian bytes
# taken as one uint32 w give  crc32c(0, w) = Z_4(w),  so a row of W words is
#
#     crc32c(0, row) = Z_4( XOR_j  Z_{4(W-1-j)}(w_j) )
#
# and every Z commutes with every other (powers of one operator).  The
# sum therefore folds by CONTIGUOUS halves, largest distance first:
# c = Z_{4h}(c[:h]) ^ c[h:].  The words lie as [r, a, 128]: the levels
# over ``a`` cut whole (8, 128) tiles apart, the last seven run over the
# 128 lanes of a [r, 128] remainder, and Z_4 is applied once, to the r
# results.  W operator applications a row in all; no table, no
# gather, no lane-strided slice.  Z_L is 32 masked XORs of trace-time
# constants (register bit i set -> XOR in the image of bit i): plain
# uint32 VPU work on the array itself.  Rows pad with zero bytes on the
# LEFT to a power of two: leading zeros are free for a zero register,
# and every level stays an exact halving (static shapes, one compilation
# per (r, n)).
#
# Only distances that are whole words occur, and _crc_advance refuses
# any other.  On a v5e (PR 26's chip runs) the same fold over byte-wide
# registers, whose last levels apply Z_1 and Z_2 (most of their images
# one bit: a pure shift), came back from one fused program with wrong
# bits 16-22 for r = 5 and r = 8, while every level jitted alone and
# every single Z_L was right -- the kind of thing PR 21 found here of a
# bit-plane matmul.  Every image of a Z_4m is dense.  chip_smoke.py
# compares this kernel with the host's crc on the chip.

_CRC_LANES = 128


def _crc_pad(n: int) -> int:
    """Bytes a row of n is left-padded to: a power of two, a word at least."""
    return max(4, 1 << (n - 1).bit_length())


def _crc_words_shape(pad: int) -> tuple[int, int]:
    """[a, lanes] of a padded row's pad // 4 words."""
    lanes = min(_CRC_LANES, pad // 4)
    return pad // 4 // lanes, lanes


def _crc_advance(regs: jax.Array, nbytes: int) -> jax.Array:
    """Z_nbytes on a uint32 array of crc32c registers, of any shape."""
    from ..backend import ecutil
    if nbytes % 4:
        raise ValueError(f"crc fold distance {nbytes} is no whole word")
    out = jnp.zeros_like(regs)
    for i, image in enumerate(ecutil.crc32c_zeros_op(nbytes)):
        # 0 - bit is all-ones where register bit i is set
        mask = jnp.uint32(0) - ((regs >> jnp.uint32(i)) & jnp.uint32(1))
        out = out ^ (mask & jnp.uint32(image))
    return out


def _crc_fold(words: jax.Array) -> jax.Array:
    """Traced: uint32 [r, a, lanes] -> uint32 [r], the crc32c(0, .) of
    each row's bytes, given as little-endian words in row order (a and
    lanes powers of two)."""
    # a stable name in every op's metadata (trace-time only): a profile
    # reader can find the checksum without knowing its shapes
    with jax.named_scope("ceph.crc32c_rows"):
        _, a, lanes = words.shape
        while a > 1:
            a //= 2
            words = _crc_advance(words[:, :a], 4 * lanes * a) ^ words[:, a:]
        c = words[:, 0]
        while lanes > 1:
            lanes //= 2
            c = _crc_advance(c[:, :lanes], 4 * lanes) ^ c[:, lanes:]
        return _crc_advance(c[:, 0], 4)


def _crc_rows_body(rows: jax.Array, pad: int) -> jax.Array:
    """Traced body: uint8 [r, n] -> uint32 [r] of crc32c(0, row); the
    bytes become words on the device."""
    with jax.named_scope("ceph.crc32c_rows"):
        r, n = rows.shape
        if pad > n:
            rows = jnp.concatenate(
                [jnp.zeros((r, pad - n), dtype=jnp.uint8), rows], axis=1)
        words = jax.lax.bitcast_convert_type(
            rows.reshape(r, *_crc_words_shape(pad), 4), jnp.uint32)
    return _crc_fold(words)


@functools.partial(jax.jit, static_argnames=("pad",))
def _crc32c_rows_jit(rows, pad):
    return _crc_rows_body(rows, pad)


_crc32c_words_jit = jax.jit(_crc_fold)


def crc32c_rows(rows) -> jax.Array:
    """Device crc32c(seed=0) of each row of a uint8 [r, n] array, in one
    jitted dispatch.  Seed-chained ceph semantics are the caller's host
    combine: ``crc32c(seed, row) == crc32c_zeros(seed, n) ^ crc32c_rows(rows)[i]``.

    A host array goes up as the little-endian words it already is (a
    view; a copy only where n is no power of two), which spares the
    device the uint8 -> uint32 relayout; a device array takes
    :func:`_crc_rows_body`, as the fused encode dispatch does."""
    if isinstance(rows, jax.Array):
        rows = rows.astype(jnp.uint8)
        return _crc32c_rows_jit(rows, _crc_pad(rows.shape[1]))
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    r, n = rows.shape
    pad = _crc_pad(n)
    if pad > n:
        padded = np.zeros((r, pad), dtype=np.uint8)
        padded[:, pad - n:] = rows
        rows = padded
    return _crc32c_words_jit(
        rows.view("<u4").reshape(r, *_crc_words_shape(pad)))


@functools.partial(jax.jit, static_argnames=("variant", "pad"))
def _gf_encode_with_crc_jit(mat, data, variant, pad):
    parity = gf_apply(mat, data, variant)
    rows = jnp.concatenate([data, parity], axis=0)
    return parity, _crc_rows_body(rows, pad)


def gf_encode_with_crc(mat, data, variant: str = "auto"):
    """The fused encode+checksum dispatch: parity rows AND the
    crc32c(0, ·) of every row of concat(data, parity), one jit call.

    mat: [m, k] uint8, data: [k, N] uint8 -> (parity [m, N] uint8,
    crcs [k + m] uint32).  Bitwise-identical to gf_apply + a host
    crc loop; the checksum pass reuses the device-resident rows the
    encode just produced instead of a second HBM round-trip."""
    mat = jnp.asarray(mat, dtype=jnp.uint8)
    data = jnp.asarray(data, dtype=jnp.uint8)
    return _gf_encode_with_crc_jit(mat, data, variant,
                                   _crc_pad(data.shape[1]))
