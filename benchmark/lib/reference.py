"""The plain reference: GF(2^8) Reed-Solomon and ceph's crc32c in numpy.

Independent of the program under test: imports nothing from ``ceph_tpu``
and takes no table, matrix or scale from it.  Same semantics as the
upstream plugins the configurations name:

- GF(2^8) with the primitive polynomial 0x11D (jerasure / ISA-L);
- ``technique=cauchy``: parity[i][j] = 1 / ((i + k) ^ j)  (ISA-L
  ``gf_gen_cauchy1_matrix``);
- decode: the first k surviving rows of the generator [I; P], inverted;
  a lost parity row is its parity row times that inverse (ISA-L
  ``ErasureCodeIsa`` decode tables);
- ``ceph_crc32c(seed, data)``: the raw reflected CRC-32C register
  update (polynomial 0x82F63B78), no final xor; HashInfo seeds it with
  0xFFFFFFFF.

Everything over whole arrays: a GF product is one table lookup per
coefficient, a crc runs many lanes at once and folds them.
"""
from __future__ import annotations

import numpy as np

GF_POLY = 0x11D
CRC32C_POLY_REFLECTED = 0x82F63B78
HINFO_SEED = 0xFFFFFFFF


# -- GF(2^8) -------------------------------------------------------------------

def _gf_tables():
    exp = np.zeros(512, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[:255]
    mul = np.zeros((256, 256), dtype=np.uint8)
    a = np.arange(1, 256)
    for c in range(1, 256):
        mul[c, 1:] = exp[log[c] + log[a]]
    return exp, log, mul


_EXP, _LOG, MUL = _gf_tables()


def gf_mul(a: int, b: int) -> int:
    return int(MUL[a, b])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(_EXP[255 - _LOG[a]])


def cauchy_parity_matrix(k: int, m: int) -> np.ndarray:
    """[m, k]: row i, column j = 1 / ((i + k) ^ j)."""
    return np.array([[gf_inv((i + k) ^ j) for j in range(k)]
                     for i in range(m)], dtype=np.uint8)


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Small matrices over GF(2^8): [r, n] x [n, c] -> [r, c]."""
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for t in range(a.shape[1]):
                acc ^= gf_mul(int(a[i, t]), int(b[t, j]))
            out[i, j] = acc
    return out


def gf_invert_matrix(a: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8); raises on a singular matrix."""
    n = a.shape[0]
    work = [[int(v) for v in row] + [1 if i == j else 0 for j in range(n)]
            for i, row in enumerate(a)]
    for col in range(n):
        piv = next((r for r in range(col, n) if work[r][col]), None)
        if piv is None:
            raise ValueError("singular matrix over GF(2^8)")
        work[col], work[piv] = work[piv], work[col]
        inv = gf_inv(work[col][col])
        work[col] = [gf_mul(v, inv) for v in work[col]]
        for r in range(n):
            f = work[r][col]
            if r != col and f:
                work[r] = [v ^ gf_mul(f, p)
                           for v, p in zip(work[r], work[col])]
    return np.array([row[n:] for row in work], dtype=np.uint8)


def decode_matrix(parity: np.ndarray, erasures, available=None):
    """``(D, src)``: lost[e] = XOR_j D[e, j] * chunk[src[j]], ``src`` the
    first k survivors in ascending order, rows of ``D`` in ascending
    order of the erased index."""
    m, k = parity.shape
    erased = sorted(int(e) for e in erasures)
    if available is None:
        available = [i for i in range(k + m) if i not in erased]
    src = sorted(int(a) for a in available if int(a) not in erased)[:k]
    if len(src) < k:
        raise ValueError(f"need {k} chunks, {len(src)} survive")
    gen = np.concatenate([np.eye(k, dtype=np.uint8), parity], axis=0)
    inv = gf_invert_matrix(gen[src])
    rows = [inv[e] if e < k else gf_matmul(parity[e - k:e - k + 1], inv)[0]
            for e in erased]
    return np.stack(rows), src


def gf_apply(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out[i] = XOR_j mat[i, j] * data[j]: [r, k] x [k, N] -> [r, N]."""
    mat = np.asarray(mat, dtype=np.uint8)
    data = np.asarray(data, dtype=np.uint8)
    out = np.zeros((mat.shape[0], data.shape[1]), dtype=np.uint8)
    for i in range(mat.shape[0]):
        for j in range(mat.shape[1]):
            c = int(mat[i, j])
            if c:
                out[i] ^= data[j] if c == 1 else MUL[c][data[j]]
    return out


def object_shards(payload: np.ndarray, k: int, parity: np.ndarray,
                  chunk_size: int) -> np.ndarray:
    """The k + m shards an EC pool stores for one object (ECUtil
    ``stripe_info_t``): stripe s spans bytes [s*k*chunk, (s+1)*k*chunk),
    data shard i holds chunk i of every stripe back to back, parity shard
    j the code of those.  The tail stripe is zero-padded.
    -> [k + m, stripes * chunk]."""
    width = k * chunk_size
    stripes = -(-len(payload) // width)
    buf = np.zeros(stripes * width, dtype=np.uint8)
    buf[:len(payload)] = payload
    data = np.ascontiguousarray(
        buf.reshape(stripes, k, chunk_size).transpose(1, 0, 2)
        .reshape(k, stripes * chunk_size))
    return np.concatenate([data, gf_apply(parity, data)], axis=0)


# -- crc32c --------------------------------------------------------------------

def _crc_tables():
    t = np.zeros((4, 256), dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ (CRC32C_POLY_REFLECTED if c & 1 else 0)
        t[0, i] = c
    for j in range(1, 4):
        t[j] = (t[j - 1] >> 8) ^ t[0][t[j - 1] & 0xFF]
    return t


_CRC_T = _crc_tables()


def _zeros_operator(nbytes: int) -> np.ndarray:
    """The linear map 'advance the register through ``nbytes`` zero
    bytes' as four byte-indexed tables: op[b][v] is the image of the
    register whose byte b holds v and whose other bytes are 0."""
    basis = np.uint32(1) << np.arange(32, dtype=np.uint32)     # e_0..e_31
    t0 = _CRC_T[0]

    def step(cols):                   # one zero byte, on every column
        return (cols >> 8) ^ t0[cols & 0xFF]

    def compose(f, g):                # f after g, both as basis images
        out = np.zeros(32, dtype=np.uint32)
        for bit in range(32):
            sel = ((g >> np.uint32(bit)) & 1).astype(bool)
            out ^= np.where(sel, f[bit], np.uint32(0))
        return out

    result, power, n = basis.copy(), step(basis), nbytes
    while n:
        if n & 1:
            result = compose(power, result)
        power = compose(power, power)
        n >>= 1
    op = np.zeros((4, 256), dtype=np.uint32)
    vals = np.arange(256, dtype=np.uint32)
    for b in range(4):
        for bit in range(8):
            sel = ((vals >> np.uint32(bit)) & 1).astype(bool)
            op[b] ^= np.where(sel, result[8 * b + bit], np.uint32(0))
    return op


def _apply_operator(op: np.ndarray, reg: np.ndarray) -> np.ndarray:
    return (op[0][reg & 0xFF] ^ op[1][(reg >> 8) & 0xFF]
            ^ op[2][(reg >> 16) & 0xFF] ^ op[3][reg >> 24])


def crc32c_rows(rows: np.ndarray, seed: int = HINFO_SEED,
                lane_bytes: int = 512) -> np.ndarray:
    """``ceph_crc32c(seed, row)`` of every row of a [R, L] uint8 array.

    The crc is linear over GF(2) once the seed is split off, so each row
    is cut into lanes of ``lane_bytes``, every lane of every row runs
    the slice-by-4 table update side by side, and neighbouring lanes are
    then folded pairwise (left register advanced through the right's
    length, xor).  The seed's own image through L zero bytes is added
    last."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    if rows.ndim != 2:
        raise ValueError("crc32c_rows wants a [rows, bytes] array")
    n_rows, length = rows.shape
    out_seed = _apply_operator(_zeros_operator(length),
                               np.array([seed], dtype=np.uint32))[0]
    if length == 0:
        return np.full(n_rows, out_seed, dtype=np.uint32)
    lanes = 1
    while length % (lanes * 2) == 0 and length // (lanes * 2) >= lane_bytes \
            and (length // (lanes * 2)) % 4 == 0:
        lanes *= 2
    span = length // lanes
    words, tail = divmod(span, 4)
    cut = rows.reshape(n_rows * lanes, span)
    reg = np.zeros(n_rows * lanes, dtype=np.uint32)
    t0, t1, t2, t3 = _CRC_T
    if words:
        w = np.ascontiguousarray(cut[:, :words * 4]).view("<u4")
        for i in range(words):
            x = reg ^ w[:, i]
            reg = (t3[x & 0xFF] ^ t2[(x >> 8) & 0xFF]
                   ^ t1[(x >> 16) & 0xFF] ^ t0[x >> 24])
    for i in range(words * 4, words * 4 + tail):
        reg = (reg >> 8) ^ t0[(reg ^ cut[:, i]) & 0xFF]
    reg = reg.reshape(n_rows, lanes)
    while reg.shape[1] > 1:
        op = _zeros_operator(span)
        reg = _apply_operator(op, reg[:, 0::2]) ^ reg[:, 1::2]
        span *= 2
    return reg[:, 0] ^ out_seed


def crc32c_bytewise(data: bytes, seed: int = HINFO_SEED) -> int:
    """The definition, one byte at a time: the tests' witness for
    :func:`crc32c_rows`, never used on a run's data."""
    reg = seed & 0xFFFFFFFF
    t0 = _CRC_T[0]
    for b in data:
        reg = (reg >> 8) ^ int(t0[(reg ^ b) & 0xFF])
    return reg
