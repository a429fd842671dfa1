"""Pallas TPU kernels for GF(2^8) matrix application (the RS hot op).

Same math as :mod:`ceph_tpu.ops.rs_kernels` (out = mat @GF data), but the
whole bitslice pipeline — byte->bit-plane unpack, GF(2) matmul on the MXU,
mod-2, bit-plane->byte pack — is fused into ONE kernel over VMEM tiles.

Why it beats the XLA path: the XLA bitslice graph materialises the
unpacked bit-planes ([8k, N] bf16 = 16x the input bytes) and the f32
accumulator ([8r, N] = 32x the output bytes) in HBM between fusions; this
kernel streams uint8 in and uint8 out, holding the inflation only in VMEM
— HBM traffic drops to the information-theoretic (k+r)/N bytes per byte.
That does not make the op HBM-bound: on a v5e the k=8 m=4 encode of
256 MiB runs at 57% of the HBM roofline and its two-erasure decode at
52% (PERF.md section 5, PR 31); the vector unit's bit-plane work and the
MXU's row streaming each cost more than the DMA.

Bit-plane layout is plane-major (row b*k+j = bit b of chunk j) so the
in-kernel unpack/pack are static concatenates/slices — no sublane
reshuffles for Mosaic to choke on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..gf.tables import MUL_TABLE

# Block index maps return this, never a Python 0: under jax_enable_x64
# (which CRUSH bulk mapping needs process-wide) a Python int traces as
# i64, and Mosaic cannot legalize an index map that returns i64.
_I0 = np.int32(0)


def _out_like(data: jax.Array, shape: tuple[int, int]):
    """The uint8 out_shape of a kernel whose output varies over the same
    mesh axes as its data operand: inside ``shard_map`` the varying-axes
    check refuses an out_shape that does not say (and outside it the set
    is empty)."""
    return jax.ShapeDtypeStruct(shape, jnp.uint8, vma=jax.typeof(data).vma)


def expand_bits_plane_major(mat: jax.Array) -> jax.Array:
    """GF(2^8) matrix [r, k] -> GF(2) bit-matrix [8r, 8k], plane-major:

    B[bi*r + i, bj*k + j] = bit bi of (mat[i, j] * 2^bj  in GF(2^8)).
    """
    from .rs_kernels import expand_bits_raw
    r, k = mat.shape
    bits = expand_bits_raw(mat)                   # [r, bi, k, bj]
    return bits.transpose(1, 0, 3, 2).reshape(8 * r, 8 * k)


def _gf_stripes_kernel(bmat_ref, data_ref, out_ref, *, r: int, k: int,
                       groups: int):
    """Vertical-layout fused kernel: the block holds ``groups`` stripe
    slabs of k chunk rows each; all slabs go through ONE int8 MXU matmul
    against a block-diagonal bit-matrix.

    Why this shape was chosen:
    - int8 with int32 accumulation doubles MXU peak vs bf16 (the sums are
      0/1 bits, <= 8k terms, exact either way);
    - the block-diagonal stacking lifts the degenerate [8r, 8k] = [32, 64]
      stationary operand (1/8 of the 128x128 MXU busy at k=8, m=4) to
      [G*8r, G*8k] = [128, 256] — full tiles;
    - tall [G*k, T] uint8 blocks occupy 32 sublanes instead of 8, so the
      VMEM copies and DMAs run at full width.
    """
    d = data_ref[:].astype(jnp.int32)                 # [G*k, T]
    parts = []
    for g in range(groups):
        slab = d[g * k:(g + 1) * k]
        parts.extend(((slab >> b) & 1) for b in range(8))
    bits = jnp.concatenate(parts, axis=0).astype(jnp.int8)   # [G*8k, T]
    acc = jax.lax.dot_general(
        bmat_ref[:], bits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32) & 1         # [G*8r, T], mod 2
    outs = []
    for g in range(groups):
        base = g * 8 * r
        o = acc[base:base + r]
        for b in range(1, 8):
            o = o | (acc[base + b * r:(base + (b + 1) * r)] << b)
        outs.append(o)
    out_ref[:] = jnp.concatenate(outs, axis=0).astype(jnp.uint8)


def _lane_tiles(n: int, tile_max: int, lanes: int = 128) -> tuple[int, int]:
    """Split a byte axis of n columns into (n_tiles, tile): the fewest
    tiles no wider than tile_max, each the smallest multiple of ``lanes``
    (a whole number of 128-lane vregs) that covers n — padding waste stays
    under ``lanes`` columns per tile (a fixed tile would do up to 2x
    wasted work at n just over a tile boundary)."""
    n_tiles = max(1, -(-n // max(lanes, tile_max)))
    per_tile = -(-n // n_tiles)
    return n_tiles, max(lanes, -(-per_tile // lanes) * lanes)


def _stripe_groups(k: int, r: int, stripes: int) -> int:
    """Stripe slabs per block of the vertical kernel.  Mosaic wants the
    block's row counts (G*k in, G*r out) to be multiples of 8 sublanes
    unless the block spans the whole stripe axis: the MXU-filling 4 does
    that when k and r are even (8/4 encode, two-erasure decode), 8 does
    it always (any single-erasure decode, m=3 encode, odd k).  A batch
    no taller than one block is a single full-extent block, legal at any
    k and r."""
    return min(4 if k % 2 == 0 and r % 2 == 0 else 8, stripes)


# the vertical kernel's VMEM working set is its bit-plane rows (int32
# then int8 on the way in, int32 accumulators on the way out) times the
# tile width; this is the product the k=10 m=4 block compiles at with
# 8192-column tiles inside the 16 MiB scoped-VMEM limit of a v5e
_STRIPES_VMEM_ELEMS = 4 * 8 * (10 + 4) * 8192


@functools.partial(jax.jit,
                   static_argnames=("stripes", "tile_n", "interpret"))
def gf_apply_stripes_pallas(mat: jax.Array, data: jax.Array, stripes: int,
                            tile_n: int = 8192,
                            interpret: bool = False) -> jax.Array:
    """Batched GF apply over the VERTICAL stripe layout.

    data: [stripes * k, chunk_bytes] uint8 — stripe s occupies rows
    [s*k, (s+1)*k).  Returns [stripes * r, chunk_bytes], stripe s's parity
    at rows [s*r, (s+1)*r).  This is the codec's device-native batch
    layout: stripes arrive one after another from the IO path, so stacking
    them as rows is a no-copy append, and it feeds the MXU full tiles
    (see _gf_stripes_kernel).  Group count and tile width follow from
    (k, r, stripes) so every profile's encode and decode shapes lower.
    """
    from jax.experimental import pallas as pl

    mat = jnp.asarray(mat, dtype=jnp.uint8)
    data = jnp.asarray(data, dtype=jnp.uint8)
    r, k = mat.shape
    rows, n = data.shape
    assert rows == stripes * k, f"{rows} rows != {stripes} stripes x {k}"
    groups = _stripe_groups(k, r, stripes)
    # pad the stripe count to a group multiple (zero stripes encode to
    # zero parity) and the byte axis to a lane multiple
    s_pad = (-stripes) % groups
    if s_pad:
        data = jnp.pad(data, ((0, s_pad * k), (0, 0)))
    s_total = stripes + s_pad
    n_tiles, tile = _lane_tiles(
        n, min(tile_n, _STRIPES_VMEM_ELEMS // (groups * 8 * (k + r))))
    n_pad = n_tiles * tile
    if n_pad != n:
        data = jnp.pad(data, ((0, 0), (0, n_pad - n)))

    bexp = expand_bits_plane_major(mat)                       # [8r, 8k]
    blocks = []
    for g in range(groups):
        row = [jnp.zeros((8 * r, 8 * k), jnp.uint8)] * groups
        row[g] = bexp
        blocks.append(jnp.concatenate(row, axis=1))
    bmat = jnp.concatenate(blocks, axis=0).astype(jnp.int8)   # [G8r, G8k]

    out = pl.pallas_call(
        functools.partial(_gf_stripes_kernel, r=r, k=k, groups=groups),
        out_shape=_out_like(data, (s_total * r, n_pad)),
        grid=(s_total // groups, n_tiles),
        in_specs=[
            pl.BlockSpec((groups * 8 * r, groups * 8 * k),
                         lambda i, j: (_I0, _I0)),
            pl.BlockSpec((groups * k, tile), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((groups * r, tile), lambda i, j: (i, j)),
        interpret=interpret,
    )(bmat, data)
    if n_pad != n:
        out = out[:, :n]
    if s_pad:
        out = out[:stripes * r]
    return out


# Every byte of a packed 32-bit word holds one 0/1 bit-plane entry.
_PLANE_ONES = np.int32(0x01010101)
# Plane b's rows of the stationary matrix carry 2^b, so the accumulator
# already holds bit b in place and the byte is put back together with one
# AND and one OR a plane, no shift: -128 is 2^7 in the low byte (int8
# operand, int32 sum: -128*s = 128*(s mod 2) mod 256).
_PLANE_WEIGHTS = (1, 2, 4, 8, 16, 32, 64, -128)
# (k + r) * columns a grid step may move; its width is the largest power
# of two inside it (65,536 columns of a k=8 m=4 encode, 131,072 of its
# two-erasure decode), so a power-of-two row needs no padding.  PERF.md
# section 5 (PR 31) has the sweep: a Pallas grid step costs 0.1-0.2 us
# whatever it moves, a step this wide hides that, and twice as wide gains
# under 1% on the encode while the unrolled body compiles twice as long.
_GF_STEP_BYTES = 10 * 131072


def _column_groups(r: int, n: int) -> tuple[int, int]:
    """(G, rp): the column segments a grid step stacks on the sublanes,
    and the output rows a segment is padded to (zero rows of the matrix).

    The MXU streams the stationary matrix's 8*G*rp rows once per 128
    stacked columns, so its time grows with G, and the vector unit's
    falls with it (fuller uint8 vregs).  Measured on a v5e (PERF.md
    section 5, PR 31: r=4 G=2, r=2 G=4, r=1 G=8 beat G twice or half as
    large) they meet where each plane's G*rp output rows are the eight
    sublanes of one int32 vreg: 64 matrix rows, and the byte is put back
    together from whole vregs.  A row shorter than G vregs is one
    segment."""
    groups = max(1, 8 // r)
    if n < groups * 128:
        groups = 1
    return groups, -(-groups * r // 8) * 8 // groups


def _stacked_bit_matrix(mat: jax.Array, groups: int, kp: int,
                        rp: int) -> jax.Array:
    """GF(2^8) matrix [r, k] -> the int8 stationary operand
    [8*G*rp, 8*G*kp] of G stacked column segments: block-diagonal over
    the segments, plane-major then segment then chunk row on both axes,

        S[bi*G*rp + g*rp + i, bj*G*kp + g*kp + j] = 2^bi * B[bi*r+i, bj*k+j]

    with B = expand_bits_plane_major(mat); zero rows pad a segment's
    output to rp, zero columns its input to kp (whole 32-bit words)."""
    r, k = mat.shape
    b = expand_bits_plane_major(mat).reshape(8, r, 8, k).astype(jnp.int8)
    b = jnp.pad(b, ((0, 0), (0, rp - r), (0, 0), (0, kp - k)))
    b = b * jnp.asarray(_PLANE_WEIGHTS, jnp.int8)[:, None, None, None]
    eye = jnp.eye(groups, dtype=jnp.int8)
    s = eye[None, :, None, None, :, None] * b[:, None, :, :, None, :]
    return s.reshape(8 * groups * rp, 8 * groups * kp)


def _gf_kernel(bmat_ref, data_ref, out_ref, *, groups: int, kp: int,
               rp: int):
    """One grid step: [k, G*seg] columns in, [r, G*seg] out.  The G
    segments are stacked on the sublanes so the uint8 block fills its
    vregs, the bit-planes are taken from the rows packed four to a
    32-bit word (two vector ops a plane, already int8 for the MXU), and
    one int8 matmul against the block-diagonal matrix serves all G."""
    from jax.experimental.pallas import tpu as pltpu

    k, r = data_ref.shape[0], out_ref.shape[0]
    seg = data_ref.shape[1] // groups
    rows = []
    for g in range(groups):
        rows.append(data_ref[:, g * seg:(g + 1) * seg])
        if kp > k:
            rows.append(jnp.zeros((kp - k, seg), jnp.uint8))
    d = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
    w = pltpu.bitcast(d, jnp.int32)                   # [G*kp/4, seg]
    # where the stacked rows leave sublanes of the 8-sublane word vreg
    # free, copies shifted by 1, 2, .. fill them: one shift-and-mask
    # then yields that many planes at once, already in plane-major order
    fill = 32 // (groups * kp) if 32 % (groups * kp) == 0 else 1
    if fill > 1:
        w = jnp.concatenate(
            [w] + [w >> np.int32(t) for t in range(1, fill)], axis=0)
    bits = jnp.concatenate(
        [pltpu.bitcast((w >> np.int32(b)) & _PLANE_ONES, jnp.int8)
         for b in range(0, 8, fill)], axis=0)         # [8*G*kp, seg], 0/1
    # int8 x int8 -> int32: exact (|terms| <= 128, <= 8k of them) and 2x
    # the bf16 MXU peak on v5e
    acc = jax.lax.dot_general(
        bmat_ref[:], bits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)             # [8*G*rp, seg]
    gr = groups * rp
    out = acc[0:gr] & np.int32(1)                     # mod 2, bit in place
    for b in range(1, 8):
        out = out | (acc[b * gr:(b + 1) * gr] & np.int32(1 << b))
    out = out.astype(jnp.uint8)                       # [G*rp, seg]
    for g in range(groups):
        out_ref[:, g * seg:(g + 1) * seg] = out[g * rp:g * rp + r]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gf_apply_pallas(mat: jax.Array, data: jax.Array,
                    interpret: bool = False) -> jax.Array:
    """out[r, N] = mat @GF data, fused bitslice pipeline in one kernel.

    mat: [r, k] uint8, data: [k, N] uint8.  N is padded to a whole number
    of grid steps internally (zero GF columns contribute zero parity);
    segments per step and their width follow from (k, r, N).
    """
    from jax.experimental import pallas as pl

    mat = jnp.asarray(mat, dtype=jnp.uint8)
    data = jnp.asarray(data, dtype=jnp.uint8)
    r, k = mat.shape
    _, n = data.shape
    kp = -(-k // 4) * 4
    groups, rp = _column_groups(r, n)
    bmat = _stacked_bit_matrix(mat, groups, kp, rp)

    tile_max = 1 << ((_GF_STEP_BYTES // (kp + r)).bit_length() - 1)
    n_tiles, tile_n = _lane_tiles(n, tile_max, groups * 128)
    n_pad = n_tiles * tile_n
    if n_pad != n:
        data = jnp.pad(data, ((0, 0), (0, n_pad - n)))

    out = pl.pallas_call(
        functools.partial(_gf_kernel, groups=groups, kp=kp, rp=rp),
        out_shape=_out_like(data, (r, n_pad)),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(bmat.shape, lambda i: (_I0, _I0)),
            pl.BlockSpec((k, tile_n), lambda i: (_I0, i)),
        ],
        out_specs=pl.BlockSpec((r, tile_n), lambda i: (_I0, i)),
        interpret=interpret,
    )(bmat, data)
    return out[:, :n] if n_pad != n else out


def _xor_kernel(w_ref, data_ref, out_ref):
    """Binary-matrix XOR-matmul tile: the shared bit-plane core with the
    bitmatrix as the operand directly — no coefficient expansion (cf.
    _gf_kernel); inflation stays in VMEM."""
    from .rs_kernels import bitplane_xor_matmul
    out_ref[:] = bitplane_xor_matmul(w_ref[:],
                                     data_ref[:].astype(jnp.int32))


# the xor kernel's working set is 8 bit-planes of its K input and R
# output rows per tile column; this is the product liberation's [14, 28]
# compiles at with 16384-column tiles inside scoped VMEM on a v5e
_XOR_VMEM_ELEMS = (14 + 28) * 16384


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def xor_apply_pallas(W: jax.Array, packets: jax.Array,
                     tile_n: int = 16384,
                     interpret: bool = False) -> jax.Array:
    """Fused packet-layout bitmatrix apply: W [R, K] 0/1, packets [K, P]
    uint8 -> [R, P].  The data path of the bitmatrix techniques and the
    wide-word (w=16/32) codes: bit-plane inflation stays in VMEM.  Row
    counts ride full blocks and the tile narrows as R + K grows, so any
    (R, K) — liberation's [14, 28] as much as w=32 reed_sol's
    [64, 128] — lowers within scoped VMEM."""
    from jax.experimental import pallas as pl

    W = jnp.asarray(W, dtype=jnp.int8)
    packets = jnp.asarray(packets, dtype=jnp.uint8)
    r, k = W.shape
    kk, p = packets.shape
    assert kk == k
    n_tiles, tile = _lane_tiles(p, min(tile_n, _XOR_VMEM_ELEMS // (r + k)))
    p_pad = n_tiles * tile
    if p_pad != p:
        packets = jnp.pad(packets, ((0, 0), (0, p_pad - p)))
    out = pl.pallas_call(
        _xor_kernel,
        out_shape=_out_like(packets, (r, p_pad)),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((r, k), lambda i: (_I0, _I0)),
            pl.BlockSpec((k, tile), lambda i: (_I0, i)),
        ],
        out_specs=pl.BlockSpec((r, tile), lambda i: (_I0, i)),
        interpret=interpret,
    )(W, packets)
    return out[:, :p] if p_pad != p else out
