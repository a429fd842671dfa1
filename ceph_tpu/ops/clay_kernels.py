"""ClayCodec: the coupled-layer (Clay) MSR code on the device.

The code is the one ``plugins/plugin_clay.py`` computes with its host
loop (reference: src/erasure-code/clay/ErasureCodeClay.cc; Vajha et al.,
"Clay Codes: Moulding MDS Codes to Yield an MSR Code", FAST 2018): q*t
nodes on a q x t grid, q^t planes a chunk, a pairwise transform (PFT,
an RS(2, 2)) between coupled and uncoupled values of the pairs its
sewing map joins, and a scalar MDS code per plane on the uncoupled
values.

Everything in it is GF(2^8)-linear per byte position of a sub-chunk:
the byte at offset b of every sub-chunk of an output depends only on
the bytes at offset b of the input sub-chunks.  So each call signature
(the encode, one erasure set, one lost node with its helpers) is one
GF matrix from input sub-chunks to output sub-chunks -- [m*Z, k*Z] for
an encode, [Z, d*Z/q] for a repair.  It is built on the host once per
signature by running the layered decode (or the repair) of the paper
over coefficient vectors instead of bytes, then cached with its
device-resident bit-matrix, as ``RSCodec`` caches decode tables.

A call is one device dispatch, whatever the stripe count, and a Pallas
kernel reads and writes the caller's arrays as they lie in HBM, a stripe
a grid step: eight input rows through ``[S*C/128, 8, 128]`` (one
128-byte window of every row a slab, which is how XLA:TPU tiles a
``[8, S*C]`` u8 array, so the view is a bitcast), other row counts
(the repair's 11 helpers) as the 2-D ``[rows, S*C]`` itself.  In VMEM
the stripe is regathered: an int32 of the caller's layout holds four
chunk rows at one plane, and a byte transpose of four such words makes
four words of four planes of one row, the kernel's unit.  The kernel
then applies the matrix as bit-sliced int8 matmuls on the MXU.  The
matrix is sparse and blocked: the kernel groups the output planes four
to a word, and each group gathers only the input words it depends on
(14 of 128 for the encode at k=8 m=4 d=11), so the MXU does a small part
of the whole matrix's work; the grouping is chosen per signature from
the matrix itself.  Each group's planes go straight into the caller's
layout of the output: ``[4, S*C]`` (four rows to a word; two or three
rows padded to four) or, for one row, ``[S*C/128, 128]``, the tiling of
the 1-D ``[S*C]`` a repair returns.  So no relayout of the data runs
beside the kernel.  Off the TPU (and at shapes the kernel does not tile)
the same matrix goes through ``rs_kernels.gf_apply`` on a transposed
layout.  The kernel's name says what the call was (``clay_encode``,
``clay_decode``, ``clay_repair``): the TPU compiler names the Mosaic
custom call after the jit around it, so a profile reader finds each
call's kernel by name, its output shape ``u8[4, S*C]`` or
``u8[S*C/128, 128]`` beside.
"""
from __future__ import annotations

import collections
import functools
import threading
import typing

import jax
import jax.numpy as jnp
import numpy as np

from ..common.perf_counters import PerfCountersBuilder
from ..common.tracer import trace_span
from ..gf import matrix as gfm
from ..gf.tables import MUL_TABLE
from . import rs_kernels
from .pallas_kernels import _I0, _PLANE_ONES, _PLANE_WEIGHTS

# signatures a codec keeps tables for: the encode, every erasure set of
# up to m of k+m chunks at (8, 4) is 793, every lost node 12
TABLE_CACHE_SIZE = 1024
# the kernel's working set per column of a tile: the int32 accumulators
# and the int8 bit-planes; tiles are sized to keep it under this
_TILE_VMEM_BYTES = 8 << 20
_VMEM_LIMIT_BYTES = 96 << 20
# the groups' bit-matrices are resident in VMEM, double-buffered by the
# pipeline; a signature whose blocks would not fit takes the XLA path
_MATRIX_VMEM_BYTES = 16 << 20
# a stripe's input and output blocks, double-buffered, and its words;
# a chunk whose stripe would not fit takes the XLA path
_BLOCK_VMEM_BYTES = 48 << 20

_PERF = None
_PERF_LOCK = threading.Lock()


def perf_counters():
    """The process-wide ``clay`` perf-counter collection, registered with
    the default context on first use and shared by every codec."""
    global _PERF
    with _PERF_LOCK:
        if _PERF is None:
            from ..common.context import default_context
            _PERF = (PerfCountersBuilder("clay")
                     .add_u64_counter("calls", "device-codec calls: "
                                      "encode, decode and repair")
                     .add_u64_counter("device_dispatches",
                                      "jitted dispatches those calls made")
                     .add_u64_counter("kernel_calls",
                                      "calls that ran the Mosaic kernel "
                                      "on the caller's layout")
                     .add_u64_counter("repair_read_bytes",
                                      "helper bytes a repair call read")
                     .add_u64_counter("repair_bytes",
                                      "chunk bytes a repair call rebuilt")
                     .create_perf_counters())
            default_context().perf.add(_PERF)
        return _PERF


# -- the device apply ------------------------------------------------------------

def _bit_matrix(G: np.ndarray) -> np.ndarray:
    """GF(2^8) matrix [R, K] -> the int8 stationary operand [8R, 8K] of
    the kernel, plane-major on both axes (row bo*R + r, column b*K + c:
    bit bo of G[r, c] * 2^b), output plane bo's rows scaled by 2^bo so
    the int32 sum holds bit bo in place (as ``gf_apply_pallas`` does)."""
    R, K = G.shape
    powers = np.array([1 << b for b in range(8)], dtype=np.intp)
    prod = MUL_TABLE[G.astype(np.intp)[:, :, None], powers[None, None, :]]
    bits = (prod[None, :, :, :] >> np.arange(8)[:, None, None, None]) & 1
    # bits[bo, r, c, b] -> [bo, r, b, c]
    out = bits.transpose(0, 1, 3, 2).astype(np.int32)
    out = out * np.asarray(_PLANE_WEIGHTS, np.int32)[:, None, None, None]
    return out.reshape(8 * R, 8 * K).astype(np.int8)


class _Plan(typing.NamedTuple):
    """How the kernel blocks one signature's matrix.  A word is the four
    consecutive planes of a row that one int32 holds.  Output words go
    in groups of ``gs``; group g reads only the input words ``runs``
    names -- ``(row, first word, end word)`` each -- which its outputs
    depend on, padded to ``n_in`` words, so its stationary operand is
    [8 * 4*gs*r_out, 8 * 4*n_in] and not the whole [8R, 8K]."""
    gs: int
    groups: tuple           # ((first output word, runs), ...)
    n_in: int


def _plan(G: np.ndarray, r_in: int, z_in: int, r_out: int,
          z_out: int) -> _Plan:
    """The grouping whose MXU work -- row tiles times column tiles of
    the groups' operands -- is least.  An encode's output word depends
    on 14 of its 128 input words at (8, 4, 11): 64 tile-passes against
    the whole matrix's 512."""
    used = G.reshape(r_out, z_out // 4, 4, r_in, z_in // 4, 4) != 0
    best = None
    gs = 1
    while gs <= z_out // 4 and (z_out // 4) % gs == 0:
        groups = []
        for g0 in range(0, z_out // 4, gs):
            need = used[:, g0:g0 + gs].any(axis=(0, 1, 2, 5))
            runs = []
            for i in range(r_in):
                words = np.flatnonzero(need[i])
                for run in np.split(words, np.flatnonzero(
                        np.diff(words) != 1) + 1):
                    if run.size:
                        runs.append((i, int(run[0]), int(run[-1]) + 1))
            groups.append((g0, tuple(runs)))
        n_in = max(sum(b - a for _i, a, b in runs) for _g, runs in groups)
        cost = len(groups) * -(-32 * gs * r_out // 128) * -(-32 * n_in // 128)
        if best is None or cost < best[0]:
            best = (cost, _Plan(gs, tuple(groups), max(n_in, 1)))
        gs *= 2
    return best[1]


def _plan_bits(G: np.ndarray, plan: _Plan, z_in: int, r_out: int,
               z_out: int) -> np.ndarray:
    """The groups' stationary operands, [groups, 8*4*gs*r_out,
    8*4*n_in]: each the bit-matrix of G's block from the inputs the
    group reads to its outputs (rows output row then plane, columns
    input word then plane), zero columns for its padding."""
    out = []
    for g0, runs in plan.groups:
        rows = [o * z_out + z for o in range(r_out)
                for z in range(4 * g0, 4 * (g0 + plan.gs))]
        cols = [i * z_in + z for i, a, b in runs for z in range(4 * a, 4 * b)]
        block = np.zeros((len(rows), 4 * plan.n_in), dtype=np.uint8)
        block[:, :len(cols)] = G[np.ix_(rows, cols)]
        out.append(_bit_matrix(block))
    return np.stack(out)


def _transpose4(w0, w1, w2, w3):
    """The 4x4 byte transpose of four int32 words: byte e of the r-th
    word out is byte r of word e in.  Four words of four chunk rows at
    one plane each (the caller's packing) become four words of four
    planes of one row each (the kernel's), and back."""
    srl = jax.lax.shift_right_logical
    lo, hi = np.int32(0x0000FFFF), np.int32(-0x00010000)        # 0xFFFF0000
    even, odd = np.int32(0x00FF00FF), np.int32(-0x00FF0100)     # 0xFF00FF00
    half, byte = np.int32(16), np.int32(8)
    t0, t2 = (w0 & lo) | (w2 << half), srl(w0, half) | (w2 & hi)
    t1, t3 = (w1 & lo) | (w3 << half), srl(w1, half) | (w3 & hi)
    return ((t0 & even) | ((t1 & even) << byte),
            (srl(t0, byte) & even) | (t1 & odd),
            (t2 & even) | ((t3 & even) << byte),
            (srl(t2, byte) & even) | (t3 & odd))


def _gather_slabs(x_ref, s_ref, z_in: int, sc: int):
    """Eight rows of one stripe, ``[C/128, 8, 128]`` (slab t is the
    128-byte window t of every row, four rows to an int32 word), into
    the kernel's words ``s_ref`` [8, Z/4, sc]: for each window j of a
    sub-chunk and quad of rows, a strided load takes window j of the
    planes 4a+e of every a, and the byte transpose makes the words of
    planes 4a..4a+3 of each of the quad's rows."""
    from jax.experimental import pallas as pl

    spp = sc // 128
    x32 = x_ref.bitcast(jnp.int32)                  # [C/128, 2, 128]
    for w in range(2):
        for j in range(spp):
            quad = [x32[pl.ds(e * spp + j, z_in // 4, stride=4 * spp), w, :]
                    for e in range(4)]
            for r, word in enumerate(_transpose4(*quad)):
                s_ref[4 * w + r, :, j * 128:(j + 1) * 128] = word


def _gather_rows(x_ref, s_ref, z_in: int, sc: int):
    """Rows of one stripe, ``[R, C]`` (R the rows padded to whole int32
    words), into the kernel's words ``s_ref`` [rows, Z/4, sc]: the byte
    transpose of planes 4a..4a+3 of every quad of rows at once."""
    r_in = s_ref.shape[0]
    x32 = x_ref.bitcast(jnp.int32)                  # [R/4, C]
    for a in range(z_in // 4):
        quad = [x32[:, (4 * a + e) * sc:(4 * a + e + 1) * sc]
                for e in range(4)]
        for r, word in enumerate(_transpose4(*quad)):
            for w in range(-(-(r_in - r) // 4)):    # the rows 4w+r < r_in
                s_ref[4 * w + r, a:a + 1, :] = word[w:w + 1]


def _put_words(o_ref, out, planes, r_out: int, sc: int, c0: int):
    """Output planes ``out`` [r_out*n, T] (row o*n + p: row o, plane
    planes[p]) into ``[4, C]``: four rows to an int32 word, the rows
    past r_out zero."""
    from jax.experimental.pallas import tpu as pltpu
    n, tile = len(planes), out.shape[1]
    word = out[0:n]
    for o in range(1, r_out):
        word = word | (out[o * n:(o + 1) * n] << np.int32(8 * o))
    for p, z in enumerate(planes):
        o_ref[:, z * sc + c0:z * sc + c0 + tile] = pltpu.bitcast(
            word[p:p + 1], jnp.uint8)


def _put_flat(o_ref, out, planes, sc: int, c0: int):
    """One output row's planes ``out`` [n, T] into ``[C/128, 128]``,
    the row's bytes 128 a line, which is the tiling of a 1-D array:
    the lines 4k..4k+3 of a plane to an int32 word."""
    from jax.experimental.pallas import tpu as pltpu
    tile = out.shape[1]
    quads = []
    for k in range(0, tile, 512):
        word = out[:, k:k + 128]
        for i in range(1, 4):
            word = word | (out[:, k + 128 * i:k + 128 * (i + 1)]
                           << np.int32(8 * i))
        quads.append(word)                          # [n, 128]
    for p, z in enumerate(planes):
        line = (z * sc + c0) // 128
        o_ref[line:line + tile // 128, :] = pltpu.bitcast(
            jnp.concatenate([q[p:p + 1] for q in quads], axis=0), jnp.uint8)


def _clay_kernel(b_ref, x_ref, o_ref, s_ref, *, plan: _Plan, z_in: int,
                 sc: int, r_out: int, tile: int):
    """One stripe: its input rows in the caller's layout regathered into
    words of four planes of a row (``s_ref``), then for each tile of
    sub-chunk bytes and group of output words, the input words it reads
    are gathered, unpacked to bit-planes four planes to an int32 word
    (plane-major: bit, word, plane), one int8 matmul applies the group's
    block of the matrix, and the group's planes are packed into the
    caller's layout of the output."""
    from jax.experimental.pallas import tpu as pltpu

    if len(x_ref.shape) == 3:
        _gather_slabs(x_ref, s_ref, z_in, sc)
    else:
        _gather_rows(x_ref, s_ref, z_in, sc)
    rows = 4 * plan.gs * r_out
    for c0 in range(0, sc, tile):
        for gi, (g0, runs) in enumerate(plan.groups):
            pieces = [s_ref[i, a:b, c0:c0 + tile] for i, a, b in runs]
            short = plan.n_in - sum(b - a for _i, a, b in runs)
            if short:
                pieces.append(jnp.zeros((short, tile), jnp.int32))
            words = jnp.concatenate(pieces, axis=0)     # [n_in, T]
            bits = jnp.concatenate(
                [pltpu.bitcast((words >> np.int32(b)) & _PLANE_ONES, jnp.int8)
                 for b in range(8)], axis=0)              # [8*4*n_in, T]
            acc = jax.lax.dot_general(
                b_ref[gi], bits, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)         # [8*rows, T]
            out = acc[0:rows] & np.int32(1)
            for b in range(1, 8):
                out = out | (acc[b * rows:(b + 1) * rows] & np.int32(1 << b))
            planes = range(4 * g0, 4 * (g0 + plan.gs))
            if r_out == 1:
                _put_flat(o_ref, out, planes, sc, c0)
            else:
                _put_words(o_ref, out, planes, r_out, sc, c0)


def _tile(sc: int, per_col: int, least: int) -> int:
    """Columns of a pass: the widest power of two from ``least`` up
    dividing the sub-chunk whose working set (``per_col`` bytes a
    column) fits the budget."""
    tile = least
    while (sc % (2 * tile) == 0
           and 2 * tile * per_col <= _TILE_VMEM_BYTES):
        tile *= 2
    return tile


def _rows_padded(r_in: int) -> int:
    """The rows of an input block: whole int32 words of rows, and whole
    8-row tiles where the rows are not a multiple of four (11 helpers
    read as 16: the tiling pads the array to that in HBM)."""
    return r_in if r_in % 4 == 0 else -(-r_in // 8) * 8


def _pallas_fits(r_in: int, z_in: int, r_out: int, z_out: int,
                 sc: int) -> bool:
    """Shapes the kernel tiles: whole words of planes, whole lanes of
    bytes, at most a word of output rows (one row written 1 KiB of a
    plane at a time), and a stripe's blocks within the VMEM budget."""
    if z_in % 4 or z_out % 4 or sc % 128 or not 1 <= r_out <= 4:
        return False
    if r_out == 1 and sc % 1024:
        return False
    c_in, c_out = z_in * sc, z_out * sc
    blocks = (2 * (_rows_padded(r_in) * c_in + (1 if r_out == 1 else 4)
                   * c_out) + r_in * c_in)
    return blocks <= _BLOCK_VMEM_BYTES


def _pallas_apply(B, data, z_in: int, sc: int, r_out: int, z_out: int,
                  plan: _Plan, interpret: bool = False):
    """``data`` [r_in, S*z_in*sc] -> [r_out, S*z_out*sc] through the
    kernel, a stripe a grid step, in the caller's layouts: eight rows
    are read as ``[S*C/128, 8, 128]`` and others as they are; four
    output rows (and two or three, padded) are written as ``[4, S*C]``
    and one as ``[S*C/128, 128]``, each a bitcast of the array it
    stands for.  Traced inside the caller's named jit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r_in, n = data.shape
    c_in, c_out = z_in * sc, z_out * sc
    stripes = n // c_in
    if r_in == 8:
        x = data.reshape(8, n // 128, 128).transpose(1, 0, 2)
        x_spec = pl.BlockSpec((c_in // 128, 8, 128),
                              lambda s: (s, _I0, _I0))
    else:
        x = data
        x_spec = pl.BlockSpec((_rows_padded(r_in), c_in),
                              lambda s: (_I0, s))
    if r_out == 1:
        out_shape = (stripes * c_out // 128, 128)
        o_spec = pl.BlockSpec((c_out // 128, 128), lambda s: (s, _I0))
    else:
        out_shape = (4, stripes * c_out)
        o_spec = pl.BlockSpec((4, c_out), lambda s: (_I0, s))
    # bytes a column of a pass: a group's int32 accumulators and its
    # int8 bit-planes with the words they come from
    tile = _tile(sc, 4 * B.shape[1] + 3 * B.shape[2] // 2,
                 1024 if r_out == 1 else 128)
    y = pl.pallas_call(
        functools.partial(_clay_kernel, plan=plan, z_in=z_in, sc=sc,
                          r_out=r_out, tile=tile),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.uint8),
        grid=(stripes,),
        in_specs=[pl.BlockSpec(B.shape, lambda s: (_I0, _I0, _I0)), x_spec],
        out_specs=o_spec,
        scratch_shapes=[pltpu.VMEM((r_in, z_in // 4, sc), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(B, x)
    return y.reshape(1, -1) if r_out == 1 else y[:r_out]


def _xla_apply(G, x3, z_in: int, r_out: int, z_out: int):
    """The same map through ``rs_kernels.gf_apply`` on the plane-major
    transpose ``[r_in*z_in, S*sc]``: every backend, every shape."""
    r_in, rows, sc = x3.shape
    stripes = rows // z_in
    x = x3.reshape(r_in, stripes, z_in, sc).transpose(0, 2, 1, 3)
    y = rs_kernels.gf_apply(G, x.reshape(r_in * z_in, stripes * sc))
    y = y.reshape(r_out, z_out, stripes, sc).transpose(0, 2, 1, 3)
    return y.reshape(r_out, stripes * z_out * sc)


def _make_apply(name: str, flat: bool = False):
    """A jitted ``(mat, data [r_in, S*C_in]) -> [r_out, S*C_out]`` named
    ``name`` (``[S*C_out]`` if ``flat``, one output row): the kernel's
    blocks of the matrix under ``plan``, or, with no plan, the GF matrix
    through ``_xla_apply``."""
    def apply(mat, data, z_in: int, sc: int, r_out: int, z_out: int,
              plan: _Plan | None, interpret: bool = False):
        r_in, n = data.shape
        if plan is None:
            y = _xla_apply(mat, data.reshape(r_in, n // sc, sc), z_in,
                           r_out, z_out)
        else:
            y = _pallas_apply(mat, data, z_in, sc, r_out, z_out, plan,
                              interpret)
        return y.reshape(-1) if flat else y
    apply.__name__ = apply.__qualname__ = name
    return jax.jit(apply, static_argnames=("z_in", "sc", "r_out", "z_out",
                                           "plan", "interpret"))


_APPLY = {kind: _make_apply(f"clay_{kind}", flat=kind == "repair")
          for kind in ("encode", "decode", "repair")}


class _Tables:
    """One signature's cached state: the GF matrix, the chunk ids its
    input rows are, the chunk ids its output rows are, and -- built and
    uploaded on first use, by path -- what the device applies with the
    kernel's plan (None off the kernel)."""

    __slots__ = ("G", "src", "out", "dev")

    def __init__(self, G: np.ndarray, src: list[int], out: list[int]):
        self.G, self.src, self.out = G, src, out
        self.dev = {}


class ClayCodec:
    """Clay(k, m, d) over GF(2^8) with a given scalar MDS parity matrix
    ([m, k+nu]) and PFT parity matrix ([2, 2]).

    Chunk ids are the plugin's: data 0..k-1, parity k..k+m-1; nodes
    insert the nu shortened (zero) nodes after the data.  Device entry
    points take ``[rows, S*C]`` uint8 arrays, one C-byte segment a
    stripe; ``chunk_size`` is C and defaults to the whole row (one
    stripe)."""

    def __init__(self, k: int, m: int, d: int, mds_parity: np.ndarray,
                 pft_parity: np.ndarray):
        if not k <= d <= k + m - 1:
            raise ValueError(f"d={d} must be within [{k}, {k + m - 1}]")
        self.k, self.m, self.d = k, m, d
        self.q = d - k + 1
        self.nu = (self.q - (k + m) % self.q) % self.q
        self.t = (k + m + self.nu) // self.q
        self.nodes = self.q * self.t
        self.planes = self.q ** self.t
        self.mds_parity = np.asarray(mds_parity, dtype=np.uint8)
        if self.mds_parity.shape != (m, k + self.nu):
            raise ValueError(f"scalar MDS parity is {self.mds_parity.shape}, "
                             f"want {(m, k + self.nu)}")
        self._pft_gen = gfm.generator_matrix(
            np.asarray(pft_parity, dtype=np.uint8))          # [4, 2]
        self._pft_rows: dict = {}
        # digit y of plane z, most significant first (get_plane_vector)
        z = np.arange(self.planes)
        self.digits = np.stack(
            [(z // self.q ** (self.t - 1 - y)) % self.q
             for y in range(self.t)], axis=1)
        self._cache: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()
        self.perf = perf_counters()

    # -- ids ----------------------------------------------------------------------

    def _node(self, chunk: int) -> int:
        return chunk if chunk < self.k else chunk + self.nu

    def _z_sw(self, z: int, y: int, x: int) -> int:
        """Plane z with digit y replaced by x (the sewing partner's)."""
        return z + (x - int(self.digits[z, y])) * self.q ** (self.t - 1 - y)

    def repair_planes(self, lost: int) -> list[int]:
        """The planes a helper sends to repair chunk ``lost``: those
        whose digit y_lost is x_lost (get_repair_subchunks)."""
        node = self._node(lost)
        y, x = divmod(node, self.q)
        return [z for z in range(self.planes) if self.digits[z, y] == x]

    # -- the host algebra (once per signature) -----------------------------------

    def _mul(self, c: int, v: np.ndarray) -> np.ndarray:
        return MUL_TABLE[int(c)][v]

    def _pft(self, a: int, va, b: int, vb, want: int) -> np.ndarray:
        """The PFT value ``want`` from values ``a`` and ``b`` of one pair
        (0/1 coupled, 2/3 uncoupled, the pair's higher-x node first)."""
        row = self._pft_rows.get((a, b, want))
        if row is None:
            inv = gfm.gf_invert(self._pft_gen[[a, b]])
            row = self._pft_rows[a, b, want] = gfm.gf_matmul(
                self._pft_gen[want:want + 1], inv)[0]
        return self._mul(row[0], va) ^ self._mul(row[1], vb)

    def _pair(self, node: int, z: int):
        """(partner node, partner plane, this node's C index) of the
        pair (node, z) belongs to; None for a dot (C == U)."""
        y, x = divmod(node, self.q)
        a = int(self.digits[z, y])
        if a == x:
            return None
        return y * self.q + a, self._z_sw(z, y, x), 0 if x > a else 1

    def _scores(self, nodes, planes) -> dict[int, list[int]]:
        """Planes by how many of ``nodes`` have their dot in them."""
        out: dict[int, list[int]] = {}
        for z in planes:
            s = sum(1 for n in nodes
                    if self.digits[z, n // self.q] == n % self.q)
            out.setdefault(s, []).append(z)
        return dict(sorted(out.items()))

    def _mds_solver(self, erased):
        """U[erased, z] from the other nodes' U at plane z, for any z."""
        erased = sorted(erased)
        known = [n for n in range(self.nodes) if n not in erased]
        D, src = gfm.decode_matrix(self.mds_parity, erased, known)

        def solve(U, z):
            U[erased, z] = gfm.gf_matmul(D, U[src, z])
        return solve

    def _layered(self, erased: set[int], C: np.ndarray) -> None:
        """Fill ``C[erased]`` in place (C: [nodes, planes, K] coefficient
        vectors, known nodes filled): the layered decode, planes taken
        in order of how many erased dots they hold
        (ErasureCodeClay.cc:646-739)."""
        erased = set(erased)
        for n in range(self.k + self.nu, self.nodes):
            if len(erased) >= self.m:
                break
            erased.add(n)
        U = np.zeros_like(C)
        mds_solve = self._mds_solver(erased)
        for planes in self._scores(erased, range(self.planes)).values():
            for z in planes:
                for n in range(self.nodes):
                    if n in erased:
                        continue
                    pair = self._pair(n, z)
                    if pair is None:
                        U[n, z] = C[n, z]
                        continue
                    p, zs, ic = pair
                    U[n, z] = self._pft(ic, C[n, z], 1 - ic, C[p, zs], ic + 2)
                mds_solve(U, z)
            for z in planes:
                for n in sorted(erased):
                    pair = self._pair(n, z)
                    if pair is None:
                        C[n, z] = U[n, z]
                        continue
                    p, zs, ic = pair
                    if p in erased:
                        C[n, z] = self._pft(ic + 2, U[n, z], 3 - ic,
                                            U[p, zs], ic)
                    else:
                        C[n, z] = self._pft(1 - ic, C[p, zs], ic + 2,
                                            U[n, z], ic)

    def _repair_matrix(self, lost_node: int, helpers: list[int],
                       R: list[int]) -> np.ndarray:
        """[planes, d*len(R)]: the lost chunk from the helpers' repair
        planes (repair_one_lost_chunk, ErasureCodeClay.cc:469-643)."""
        q, Z = self.q, self.planes
        K = len(helpers) * len(R)
        C = np.zeros((self.nodes, Z, K), dtype=np.uint8)
        for h, node in enumerate(helpers):
            for j, z in enumerate(R):
                C[node, z, h * len(R) + j] = 1
        real = set(range(self.k)) | set(range(self.k + self.nu, self.nodes))
        aloof = real - set(helpers) - {lost_node}
        row = {lost_node - lost_node % q + x for x in range(q)}
        erased = row | aloof
        if len(erased) > self.m:
            raise ValueError("more erasures than the code corrects")
        U = np.zeros_like(C)
        mds_solve = self._mds_solver(erased)
        out = np.zeros((Z, K), dtype=np.uint8)
        for planes in self._scores({lost_node} | aloof, R).values():
            for z in planes:
                for n in range(self.nodes):
                    if n in erased:
                        continue
                    pair = self._pair(n, z)
                    if pair is None:
                        U[n, z] = C[n, z]
                        continue
                    p, zs, ic = pair
                    if p in aloof:
                        U[n, z] = self._pft(ic, C[n, z], 3 - ic, U[p, zs],
                                            ic + 2)
                    else:
                        U[n, z] = self._pft(ic, C[n, z], 1 - ic, C[p, zs],
                                            ic + 2)
                mds_solve(U, z)
                for n in sorted(row):
                    pair = self._pair(n, z)
                    if pair is None:            # the lost node's own dot
                        out[z] = U[n, z]
                        continue
                    _p, zs, ic = pair           # the partner is the lost node
                    out[zs] = self._pft(ic, C[n, z], ic + 2, U[n, z], 1 - ic)
        return out

    def _build(self, sig) -> _Tables:
        k, Z = self.k, self.planes
        kind = sig[0]
        if kind == "repair":
            _, lost, helpers = sig
            R = self.repair_planes(lost)
            G = self._repair_matrix(self._node(lost),
                                    [self._node(h) for h in helpers], R)
            return _Tables(G, list(helpers), [lost])
        if kind == "encode":
            erased = list(range(k, k + self.m))
        else:
            erased = list(sig[1])
        nodes = {self._node(e) for e in erased}
        padded = set(nodes)
        for n in range(k + self.nu, self.nodes):
            if len(padded) >= self.m:
                break
            padded.add(n)
        src = [c for c in range(k + self.m) if self._node(c) not in padded]
        C = np.zeros((self.nodes, Z, len(src) * Z), dtype=np.uint8)
        for i, c in enumerate(src):
            C[self._node(c), np.arange(Z), i * Z + np.arange(Z)] = 1
        self._layered(nodes, C)
        G = np.concatenate([C[self._node(e)] for e in erased], axis=0)
        return _Tables(G, src, erased)

    def tables(self, sig) -> _Tables:
        """Signature-LRU lookup/build: ``("encode",)``,
        ``("decode", erasures)`` or ``("repair", lost, helpers)``."""
        with self._lock:
            hit = self._cache.get(sig)
            if hit is not None:
                self._cache.move_to_end(sig)
                return hit
        with trace_span("clay.table_build", kind=sig[0]):
            entry = self._build(sig)
        with self._lock:
            entry = self._cache.setdefault(sig, entry)
            self._cache.move_to_end(sig)
            if len(self._cache) > TABLE_CACHE_SIZE:
                self._cache.popitem(last=False)
        return entry

    def decode_sources(self, erasures) -> list[int]:
        """The k chunk ids a decode of ``erasures`` reads, in the order
        ``decode_device`` wants its rows."""
        return self.tables(("decode", tuple(sorted(int(e)
                                                  for e in erasures)))).src

    # -- device calls -------------------------------------------------------------

    def _dispatch(self, kind: str, entry: _Tables, data, z_in: int,
                  sc: int):
        """One jitted call of the signature's matrix over ``data``
        [len(entry.src), S*z_in*sc]."""
        r_in, n = (int(s) for s in data.shape)
        if r_in != len(entry.src):
            raise ValueError(f"{r_in} rows for {len(entry.src)} sources")
        if sc <= 0 or n % (z_in * sc):
            raise ValueError(f"rows of {n} bytes do not hold whole "
                             f"segments of {z_in} sub-chunks of {sc}")
        r_out, z_out = len(entry.out), self.planes
        pallas = (rs_kernels._runs_on_tpu(data)
                  and _pallas_fits(r_in, z_in, r_out, z_out, sc))
        found = entry.dev.get(pallas)
        if found is None:
            plan, host = None, entry.G
            if pallas:
                plan = _plan(entry.G, r_in, z_in, r_out, z_out)
                host = _plan_bits(entry.G, plan, z_in, r_out, z_out)
                if 2 * host.nbytes > _MATRIX_VMEM_BYTES:
                    plan, host = None, entry.G
            found = entry.dev.setdefault(pallas, (jnp.asarray(host), plan))
        mat, plan = found
        out = _APPLY[kind](mat, data, z_in=z_in, sc=sc, r_out=r_out,
                           z_out=z_out, plan=plan)
        self.perf.inc("calls")
        self.perf.inc("device_dispatches")
        if plan is not None:
            self.perf.inc("kernel_calls")
        return out

    def _sub_chunk(self, row_bytes: int, z_in: int,
                   chunk_size: int | None) -> int:
        return (row_bytes // z_in if chunk_size is None
                else int(chunk_size) // self.planes)

    def encode_device(self, data, chunk_size: int | None = None):
        """[k, S*C] data -> [m, S*C] parity, on the device."""
        n = int(data.shape[1])
        with trace_span("clay.encode",
                        stripes=n // (chunk_size or n)):
            data = jnp.asarray(data, dtype=jnp.uint8)
            return self._dispatch(
                "encode", self.tables(("encode",)), data, self.planes,
                self._sub_chunk(n, self.planes, chunk_size))

    def decode_device(self, stack, erasures, chunk_size: int | None = None):
        """``stack`` [k, S*C], the rows ``decode_sources(erasures)`` names
        -> the erased chunks [len(erasures), S*C] in ascending order: the
        layered decode of 1..m erasures."""
        erasures = tuple(sorted(int(e) for e in erasures))
        if not 1 <= len(erasures) <= self.m:
            raise ValueError(f"{len(erasures)} erasures, the code takes "
                             f"1..{self.m}")
        n = int(stack.shape[1])
        with trace_span("clay.decode", stripes=n // (chunk_size or n),
                        erasures=len(erasures)):
            stack = jnp.asarray(stack, dtype=jnp.uint8)
            return self._dispatch(
                "decode", self.tables(("decode", erasures)), stack,
                self.planes, self._sub_chunk(n, self.planes, chunk_size))

    def repair_device(self, helpers, lost: int, helper_ids,
                      chunk_size: int | None = None):
        """``helpers`` [d, S*C/q]: the repair planes of every stripe of the
        d chunks ``helper_ids`` names, in that order (the plugin's
        ``minimum_to_decode({lost}, ...)``) -> the lost chunk [S*C]."""
        lost = int(lost)
        ids = tuple(int(h) for h in helper_ids)
        if len(ids) != self.d or lost in ids:
            raise ValueError(f"repair of {lost} takes {self.d} other "
                             f"chunks, not {list(ids)}")
        z_in = self.planes // self.q
        n = int(helpers.shape[1])
        segment = n if chunk_size is None else int(chunk_size) // self.q
        with trace_span("clay.repair", stripes=n // segment, lost=lost):
            helpers = jnp.asarray(helpers, dtype=jnp.uint8)
            out = self._dispatch(
                "repair", self.tables(("repair", lost, ids)), helpers,
                z_in, self._sub_chunk(n, z_in, chunk_size))
            self.perf.inc("repair_read_bytes", len(ids) * n)
            self.perf.inc("repair_bytes", n * self.q)
            return out

    # -- host wrappers ------------------------------------------------------------

    def encode(self, data: np.ndarray, chunk_size: int | None = None
               ) -> np.ndarray:
        return np.asarray(jax.device_get(
            self.encode_device(np.ascontiguousarray(data, np.uint8),
                               chunk_size)))

    def decode(self, stack: np.ndarray, erasures,
               chunk_size: int | None = None) -> np.ndarray:
        return np.asarray(jax.device_get(
            self.decode_device(np.ascontiguousarray(stack, np.uint8),
                               erasures, chunk_size)))

    def repair(self, helpers: np.ndarray, lost: int, helper_ids,
               chunk_size: int | None = None) -> np.ndarray:
        return np.asarray(jax.device_get(
            self.repair_device(np.ascontiguousarray(helpers, np.uint8),
                               lost, helper_ids, chunk_size)))
