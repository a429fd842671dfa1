"""Pallas fused GF kernel: bit-exact vs the numpy field math (interpret
mode on CPU; the same kernel compiles for TPU where it is the auto-routed
encode path)."""
import jax
import numpy as np
import pytest

from ceph_tpu.gf import cauchy1, decode_matrix
from ceph_tpu.gf import matrix as gfm
from ceph_tpu.gf import ref as gfref
from ceph_tpu.ops import rs_kernels
from ceph_tpu.ops.pallas_kernels import (_column_groups,
                                         expand_bits_plane_major,
                                         gf_apply_pallas,
                                         gf_apply_stripes_pallas)


@pytest.mark.parametrize("r,k,S,n,tile", [
    (4, 8, 8, 1024, 512),     # even groups of 4
    (4, 8, 6, 1024, 512),     # stripe count not a group multiple
    (2, 4, 3, 700, 256),      # ragged columns + fewer stripes than a group
    (4, 8, 1, 512, 512),      # single stripe
    (1, 8, 16, 512, 256),     # single-erasure decode: groups of 8
    (3, 7, 12, 512, 256),     # m=3 over odd k: groups of 8 + stripe pad
    (3, 10, 5, 384, 128),     # odd r, batch shorter than one group
])
def test_stripes_kernel_matches_field_math(r, k, S, n, tile):
    """Vertical layout: stripe s = rows [s*k, (s+1)*k); parity at
    [s*r, (s+1)*r).  Bit-exact vs per-stripe host math."""
    rng = np.random.default_rng(r * 1000 + S)
    mat = rng.integers(0, 256, size=(r, k), dtype=np.uint8)
    data = rng.integers(0, 256, size=(S * k, n), dtype=np.uint8)
    got = np.asarray(gf_apply_stripes_pallas(
        mat, data, S, tile_n=tile, interpret=True))
    assert got.shape == (S * r, n)
    for s in range(S):
        want = gfm.gf_matmul(mat, data[s * k:(s + 1) * k])
        assert np.array_equal(got[s * r:(s + 1) * r], want), f"stripe {s}"


def test_stripes_dispatch_fallback_matches():
    """rs_kernels.gf_apply_stripes off-TPU folds to the XLA path and must
    agree with the interpret-mode pallas kernel."""
    rng = np.random.default_rng(4)
    mat = rng.integers(0, 256, size=(4, 8), dtype=np.uint8)
    data = rng.integers(0, 256, size=(5 * 8, 512), dtype=np.uint8)
    a = np.asarray(rs_kernels.gf_apply_stripes(mat, data, 5))
    b = np.asarray(gf_apply_stripes_pallas(mat, data, 5, tile_n=256,
                                           interpret=True))
    assert np.array_equal(a, b)


# upstream's bench.sh grid + the metric of record + the plugin default
# (tests/test_tpu_lowering.py compiles the same pairs for a v5e)
GRID = [(2, 1), (3, 2), (4, 2), (4, 3), (6, 2), (6, 3), (6, 4), (10, 3),
        (10, 4), (8, 4), (8, 3), (7, 3)]
# one grid step, a few, a ragged tail that pads, and a row too short to
# stack column groups (under G * 128: one segment through the same body)
WIDTHS = (1024, 4096, 131072, 131072 + 384, "short")


def _grid_cases():
    for k, m in GRID:
        for kind, erasures in (("encode", None), ("decode1", [0]),
                               ("decode2", [0, k + 1])):
            if erasures and len(erasures) > m:
                continue
            for n in WIDTHS:
                yield pytest.param(k, m, erasures, n,
                                   id=f"k{k}m{m}-{kind}-{n}")


def _apply_matrix(k, m, erasures):
    pm = cauchy1(k, m)
    return pm if erasures is None else decode_matrix(pm, erasures)[0]


@pytest.mark.parametrize("k,m,erasures,n", _grid_cases())
def test_pallas_matches_gf_ref(k, m, erasures, n):
    """Every profile's encode, one- and two-erasure decode matrix through
    the horizontal kernel (interpret mode, x64 on as conftest sets it),
    byte for byte against the pure-numpy field reference."""
    mat = _apply_matrix(k, m, erasures)
    if n == "short":
        n = _column_groups(mat.shape[0], 1 << 20)[0] * 128 - 28
    rng = np.random.default_rng(1000 * k + 10 * m + len(erasures or ()))
    data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
    got = np.asarray(gf_apply_pallas(mat, data, interpret=True))
    assert np.array_equal(got, gfref.apply_matrix(mat, data))


@pytest.mark.parametrize("r,k,n", [
    (4, 8, 2048),        # even tiles
    (2, 4, 3000),        # ragged tail -> padding path
    (3, 5, 512),         # odd k padded to whole words, odd r
    (1, 2, 256),         # minimal shapes
    (5, 8, 70000),       # r > 4: one segment, two grid steps
])
def test_pallas_matches_field_math(r, k, n):
    """Random matrices, and without x64 (a codec process that never
    places PGs)."""
    rng = np.random.default_rng(r * 100 + k)
    mat = rng.integers(0, 256, size=(r, k), dtype=np.uint8)
    data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
    with jax.enable_x64(False):
        got = np.asarray(gf_apply_pallas(mat, data, interpret=True))
    assert np.array_equal(got, gfm.gf_matmul(mat, data))


def test_pallas_matches_xla_bitslice():
    rng = np.random.default_rng(7)
    mat = rng.integers(0, 256, size=(4, 8), dtype=np.uint8)
    data = rng.integers(0, 256, size=(8, 4096), dtype=np.uint8)
    a = np.asarray(gf_apply_pallas(mat, data, interpret=True))
    b = np.asarray(rs_kernels.gf_apply_bitslice(mat, data))
    assert np.array_equal(a, b)


def test_plane_major_expansion_consistent():
    """The plane-major bit matrix must express the same linear map as the
    interleaved one used by the XLA path."""
    rng = np.random.default_rng(9)
    mat = rng.integers(0, 256, size=(3, 4), dtype=np.uint8)
    B = np.asarray(expand_bits_plane_major(mat))
    r, k = mat.shape
    data = rng.integers(0, 256, size=(k, 64), dtype=np.uint8)
    # manual plane-major apply
    planes = np.concatenate([(data >> b) & 1 for b in range(8)], axis=0)
    acc = (B.astype(np.int64) @ planes.astype(np.int64)) & 1
    out = np.zeros((r, 64), dtype=np.uint8)
    for b in range(8):
        out |= (acc[b * r:(b + 1) * r] << b).astype(np.uint8)
    assert np.array_equal(out, gfm.gf_matmul(mat, data))


def test_auto_routing_off_tpu_stays_on_xla():
    """On the CPU test backend, auto must not pick pallas (it would need
    interpret mode)."""
    rng = np.random.default_rng(11)
    mat = rng.integers(0, 256, size=(2, 4), dtype=np.uint8)
    data = rng.integers(0, 256, size=(4, 2048), dtype=np.uint8)
    out = np.asarray(rs_kernels.gf_apply(mat, data, "auto"))
    assert np.array_equal(out, gfm.gf_matmul(mat, data))
