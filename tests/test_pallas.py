"""Pallas fused GF kernel: bit-exact vs the numpy field math (interpret
mode on CPU; the same kernel compiles for TPU where it is the auto-routed
encode path)."""
import numpy as np
import pytest

from ceph_tpu.gf import matrix as gfm
from ceph_tpu.ops import rs_kernels
from ceph_tpu.ops.pallas_kernels import (expand_bits_plane_major,
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


@pytest.mark.parametrize("r,k,n,tile", [
    (4, 8, 2048, 512),       # even tiles
    (2, 4, 3000, 512),       # ragged tail -> padding path
    (3, 5, 512, 1024),       # single partial tile
    (1, 2, 256, 256),        # minimal shapes
])
def test_pallas_matches_field_math(r, k, n, tile):
    rng = np.random.default_rng(r * 100 + k)
    mat = rng.integers(0, 256, size=(r, k), dtype=np.uint8)
    data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
    got = np.asarray(gf_apply_pallas(mat, data, tile_n=tile, interpret=True))
    assert np.array_equal(got, gfm.gf_matmul(mat, data))


def test_pallas_matches_xla_bitslice():
    rng = np.random.default_rng(7)
    mat = rng.integers(0, 256, size=(4, 8), dtype=np.uint8)
    data = rng.integers(0, 256, size=(8, 4096), dtype=np.uint8)
    a = np.asarray(gf_apply_pallas(mat, data, tile_n=1024, interpret=True))
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
