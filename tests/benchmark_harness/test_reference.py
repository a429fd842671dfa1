"""The plain reference against witnesses that are not the program:
the byte-at-a-time definition of the crc, the standard check value,
google_crc32c where it is installed, and the algebra of the code."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.lib import reference as R  # noqa: E402


def test_crc32c_check_value():
    # CRC-32C("123456789") = 0xE3069283 with the usual final xor; ceph's
    # register form leaves the xor to the caller
    assert R.crc32c_bytewise(b"123456789") ^ 0xFFFFFFFF == 0xE3069283


@pytest.mark.parametrize("length", [0, 1, 3, 4, 7, 511, 512, 1024, 4096,
                                    5000, 65536, 131072 + 4])
def test_crc32c_rows_equals_the_definition(length):
    rng = np.random.default_rng(length)
    rows = rng.integers(0, 256, (3, length), dtype=np.uint8)
    want = [R.crc32c_bytewise(r.tobytes()) for r in rows]
    assert [int(c) for c in R.crc32c_rows(rows)] == want


@pytest.mark.parametrize("seed", [0, 1, 0xFFFFFFFF, 0x12345678])
def test_crc32c_rows_carries_the_seed(seed):
    rows = np.arange(2 * 2048, dtype=np.uint8).reshape(2, 2048)
    want = [R.crc32c_bytewise(r.tobytes(), seed) for r in rows]
    assert [int(c) for c in R.crc32c_rows(rows, seed)] == want


def test_crc32c_rows_against_google_crc32c_at_a_shards_size():
    google_crc32c = pytest.importorskip("google_crc32c")
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 256, (12, 524288), dtype=np.uint8)
    want = [google_crc32c.value(r.tobytes()) ^ 0xFFFFFFFF for r in rows]
    assert [int(c) for c in R.crc32c_rows(rows)] == want


def test_gf_field_axioms():
    for a in (1, 2, 3, 0x53, 0xCA, 255):
        assert R.gf_mul(a, R.gf_inv(a)) == 1
        assert R.gf_mul(a, 1) == a and R.gf_mul(a, 0) == 0
    assert R.gf_mul(0x02, 0x80) == 0x1D          # x * x^7 = x^8 = 0x11D - x^8
    with pytest.raises(ZeroDivisionError):
        R.gf_inv(0)


def test_cauchy_matrix_is_the_isa_l_form():
    p = R.cauchy_parity_matrix(8, 4)
    assert p.shape == (4, 8)
    for i in range(4):
        for j in range(8):
            assert R.gf_mul(int(p[i, j]), (i + 8) ^ j) == 1


def test_matrix_inverse_and_product():
    rng = np.random.default_rng(2)
    gen = np.concatenate([np.eye(8, dtype=np.uint8),
                          R.cauchy_parity_matrix(8, 4)])
    for _ in range(5):
        rows = sorted(rng.choice(12, size=8, replace=False))
        a = gen[rows]
        assert np.array_equal(R.gf_matmul(a, R.gf_invert_matrix(a)),
                              np.eye(8, dtype=np.uint8))
    with pytest.raises(ValueError):
        R.gf_invert_matrix(np.zeros((2, 2), dtype=np.uint8))


@pytest.mark.parametrize("erased", [[0, 9], [3], [8, 11], [0, 1, 2, 3],
                                    [4, 5, 10, 11]])
def test_decode_recovers_what_encode_made(erased):
    rng = np.random.default_rng(7)
    p = R.cauchy_parity_matrix(8, 4)
    data = rng.integers(0, 256, (8, 4096), dtype=np.uint8)
    full = np.concatenate([data, R.gf_apply(p, data)])
    d, src = R.decode_matrix(p, erased)
    assert src == [i for i in range(12) if i not in erased][:8]
    assert np.array_equal(R.gf_apply(d, full[src]), full[sorted(erased)])


def test_decode_refuses_too_few_survivors():
    with pytest.raises(ValueError):
        R.decode_matrix(R.cauchy_parity_matrix(8, 4), [0, 1, 2, 3, 4])


def test_gf_apply_is_the_scalar_definition():
    rng = np.random.default_rng(3)
    mat = rng.integers(0, 256, (3, 5), dtype=np.uint8)
    data = rng.integers(0, 256, (5, 64), dtype=np.uint8)
    want = np.zeros((3, 64), dtype=np.uint8)
    for i in range(3):
        for n in range(64):
            acc = 0
            for j in range(5):
                acc ^= R.gf_mul(int(mat[i, j]), int(data[j, n]))
            want[i, n] = acc
    assert np.array_equal(R.gf_apply(mat, data), want)


def test_object_shards_layout_and_padding():
    k, chunk = 8, 16
    p = R.cauchy_parity_matrix(k, 4)
    payload = np.arange(3 * k * chunk - 5, dtype=np.uint32).astype(np.uint8)
    shards = R.object_shards(payload, k, p, chunk)
    assert shards.shape == (12, 3 * chunk)
    padded = np.concatenate([payload, np.zeros(5, np.uint8)])
    # data shard i holds chunk i of every stripe, back to back
    for i in range(k):
        for s in range(3):
            lo = s * k * chunk + i * chunk
            assert np.array_equal(shards[i, s * chunk:(s + 1) * chunk],
                                  padded[lo:lo + chunk])
    assert np.array_equal(shards[k:], R.gf_apply(p, shards[:k]))


def test_the_reference_imports_nothing_of_the_program():
    src = (Path(__file__).resolve().parents[2] / "benchmark" / "lib"
           / "reference.py").read_text()
    assert "ceph_tpu" not in src.replace("``ceph_tpu``", "")
    assert "import jax" not in src
