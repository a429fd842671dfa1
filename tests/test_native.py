"""Native C++ runtime: registry dlopen contract, RS codec parity with the
Python/JAX field math, broken-plugin failure paths.

Mirrors the reference's registry tests (reference:
src/test/erasure-code/TestErasureCodePlugin.cc exercising the deliberately
broken ErasureCodePlugin{FailToInitialize,FailToRegister,MissingEntryPoint,
MissingVersion}.cc) and per-plugin encode/decode roundtrips
(TestErasureCodeIsa.cc / TestErasureCodeJerasure.cc:80-135)."""
import numpy as np
import pytest

from ceph_tpu.gf import matrix as gfm
from ceph_tpu.native import NativeRegistry, build


@pytest.fixture(scope="module")
def registry():
    build()
    return NativeRegistry.instance()


@pytest.fixture(scope="module")
def rs(registry):
    return registry.factory("cpp_rs", {"k": 4, "m": 2,
                                       "technique": "reed_sol_van"})


def payload(k, chunk, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=(k, chunk), dtype=np.uint8)


class TestRegistry:
    def test_load_and_count(self, registry):
        registry.load("cpp_rs")
        assert registry.count() >= 1
        registry.load("cpp_rs")          # idempotent

    def test_factory_unknown_plugin(self, registry):
        with pytest.raises(IOError):
            registry.factory("does_not_exist", {})

    def test_wrong_version_rejected(self, registry):
        with pytest.raises(IOError) as ei:
            registry.load("badver")
        assert "version" in str(ei.value)

    def test_fail_to_initialize(self, registry):
        with pytest.raises(IOError):
            registry.load("failinit")

    def test_fail_to_register(self, registry):
        with pytest.raises(IOError) as ei:
            registry.load("noreg")
        assert "register" in str(ei.value)

    def test_missing_entry_point(self, registry):
        with pytest.raises(IOError) as ei:
            registry.load("noentry")
        assert "__erasure_code_init" in str(ei.value)

    def test_bad_profile_rejected(self, registry):
        with pytest.raises(ValueError):
            registry.factory("cpp_rs", {"k": 300, "m": 2})
        with pytest.raises(ValueError):
            registry.factory("cpp_rs", {"k": 4, "m": 2,
                                        "technique": "nope"})

    def test_preload(self, registry):
        registry.preload("cpp_rs")


class TestNativeRS:
    @pytest.mark.parametrize("technique,pyfn", [
        ("reed_sol_van", gfm.rs_vandermonde_jerasure),
        ("cauchy", gfm.cauchy1),
        ("vandermonde_isa", gfm.rs_vandermonde_isa),
    ])
    def test_encode_matches_python_field_math(self, registry, technique,
                                              pyfn):
        """The native codec and the Python/JAX path share one field: the
        parity bytes must be identical."""
        k, m, chunk = 5, 3, 512
        codec = registry.factory("cpp_rs", {"k": k, "m": m,
                                            "technique": technique})
        data = payload(k, chunk, seed=1)
        got = codec.encode(data)
        want = gfm.gf_matmul(pyfn(k, m), data)
        assert np.array_equal(got, want)

    def test_roundtrip_all_single_erasures(self, rs):
        k, chunk = 4, 256
        data = payload(k, chunk, seed=2)
        parity = rs.encode(data)
        full = {i: data[i] for i in range(k)}
        full.update({k + i: parity[i] for i in range(parity.shape[0])})
        for lost in range(6):
            avail = {i: v for i, v in full.items() if i != lost}
            rec = rs.decode(avail, [lost], chunk)
            assert np.array_equal(rec[lost], full[lost]), f"chunk {lost}"

    def test_roundtrip_double_erasures(self, rs):
        k, chunk = 4, 256
        data = payload(k, chunk, seed=3)
        parity = rs.encode(data)
        full = {i: data[i] for i in range(k)}
        full.update({k + i: parity[i] for i in range(2)})
        for a in range(6):
            for b in range(a + 1, 6):
                avail = {i: v for i, v in full.items() if i not in (a, b)}
                rec = rs.decode(avail, [a, b], chunk)
                assert np.array_equal(rec[a], full[a])
                assert np.array_equal(rec[b], full[b])

    def test_too_many_erasures(self, rs):
        k, chunk = 4, 64
        data = payload(k, chunk)
        parity = rs.encode(data)
        avail = {0: data[0], 1: data[1], 4: parity[0]}
        with pytest.raises(IOError):
            rs.decode(avail, [2, 3, 5], chunk)

    def test_minimum_to_decode(self, rs):
        got = rs.minimum_to_decode([0], [1, 2, 3, 4, 5])
        assert len(got) == 4
        assert set(got) <= {1, 2, 3, 4, 5}
        with pytest.raises(IOError):
            rs.minimum_to_decode([0, 1, 2], [3, 4])

    def test_chunk_size_alignment(self, rs):
        # ceil(object/k) aligned up to 32 (SIMD_ALIGN, ErasureCode.cc:42)
        assert rs.get_chunk_size(4096) == 1024
        assert rs.get_chunk_size(4097) == 1056
        assert rs.get_chunk_size(1) == 32

    def test_defaults_are_reed_sol_van_7_3(self, registry):
        codec = registry.factory("cpp_rs", {})
        assert codec.k == 7 and codec.n == 10


class TestPythonPluginBridge:
    """cpp_rs through the Python plugin registry: same interface, same
    bytes as the jax_rs plugin (they share one field)."""

    def test_roundtrip_via_python_interface(self):
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        reg = ErasureCodePluginRegistry.instance()
        ec = reg.factory("cpp_rs", "", {"k": "4", "m": "2",
                                        "technique": "reed_sol_van"})
        data = bytes(payload(1, 4096, seed=7)[0].tobytes())
        encoded = ec.encode(set(range(6)), data)
        assert len(encoded) == 6
        # drop two chunks, decode, compare
        chunks = {i: v for i, v in encoded.items() if i not in (1, 4)}
        decoded = ec.decode({0, 1, 2, 3}, chunks, chunk_size=encoded[0].nbytes)
        got = b"".join(decoded[i].tobytes() for i in range(4))[:len(data)]
        assert got == data

    def test_matches_jax_rs_bytes(self):
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        reg = ErasureCodePluginRegistry.instance()
        prof = {"k": "4", "m": "2", "technique": "cauchy"}
        cpp = reg.factory("cpp_rs", "", dict(prof))
        jax_rs = reg.factory("jax_rs", "", dict(prof, device="numpy"))
        data = bytes(payload(1, 8192, seed=8)[0].tobytes())
        a = cpp.encode(set(range(6)), data)
        b = jax_rs.encode(set(range(6)), data)
        for i in range(6):
            assert np.array_equal(a[i], b[i]), f"chunk {i} differs"

    def test_mapping_profile_matches_jax_rs(self):
        """The mapping= profile key must produce the same chunk layout in
        both plugins (review regression)."""
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        reg = ErasureCodePluginRegistry.instance()
        prof = {"k": "2", "m": "1", "technique": "cauchy",
                "mapping": "_DD"}
        cpp = reg.factory("cpp_rs", "", dict(prof))
        jx = reg.factory("jax_rs", "", dict(prof, device="numpy"))
        data = bytes(payload(1, 1024, seed=9)[0].tobytes())
        a = cpp.encode(set(range(3)), data)
        b = jx.encode(set(range(3)), data)
        for i in range(3):
            assert np.array_equal(a[i], b[i]), f"chunk {i} differs"

    def test_concurrent_decodes_thread_safe(self):
        """Concurrent decodes through the shared LRU (review regression:
        the cached entry must be copied out under the lock)."""
        import threading
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        reg = ErasureCodePluginRegistry.instance()
        ec = reg.factory("cpp_rs", "", {"k": "4", "m": "2",
                                        "technique": "cauchy"})
        data = bytes(payload(1, 4096, seed=10)[0].tobytes())
        encoded = ec.encode(set(range(6)), data)
        csz = encoded[0].nbytes
        errors = []

        def worker(drop):
            try:
                for _ in range(50):
                    chunks = {i: v for i, v in encoded.items()
                              if i not in drop}
                    dec = ec.decode(set(range(4)), chunks, chunk_size=csz)
                    got = b"".join(dec[i].tobytes()
                                   for i in range(4))[:len(data)]
                    assert got == data
            except BaseException as e:      # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=({a, b},))
                   for a in range(3) for b in range(3, 6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:1]

    def test_mapped_decode_roundtrip_cpp_rs(self):
        """Decode must invert the physical->logical mapping (review/corpus
        regression: encode remapped but decode did not)."""
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        reg = ErasureCodePluginRegistry.instance()
        ec = reg.factory("cpp_rs", "", {"k": "4", "m": "2",
                                        "technique": "reed_sol_van",
                                        "mapping": "_DDD_D"})
        data = bytes(payload(1, 8192, seed=12)[0].tobytes())
        enc = ec.encode(set(range(6)), data)
        for lost in ((0,), (1,), (0, 1), (1, 5), (0, 4)):
            avail = {i: v for i, v in enc.items() if i not in lost}
            got = ec.decode_concat(avail)[:len(data)]
            assert bytes(got) == data, f"erasure {lost}"
