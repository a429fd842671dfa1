"""ctypes bindings for the native runtime (native/).

The reference's plugin host is C++ loading plugin .so files via dlopen
(reference: src/erasure-code/ErasureCodePlugin.cc:126-184); here the native
registry (native/src/registry.cc) implements that exact contract and Python
binds it with ctypes (no pybind11 in this environment).
"""
from __future__ import annotations

import ctypes as C
import os
import subprocess
import threading

import numpy as np

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
BUILD_DIR = os.path.join(NATIVE_DIR, "build")

_build_lock = threading.Lock()
_built = False
_registry_lib = None


def build(force: bool = False) -> str:
    """Run `make -C native`; returns the build dir.

    make itself is the staleness check (cheap no-op when up to date), so a
    stale pre-existing build/ never masks newer kernels — it runs once per
    process, unconditionally."""
    global _built
    with _build_lock:
        if force or not _built:
            subprocess.run(["make", "-C", NATIVE_DIR],
                           check=True, capture_output=True)
            _built = True
    return BUILD_DIR


def registry_lib() -> C.CDLL:
    """The process-wide handle to libec_registry.so (builds on demand).

    Shared by every ctypes consumer (NativeRegistry, the gf8 SIMD fast
    path, the native crc32c) so the library is built and dlopened once."""
    global _registry_lib
    with _build_lock:
        if _registry_lib is not None:
            return _registry_lib
    build()
    lib = C.CDLL(os.path.join(BUILD_DIR, "libec_registry.so"))
    lib.ec_simd_level.restype = C.c_int
    lib.ec_crc32c.restype = C.c_uint32
    lib.ec_crc32c.argtypes = [C.c_uint32, C.c_void_p, C.c_size_t]
    lib.ec_apply_matrix.restype = C.c_int
    lib.ec_apply_matrix.argtypes = [
        C.c_void_p, C.c_int, C.c_int, C.c_void_p, C.c_void_p, C.c_size_t]
    lib.ec_store_commit_batch.restype = C.c_int
    lib.ec_store_commit_batch.argtypes = [C.c_int] + [C.c_void_p] * 13
    with _build_lock:
        _registry_lib = lib
    return _registry_lib


class _CodecOps(C.Structure):
    _fields_ = [
        ("create", C.c_void_p),
        ("destroy", C.c_void_p),
        ("get_data_chunk_count", C.c_void_p),
        ("get_chunk_count", C.c_void_p),
        ("get_chunk_size", C.c_void_p),
        ("encode", C.c_void_p),
        ("decode", C.c_void_p),
        ("minimum_to_decode", C.c_void_p),
    ]


_CREATE = C.CFUNCTYPE(C.c_void_p, C.POINTER(C.c_char_p),
                      C.POINTER(C.c_char_p), C.c_int, C.c_char_p, C.c_int)
_DESTROY = C.CFUNCTYPE(None, C.c_void_p)
_GETINT = C.CFUNCTYPE(C.c_int, C.c_void_p)
_CHUNKSZ = C.CFUNCTYPE(C.c_uint, C.c_void_p, C.c_uint)
_ENCODE = C.CFUNCTYPE(C.c_int, C.c_void_p, C.POINTER(C.c_ubyte),
                      C.POINTER(C.c_ubyte), C.c_size_t)
_DECODE = C.CFUNCTYPE(C.c_int, C.c_void_p, C.POINTER(C.c_void_p), C.c_size_t,
                      C.POINTER(C.c_int), C.c_int)
_MINIMUM = C.CFUNCTYPE(C.c_int, C.c_void_p, C.POINTER(C.c_int), C.c_int,
                       C.POINTER(C.c_int), C.c_int, C.POINTER(C.c_int),
                       C.c_int)


class NativeRegistry:
    """Binding for libec_registry.so (the dlopen plugin host)."""

    _instance = None

    def __init__(self):
        self.lib = registry_lib()
        self.lib.ec_registry_load.argtypes = [C.c_char_p, C.c_char_p,
                                              C.c_char_p, C.c_int]
        self.lib.ec_registry_get.restype = C.POINTER(_CodecOps)
        self.lib.ec_registry_get.argtypes = [C.c_char_p]
        self.lib.ec_registry_count.restype = C.c_int
        self.lib.ec_registry_preload.argtypes = [C.c_char_p, C.c_char_p,
                                                 C.c_char_p, C.c_int]

    @classmethod
    def instance(cls) -> "NativeRegistry":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def load(self, name: str, directory: str | None = None) -> None:
        err = C.create_string_buffer(512)
        rc = self.lib.ec_registry_load(
            name.encode(), (directory or BUILD_DIR).encode(), err, 512)
        if rc != 0:
            raise IOError(rc, err.value.decode() or f"load {name} failed")

    def preload(self, names_csv: str, directory: str | None = None) -> None:
        err = C.create_string_buffer(512)
        rc = self.lib.ec_registry_preload(
            names_csv.encode(), (directory or BUILD_DIR).encode(), err, 512)
        if rc != 0:
            raise IOError(rc, err.value.decode() or "preload failed")

    def count(self) -> int:
        return self.lib.ec_registry_count()

    def factory(self, name: str, profile: dict[str, str],
                directory: str | None = None) -> "NativeCodec":
        """registry.factory (ErasureCodePlugin.cc:92-120): load on demand,
        instantiate with the profile."""
        ops = self.lib.ec_registry_get(name.encode())
        if not ops:
            self.load(name, directory)
            ops = self.lib.ec_registry_get(name.encode())
        if not ops:
            raise IOError(f"plugin {name} not registered after load")
        return NativeCodec(ops.contents, profile)


class NativeCodec:
    """One codec instance behind the C vtable."""

    def __init__(self, ops: _CodecOps, profile: dict[str, str]):
        self._ops = ops
        self._create = _CREATE(ops.create)
        self._destroy = _DESTROY(ops.destroy)
        self._k_fn = _GETINT(ops.get_data_chunk_count)
        self._n_fn = _GETINT(ops.get_chunk_count)
        self._chunk_size = _CHUNKSZ(ops.get_chunk_size)
        self._encode = _ENCODE(ops.encode)
        self._decode = _DECODE(ops.decode)
        self._minimum = _MINIMUM(ops.minimum_to_decode)

        keys = (C.c_char_p * len(profile))(
            *[k.encode() for k in profile])
        vals = (C.c_char_p * len(profile))(
            *[str(v).encode() for v in profile.values()])
        err = C.create_string_buffer(256)
        self._h = self._create(keys, vals, len(profile), err, 256)
        if not self._h:
            raise ValueError(err.value.decode() or "codec init failed")

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._destroy(h)
            self._h = None

    @property
    def k(self) -> int:
        return self._k_fn(self._h)

    @property
    def n(self) -> int:
        return self._n_fn(self._h)

    def get_chunk_size(self, object_size: int) -> int:
        return self._chunk_size(self._h, object_size)

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data [k, chunk] uint8 -> parity [m, chunk]."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        k, chunk = data.shape
        assert k == self.k, f"expected {self.k} data chunks"
        parity = np.zeros((self.n - k, chunk), dtype=np.uint8)
        rc = self._encode(
            self._h, data.ctypes.data_as(C.POINTER(C.c_ubyte)),
            parity.ctypes.data_as(C.POINTER(C.c_ubyte)), chunk)
        if rc != 0:
            raise IOError(rc, "encode failed")
        return parity

    def decode(self, chunks: dict[int, np.ndarray],
               erasures: list[int], chunk_size: int) -> dict[int, np.ndarray]:
        """chunks: available chunk id -> [chunk] uint8; returns the
        reconstructed chunks for `erasures`."""
        n = self.n
        bufs: list[np.ndarray | None] = [None] * n
        ptrs = (C.c_void_p * n)()
        for i, arr in chunks.items():
            arr = np.ascontiguousarray(arr, dtype=np.uint8)
            assert arr.nbytes == chunk_size
            bufs[i] = arr
            ptrs[i] = arr.ctypes.data
        out = {}
        for e in erasures:
            buf = np.zeros(chunk_size, dtype=np.uint8)
            bufs[e] = buf
            ptrs[e] = buf.ctypes.data
            out[e] = buf
        er = (C.c_int * len(erasures))(*erasures)
        rc = self._decode(self._h, ptrs, chunk_size, er, len(erasures))
        if rc != 0:
            raise IOError(rc, "decode failed")
        return out

    def minimum_to_decode(self, erasures: list[int],
                          available: list[int]) -> list[int]:
        er = (C.c_int * len(erasures))(*erasures)
        av = (C.c_int * len(available))(*available)
        out = (C.c_int * self.k)()
        got = self._minimum(self._h, er, len(erasures), av, len(available),
                            out, self.k)
        if got < 0:
            raise IOError(got, "cannot decode")
        return list(out[:got])


__all__ = ["build", "registry_lib", "NativeRegistry", "NativeCodec",
           "BUILD_DIR", "NATIVE_DIR"]
