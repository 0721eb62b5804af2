"""ctypes loader for the native C++ kernel library (lazy build via make).

Python<->native binding uses ctypes (no pybind11 in this image). The library
is built on first use into ops/native/_build/ (ignored by git, so a fresh
checkout builds it from the committed .cc files) and cached; if the
toolchain is unavailable the loader logs the cause once and callers fall
back to numpy paths (ops/backend.py resolution order).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

from ceph_tpu.utils.dout import Dout

log = Dout("native")

_DIR = Path(__file__).parent / "native"
_SO = _DIR / "_build" / "libceph_tpu_native.so"
_lock = threading.Lock()
_lib = None
_failed = False


def get_lib():
    """Return the loaded library or None if build/load failed."""
    global _lib, _failed
    if _lib is not None or _failed:
        return _lib
    with _lock:
        if _lib is not None or _failed:
            return _lib
        try:
            srcs = [_DIR / "gf256.cc", _DIR / "io_engine.cc",
                    _DIR / "lzcodecs.cc"]
            if not _SO.exists() or any(
                    _SO.stat().st_mtime < src.stat().st_mtime
                    for src in srcs if src.exists()):
                subprocess.run(
                    ["make", "-s", "-C", str(_DIR)],
                    check=True, capture_output=True, timeout=300)
            lib = ctypes.CDLL(str(_SO))
            _bind(lib)
            lib.gf256_init()
            _lib = lib
        except Exception as exc:
            _failed = True
            # a failed make carries the compiler's words in stderr
            detail = getattr(exc, "stderr", None) or b""
            log(0, f"native library unavailable ({exc!r}"
                + (f": {detail.decode(errors='replace').strip()}"
                   if detail else "")
                + "); host kernels fall back to the numpy twin")
        return _lib


def _bind(lib) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.gf256_init.restype = None
    lib.gf256_region_xor.argtypes = [u8p, u8p, ctypes.c_uint64]
    lib.gf256_region_mul_add.argtypes = [u8p, u8p, ctypes.c_uint8,
                                         ctypes.c_uint64]
    lib.gf256_matvec.argtypes = [u8p, ctypes.c_int, ctypes.c_int, u8p, u8p,
                                 ctypes.c_uint64]
    lib.ceph_crc32c.restype = ctypes.c_uint32
    lib.ceph_crc32c.argtypes = [ctypes.c_uint32, u8p, ctypes.c_uint64]
    lib.ceph_xxhash64.restype = ctypes.c_uint64
    lib.ceph_xxhash64.argtypes = [ctypes.c_uint64, u8p, ctypes.c_uint64]
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.ioeng_open.restype = ctypes.c_int
    lib.ioeng_open.argtypes = [ctypes.c_char_p]
    lib.ioeng_size.restype = ctypes.c_int64
    lib.ioeng_size.argtypes = [ctypes.c_int]
    lib.ioeng_append.restype = ctypes.c_int64
    lib.ioeng_append.argtypes = [ctypes.c_int, u8p, ctypes.c_uint64,
                                 ctypes.c_uint32, u32p]
    lib.ioeng_read.restype = ctypes.c_int64
    lib.ioeng_read.argtypes = [ctypes.c_int, ctypes.c_uint64, u8p,
                               ctypes.c_uint64, ctypes.c_uint32, u32p]
    lib.ioeng_sync.restype = ctypes.c_int
    lib.ioeng_sync.argtypes = [ctypes.c_int]
    lib.ioeng_close.restype = ctypes.c_int
    lib.ioeng_close.argtypes = [ctypes.c_int]
    lib.ceph_xxhash32.restype = ctypes.c_uint32
    lib.ceph_xxhash32.argtypes = [ctypes.c_uint32, u8p, ctypes.c_uint64]
    for fn in ("lz4_compress", "lz4_decompress", "snappy_compress",
               "snappy_decompress"):
        f = getattr(lib, fn)
        f.restype = ctypes.c_int64
        f.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64]
    for fn in ("lz4_max_compressed", "snappy_max_compressed"):
        f = getattr(lib, fn)
        f.restype = ctypes.c_int64
        f.argtypes = [ctypes.c_int64]
    lib.snappy_uncompressed_length.restype = ctypes.c_int64
    lib.snappy_uncompressed_length.argtypes = [u8p, ctypes.c_int64]


def _as_u8p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def available() -> bool:
    return get_lib() is not None


def matvec(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """[m,k] (x) [k,N] -> [m,N] via the native ec_encode_data-role kernel."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    m, k = mat.shape
    n = data.shape[1]
    out = np.empty((m, n), dtype=np.uint8)
    lib.gf256_matvec(_as_u8p(mat), m, k, _as_u8p(data), _as_u8p(out), n)
    return out


def region_xor(dst: np.ndarray, src: np.ndarray) -> None:
    lib = get_lib()
    lib.gf256_region_xor(_as_u8p(dst), _as_u8p(src), dst.size)


def crc32c(data, crc: int = 0) -> int:
    """Standard CRC-32C (Castagnoli): crc32c(b"123456789") == 0xE3069283.
    Pass the previous value to continue a running crc."""
    lib = get_lib()
    if lib is None:
        from ceph_tpu.utils import checksum
        return checksum.crc32c_sw(data, crc)
    buf = np.frombuffer(memoryview(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else np.ascontiguousarray(data, np.uint8)
    return int(lib.ceph_crc32c(ctypes.c_uint32(crc), _as_u8p(buf), buf.size))


def xxhash64(data, seed: int = 0) -> int:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    buf = np.frombuffer(memoryview(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else np.ascontiguousarray(data, np.uint8)
    return int(lib.ceph_xxhash64(ctypes.c_uint64(seed), _as_u8p(buf), buf.size))


def xxhash32(data, seed: int = 0) -> int:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    buf = np.frombuffer(memoryview(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else np.ascontiguousarray(data, np.uint8)
    return int(lib.ceph_xxhash32(ctypes.c_uint32(seed), _as_u8p(buf), buf.size))


def _lz_roundtrip(name: str, data, op: str) -> bytes:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    buf = np.frombuffer(memoryview(bytes(data)), dtype=np.uint8)
    if op == "c":
        cap = int(getattr(lib, f"{name}_max_compressed")(buf.size))
    elif name == "snappy":
        cap = int(lib.snappy_uncompressed_length(_as_u8p(buf),
                                                 buf.size)) \
            if buf.size else 0
        # the header varint is untrusted blob bytes: clamp against
        # snappy's max expansion (<64x) BEFORE allocating, or a
        # corrupt prefix commits terabytes
        if cap < 0 or cap > max(buf.size * 64, 1 << 16):
            raise ValueError("corrupt snappy header")
    else:
        # LZ4 block carries no length header (the reference's
        # compressor framing records raw length; ours stores it in
        # the blob extent) — callers prepend it, see compressor layer
        raise ValueError("lz4 decompress needs an explicit capacity")
    out = np.empty(max(cap, 1), dtype=np.uint8)
    fn = getattr(lib, f"{name}_{'compress' if op == 'c' else 'decompress'}")
    got = int(fn(_as_u8p(buf), buf.size, _as_u8p(out), out.size))
    if got < 0:
        raise ValueError(f"{name} codec error")
    return out[:got].tobytes()


def snappy_compress(data) -> bytes:
    return _lz_roundtrip("snappy", data, "c")


def snappy_decompress(data) -> bytes:
    return _lz_roundtrip("snappy", data, "d")


def lz4_compress(data) -> bytes:
    return _lz_roundtrip("lz4", data, "c")


def lz4_decompress(data, raw_len: int) -> bytes:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    buf = np.frombuffer(memoryview(bytes(data)), dtype=np.uint8)
    out = np.empty(max(raw_len, 1), dtype=np.uint8)
    got = int(lib.lz4_decompress(_as_u8p(buf), buf.size, _as_u8p(out),
                                 raw_len))
    if got != raw_len:
        raise ValueError("lz4 codec error")
    return out[:got].tobytes()
