"""Native C++ library tests: GF kernels vs numpy oracle, checksum vectors.

Cross-backend bit-exactness is the corpus gate (SURVEY.md §4.2); checksum
functions are validated against published check values.
"""

import os

import numpy as np
import pytest

from ceph_tpu.ops import backend, gf256, native_loader
from ceph_tpu.utils import checksum

pytestmark = pytest.mark.skipif(
    not native_loader.available(), reason="native library unavailable")


def test_native_matvec_bit_exact():
    rng = np.random.default_rng(0)
    for k, m, n in [(2, 1, 64), (8, 3, 4096), (12, 4, 1000)]:
        mat = rng.integers(0, 256, size=(m, k), dtype=np.uint8)
        data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
        assert np.array_equal(native_loader.matvec(mat, data),
                              gf256.gf_matvec_chunks(mat, data))


def test_native_backend_registered():
    assert "native" in backend.available_backends()


def test_native_codec_roundtrip():
    from ceph_tpu.models import instance
    codec = instance().factory("isa", {"k": "8", "m": "3",
                                       "backend": "native"})
    data = bytes(range(256)) * 64
    enc = codec.encode(list(range(11)), data)
    cs = codec.get_chunk_size(len(data))
    avail = {i: enc[i] for i in range(11) if i not in (0, 9)}
    dec = codec.decode([0, 9], avail, cs)
    assert np.array_equal(dec[0], enc[0])
    assert np.array_equal(dec[9], enc[9])


def test_crc32c_check_value():
    # iSCSI CRC-32C published check value
    assert checksum.crc32c(b"123456789") == 0xE3069283
    assert checksum.crc32c_sw(b"123456789") == 0xE3069283


def test_crc32c_incremental():
    whole = checksum.crc32c(b"hello world")
    part = checksum.crc32c(b"world", checksum.crc32c(b"hello "))
    assert whole == part
    assert checksum.crc32c_sw(b"world", checksum.crc32c_sw(b"hello ")) == whole


def test_crc32c_native_matches_sw_random():
    rng = np.random.default_rng(1)
    for n in (1, 7, 8, 63, 4096):
        buf = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        assert checksum.crc32c(buf) == checksum.crc32c_sw(buf)


def test_xxhash64_vectors():
    # published XXH64 test vectors
    assert checksum.xxhash64(b"") == 0xEF46DB3751D8E999
    assert checksum.xxhash64(b"a") == 0xD24EC4F1A98C6E5B
    assert checksum.xxhash64(b"abc") == 0x44BC2CF5AD770999


def test_xxhash32_vectors():
    assert checksum.xxhash32(b"") == 0x02CC5D05
    assert checksum.xxhash32(b"a") == 0x550D7456


def test_checksummer_blockwise():
    data = np.arange(16384, dtype=np.uint32).view(np.uint8)
    cs = checksum.Checksummer("crc32c", 4096)
    sums = cs.calculate(data)
    assert len(sums) == len(data) // 4096
    assert cs.verify(data, sums) == -1
    corrupted = data.copy()
    corrupted[5000] ^= 0xFF
    assert cs.verify(corrupted, sums) == 4096


def test_region_xor():
    rng = np.random.default_rng(2)
    a = rng.integers(0, 256, size=1000, dtype=np.uint8)
    b = rng.integers(0, 256, size=1000, dtype=np.uint8)
    want = a ^ b
    dst = a.copy()
    native_loader.region_xor(dst, b)
    assert np.array_equal(dst, want)


def test_native_io_engine_roundtrip_and_crc(tmp_path):
    """io_engine.cc (KernelDevice/aio role): append returns the blob
    offset + one-pass crc32c identical to utils.checksum; pread
    verifies without a second hash pass; format interoperates with the
    pure-python engine."""
    from ceph_tpu.store.native_io import NativeDataFile
    from ceph_tpu.utils import checksum

    path = str(tmp_path / "data")
    eng = NativeDataFile.open(path)
    if eng is None:
        pytest.skip("native library unavailable")
    blobs = [os.urandom(n) for n in (1, 4096, 100_000)]
    offs = []
    for b in blobs:
        off, crc = eng.append(b)
        assert crc == checksum.crc32c(b)
        offs.append(off)
    assert offs == [0, 1, 4097]
    eng.sync()
    for off, b in zip(offs, blobs):
        data, crc = eng.read(off, len(b))
        assert data == b and crc == checksum.crc32c(b)
    # short read at EOF reports actual length
    data, _ = eng.read(offs[-1], 10 ** 6)
    assert data == blobs[-1]
    assert eng.size() == sum(len(b) for b in blobs)
    eng.close()
    # the python engine reads the same file
    from ceph_tpu.store.blockstore import _PyDataFile
    py = _PyDataFile(path)
    assert py.read(offs[1], len(blobs[1]))[0] == blobs[1]
    py.close()


def test_blockstore_native_python_engines_interoperate(tmp_path):
    """A store written under one data-plane engine opens and verifies
    under the other (same on-disk format, same crcs)."""
    from unittest import mock
    from ceph_tpu.store.object_store import Transaction, create_store

    path = str(tmp_path / "bs")
    s = create_store("blockstore", path)
    s.mount()
    t = Transaction().create_collection("c")
    payload = os.urandom(50_000)
    t.write("c", "o", 0, payload)
    s.queue_transaction(t)
    s.umount()
    # force the python engine on remount
    with mock.patch("ceph_tpu.store.native_io.NativeDataFile.open",
                    return_value=None):
        s2 = create_store("blockstore", path)
        s2.mount()
        assert s2.read("c", "o") == payload
        t2 = Transaction().write("c", "o2", 0, b"py-written")
        s2.queue_transaction(t2)
        s2.umount()
    # and back under the native engine
    s3 = create_store("blockstore", path)
    s3.mount()
    assert s3.read("c", "o") == payload
    assert s3.read("c", "o2") == b"py-written"
    s3.umount()


def test_failed_build_names_its_cause_once(monkeypatch, tmp_path):
    """A toolchain fault must not silently select the numpy twin: the
    loader logs the compiler's words, once, and stays unavailable."""
    import subprocess

    from ceph_tpu.ops import native_loader as nl

    def no_make(*a, **kw):
        raise subprocess.CalledProcessError(
            2, "make", stderr=b"g++: command not found")

    logged = []
    monkeypatch.setattr(nl, "_lib", None)
    monkeypatch.setattr(nl, "_failed", False)
    monkeypatch.setattr(nl, "_SO", tmp_path / "absent.so")
    monkeypatch.setattr(nl.subprocess, "run", no_make)
    monkeypatch.setattr(nl, "log", lambda lvl, msg: logged.append(msg))
    assert nl.get_lib() is None
    assert nl.get_lib() is None           # remembered, not retried
    assert len(logged) == 1
    assert "g++: command not found" in logged[0]
    assert "numpy twin" in logged[0]
