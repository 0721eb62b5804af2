"""The plain reference starts out equal to today's host codec and
crc32c, so that only the program can drift from it; and it imports
nothing of the program."""

import ast
import os

import numpy as np
import pytest

from bench_tiny import BENCH_DIR

import reference


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "reference.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "numpy"}, names


def test_crc32c_check_value():
    assert reference.crc32c(b"123456789") == 0xE3069283
    assert reference.crc32c_bytewise(b"123456789") == 0xE3069283
    assert reference.crc32c(b"") == 0
    assert reference.crc32c(b"", 0x1234) == 0x1234


@pytest.mark.parametrize("length", [1, 7, 127, 128, 129, 4096, 70001,
                                    262144])
@pytest.mark.parametrize("seed", [0, reference.HINFO_SEED, 0xDEADBEEF])
def test_crc32c_equals_the_programs(length, seed):
    from ceph_tpu.utils import checksum
    data = np.random.default_rng(length).bytes(length)
    assert reference.crc32c(data, seed) == checksum.crc32c(data, seed)
    if length <= 4096:
        assert reference.crc32c_bytewise(data, seed) == \
            reference.crc32c(data, seed)


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2)])
def test_coding_matrix_equals_the_programs(k, m):
    from ceph_tpu.ops import gf256
    assert np.array_equal(
        np.array(reference.coding_matrix(k, m), dtype=np.uint8),
        gf256.rs_vandermonde_matrix(k, m))


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2)])
@pytest.mark.parametrize("nbytes", [1, 4096, 100000, 1 << 20])
def test_encode_equals_the_numpy_codec(k, m, nbytes):
    from ceph_tpu.models import registry
    from ceph_tpu.osd import ec_util
    unit = 4096
    codec = registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": str(k), "m": str(m),
                     "backend": "numpy"})
    sinfo = ec_util.StripeInfo(stripe_width=k * unit, chunk_size=unit)
    data = np.random.default_rng([k, m, nbytes]).bytes(nbytes)
    padded = data + b"\x00" * (-len(data) % (k * unit))
    want = ec_util.encode(sinfo, codec,
                          np.frombuffer(padded, dtype=np.uint8))
    got = reference.encode(data, k, m, unit)
    assert len(got) == k + m
    for pos in range(k + m):
        assert np.array_equal(got[pos], want[pos]), pos
    from ceph_tpu.utils import checksum
    assert reference.shard_crcs(got) == [
        checksum.crc32c(want[pos].tobytes(), ec_util.HINFO_SEED)
        for pos in range(k + m)]


def test_gf_field_axioms():
    for a in (1, 2, 29, 142, 255):
        assert reference.gf_mul(a, reference.gf_inv(a)) == 1
        assert reference.gf_pow(a, 255) == 1
    assert reference.gf_mul(2, 128) == 0x1D     # x^8 = x^4+x^3+x^2+1
    assert reference.MUL[3, 7] == reference.gf_mul(3, 7)
