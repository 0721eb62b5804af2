"""The plain reference: what an EC pool's stored bytes have to be.

Independent of the program under test: this file imports numpy and
nothing of ``ceph_tpu``. It states the semantics the configurations
promise and computes them the straightforward way:

- GF(2^8) with the primitive polynomial x^8+x^4+x^3+x^2+1 (0x11d),
  generator 2 (gf-complete's w=8 default);
- the coding matrix of the pool's ``technique=reed_sol_van`` profile AS
  THE CONFIGURATION FILES STATE IT: the (k+m) x k Vandermonde matrix
  V[i][j] = i^j, column-reduced until its top k x k block is the
  identity; the bottom m rows are the coding matrix. (Upstream jerasure
  goes one step further and rescales so that the first coding row is
  all ones; the program does not, see PERF.md Open questions.)
- the layout of an object: padded with zeros to a multiple of
  ``k * stripe_unit``, cut into stripes of k chunks of ``stripe_unit``
  bytes; shard i holds chunk i of every stripe, in order; parity shard
  k+j is the GF matrix-vector product of row j with the data shards,
  byte position by byte position;
- crc32c (Castagnoli, reflected, polynomial 0x82F63B78) of a whole
  shard, continued from the seed 0xFFFFFFFF the store's ``hinfo``
  uses; ``crc32c(b"123456789") == 0xE3069283``.
"""

from __future__ import annotations

import numpy as np

GF_POLY = 0x11D
HINFO_SEED = 0xFFFFFFFF
CRC32C_POLY = 0x82F63B78


def _gf_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _gf_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[_LOG[a] + _LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(_EXP[255 - _LOG[a]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(_EXP[(_LOG[a] * n) % 255])


def _mul_table() -> np.ndarray:
    tbl = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            tbl[a, b] = _EXP[_LOG[a] + _LOG[b]]
    return tbl


MUL = _mul_table()


def coding_matrix(k: int, m: int) -> list[list[int]]:
    """The m x k coding matrix of ``reed_sol_van`` as stated above."""
    n = k + m
    if n > 256:
        raise ValueError(f"k+m={n} does not fit GF(2^8)")
    v = [[gf_pow(i, j) for j in range(k)] for i in range(n)]
    for i in range(k):
        if v[i][i] == 0:
            swap = next(j for j in range(i + 1, k) if v[i][j])
            for row in v:
                row[i], row[swap] = row[swap], row[i]
        inv = gf_inv(v[i][i])
        if inv != 1:
            for row in v:
                row[i] = gf_mul(row[i], inv)
        for j in range(k):
            f = v[i][j]
            if j != i and f:
                for row in v:
                    row[j] ^= gf_mul(f, row[i])
    for i in range(k):
        if v[i] != [int(a == i) for a in range(k)]:
            raise AssertionError("top block did not reduce to identity")
    return v[k:]


def encode(data: bytes, k: int, m: int, stripe_unit: int,
           matrix=None) -> list[np.ndarray]:
    """The k+m shards of one object."""
    mat = coding_matrix(k, m) if matrix is None else matrix
    width = k * stripe_unit
    buf = np.frombuffer(data, dtype=np.uint8)
    pad = -len(buf) % width
    if pad or not len(buf):
        buf = np.concatenate(
            [buf, np.zeros(pad or width, dtype=np.uint8)])
    stripes = len(buf) // width
    shards = [np.ascontiguousarray(s).reshape(-1) for s in
              buf.reshape(stripes, k, stripe_unit).transpose(1, 0, 2)]
    for row in mat:
        acc = np.zeros(stripes * stripe_unit, dtype=np.uint8)
        for coef, shard in zip(row, shards[:k]):
            if coef:
                acc ^= MUL[coef][shard]
        shards.append(acc)
    return shards


_MATRICES: dict[tuple[int, int], list[list[int]]] = {}


def shards(data: bytes, pool: dict) -> list[np.ndarray]:
    """The k+m shards an object's bytes are stored as in ``pool`` (a
    configuration's whole ``pool`` object): what every reference
    module states, here for ``technique=reed_sol_van``."""
    k, m = pool["k"], pool["m"]
    if (k, m) not in _MATRICES:
        _MATRICES[k, m] = coding_matrix(k, m)
    return encode(data, k, m, pool["stripe_unit"],
                  matrix=_MATRICES[k, m])


# -- crc32c -------------------------------------------------------------

def _crc_table() -> np.ndarray:
    tbl = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ (CRC32C_POLY if c & 1 else 0)
        tbl[i] = c
    return tbl


_CRC = _crc_table()


def crc32c_bytewise(data: bytes, crc: int = 0) -> int:
    """The definition, one byte at a time (small inputs, tests)."""
    reg = ~crc & 0xFFFFFFFF
    for b in bytes(data):
        reg = int(_CRC[(reg ^ b) & 0xFF]) ^ (reg >> 8)
    return ~reg & 0xFFFFFFFF


def _zero_op(nbytes: int) -> np.ndarray:
    """The linear map 'feed ``nbytes`` zero bytes' on the crc register,
    as the 32 images of the register's bits."""
    op = np.array([1 << b for b in range(32)], dtype=np.uint32)
    step = np.array(
        [int(_CRC[(1 << b) & 0xFF]) ^ ((1 << b) >> 8)
         for b in range(32)], dtype=np.uint32)
    while nbytes:
        if nbytes & 1:
            op = _apply(step, op)
        step = _apply(step, step)
        nbytes >>= 1
    return op


def _apply(op: np.ndarray, regs: np.ndarray) -> np.ndarray:
    """``op`` (32 column images) applied to every register in
    ``regs``."""
    regs = np.asarray(regs, dtype=np.uint32)
    out = np.zeros_like(regs)
    for b in range(32):
        bit = (regs >> np.uint32(b)) & np.uint32(1)
        out ^= bit * op[b]
    return out


#: most lanes a buffer is cut into; each lane is hashed bytewise, all
#: lanes at once, and the lane registers are folded pairwise
_MAX_LANES = 4096


def crc32c(data, crc: int = 0) -> int:
    """crc32c of ``data`` continued from ``crc``; equal to
    :func:`crc32c_bytewise`, computed over many lanes at once (crc is
    linear over GF(2): the register after A||B from 0 is the register
    after A advanced by len(B) zero bytes, xor the register after
    B)."""
    buf = np.frombuffer(memoryview(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else data.reshape(-1)
    total = len(buf)
    init = ~crc & 0xFFFFFFFF
    if total == 0:
        return crc & 0xFFFFFFFF
    lanes = 1
    while lanes < _MAX_LANES and lanes * 128 <= total:
        lanes <<= 1
    n = -(-total // lanes)
    # zeros in front of the data leave a zero register untouched
    padded = np.zeros(lanes * n, dtype=np.uint8)
    padded[lanes * n - total:] = buf
    cols = np.ascontiguousarray(padded.reshape(lanes, n).T)
    regs = np.zeros(lanes, dtype=np.uint32)
    for j in range(n):
        regs = _CRC[(regs ^ cols[j]) & np.uint32(0xFF)] ^ (regs >> np.uint32(8))
    op = _zero_op(n)
    while len(regs) > 1:
        regs = _apply(op, regs[0::2]) ^ regs[1::2]
        op = _apply(op, op)
    reg = int(regs[0])
    if init:
        reg ^= int(_apply(_zero_op(total),
                          np.array([init], dtype=np.uint32))[0])
    return ~reg & 0xFFFFFFFF


def shard_crcs(shards: list[np.ndarray]) -> list[int]:
    """The crc each shard's ``hinfo`` has to hold."""
    return [crc32c(s, HINFO_SEED) for s in shards]
