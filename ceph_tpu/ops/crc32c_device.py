"""crc32c on the TPU as two bit-sliced GF(2) matmuls.

Role: the device half of src/common/Checksummer.h (crc32c backends
src/common/crc32c_intel_fast_asm.s etc.) — BlueStore-style blob/shard
checksums computed from the SAME HBM buffers the EC encode just
produced (SURVEY.md §0 item (c); BlueStore verify seam
src/os/bluestore/BlueStore.cc:8061).

Why this works: the crc32c state update is affine over GF(2) in
(state, data), so with

    L(M) := crc32c(M, 0) XOR crc32c(0^len, 0)        (the linear part)

we have for any seed s:

    crc32c(M, s) = L(M) XOR crc32c(0^len, s)

and L is (a) linear in the bits of M and (b) invariant under FRONT
zero-padding (zero bytes contribute nothing to a linear form). That
turns a batch of crcs into dense linear algebra:

  1. view each buffer as rows of C bytes; a row's L-contribution is
     ``bits[C*8] @ B[C*8, 32]`` where B holds each (byte-position,
     bit)'s basis crc — an MXU matmul over all rows of all buffers;
  2. rows combine through per-row byte-shift matrices:
     ``rowbits[R*32] @ P[R*32, 32]`` — a second tiny matmul.

Both matmuls are int8->int32 (exact), so the result is bit-equal to
the host oracle (utils/checksum.py), gated by tests/test_crc_device.py
across lengths and seeds. The seed correction crc32c(0^len, s) is an
O(32^2 log len) host computation via squared affine maps (the
classic crc32_combine technique).
"""

from __future__ import annotations

import functools

import numpy as np

from ceph_tpu.utils import checksum

#: bytes per row of the stage-1 matmul (contraction = 8*C = 4096,
#: a full MXU pass at int8)
ROW_BYTES = 512


# -- host-side GF(2)/affine machinery ---------------------------------

def _one_zero_affine() -> tuple[np.ndarray, int]:
    """The affine map of processing ONE zero byte: s -> A·s ^ c."""
    c0 = checksum.crc32c(b"\x00", 0)
    cols = np.zeros(32, dtype=np.uint64)
    for i in range(32):
        cols[i] = checksum.crc32c(b"\x00", 1 << i) ^ c0
    return cols, c0


def _apply(cols: np.ndarray, s: int) -> int:
    out = 0
    v = s
    i = 0
    while v:
        if v & 1:
            out ^= int(cols[i])
        v >>= 1
        i += 1
    return out


def _compose(a2: np.ndarray, c2: int, a1: np.ndarray, c1: int):
    """(A2,c2) after (A1,c1): s -> A2(A1 s ^ c1) ^ c2."""
    cols = np.array([_apply(a2, int(x)) for x in a1], dtype=np.uint64)
    return cols, _apply(a2, c1) ^ c2


@functools.lru_cache(maxsize=64)
def _zero_affine_pow(n: int) -> tuple[tuple, int]:
    """Affine map of n zero bytes, by repeated squaring."""
    a, c = _one_zero_affine()
    # identity
    ra = np.array([1 << i for i in range(32)], dtype=np.uint64)
    rc = 0
    while n:
        if n & 1:
            ra, rc = _compose(a, c, ra, rc)
        a, c = _compose(a, c, a, c)
        n >>= 1
    return tuple(int(x) for x in ra), rc


def zeros_crc(n: int, seed: int) -> int:
    """crc32c(b"\\x00"*n, seed) in O(32^2 log n) — the seed-correction
    term of the affine identity (and the crc32_combine shift)."""
    ra, rc = _zero_affine_pow(n)
    return _apply(np.array(ra, dtype=np.uint64), seed) ^ rc


@functools.lru_cache(maxsize=8)
def _B_matrix(c_bytes: int) -> np.ndarray:
    """[C*8, 32] int8: row (c*8 + b) = bits of L(byte(1<<b) at column
    c of a C-byte row) — i.e. shifted by (C-1-c) bytes."""
    a, _c0 = _one_zero_affine()
    out = np.zeros((c_bytes * 8, 32), dtype=np.int8)
    for bit in range(8):
        v = checksum.crc32c(bytes([1 << bit]), 0) ^ \
            checksum.crc32c(b"\x00", 0)          # L of the single byte
        for dist in range(c_bytes):
            col = c_bytes - 1 - dist
            out[col * 8 + bit] = [(v >> j) & 1 for j in range(32)]
            v = _apply(a, v)                      # one more zero byte
    return out


@functools.lru_cache(maxsize=32)
def _P_matrix(r_rows: int, c_bytes: int) -> np.ndarray:
    """[R*32, 32] int8: row (r*32 + i) = bits of (basis-bit i of row
    r's crc) shifted by (R-1-r)*C bytes."""
    ra, _rc = _zero_affine_pow(c_bytes)
    s_cols = np.array(ra, dtype=np.uint64)        # linear shift-by-C
    out = np.zeros((r_rows * 32, 32), dtype=np.int8)
    cur = np.array([1 << i for i in range(32)], dtype=np.uint64)  # I
    for r in range(r_rows - 1, -1, -1):
        for i in range(32):
            v = int(cur[i])
            out[r * 32 + i] = [(v >> j) & 1 for j in range(32)]
        if r:
            cur = np.array([_apply(s_cols, int(x)) for x in cur],
                           dtype=np.uint64)
    return out


# -- device kernels ---------------------------------------------------

def _get_jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


#: rows per fold group per grid step
_TR = 256
#: row groups folded block-diagonally per matmul: widens the output
#: from 32 to _G*32 = 128 lanes — without the fold the matmul leaves
#: three quarters of the MXU's output lanes idle (the same g-fold
#: trick gf_pallas uses on the contraction side)
_G = 4


@functools.lru_cache(maxsize=1)
def _pallas_rows_fn():
    """Fused stage-1 kernel: unpack -> MXU matmul -> mod-2, all in
    VMEM per tile (the plain-XLA path materializes the 8x bit
    expansion in HBM — measured 1 GB/s vs ~500 for the same-shaped GF
    kernel). Input [rows, C] uint8, B block-diag [G*C*8, G*32] ->
    [rows, 32] int8 bits of each row's crc contribution; each grid
    step processes G row groups through ONE full-width matmul."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = ROW_BYTES

    def kernel(b_ref, x_ref, o_ref):
        x = x_ref[:].astype(jnp.int32)             # [G*tr, c]
        # per group: bit planes concatenated along LANES (mosaic
        # supports the concat where it rejects a minor-dim reshape; B
        # is permuted to the matching (bit*c + col) row order
        # host-side); groups stack block-diagonally along lanes
        groups = []
        for g in range(_G):
            grp = x[g * _TR:(g + 1) * _TR]
            planes = [((grp >> b) & 1) for b in range(8)]
            groups.append(jnp.concatenate(planes, axis=1))  # [tr, 8c]
        bits = jnp.concatenate(groups, axis=1)     # [tr, G*8c]
        acc = jax.lax.dot_general(
            bits.astype(jnp.bfloat16),
            b_ref[:].astype(jnp.bfloat16),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # exact: sums<=4096
        bo = (acc.astype(jnp.int32) & 1).astype(jnp.int8)
        for g in range(_G):
            o_ref[g * _TR:(g + 1) * _TR, :] = \
                bo[:, g * 32:(g + 1) * 32]

    block = _G * _TR

    @functools.partial(jax.jit, static_argnames=("rows",))
    def run(x, b_mat, rows: int):
        grid = (rows // block,)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((_G * c * 8, _G * 32), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((block, c), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((block, 32), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((rows, 32), jnp.int8),
            name="crc32c_rows",
        )(b_mat, x)

    return run


@functools.lru_cache(maxsize=8)
def _B_matrix_planar(c_bytes: int) -> np.ndarray:
    """B rows reordered to the pallas kernel's plane-major bit layout
    (row (bit*C + col) = _B_matrix row (col*8 + bit)), stacked
    block-diagonally _G times so each matmul fills all 128 output
    lanes with _G independent row groups."""
    b = _B_matrix(c_bytes)
    planar = np.empty_like(b)
    for bit in range(8):
        for col in range(c_bytes):
            planar[bit * c_bytes + col] = b[col * 8 + bit]
    r, w = planar.shape
    out = np.zeros((_G * r, _G * w), dtype=planar.dtype)
    for g in range(_G):
        out[g * r:(g + 1) * r, g * w:(g + 1) * w] = planar
    return out


def _pallas_available() -> bool:
    """The Pallas row kernel serves on a TPU. No fallback is decided
    here: a failure to ask is raised, because the answer is frozen
    into the jitted program below and the plain-XLA branch (8x bit
    expansion in HBM) would then ride every write unseen."""
    import jax
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=1)
def _jit_linear_batch():
    jax, jnp = _get_jnp()

    use_pallas = _pallas_available()

    @functools.partial(jax.jit, static_argnames=("r", "c"))
    def run(x, b_mat, p_mat, r: int, c: int):
        n = x.shape[0]
        if use_pallas:
            rows = n * r
            rows_p = _round_up(rows, _G * _TR)
            flat = x.reshape(rows, c)
            if rows_p != rows:
                # zero rows contribute nothing (crc linearity)
                flat = jnp.pad(flat, ((0, rows_p - rows), (0, 0)))
            b_planar = jnp.asarray(_B_matrix_planar(c))
            rowb = _pallas_rows_fn()(flat, b_planar.astype(jnp.int8),
                                     rows_p)[:rows]
        else:
            shifts = jnp.arange(8, dtype=jnp.uint8)
            bits = ((x[:, :, None] >> shifts) & 1).astype(jnp.int8)
            bits = bits.reshape(n * r, c * 8)
            rowb = (jax.lax.dot_general(
                bits, b_mat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32) & 1)     # [n*r, 32]
        rowb = rowb.reshape(n, r * 32).astype(jnp.int8)
        outb = jax.lax.dot_general(
            rowb, p_mat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32) & 1           # [n, 32]
        w = jnp.left_shift(jnp.uint32(1),
                           jnp.arange(32, dtype=jnp.uint32))
        return jnp.sum(outb.astype(jnp.uint32) * w, axis=1,
                       dtype=jnp.uint32)

    return run


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def crc_linear_device(x, length: int | None = None):
    """Device-resident linear crc parts of a [n, L] uint8 batch.

    Returns a device [n] uint32 array of L-values (combine with
    ``zeros_crc(L, seed)`` for a full crc32c). Front-pads to a
    multiple of ROW_BYTES — free, by linearity. Accepts a jax array
    (stays on device — the 'same HBM buffers' contract) or numpy.
    """
    jax, jnp = _get_jnp()
    x = jnp.asarray(x, dtype=jnp.uint8)
    n, ln = x.shape
    if length is not None:
        assert length == ln
    c = ROW_BYTES
    padded = _round_up(max(ln, 1), c)
    if padded != ln:
        x = jnp.pad(x, ((0, 0), (padded - ln, 0)))
    r = padded // c
    b_mat = jnp.asarray(_B_matrix(c))
    p_mat = jnp.asarray(_P_matrix(r, c))
    return _jit_linear_batch()(x, b_mat, p_mat, r, c)


def crc32c_from_linear(lin: int, length: int, seed: int = 0) -> int:
    """Recover a full crc32c from a device-computed LINEAR part (the
    affine identity): ``crc32c(M, seed) = L(M) ^ crc32c(0^len,
    seed)``. ``length`` is the TRUE buffer length — front zero-padding
    applied on device (shape bucketing) does not change L, so callers
    pass the unpadded length here. O(32^2 log len) host work."""
    return int(np.uint32(lin)) ^ zeros_crc(length, seed)


def crc32c_device(x, seed: int = 0) -> np.ndarray:
    """Batched crc32c of every row of ``x`` [n, L] with ``seed`` —
    bit-equal to utils.checksum.crc32c(row, seed)."""
    x = np.asarray(x) if not hasattr(x, "shape") else x
    n, ln = x.shape
    lin = np.asarray(crc_linear_device(x))
    corr = np.uint32(zeros_crc(ln, seed))
    return lin ^ corr
