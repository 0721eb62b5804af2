"""Pallas TPU kernel for GF(2^8) matrix-stripe multiply.

The plain-XLA bit-sliced path (ops/gf_jax.py) materializes the 8x bit-plane
expansion in HBM (XLA does not fuse elementwise producers into dot
operands), so encode pays ~30x HBM amplification. This kernel does
unpack -> MXU matmul -> pack entirely in VMEM per tile: HBM traffic drops
to data-in + parity-out, the same minimal movement the reference's SIMD
loop achieves in L1 (isa-l ``ec_encode_data``; call site
src/erasure-code/isa/ErasureCodeIsa.cc:118-130).

Math per grid step (g independent lane-groups of T bytes each):

    d        : [k, g*T] uint8
    bits     : [g*8k, T]  — per group q, 8 bit planes of its T lanes (VPU)
    acc      : Bg @ bits  with Bg = blockdiag_g([8m, 8k] binary)  (MXU, f32)
    parity   : Pg @ (acc & 1) with Pg = blockdiag_g(2^r pack)     (MXU, f32)
               -> [g*m, T] -> regrouped to [m, g*T] uint8

The g-fold block-diagonal stacking fills the MXU's 128-deep contraction
dimension (8k = 64 for k=8 would otherwise leave half the systolic array
idle): one pass processes g groups' bits, doubling (k=8) or quadrupling
(k=4) throughput over the naive [8m, 8k] matmul. Bit-packing runs as a
second tiny matmul with power-of-two weights instead of a scalar row loop.
Exactness: accumulator values are <= 8k <= 2048 < 2^24, exact in f32; pack
weights (2^r <= 128) and 0/1 bits are exact in bf16 with f32 accumulate,
so output is byte-identical to the numpy oracle (on the chip:
chip_smoke.py's oracle phase; that Mosaic accepts the kernel at the
served shapes: tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ceph_tpu.ops import bitmatrix

#: total lanes (chunk bytes across all g groups) per grid step; small
#: blocks double-buffer better through VMEM (measured optimum on v5e)
DEFAULT_TILE = 8192

#: MXU contraction depth to fill with g-fold stacking
_MXU_DEPTH = 128


def _fold(k: int) -> int:
    return max(1, _MXU_DEPTH // (8 * k))


def _permute_bitmatrix(mat: np.ndarray) -> np.ndarray:
    """[m,k] GF matrix -> [8m, 8k] binary matrix, columns regrouped by bit:
    out[:, c*k + j] = B[:, 8j + c]."""
    bmat = bitmatrix.expand_bitmatrix(mat)  # [8m, 8k]
    r, kc = bmat.shape
    k = kc // 8
    perm = [c * k + j for j in range(k) for c in range(8)]
    inv = np.empty(kc, dtype=np.int64)
    inv[perm] = np.arange(kc)
    # column 8j+c of bmat must land at c*k+j
    out = np.empty_like(bmat)
    for j in range(k):
        for c in range(8):
            out[:, c * k + j] = bmat[:, 8 * j + c]
    return out


def _gf_matvec_kernel(bmat_ref, data_ref, out_ref, *,
                      k: int, m_out: int, g: int, t: int):
    d = data_ref[:].astype(jnp.int32)              # [k, g*t]
    # per-group bit planes stacked on sublanes: row q*8k + c*k + j holds
    # bit c of data byte j of group q — matching blockdiag(Bperm) columns
    parts = []
    for q in range(g):
        grp = d[:, q * t:(q + 1) * t]
        for c in range(8):
            parts.append((grp >> c) & 1)
    bits = jnp.concatenate(parts, axis=0)          # [g*8k, t] int32
    acc = jax.lax.dot_general(
        bmat_ref[:].astype(jnp.bfloat16), bits.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    b = acc.astype(jnp.int32) & 1                  # [g*8m, t]
    # pack on the VPU: output byte (q,i) = sum_r b[8*(q*m+i)+r] << r —
    # one weighted sublane reduction per row (a second matmul here would
    # cost a full column-stream MXU pass)
    w = jnp.left_shift(
        1, jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0))
    rows = []
    for j in range(g * m_out):
        bb = b[8 * j:8 * j + 8]                    # [8, t]
        rows.append(jnp.sum(bb * w, axis=0, keepdims=True))
    pb = jnp.concatenate(rows, axis=0).astype(jnp.uint8)   # [g*m, t]
    for q in range(g):
        out_ref[:, q * t:(q + 1) * t] = pb[q * m_out:(q + 1) * m_out, :]


def _matvec_padded_impl(bmat: jax.Array, data: jax.Array,
                        k: int, m_out: int, g: int,
                        tile: int) -> jax.Array:
    n = data.shape[1]
    block = g * tile
    grid = (n // block,)
    return pl.pallas_call(
        functools.partial(_gf_matvec_kernel, k=k, m_out=m_out, g=g,
                          t=tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((g * 8 * m_out, g * 8 * k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m_out, block), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m_out, n), jnp.uint8),
        name="gf_matvec",
    )(bmat, data)


_matvec_padded = jax.jit(
    _matvec_padded_impl, static_argnames=("k", "m_out", "g", "tile"))

#: donating variant: the data buffer's HBM is handed to XLA for reuse,
#: so steady-state encode stops allocating a fresh input block per
#: launch. Used ONLY when matvec_device owns the buffer (host input,
#: or a fresh pad copy) — a caller-retained jax array must never be
#: invalidated under its owner. Parity [m, N] cannot alias the larger
#: [k, N] input as an output, so XLA's "not usable" aliasing warning
#: is suppressed (the win is the freed block covering the in-VMEM/HBM
#: intermediates, not output aliasing).
import warnings as _warnings  # noqa: E402

_warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

_matvec_padded_donated = jax.jit(
    _matvec_padded_impl, static_argnames=("k", "m_out", "g", "tile"),
    donate_argnums=(1,))


def _tracing() -> bool:
    from ceph_tpu.ops.jax_util import tracing_active
    return tracing_active()


class _PermMatrixCache:
    """Caches the block-diagonal bit matrix: host-side always, plus a
    device copy used only OUTSIDE tracing. Under an outer jit the
    numpy constant is embedded per-trace (handing out a cached device
    array there would leak a tracer); on the eager hot path the device
    copy avoids re-uploading the matrix every call."""

    def __init__(self) -> None:
        self._host: dict[bytes, np.ndarray] = {}
        self._dev: dict[bytes, jax.Array] = {}

    def get(self, mat: np.ndarray, g: int):
        key = (mat.shape[0].to_bytes(2, "little") +
               g.to_bytes(2, "little") + mat.tobytes())
        big = self._host.get(key)
        if big is None:
            perm = _permute_bitmatrix(mat).astype(np.int32)
            r, c = perm.shape
            big = np.zeros((g * r, g * c), dtype=np.int32)
            for q in range(g):
                big[q * r:(q + 1) * r, q * c:(q + 1) * c] = perm
            self._host[key] = big
        if _tracing():
            return jnp.asarray(big)
        dev = self._dev.get(key)
        if dev is None:
            dev = self._dev[key] = jnp.asarray(big)
        return dev


_perm_cache = _PermMatrixCache()


def matvec_device(mat: np.ndarray, data, tile: int = DEFAULT_TILE):
    """Device-in/device-out GF matvec via the Pallas kernel.

    data: [k, N] uint8 (jax or numpy). N is padded UP TO A POW2 GRID
    BUCKET with zeros (GF-linear => padding encodes to zeros and is
    sliced off). Bucketing bounds the compile count to O(log N) — the
    OSD's batch engine feeds arbitrary batch sizes, and an exact-fit
    grid would recompile per size.
    """
    mat = np.asarray(mat, dtype=np.uint8)
    m_out, k = mat.shape
    g = _fold(k)
    bmat = _perm_cache.get(mat, g)
    # we own (and may donate) the device buffer unless the CALLER
    # handed us a live jax array — jnp.asarray is a no-op then, and
    # donating it would invalidate the caller's copy
    owned = not isinstance(data, jax.Array)
    data = jnp.asarray(data, dtype=jnp.uint8)
    n = data.shape[1]
    t = min(tile // g, max(128, _round_up(-(-n // g), 128)))
    block = g * t
    nb = block
    while nb < n:
        nb <<= 1
    pad = nb - n
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
        owned = True               # the pad copy is ours to donate
    if _tracing():
        # under an outer jit the call inlines into the caller's trace:
        # timing/cache introspection would account the OUTER compile
        # (and donation is meaningless on a traced value)
        out = _matvec_padded(bmat, data, k, m_out, g, t)
    else:
        from ceph_tpu.utils.device_telemetry import telemetry
        fn = _matvec_padded_donated if owned else _matvec_padded
        out = telemetry().timed_call(
            f"gf_pallas[{m_out}x{k}]g{g}t{t}N{nb}"
            + ("d" if owned else ""),
            fn, bmat, data, k, m_out, g, t)
    return out[:, :n] if pad else out


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


#: beyond this the in-VMEM bit-plane expansion and the unrolled pack loop
#: stop fitting/compiling well; bigger matrices (e.g. Clay's linearized
#: [m*subchunks, k*subchunks] transforms) take the plain-XLA bit-sliced
#: path, which tiles arbitrary shapes through the MXU.
_MAX_M, _MAX_K = 32, 128


def matvec(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Host-in/host-out wrapper (ops.backend contract)."""
    m_out, k = mat.shape
    if m_out > _MAX_M or k > _MAX_K:
        from ceph_tpu.ops import gf_jax
        return gf_jax.matvec(mat, data)
    return np.asarray(jax.device_get(matvec_device(mat, data)))
