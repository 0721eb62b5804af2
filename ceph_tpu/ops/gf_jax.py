"""JAX/TPU GF(2^8) kernel path — bit-sliced binary matmul on the MXU.

The reference's hot kernel (ISA-L ``ec_encode_data`` /
``jerasure_matrix_encode``, called from
src/erasure-code/isa/ErasureCodeIsa.cc:118-130) does position-wise GF(2^8)
multiply-accumulate with SIMD nibble tables. A TPU has no byte-granular
shuffle ALU, so translating that would waste the chip (SURVEY.md §7 "hard
parts"). Instead, multiplication by a fixed field element is lowered to
GF(2) linear algebra (ops/bitmatrix.py):

    parity_bits[8m, N] = B[8m, 8k] @ data_bits[8k, N]   (mod 2)

which is an int8 matmul with int32 accumulation — exactly the MXU's native
operation — followed by ``& 1``. Unpack/pack of byte -> bit-planes are
cheap VPU shifts that XLA fuses around the matmul. The result is
byte-identical to the numpy reference (the cross-backend corpus gate,
tests/test_gf_jax.py).

The encode for a whole stripe *batch* is the same matmul with N = batch *
chunk_size — stripes are a free leading dimension folded into the lane axis
(SURVEY.md §5 "stripe batch = leading vmap dim").

Matrices are tiny and static per codec; they are expanded host-side once and
cached as device constants. Jit specializes per (8m, 8k, N) — callers should
bucket N (chunk sizes are already 32-aligned by the base class) to bound
recompiles.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ceph_tpu.ops import backend as backend_mod
from ceph_tpu.ops import bitmatrix

_SHIFTS = np.arange(8, dtype=np.uint8)


@jax.jit
def _bitsliced_matvec_device(bmat: "jax.Array", data: "jax.Array") -> "jax.Array":
    """bmat [R, 8k] int8 (0/1), data [k, N] uint8 -> [R//8, N] uint8."""
    k, n = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    # unpack: [k, N] -> [8k, N] bit planes (plane 8j+c = bit c of chunk j)
    dbits = ((data[:, None, :] >> shifts[None, :, None]) & 1).astype(jnp.int8)
    dbits = dbits.reshape(8 * k, n)
    # MXU: int8 x int8 -> int32
    acc = jax.lax.dot_general(
        bmat, dbits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    pbits = (acc & 1).astype(jnp.uint8)  # [R, N]
    r = bmat.shape[0]
    planes = pbits.reshape(r // 8, 8, n)
    weights = (jnp.uint8(1) << shifts)[None, :, None]
    return (planes * weights).sum(axis=1, dtype=jnp.uint32).astype(jnp.uint8)


class _MatrixCache:
    """Host GF matrix -> device-resident binary matrix, keyed by bytes.

    Trace-safe like gf_pallas._PermMatrixCache: under an outer jit
    (e.g. the fused encode+crc flush, osd/ec_util.py) the expansion is
    handed out as a fresh numpy constant — caching the jnp array there
    would store a tracer and poison every later call."""

    def __init__(self) -> None:
        self._host: dict[bytes, np.ndarray] = {}
        self._dev: dict[bytes, "jax.Array"] = {}

    def get(self, mat: np.ndarray) -> "jax.Array":
        key = mat.shape[0].to_bytes(2, "little") + mat.tobytes()
        bmat = self._host.get(key)
        if bmat is None:
            bmat = self._host[key] = \
                bitmatrix.expand_bitmatrix(mat).astype(np.int8)
        from ceph_tpu.ops.jax_util import tracing_active
        if tracing_active():
            return jnp.asarray(bmat)
        dev = self._dev.get(key)
        if dev is None:
            dev = self._dev[key] = jnp.asarray(bmat)
        return dev


_matrix_cache = _MatrixCache()

#: donating twin of the bit-sliced entry (same semantics as
#: gf_pallas._matvec_padded_donated): the input buffer is released to
#: XLA when matvec_device owns it, so steady-state encode reuses the
#: block instead of allocating per launch. Parity [m, N] is smaller
#: than data [k, N], so XLA cannot alias it INTO an output and warns
#: "not usable" — the win is the freed block covering the 8x
#: bit-plane intermediates, so the aliasing warning is suppressed.
import warnings as _warnings  # noqa: E402

_warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

_bitsliced_matvec_device_donated = jax.jit(
    _bitsliced_matvec_device.__wrapped__, donate_argnums=(1,))


def matvec_device(mat: np.ndarray, data) -> "jax.Array":
    """Device-in/device-out encode: data may be a jax array already in HBM.

    A HOST input (numpy/bytes) is uploaded by this call, which then
    owns the device buffer and donates it to the kernel; a live jax
    array stays the caller's — it is never donated."""
    bmat = _matrix_cache.get(np.asarray(mat, dtype=np.uint8))
    owned = not isinstance(data, jax.Array)
    data = jnp.asarray(data, dtype=jnp.uint8)
    from ceph_tpu.ops.jax_util import tracing_active
    if tracing_active():
        # under an outer jit the call inlines: compile accounting
        # belongs to the outer program, not this entry (and donation
        # is meaningless on a traced value)
        return _bitsliced_matvec_device(bmat, data)
    from ceph_tpu.utils.device_telemetry import telemetry
    fn = _bitsliced_matvec_device_donated if owned \
        else _bitsliced_matvec_device
    # the jit specializes on shapes only (bmat is a traced operand),
    # so the signature is exactly (m, k, N)
    return telemetry().timed_call(
        f"gf_jax[{bmat.shape[0] // 8}x{bmat.shape[1] // 8}]"
        f"N{data.shape[1]}" + ("d" if owned else ""),
        fn, bmat, data)


#: smallest jit-specialization bucket for the host entry (bytes of N)
_BUCKET_MIN = 4096


def _bucket(n: int) -> int:
    b = _BUCKET_MIN
    while b < n:
        b <<= 1
    return b


def matvec(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Host-in/host-out backend entry conforming to ops.backend contract.

    N is padded up to a power-of-2 bucket so jit specializes per
    (matrix, bucket) instead of per exact chunk length — a daemon
    serving arbitrary object sizes would otherwise recompile (and
    stall) on every new size. Zero-padding is exact for GF matmul:
    extra columns produce extra parity columns we slice off.
    """
    k, n = data.shape
    nb = _bucket(n)
    if nb != n:
        padded = np.zeros((k, nb), dtype=np.uint8)
        padded[:, :n] = data
        data = padded
    out = np.asarray(jax.device_get(matvec_device(mat, data)))
    return out[:, :n] if nb != n else out


backend_mod.register_backend("jax", matvec)
