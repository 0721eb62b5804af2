"""Sharded EC compute: the multi-chip encode/placement/read pipeline.

The reference distributes EC work as: primary OSD encodes a stripe, fans
sub-writes out to k+m shard OSDs over the cluster messenger
(ECBackend.cc:1986-2048), and degraded reads gather k surviving shards and
decode (ECBackend.cc:2301). On a TPU pod the same dataflow maps to a 2D
mesh (parallel/mesh.py):

- encode is position-wise over chunk bytes, so the byte axis shards cleanly
  over ``shard`` and stripe batches over ``stripe`` — zero-communication
  compute (the good kind);
- chunk *placement* to their home shard position is a ring step along
  ``shard`` (the ICI stand-in for the messenger fan-out);
- degraded read reconstruction gathers surviving shard bytes along
  ``shard`` and decodes locally;
- stripe-batch integrity stats (the hinfo crc role, ECUtil.h:101-162)
  reduce over the whole mesh.

Since ISSUE 12 every step is built on the layout/compile seam
(parallel/mesh_compile.py): the per-stage PartitionSpecs live in ONE
``SpecLayout`` table, and each step carries two spellings — a
global-view body (``jax.jit`` + ``in_shardings``/``out_shardings``;
XLA's SPMD partitioner inserts the collectives), the default, and the
per-shard ``shard_map`` body with explicit ``ppermute``/``psum``/
``all_gather`` that ``mesh_compile_mode=shard_map`` selects. The
global bodies are AXIS-PRESERVING on purpose: folding the sharded
stripe axis into the byte axis (the local spelling's trick) would
make the partitioner reshard the whole batch — measured ~10x
overhead — so the batched ``dot_general`` contracts only the
replicated symbol axis and every sharded dim stays put.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ceph_tpu.ops import bitmatrix
from ceph_tpu.parallel import mesh_compile
from ceph_tpu.parallel.mesh_compile import LAYOUT


def _instrumented(step, sig: str):
    """Wrap a jitted mesh step with device telemetry: per-call
    dispatch count plus compile accounting keyed by ``sig`` (a mesh
    step recompiling under a steady batch shape is the same bug-class
    signal as any other device entry point)."""
    from ceph_tpu.utils.device_telemetry import telemetry

    def run(*args):
        tel = telemetry()
        tel.note_mesh_dispatch()
        return tel.timed_call(sig, step, *args)

    run.__wrapped__ = step
    run.compile_path = getattr(step, "compile_path", "?")
    return run


def _mat_sig(kind: str, mesh: Mesh, mat: np.ndarray) -> str:
    import zlib
    shape = "x".join(str(s) for s in mat.shape)
    return (f"sharded_codec.{kind}[{shape}]"
            f"#{zlib.crc32(np.ascontiguousarray(mat).tobytes()):08x}"
            f"@mesh{dict(mesh.shape)}")


def _bitsliced_encode_local(bmat: jax.Array, data: jax.Array) -> jax.Array:
    """[8m,8k] x [k, N] -> [m, N] local bit-sliced GF matmul (ops/gf_jax.py)."""
    k, n = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    dbits = ((data[:, None, :] >> shifts[None, :, None]) & 1).astype(jnp.int8)
    dbits = dbits.reshape(8 * k, n)
    acc = jax.lax.dot_general(bmat, dbits, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    pbits = (acc & 1).astype(jnp.uint8)
    planes = pbits.reshape(bmat.shape[0] // 8, 8, n)
    return (planes * (jnp.uint8(1) << shifts)[None, :, None]).sum(
        axis=1, dtype=jnp.uint32).astype(jnp.uint8)


def _bitsliced_matmul_batched(bmat: jax.Array, x: jax.Array) -> jax.Array:
    """[8w,8p] x [S, p, C] -> [S, w, C] bit-sliced GF matmul, batched
    over stripes WITHOUT merging axes — the global-view spelling. The
    contraction runs over the replicated symbol axis only, so a
    (stripe, -, shard)-sharded input partitions with zero
    communication under the SPMD partitioner."""
    s, p, c = x.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    xbits = ((x[:, :, None, :] >> shifts[None, None, :, None]) & 1
             ).astype(jnp.int8)
    xbits = xbits.reshape(s, 8 * p, c)
    acc = jax.lax.dot_general(bmat, xbits, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    rbits = (acc & 1).astype(jnp.uint8)          # [8w, S, C]
    planes = rbits.reshape(bmat.shape[0] // 8, 8, s, c)
    out = (planes * (jnp.uint8(1) << shifts)[None, :, None, None]).sum(
        axis=1, dtype=jnp.uint32).astype(jnp.uint8)
    return out.transpose(1, 0, 2)                # [S, w, C]


def _finish_step(compiled, path: str, mesh: Mesh, bmat: np.ndarray,
                 sig: str):
    """Bind the replicated bit-matrix and wrap with telemetry. The
    matrix rides as an ARGUMENT (layout-table spec'd), uploaded once
    here — per-signature compile accounting stays intact through the
    ``_cache_size`` forward."""
    bmat_dev = jax.device_put(
        jnp.asarray(bmat), NamedSharding(mesh, LAYOUT.coding_matrix()))

    def step(data):
        return compiled(bmat_dev, data)

    if hasattr(compiled, "_cache_size"):
        step._cache_size = compiled._cache_size
    step.compile_path = path
    return _instrumented(step, f"{sig}/{path}")


def make_encode_step(mesh: Mesh, coding_matrix: np.ndarray,
                     place: bool = True):
    """Build the jitted distributed EC write step.

    Input  : data [S, k, C] uint8, sharded (stripe, -, shard).
    Output : chunks [S, k+m, C] uint8 and a mesh-reduced integrity
             checksum per chunk position. With ``place`` (default),
             parity is shipped one shard-ring position away (the
             messenger fan-out analog) — the host-visible parity bytes
             are then ring-rolled along C by device blocks;
             ``place=False`` keeps parity home (the batcher flush
             path, where the TCP messenger owns placement and the
             bytes must be exact)."""
    bmat = bitmatrix.expand_bitmatrix(coding_matrix).astype(np.int8)
    m, k = coding_matrix.shape
    n_shard = mesh.shape["shard"]

    def encode_global(bmat, data):       # [S, k, C] global view
        parity = _bitsliced_matmul_batched(bmat, data)
        if place:
            s, mm, c = parity.shape
            c_l = c // n_shard
            # placement: device block b's parity lands at block b+1 —
            # the SPMD partitioner lowers the block roll to the same
            # ring collective-permute the shard spelling writes by
            # hand (ECBackend.cc:2023-2039 fan-out analog)
            parity = jnp.roll(parity.reshape(s, mm, n_shard, c_l),
                              1, axis=2).reshape(s, mm, c)
        chunks = jnp.concatenate([data, parity], axis=1)
        csum = jnp.sum(chunks.astype(jnp.uint32), axis=(0, 2))
        return chunks, csum

    def encode_shard(bmat, data):        # local block [S_l, k, C_l]
        s_l, k_, c_l = data.shape
        # encode: fold stripes into the byte axis (position-wise math)
        flat = data.transpose(1, 0, 2).reshape(k_, s_l * c_l)
        parity = _bitsliced_encode_local(bmat, flat)
        parity = parity.reshape(m, s_l, c_l).transpose(1, 0, 2)
        if place:
            perm = [(i, (i + 1) % n_shard) for i in range(n_shard)]
            parity = jax.lax.ppermute(parity, "shard", perm)
        chunks = jnp.concatenate([data, parity], axis=1)
        # integrity stats over the full mesh (hinfo crc role)
        csum = jnp.sum(chunks.astype(jnp.uint32), axis=(0, 2))
        csum = jax.lax.psum(csum, ("stripe", "shard"))
        return chunks, csum

    compiled, path = mesh_compile.compile_step(
        mesh, global_fn=encode_global, shard_fn=encode_shard,
        in_specs=(LAYOUT.coding_matrix(), LAYOUT.stage_batch()),
        out_specs=(LAYOUT.chunks_out(), LAYOUT.csum_out()))
    return _finish_step(compiled, path, mesh, bmat,
                        _mat_sig("encode", mesh, coding_matrix))


def make_matrix_step(mesh: Mesh, flat_matrix: np.ndarray,
                     kind: str = "matrix", gather: bool = True):
    """Generic distributed GF matrix step: [S, rows_in, C] sharded
    (stripe, -, shard) -> (local [S, rows_out, C], gathered full
    rows). This is the collective shape shared by degraded reads AND
    the Clay linearized repair (models/clay.py _repair_matrix): helper
    sub-chunk fragments gather along ``shard`` and one flat GF matmul
    reconstructs the lost chunk's sub-chunks. ``kind`` keys the
    telemetry signature (degraded reads group separately).

    ``gather=False`` drops the second (device-side all-gathered)
    output: the engine's flush_decode_mesh twin reassembles on the
    HOST from the sharded rows, so paying the device all-gather for
    an output nobody reads would be pure ICI waste."""
    bmat = bitmatrix.expand_bitmatrix(flat_matrix).astype(np.int8)
    w = flat_matrix.shape[0]

    def matrix_global(bmat, x):
        rec = _bitsliced_matmul_batched(bmat, x)
        # second output replicates the byte axis (gathered_out spec):
        # the partitioner inserts the all-gather the shard spelling
        # writes explicitly
        return (rec, rec) if gather else rec

    def matrix_shard(bmat, x):           # [S_l, rows_in, C_l]
        s_l, p, c_l = x.shape
        flat = x.transpose(1, 0, 2).reshape(p, s_l * c_l)
        rec = _bitsliced_encode_local(bmat, flat)
        rec = rec.reshape(w, s_l, c_l).transpose(1, 0, 2)
        if not gather:
            return rec
        full = jax.lax.all_gather(rec, "shard", axis=2, tiled=True)
        return rec, full

    out_specs = (LAYOUT.chunks_out(), LAYOUT.gathered_out()) \
        if gather else LAYOUT.chunks_out()
    compiled, path = mesh_compile.compile_step(
        mesh, global_fn=matrix_global, shard_fn=matrix_shard,
        in_specs=(LAYOUT.coding_matrix(), LAYOUT.stage_batch()),
        out_specs=out_specs)
    return _finish_step(compiled, path, mesh, bmat,
                        _mat_sig(kind, mesh, flat_matrix))


def make_degraded_read_step(mesh: Mesh, generator: np.ndarray,
                            present_rows: list[int],
                            want_rows: list[int],
                            gather: bool = True):
    """Build the jitted distributed reconstruct step (degraded read).

    Surviving chunk bytes [S, p, C] sharded (stripe, -, shard) are decoded
    into the wanted chunks. The decode matrix is built host-side from the
    erasure signature exactly as the reference inverts the k x k submatrix
    (ErasureCodeIsa.cc:150-310); the byte work is the same MXU matmul. The
    second output reassembles full chunk bytes at every shard position
    (the read-reply gather of ECBackend.cc:1123).
    """
    from ceph_tpu.ops import gf256
    dmat = gf256.decode_matrix(generator, present_rows, want_rows)
    return make_matrix_step(mesh, dmat, kind="degraded_read",
                            gather=gather)


def make_verify_step(mesh: Mesh, mat: np.ndarray, k: int):
    """Mesh twin of the deep-scrub fused verify program
    (osd/scrub_engine.verify_fn): a [N, k+m, L] object batch spreads
    over EVERY chip (both mesh axes flattened — each chip re-encodes
    and crcs its objects entirely locally, zero communication), and
    only the [N, m] mismatch bitmap + [N, k+m] crc linear parts come
    home. N must divide by the mesh's device count (callers pad)."""
    mat = np.asarray(mat, dtype=np.uint8)
    bmat = bitmatrix.expand_bitmatrix(mat).astype(np.int8)
    m = mat.shape[0]

    def verify_body(bmat, batch):        # shape-agnostic: global AND
        from ceph_tpu.ops import crc32c_device as cd  # per-shard view
        nobj, n_, l = batch.shape
        par = _bitsliced_matmul_batched(bmat, batch[:, :k, :])
        mism = jnp.any(par != batch[:, k:, :], axis=2)   # [N, m]
        lin = cd.crc_linear_device(batch.reshape(nobj * n_, l))
        return mism, lin.reshape(nobj, n_)

    compiled, path = mesh_compile.compile_step(
        mesh, global_fn=verify_body, shard_fn=verify_body,
        in_specs=(LAYOUT.coding_matrix(), LAYOUT.object_batch()),
        out_specs=(LAYOUT.verdict_out(), LAYOUT.verdict_out()))
    return _finish_step(compiled, path, mesh, bmat,
                        _mat_sig(f"scrub_verify_k{k}", mesh, mat))


def shard_stripe_batch(mesh: Mesh, data: np.ndarray) -> jax.Array:
    """Place a host [S, k, C] batch onto the mesh with the layout
    table's stage-batch spec."""
    sharding = NamedSharding(mesh, LAYOUT.stage_batch())
    return jax.device_put(data, sharding)


def shard_object_batch(mesh: Mesh, batch: np.ndarray) -> jax.Array:
    """Place a host [N, n, L] per-object shard batch onto the mesh
    with the layout table's object-batch spec (deep-scrub verify)."""
    sharding = NamedSharding(mesh, LAYOUT.object_batch())
    return jax.device_put(batch, sharding)
