"""Stripe math + batched encode/decode — the ECUtil role, TPU-batched.

Reference: src/osd/ECUtil.{h,cc}. ``stripe_info_t`` (ECUtil.h:27-80) maps
logical object offsets to stripes and chunk offsets; ``ECUtil::encode``
loops ``ec_impl->encode`` once per stripe_width window (ECUtil.cc:120-159).

The TPU translation (SURVEY.md §5 "stripe batch = leading vmap dim"): the
per-stripe loop disappears. For matrix codecs the position-wise math lets S
stripes fold into one [k, S*chunk_size] kernel call — one launch for a
whole append batch instead of S launches. A sub-chunked codec (Clay:
``device_flush = "layered"``) is position-wise ALONG a sub-chunk, so S
stripes re-laid plane-major are one chunk with S times longer
sub-chunks: one codec call on the host, one layered program a flush on
the device (``layered_program``, ``layered_decode_program``). The
generic fallback loops (lrc).

``HashInfo`` is the cumulative per-shard crc xattr (ECUtil.h:101-162,
append logic ECUtil.cc:161-177, stored under the hinfo key :235): every
shard append folds the new chunk bytes into a running crc32c so scrub can
verify a shard without reading its peers.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ceph_tpu.models.interface import ErasureCodeError
from ceph_tpu.utils import checksum
from ceph_tpu.utils import profiler as _prof
from ceph_tpu.utils.dout import Dout

log = Dout("osd")

#: initial per-shard crc seed (the reference seeds with -1, ECUtil.h:117)
HINFO_SEED = 0xFFFFFFFF


def _phase(span: str, n_ops: int, nbytes: int, fn, *args):
    """``fn(*args)`` as one flush phase of the calling thread, through
    the profiler's seam: ``flush_launch`` is the call that hands a
    batch to the codec (the jit call on the device routes: dispatch
    plus the host-to-device enqueue; the whole matvec on the host
    route, the whole synchronous encode on the plain one), and
    ``flush_download`` the wait for a device program and the copy of
    its results to the host. The engine's ``flush_build`` and
    ``flush_dispatch`` marks are open around them."""
    mark = _prof.push_stage(
        "engine_stage_wait" if span == "flush_launch"
        else "device_finalize", span=span, ops=n_ops, bytes=nbytes)
    try:
        return fn(*args)
    finally:
        _prof.pop_stage(mark)


@dataclass(frozen=True)
class StripeInfo:
    """stripe_width/chunk offset algebra (stripe_info_t, ECUtil.h:27-80)."""

    stripe_width: int   # k * chunk_size bytes of logical data per stripe
    chunk_size: int     # bytes per chunk per stripe

    def __post_init__(self):
        if self.stripe_width % self.chunk_size:
            raise ValueError(
                f"stripe_width {self.stripe_width} not a multiple of "
                f"chunk_size {self.chunk_size}")

    @property
    def k(self) -> int:
        return self.stripe_width // self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        return -(-offset // self.stripe_width) * self.stripe_width

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return -(-offset // self.stripe_width) * self.chunk_size

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return (offset // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return (offset // self.chunk_size) * self.stripe_width

    def offset_len_to_stripe_bounds(self, offset: int,
                                    length: int) -> tuple[int, int]:
        """Expand [offset, offset+length) to stripe-aligned bounds
        (ECUtil.h:72-79)."""
        start = self.logical_to_prev_stripe_offset(offset)
        end = self.logical_to_next_stripe_offset(offset + length)
        return start, end - start


def encode(sinfo: StripeInfo, codec, data: bytes | np.ndarray,
           want: list[int] | None = None) -> dict[int, np.ndarray]:
    """Encode a stripe-aligned logical extent into per-shard buffers.

    data length must be a multiple of stripe_width; the result maps shard
    id -> concatenated chunk bytes across all S stripes (what each shard
    OSD stores contiguously). Matrix codecs encode all S stripes in ONE
    kernel call; others loop (ECUtil.cc:136-148 semantics).
    """
    buf = np.frombuffer(bytes(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else data.astype(np.uint8, copy=False).ravel()
    sw, cs = sinfo.stripe_width, sinfo.chunk_size
    if len(buf) % sw:
        raise ErasureCodeError(
            f"encode: length {len(buf)} not a multiple of stripe_width {sw}")
    s = len(buf) // sw
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    assert sw == k * cs, (sw, k, cs)
    want = list(range(n)) if want is None else list(want)
    # [S, k, cs] -> per-shard contiguous [S*cs]
    stripes = buf.reshape(s, k, cs)
    data_shards = stripes.transpose(1, 0, 2).reshape(k, s * cs)
    out: dict[int, np.ndarray] = {}
    kind = flush_kind(codec)
    if kind == "matrix":
        # position-wise codec: stripes fold into the byte axis
        parity = codec._matvec(codec.coding_matrix, data_shards)
        for i in want:
            out[i] = data_shards[i] if i < k else parity[i - k]
    elif kind == "layered":
        # sub-chunked codec: the math is position-wise along a
        # sub-chunk, so S stripes re-laid plane-major are ONE chunk
        # whose sub-chunks are S times as long: one codec call
        ssc = codec.get_sub_chunk_count()
        planes = _turn(data_shards, s, ssc)
        parity = codec.encode_chunks(
            [i for i in want if i >= k],
            {j: planes[j] for j in range(k)})
        for i in want:
            out[i] = data_shards[i] if i < k else \
                _turn(parity[i][None, :], ssc, s)[0]
    else:
        per_stripe = [codec.encode_chunks(
            want, {j: stripes[si, j] for j in range(k)}) for si in range(s)]
        for i in want:
            if i < k:
                out[i] = data_shards[i]
            else:
                out[i] = np.concatenate([per_stripe[si][i] for si in range(s)])
    return out


def xor_decodable(codec, shards: dict[int, np.ndarray],
                  missing: list[int]) -> bool:
    """True when reconstructing ``missing`` from ``shards`` reduces
    to bitwise XOR — the decode matrix for this erasure signature has
    only 0/1 coefficients (GF multiply by 1 is identity, GF add is
    XOR). Single-parity RS and XOR-structured codes hit this on every
    single-erasure signature; for those a host XOR beats any device
    staging round-trip, so callers use this to skip the engine.
    Mirrors decode_chunks' survivor selection (sorted, first k)."""
    from ceph_tpu.models.matrix_codec import MatrixErasureCode
    if not missing or not isinstance(codec, MatrixErasureCode):
        return False
    have = sorted(shards)
    k = codec.get_data_chunk_count()
    if len(have) < k:
        return False
    try:
        dmat = codec._decode_matrix(tuple(have[:k]), tuple(missing))
    except Exception:
        return False
    return bool(((dmat == 0) | (dmat == 1)).all())


def decode(sinfo: StripeInfo, codec, shards: dict[int, np.ndarray],
           want: list[int]) -> dict[int, np.ndarray]:
    """Reconstruct wanted shards from surviving per-shard buffers
    (ECUtil.cc:47-118). Shard buffers hold S concatenated chunks."""
    some = next(iter(shards.values()))
    cs = sinfo.chunk_size
    if len(some) % cs:
        raise ErasureCodeError(
            f"decode: shard length {len(some)} not a multiple of {cs}")
    s = len(some) // cs
    missing = [i for i in want if i not in shards]
    if not missing:
        return {i: np.asarray(shards[i], dtype=np.uint8) for i in want}
    kind = flush_kind(codec)
    if kind == "matrix":
        # one kernel call across all stripes
        return codec.decode_chunks(
            want, {i: np.asarray(v, dtype=np.uint8)
                   for i, v in shards.items()})
    if kind == "layered":
        if device_layered(codec):
            # one layered program over the whole batch
            return decode_layered(sinfo, codec, shards, want)
        # host twin: re-laid plane-major the S stripes are one chunk
        # with S times longer sub-chunks (see encode): one codec call
        ssc = codec.get_sub_chunk_count()
        ids = sorted(shards)
        planes = _turn(np.stack(
            [np.asarray(shards[i], dtype=np.uint8) for i in ids]),
            s, ssc)
        got = codec.decode_chunks(
            want, {i: planes[j] for j, i in enumerate(ids)})
        return {i: np.asarray(shards[i], dtype=np.uint8)
                if i in shards else
                _turn(np.asarray(got[i])[None, :], ssc, s)[0]
                for i in want}
    out = {i: np.zeros(s * cs, dtype=np.uint8) for i in want}
    for si in range(s):
        got = codec.decode_chunks(
            want, {i: np.asarray(v[si * cs:(si + 1) * cs], dtype=np.uint8)
                   for i, v in shards.items()})
        for i in want:
            out[i][si * cs:(si + 1) * cs] = got[i]
    return out


class HashInfo:
    """Cumulative per-shard crc32c (ECUtil.h:101-162).

    Updated on every append; serialized as a shard xattr so
    handle_sub_read can verify a shard against it (ECBackend.cc:1032-1051).
    """

    def __init__(self, num_chunks: int) -> None:
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [HINFO_SEED] * num_chunks

    def append(self, old_size: int, shard_chunks: dict[int, np.ndarray]):
        """Fold an append at chunk-offset ``old_size`` into the crcs
        (ECUtil.cc:161-177: appends must be contiguous)."""
        if old_size != self.total_chunk_size:
            raise ValueError(
                f"hinfo append at {old_size} != current size "
                f"{self.total_chunk_size} (appends must be contiguous)")
        sizes = {len(v) for v in shard_chunks.values()}
        if len(sizes) != 1:
            raise ValueError("hinfo append: unequal shard chunk sizes")
        for shard, data in shard_chunks.items():
            self.cumulative_shard_hashes[shard] = checksum.crc32c(
                data, self.cumulative_shard_hashes[shard])
        self.total_chunk_size += sizes.pop()

    def append_linear(self, old_size: int, linear: dict[int, int],
                      chunk_len: int) -> None:
        """Fold an append whose per-shard LINEAR crc parts were
        computed on device (ops/crc32c_device.py): the running crc is
        recovered host-side as L(chunk) ^ crc32c(0^len, prev) — the
        affine identity — in O(32^2 log len), no byte re-hash."""
        if old_size != self.total_chunk_size:
            raise ValueError(
                f"hinfo append at {old_size} != current size "
                f"{self.total_chunk_size} (appends must be contiguous)")
        from ceph_tpu.ops.crc32c_device import zeros_crc
        for shard, lv in linear.items():
            self.cumulative_shard_hashes[shard] = int(lv) ^ zeros_crc(
                chunk_len, self.cumulative_shard_hashes[shard])
        self.total_chunk_size += chunk_len

    def get_chunk_hash(self, shard: int) -> int:
        return self.cumulative_shard_hashes[shard]

    def to_dict(self) -> dict:
        return {"total_chunk_size": self.total_chunk_size,
                "hashes": list(self.cumulative_shard_hashes)}

    @classmethod
    def from_dict(cls, d: dict) -> "HashInfo":
        hi = cls(len(d["hashes"]))
        hi.total_chunk_size = d["total_chunk_size"]
        hi.cumulative_shard_hashes = list(d["hashes"])
        return hi


class StripeBatcher:
    """Device-side stripe batch accumulator (SURVEY.md §7.5, the novel
    piece): coalesce many small sub-writes into one kernel launch.

    Appends are queued host-side; ``flush()`` encodes everything queued in
    a single batched call and returns per-op shard buffers in submission
    order (commit order is preserved — the pipeline-ordering invariant of
    ECBackend::check_ops, ECBackend.cc:2107). Size-triggered auto-flush;
    the OSD write pipeline calls flush() at commit points.
    """

    def __init__(self, sinfo: StripeInfo, codec,
                 flush_bytes: int = 8 << 20, mesh=None,
                 on_fallback=None) -> None:
        self.sinfo = sinfo
        self.codec = codec
        self.flush_bytes = flush_bytes
        #: jax.sharding.Mesh: when set (and the codec is a plain
        #: matrix codec), flushes run the DISTRIBUTED encode step over
        #: the mesh (sharded_codec.make_encode_step) — stripe batches
        #: shard over ('stripe' x 'shard'), parity computes with zero
        #: communication, integrity stats psum over ICI
        self.mesh = mesh
        #: on_fallback(path, exc): a mesh/fused flush failed and the
        #: batch re-ran on the plain path — callers count it (the
        #: engine's device_fused_fallbacks stat); a persistent
        #: regression must not silently degrade every flush while
        #: stats still claim device batches (r2 verdict weak #3)
        self.on_fallback = on_fallback
        self._pending: list[tuple[object, np.ndarray]] = []
        self._pending_bytes = 0
        #: zero-copy staging (ISSUE 9): when the appended buffers are
        #: adjacent views into ONE contiguous array (the engine's
        #: concat buffer of one program key, filled at stage time), the
        #: caller hands that array here and flush skips its own
        #: np.concatenate — the flush-time copy the old path paid
        self._preconcat: np.ndarray | None = None

    def append(self, op_id, data: bytes | np.ndarray) -> None:
        buf = np.frombuffer(bytes(data), dtype=np.uint8) \
            if not isinstance(data, np.ndarray) else data
        if len(buf) % self.sinfo.stripe_width:
            raise ErasureCodeError(
                f"append: {len(buf)} bytes not stripe-aligned")
        self._pending.append((op_id, buf))
        self._pending_bytes += len(buf)

    def set_preconcat(self, batch: np.ndarray) -> None:
        """Declare that every appended buffer is a view into ``batch``
        in append order (total length must match); flush then uses
        ``batch`` directly instead of concatenating."""
        self._preconcat = batch

    def should_flush(self) -> bool:
        return self._pending_bytes >= self.flush_bytes

    def flush(self, with_crcs: bool = False
              ) -> list[tuple[object, dict[int, np.ndarray],
                              dict[int, int] | None]]:
        """Encode all queued ops in one batch; returns
        [(op_id, shards, crcs-or-None)] in submission order.

        ``with_crcs`` computes each op's per-shard LINEAR crc parts on
        device from the same buffers as the encode (SURVEY.md §0 item
        (c) — the Checksummer/BlueStore-verify pass riding the encode's
        HBM residency); only available on the fused device path, None
        otherwise (callers fall back to host hashing).
        """
        return self.flush_async(with_crcs)()

    def flush_async(self, with_crcs: bool = False):
        """Launch the batch and return ``finalize() -> results``.

        On the fused device path the launch is ASYNC (jax dispatch):
        finalize blocks on the download. The engine exploits this to
        double-buffer — stage and launch batch N+1 while N's results
        stream back, which is what amortizes the host-device round
        trip. The mesh and plain paths compute synchronously here
        and finalize trivially. Device faults surface from
        finalize() — callers route them to their host fallback."""
        if not self._pending:
            return lambda: []
        ops, bufs = zip(*self._pending)
        preconcat = self._preconcat
        if preconcat is not None and \
                len(preconcat) != sum(len(b) for b in bufs):
            preconcat = None       # caller's contract broken: re-copy
        self._pending, self._pending_bytes = [], 0
        self._preconcat = None
        if self.mesh is not None and device_fusable(self.codec):
            try:
                # ASYNC since ISSUE 12: the mesh step launches here
                # (jax async dispatch) and the returned finalize
                # downloads — mesh flushes ride the engine's in-flight
                # window like fused single-chip flushes, so flushes on
                # DIFFERENT placement slots (disjoint devices) overlap
                return _flush_mesh(self.mesh, self.sinfo,
                                   self.codec, ops, bufs,
                                   batch=preconcat)
            except Exception as exc:
                self._note_fallback("mesh", exc)
                # single-device fallback below
        if device_layered(self.codec):
            try:
                return _flush_layered_async(
                    self.sinfo, self.codec, ops, bufs,
                    batch=preconcat, with_crcs=with_crcs)
            except Exception as exc:
                # the plain path below re-encodes the whole batch
                # with one call of the codec (counted)
                self._note_fallback("layered", exc)
        if with_crcs and device_fusable(self.codec):
            try:
                return _flush_device_fused_async(
                    self.sinfo, self.codec, ops, bufs,
                    batch=preconcat)
            except Exception as exc:
                # fused path failure must not lose the batch: the
                # plain path below re-encodes (host or device)
                self._note_fallback("fused_crc", exc)
        batch = preconcat if preconcat is not None \
            else np.concatenate(bufs)
        shards = _phase("flush_launch", len(ops), batch.nbytes,
                        encode, self.sinfo, self.codec, batch)
        results = []
        cs, sw = self.sinfo.chunk_size, self.sinfo.stripe_width
        off = 0  # in chunk units per shard
        for op_id, buf in zip(ops, bufs):
            nchunk = len(buf) // sw * cs
            results.append((op_id, {
                i: v[off:off + nchunk] for i, v in shards.items()},
                None))
            off += nchunk
        return lambda: results

    #: failure classes already logged (log once per class per process:
    #: a persistent fault would otherwise spam every flush)
    _logged_fallbacks: set = set()

    def _note_fallback(self, path: str, exc: Exception) -> None:
        cls = (path, type(exc).__name__)
        if cls not in StripeBatcher._logged_fallbacks:
            StripeBatcher._logged_fallbacks.add(cls)
            log(0, f"{path} flush path failed "
                f"({type(exc).__name__}: {exc}); falling back to the "
                "plain flush (logged once per failure class)")
        if self.on_fallback is not None:
            try:
                self.on_fallback(path, exc)
            except Exception:
                pass


#: pool-profile backends whose matvec runs on the accelerator
_DEVICE_MATVEC = {"jax", "pallas"}

#: upper bound on the fused path's padded crc working set (the bit
#: unpack amplifies 8x in device memory; a ragged op mix must fall
#: back to the plain flush instead of OOMing the runtime)
_FUSE_CRC_MAX_SEG_BYTES = 256 << 20


def flush_kind(codec) -> str | None:
    """The capability a codec STATES to the engine's seams
    (``device_flush``, models/base.py): ``"matrix"``: a batch is one
    GF matrix over the byte stream; ``"layered"``: a batch is one
    layered program over plane-major lanes (clay); None: stripe by
    stripe on the host codec."""
    return getattr(codec, "device_flush", None)


def device_fusable(codec) -> bool:
    """A plain matrix codec on a device backend: its flushes are one
    fused program (:func:`_flush_device_fused_async`), and a range
    overwrite's re-encode rides the engine's overwrite flush. Layered
    and chunk-mapped codecs (clay, lrc) encode an overwrite inline."""
    return (flush_kind(codec) == "matrix"
            and getattr(codec, "backend", "") in _DEVICE_MATVEC)


def device_layered(codec) -> bool:
    """A layered codec whose profile names a device backend: its
    flushes and reconstructs are one layered program each."""
    return (flush_kind(codec) == "layered"
            and getattr(codec, "backend", "") in _DEVICE_MATVEC)


def host_flushable(codec) -> bool:
    """Whether the engine's SMALL-flush host route can take this
    codec: plain matrix codecs encode with one host matvec over the
    coding matrix (layered/chunk-mapped codecs keep their own encode
    path)."""
    return (flush_kind(codec) == "matrix"
            and codec.coding_matrix is not None)


def _turn(streams: np.ndarray, outer: int, inner: int) -> np.ndarray:
    """``[c, outer*inner*sub]`` -> the same bytes with the two leading
    axes of every row swapped. ``_turn(shards, S, ssc)``: S stored
    chunks of ``ssc`` sub-chunks each become plane-major (sub-chunk z
    of every stripe side by side), which a layered codec reads as ONE
    chunk whose sub-chunks are S times as long; ``_turn(planes, ssc,
    S)`` is the way back."""
    c, n = streams.shape
    sub = n // (outer * inner)
    return np.ascontiguousarray(
        streams.reshape(c, outer, inner, sub).transpose(0, 2, 1, 3)
    ).reshape(c, n)


_host_matvec_backend: str | None = None


def _host_backend() -> str:
    global _host_matvec_backend
    if _host_matvec_backend is None:
        from ceph_tpu.ops import backend as backend_mod
        avail = backend_mod.available_backends()
        _host_matvec_backend = \
            "native" if "native" in avail else "numpy"
    return _host_matvec_backend


def flush_host_async(sinfo: StripeInfo, codec, ops, bufs,
                     batch=None):
    """Small-flush HOST route (bulk-ingest ISSUE 9): same
    ``finalize() -> [(op_id, shards, None)]`` contract as
    :func:`_flush_device_fused_async`, but the encode is one host
    matvec (native/numpy) run at finalize time — below the engine's
    ``host_flush_bytes`` threshold the FIXED device dispatch cost
    (jit call + transfer round trip, measured ~5 ms on the CPU quick
    run) dwarfs the ~0.4 ms host encode of a 64 KiB flush. crcs are
    None: the backend hashes on host, which is in the same noise
    floor at these sizes."""
    cs, sw = sinfo.chunk_size, sinfo.stripe_width
    k = codec.get_data_chunk_count()
    lens = [len(b) // sw * cs for b in bufs]
    if batch is None:
        batch = np.concatenate(bufs)
    mat = codec.coding_matrix
    backend = _host_backend()

    def finalize():
        from ceph_tpu.ops import backend as backend_mod
        s = len(batch) // sw
        data_shards = np.ascontiguousarray(
            batch.reshape(s, k, cs).transpose(1, 0, 2)
            .reshape(k, s * cs))
        parity = _phase("flush_launch", len(ops), batch.nbytes,
                        backend_mod.matvec, mat, data_shards, backend)
        results = []
        off = 0
        for op_id, ln in zip(ops, lens):
            shards = {i: data_shards[i, off:off + ln]
                      for i in range(k)}
            for j in range(parity.shape[0]):
                shards[k + j] = parity[j, off:off + ln]
            results.append((op_id, shards, None))
            off += ln
        return results

    return finalize


def device_decodable(codec) -> bool:
    """Whether the daemon's batched DECODE path can take this codec:
    plain matrix codecs reconstruct with one signature-keyed matmul
    (decode() above collapses to a single device launch), a layered
    codec (clay) with one layered program whose signature table is an
    operand (:func:`decode_layered`); mapped codecs (lrc) keep their
    host machinery."""
    return device_fusable(codec) or device_layered(codec)


def fuse_crc_policy(codec) -> bool:
    """Whether the engine should ask for device-fused crcs: on the
    real accelerator (pallas) yes; the plain-XLA jax backend — which
    mostly means CPU CI, where the crc bit-unpack's 8x memory
    amplification across many in-process OSDs thrashes the host —
    only when explicitly forced (CEPH_TPU_FUSE_CRC=1)."""
    import os
    if not device_decodable(codec):
        return False
    return codec.backend == "pallas" or \
        bool(os.environ.get("CEPH_TPU_FUSE_CRC"))


#: (backend, matrix bytes, Nb, lmax_b, nops_b) -> jitted fused fn —
#: all dimensions are pow2-BUCKETED so the compile cache stays small
#: no matter what op-size mixes the daemon sees (an unbucketed
#: signature recompiles per batch shape and stalls the op path)
_fused_cache: dict = {}


def _pow2_bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


#: id(mesh) -> {(matrix bytes): jitted encode step}; bounded — each
#: closure pins its mesh + compiled executables, so unbounded growth
#: across mesh reconfigurations would leak device programs
_mesh_step_cache: dict = {}
_MESH_STEP_CACHE_MAX = 8


def _mesh_step(mesh, key, build):
    """One slot of the bounded per-mesh step cache: each compiled
    step pins its mesh + executables, so growth across mesh
    reconfigurations (or placement submeshes) stays bounded."""
    if id(mesh) not in _mesh_step_cache and \
            len(_mesh_step_cache) >= _MESH_STEP_CACHE_MAX:
        _mesh_step_cache.clear()
    per_mesh = _mesh_step_cache.setdefault(id(mesh), {})
    step = per_mesh.get(key)
    if step is None:
        step = per_mesh[key] = build()
    return step


def _round_stripes(data: np.ndarray, n_stripe: int) -> np.ndarray:
    """pow2-bucket the stripe count (bounds compiles) and round to
    the stripe axis; zero stripes encode/decode to zero and slice
    off."""
    s = data.shape[0]
    s_pad = _pow2_bucket(max(s, n_stripe), n_stripe)
    if s_pad % n_stripe:
        s_pad = -(-s_pad // n_stripe) * n_stripe
    if s_pad != s:
        pad = np.zeros((s_pad - s,) + data.shape[1:], dtype=np.uint8)
        data = np.concatenate([data, pad])
    return data


def _flush_mesh(mesh, sinfo: StripeInfo, codec, ops, bufs,
                batch=None):
    """Flush the batch through the MULTI-CHIP encode step: stripes
    shard over the mesh's ('stripe' x 'shard') axes, parity computes
    locally on every chip (position-wise math — zero communication),
    and the integrity stat reduces over ICI. Parity bytes are
    bit-exact vs the host codec (place=False keeps them home; the TCP
    messenger owns shard placement in this architecture).

    Returns ``finalize() -> results`` (ISSUE 12): the step call here
    only LAUNCHES the sharded program (jax async dispatch); finalize
    downloads — the engine parks mesh flushes on its in-flight window
    so different placement slots' flushes overlap on their disjoint
    devices."""
    from ceph_tpu.parallel import sharded_codec
    cs, sw = sinfo.chunk_size, sinfo.stripe_width
    k = codec.get_data_chunk_count()
    n_chunks = codec.get_chunk_count()
    lens = [len(b) // sw * cs for b in bufs]
    if batch is None:
        batch = np.concatenate(bufs)
    s = len(batch) // sw
    data = _round_stripes(batch.reshape(s, k, cs),
                          mesh.shape["stripe"])
    step = _mesh_step(
        mesh, codec.coding_matrix.tobytes(),
        lambda: sharded_codec.make_encode_step(
            mesh, np.asarray(codec.coding_matrix, dtype=np.uint8),
            place=False))
    chunks_dev, _csum = _phase(
        "flush_launch", len(ops), batch.nbytes,
        lambda: step(sharded_codec.shard_stripe_batch(mesh, data)))

    def finalize():
        chunks = _phase("flush_download", len(ops), batch.nbytes,
                        np.asarray, chunks_dev)[:s]    # [s, k+m, cs]
        streams = {i: np.ascontiguousarray(
            chunks[:, i, :]).reshape(-1) for i in range(n_chunks)}
        results = []
        off = 0
        for op_id, ln in zip(ops, lens):
            results.append((op_id,
                            {i: streams[i][off:off + ln]
                             for i in range(n_chunks)}, None))
            off += ln
        return results

    return finalize


def flush_decode_mesh(mesh, sinfo: StripeInfo, codec,
                      shards: dict[int, np.ndarray],
                      want: list[int]) -> dict[int, np.ndarray]:
    """Mesh twin of :func:`decode` (ISSUE 12): the engine's
    signature-batched reconstruct as ONE sharded matmul — stripes
    over the ``stripe`` axis, chunk bytes over ``shard``, the decode
    matrix keyed by the erasure signature exactly like the single-chip
    route. Present rows return verbatim; bit-exactness vs the host
    corpus is gated in tier-1. Raises on shapes the mesh cannot take
    (callers fall back to the single-chip/host path)."""
    from ceph_tpu.ops import gf256
    from ceph_tpu.parallel import sharded_codec
    cs = sinfo.chunk_size
    present = sorted(shards)
    missing = [i for i in want if i not in shards]
    out = {i: np.asarray(shards[i], dtype=np.uint8)
           for i in want if i in shards}
    if not missing:
        return out
    n_shard = mesh.shape["shard"]
    if cs % n_shard:
        raise ErasureCodeError(
            f"chunk size {cs} does not shard over {n_shard} devices")
    k = codec.get_data_chunk_count()
    if len(present) < k:
        raise ErasureCodeError(
            f"{len(present)} survivors < k={k}")
    # any k survivors reconstruct the same bytes (MDS); take the
    # first k deterministically so the decode matrix signature is
    # stable per erasure signature
    present = present[:k]
    some = np.asarray(next(iter(shards.values())))
    s = len(some) // cs
    x = np.stack([np.asarray(shards[i], dtype=np.uint8).reshape(s, cs)
                  for i in present], axis=1)       # [s, k, cs]
    x = _round_stripes(x, mesh.shape["stripe"])
    mat = np.asarray(codec.coding_matrix, dtype=np.uint8)
    step = _mesh_step(
        mesh, ("dec", mat.tobytes(), tuple(present), tuple(missing)),
        lambda: sharded_codec.make_degraded_read_step(
            mesh, gf256.systematic_generator(mat),
            list(present), list(missing), gather=False))
    rec = step(sharded_codec.shard_stripe_batch(mesh, x))
    rec = np.asarray(rec)[:s]                      # [s, w, cs]
    for j, c in enumerate(missing):
        out[c] = np.ascontiguousarray(rec[:, j, :]).reshape(-1)
    return out


def fused_program(codec, n_b: int, lmax_b: int, nops_b: int,
                  with_crcs: bool = True):
    """The jitted encode+crc program of one bucketed batch signature:
    ``fn(data [k, n_b], offs [nops_b], seg_lens [nops_b]) -> (parity
    [m, n_b], crc linear parts [nops_b * (k+m)] | None)``. Without
    crcs (an overwrite flush: a range overwrite drops the shards'
    ``hinfo``) the program is the GF encode alone. Returns ``(fn,
    is_new)``; cached per (backend, matrix, buckets), so the flush
    path and anything that compiles the program ahead of time
    (tests/test_chip_compile.py) build the SAME function."""
    import jax
    import jax.numpy as jnp
    key = (codec.backend, codec.coding_matrix.tobytes(),
           n_b, lmax_b, nops_b, with_crcs)
    fn = _fused_cache.get(key)
    if fn is not None:
        return fn, False
    if len(_fused_cache) > 256:
        _fused_cache.clear()
    if codec.backend == "pallas":
        from ceph_tpu.ops import gf_pallas as dev
    else:
        from ceph_tpu.ops import gf_jax as dev
    mat = np.asarray(codec.coding_matrix, dtype=np.uint8)

    def fused(data, offs, seg_lens):
        # stable names for a device trace: the two halves of the
        # program appear under ``encode`` and ``crc_windows``
        with jax.named_scope("encode"):
            parity = dev.matvec_device(mat, data)
            if not with_crcs:
                return parity, None
            shards = jnp.concatenate(
                [data, parity.astype(jnp.uint8)], axis=0)
        return parity, _crc_windows(shards, offs, seg_lens, lmax_b)

    fn = _fused_cache[key] = jax.jit(fused)
    return fn, True


def _crc_windows(shards, offs, seg_lens, lmax_b: int):
    """Inside a flush program: every op's per-shard LINEAR crc part
    from the device-resident ``shards [n_chunks, n_b]`` in stored
    order. Per-op segment boundaries are dynamic inputs; a fixed-width
    window ENDING at each segment's end is cut, and the bytes before
    the segment (neighbour ops, padding) masked to zero (free under
    crc linearity). Returns ``[nops_b * n_chunks]``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from ceph_tpu.ops import crc32c_device as cd
    n_chunks = shards.shape[0]
    nops_b = offs.shape[0]

    def seg(off, ln):
        win = lax.dynamic_slice(
            padded, (0, off + ln), (n_chunks, lmax_b))
        mask = jnp.arange(lmax_b) >= (lmax_b - ln)
        return win * mask.astype(jnp.uint8)

    with jax.named_scope("crc_windows"):
        padded = jnp.pad(shards, ((0, 0), (lmax_b, 0)))
        segs = jax.vmap(seg)(offs, seg_lens)
        return cd.crc_linear_device(
            segs.reshape(nops_b * n_chunks, lmax_b))


def layered_program(codec, chunk_size: int, s_b: int, lmax_b: int,
                    nops_b: int, with_crcs: bool = True):
    """The jitted flush program of a layered (sub-chunked) codec for
    one bucketed batch signature: ``fn(batch [s_b * k * chunk_size]
    uint8, offs [nops_b], seg_lens [nops_b]) -> (parity [m, s_b *
    chunk_size], crc linear parts [nops_b * (k+m)] | None)``.

    ``batch`` is the staged payload AS THE STAGER HOLDS IT: ``s_b``
    stripes of k chunks of ``ssc`` sub-chunks. On the device it is
    turned to plane-major lanes ``[k, ssc, s_b * sub]``, runs the
    codec's layered encode (``codec.flush_encoder()``:
    uncouple, the plane-wise solves, recouple), is turned back to the
    m parity shards in stored order, and the linear crc parts of all
    k+m shards of every op come from the same device-resident bytes.
    Returns ``(fn, is_new)``; cached per (profile, buckets)."""
    import jax
    import jax.numpy as jnp
    k = codec.get_data_chunk_count()
    m = codec.get_chunk_count() - k
    ssc = codec.get_sub_chunk_count()
    sub = chunk_size // ssc
    key = ("layered", codec.flush_key(), chunk_size, s_b, lmax_b,
           nops_b, with_crcs)
    fn = _fused_cache.get(key)
    if fn is not None:
        return fn, False
    if len(_fused_cache) > 256:
        _fused_cache.clear()
    encode = codec.flush_encoder()
    n_b = s_b * chunk_size

    def layered(batch, offs, seg_lens):
        x = batch.reshape(s_b, k, ssc, sub)
        with jax.named_scope("relayout"):
            planes = x.transpose(1, 2, 0, 3).reshape(k, ssc, s_b * sub)
        with jax.named_scope("encode"):
            par = encode(planes).astype(jnp.uint8)
        with jax.named_scope("relayout"):
            parity = par.reshape(m, ssc, s_b, sub).transpose(
                0, 2, 1, 3).reshape(m, n_b)
        if not with_crcs:
            return parity, None
        with jax.named_scope("relayout"):
            data = x.transpose(1, 0, 2, 3).reshape(k, n_b)
            shards = jnp.concatenate([data, parity], axis=0)
        return parity, _crc_windows(shards, offs, seg_lens, lmax_b)

    fn = _fused_cache[key] = jax.jit(layered)
    return fn, True


def layered_decode_program(codec, chunk_size: int, n_b: int, e: int):
    """The jitted decode program of a layered codec for one shape
    bucket: ``fn(survivors [k, n_b] uint8 in stored order, table) ->
    [e, n_b]`` rebuilt shards in stored order. ``table`` (the erasure
    signature's, ``codec.flush_decode_table``) is an OPERAND,
    so one compiled program serves every signature. Returns ``(fn,
    is_new)``."""
    import jax
    k = codec.get_data_chunk_count()
    ssc = codec.get_sub_chunk_count()
    sub = chunk_size // ssc
    s_b = n_b // chunk_size
    key = ("layered_dec", codec.flush_key(), chunk_size, n_b, e)
    fn = _fused_cache.get(key)
    if fn is not None:
        return fn, False
    if len(_fused_cache) > 256:
        _fused_cache.clear()

    def layered_decode(survivors, table):
        with jax.named_scope("relayout"):
            planes = survivors.reshape(k, s_b, ssc, sub).transpose(
                0, 2, 1, 3).reshape(k * ssc, s_b * sub)
        with jax.named_scope("decode"):
            rec = codec.flush_decode(table, planes)
        with jax.named_scope("relayout"):
            return rec.reshape(e, ssc, s_b, sub).transpose(
                0, 2, 1, 3).reshape(e, n_b)

    fn = _fused_cache[key] = jax.jit(layered_decode)
    return fn, True


def decode_signature(codec, shards, want) -> tuple[tuple, tuple]:
    """(present, missing) of a layered reconstruct: the first k
    surviving chunks in order (any k rebuild the same bytes; taking
    the first k keeps the table stable per erasure signature) and the
    wanted chunks that are not there."""
    k = codec.get_data_chunk_count()
    return (tuple(sorted(shards)[:k]),
            tuple(i for i in want if i not in shards))


def signature_table(codec, present: tuple, missing: tuple):
    """``(table, built)``: the operand of :func:`decode_layered` for
    one erasure signature, from the process-wide cache or built now
    (the caller counts and times a build)."""
    return codec.flush_decode_table(present, missing)


def decode_layered(sinfo: StripeInfo, codec,
                   shards: dict[int, np.ndarray], want: list[int],
                   table=None) -> dict[int, np.ndarray]:
    """Batched reconstruct of a layered codec on the device: the
    survivors of every op of the batch, concatenated per shard, go
    through ONE program (:func:`layered_decode_program`), pow2-
    bucketed on the byte axis. ``table``: the signature's operand when
    the caller already holds it (the engine, which counts builds)."""
    cs = sinfo.chunk_size
    k = codec.get_data_chunk_count()
    present, missing = decode_signature(codec, shards, want)
    out = {i: np.asarray(shards[i], dtype=np.uint8)
           for i in want if i in shards}
    if not missing:
        return out
    if len(present) < k:
        raise ErasureCodeError(
            f"layered decode: {len(present)} survivors < k={k}",
            errno_=5)
    n = len(np.asarray(shards[present[0]]))
    if n % cs:
        raise ErasureCodeError(
            f"decode: shard length {n} not a multiple of {cs}")
    if table is None:
        table, _built = signature_table(codec, present, missing)
    n_b = _pow2_bucket(n, 1 << 14)
    fn, _new = layered_decode_program(codec, cs, n_b, len(missing))
    survivors = np.empty((k, n_b), dtype=np.uint8)
    survivors[:, n:] = 0
    for row, c in enumerate(present):
        survivors[row, :n] = np.asarray(shards[c], dtype=np.uint8)
    from ceph_tpu.utils.device_telemetry import telemetry
    rec = np.asarray(telemetry().timed_call(
        f"layered_dec[{codec.backend} k{k}e{len(missing)}]N{n_b}",
        fn, survivors, table))
    for row, c in enumerate(missing):
        out[c] = rec[row, :n]
    return out


def fused_buckets(n_bytes: int, max_len: int, n_ops: int
                  ) -> tuple[int, int, int]:
    """(n_b, lmax_b, nops_b): the pow2 buckets of a batch of
    ``n_ops`` ops holding ``n_bytes`` per shard, the longest op
    ``max_len`` per shard."""
    from ceph_tpu.ops import crc32c_device as cd
    return (_pow2_bucket(n_bytes, 1 << 14),
            _pow2_bucket(max_len, max(cd.ROW_BYTES, 1 << 12)),
            _pow2_bucket(n_ops, 1))


def _segments(lens: list[int], nops_b: int
              ) -> tuple[np.ndarray, np.ndarray]:
    """The per-op (offset, length) operands of a flush program, per
    shard in bytes, zero beyond the batch's ops."""
    offs_arr = np.zeros(nops_b, dtype=np.int32)
    offs_arr[:len(lens)] = np.cumsum([0] + lens[:-1])
    lens_arr = np.zeros(nops_b, dtype=np.int32)
    lens_arr[:len(lens)] = lens
    return offs_arr, lens_arr


def _per_op(ops, lens, data_shards, parity, lin) -> list:
    """``[(op_id, shards, crcs)]`` of a fused flush: every op's slice
    of the k data and m parity streams, and its row of the crc linear
    parts (``lin [nops_b, k+m]`` or None)."""
    k = data_shards.shape[0]
    results = []
    off = 0
    for idx, (op_id, ln) in enumerate(zip(ops, lens)):
        shards = {i: data_shards[i, off:off + ln] for i in range(k)}
        for j in range(parity.shape[0]):
            shards[k + j] = parity[j, off:off + ln]
        crcs = None if lin is None else \
            {i: int(v) for i, v in enumerate(lin[idx])}
        results.append((op_id, shards, crcs))
        off += ln
    return results


#: an overwrite flush's per-shard bucket: the engine flushes its
#: overwrite group before an op would take it past this many bytes a
#: shard (16 stripes of 4 KiB chunks), so every batch of overwrites up
#: to it runs ONE compiled program whatever the queue depth; only an
#: op larger than a bucket alone meets a larger one
OVERWRITE_BUCKET = 1 << 16


def _flush_device_fused_async(sinfo: StripeInfo, codec, ops, bufs,
                              batch=None, with_crcs: bool = True):
    """One device program per bucketed batch signature: upload the
    stripe batch once, encode parity, and take every op's per-shard
    crc linear part from the SAME device-resident shards (one download
    round trip for parity + 4 bytes/shard of crcs). Per-op segment
    boundaries are DYNAMIC inputs (offsets/lengths arrays), with
    front-zero padding — free under crc linearity — masking the
    neighbour bytes a fixed-width window drags in.

    Without crcs (the engine's overwrite flush, never host-routed,
    whatever its size) the program encodes alone, padded to at least
    :data:`OVERWRITE_BUCKET`; ``finalize.overwrite`` marks its ops for
    the engine's ``overwrite_ops``.

    Returns ``finalize() -> results``: the jit call here only QUEUES
    the program (jax async dispatch); finalize downloads — callers
    can launch the next batch before finalizing this one."""
    cs, sw = sinfo.chunk_size, sinfo.stripe_width
    k = codec.get_data_chunk_count()
    n_chunks = codec.get_chunk_count()
    lens = [len(b) // sw * cs for b in bufs]
    if batch is None:
        batch = np.concatenate(bufs)
    s = len(batch) // sw
    n_bytes = s * cs
    data_shards = np.ascontiguousarray(
        batch.reshape(s, k, cs).transpose(1, 0, 2).reshape(k, n_bytes))

    if with_crcs:
        n_b, lmax_b, nops_b = fused_buckets(n_bytes, max(lens),
                                            len(ops))
    else:
        # the segment operands are not read: one program a bucket
        n_b, lmax_b, nops_b = (_pow2_bucket(n_bytes, OVERWRITE_BUCKET),
                               1, 1)
    if nops_b * n_chunks * lmax_b > _FUSE_CRC_MAX_SEG_BYTES:
        raise ValueError("fused crc working set too large; "
                         "plain flush")
    fn, fn_is_new = fused_program(codec, n_b, lmax_b, nops_b,
                                  with_crcs)
    if n_b != n_bytes:
        data_dev = np.zeros((k, n_b), dtype=np.uint8)
        data_dev[:, :n_bytes] = data_shards
    else:
        data_dev = data_shards
    offs_arr, lens_arr = _segments(lens if with_crcs else [0], nops_b)
    from ceph_tpu.utils.device_telemetry import telemetry
    signature = (f"{'fused_crc' if with_crcs else 'overwrite'}"
                 f"[{codec.backend}{list(codec.coding_matrix.shape)}]"
                 f"N{n_b}L{lmax_b}ops{nops_b}")
    if fn_is_new:
        import os as _os
        if _os.environ.get("CEPH_TPU_COST_ANALYSIS"):
            # per-signature compiled cost analysis (FLOPs / bytes
            # accessed) into the device telemetry table; opt-in — the
            # AOT lower+compile does not share the jit call cache, so
            # it would double the cold-compile cost of the hot path
            from ceph_tpu.ops import cost_model
            cost_model.analyze(fn, data_dev, offs_arr, lens_arr,
                               signature=signature)
    parity_dev, lin_dev = _phase(
        "flush_launch", len(ops), batch.nbytes,
        telemetry().timed_call, signature, fn, data_dev, offs_arr,
        lens_arr)

    def finalize():
        parity, lin = _phase(
            "flush_download", len(ops), batch.nbytes,
            lambda: (np.asarray(parity_dev),
                     None if lin_dev is None else
                     np.asarray(lin_dev).reshape(nops_b, n_chunks)))
        return _per_op(ops, lens, data_shards, parity, lin)

    # expose the compiled program + staged host inputs for harnesses
    # (bench/engine_loop.py measures THIS exact program — reaching
    # into the cache with a hand-copied key would silently drift)
    finalize.fused_fn = fn
    finalize.staged = (data_dev, offs_arr, lens_arr)
    finalize.overwrite = not with_crcs
    return finalize


def _flush_layered_async(sinfo: StripeInfo, codec, ops, bufs,
                         batch=None, with_crcs: bool = True):
    """One :func:`layered_program` per bucketed batch signature for a
    sub-chunked codec: the staged batch is uploaded AS STAGED (no host
    re-layout before the launch), the device turns it plane-major,
    encodes, turns the parity back and takes every op's crc parts.
    The k data shards' stored form (the transposition the matrix route
    makes before its launch) is built at finalize, on the retire
    thread, while the device runs. Same ``finalize() -> results``
    contract as :func:`_flush_device_fused_async`."""
    cs, sw = sinfo.chunk_size, sinfo.stripe_width
    k = codec.get_data_chunk_count()
    n_chunks = codec.get_chunk_count()
    m = n_chunks - k
    lens = [len(b) // sw * cs for b in bufs]
    if batch is None:
        batch = np.concatenate(bufs)
    s = len(batch) // sw
    n_bytes = s * cs
    n_b, lmax_b, nops_b = fused_buckets(n_bytes, max(lens), len(ops))
    s_b = n_b // cs
    if nops_b * n_chunks * lmax_b > _FUSE_CRC_MAX_SEG_BYTES:
        with_crcs = False          # the backend hashes on the host
    fn, _new = layered_program(codec, cs, s_b, lmax_b, nops_b,
                               with_crcs)
    if s_b != s:
        staged = np.empty(s_b * sw, dtype=np.uint8)
        staged[:len(batch)] = batch
        staged[len(batch):] = 0
    else:
        staged = batch
    offs_arr, lens_arr = _segments(lens, nops_b)
    from ceph_tpu.utils.device_telemetry import telemetry
    signature = (f"layered[{codec.backend} k{k}m{m}"
                 f"{'crc' if with_crcs else ''}]"
                 f"S{s_b}L{lmax_b}ops{nops_b}")
    parity_dev, lin_dev = _phase(
        "flush_launch", len(ops), batch.nbytes,
        telemetry().timed_call, signature, fn, staged, offs_arr,
        lens_arr)

    def finalize():
        data_shards = np.ascontiguousarray(
            batch.reshape(s, k, cs).transpose(1, 0, 2)
        ).reshape(k, n_bytes)
        parity, lin = _phase(
            "flush_download", len(ops), batch.nbytes,
            lambda: (np.asarray(parity_dev),
                     None if lin_dev is None else
                     np.asarray(lin_dev).reshape(nops_b, n_chunks)))
        return _per_op(ops, lens, data_shards, parity, lin)

    #: the engine counts the ops of such a flush (layered_encode_ops)
    finalize.layered = True
    return finalize
