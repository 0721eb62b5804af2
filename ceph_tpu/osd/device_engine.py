"""DeviceEncodeEngine — the OSD's device-side stripe-batch pipeline.

This is the seam SURVEY.md §0 calls the north star: "ECBackend
accumulates sub-writes into device-side stripe batches". The reference
encodes synchronously inside try_reads_to_commit
(src/osd/ECBackend.cc:1986-2048, per-stripe loop ECUtil.cc:120-159);
a TPU cannot be fed per-4KiB-op without drowning in dispatch latency,
so the daemon's encode work is decoupled from the op path:

- ``stage_encode`` queues an op's padded payload; the engine folds
  every queued payload of one :func:`program_key` (across PGs and
  OSDs — batching across placement groups is where the batch size
  comes from: each PG has its own codec OBJECT, the key is what the
  flush program depends on) into ONE device kernel launch
  via :class:`ceph_tpu.osd.ec_util.StripeBatcher`, then dispatches
  each op's continuation (hinfo + shard-txn build + fan-out) back
  onto the OSD's sharded op queue. A range overwrite's spliced stripe
  window stages the same way (``overwrite=True``): overwrites of every
  PG meet in an overwrite flush of their own, one GF encode on the
  device with no crc pass and no host route, held to one bucket
  (``ec_util.OVERWRITE_BUCKET``) so that one program serves every
  batch, whatever the queue depth. A PG whose ops would sit in both
  kinds of group flushes first: groups flush one after the other,
  and its ops must ship in submission order.
- ``stage_barrier`` queues a NON-encode mutation (remove, truncate,
  setattrs, the partial write of a codec without the overwrite
  route). A barrier flushes everything staged before it and
  is dispatched after those continuations — on the same per-PG FIFO
  wq shard — so per-PG commit order is exactly submission order (the
  check_ops pipeline-ordering invariant, ECBackend.cc:2107-2112).
- ``stage_decode`` queues a reconstruct (degraded read, recovery
  decode — the objects_read_and_reconstruct / continue_recovery_op
  consumers, src/osd/ECBackend.cc:2301,537,955). Decodes group by
  ERASURE SIGNATURE (present-set, want-set — the ISA decode-table
  cache key, src/erasure-code/isa/ErasureCodeIsa.cc:226-303) and
  each group flushes as ONE device matmul; concurrent degraded
  reads and parallel recovery builds coalesce. Unlike encode
  continuations, decode continuations run INLINE on the engine
  thread: callers block synchronously (decode_sync) on op-worker
  threads, so dispatching through the per-PG wq would deadlock
  behind the very thread that is waiting.

Batching policy ("batch while busy"): the engine thread drains
whatever is queued and encodes it in one launch; while the device
works, new ops accumulate for the next launch. An idle engine
therefore adds no latency (a lone op flushes immediately) and a busy
one amortizes dispatch over the whole backlog. A size cap
(``flush_bytes``) bounds the device working set.

Launch pipeline (the round-9 tentpole): encode flushes exploit JAX
async dispatch — a flush LAUNCHES its device program and parks the
``finalize`` (download) on a bounded in-flight deque instead of
blocking. Up to ``window`` (default 3, ``CEPH_TPU_ENGINE_WINDOW``)
batches stay in flight: while batch N computes on device, batch N+1
stages/uploads and batch N-1's parity downloads. Retirement is
strictly in deque order, so continuations still dispatch in
submission order and every ordering point — ``stage_barrier``,
``run_sync``, ``stop``, a launch failure — drains the whole window
first; the pre-pipeline per-PG commit-order invariant is preserved
exactly. ``window=1`` degenerates to the old serial engine (launch,
then immediately download), which is what the overlap tests compare
against.

Multi-chip routing: when a process default mesh is configured
(parallel/mesh.py), flushes whose batch size reaches
``mesh_flush_bytes`` (default 1 MiB, ``CEPH_TPU_MESH_FLUSH_BYTES``)
run the sharded encode step across all mesh devices
(parallel/sharded_codec.make_encode_step); smaller flushes stay on
the single-chip path, where one kernel launch beats paying the
collective/placement overhead (the dense-vs-sharded crossover,
BASELINE.md "Pipelined engine").

Failure containment: a device encode error fails over to the op
continuations with the error; ECBackend re-encodes those ops on its
host codec (the daemon must never wedge on an accelerator fault).

Bulk ingest (ISSUE 9, ``CEPH_TPU_BULK_INGEST``, default on) — three
coupled changes that move work across every boundary in batches:

- **Zero-copy staging**: ``stage_encode`` writes each op's payload
  into a preallocated concat buffer per :func:`program_key` at
  staging time (:class:`_ConcatStager`), so the flush hands the
  device ONE contiguous view instead of re-concatenating N per-op
  arrays on the engine thread (``staging_copies_avoided_bytes``
  counts the bytes that skipped the flush-time copy). Buffer
  ownership passes to the flush results; a fresh buffer backs the
  next flush.
- **Batched continuation dispatch**: a retired flush dispatches ONE
  wrapper per distinct key (pgid) instead of one callable per op;
  the wrappers share a :class:`FlushGroup`, and when the LAST one
  has finished the engine's ship thread ships the flush's deferred
  cross-PG work — the per-peer MECSubWriteBatch fan-out and the
  merged local txn group ECBackend registers via
  :func:`current_group`. Groups ship in strict flush order, one
  after the other on that one thread (never on an op-wq worker);
  overwrite groups already ready behind one another ship as one
  (:func:`ship_groups`: one batch a peer for all of them). A barrier
  is dispatched behind the last group's ship, and its key's
  continuations retired while it waits are dispatched behind
  the barrier, so per-PG commit order is exactly the pre-batching
  order however far behind the ship thread is.
- **Shared engine service**: co-located OSDs attach to one
  process-wide engine (:func:`shared_engine_attach`) instead of one
  engine each — cross-OSD flushes aggregate into bigger batches and
  the >= 1 MiB mesh route fires more often. Each attach wraps keys
  with its token (:class:`AttachedKey`) so continuations dispatch on
  the owner OSD's op queue; the engine stops when the last OSD
  detaches.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time as _time
from typing import Callable

import numpy as np

from ceph_tpu.analysis.lock_witness import make_condition, make_lock
from ceph_tpu.osd import ec_util
from ceph_tpu.utils import faults as _faults
from ceph_tpu.utils import profiler as _prof
from ceph_tpu.utils import stage_clock as _stage_clock
from ceph_tpu.utils.device_telemetry import telemetry as _telemetry
from ceph_tpu.utils import dispatch_telemetry as _dsp
from ceph_tpu.utils import flow_telemetry as _flows
from ceph_tpu.utils.dout import Dout
from ceph_tpu.utils.tracing import NOOP

log = Dout("osd")

from ceph_tpu.utils import tracepoints as _tracepoints  # noqa: E402

_TP_FLUSH = _tracepoints.provider("osd").point(
    "device_flush", "ops", "bytes")
_TP_DECODE_FLUSH = _tracepoints.provider("osd").point(
    "device_decode_flush", "ops", "signature")


def bulk_ingest_enabled() -> bool:
    """The ISSUE-9 data-plane master switch: batched sub-write
    fan-out + zero-copy staging + the shared engine service. Read at
    engine/OSD construction time so ``CEPH_TPU_BULK_INGEST=0|1`` can
    A/B consecutive clusters in one process (the gap report's
    before/after regression mode)."""
    import os
    return os.environ.get("CEPH_TPU_BULK_INGEST", "1") != "0"


def mesh_flush_threshold() -> int:
    """The dense->mesh crossover in bytes: flushes at least this big
    route through the default mesh's sharded steps. A real g_conf
    Option since ISSUE 12 (registry-drift-lint covered; the ISSUE-13
    tuner adjusts it at runtime through the engine's cached
    observer), env override preserved for A/B runs — and an env pin
    freezes the knob against tuner pushes."""
    import os
    env = os.environ.get("CEPH_TPU_MESH_FLUSH_BYTES")
    if env is not None:
        return int(env)
    try:
        from ceph_tpu.utils.config import g_conf
        return int(g_conf()["mesh_flush_bytes"])
    except Exception:
        return 1 << 20


def _conf_knob(env_name: str, read_conf, fallback: int
               ) -> tuple[int, bool]:
    """Resolve one engine knob at construction: env beats the
    declared Option (the A/B convention), Option beats the compiled
    fallback. Returns (value, pinned) — a pinned knob (env) must NOT
    track runtime config pushes, an unpinned one must (the tuner's
    actuation path is exactly a runtime ``config set``)."""
    import os
    env = os.environ.get(env_name)
    if env is not None:
        return int(env), True
    try:
        return int(read_conf()), False
    except Exception:
        return fallback, True


def _placement_slot(key) -> int:
    """The PG-placement slot for one staged op's dispatch key (the
    pgid, possibly wrapped by a shared-engine attachment): stripe-row
    coordinate of the default mesh, 0 when no multi-slot map is
    active. Computed at STAGE time so the staging buffers key by
    (signature, slot) and each slot's bytes stay contiguous. Runs on
    every staged op's producer thread: the no-mesh common case must
    stay one attribute read, no map machinery."""
    from ceph_tpu.parallel import mesh as mesh_mod
    if mesh_mod.get_default_mesh() is None:
        return 0
    from ceph_tpu.parallel import placement as _placement
    pmap = _placement.active_map()
    if pmap is None or pmap.n_slots <= 1:
        return 0
    if isinstance(key, AttachedKey):
        key = key[1]
    return pmap.slot(key)


_opaque_codec_seq = itertools.count(1)
_program_key_lock = make_lock("engine.program_key")


def program_key(codec, sinfo: ec_util.StripeInfo) -> tuple:
    """The key under which staged ops meet in one flush: what the
    flush program and its results depend on and nothing else — the
    codec's class and ``backend``, its coding matrix (what
    :func:`ec_util.fused_program` keys its cache by),
    ``chunk_mapping``, and the stripe geometry. Every PG's ECBackend
    builds its own codec object from the pool's profile; equal keys
    mean the objects are interchangeable, so ops of different PGs
    (and OSDs) of one pool share a flush, and pools that differ in
    any of these never do. A layered codec (clay) states its own
    value (``flush_key()``: backend, k, m, d and the two sub-codes'
    matrices, what :func:`ec_util.layered_program` keys its cache
    by). A codec that is neither (lrc: state the key cannot see) gets
    a key of its own. Runs on every producer thread: the codec's part
    is computed once per codec object and cached on it, valid while
    the codec keeps the matrix it was computed from."""
    cached = getattr(codec, "_engine_program_key", None)
    mat = getattr(codec, "coding_matrix", None)
    if cached is None or cached[0] is not mat:
        from ceph_tpu.models.matrix_codec import MatrixErasureCode
        with _program_key_lock:     # one key a codec, whoever is first
            cached = getattr(codec, "_engine_program_key", None)
            if cached is None or cached[0] is not mat:
                if isinstance(codec, MatrixErasureCode) and \
                        mat is not None:
                    ck = (type(codec), codec.backend, mat.shape,
                          mat.tobytes(), tuple(codec.chunk_mapping))
                elif ec_util.flush_kind(codec) == "layered":
                    ck = (type(codec),) + tuple(codec.flush_key())
                else:
                    ck = (type(codec), next(_opaque_codec_seq))
                cached = codec._engine_program_key = (mat, ck)
    return (cached[1], sinfo.stripe_width, sinfo.chunk_size)


class _ConcatStager:
    """Per-program-key preallocated concat buffers, written at
    staging time (the zero-copy leg of ISSUE 9). ``append`` copies
    the op's payload into its key's open buffer on the PRODUCER
    thread; ``take`` hands the engine the consumed prefix as one
    contiguous view plus per-op views into it — no flush-time
    np.concatenate. A key is (:func:`program_key`, placement slot):
    ops of every PG whose codec and stripe geometry are equal land in
    ONE buffer, in queue order. Ownership of the handed buffer passes
    to the flush (result shard views may alias it); unconsumed tail
    bytes (ops racing the flush cut, of whatever PGs) relocate into a
    fresh buffer."""

    _MIN_CAP = 256 << 10

    def __init__(self) -> None:
        self.lock = make_lock("engine.stager")
        #: (program_key, placement slot) -> {"buf", "used",
        #: "slots": [[off, len], ...]} — keyed by program AND slot
        #: (ISSUE 12) so each placement slot's flush hands its owning
        #: submesh one contiguous view
        self._by_key: dict[tuple, dict] = {}
        self.stats = {"staged_bytes": 0, "relocated_bytes": 0}

    def _state(self, gkey: tuple) -> dict:
        st = self._by_key.get(gkey)
        if st is None:
            st = self._by_key[gkey] = {
                "buf": np.empty(self._MIN_CAP, dtype=np.uint8),
                "used": 0, "slots": []}
        return st

    def append_locked(self, gkey: tuple, data: np.ndarray) -> None:
        """Caller holds ``self.lock`` (the engine queue put rides the
        same critical section so per-key order == queue order)."""
        st = self._state(gkey)
        need = st["used"] + data.nbytes
        if need > len(st["buf"]):
            cap = max(len(st["buf"]), self._MIN_CAP)
            while cap < need:
                cap <<= 1
            buf = np.empty(cap, dtype=np.uint8)
            buf[:st["used"]] = st["buf"][:st["used"]]
            st["buf"] = buf
        st["buf"][st["used"]:need] = data.ravel()
        st["slots"].append([st["used"], data.nbytes])
        st["used"] = need
        self.stats["staged_bytes"] += data.nbytes

    def take(self, gkey: tuple, count: int
             ) -> tuple[np.ndarray, list[np.ndarray]]:
        """Detach the first ``count`` staged ops of this
        (program key, slot): returns (contiguous batch view, per-op
        views). The tail (ops staged after the engine decided to
        flush) moves to a fresh buffer so its queued tokens stay
        valid."""
        with self.lock:
            st = self._state(gkey)
            slots = st["slots"][:count]
            tail = st["slots"][count:]
            buf = st["buf"]
            cut = (slots[-1][0] + slots[-1][1]) if slots else 0
            if tail:
                tail_bytes = st["used"] - cut
                cap = self._MIN_CAP
                while cap < tail_bytes:
                    cap <<= 1
                fresh = np.empty(cap, dtype=np.uint8)
                fresh[:tail_bytes] = buf[cut:st["used"]]
                for slot in tail:
                    slot[0] -= cut
                st["buf"] = fresh
                st["used"] = tail_bytes
                st["slots"] = tail
                self.stats["relocated_bytes"] += tail_bytes
            else:
                st["buf"] = np.empty(self._MIN_CAP, dtype=np.uint8)
                st["used"] = 0
                st["slots"] = []
            views = [buf[off:off + ln] for off, ln in slots]
            return buf[:cut], views


class FlushGroup:
    """Per-retired-flush rendezvous (the batched fan-out leg of
    ISSUE 9): the engine dispatches one continuation wrapper per
    distinct key; each wrapper's ops may :meth:`defer` cross-PG work
    (per-peer sub-write batches, merged local txn groups). When the
    LAST wrapper has finished the group is ``ready``, and the
    engine's ship thread ships it: groups ship one after the other in
    flush order, so sends to a peer keep flush order (the per-PG
    commit-order contract extended across the batch boundary).
    Barriers chain behind the flush via :meth:`after_flush`.

    The ship must NOT run on a wrapper's thread: chained there
    through after-flush callbacks, a busy chain makes one op-wq
    worker ship dozens of groups in one nested cascade (65 deep,
    2.4 s measured) while every op and sub-write hashed to its shard
    waits — that was the 4 MiB write's tail (PERF.md section 6,
    PR 27)."""

    def __init__(self, nkeys: int, overwrite: bool = False) -> None:
        self._lock = make_lock("engine.flush_group")
        self._pending = max(1, nkeys)
        #: the flush encoded range overwrites' stripe windows: its
        #: deferred items are small, so the ship thread may ship it
        #: with the ready overwrite groups queued behind it
        self.overwrite = overwrite
        #: bucket -> (ship_fn, [items]); insertion-ordered
        self._deferred: dict = {}
        self._after: list = []
        self._flushed = False
        #: every wrapper finished: the ship thread may ship
        self.ready = threading.Event()
        #: shipped
        self.event = threading.Event()

    def defer(self, bucket, ship_fn, item) -> None:
        """Queue ``item`` for ``ship_fn(items)`` at group flush;
        items of one bucket ship together (one message / one txn
        group)."""
        with self._lock:
            ent = self._deferred.get(bucket)
            if ent is None:
                ent = self._deferred[bucket] = (ship_fn, [])
            ent[1].append(item)

    def after_flush(self, cb) -> None:
        """Run ``cb`` once the group has shipped and every callback
        registered before it has run (immediately if that is so
        already): callbacks run strictly in registration order."""
        with self._lock:
            if not self._flushed:
                self._after.append(cb)
                return
        cb()

    def done(self) -> None:
        """One per-key wrapper finished; after the last one the group
        is ready to ship. Never blocks and never ships: the wq worker
        goes back to its queue."""
        with self._lock:
            self._pending -= 1
            if self._pending > 0:
                return
        self.ready.set()

    def _take_deferred(self) -> dict:
        with self._lock:
            deferred, self._deferred = self._deferred, {}
        return deferred

    def _shipped(self) -> None:
        """Mark the group shipped and run its after-flush callbacks."""
        self.event.set()
        while True:
            # a callback registered while these run queues behind
            # them instead of running at once on its caller's thread
            with self._lock:
                after, self._after = self._after, []
                if not after:
                    self._flushed = True
                    return
            for cb in after:
                try:
                    cb()
                except Exception as exc:
                    log(0, "flush-group after-flush cb failed: "
                        f"{exc!r}")


def ship_groups(groups: list) -> None:
    """Ship consecutive ready flush groups as one (group commit at the
    ship thread): the deferred items of equal buckets concatenate in
    flush order and each bucket's ``ship_fn`` is called once, so a peer
    gets one ``MECSubWriteBatch`` and a primary's local shards one txn
    group for all of them. Then, group by group in flush order, each is
    marked shipped and runs its after-flush callbacks (the engine's
    ship thread, once every one of them is ready and every earlier
    group has shipped)."""
    merged: dict = {}
    for group in groups:
        for bucket, (ship_fn, items) in group._take_deferred().items():
            ent = merged.setdefault(bucket, (ship_fn, []))
            ent[1].extend(items)
    for ship_fn, items in merged.values():
        try:
            ship_fn(items)
        except Exception as exc:
            log(0, f"flush-group ship failed: {exc!r}")
    for group in groups:
        group._shipped()


_group_tls = threading.local()


def current_group() -> "FlushGroup | None":
    """The FlushGroup whose continuation wrapper is running on this
    thread (None outside one) — how ECBackend's fan-out discovers it
    can defer sends into the per-peer batch instead of shipping one
    MECSubWrite per shard."""
    return getattr(_group_tls, "group", None)


class _StagedRef:
    """Placeholder riding the queue in place of the payload when the
    bytes already live in the stager's concat buffer (only the byte
    count is still needed on the engine loop's flush threshold)."""

    __slots__ = ("nbytes",)

    def __init__(self, nbytes: int) -> None:
        self.nbytes = nbytes


class DeviceEncodeEngine:
    """One per OSD — or one per PROCESS through the shared engine
    service (:func:`shared_engine_attach`); owns the device dispatch
    thread."""

    def __init__(self, dispatch: Callable[[object, Callable], None],
                 flush_bytes: int | None = None,
                 counters=None, window: int | None = None,
                 mesh_flush_bytes: int | None = None) -> None:
        import os
        #: dispatch(key, fn): run fn on the per-key FIFO executor (the
        #: OSD passes op_wq.enqueue, keyed by pgid). None for the
        #: shared engine service, where every key is an AttachedKey
        #: routed through the per-OSD dispatcher table below.
        self._dispatch_default = dispatch
        #: attach token -> that OSD's dispatch fn (shared engine)
        self._dispatchers: dict[int, Callable] = {}
        #: ISSUE 9 bulk-ingest legs, captured at construction so
        #: CEPH_TPU_BULK_INGEST can A/B consecutive clusters
        self._bulk = bulk_ingest_enabled()
        self._stager = _ConcatStager() if self._bulk else None
        #: flush order: every retired flush's FlushGroup goes on
        #: this queue as it is made, and the ship thread ships them
        #: in that order, each once it is ready
        self._ship_q: queue.SimpleQueue = queue.SimpleQueue()
        self._last_group: FlushGroup | None = None
        #: dispatch key -> the group whose ship dispatches that key's
        #: newest barrier: the key's continuations retired since then
        #: are dispatched behind the barrier, not past it (written on
        #: the launch thread with the window drained, read on the
        #: retire thread)
        self._barrier_group: dict = {}
        self._counters = counters
        # ISSUE 13: the four engine knobs resolve explicit-arg > env
        # > g_conf Option, and every UNPINNED one registers a config
        # observer so the mgr tuner's runtime pushes land here as one
        # cached attribute write — never a per-flush g_conf read (the
        # hot-path audit: the same RLock fix the tracing PR measured)
        self._cfg_observers: list[tuple[str, Callable]] = []
        #: staged payload bytes that force a launch (the batch-size
        #: cap bounding the device working set)
        from ceph_tpu.utils.config import g_conf
        if flush_bytes is None:
            flush_bytes, fb_pinned = _conf_knob(
                "CEPH_TPU_ENGINE_FLUSH_BYTES",
                lambda: g_conf()["engine_flush_bytes"], 64 << 20)
        else:
            fb_pinned = True
        self._flush_bytes = flush_bytes
        #: max launched-not-retired encode batches (the pipeline
        #: depth); 1 = the old serial engine
        if window is None:
            window, w_pinned = _conf_knob(
                "CEPH_TPU_ENGINE_WINDOW",
                lambda: g_conf()["engine_window"], 3)
        else:
            w_pinned = True
        self._window = max(1, window)
        #: batches at least this big route through the default mesh's
        #: sharded encode step (when one is configured); smaller ones
        #: stay single-chip
        if mesh_flush_bytes is None:
            mesh_flush_bytes = mesh_flush_threshold()
            mfb_pinned = "CEPH_TPU_MESH_FLUSH_BYTES" in os.environ
        else:
            mfb_pinned = True
        self._mesh_flush_bytes = mesh_flush_bytes
        #: flushes SMALLER than this take the host matvec instead of
        #: a device launch (the fixed dispatch cost dominates tiny
        #: batches — the bottom end of the routing ladder: host <
        #: host_flush_bytes <= single-chip device < mesh_flush_bytes
        #: <= mesh). 0 disables; bulk-ingest only.
        self._host_flush_bytes, hfb_pinned = _conf_knob(
            "CEPH_TPU_HOST_FLUSH_BYTES",
            lambda: g_conf()["host_flush_bytes"], 512 << 10)
        #: which knobs track runtime config pushes (env pins do not)
        self._knob_unpinned = {"engine_flush_bytes": not fb_pinned,
                               "engine_window": not w_pinned,
                               "mesh_flush_bytes": not mfb_pinned,
                               "host_flush_bytes": not hfb_pinned}
        # warmup-kill: per-signature device programs persist across
        # processes (best-effort; a disabled/failed cache only costs
        # recompiles, never correctness)
        from ceph_tpu.utils import compile_cache
        compile_cache.enable()
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._running = True
        #: introspection (asok / tests): launches, ops, bytes, and the
        #: largest ops-per-launch seen — proof the batching engages
        self.stats = {"flushes": 0, "ops": 0, "bytes": 0,
                      "max_batch_ops": 0, "errors": 0,
                      # ops retired in a flush that held ops of more
                      # than one dispatch key (PG): how often the
                      # program key lets PGs share a flush
                      "cross_pg_ops": 0, "decode_cross_pg_ops": 0,
                      "decode_flushes": 0, "decode_ops": 0,
                      "decode_bytes": 0, "max_decode_batch_ops": 0,
                      "decode_errors": 0, "device_fused_fallbacks": 0,
                      # launch-pipeline occupancy: the deepest the
                      # in-flight window ever got (>= 2 proves
                      # upload/compute/download overlapped) and how
                      # many flushes routed through the mesh
                      "max_inflight_depth": 0, "mesh_flushes": 0,
                      # pod-scale sharded serving (ISSUE 12): decode
                      # flushes that rode the mesh twin, and flushes
                      # launched on a PG-placement slot submesh
                      "mesh_decode_flushes": 0,
                      "placement_flushes": 0,
                      # slot -> flushes launched on that slot's
                      # submesh: the observable placement decisions
                      # (the loopback-vs-TCP fidelity check compares
                      # these across wire paths)
                      "per_slot_flushes": {},
                      # small flushes routed to the host matvec (the
                      # bulk-ingest bottom rung of the routing ladder)
                      "host_flushes": 0,
                      # auxiliary device work run via run_sync (deep
                      # scrub verify launches)
                      "aux_runs": 0,
                      # a layered codec's (clay) ops that went through
                      # the one-program-a-flush routes, and erasure
                      # signatures whose decode table had to be built
                      # by a flush, on first use (tables a primary
                      # builds when it peers are not among them)
                      "layered_encode_ops": 0,
                      "layered_decode_ops": 0,
                      "signature_builds": 0,
                      # range overwrites' spliced stripe windows
                      # encoded by the overwrite route (never
                      # host-routed, no crc pass); also counted in
                      # ops / flushes
                      "overwrite_ops": 0, "overwrite_flushes": 0,
                      # decode ops an overwrite's ranged read staged
                      # (its stripe lacked a data chunk); also
                      # counted in decode_ops
                      "rmw_decodes": 0,
                      # the ship thread's ships and the flush groups
                      # they carried (overwrite groups queued ready
                      # behind one another ship as one)
                      "ships": 0, "ship_groups": 0}
        _telemetry().note_engine_window(self._window)
        #: launch pipeline: deque of (items, finalize, kspans,
        #: nbytes) batches whose device programs are queued
        #: but not yet downloaded — up to ``window`` deep. The RETIRE
        #: thread harvests strictly FIFO, so continuation order equals
        #: launch order; the engine thread never blocks on a download
        #: (ops staged during batch N's device round coalesce into
        #: batch N+1 instead of waiting behind its harvest — the
        #: bulk-ingest batching lever).
        import collections
        self._inflight: collections.deque = collections.deque()
        self._ifcv = make_condition("engine.inflight")
        self._retiring = False        # retire thread mid-harvest
        self._retire_stop = False
        self._thread = threading.Thread(
            target=self._run, name="ec-device-engine", daemon=True)
        self._thread.start()
        self._retire_thread = threading.Thread(
            target=self._retire_run, name="ec-device-retire",
            daemon=True)
        self._retire_thread.start()
        self._ship_thread = threading.Thread(
            target=self._ship_run, name="ec-device-ship", daemon=True)
        self._ship_thread.start()
        # runtime knob observers attach LAST (fully-built engine: the
        # window observer touches the inflight CV) — literal names so
        # the registry-drift lint can hold every tuner-managed knob
        # to the cached-observer bar
        self._observe_knob("engine_flush_bytes",
                           self._set_flush_bytes)
        self._observe_knob("engine_window", self._set_window)
        self._observe_knob("mesh_flush_bytes",
                           self._set_mesh_flush_bytes)
        self._observe_knob("host_flush_bytes",
                           self._set_host_flush_bytes)

    # -- runtime knob observers (ISSUE 13) ----------------------------
    def _observe_knob(self, option: str, fn) -> None:
        if not self._knob_unpinned.get(option, False):
            return              # env/arg pins win for this engine
        try:
            from ceph_tpu.utils.config import g_conf
            g_conf().add_observer(option, fn)
            self._cfg_observers.append((option, fn))
        except Exception:
            pass            # a schema-less embedder keeps the pins

    def _set_window(self, _name: str, value) -> None:
        """Runtime window change: widen wakes launchers blocked in
        _wait_window; shrink takes effect on their next wait check
        (in-flight batches above the new bound drain naturally — the
        window is a launch gate, not a hard cap on what is already
        out)."""
        with self._ifcv:
            self._window = max(1, int(value))
            self._ifcv.notify_all()
        _telemetry().note_engine_window(self._window)

    def _set_flush_bytes(self, _name: str, value) -> None:
        self._flush_bytes = max(1, int(value))

    def _set_mesh_flush_bytes(self, _name: str, value) -> None:
        self._mesh_flush_bytes = max(0, int(value))

    def _set_host_flush_bytes(self, _name: str, value) -> None:
        self._host_flush_bytes = max(0, int(value))

    # -- dispatch routing (per-OSD when shared) -----------------------
    def _dispatch(self, key, fn) -> None:
        if isinstance(key, AttachedKey):
            d = self._dispatchers.get(key[0])
            if d is None:
                log(1, "dropping continuation for detached engine "
                    f"attachment {key[0]}")
                return
            d(key[1], fn)
            return
        self._dispatch_default(key, fn)

    def register_dispatcher(self, token: int, dispatch) -> None:
        self._dispatchers[token] = dispatch
        _telemetry().note_attached_osds(len(self._dispatchers))

    def unregister_dispatcher(self, token: int) -> None:
        self._dispatchers.pop(token, None)
        _telemetry().note_attached_osds(len(self._dispatchers))

    # -- batched continuation dispatch (ISSUE 9) ----------------------
    def _dispatch_entries(self, entries, overwrite: bool = False) -> None:
        """Dispatch a retired flush's continuations: one wrapper per
        distinct key (batched mode) sharing a FlushGroup, or the
        legacy one-callable-per-op dispatch. ``entries`` is ordered
        [(key, fn)]; ``overwrite``: the flush was an overwrite
        flush."""
        if not self._bulk:
            for key, fn in entries:
                self._dispatch(key, fn)
            return
        by_key: dict = {}
        for key, fn in entries:
            by_key.setdefault(key, []).append(fn)
        group = FlushGroup(len(by_key), overwrite=overwrite)
        self._last_group = group
        # queued BEFORE its wrappers run: creation order (the retire
        # thread alone makes groups) is ship order
        self._ship_q.put(group)

        for key, fns in by_key.items():
            def run(fns=fns, group=group):
                _group_tls.group = group
                try:
                    for fn in fns:
                        try:
                            fn()
                        except Exception as exc:
                            log(0, f"batched continuation failed: "
                                f"{exc!r}")
                finally:
                    _group_tls.group = None
                    group.done()
            run._profile_stage = "commit_wait"
            fence = self._barrier_group.get(key)
            if fence is not None and fence._flushed:
                del self._barrier_group[key]    # long dispatched
                fence = None
            if fence is None:
                self._dispatch(key, run)
            else:
                # a barrier of this key waits for an earlier group's
                # ship (or has been dispatched after it): keep the
                # key's order, this wrapper goes behind the barrier
                fence.after_flush(
                    lambda key=key, run=run: self._dispatch(key, run))

    def _ship_run(self) -> None:
        """Ship retired flushes' groups strictly in flush order, on
        this thread alone: a group ships when its last wrapper has
        finished and every earlier group has shipped. An overwrite
        group takes along every overwrite group queued behind it that
        is already ready (:func:`ship_groups`), up to the first that
        is not ready or not an overwrite group, which heads the next
        ship; nothing is waited for to fill a ship, and full-write
        groups, whose items are chunks of whole objects, ship alone.
        On a profiler trace a ship is ``flush_ship`` (stat ``groups``);
        waiting for a group, or for its wrappers, is ``ship_idle``."""
        _prof.thread_role("engine_ship")
        carried: list = []
        while True:
            mark = _prof.push_stage("idle", span="ship_idle")
            try:
                group = carried.pop() if carried else self._ship_q.get()
                if group is None:
                    return
                group.ready.wait()
            finally:
                _prof.pop_stage(mark)
            groups = [group]
            while group.overwrite:
                try:
                    group = self._ship_q.get_nowait()
                except queue.Empty:
                    break
                if group is None or not group.overwrite or \
                        not group.ready.is_set():
                    carried.append(group)
                    break
                groups.append(group)
            self.stats["ships"] += 1
            self.stats["ship_groups"] += len(groups)
            mark = _prof.push_stage("commit_wait", span="flush_ship",
                                    groups=len(groups))
            try:
                ship_groups(groups)
            finally:
                _prof.pop_stage(mark)

    def _dispatch_barrier(self, key, fn) -> None:
        """Dispatch a barrier's ``fn`` on ``key`` after the most
        recently dispatched flush group has shipped (at once when
        there is none) — the barrier ordering point extended across
        deferred batch sends. The ship thread may be groups behind,
        so the key is fenced on that group: its continuations retired
        from now on are dispatched behind the barrier
        (:meth:`_dispatch_entries`), and per-key order stays
        submission order however late the ship is."""
        group = self._last_group
        if group is not None and self._bulk:
            self._barrier_group[key] = group
            group.after_flush(lambda: self._dispatch(key, fn))
        else:
            self._dispatch(key, fn)

    # -- producer side (op-shard threads) -----------------------------
    @staticmethod
    def _note_staged_flow(cont, nbytes: int) -> None:
        """Tenant attribution at the staging seam (ISSUE 20): the
        producer thread's flow owns these HBM-staged bytes; the label
        rides the continuation so retirement can split the flush's
        occupancy per flow."""
        ft = _flows.flows_if_active()
        if ft is None:
            return
        label = _flows.current_flow() or ""
        try:
            cont._flow = label
        except AttributeError:
            pass
        try:
            ft.note_engine_staged(label, nbytes)
        except Exception:
            pass

    def stage_encode(self, key, codec, sinfo: ec_util.StripeInfo,
                     data: np.ndarray,
                     cont: Callable[[dict | None, dict | None,
                                     Exception | None], None],
                     span=NOOP, clock=_stage_clock.NOOP,
                     overwrite: bool = False) -> None:
        """Queue one op's stripe-aligned payload for batched device
        encode; ``cont(shards, crcs, err)`` is dispatched on ``key``
        (crcs = per-shard LINEAR crc parts computed on device from the
        same buffers, or None; err set and shards None on device
        failure — caller falls back). ``span``: the op's dataflow
        trace continues through the engine (flush launch, kernel
        dispatch, crc pass events); ``clock``: the op's StageClock —
        the engine marks engine_stage_wait / device_window_wait /
        device_finalize on it, so the per-op timeline survives the
        engine boundary. Both defaults are free no-ops.
        ``overwrite``: the payload is a range overwrite's spliced
        stripe window; it meets only other overwrites in a flush,
        which encodes on the device without a crc pass (crcs None)
        whatever its size (``ec_util._flush_device_fused_async``
        without crcs)."""
        import time as _time
        # HBM ledger: bytes enter the staged bucket here and leave it
        # at launch (-> in-window) or on a launch fault (-> retired)
        _telemetry().note_hbm(staged_delta=data.nbytes)
        self._note_staged_flow(cont, data.nbytes)
        # PG placement (ISSUE 12): the slot is part of the staging
        # key, so each stripe row's bytes accumulate contiguously and
        # flush onto their owning chips. The per-slot staged ledger
        # (ISSUE 13) is the tuner's chip-load signal for load-aware
        # placement weighting.
        pslot = _placement_slot(key)
        _telemetry().note_slot_staged(pslot, data.nbytes)
        # the key under which this op meets others in a flush,
        # computed HERE and carried on the queue: the stager's buffer
        # and the engine's batch are found by the same value
        gkey = (program_key(codec, sinfo), pslot, overwrite)
        if self._stager is not None:
            # zero-copy staging: the payload lands in its program
            # key's concat buffer NOW, on this producer thread; the
            # engine flush takes one contiguous view. The queue put
            # rides the stager lock so per-key slot order == queue
            # order.
            ref = _StagedRef(data.nbytes)
            with self._stager.lock:
                self._stager.append_locked(gkey, data)
                self._q.put(("enc", key, codec, sinfo, ref, cont,
                             span, clock, _time.monotonic(), gkey))
            return
        self._q.put(("enc", key, codec, sinfo, data, cont, span,
                     clock, _time.monotonic(), gkey))

    def stage_barrier(self, key, fn: Callable[[], None]) -> None:
        """Queue an ordering barrier: ``fn`` dispatches on ``key``
        after every previously staged op's continuation."""
        self._q.put(("bar", key, fn))

    def stage_decode(self, key, codec, sinfo: ec_util.StripeInfo,
                     shards: dict[int, np.ndarray], want: list[int],
                     cont: Callable[[dict | None, Exception | None],
                                    None], span=NOOP,
                     clock=_stage_clock.NOOP,
                     overwrite: bool = False) -> None:
        """Queue a reconstruct of ``want`` chunk streams from the
        surviving ``shards``; ``cont(decoded, err)`` runs INLINE on
        the engine thread (must be cheap and lock-free — the typical
        continuation publishes the result and sets an event for a
        blocked decode_sync caller). ``ECBackend.read_object_async``
        violates this: its continuation reassembles the object and
        sends the reply here, one op of a flush after the other.
        Dispatching it on ``key`` instead was measured and earned no
        rate (PERF.md section 6).
        ``overwrite``: a range overwrite's ranged read staged it
        (counted in ``rmw_decodes``)."""
        import time as _time
        _telemetry().note_hbm(staged_delta=_shards_nbytes(shards))
        self._note_staged_flow(cont, _shards_nbytes(shards))
        pslot = _placement_slot(key)
        _telemetry().note_slot_staged(pslot, _shards_nbytes(shards))
        sig = (program_key(codec, sinfo), tuple(sorted(shards)),
               tuple(sorted(want)), pslot)
        self._q.put(("dec", key, codec, sinfo, shards, want, cont,
                     span, clock, _time.monotonic(), sig, overwrite))

    def decode_sync(self, key, codec, sinfo: ec_util.StripeInfo,
                    shards: dict[int, np.ndarray], want: list[int],
                    timeout: float = 60.0,
                    span=NOOP,
                    clock=_stage_clock.NOOP,
                    overwrite: bool = False
                    ) -> dict[int, np.ndarray] | None:
        """Blocking decode through the batched engine; returns the
        decoded {chunk: bytes} map or None on device fault/timeout
        (the caller falls back to its host twin). ``overwrite``: a
        range overwrite's ranged read asks (``rmw_decodes``). An
        op-wq worker may block here on one condition: the decode's
        continuation is never dispatched to a wq shard. It runs
        inline in the engine thread's decode flush, and that thread
        never waits for a wq worker (a barrier or a retired flush's
        continuations are queued, not run), so the caller's own
        blocked shard cannot hold its decode back."""
        ev = threading.Event()
        box: list = [None, None]

        def cont(out, err):
            box[0], box[1] = out, err
            ev.set()

        self.stage_decode(key, codec, sinfo, shards, want, cont,
                          span=span, clock=clock, overwrite=overwrite)
        if not ev.wait(timeout):
            log(0, f"device decode timed out after {timeout}s; "
                "host fallback")
            self.stats["decode_errors"] += 1
            return None
        if box[1] is not None:
            return None
        return box[0]

    def run_sync(self, fn: Callable[[], object],
                 timeout: float = 120.0):
        """Run ``fn`` on the engine thread and return its result
        (deep scrub's verify launches ride here so background
        verification serializes with client encode/decode flushes on
        the one device instead of contending mid-download). Raises
        what ``fn`` raises; raises TimeoutError when the engine is
        stopped or wedged."""
        ev = threading.Event()
        box: list = [None, None]
        self._q.put(("run", fn, box, ev))
        if not ev.wait(timeout):
            raise TimeoutError("device engine run_sync timed out")
        if box[1] is not None:
            raise box[1]
        return box[0]

    def stop(self) -> None:
        # detach the knob observers first: a tuner push must not land
        # an attribute write on an engine that is tearing down
        if self._cfg_observers:
            try:
                from ceph_tpu.utils.config import g_conf
                for option, fn in self._cfg_observers:
                    g_conf().remove_observer(option, fn)
            except Exception:
                pass
            self._cfg_observers = []
        self._running = False
        self._q.put(None)
        self._thread.join(timeout=10)
        with self._ifcv:
            self._retire_stop = True
            self._ifcv.notify_all()
        self._retire_thread.join(timeout=10)
        # shutdown drain, batched edition: the retire thread has
        # DISPATCHED every continuation wrapper and queued every
        # flush group; the ship thread ships them as the wrappers
        # finish on the op-wq — wait for the last ship so nothing
        # chained behind it (barriers, local txn groups) is dropped
        # by a wq that stops right after us
        self._ship_q.put(None)
        self._ship_thread.join(timeout=10)
        if self._ship_thread.is_alive():
            log(1, "engine stop: last flush group never shipped")

    # -- retire thread ------------------------------------------------
    def _retire_run(self) -> None:
        """Harvest launched batches strictly FIFO on a dedicated
        thread: while batch N's download blocks HERE, the engine
        thread keeps accumulating and launching batches N+1.. — ops
        no longer queue behind a blocking drain (the measured
        engine_stage_wait share), and bigger flushes amortize the
        per-peer sub-write batches."""
        _prof.thread_role("engine_retire")
        while True:
            _pidle = _prof.push_stage("idle", span="retire_idle")
            try:
                with self._ifcv:
                    while not self._inflight and \
                            not self._retire_stop:
                        self._ifcv.wait()
                    if not self._inflight and self._retire_stop:
                        return
                    entry = self._inflight.popleft()
                    self._retiring = True
                    self._ifcv.notify_all()
            finally:
                _prof.pop_stage(_pidle)
            try:
                self._retire_one(entry)
            finally:
                with self._ifcv:
                    self._retiring = False
                    self._ifcv.notify_all()

    # -- engine thread ------------------------------------------------
    def _run(self) -> None:
        _prof.thread_role("engine_launch")
        while True:
            # profiler join: blocking on an empty queue is idle time,
            # not engine work — without the mark, every sample of the
            # parked engine thread would inflate engine_stage_wait
            _pidle = _prof.push_stage("idle")
            item = self._q.get()
            _prof.pop_stage(_pidle)
            if item is None:
                self._drain_inflight()
                return
            # (program_key, placement slot) -> (codec, sinfo, slot,
            # items): ops of every PG with an equal program key meet
            # in one flush, which runs with the FIRST op's codec and
            # sinfo objects (interchangeable by construction of the
            # key) — slot-keyed (ISSUE 12) so each stripe row's flush
            # launches on its owning submesh
            pending: dict[tuple, tuple] = {}
            # (program_key, present, want, slot) -> state: a decode
            # flush shares one decode matrix
            dec_pending: dict[tuple, tuple] = {}
            nbytes = 0
            while True:
                if item is None:
                    self._flush(pending)
                    self._flush_decodes(dec_pending)
                    self._drain_inflight()
                    return
                if item[0] == "enc":
                    (_, key, codec, sinfo, data, cont, span, clock,
                     ts, gkey) = item
                    # handoff seam (ISSUE 17): producer put -> engine
                    # thread pickup, one cross-thread hop per stage
                    _dsp.telemetry().note_handoff(
                        "engine_stage", _time.monotonic() - ts)
                    if self._flush_first(pending, gkey, key, data):
                        self._flush(pending)
                        self._flush_decodes(dec_pending)
                        pending, dec_pending, nbytes = {}, {}, 0
                    _, _, _, items = pending.setdefault(
                        gkey, (codec, sinfo, gkey[1], []))
                    items.append((key, data, cont, span, clock, ts))
                    nbytes += data.nbytes
                    if nbytes >= self._flush_bytes:
                        # flush BOTH kinds: the byte counter is
                        # shared, and a staged decode left behind
                        # here would wait for the next barrier/idle
                        # while its decode_sync caller blocks
                        self._flush(pending)
                        self._flush_decodes(dec_pending)
                        pending, dec_pending, nbytes = {}, {}, 0
                elif item[0] == "dec":
                    (_, key, codec, sinfo, shards, want, cont, span,
                     clock, ts, sig, overwrite) = item
                    if overwrite:
                        self.stats["rmw_decodes"] += 1
                    _dsp.telemetry().note_handoff(
                        "engine_stage", _time.monotonic() - ts)
                    _, _, _, items = dec_pending.setdefault(
                        sig, (codec, sinfo, sig[3], []))
                    items.append((key, shards, want, cont, span,
                                  clock, ts))
                    nbytes += sum(np.asarray(v).nbytes
                                  for v in shards.values())
                    if nbytes >= self._flush_bytes:
                        self._flush(pending)
                        self._flush_decodes(dec_pending)
                        pending, dec_pending, nbytes = {}, {}, 0
                elif item[0] == "run":
                    # auxiliary device work (deep-scrub verify): runs
                    # after the in-flight batch drains so it never
                    # contends with an encode download on the device
                    self._flush(pending)
                    self._flush_decodes(dec_pending)
                    self._drain_inflight()
                    pending, dec_pending, nbytes = {}, {}, 0
                    _, fn, box, ev = item
                    prev_stage = _prof.push_stage("scrub")
                    try:
                        box[0] = fn()
                    except Exception as exc:
                        box[1] = exc
                    finally:
                        _prof.pop_stage(prev_stage)
                    self.stats["aux_runs"] += 1
                    ev.set()
                else:                        # barrier
                    self._flush(pending)
                    self._flush_decodes(dec_pending)
                    # the barrier fn must run AFTER every prior op's
                    # continuation: drain the launch pipeline first
                    self._drain_inflight()
                    pending, dec_pending, nbytes = {}, {}, 0
                    _, key, fn = item
                    # ...and after the last flush group SHIPPED its
                    # deferred batch sends: a barrier's own fan-out
                    # (remove/RMW) must not beat the older writes'
                    # batched sub-writes to the shards
                    self._dispatch_barrier(key, fn)
                try:
                    item = self._q.get_nowait()
                except queue.Empty:
                    # nothing else queued: launch what we have now
                    # (an idle engine adds no batching latency). The
                    # RETIRE thread harvests it — no drain here, so
                    # ops arriving during the device round coalesce
                    # into the next flush instead of queueing behind
                    # a blocking download
                    self._flush(pending)
                    self._flush_decodes(dec_pending)
                    pending, dec_pending, nbytes = {}, {}, 0
                    break
            # shutdown is the None sentinel, NOT self._running: ops
            # staged before stop() must still flush (checking the
            # flag here raced the idle drain and dropped them)

    @staticmethod
    def _flush_first(pending: dict, gkey, key, data) -> bool:
        """Whether what is pending flushes before an op of ``key``
        joins group ``gkey``: the PG has ops pending under the other
        kind of group (full write / overwrite; the groups of one flush
        launch one after the other, so the PG's ops would ship out of
        submission order), or the op would take an overwrite group
        past one bucket (``ec_util.OVERWRITE_BUCKET`` a shard)."""
        other = pending.get(gkey[:2] + (not gkey[2],))
        if other is not None and any(it[0] == key for it in other[3]):
            return True
        group = pending.get(gkey)
        if not gkey[2] or group is None:
            return False
        sinfo, items = group[1], group[3]
        held = sum(it[1].nbytes for it in items) + data.nbytes
        return (held // sinfo.stripe_width * sinfo.chunk_size
                > ec_util.OVERWRITE_BUCKET)

    def _flush(self, pending: dict) -> None:
        for gkey, (codec, sinfo, pslot, items) in pending.items():
            # profiler join: while the engine thread stages/launches,
            # a sample of it belongs to the op's engine_stage_wait
            # interval. On a profiler trace the group's host work is
            # ``flush_build``; the window wait and the launch itself
            # nest inside it under their own names
            mark = _prof.push_stage(
                "engine_stage_wait", span="flush_build",
                ops=len(items),
                bytes=sum(it[1].nbytes for it in items))
            try:
                self._flush_group(gkey, codec, sinfo, pslot, items)
            finally:
                _prof.pop_stage(mark)
        pending.clear()

    def _flush_group(self, gkey, codec, sinfo, pslot, items) -> None:
        import time as _time
        from ceph_tpu.parallel import mesh as mesh_mod
        from ceph_tpu.parallel import placement as _placement
        if self._stager is not None:
            # zero-copy staging: the payloads are already
            # contiguous in the key's concat buffer — detach the
            # consumed prefix as one view (no flush-time
            # np.concatenate on this thread)
            batch, views = self._stager.take(gkey, len(items))
            nbytes = batch.nbytes
        else:
            batch = None
            views = [d for _k, d, _c, _s, _cl, _t in items]
            nbytes = sum(d.nbytes for d in views)
        _telemetry().note_slot_staged(pslot, -nbytes)
        # a configured default mesh takes the flush through the
        # multi-chip encode step (pod deployments; dryrun/tests)
        # — but only once the batch is big enough to amortize the
        # collective/placement overhead; small flushes stay on
        # the single-chip kernel (the dense-vs-sharded threshold,
        # BASELINE.md "Pipelined engine")
        # an overwrite flush (range overwrites' stripe windows) takes
        # neither the mesh nor the host route: one device program
        overwrite = gkey[2]
        mesh = mesh_mod.get_default_mesh()
        if overwrite or (mesh is not None
                         and nbytes < self._mesh_flush_bytes):
            mesh = None
        placed = False
        if mesh is not None:
            # PG placement (ISSUE 12): this slot's flush launches
            # on its owning stripe row — a (1, shard) submesh —
            # so flushes of different slots occupy DISJOINT chips
            # and genuinely overlap inside the in-flight window
            pmap = _placement.active_map()
            if pmap is not None and pmap.n_slots > 1:
                mesh = pmap.submesh(pslot)
                placed = True
        # SMALL flushes route to the HOST matvec (bulk ingest):
        # below host_flush_bytes the fixed device dispatch cost
        # (jit call + transfer round trip, ~5 ms measured on the
        # CPU quick run) dwarfs the host encode (~0.4 ms at
        # 64 KiB) — the same measured-crossover policy shape as
        # the mesh threshold above it and the sparse-vs-dense
        # calibration below it. The encode runs at finalize time
        # on the RETIRE thread, riding the same FIFO as device
        # batches, so ordering is identical.
        host = (self._bulk and mesh is None and not overwrite
                and nbytes < self._host_flush_bytes
                and ec_util.host_flushable(codec))
        if batch is not None:
            _telemetry().note_staging_copies_avoided(nbytes)
        if not host and not overwrite:
            batcher = ec_util.StripeBatcher(
                sinfo, codec, mesh=mesh,
                on_fallback=self._note_fused_fallback)
            for i, buf in enumerate(views):
                batcher.append(i, buf)
            if batch is not None:
                batcher.set_preconcat(batch)
        if mesh is not None:
            self.stats["mesh_flushes"] += 1
            _telemetry().note_mesh_flush("encode")
            if placed:
                self.stats["placement_flushes"] += 1
                per_slot = self.stats["per_slot_flushes"]
                per_slot[pslot] = per_slot.get(pslot, 0) + 1
                _telemetry().note_placement_flush()
        # window backpressure BEFORE the launch: with window=1
        # batch N+1 launches only after N fully retired (the old
        # serial engine); deeper windows overlap N+1's staging/
        # upload with N's compute and N-1's download
        mark = _prof.push_stage(
            "engine_stage_wait", span="flush_window_wait",
            ops=len(items), bytes=nbytes)
        try:
            self._wait_window()
        finally:
            _prof.pop_stage(mark)
        try:
            # chaos-harness seam (utils/faults engine_launch
            # rules): an injected launch failure rides the exact
            # failure-drain path a real device fault takes
            _faults.engine_fault("launch")
            if host:
                finalize = ec_util.flush_host_async(
                    sinfo, codec, list(range(len(views))),
                    views, batch=batch)
                self.stats["host_flushes"] += 1
            elif overwrite:
                finalize = ec_util._flush_device_fused_async(
                    sinfo, codec, list(range(len(views))),
                    views, batch=batch, with_crcs=False)
            else:
                finalize = batcher.flush_async(
                    with_crcs=ec_util.fuse_crc_policy(codec))
        except Exception as exc:
            # launch failed: older batches' continuations must
            # still run BEFORE these error continuations (per-PG
            # order) — ride the SAME in-flight FIFO as a poison
            # entry whose "finalize" raises; the retire thread's
            # failure-drain path dispatches the error
            # continuations in exact launch order. Bytes move
            # staged -> in-window here and leave at retirement
            # (fate decided there: host fallback).
            def _poison(exc=exc):
                raise exc
            kspans = [span.child("kernel_dispatch")
                      for _k, _d, _c, span, _cl, _t in items]
            self._park((items, _poison, kspans, nbytes))
            return
        # batch launched (async): park it on the in-flight deque
        # — its compute+download overlaps the NEXT batch's
        # staging/upload; only the window bound forces a harvest
        if _TP_FLUSH.enabled:
            _TP_FLUSH(len(items), nbytes)
        launched = _time.monotonic()
        tel = _telemetry()
        kspans = []
        for _key, _data, _cont, span, clock, ts in items:
            # queue wait = stage -> launch (the batching latency
            # an op paid for its amortization win)
            tel.note_queue_wait("encode", launched - ts)
            clock.mark("engine_stage_wait", t=launched)
            if span is not NOOP:   # no formatting when untraced
                span.event(f"batch_flush ops={len(items)} "
                           f"bytes={nbytes}")
            kspans.append(span.child("kernel_dispatch"))
        entry = (items, finalize, kspans, nbytes)
        if host and not self._inflight and not self._retiring:
            # light-load fast path: nothing in flight, so FIFO
            # order is trivially kept — retire the host flush
            # INLINE instead of paying a retire-thread handoff
            # (one fewer cross-thread wakeup on the op's
            # critical path; the wait chain IS the measured
            # latency). Only the engine thread parks entries, so
            # the emptiness check cannot race.
            tel.note_hbm(staged_delta=-nbytes,
                         inflight_delta=nbytes)
            self._retire_one(entry)
        else:
            self._park(entry)

    def _wait_window(self) -> None:
        """Block until the launch window has a free slot (counting a
        batch mid-harvest): with window=1 this is the old serial
        engine — batch N+1 launches only after N fully retired."""
        with self._ifcv:
            while len(self._inflight) + \
                    (1 if self._retiring else 0) >= self._window:
                self._ifcv.wait()

    def _park(self, entry) -> None:
        """Hand a launched (or poison) batch to the retire thread:
        staged -> in-window on the HBM ledger; the byte count rides
        the entry so retirement reconciles it on both outcomes."""
        nbytes = entry[-1]
        tel = _telemetry()
        tel.note_hbm(staged_delta=-nbytes, inflight_delta=nbytes)
        with self._ifcv:
            self._inflight.append(entry)
            depth = len(self._inflight) + \
                (1 if self._retiring else 0)
            self._ifcv.notify_all()
        self.stats["max_inflight_depth"] = max(
            self.stats["max_inflight_depth"], depth)
        tel.note_inflight_depth(depth)
        tel.note_engine_inflight(depth)

    def _drain_inflight(self) -> None:
        """Wait until the retire thread has harvested EVERY in-flight
        batch (ordering points: barrier, run_sync, stop)."""
        with self._ifcv:
            while self._inflight or self._retiring:
                self._ifcv.wait()

    def _retire_one(self, entry) -> None:
        """Harvest one in-flight batch (download + dispatch its
        continuations). Runs on the retire thread only — it is the
        sole creator of FlushGroups, so the ship queue's order is
        flush order. On a profiler trace the harvest is
        ``flush_dispatch``, with the blocking ``flush_download``
        (marked in ``finalize``) nested inside it."""
        (items, _finalize, _kspans, nbytes) = entry
        mark = _prof.push_stage(
            "device_finalize", span="flush_dispatch",
            ops=len(items), bytes=nbytes)
        try:
            self._retire_batch(entry)
        finally:
            _prof.pop_stage(mark)

    def _retire_batch(self, entry) -> None:
        import time as _time
        harvest_t = _time.monotonic()
        (items, finalize, kspans, nbytes) = entry
        # per-op timeline: launch -> harvest begin is the pipeline-
        # window wait (overlapped with younger batches' staging)
        for _key, _data, _cont, _span, clock, _ts in items:
            clock.mark("device_window_wait", t=harvest_t)
        try:
            results = finalize()
        except Exception as exc:
            log(0, f"device encode batch of {len(items)} ops "
                f"failed: {exc!r}")
            self.stats["errors"] += 1
            entries = []
            for (key, _data, cont, span, _clock, _ts), kspan in \
                    zip(items, kspans):
                kspan.event(f"device_error {exc!r}")
                # the error rides up so the tail sampler keeps the
                # whole trace (the op falls back to the host twin)
                kspan.set_error(f"engine_launch: {exc!r}")
                kspan.finish()
                span.set_error(f"engine_launch: {exc!r}")
                span.finish()
                entries.append((key, _bind(cont, None, None, exc)))
            self._dispatch_entries(entries)
            results = None
        if results is not None:
            done_t = _time.monotonic()
            self.stats["flushes"] += 1
            self.stats["ops"] += len(items)
            if getattr(finalize, "layered", False):
                self.stats["layered_encode_ops"] += len(items)
            overwrite = getattr(finalize, "overwrite", False)
            if overwrite:
                self.stats["overwrite_ops"] += len(items)
                self.stats["overwrite_flushes"] += 1
            if _spans_keys(items):
                self.stats["cross_pg_ops"] += len(items)
            self.stats["bytes"] += nbytes
            ft = _flows.flows_if_active()
            if ft is not None:
                # each flow's byte share of THIS retired flush is its
                # occupancy slice of the device round (ISSUE 20)
                shares: dict = {}
                for key, data, cont, *_rest in items:
                    fl = getattr(cont, "_flow", "")
                    if fl:
                        shares[fl] = shares.get(fl, 0) + \
                            getattr(data, "nbytes", 0)
                if shares:
                    try:
                        ft.note_flush_group(shares)
                    except Exception:
                        pass
            self.stats["max_batch_ops"] = max(
                self.stats["max_batch_ops"], len(items))
            if self._counters is not None:
                self._counters.inc("device_batches")
                self._counters.inc("device_batch_ops", len(items))
            entries = []
            for (key, _data, cont, span, clock, _ts), \
                    (_i, shards, crcs), kspan in zip(items, results,
                                                     kspans):
                if crcs is not None:
                    kspan.event("crc_pass")
                kspan.finish()
                span.finish()
                clock.mark("device_finalize", t=done_t)
                entries.append((key, _bind(cont, shards, crcs, None)))
            # ONE wrapper per distinct key instead of one callable
            # per op: the flush's continuations share a FlushGroup
            # whose last member ships the per-peer sub-write batches
            # and the merged local txn groups; an overwrite flush's
            # group may ship with the next ones
            self._dispatch_entries(entries, overwrite=overwrite)
            _telemetry().note_encode_flush(
                len(items), nbytes,
                trace_id=_first_trace_id(items, span_idx=3))
        tel = _telemetry()
        tel.note_engine_retired()
        tel.note_engine_inflight(len(self._inflight))
        # the batch's bytes leave the window on BOTH outcomes
        # (download or failover) — the gauges-to-zero invariant
        tel.note_hbm(inflight_delta=-nbytes, retired=nbytes)

    def _note_fused_fallback(self, path: str, exc: Exception) -> None:
        """A mesh/fused flush path failed and the batch re-ran on the
        plain path: count it (asok 'status' surfaces the stats dict),
        so a persistent regression is visible instead of silently
        degrading every flush to host hashing (r2 verdict weak #3)."""
        self.stats["device_fused_fallbacks"] += 1
        _telemetry().note_fused_fallback()
        if self._counters is not None:
            self._counters.inc("device_fused_fallbacks")

    def _flush_decodes(self, dec_pending: dict) -> None:
        """One device matmul per erasure signature: every queued op of
        a signature shares the decode matrix (the LRU the codec keeps,
        keyed exactly like the ISA decode-table cache), so their shard
        streams concatenate along the byte axis into a single launch.
        Continuations run inline (see stage_decode)."""
        for (_cid, present, want, pslot), \
                (codec, sinfo, _slot, items) in dec_pending.items():
            self._flush_decode_group(present, want, pslot, codec,
                                     sinfo, items)
        dec_pending.clear()

    def _flush_decode_group(self, present, want, pslot, codec, sinfo,
                            items) -> None:
        """One signature's decode flush. To the sampling profiler all
        of it is ``device_finalize``; a profiler trace sees three
        states in turn: ``decode_build`` (the survivors' concatenate),
        ``decode_run`` (upload, program and download, synchronous on
        this thread) and ``decode_dispatch`` (the continuations); a
        layered codec's flush has ``signature_build`` between the
        first two: getting the signature's table, a cache lookup, and
        the build when no primary built it at peering (counted)."""
        import time as _time
        from ceph_tpu.parallel import mesh as mesh_mod
        from ceph_tpu.parallel import placement as _placement
        staged = sum(_shards_nbytes(shards)
                     for _k, shards, _w, _c, _s, _cl, _t in items)
        batch = {"ops": len(items), "bytes": staged}
        mark = _prof.push_stage("device_finalize",
                                span="decode_build", **batch)
        try:
            launched = _time.monotonic()
            tel = _telemetry()
            # staged bytes leave the ledger here: whatever happens
            # below (decode or fault), this group's buffers are done
            tel.note_hbm(staged_delta=-staged, retired=staged)
            tel.note_slot_staged(pslot, -staged)
            for _key, _shards, _want, _cont, span, clock, ts in items:
                tel.note_queue_wait("decode", launched - ts)
                clock.mark("engine_stage_wait", t=launched)
                if span is not NOOP:   # no formatting when untraced
                    span.event(f"decode_flush ops={len(items)} "
                               f"sig={list(present)}->{list(want)}")
            try:
                # chaos-harness seam: injected decode-flush failure ->
                # every op in the group falls back to its host twin
                _faults.engine_fault("decode")
                merged = {
                    c: np.concatenate(
                        [np.asarray(shards[c], dtype=np.uint8)
                         for _k, shards, _w, _c, _s, _cl, _t in items])
                    for c in present}
                lens = [len(np.asarray(shards[present[0]]))
                        for _k, shards, _w, _c, _s, _cl, _t in items]
                _prof.pop_stage(mark)
                layered = ec_util.device_layered(codec)
                table = None
                if layered:
                    mark = _prof.push_stage(
                        "device_finalize", span="signature_build",
                        **batch)
                    table, built = ec_util.signature_table(
                        codec, *ec_util.decode_signature(
                            codec, merged, want))
                    if built:
                        self.stats["signature_builds"] += 1
                    _prof.pop_stage(mark)
                mark = _prof.push_stage("device_finalize",
                                        span="decode_run", **batch)
                # multi-chip decode (ISSUE 12): a big-enough
                # signature batch rides the mesh twin of the decode
                # matmul on this PG slot's submesh — the same
                # dense->mesh crossover as encode; any mesh fault
                # falls back to the single-chip/host route below
                out = None
                mesh = mesh_mod.get_default_mesh()
                if mesh is not None and not layered and \
                        staged >= self._mesh_flush_bytes and \
                        ec_util.device_decodable(codec):
                    placed = False
                    pmap = _placement.active_map()
                    if pmap is not None and pmap.n_slots > 1:
                        mesh = pmap.submesh(pslot)
                        placed = True
                    try:
                        out = ec_util.flush_decode_mesh(
                            mesh, sinfo, codec, merged, list(want))
                        self.stats["mesh_decode_flushes"] += 1
                        tel.note_mesh_flush("decode")
                        if placed:
                            self.stats["placement_flushes"] += 1
                            tel.note_placement_flush()
                    except Exception as exc:
                        self._note_fused_fallback("mesh_decode", exc)
                if layered:
                    out = ec_util.decode_layered(
                        sinfo, codec, merged, list(want), table=table)
                elif out is None:
                    out = ec_util.decode(sinfo, codec, merged,
                                         list(want))
            except Exception as exc:
                log(0, f"device decode batch of {len(items)} ops "
                    f"(sig {present}->{want}) failed: {exc!r}")
                self.stats["decode_errors"] += 1
                for (_key, _shards, _want, cont, span, _clock,
                     _ts) in items:
                    span.event(f"device_error {exc!r}")
                    # a failed flush is a keep-worthy outcome: the
                    # tail sampler retains the op's trace (error rule)
                    span.set_error(f"engine_decode: {exc!r}")
                    span.finish()
                    cont(None, exc)
                return
            _prof.pop_stage(mark)
            mark = _prof.push_stage("device_finalize",
                                    span="decode_dispatch", **batch)
            if _TP_DECODE_FLUSH.enabled:
                _TP_DECODE_FLUSH(len(items), str(present))
            nbytes = sum(ln * len(present) for ln in lens)
            self.stats["decode_flushes"] += 1
            self.stats["decode_ops"] += len(items)
            if layered:
                self.stats["layered_decode_ops"] += len(items)
            if _spans_keys(items):
                self.stats["decode_cross_pg_ops"] += len(items)
            self.stats["decode_bytes"] += nbytes
            self.stats["max_decode_batch_ops"] = max(
                self.stats["max_decode_batch_ops"], len(items))
            if self._counters is not None:
                self._counters.inc("device_decode_batches")
                self._counters.inc("device_decode_ops", len(items))
            tel.note_decode_flush(
                len(items), nbytes,
                trace_id=_first_trace_id(items, span_idx=4))
            done_t = _time.monotonic()
            off = 0
            for (_key, _shards, _want, cont, span, clock, _ts), ln \
                    in zip(items, lens):
                span.event("decode_done")
                span.finish()
                clock.mark("device_finalize", t=done_t)
                cont({c: v[off:off + ln] for c, v in out.items()},
                     None)
                off += ln
        finally:
            _prof.pop_stage(mark)


def _first_trace_id(items, span_idx: int) -> str | None:
    """First traced op's trace_id in a flush batch — the histogram
    exemplar candidate (NOOP spans carry an empty trace_id)."""
    for it in items:
        tid = getattr(it[span_idx], "trace_id", "")
        if tid:
            return tid
    return None


def _spans_keys(items) -> bool:
    """Whether a flush's items (dispatch key first) are of more than
    one dispatch key, i.e. of more than one PG."""
    first = items[0][0]
    return any(it[0] != first for it in items)


def _shards_nbytes(shards: dict) -> int:
    """Byte count of one staged decode's survivor map — the SAME
    expression on the staging and retiring side, so the HBM ledger
    reconciles exactly."""
    return sum(np.asarray(v).nbytes for v in shards.values())


class AttachedKey(tuple):
    """(attach token, key): routes a shared-engine continuation to
    the attaching OSD's dispatcher while hashing like the wrapped key
    for per-PG FIFO placement. A plain tuple subclass so it stays
    hashable and cheap."""
    __slots__ = ()


class EngineHandle:
    """One OSD's view of the process-wide shared engine: the same
    surface as a private DeviceEncodeEngine (stage_*, decode_sync,
    run_sync, stats, stop), with every key wrapped in this
    attachment's token so continuations land on the owner OSD's op
    queue. ``stop`` detaches; the engine itself stops when the last
    attachment leaves."""

    def __init__(self, engine: DeviceEncodeEngine, token: int) -> None:
        self.engine = engine
        self._token = token
        self._detached = False

    @property
    def stats(self) -> dict:
        return self.engine.stats

    def _key(self, key) -> AttachedKey:
        return AttachedKey((self._token, key))

    def stage_encode(self, key, *a, **kw) -> None:
        self.engine.stage_encode(self._key(key), *a, **kw)

    def stage_barrier(self, key, fn) -> None:
        self.engine.stage_barrier(self._key(key), fn)

    def stage_decode(self, key, *a, **kw) -> None:
        self.engine.stage_decode(self._key(key), *a, **kw)

    def decode_sync(self, key, *a, **kw):
        return self.engine.decode_sync(self._key(key), *a, **kw)

    def run_sync(self, fn, timeout: float = 120.0):
        return self.engine.run_sync(fn, timeout)

    def stop(self) -> None:
        """Detach this OSD: drain everything staged so far (its
        continuations are dispatched before the dispatcher goes), then
        stop the engine if this was the last attachment."""
        if self._detached:
            return
        self._detached = True
        try:
            # a run_sync flushes all pending work and drains the
            # in-flight window on the engine thread
            self.engine.run_sync(lambda: None, timeout=30)
        except Exception:
            pass
        _detach(self.engine, self._token)


_shared_lock = make_lock("engine.shared_service")
_shared_engine: DeviceEncodeEngine | None = None
_attach_seq = 0


def shared_engine_attach(dispatch, flush_bytes: int | None = None
                         ) -> EngineHandle:
    """Attach one OSD to the process-wide shared engine (the ISSUE-9
    shared engine service): co-located OSDs feed ONE device pipeline,
    so cross-OSD flushes aggregate into bigger batches and the mesh
    threshold fires more often. Creates the engine on first attach,
    restarts it if a previous generation fully detached."""
    global _shared_engine, _attach_seq
    with _shared_lock:
        eng = _shared_engine
        if eng is None or not eng._running:
            eng = _shared_engine = DeviceEncodeEngine(
                None, flush_bytes=flush_bytes)
        _attach_seq += 1
        token = _attach_seq
        eng.register_dispatcher(token, dispatch)
        return EngineHandle(eng, token)


def _detach(engine: DeviceEncodeEngine, token: int) -> None:
    global _shared_engine
    stop = False
    with _shared_lock:
        engine.unregister_dispatcher(token)
        if not engine._dispatchers:
            stop = True
            if _shared_engine is engine:
                _shared_engine = None
    if stop:
        engine.stop()


def _bind(cont, shards, crcs, err):
    # re-install the flow label stamped at stage time: the retire
    # thread has no tenant context of its own, and the continuation's
    # fan-out captures current_flow() when it defers sub-writes into
    # the flush group
    flow = getattr(cont, "_flow", "")

    def fn():
        with _flows.flow_scope(flow or None):
            cont(shards, crcs, err)

    # the continuation builds hinfo/shard txns and fans sub-writes out
    # — commit_wait work; the op-wq worker running it picks the tag up
    # for the profiler's stage join
    fn._profile_stage = "commit_wait"
    return fn
