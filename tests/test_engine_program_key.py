"""The key under which staged ops meet in an engine flush
(``device_engine.program_key``): what the flush program and its
results depend on, not the codec OBJECT. Every PG's ECBackend builds
its own codec from the pool's profile, so ops of different PGs share a
flush exactly when their codecs and stripe geometry are equal.

Counts and order only, on the CPU (``backend=jax``, the plain flush
path): the launch thread is held inside a ``run_sync`` while ops are
staged, so what leaves when it is released is decided by the key alone
and not by timing.
"""

import queue
import threading

import numpy as np
import pytest

from ceph_tpu.models import registry as ec_registry
from ceph_tpu.models.isa import ErasureCodeIsa
from ceph_tpu.osd import device_engine, ec_util
from ceph_tpu.osd.device_engine import (DeviceEncodeEngine,
                                        _ConcatStager, program_key)
from ceph_tpu.osd.ec_util import StripeInfo

CS = 1024                       # stripe unit of the tests


@pytest.fixture(autouse=True)
def _pin_device_route(monkeypatch):
    """Keep the tiny test flushes off the small-flush host route."""
    monkeypatch.setenv("CEPH_TPU_HOST_FLUSH_BYTES", "0")


def _codec(k=2, m=1, backend="jax", plugin="jerasure", mapping=None):
    codec = ec_registry.instance().factory(
        plugin, {"plugin": plugin, "k": str(k), "m": str(m),
                 "backend": backend})
    if mapping is not None:
        codec.chunk_mapping = list(mapping)
    return codec


def _sinfo(codec, cs=CS):
    return StripeInfo(stripe_width=codec.get_data_chunk_count() * cs,
                      chunk_size=cs)


def _payload(codec, cs, seed, stripes=2):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, codec.get_data_chunk_count() * cs
                        * stripes, dtype=np.uint8)


class _Held:
    """Hold the engine's launch thread inside a run_sync until
    ``release``: everything staged meanwhile is picked up in one go."""

    def __init__(self, eng) -> None:
        self._entered = threading.Event()
        self._release = threading.Event()
        self._thread = threading.Thread(
            target=lambda: eng.run_sync(self._fn), daemon=True)
        self._thread.start()
        assert self._entered.wait(30)

    def _fn(self):
        self._entered.set()
        assert self._release.wait(60)

    def release(self) -> None:
        self._release.set()
        self._thread.join(30)


class _KeyedExecutor:
    """A per-key FIFO executor like the OSD's sharded op queue: a key
    always lands on the same worker, workers run concurrently."""

    def __init__(self, n=2) -> None:
        self._qs = [queue.SimpleQueue() for _ in range(n)]
        self._threads = [threading.Thread(target=self._run, args=(q,),
                                          daemon=True)
                         for q in self._qs]
        for t in self._threads:
            t.start()

    def _run(self, q) -> None:
        while True:
            fn = q.get()
            if fn is None:
                return
            fn()

    def dispatch(self, key, fn) -> None:
        self._qs[hash(key) % len(self._qs)].put(fn)

    def stop(self) -> None:
        for q in self._qs:
            q.put(None)
        for t in self._threads:
            t.join(10)


def _encode_held(ops, flush_bytes=64 << 20, dispatch=None):
    """Stage ``ops`` = [(dispatch key, codec, sinfo, payload)] while
    the launch thread is held; returns (results in completion order
    [(index, shards, err)], stats)."""
    results: list = []
    done = threading.Event()
    lock = threading.Lock()
    eng = DeviceEncodeEngine(dispatch or (lambda k, f: f()),
                             flush_bytes=flush_bytes, window=3)
    try:
        held = _Held(eng)
        for i, (key, codec, sinfo, data) in enumerate(ops):
            def cont(shards, crcs, err, i=i):
                with lock:
                    results.append((i, shards, err))
                    if len(results) == len(ops):
                        done.set()
            eng.stage_encode(key, codec, sinfo, data, cont)
        held.release()
        assert done.wait(60), [r[0] for r in results]
    finally:
        eng.stop()
    return results, dict(eng.stats)


def _assert_shards(ops, results) -> None:
    """Every op got the shards of ITS payload (host twin's encode)."""
    for i, shards, err in results:
        _key, codec, sinfo, data = ops[i]
        assert err is None, err
        k = codec.get_data_chunk_count()
        host = _codec(k=k, m=codec.get_chunk_count() - k,
                      backend="numpy",
                      plugin="isa" if isinstance(codec, ErasureCodeIsa)
                      else "jerasure",
                      mapping=codec.chunk_mapping or None)
        want = ec_util.encode(sinfo, host, data)
        assert sorted(shards) == sorted(want)
        for c in want:
            assert np.array_equal(np.asarray(shards[c]), want[c]), \
                (i, c)


# -- the key ----------------------------------------------------------

@pytest.mark.parametrize("profile", [
    dict(k=2, m=1), dict(k=8, m=3), dict(k=4, m=2),
    dict(k=4, m=2, backend="numpy"),
    dict(k=4, m=2, plugin="isa"),
    dict(k=2, m=1, mapping=[1, 0, 2]),
], ids=["k2m1", "k8m3", "k4m2", "k4m2_numpy", "k4m2_isa",
        "k2m1_mapped"])
def test_codecs_of_one_profile_share_a_key(profile):
    a, b = _codec(**profile), _codec(**profile)
    assert a is not b
    ka, kb = program_key(a, _sinfo(a)), program_key(b, _sinfo(b))
    assert ka == kb and hash(ka) == hash(kb)
    # the codec's part is computed once per object and kept on it
    assert program_key(a, _sinfo(a))[0] is ka[0]
    assert a._engine_program_key[0] is a.coding_matrix


def _differing(what):
    """Two (codec, stripe unit) whose keys must differ in ``what``."""
    if what == "matrix":
        return (_codec(8, 3), CS), (_codec(4, 2), CS)
    if what == "stripe_unit":
        return (_codec(4, 2), CS), (_codec(4, 2), 2 * CS)
    if what == "backend":
        return (_codec(4, 2), CS), (_codec(4, 2, backend="numpy"), CS)
    if what == "chunk_mapping":
        return (_codec(2, 1), CS), \
            (_codec(2, 1, mapping=[1, 0, 2]), CS)
    if what == "plugin":
        return (_codec(4, 2), CS), (_codec(4, 2, plugin="isa"), CS)
    raise AssertionError(what)


@pytest.mark.parametrize("what", ["matrix", "stripe_unit", "backend",
                                  "chunk_mapping", "plugin"])
def test_codecs_that_differ_never_share_a_flush(what):
    (ca, csa), (cb, csb) = _differing(what)
    sa, sb = _sinfo(ca, csa), _sinfo(cb, csb)
    assert program_key(ca, sa) != program_key(cb, sb)
    # two pools on one engine, ops interleaved
    ops = [("pgA", ca, sa, _payload(ca, csa, 1)),
           ("pgB", cb, sb, _payload(cb, csb, 2)),
           ("pgA", ca, sa, _payload(ca, csa, 3)),
           ("pgB", cb, sb, _payload(cb, csb, 4))]
    results, stats = _encode_held(ops)
    assert stats["flushes"] == 2 and stats["ops"] == 4, stats
    assert stats["cross_pg_ops"] == 0, stats
    assert stats["errors"] == 0
    _assert_shards(ops, results)


def test_a_codec_without_a_plain_matrix_keeps_a_key_of_its_own():
    class Layered:
        coding_matrix = None
    a, b = Layered(), Layered()
    s = StripeInfo(stripe_width=2 * CS, chunk_size=CS)
    assert program_key(a, s) != program_key(b, s)
    assert program_key(a, s) == program_key(a, s)


# -- one flush for ops of several PGs ---------------------------------

@pytest.mark.parametrize("bulk", ["1", "0"], ids=["stager", "no_stager"])
@pytest.mark.parametrize("n_pgs", [1, 2, 3, 8])
def test_ops_of_different_pgs_leave_as_one_flush(monkeypatch, n_pgs,
                                                 bulk):
    monkeypatch.setenv("CEPH_TPU_BULK_INGEST", bulk)
    ops = []
    for i in range(max(n_pgs, 2)):
        codec = _codec(4, 2)            # its own object, as a PG has
        ops.append((f"pg{i % n_pgs}", codec, _sinfo(codec),
                    _payload(codec, CS, 10 + i, stripes=1 + i % 3)))
    results, stats = _encode_held(ops)
    assert stats["flushes"] == 1, stats
    assert stats["ops"] == stats["max_batch_ops"] == len(ops)
    assert stats["cross_pg_ops"] == (len(ops) if n_pgs > 1 else 0)
    assert stats["errors"] == stats["device_fused_fallbacks"] == 0
    _assert_shards(ops, results)


# -- per-PG order -----------------------------------------------------

@pytest.mark.parametrize("ops_per_flush", [None, 2, 1],
                         ids=["one_shared_flush", "two_op_flushes",
                              "one_op_flushes"])
def test_continuations_of_one_key_run_in_staging_order(ops_per_flush):
    pgs = ["pgA", "pgB", "pgA", "pgC", "pgB", "pgA", "pgA", "pgC"]
    ops = []
    for i, pg in enumerate(pgs):
        codec = _codec(2, 1)
        ops.append((pg, codec, _sinfo(codec),
                    _payload(codec, CS, 20 + i, stripes=1)))
    op_bytes = 2 * CS
    flush_bytes = 64 << 20 if ops_per_flush is None \
        else ops_per_flush * op_bytes
    pool = _KeyedExecutor(2)
    try:
        results, stats = _encode_held(ops, flush_bytes=flush_bytes,
                                      dispatch=pool.dispatch)
    finally:
        pool.stop()
    want_flushes = 1 if ops_per_flush is None \
        else len(ops) // ops_per_flush
    assert stats["flushes"] == want_flushes, stats
    assert stats["ops"] == len(ops)
    for pg in set(pgs):
        ran = [i for i, _s, _e in results if pgs[i] == pg]
        assert ran == sorted(ran), (pg, ran)
    # the cut leaves a tail of several PGs in the stager; its queued
    # refs still name the right bytes after the relocation
    _assert_shards(ops, results)


# -- the stager -------------------------------------------------------

@pytest.mark.parametrize("first", [0, 1, 2, 4, 5])
def test_stager_tail_of_several_pgs_survives_a_take(first):
    stager = _ConcatStager()
    gkey = (("prog",), 0)
    other = (("other prog",), 0)
    rng = np.random.default_rng(7)
    # five ops of different PGs and sizes under ONE program key, and
    # a bystander under another; 300 KiB crosses the buffer's growth
    sizes = [4096, 300 << 10, 8192, 4096, 12288]
    datas = [rng.integers(0, 256, n, dtype=np.uint8) for n in sizes]
    bystander = rng.integers(0, 256, 4096, dtype=np.uint8)
    with stager.lock:
        for d in datas[:3]:
            stager.append_locked(gkey, d)
        stager.append_locked(other, bystander)
        for d in datas[3:]:
            stager.append_locked(gkey, d)
    batch, views = stager.take(gkey, first)
    assert len(views) == first
    assert batch.nbytes == sum(sizes[:first])
    for v, d in zip(views, datas):
        assert np.array_equal(v, d)
    assert np.array_equal(
        batch, np.concatenate(datas[:first]) if first
        else np.empty(0, np.uint8))
    assert stager.stats["relocated_bytes"] == sum(sizes[first:])
    # an op staged after the cut lands behind the relocated tail
    late = rng.integers(0, 256, 4096, dtype=np.uint8)
    with stager.lock:
        stager.append_locked(gkey, late)
    rest = datas[first:] + [late]
    batch, views = stager.take(gkey, len(rest))
    assert [v.nbytes for v in views] == [d.nbytes for d in rest]
    for v, d in zip(views, rest):
        assert np.array_equal(v, d)
    assert np.array_equal(batch, np.concatenate(rest))
    _b, views = stager.take(other, 1)
    assert np.array_equal(views[0], bystander)


# -- decodes ----------------------------------------------------------

def _decode_case(case):
    """Two staged reconstructs: (keyA, codecA, lost A, want A),
    (keyB, ...), flushes expected."""
    a, b = _codec(4, 2), _codec(4, 2)
    if case == "equal_signature":
        return ("pgA", a, [1], [1]), ("pgB", b, [1], [1]), 1
    if case == "one_pg":
        return ("pgA", a, [1], [1]), ("pgA", b, [1], [1]), 1
    if case == "other_present":
        return ("pgA", a, [1], [1]), ("pgB", b, [2], [1]), 2
    if case == "other_want":
        return ("pgA", a, [0, 1], [0]), ("pgB", b, [0, 1], [1]), 2
    if case == "other_slot":
        return ("pgA", a, [1], [1]), ("slot1", b, [1], [1]), 2
    if case == "other_profile":
        return ("pgA", a, [1], [1]), ("pgB", _codec(2, 1), [1], [1]), 2
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "equal_signature", "one_pg", "other_present", "other_want",
    "other_slot", "other_profile"])
def test_decodes_share_a_flush_only_with_equal_signature(monkeypatch,
                                                         case):
    monkeypatch.setattr(device_engine, "_placement_slot",
                        lambda key: 1 if key == "slot1" else 0)
    *reqs, want_flushes = _decode_case(case)
    staged = []
    for n, (key, codec, lost, want) in enumerate(reqs):
        sinfo = _sinfo(codec)
        data = _payload(codec, CS, 30 + n)
        k = codec.get_data_chunk_count()
        host = _codec(k, codec.get_chunk_count() - k, backend="numpy")
        full = ec_util.encode(sinfo, host, data)
        have = {c: v for c, v in full.items() if c not in lost}
        staged.append((key, codec, sinfo, have, want, full))
    out: dict = {}
    done = threading.Event()
    eng = DeviceEncodeEngine(lambda k, f: f(), window=3)
    try:
        held = _Held(eng)
        for n, (key, codec, sinfo, have, want, _f) in enumerate(staged):
            def cont(decoded, err, n=n):
                out[n] = (decoded, err)
                if len(out) == len(staged):
                    done.set()
            eng.stage_decode(key, codec, sinfo, have, want, cont)
        held.release()
        assert done.wait(60), out
    finally:
        eng.stop()
    stats = eng.stats
    assert stats["decode_flushes"] == want_flushes, stats
    assert stats["decode_ops"] == 2 and stats["decode_errors"] == 0
    assert stats["decode_cross_pg_ops"] == \
        (2 if case == "equal_signature" else 0)
    for n, (_k, _c, _s, _h, want, full) in enumerate(staged):
        decoded, err = out[n]
        assert err is None, err
        for c in want:
            assert np.array_equal(np.asarray(decoded[c]), full[c])
