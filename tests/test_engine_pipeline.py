"""The round-9 pipelined device engine: a bounded window of launched
encode batches stays in flight (upload N+1 while N computes and N-1
downloads), retirement is strictly FIFO, and every ordering point
(barrier, decode_sync, stop) drains the window — so the pre-pipeline
per-PG commit order is observed EXACTLY, just faster.

The device here is a fake fused-flush path whose ``finalize`` blocks
until ``launch + DEVICE_S`` — the engine's overlap structure is what
is under test, not the kernel.
"""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.models import registry as ec_registry
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.device_engine import DeviceEncodeEngine
from ceph_tpu.osd.ec_util import StripeInfo


@pytest.fixture(autouse=True)
def _pin_device_route(monkeypatch):
    """These tests pin the DEVICE launch pipeline (fused-flush
    fakes); keep the tiny test flushes off the bulk-ingest
    small-flush host route."""
    monkeypatch.setenv("CEPH_TPU_HOST_FLUSH_BYTES", "0")


def _codec(backend="jax", k=2, m=1):
    return ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": str(k), "m": str(m),
                     "backend": backend})


#: seconds the fake device "computes" per batch
DEVICE_S = 0.1


def _fake_device(monkeypatch, launches: list):
    """Replace the fused flush with a device that computes every batch
    in DEVICE_S, concurrently (finalize blocks until its own launch
    deadline) — overlap shows up as wall clock, serial as 8x."""

    real_encode = ec_util.encode    # survives later encode poisoning

    def fake_async(sinfo, codec, ops, bufs, batch=None):
        t_launch = time.perf_counter()
        launches.append(t_launch)
        host = _codec(backend="numpy",
                      k=codec.get_data_chunk_count(),
                      m=codec.get_chunk_count()
                      - codec.get_data_chunk_count())
        cs, sw = sinfo.chunk_size, sinfo.stripe_width

        def finalize():
            wait = t_launch + DEVICE_S - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            shards = real_encode(sinfo, host, np.concatenate(bufs))
            out = []
            off = 0
            for op_id, buf in zip(ops, bufs):
                nchunk = len(buf) // sw * cs
                out.append((op_id,
                            {i: v[off:off + nchunk]
                             for i, v in shards.items()}, None))
                off += nchunk
            return out

        return finalize

    monkeypatch.setenv("CEPH_TPU_FUSE_CRC", "1")
    monkeypatch.setattr(ec_util, "_flush_device_fused_async",
                        fake_async)


def _burst(window: int, monkeypatch, n_ops: int = 8):
    """Stage ``n_ops`` single-op flushes; returns (wall_s, order,
    stats)."""
    launches: list = []
    _fake_device(monkeypatch, launches)
    codec = _codec()
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    data = np.zeros(2048, dtype=np.uint8)
    done: list = []
    all_done = threading.Event()
    # flush_bytes == payload: every op flushes (and launches) alone
    eng = DeviceEncodeEngine(lambda k, f: f(), flush_bytes=2048,
                             window=window)
    try:
        t0 = time.perf_counter()
        for i in range(n_ops):
            def cont(i=i):
                def fn(shards, crcs, err):
                    assert err is None, err
                    done.append(i)
                    if len(done) == n_ops:
                        all_done.set()
                return fn
            eng.stage_encode("pgA", codec, sinfo, data, cont())
        assert all_done.wait(30), done
        wall = time.perf_counter() - t0
    finally:
        eng.stop()
    return wall, done, dict(eng.stats)


def test_pipelined_burst_overlaps_and_beats_serial(monkeypatch):
    """The acceptance gate: an 8-flush burst through the pipelined
    engine reports in-flight depth >= 2 and strictly lower wall clock
    than the same burst with window=1 (the serial engine).

    ISSUE 13 de-flake: the depth/overlap assertions are the core
    overlap proof (sleep-based fake device, core-count independent);
    the wall-clock bar stays DIRECTIONAL everywhere, but on a <= 2
    core box a single scheduler preemption inside the ~0.33 s piped
    window can eat the 0.8 s margin, so the paired measurement gets
    one retry there before failing (a genuinely serial pipeline
    fails both attempts at ~1.0x)."""
    import os
    from ceph_tpu.utils.device_telemetry import telemetry
    telemetry().reset()
    attempts = 1 if len(os.sched_getaffinity(0)) > 2 else 2
    for attempt in range(attempts):
        wall_serial, order_serial, stats_serial = \
            _burst(1, monkeypatch)
        wall_piped, order_piped, stats_piped = _burst(3, monkeypatch)
        # continuation order is submission order under BOTH windows
        assert order_serial == list(range(8))
        assert order_piped == list(range(8))
        # the window filled: batches genuinely overlapped
        assert stats_piped["max_inflight_depth"] >= 2, stats_piped
        assert stats_serial["max_inflight_depth"] == 1, stats_serial
        assert stats_piped["flushes"] == 8 and \
            stats_serial["flushes"] == 8
        # serial pays ~8x DEVICE_S; the pipeline hides most of it
        if wall_piped < wall_serial:
            break
        if attempt == attempts - 1:
            raise AssertionError(
                f"pipelined burst never beat serial: "
                f"{wall_piped:.3f}s vs {wall_serial:.3f}s")
    # telemetry saw the depth histogram (histograms dump as
    # pow2-bucket lists; bucket b holds [2^(b-1), 2^b), so depth >= 2
    # lands in buckets[2:]) and every batch's retirement
    counters = telemetry().snapshot()["counters"]
    depth_hist = counters["engine_inflight_depth"]
    assert sum(depth_hist[2:]) > 0, depth_hist
    assert counters["engine_retired"] >= 8


def test_barrier_sees_all_prior_flushes_retired(monkeypatch):
    """stage_encode x N interleaved with stage_barrier under the
    in-flight window observes exactly the pre-pipeline ordering: a
    barrier's fn runs only after every previously staged op's
    continuation, on the same key."""
    launches: list = []
    _fake_device(monkeypatch, launches)
    codec = _codec()
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    data = np.zeros(2048, dtype=np.uint8)
    order: list = []
    done = threading.Event()
    eng = DeviceEncodeEngine(lambda k, f: f(), flush_bytes=2048,
                             window=3)
    try:
        for i in range(1, 4):
            eng.stage_encode(
                "A", codec, sinfo, data,
                lambda s, c, e, i=i: order.append(f"e{i}"))
        eng.stage_barrier("A", lambda: order.append("b1"))
        eng.stage_encode("A", codec, sinfo, data,
                         lambda s, c, e: order.append("e4"))
        eng.stage_barrier(
            "A", lambda: (order.append("b2"), done.set()))
        assert done.wait(30), order
    finally:
        eng.stop()
    assert order == ["e1", "e2", "e3", "b1", "e4", "b2"], order


def test_decode_sync_correct_while_window_full(monkeypatch):
    """A blocking decode issued while encode batches are in flight
    returns bit-exact data (decodes serialize behind the staged
    encodes on the engine thread; the window never reorders them into
    a wrong answer)."""
    launches: list = []
    _fake_device(monkeypatch, launches)
    codec = _codec()
    host = _codec(backend="numpy")
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    rng = np.random.default_rng(4)
    payload = rng.integers(0, 256, 4096, dtype=np.uint8)
    full = ec_util.encode(sinfo, host, payload)
    eng = DeviceEncodeEngine(lambda k, f: f(), flush_bytes=2048,
                             window=3)
    try:
        for _ in range(4):
            eng.stage_encode("A", codec, sinfo,
                             np.zeros(2048, dtype=np.uint8),
                             lambda s, c, e: None)
        out = eng.decode_sync("A", codec, sinfo,
                              {0: full[0], 2: full[2]}, [0, 1])
        assert out is not None
        assert np.array_equal(np.asarray(out[1]), full[1])
    finally:
        eng.stop()


def test_stop_drains_window(monkeypatch):
    """stop() retires every in-flight batch AND flushes everything
    staged before it: no continuation is ever dropped on shutdown —
    including ops queued while the engine was mid-drain (the idle
    drain used to race the _running flag and drop them)."""
    launches: list = []
    _fake_device(monkeypatch, launches)
    codec = _codec()
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    done: list = []
    eng = DeviceEncodeEngine(lambda k, f: f(), flush_bytes=2048,
                             window=4)
    eng.stage_encode("A", codec, sinfo,
                     np.zeros(2048, dtype=np.uint8),
                     lambda s, c, e: done.append(0))
    # let the engine reach its idle drain (the fake device holds the
    # batch DEVICE_S), then stage more and stop immediately
    time.sleep(DEVICE_S / 2)
    for i in range(1, 4):
        eng.stage_encode("A", codec, sinfo,
                         np.zeros(2048, dtype=np.uint8),
                         lambda s, c, e, i=i: done.append(i))
    eng.stop()
    assert done == [0, 1, 2, 3], done


def test_launch_failure_drains_older_batches_first(monkeypatch):
    """A failed launch must not let its error continuation overtake
    OLDER in-flight batches' continuations (per-PG order)."""
    launches: list = []
    _fake_device(monkeypatch, launches)
    codec = _codec()
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    order: list = []
    done = threading.Event()

    orig = ec_util._flush_device_fused_async
    calls = {"n": 0}

    def flaky(sinfo_, codec_, ops, bufs, **kw):
        calls["n"] += 1
        if calls["n"] == 2:            # second batch's launch dies
            raise RuntimeError("injected launch fault")
        return orig(sinfo_, codec_, ops, bufs, **kw)

    monkeypatch.setattr(ec_util, "_flush_device_fused_async", flaky)
    # the plain-path fallback would normally re-encode; poison it so
    # the fault truly surfaces as an error continuation
    monkeypatch.setattr(
        ec_util, "encode",
        lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("injected plain fault")))
    eng = DeviceEncodeEngine(lambda k, f: f(), flush_bytes=2048,
                             window=3)
    try:
        eng.stage_encode("A", codec, sinfo,
                         np.zeros(2048, dtype=np.uint8),
                         lambda s, c, e: order.append(("ok1", e)))
        eng.stage_encode("A", codec, sinfo,
                         np.zeros(2048, dtype=np.uint8),
                         lambda s, c, e: (order.append(("bad", e)),
                                          done.set()))
        assert done.wait(30), order
    finally:
        eng.stop()
    assert [tag for tag, _e in order] == ["ok1", "bad"], order
    assert order[0][1] is None
    assert isinstance(order[1][1], RuntimeError)


def test_compile_once_across_100_pipelined_flushes(monkeypatch):
    """100 same-signature flushes through the pipelined engine compile
    the fused program exactly once (the pow2-bucketed signature pin —
    pipelining must not leak shapes into the jit cache)."""
    from ceph_tpu.utils.device_telemetry import telemetry
    monkeypatch.setenv("CEPH_TPU_FUSE_CRC", "1")
    telemetry().reset()
    ec_util._fused_cache.clear()
    codec = _codec()
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    rng = np.random.default_rng(7)
    data = [rng.integers(0, 256, 2048, dtype=np.uint8)
            for _ in range(100)]
    done: list = []
    all_done = threading.Event()
    eng = DeviceEncodeEngine(lambda k, f: f(), flush_bytes=2048,
                             window=3)
    try:
        for i in range(100):
            eng.stage_encode(
                "A", codec, sinfo, data[i],
                lambda s, c, e, i=i: (done.append((i, e)),
                                      all_done.set()
                                      if len(done) == 100 else None))
        assert all_done.wait(60), len(done)
    finally:
        eng.stop()
    assert [i for i, _ in done] == list(range(100))
    assert all(e is None for _, e in done)
    snap = telemetry().snapshot()
    fused = {s: v for s, v in snap["compiles_by_signature"].items()
             if s.startswith("fused_crc[jax")}
    assert len(fused) == 1, fused
    assert next(iter(fused.values()))["compiles"] == 1, fused
    assert snap["counters"]["recompiles"] == 0, snap["counters"]
    telemetry().reset()


def test_hbm_gauges_reconcile_to_zero(monkeypatch):
    """ISSUE 7 satellite: per-batch byte counts survive retirement,
    so the live HBM gauges (staged / in-window) read exactly zero
    once a burst drains — and the retired counter accounts every
    byte that passed through."""
    from ceph_tpu.utils.device_telemetry import telemetry
    telemetry().reset()
    _wall, order, stats = _burst(3, monkeypatch)
    assert order == list(range(8))
    tel = telemetry()
    assert tel.hbm_live_bytes() == 0
    assert tel.perf.get("hbm_staged_bytes") == 0
    assert tel.perf.get("hbm_inflight_bytes") == 0
    assert tel.perf.get("hbm_live_bytes") == 0
    # all 8 x 2048-byte payloads retired; the peak saw the window
    assert tel.perf.get("hbm_retired_bytes") == 8 * 2048
    assert tel.perf.get("hbm_peak_live_bytes") >= 2048
    telemetry().reset()


def test_hbm_gauges_reconcile_on_launch_failure(monkeypatch):
    """The failure path reconciles too: a batch whose launch dies
    leaves nothing behind in the live gauges (its bytes count as
    retired/failed-over)."""
    from ceph_tpu.utils.device_telemetry import telemetry
    telemetry().reset()
    codec = _codec()
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    done = threading.Event()
    monkeypatch.setenv("CEPH_TPU_FUSE_CRC", "1")
    monkeypatch.setattr(
        ec_util, "_flush_device_fused_async",
        lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("injected launch fault")))
    monkeypatch.setattr(
        ec_util, "encode",
        lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("injected plain fault")))
    eng = DeviceEncodeEngine(lambda k, f: f(), flush_bytes=2048,
                             window=3)
    try:
        eng.stage_encode("A", codec, sinfo,
                         np.zeros(2048, dtype=np.uint8),
                         lambda s, c, e: done.set())
        assert done.wait(30)
    finally:
        eng.stop()
    tel = telemetry()
    assert tel.hbm_live_bytes() == 0
    assert tel.perf.get("hbm_retired_bytes") == 2048
    telemetry().reset()


def test_hbm_gauges_zero_across_cluster_lifecycles():
    """The PR-6 shutdown-safety bar, HBM edition: full MiniCluster
    lifecycles (writes + degraded read through the decode seam) leave
    the live gauges at exactly zero every time."""
    from ceph_tpu.qa.cluster import MiniCluster
    from ceph_tpu.utils.device_telemetry import telemetry
    telemetry().reset()
    tel = telemetry()
    for cycle in range(2):
        with MiniCluster(n_osds=3) as cluster:
            rados = cluster.client()
            cluster.create_ec_pool("hbm", k=2, m=1, pg_num=4,
                                   backend="jax")
            io = rados.open_ioctx("hbm")
            io.op_timeout = 120.0
            for i in range(4):
                io.write_full(f"o{i}", b"h" * 8192)
            assert io.read("o0") == b"h" * 8192
        assert tel.hbm_live_bytes() == 0, \
            f"live HBM bytes leaked in lifecycle {cycle}"
        assert tel.perf.get("hbm_staged_bytes") == 0
        assert tel.perf.get("hbm_inflight_bytes") == 0
    assert tel.perf.get("hbm_retired_bytes") > 0
    telemetry().reset()


def test_compile_cache_warm_process_counts_hits(tmp_path):
    """The warmup-kill acceptance gate: a second 'process' (fresh
    ledger load) against the same persistent cache dir records the
    signature's warm compile below the cold run's wall time and the
    compile_cache_hits counter lands in the telemetry snapshot."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.utils import compile_cache
    from ceph_tpu.utils.device_telemetry import telemetry

    cc_dir = str(tmp_path / "cc")

    def make_big_fn():
        # a FRESH closure per phase: jitting the same function object
        # twice shares one in-process jit cache, which would mask the
        # second "process"'s compile entirely. Same computation =>
        # same HLO hash => the persistent disk cache still serves it.
        def big_fn(x):
            # a real multi-op program (cache entries of honest size);
            # NOTE the wall-clock saving itself is not asserted below
            # — on CPU a warm disk load costs about as much as the
            # cold compile (~0.25 s vs ~0.23 s measured), so
            # warm < cold is a coin flip here; only the chip's ~35 s
            # compiles make it decisive
            for i in range(60):
                x = x * 2 + i
                x = jnp.where(x > 7, x - 3, x + 1)
            return x.sum()
        return big_fn

    x = jnp.arange(4096, dtype=jnp.int32)
    try:
        compile_cache._reset_for_tests()
        assert compile_cache.enable(cc_dir) == cc_dir
        telemetry().reset()
        telemetry().timed_call("warmkill_sig", jax.jit(make_big_fn()),
                               x)
        led = compile_cache.ledger()
        assert "warmkill_sig" in led
        cold = led["warmkill_sig"]["cold_s"]
        assert cold > 0
        assert telemetry().snapshot()["counters"][
            "compile_cache_misses"] >= 1

        # fresh process against the same cache dir: the ledger knows
        # the signature and XLA's disk cache serves the executable
        compile_cache._reset_for_tests()
        telemetry().reset()
        assert compile_cache.enable(cc_dir) == cc_dir
        telemetry().timed_call("warmkill_sig", jax.jit(make_big_fn()),
                               x)
        counters = telemetry().snapshot()["counters"]
        assert counters["compile_cache_hits"] >= 1, counters
        led = compile_cache.ledger()
        warm = led["warmkill_sig"].get("warm_s")
        assert warm is not None
        # the accounting contract, not a wall-clock race: on CPU the
        # disk load is the same order as the compile (see big_fn
        # note), so pin recording + a generous sanity bound instead
        # of the flaky strict inequality
        assert 0 < warm < cold * 5, (warm, cold)
        # the bench metric-line brief surfaces the counter
        assert telemetry().snapshot_brief().get(
            "compile_cache_hits", 0) >= 1
    finally:
        compile_cache._reset_for_tests()
        telemetry().reset()


# -- ISSUE 9: ordering + shutdown drain under the shared engine -------

def test_interleaved_write_remove_order_through_shared_engine():
    """Per-PG commit order across the BATCHED fan-out: interleaved
    write/remove rounds on one object through the shared engine
    (writes ride flush-group batches, removes the barrier path) must
    leave every shard consistent — the deep scrub's fused parity
    verify is the cross-shard ordering oracle, and the final write
    must win the readback."""
    import concurrent.futures

    from ceph_tpu.qa.cluster import MiniCluster

    with MiniCluster(n_osds=3) as cluster:
        rados = cluster.client()
        cluster.create_ec_pool("ord", k=2, m=1, pg_num=4,
                               backend="jax")
        io = rados.open_ioctx("ord")
        io.op_timeout = 120.0

        def _quiet(fn, *a):
            try:
                fn(*a)
            except Exception:
                pass        # remove of a not-yet-created oid etc.

        for r in range(6):
            pay = bytes(((r * 41 + j) & 0xFF) for j in range(8192))
            alt = bytes(((r * 43 + j) & 0xFF) for j in range(8192))
            with concurrent.futures.ThreadPoolExecutor(2) as pool:
                if r % 2:
                    fs = [pool.submit(io.write_full, "hot", pay),
                          pool.submit(_quiet, io.remove, "hot")]
                else:
                    fs = [pool.submit(io.write_full, "hot", pay),
                          pool.submit(io.write_full, "hot", alt)]
                for f in fs:
                    f.result()
        final = b"f" * 8192
        io.write_full("hot", final)
        assert io.read("hot") == final
        # cross-shard consistency: a reordered sub-write batch would
        # leave shards encoding different object versions
        rep = cluster.scrub_pool("ord", repair=False, deep=True)
        assert rep["inconsistent"] == {}, rep


def test_shared_engine_shutdown_drain_multiple_attachments():
    """The shutdown drain with ONE engine serving several OSDs: a
    detaching attachment drains its own staged work (continuations
    dispatched before its dispatcher goes), later attachments keep
    the engine alive, and the LAST detach stops it and releases the
    process-wide instance."""
    import numpy as np

    from ceph_tpu.osd import device_engine as de

    codec = _codec()
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    done_a: list = []
    done_b: list = []
    h1 = de.shared_engine_attach(lambda k, fn: fn())
    h2 = de.shared_engine_attach(lambda k, fn: fn())
    try:
        assert h1.engine is h2.engine
        for i in range(4):
            h1.stage_encode(f"pg{i}", codec, sinfo,
                            np.zeros(2048, dtype=np.uint8),
                            lambda s, c, e, i=i: done_a.append((i, e)))
            h2.stage_encode(f"pg{i}", codec, sinfo,
                            np.zeros(2048, dtype=np.uint8),
                            lambda s, c, e, i=i: done_b.append((i, e)))
        h1.stop()
        # h1's staged work was drained before its dispatcher left
        assert [i for i, _ in done_a] == [0, 1, 2, 3]
        assert all(e is None for _, e in done_a)
        # the engine survives for h2...
        assert h1.engine._running
        h2.stage_encode("pg9", codec, sinfo,
                        np.zeros(2048, dtype=np.uint8),
                        lambda s, c, e: done_b.append((9, e)))
        h2.stop()
        assert [i for i, _ in done_b] == [0, 1, 2, 3, 9]
        assert all(e is None for _, e in done_b)
        # ...and the LAST detach stopped and released it
        assert not h2.engine._running
        assert de._shared_engine is None
    finally:
        h1.stop()
        h2.stop()
