"""A range overwrite on an undersized PG: a pool of exactly k+m = 11
OSDs (k=8, m=3) with OSD 5 down, so every PG keeps 10 positions and
takes writes with no spare to backfill onto. Where the PG lost a data
position, the overwrite's ranged read of its stripe lacks that chunk
and rebuilds it on the engine's decode flush (counter ``rmw_decodes``,
stage ``rmw_decode``) while the primary's op-wq worker waits; the
decode's continuation runs on the engine's thread, never on a wq
shard. Held to the plain reference ``benchmarks/reference.py``. CPU,
``backend=jax``: results, counters and threads, no times."""

import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import reference  # noqa: E402

from ceph_tpu.osd.pg import pg_cid  # noqa: E402

POOL = "rs83"
PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": 8, "m": 3, "backend": "jax"}
K, M, UNIT = 8, 3, 4096
#: objects of four stripes
WIDTH = 4 * K * UNIT
VICTIM = 5


@pytest.fixture(scope="module")
def cluster():
    from ceph_tpu.qa.cluster import MiniCluster
    from ceph_tpu.utils.config import g_conf
    conf = g_conf()
    old = {k: conf[k] for k in ("osd_heartbeat_interval",
                                "osd_heartbeat_grace")}
    conf.set("osd_heartbeat_interval", 0.5)
    conf.set("osd_heartbeat_grace", 8.0)
    try:
        with MiniCluster(n_osds=K + M) as c:
            c.create_ec_pool(POOL, pg_num=8, **PROFILE)
            c.rados = c.client()
            c.io = c.rados.open_ioctx(POOL)
            osdmap = c.mon.osdmap
            c.pool_id = osdmap.pool_by_name[POOL]
            # every position placed while all 11 are up
            for ps in osdmap.pgs_of_pool(c.pool_id):
                acting = osdmap.pg_to_up_acting(c.pool_id, ps)[1]
                assert sorted(acting) == list(range(K + M)), (ps, acting)
            yield c
    finally:
        for k, v in old.items():
            conf.set(k, v)


@pytest.fixture(scope="module")
def undersized(cluster):
    """OSD 5 killed and marked down: every PG lost the position it
    held, and nothing else."""
    c = cluster
    osdmap = c.mon.osdmap
    held = {ps: osdmap.pg_to_up_acting(c.pool_id, ps)[1].index(VICTIM)
            for ps in osdmap.pgs_of_pool(c.pool_id)}
    epoch = c.epoch()
    c.kill_osd(VICTIM)
    c.wait_for_osd_down(VICTIM, timeout=60)
    c.rados.wait_for_epoch(epoch + 1, timeout=30)
    c.wait_for_clean(timeout=60)
    osdmap = c.mon.osdmap               # the map of this epoch
    for ps, pos in held.items():
        acting = osdmap.pg_to_up_acting(c.pool_id, ps)[1]
        assert [i for i, o in enumerate(acting) if o < 0] == [pos]
    c.lost = held
    return c


def _engine(c):
    engines = {id(getattr(h, "engine", h)): h for h in (
        osd.device_engine() for osd in c.osds.values())}
    assert len(engines) == 1
    return next(iter(engines.values()))


def _stats(c) -> dict:
    return {k: v for k, v in _engine(c).stats.items()
            if isinstance(v, int)}


def _fallbacks() -> int:
    from ceph_tpu.utils.device_telemetry import telemetry
    return int(telemetry().perf.get("engine_decode_fallbacks"))


def _stage_counts() -> dict:
    from ceph_tpu.utils.dataplane import dataplane
    dump = dataplane().perf.dump()
    return {s: dump[f"stage_{s}"]["avgcount"]
            for s in ("rmw_read", "rmw_decode")}


def _seeded(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).bytes(n)


def _name_in(c, want_hole, prefix: str) -> str:
    """A name whose PG lost a position ``want_hole`` accepts."""
    osdmap = c.mon.osdmap
    for i in range(10000):
        name = f"{prefix}_{i}"
        if want_hole(c.lost[osdmap.object_to_pg(c.pool_id, name)]):
            return name
    raise AssertionError("no such PG")


def _stored(c, name: str) -> dict:
    """position -> (shard bytes, hinfo or None) for the live positions
    of the object's acting set."""
    osdmap = c.mon.osdmap
    ps, acting, _ = osdmap.object_locator(c.pool_id, name)
    out = {}
    for pos, osd_id in enumerate(acting):
        osd = c.osds.get(osd_id)
        if osd is None:
            continue
        cid = pg_cid(c.pool_id, ps, pos)
        out[pos] = (bytes(osd.store.read(cid, name)),
                    osd.store.getattrs(cid, name).get("hinfo"))
    return out


def _overwrite(c, name: str, seed: int) -> tuple[bytes, dict]:
    """Preload ``name``, overwrite 4 KiB of its second stripe;
    (what it holds, the engine's counters' growth)."""
    data = bytearray(_seeded(seed, WIDTH))
    c.io.write_full(name, bytes(data))
    extent, at = _seeded(seed + 1, UNIT), K * UNIT + 3 * UNIT
    before = _stats(c)
    c.io.write(name, extent, at)
    after = _stats(c)
    data[at:at + UNIT] = extent
    return bytes(data), {k: after[k] - before.get(k, 0) for k in after}


def _assert_reference(c, name: str, data: bytes, live: int) -> None:
    assert c.io.read(name) == data
    want = reference.shards(data, dict(PROFILE, stripe_unit=UNIT))
    stored = _stored(c, name)
    assert len(stored) == live
    for pos, (got, hinfo) in stored.items():
        assert got == want[pos].tobytes(), pos
        assert hinfo is None, pos       # a range overwrite drops it


def _wait_for(fn, limit: float = 10.0) -> None:
    t0 = time.monotonic()
    while not fn():
        assert time.monotonic() - t0 < limit
        time.sleep(0.02)


def test_a_healthy_overwrite_makes_no_rmw_decode_mark(cluster):
    before = _stage_counts()
    data, grown = _overwrite(cluster, "healthy", 400)
    _wait_for(lambda: _stage_counts()["rmw_read"] > before["rmw_read"])
    assert _stage_counts()["rmw_decode"] == before["rmw_decode"]
    assert grown["overwrite_ops"] == 1
    assert grown["rmw_decodes"] == grown["decode_ops"] == 0
    _assert_reference(cluster, "healthy", data, K + M)


def test_a_lost_data_chunk_is_rebuilt_on_the_decode_flush(
        undersized, monkeypatch):
    """And the decode's continuation runs on the engine's thread,
    never on the caller's wq shard, which waits in ``decode_sync``."""
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    c = undersized
    name = _name_in(c, lambda pos: pos < K, "lostdata")
    real = DeviceEncodeEngine.stage_decode
    threads = []

    def stage_decode(self, key, codec, sinfo, shards, want, cont,
                     **kw):
        caller = threading.current_thread().name

        def traced(out, err):
            threads.append((caller, threading.current_thread().name,
                            kw.get("overwrite")))
            cont(out, err)
        real(self, key, codec, sinfo, shards, want, traced, **kw)
    monkeypatch.setattr(DeviceEncodeEngine, "stage_decode",
                        stage_decode)
    fallbacks, stages = _fallbacks(), _stage_counts()
    data, grown = _overwrite(c, name, 410)
    assert grown["rmw_decodes"] == 1
    assert grown["decode_ops"] == grown["decode_flushes"] == 1
    assert grown["overwrite_ops"] == 1
    assert grown["decode_errors"] == grown["host_flushes"] == 0
    assert _fallbacks() == fallbacks
    _wait_for(lambda: _stage_counts()["rmw_decode"]
              == stages["rmw_decode"] + 1)
    assert threads == [(threads[0][0], "ec-device-engine", True)]
    assert "-wq-" in threads[0][0]
    monkeypatch.undo()
    _assert_reference(c, name, data, K + M - 1)


def test_a_lost_parity_chunk_needs_no_rebuild(undersized):
    c = undersized
    name = _name_in(c, lambda pos: pos >= K, "lostparity")
    stages = _stage_counts()
    data, grown = _overwrite(c, name, 420)
    assert grown["overwrite_ops"] == 1
    assert grown["rmw_decodes"] == grown["decode_ops"] == 0
    _wait_for(lambda: _stage_counts()["rmw_read"] > stages["rmw_read"])
    assert _stage_counts()["rmw_decode"] == stages["rmw_decode"]
    _assert_reference(c, name, data, K + M - 1)
