"""Range overwrites on a matrix codec's device backend ride the engine's
overwrite route (ISSUE 35): the spliced stripe window stages as an
overwrite encode, overwrites of several PGs share one flush, and the
range-write fan-out runs in the op's continuation. Held to the plain
reference ``benchmarks/reference.py`` (``clay_reference.py`` for the
Clay pool, whose overwrites keep the inline encode). CPU,
``backend=jax``: results and counters, no times."""

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

import clay_reference  # noqa: E402
import reference  # noqa: E402

from ceph_tpu.osd.pg import pg_cid  # noqa: E402

UNIT = 4096
POOLS = {
    "rs83": {"plugin": "jerasure", "technique": "reed_sol_van",
             "k": 8, "m": 3, "backend": "jax"},
    "rs42": {"plugin": "jerasure", "technique": "reed_sol_van",
             "k": 4, "m": 2, "backend": "jax"},
    "clay42": {"plugin": "clay", "k": 4, "m": 2, "d": 5,
               "scalar_mds": "jerasure", "technique": "reed_sol_van",
               "backend": "jax"},
}
#: objects of four stripes
STRIPES = 4


def _ref_pool(pool: str) -> dict:
    return dict(POOLS[pool], stripe_unit=UNIT)


def _object_bytes(pool: str) -> int:
    return STRIPES * POOLS[pool]["k"] * UNIT


@pytest.fixture(scope="module")
def cluster():
    from ceph_tpu.qa.cluster import MiniCluster
    from ceph_tpu.utils.config import g_conf
    conf = g_conf()
    old = conf["osd_heartbeat_grace"]
    # 12 daemons under one interpreter lock beside the run's other
    # workers: upstream's grace, as the benchmark's cluster runs
    conf.set("osd_heartbeat_grace", 20.0)
    try:
        with MiniCluster(n_osds=12) as c:
            for name, profile in POOLS.items():
                c.create_ec_pool(name, pg_num=8, **{
                    k: v for k, v in profile.items()})
            c.rados = c.client()
            yield c
    finally:
        conf.set("osd_heartbeat_grace", old)


def _engine(cluster):
    engines = {id(getattr(h, "engine", h)): h for h in (
        osd.device_engine() for osd in cluster.osds.values())}
    assert len(engines) == 1
    return next(iter(engines.values()))


def _stats(cluster) -> dict:
    return {k: v for k, v in _engine(cluster).stats.items()
            if isinstance(v, int)}


def _grown(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def _stored(cluster, pool: str, name: str) -> dict:
    """position -> (shard bytes, hinfo or None) as the stores hold
    them, for the live members of the acting set."""
    osdmap = cluster.mon.osdmap
    pool_id = osdmap.pool_by_name[pool]
    ps, acting, _ = osdmap.object_locator(pool_id, name)
    out = {}
    for pos, osd_id in enumerate(acting):
        osd = cluster.osds.get(osd_id)
        if osd is None or not osdmap.osds[osd_id].up:
            continue
        cid = pg_cid(pool_id, ps, pos)
        out[pos] = (bytes(osd.store.read(cid, name)),
                    osd.store.getattrs(cid, name).get("hinfo"))
    return out


def _assert_exact(cluster, pool: str, expected: dict, written: set,
                  ref=reference) -> None:
    io = cluster.rados.open_ioctx(pool)
    for name, data in expected.items():
        assert io.read(name) == data, name
        want = ref.shards(data, _ref_pool(pool))
        stored = _stored(cluster, pool, name)
        assert len(stored) >= POOLS[pool]["k"], name
        for pos, (got, hinfo) in stored.items():
            assert got == want[pos].tobytes(), (name, pos)
            # a range overwrite drops the whole-shard crc
            assert (hinfo is None) == (name in written), (name, pos)


def _seeded(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).bytes(n)


def _write_full(io, names, pool, seed) -> dict:
    out = {}
    for i, name in enumerate(names):
        out[name] = _seeded(seed + i, _object_bytes(pool))
        io.write_full(name, out[name])
    return out


class _Held:
    """The engine's launch thread held (``run_sync``, the call deep
    scrub and the benchmark's warm-up gate use): ops staged meanwhile
    leave in the flush that follows the release."""

    def __init__(self, cluster) -> None:
        self.engine = _engine(cluster)
        self.gate, self.entered = threading.Event(), threading.Event()
        self.thread = threading.Thread(
            target=lambda: self.engine.run_sync(self._hold, timeout=60))

    def _hold(self) -> None:
        self.entered.set()
        self.gate.wait(60)

    def __enter__(self):
        self.thread.start()
        assert self.entered.wait(30)
        return self

    def __exit__(self, *exc) -> None:
        self.gate.set()
        self.thread.join(60)


def _staged(cluster, n_ops: int, nbytes: int, limit: float = 30.0):
    """Until ``n_ops`` overwrite windows of ``nbytes`` are staged."""
    from ceph_tpu.utils.device_telemetry import telemetry
    t0 = time.monotonic()
    while telemetry().hbm_live_bytes() < n_ops * nbytes:
        assert time.monotonic() - t0 < limit, "never staged"
        time.sleep(0.01)
    time.sleep(0.1)


def _concurrently(fns) -> tuple[list, list]:
    errors = []

    def run(fn):
        try:
            fn()
        except Exception as exc:            # pragma: no cover
            errors.append(exc)
    threads = [threading.Thread(target=run, args=(fn,)) for fn in fns]
    for th in threads:
        th.start()
    return threads, errors


@pytest.mark.parametrize("pool", ["rs83", "rs42"])
def test_seeded_overwrites_equal_the_reference(cluster, pool):
    io = cluster.rados.open_ioctx(pool)
    names = [f"ow_{pool}_{i}" for i in range(6)]
    expected = _write_full(io, names, pool, seed=350)
    width = _object_bytes(pool)
    rng = np.random.default_rng(35)
    before = _stats(cluster)
    extents = []
    for i in range(12):
        name = names[int(rng.integers(4))]      # the last two untouched
        if i % 3:                               # 4 KiB, aligned
            off = int(rng.integers(width // UNIT)) * UNIT
            length = UNIT
        else:                                   # unaligned, may span
            off = int(rng.integers(width - 9000))
            length = int(rng.integers(1, 9000))
        extents.append((name, off, _seeded(1000 + i, length)))
    for name, off, data in extents:
        io.write(name, data, off)
        buf = bytearray(expected[name])
        buf[off:off + len(data)] = data
        expected[name] = bytes(buf)
    grown = _grown(before, _stats(cluster))
    assert grown["overwrite_ops"] == len(extents)
    assert grown["overwrite_flushes"] >= 1
    assert grown["ops"] >= grown["overwrite_ops"]
    assert grown["host_flushes"] == 0
    assert grown["errors"] == 0
    _assert_exact(cluster, pool, expected,
                  {name for name, _o, _d in extents})


def test_two_inflight_overwrites_of_one_stripe(cluster):
    pool = "rs83"
    io = cluster.rados.open_ioctx(pool)
    expected = _write_full(io, ["pair"], pool, seed=351)
    stripe = POOLS[pool]["k"] * UNIT
    a, b = _seeded(7, UNIT), _seeded(8, UNIT)
    before = _stats(cluster)
    with _Held(cluster):
        threads, errors = _concurrently([
            lambda: io.write("pair", a, stripe + UNIT)])
        _staged(cluster, 1, stripe)
        more, errors2 = _concurrently([
            lambda: io.write("pair", b, stripe + 5 * UNIT)])
        _staged(cluster, 2, stripe)
    for th in threads + more:
        th.join(60)
    assert not errors and not errors2
    assert not any(th.is_alive() for th in threads + more)
    grown = _grown(before, _stats(cluster))
    assert grown["overwrite_ops"] == 2
    assert grown["overwrite_flushes"] == 1     # both in one flush
    assert grown["host_flushes"] == 0
    buf = bytearray(expected["pair"])
    buf[stripe + UNIT:stripe + 2 * UNIT] = a
    buf[stripe + 5 * UNIT:stripe + 6 * UNIT] = b
    _assert_exact(cluster, pool, {"pair": bytes(buf)}, {"pair"})


@pytest.mark.parametrize("first", ["write_full", "overwrite"])
def test_the_later_of_a_write_full_and_an_overwrite_wins(cluster, first):
    pool = "rs83"
    io = cluster.rados.open_ioctx(pool)
    name = f"order_{first}"
    old = _write_full(io, [name], pool, seed=352)[name]
    full = _seeded(353, _object_bytes(pool))
    extent, at = _seeded(354, UNIT), 3 * UNIT
    ops = {"write_full": lambda: io.write_full(name, full),
           "overwrite": lambda: io.write(name, extent, at)}
    second = "overwrite" if first == "write_full" else "write_full"
    before = _stats(cluster)
    with _Held(cluster):
        threads, errors = _concurrently([ops[first]])
        _staged(cluster, 1, POOLS[pool]["k"] * UNIT)
        more, errors2 = _concurrently([ops[second]])
        time.sleep(0.5)
    for th in threads + more:
        th.join(60)
    assert not errors and not errors2
    assert not any(th.is_alive() for th in threads + more)
    grown = _grown(before, _stats(cluster))
    assert grown["overwrite_ops"] == 1
    # the write_full's own flush may take the host route (128 KiB is
    # under host_flush_bytes); the overwrite's never does
    assert grown["host_flushes"] <= grown["flushes"] - 1
    if first == "write_full":
        buf = bytearray(full)
        buf[at:at + UNIT] = extent
        want, written = bytes(buf), {name}
    else:
        # the write_full replaced the object and wrote its hinfo
        want, written = full, set()
    assert want != old
    _assert_exact(cluster, pool, {name: want}, written)


def test_a_pgs_ops_in_both_kinds_of_group_ship_in_order(cluster):
    """A full write of another PG opens the full-write group first;
    then an overwrite of X, then a write_full of X, all staged while
    the engine is held. X's ops would sit in two kinds of group, and
    the groups flush one after the other: the write_full still lands
    last."""
    pool = "rs83"
    io = cluster.rados.open_ioctx(pool)
    osdmap = cluster.mon.osdmap
    pool_id = osdmap.pool_by_name[pool]
    x, other = "late_full", "early_full"
    assert osdmap.object_to_pg(pool_id, x) != \
        osdmap.object_to_pg(pool_id, other)
    _write_full(io, [x, other], pool, seed=361)
    width, stripe = _object_bytes(pool), POOLS[pool]["k"] * UNIT
    a, w2 = _seeded(362, width), _seeded(363, width)
    extent = _seeded(364, UNIT)
    before = _stats(cluster)
    threads, errors = [], []
    with _Held(cluster):
        for fn, live in ((lambda: io.write_full(other, a), width),
                         (lambda: io.write(x, extent, UNIT),
                          width + stripe),
                         (lambda: io.write_full(x, w2),
                          2 * width + stripe)):
            more, errs = _concurrently([fn])
            threads += more
            errors += errs
            _staged(cluster, 1, live)
    for th in threads:
        th.join(60)
    assert not errors and not any(th.is_alive() for th in threads)
    grown = _grown(before, _stats(cluster))
    assert grown["overwrite_ops"] == 1
    _assert_exact(cluster, pool, {x: w2, other: a}, set())


def test_overwrites_of_several_pgs_share_one_flush(cluster):
    pool = "rs83"
    io = cluster.rados.open_ioctx(pool)
    osdmap = cluster.mon.osdmap
    pool_id = osdmap.pool_by_name[pool]
    names, pgs, i = [], set(), 0
    while len(names) < 6:
        name = f"share_{i}"
        i += 1
        ps = osdmap.object_to_pg(pool_id, name)
        if ps not in pgs:
            pgs.add(ps)
            names.append(name)
    expected = _write_full(io, names, pool, seed=355)
    extents = {name: _seeded(356 + j, UNIT)
               for j, name in enumerate(names)}
    before = _stats(cluster)
    with _Held(cluster):
        threads, errors = _concurrently([
            (lambda n=name: io.write(n, extents[n], 2 * UNIT))
            for name in names])
        _staged(cluster, len(names), POOLS[pool]["k"] * UNIT)
    for th in threads:
        th.join(60)
    assert not errors and not any(th.is_alive() for th in threads)
    grown = _grown(before, _stats(cluster))
    assert grown["overwrite_ops"] == len(names)
    assert grown["overwrite_flushes"] < grown["overwrite_ops"]
    assert grown["cross_pg_ops"] >= 2
    assert grown["host_flushes"] == 0
    for name in names:
        buf = bytearray(expected[name])
        buf[2 * UNIT:3 * UNIT] = extents[name]
        expected[name] = bytes(buf)
    _assert_exact(cluster, pool, expected, set(names))


def test_a_clay_overwrite_keeps_the_inline_encode(cluster):
    pool = "clay42"
    io = cluster.rados.open_ioctx(pool)
    expected = _write_full(io, ["clay_ow"], pool, seed=357)
    extent = _seeded(358, 5000)
    before = _stats(cluster)
    io.write("clay_ow", extent, 3000)
    grown = _grown(before, _stats(cluster))
    assert grown["overwrite_ops"] == 0 and grown["ops"] == 0
    buf = bytearray(expected["clay_ow"])
    buf[3000:8000] = extent
    _assert_exact(cluster, pool, {"clay_ow": bytes(buf)}, {"clay_ow"},
                  ref=clay_reference)


def test_an_overwrite_with_one_osd_down_decodes_its_read():
    from ceph_tpu.qa.cluster import MiniCluster
    from ceph_tpu.utils.config import g_conf
    conf = g_conf()
    old = {k: conf[k] for k in ("osd_heartbeat_interval",
                                "osd_heartbeat_grace")}
    conf.set("osd_heartbeat_interval", 0.5)
    conf.set("osd_heartbeat_grace", 6.0)
    pool = "rs42"
    try:
        with MiniCluster(n_osds=6) as c:
            c.create_ec_pool(pool, pg_num=4, **POOLS[pool])
            c.rados = c.client()
            io = c.rados.open_ioctx(pool)
            expected = _write_full(io, ["deg"], pool, seed=359)
            osdmap = c.mon.osdmap
            _ps, acting, _ = osdmap.object_locator(
                osdmap.pool_by_name[pool], "deg")
            victim = acting[1]               # a data shard's holder
            epoch = c.epoch()
            c.kill_osd(victim)
            c.wait_for_osd_down(victim, timeout=60)
            c.rados.wait_for_epoch(epoch + 1, timeout=30)
            extent = _seeded(360, UNIT)
            before = _stats(c)
            io.write("deg", extent, UNIT)    # reads shard 1's chunk
            grown = _grown(before, _stats(c))
            assert grown["overwrite_ops"] == 1
            assert grown["decode_ops"] >= 1
            assert grown["host_flushes"] == 0
            buf = bytearray(expected["deg"])
            buf[UNIT:2 * UNIT] = extent
            _assert_exact(c, pool, {"deg": bytes(buf)}, {"deg"})
    finally:
        for k, v in old.items():
            conf.set(k, v)


def test_a_flush_holds_one_pgs_ops_in_one_kind_and_one_bucket():
    """The engine's rule for flushing what is pending before an op
    joins its group: the op's PG has ops under the other kind of group,
    or an overwrite group would pass one bucket a shard."""
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    first = DeviceEncodeEngine._flush_first
    sinfo = ec_util.StripeInfo(8 * UNIT, UNIT)
    stripe = np.zeros(8 * UNIT, dtype=np.uint8)
    full, over = ("prog", 0, False), ("prog", 0, True)

    def group(keys, n_stripes=1):
        return (None, sinfo, 0,
                [(key, np.zeros(n_stripes * 8 * UNIT, np.uint8))
                 for key in keys])

    assert not first({}, over, "pgA", stripe)
    assert first({full: group(["pgA"])}, over, "pgA", stripe)
    assert first({over: group(["pgA"])}, full, "pgA", stripe)
    assert not first({full: group(["pgB"])}, over, "pgA", stripe)
    per_bucket = ec_util.OVERWRITE_BUCKET // UNIT       # 16 stripes
    below = {over: group(["pgB"] * (per_bucket - 1))}
    assert not first(below, over, "pgA", stripe)
    at = {over: group(["pgB"] * per_bucket)}
    assert first(at, over, "pgA", stripe)
    # a full-write group is bounded by the engine's byte cap alone
    assert not first({full: group(["pgB"] * per_bucket)}, full, "pgA",
                     stripe)


class _ShipHeld:
    """The engine's ship thread held inside a ship of its own (a ready
    group of no op whose one item waits for a gate): the flush groups
    retired meanwhile queue behind it, ready, as under load."""

    def __init__(self, cluster) -> None:
        handle = _engine(cluster)
        self.engine = getattr(handle, "engine", handle)
        self.gate, self.entered = threading.Event(), threading.Event()

    def _hold(self, _items) -> None:
        self.entered.set()
        self.gate.wait(60)

    def __enter__(self):
        from ceph_tpu.osd.device_engine import FlushGroup
        plug = FlushGroup(1)
        plug.defer("hold", self._hold, None)
        plug.done()
        self.engine._ship_q.put(plug)
        assert self.entered.wait(30)
        return self

    def __exit__(self, *exc) -> None:
        self.gate.set()


def _until_grown(cluster, before: dict, key: str, n: int,
                 limit: float = 60.0) -> None:
    t0 = time.monotonic()
    while _grown(before, _stats(cluster))[key] < n:
        assert time.monotonic() - t0 < limit, f"{key} never grew by {n}"
        time.sleep(0.02)


def test_overwrites_queued_at_the_ship_thread_ship_as_one(cluster,
                                                          monkeypatch):
    """16 writers' 4 KiB overwrites of objects in several PGs retire
    while the ship thread is held: the overwrite groups queued ready
    ship in fewer ships than there are groups, a write_full of one of
    the objects, staged between two waves of them, ships alone, and
    every object equals the reference with the overwrites laid over it
    in the order its PG committed them."""
    from ceph_tpu.osd import device_engine
    kinds: list = []
    ship = device_engine.ship_groups

    def spy(groups):
        kinds.append([g.overwrite for g in groups])
        ship(groups)
    monkeypatch.setattr(device_engine, "ship_groups", spy)
    pool = "rs83"
    io = cluster.rados.open_ioctx(pool)
    osdmap = cluster.mon.osdmap
    pool_id = osdmap.pool_by_name[pool]
    names, pgs, i = [], set(), 0
    while len(names) < 4:
        name = f"merge_{i}"
        i += 1
        ps = osdmap.object_to_pg(pool_id, name)
        if ps not in pgs:
            pgs.add(ps)
            names.append(name)
    expected = _write_full(io, names, pool, seed=370)
    k = POOLS[pool]["k"]
    full = _seeded(371, _object_bytes(pool))

    def wave(stripes, seed):
        """Two writers an object, each a 4 KiB block of its own stripe
        (waves never share a stripe)."""
        return [(name, (s * k + j % k) * UNIT, _seeded(seed + j, UNIT))
                for j, (name, s) in enumerate(
                    (n, s) for n in names for s in stripes)]

    first, second = wave((0, 2), 380), wave((1, 3), 390)

    def writes(ops):
        return [(lambda n=n, o=o, d=d: io.write(n, d, o))
                for n, o, d in ops]
    before = _stats(cluster)
    threads, errors, staged = [], [], 0
    with _ShipHeld(cluster):
        for fns in (writes(first),
                    [lambda: io.write_full(names[0], full)],
                    writes(second)):
            more, errs = _concurrently(fns)
            threads += more
            errors += errs
            staged += len(fns)
            _until_grown(cluster, before, "ops", staged)
        time.sleep(0.5)                 # the last wrappers' done()
    for th in threads:
        th.join(60)
    assert not errors and not any(th.is_alive() for th in threads)
    grown = _grown(before, _stats(cluster))
    assert grown["overwrite_ops"] == len(first) + len(second)
    assert grown["ship_groups"] > grown["ships"], grown
    assert any(len(ks) > 1 for ks in kinds), kinds
    assert all(all(ks) for ks in kinds if len(ks) > 1), kinds
    assert kinds.count([False]) >= 2, kinds     # the plug, the write_full

    def lay(ops):
        for name, off, data in ops:
            buf = bytearray(expected[name])
            buf[off:off + UNIT] = data
            expected[name] = bytes(buf)
    lay(first)
    expected[names[0]] = full
    lay(second)
    _assert_exact(cluster, pool, expected, set(names))
