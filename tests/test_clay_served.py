"""The Clay pool's served path (ISSUE 29): one layered encode+crc
program a flush, one signature-batched layered decode a flush, held to
the plain reference ``benchmarks/clay_reference.py`` (written from the
published algorithm, independent of ``ceph_tpu``) and to the program's
own host oracle (the plane-by-plane machinery of ``models/clay.py``).
CPU, ``backend=jax``: results and counters, no times."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import clay_reference  # noqa: E402
import reference  # noqa: E402

from ceph_tpu.models.registry import instance  # noqa: E402
from ceph_tpu.ops.crc32c_device import zeros_crc  # noqa: E402
from ceph_tpu.osd import device_engine, ec_util  # noqa: E402

UNIT = 4096
POOL = {"plugin": "clay", "k": 8, "m": 4, "d": 11,
        "scalar_mds": "jerasure", "technique": "reed_sol_van",
        "backend": "jax", "stripe_unit": UNIT, "pg_num": 8}
SINFO = ec_util.StripeInfo(stripe_width=8 * UNIT, chunk_size=UNIT)


def _codec(**over):
    profile = {k: str(v) for k, v in POOL.items()
               if k not in ("stripe_unit", "pg_num")}
    profile.update({k: str(v) for k, v in over.items()})
    return instance().factory("clay", profile)


@pytest.fixture(scope="module")
def served():
    """The codec object a pool's ECBackend would hand the engine."""
    return _codec()


@pytest.fixture(scope="module")
def oracle():
    """The program's host oracle: the plane machinery, not linearized."""
    codec = _codec(backend="numpy", linearize="false")
    assert codec.linearize is False
    return codec


def _payload(seed: int, stripes: int) -> np.ndarray:
    return np.random.default_rng([29, seed]).integers(
        0, 256, stripes * SINFO.stripe_width, dtype=np.uint8)


def _flush(codec, bufs, with_crcs=True):
    finalize = ec_util._flush_layered_async(
        SINFO, codec, list(range(len(bufs))), bufs,
        with_crcs=with_crcs)
    assert finalize.layered
    return finalize()


# -- encode ---------------------------------------------------------------

@pytest.mark.parametrize("stripes", [1, 2, 5, 128])
def test_reference_oracle_and_batched_program_agree(served, oracle,
                                                    stripes):
    data = _payload(stripes, stripes)
    want = clay_reference.shards(data.tobytes(), POOL)
    assert len(want) == 12 and all(
        len(s) == stripes * UNIT for s in want)
    # the host oracle, stripe by stripe (one stripe is what the
    # codec's interface encodes)
    rows = range(stripes) if stripes <= 5 else (0, 77, 127)
    for si in rows:
        chunks = data.reshape(stripes, 8, UNIT)[si]
        par = oracle.encode_chunks(
            list(range(8, 12)), {j: chunks[j] for j in range(8)})
        for c in range(8, 12):
            assert np.array_equal(
                par[c], want[c][si * UNIT:(si + 1) * UNIT]), (si, c)
    # the host twin's batched form: one codec call for the batch
    host = ec_util.encode(SINFO, _codec(backend="numpy"), data)
    # the served program: one device program for the flush
    [(_, shards, crcs)] = _flush(served, [data])
    for c in range(12):
        assert np.array_equal(host[c], want[c]), c
        assert np.array_equal(shards[c], want[c]), c
        crc = crcs[c] ^ zeros_crc(len(want[c]), ec_util.HINFO_SEED)
        assert crc == reference.crc32c(want[c], reference.HINFO_SEED)


def test_ragged_batch_of_several_ops_is_one_program(served):
    """Ops of 3, 1, 7 and 2 stripes in one flush: every op's shards
    and crcs are its own, whatever the neighbours and the bucket's
    padding hold."""
    bufs = [_payload(100 + i, n) for i, n in enumerate((3, 1, 7, 2))]
    results = _flush(served, bufs)
    assert [r[0] for r in results] == [0, 1, 2, 3]
    for (_, shards, crcs), buf in zip(results, bufs):
        want = clay_reference.shards(buf.tobytes(), POOL)
        for c in range(12):
            assert np.array_equal(shards[c], want[c]), c
            crc = crcs[c] ^ zeros_crc(len(want[c]), ec_util.HINFO_SEED)
            assert crc == reference.crc32c(want[c],
                                           reference.HINFO_SEED)
    # without the crc pass (the jax backend's default) the parity is
    # the same and the backend hashes on the host
    for (_, shards, crcs), buf in zip(_flush(served, bufs, False), bufs):
        assert crcs is None
        want = clay_reference.shards(buf.tobytes(), POOL)
        assert all(np.array_equal(shards[c], want[c])
                   for c in range(12))


def test_stripe_batcher_takes_the_layered_route(served, monkeypatch):
    """The engine's batcher makes no per-stripe call for a layered
    codec: the codec's own encode is never entered."""
    from ceph_tpu.models.clay import ErasureCodeClay
    monkeypatch.setattr(
        ErasureCodeClay, "encode_chunks",
        lambda *a, **k: pytest.fail("per-stripe encode on the "
                                    "served path"))
    batcher = ec_util.StripeBatcher(SINFO, served)
    bufs = [_payload(200 + i, 2) for i in range(3)]
    for i, buf in enumerate(bufs):
        batcher.append(i, buf)
    for (_, shards, crcs), buf in zip(batcher.flush(), bufs):
        want = clay_reference.shards(buf.tobytes(), POOL)
        assert crcs is None     # jax backend: no fused crc unasked
        assert all(np.array_equal(shards[c], want[c])
                   for c in range(12))


# -- decode ---------------------------------------------------------------

def _signatures():
    rng = np.random.default_rng(2911)
    sigs = [(c,) for c in range(12)]
    for size, count in ((2, 6), (3, 4), (4, 4)):
        for _ in range(count):
            sigs.append(tuple(sorted(
                int(c) for c in rng.choice(12, size, replace=False))))
    return sigs


@pytest.fixture(scope="module")
def stored():
    data = _payload(300, 3)
    return clay_reference.shards(data.tobytes(), POOL)


@pytest.mark.parametrize("lost", _signatures(),
                         ids=lambda sig: "lost_" + "_".join(map(str, sig)))
def test_batched_decode_equals_the_reference(served, stored, lost):
    present = {c: stored[c] for c in range(12) if c not in lost}
    want = clay_reference.decode(present, list(lost), POOL)
    got = ec_util.decode(SINFO, served, present, list(lost))
    host = ec_util.decode(SINFO, _codec(backend="numpy"), present,
                          list(lost))
    for c in lost:
        assert np.array_equal(want[c], stored[c]), c
        assert np.array_equal(got[c], stored[c]), c
        assert np.array_equal(host[c], stored[c]), c


def test_one_decode_program_serves_every_signature(served, stored):
    """The signature's table is an operand: two signatures of one
    shape run the SAME compiled program, and a table is built once."""
    fn_a, new_a = ec_util.layered_decode_program(served, UNIT,
                                                 1 << 14, 1)
    fn_b, new_b = ec_util.layered_decode_program(served, UNIT,
                                                 1 << 14, 1)
    assert fn_a is fn_b and not new_b
    before = fn_a._cache_size()
    for lost in (2, 9):
        present = {c: stored[c] for c in range(12) if c != lost}
        got = ec_util.decode_layered(SINFO, served, present, [lost])
        assert np.array_equal(got[lost], stored[lost])
    assert fn_a._cache_size() - before <= 1
    sig = ec_util.decode_signature(
        served, {c: None for c in range(12) if c != 2}, [2])
    assert sig == ((0, 1, 3, 4, 5, 6, 7, 8), (2,))
    _, built = ec_util.signature_table(served, *sig)
    assert built is False       # cached by the decode above


# -- the engine's key ---------------------------------------------------

def test_program_key_is_a_value():
    a, b = _codec(), _codec()
    assert a is not b
    key = device_engine.program_key(a, SINFO)
    assert key == device_engine.program_key(b, SINFO)
    assert hash(key) == hash(device_engine.program_key(b, SINFO))
    other = _codec(k=8, m=4, d=10)
    assert device_engine.program_key(other, SINFO) != key
    assert device_engine.program_key(_codec(backend="numpy"),
                                     SINFO) != key
    # the capability the seams ask, not a class test
    assert ec_util.flush_kind(a) == "layered"
    assert ec_util.device_decodable(a)
    assert not ec_util.device_decodable(_codec(backend="numpy"))
    rs = instance().factory("jerasure", {
        "plugin": "jerasure", "technique": "reed_sol_van", "k": "8",
        "m": "3", "backend": "jax"})
    assert ec_util.flush_kind(rs) == "matrix"
    assert ec_util.device_decodable(rs)
    assert not ec_util.host_flushable(a) and ec_util.host_flushable(rs)


# -- the pool, through the normal path ------------------------------------

@pytest.fixture
def fast_death():
    from ceph_tpu.utils.config import g_conf
    conf = g_conf()
    old = {k: conf[k] for k in ("osd_heartbeat_interval",
                                "osd_heartbeat_grace")}
    # 13 daemons under one interpreter lock, and the test run's other
    # workers beside them: a shorter grace marks healthy OSDs down
    conf.set("osd_heartbeat_interval", 0.5)
    conf.set("osd_heartbeat_grace", 6.0)
    yield
    for k, v in old.items():
        conf.set(k, v)


def _engine_stats(cluster) -> dict:
    engines = {id(getattr(h, "engine", h)): h for h in (
        osd.device_engine() for osd in cluster.osds.values())}
    assert len(engines) == 1
    return dict(next(iter(engines.values())).stats)


def test_clay_pool_is_served_by_the_device_engine(fast_death,
                                                  monkeypatch):
    from ceph_tpu.models.clay import ErasureCodeClay
    from ceph_tpu.qa.cluster import MiniCluster
    from ceph_tpu.utils.device_telemetry import telemetry
    per_stripe = {"encode": 0}
    real = ErasureCodeClay.encode_chunks

    def counted(self, *args, **kwargs):
        per_stripe["encode"] += 1
        return real(self, *args, **kwargs)

    monkeypatch.setattr(ErasureCodeClay, "encode_chunks", counted)
    blobs = {f"obj{i}": os.urandom(3 * SINFO.stripe_width - 17 * i)
             for i in range(6)}
    with MiniCluster(n_osds=13) as c:
        rados = c.client()
        c.create_ec_pool("clay", pg_num=8, **{
            k: v for k, v in POOL.items()
            if k not in ("stripe_unit", "pg_num")})
        io = rados.open_ioctx("clay")
        before = _engine_stats(c)
        for name, blob in blobs.items():
            io.write_full(name, blob)
        grown = _engine_stats(c)
        assert grown["flushes"] > before["flushes"]
        assert grown["layered_encode_ops"] - \
            before["layered_encode_ops"] == len(blobs)
        assert grown["ops"] - before["ops"] == len(blobs)
        assert grown["errors"] == 0 == grown["device_fused_fallbacks"]
        assert per_stripe["encode"] == 0
        for name, blob in blobs.items():
            assert io.read(name) == blob
        # an OSD that holds a data shard of obj0 dies: reads of the
        # PG reconstruct through the decode flush
        pool_id = c.mon.osdmap.pool_by_name["clay"]
        _, acting, primary = c.mon.osdmap.object_locator(
            pool_id, "obj0")
        victim = next(o for o in list(acting)[:8] if o != primary)
        epoch = c.epoch()
        c.kill_osd(victim)
        c.wait_for_osd_down(victim, timeout=60)
        rados.wait_for_epoch(epoch + 1, timeout=30)
        fallbacks = telemetry().perf.get("engine_decode_fallbacks")
        before = _engine_stats(c)
        for name, blob in blobs.items():
            assert io.read(name) == blob
        grown = _engine_stats(c)
        assert grown["decode_flushes"] > before["decode_flushes"]
        assert grown["layered_decode_ops"] - \
            before["layered_decode_ops"] == \
            grown["decode_ops"] - before["decode_ops"] >= 1
        assert grown["decode_errors"] == 0
        assert telemetry().perf.get("engine_decode_fallbacks") == \
            fallbacks
