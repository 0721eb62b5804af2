"""A reconstructing read's last steps (PR 33): the reassembly of chunk
streams into the object's bytes (``ECBackend._chunks_to_logical``: one
strided array-to-array copy a stream, then a contiguous ``tobytes``),
the engine's blocking decode called from the one worker of its key's
shard, and a pool read back with m-1 OSDs down.

Bytes and counts only, on the CPU (``backend=jax``), over an RS and a
Clay codec where the code is shared."""

import os
import threading

import numpy as np
import pytest

from ceph_tpu.models import registry as ec_registry
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.device_engine import DeviceEncodeEngine
from ceph_tpu.osd.ec_util import StripeInfo
from ceph_tpu.osd.osd import ShardedOpWQ
from ceph_tpu.utils import faults

UNIT = 1024
PROFILES = {
    "rs": {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": 4, "m": 2},
    "clay": {"plugin": "clay", "k": 4, "m": 2, "d": 5,
             "scalar_mds": "jerasure", "technique": "reed_sol_van"},
}
CODECS = pytest.mark.parametrize("family", sorted(PROFILES))
SINFO = StripeInfo(stripe_width=4 * UNIT, chunk_size=UNIT)
LOST = (1, 4)                   # a data and a parity chunk


@pytest.fixture(autouse=True)
def _steer(monkeypatch):
    """Keep tiny flushes on the device route; no rule outlives a test."""
    monkeypatch.setenv("CEPH_TPU_HOST_FLUSH_BYTES", "0")
    faults.reset_for_tests(0)
    yield
    faults.reset_for_tests(0)


def _codec(family: str, backend: str = "jax"):
    profile = {k: str(v) for k, v in PROFILES[family].items()}
    profile["backend"] = backend
    return ec_registry.instance().factory(profile["plugin"], profile)


# -- the reassembly -------------------------------------------------------

@pytest.mark.parametrize("k,cs,stripes,cut", [
    (8, 4096, 128, 0),          # the cells' 4 MiB object
    (8, 4096, 128, 77),         # an object that ends inside a stripe
    (4, 1024, 5, 1023), (2, 64, 1, 0), (3, 16, 7, 40), (4, 1024, 0, 0)])
def test_chunks_to_logical_is_the_stripe_major_interleave(k, cs, stripes,
                                                          cut):
    """Chunk streams back to the object's bytes: stripe by stripe,
    chunk by chunk, cut to the object's size; streams of unequal
    length are refused."""
    from types import SimpleNamespace
    from ceph_tpu.osd.ec_backend import ECBackend
    be = SimpleNamespace(
        k=k, sinfo=StripeInfo(stripe_width=k * cs, chunk_size=cs))
    data = np.random.default_rng([33, k, cs, stripes]).integers(
        0, 256, stripes * k * cs, dtype=np.uint8).tobytes()
    shards = {c: np.frombuffer(b"".join(
        data[(st * k + c) * cs:(st * k + c + 1) * cs]
        for st in range(stripes)), dtype=np.uint8) for c in range(k)}
    shards[k] = np.zeros(stripes * cs, np.uint8)    # parity: not read
    size = max(len(data) - cut, 0)
    out = ECBackend._chunks_to_logical(be, shards, size)
    assert isinstance(out, bytes) and out == data[:size]
    if stripes:
        shards[k - 1] = shards[k - 1][:-cs]
        with pytest.raises(ValueError):
            ECBackend._chunks_to_logical(be, shards, size)


# -- the engine's blocking decode -----------------------------------------

@CODECS
def test_decode_sync_from_the_one_worker_of_its_shard_returns(family):
    """The caller holds the key's one worker while it waits: its
    continuation runs inline on the engine's thread (dispatched on
    the key it would wait behind its own caller until the timeout)."""
    wq = ShardedOpWQ("ds", 1)
    eng = DeviceEncodeEngine(wq.enqueue)
    data = np.random.default_rng([33, 7]).integers(
        0, 256, 2 * SINFO.stripe_width, dtype=np.uint8)
    full = ec_util.encode(SINFO, _codec(family, "numpy"), data)
    have = {c: v for c, v in full.items() if c not in LOST}
    box: list = []
    done = threading.Event()

    def op():
        box.append((threading.current_thread(), eng.decode_sync(
            "pg", _codec(family), SINFO, have, list(LOST),
            timeout=300)))
        done.set()

    try:
        wq.enqueue("pg", op)
        assert done.wait(330)
    finally:
        eng.stop()
        wq.drain_stop()
    [(caller, out)] = box
    assert caller is wq._threads[0]
    assert out is not None
    for c in LOST:
        assert np.array_equal(np.asarray(out[c]), full[c])
    assert eng.stats["decode_ops"] == 1
    assert eng.stats["decode_errors"] == 0


# -- the pool, through the normal path ------------------------------------

@pytest.fixture
def fast_death():
    from ceph_tpu.utils.config import g_conf
    conf = g_conf()
    old = {k: conf[k] for k in ("osd_heartbeat_interval",
                                "osd_heartbeat_grace")}
    # six or seven daemons under one interpreter lock, and the test
    # run's other workers beside them: a shorter grace marks healthy
    # OSDs down
    conf.set("osd_heartbeat_interval", 0.5)
    conf.set("osd_heartbeat_grace", 6.0)
    yield
    for k, v in old.items():
        conf.set(k, v)


@CODECS
def test_reads_with_m_minus_1_osds_down_are_byte_exact(
        family, fast_death, monkeypatch):
    """k+m OSDs (no spare: nothing recovers), m-1 of them dead: RS
    k=4, m=3 with two down, Clay k=4, m=2, d=5 with one (its 8
    sub-chunks divide the pool's 4 KiB stripe unit; m=3 gives 27,
    which do not). Every object, whole stripes or not, reads back
    byte-exact through the engine's decode flush and the reassembly;
    after an injected device fault the host twin serves the same
    bytes, every fallback counted."""
    from ceph_tpu.osd.ec_backend import ECBackend
    from ceph_tpu.qa.cluster import MiniCluster
    from ceph_tpu.utils.device_telemetry import telemetry
    profile = dict(PROFILES[family])
    if family == "rs":
        profile["m"] = 3
    n_osds = profile["k"] + profile["m"]
    reassembled = {"n": 0}
    real_logical = ECBackend._chunks_to_logical

    def logical(self, shards, size):
        reassembled["n"] += 1
        return real_logical(self, shards, size)

    monkeypatch.setattr(ECBackend, "_chunks_to_logical", logical)
    host_twin = {"n": 0}
    real_decode = ec_util.decode

    def decode(sinfo, codec, shards, want):
        if not ec_util.device_decodable(codec):
            host_twin["n"] += 1
        return real_decode(sinfo, codec, shards, want)

    monkeypatch.setattr(ec_util, "decode", decode)
    blobs = {f"obj{i}": os.urandom(5 * SINFO.stripe_width - 13 * i)
             for i in range(12)}
    with MiniCluster(n_osds=n_osds) as c:
        rados = c.client()
        c.create_ec_pool("dd", pg_num=8, backend="jax", **profile)
        io = rados.open_ioctx("dd")
        io.op_timeout = 300.0
        for name, blob in blobs.items():
            io.write_full(name, blob)
        victims = sorted(c.osds)[:profile["m"] - 1]
        epoch = c.epoch()
        for victim in victims:
            c.kill_osd(victim)
        for victim in victims:
            c.wait_for_osd_down(victim, timeout=60)
        rados.wait_for_epoch(epoch + 1, timeout=30)
        eng = next(iter(c.osds.values())).device_engine().engine
        before = dict(eng.stats)
        reassembled["n"] = 0
        for name, blob in blobs.items():
            assert io.read(name) == blob, name
        grown = {k: eng.stats[k] - before[k] for k in (
            "decode_ops", "decode_errors")}
        assert grown["decode_ops"] >= 1 and grown["decode_errors"] == 0
        assert reassembled["n"] >= len(blobs)
        # a device fault: the host twin serves, counted
        fallbacks = telemetry().perf.get("engine_decode_fallbacks")
        faults.registry().add("engine_decode")
        host_twin["n"] = 0
        for name, blob in blobs.items():
            assert io.read(name) == blob, name
        faults.reset_for_tests(0)
        assert telemetry().perf.get("engine_decode_fallbacks") - \
            fallbacks == host_twin["n"] >= 1
