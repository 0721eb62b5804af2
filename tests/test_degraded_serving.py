"""Degraded-mode serving under sustained faults (ISSUE 8).

The tier-1 end of the chaos scenario family: a MiniCluster takes an
OSD kill MID-BURST while client load runs, and the acceptance bars are
asserted exactly as the issue names them — zero lost acked writes,
zero wrong bytes, health back to HEALTH_OK after recovery, and the
batched decode-on-read route coalescing same-signature degraded reads
into fewer engine flushes than ops. The long-thrash variants (multiple
kill/revive cycles, msgr fault windows, open-loop pacing) ride tier-2
behind ``@pytest.mark.slow``.
"""

import threading
import time

import pytest

from ceph_tpu.bench.load_gen import (
    LoadGen,
    LoadSpec,
    Zipf,
    _hash01,
    payload_for,
    verify_payload,
)
from ceph_tpu.qa.cluster import MiniCluster
from ceph_tpu.utils import faults
from ceph_tpu.utils.config import g_conf


@pytest.fixture
def fast_death():
    """Tighten failure detection so kill->down takes ~1s, and hand
    every test a freshly-seeded process-wide fault registry."""
    conf = g_conf()
    old = {k: conf[k] for k in ("osd_heartbeat_interval",
                                "osd_heartbeat_grace")}
    conf.set("osd_heartbeat_interval", 0.2)
    conf.set("osd_heartbeat_grace", 0.8)
    faults.reset_for_tests(seed=0)
    yield
    faults.reset_for_tests(seed=0)
    for k, v in old.items():
        conf.set(k, v)


# -- workload-model determinism (no cluster: pure functions) -----------

def test_op_stream_reproduces_per_seed():
    """The load generator's op kinds and key choices are hash-derived
    from (seed, op index): the same seed replays the same workload,
    a different seed decorrelates it — the other half of the
    reproducibility contract next to the fault registry's."""
    z = Zipf(64, 0.99)

    def stream(seed, n=200):
        return [(z.rank(_hash01(seed, "key", i)),
                 _hash01(seed, "rw", i) < 0.5) for i in range(n)]

    assert stream(7) == stream(7)
    assert stream(7) != stream(8)
    # zipf skew is real: the hottest key dominates a uniform share
    ranks = [r for r, _ in stream(7, 500)]
    assert ranks.count(0) > 500 / 64 * 3


def test_payload_verification_catches_corruption():
    data = payload_for("lg_00001", 7, 4096)
    assert verify_payload(data) == ("lg_00001", 7)
    flipped = bytearray(data)
    flipped[-1] ^= 0xFF
    with pytest.raises(ValueError):
        verify_payload(bytes(flipped))
    # a mix of two valid payloads (torn write) must not verify either
    other = payload_for("lg_00001", 8, 4096)
    torn = data[:2048] + other[2048:]
    with pytest.raises(ValueError):
        verify_payload(torn)


# -- the tier-1 chaos scenario -----------------------------------------

def test_midburst_kill_zero_lost_writes_and_health_recovers(fast_death):
    """The acceptance scenario: the fault schedule kills an OSD
    MID-BURST (at an op-count mark, while client ops are in flight),
    the full phase ladder runs under load, and afterwards every acked
    write reads back bit-exact, nothing was lost, no wrong bytes were
    ever returned, client p99 in the degraded/recovering phases stays
    inside the documented QoS bar, and health returns to HEALTH_OK."""
    with MiniCluster(n_osds=3) as cluster:
        reg = cluster.faults
        reg.reseed(11)
        victim = 2
        reg.schedule("kill_osd", at_ops=25, osd=victim)
        cluster.create_ec_pool("dg", k=2, m=1, pg_num=4)
        spec = LoadSpec(n_keys=12, obj_size=4096, read_frac=0.5,
                        concurrency=3, phase_seconds=0.8, seed=11)
        gen = LoadGen(cluster, "dg", spec)
        out = gen.run(victim_osd=victim, clean_timeout=40.0)

        # durability bars: zero lost acked writes, zero wrong bytes
        assert out["verify"]["lost_acked"] == []
        assert out["verify"]["wrong_bytes"] == []
        assert out["verify"]["corruptions"] == []
        # the burst really ran in every phase
        for ph in out["phases"]:
            assert ph["ops"] > 0, ph
        # no op errored: in-flight ops at the kill were resent and
        # completed through the degraded route
        assert sum(p["errors"] for p in out["phases"]) == 0, \
            [p["error_kinds"] for p in out["phases"]]
        # the QoS bar (degraded + recovering phases only)
        assert out["qos"]["within_bar"], out["qos"]
        # health transited and recovered
        assert out["phases"][1]["health"]["status"] != "HEALTH_OK"
        assert out["phases"][-1]["health"]["status"] == "HEALTH_OK"
        # the scheduled mid-burst kill fired exactly once, and the
        # whole fault sequence reads back from the one event log
        acts = [e for e in out["fault_log"] if e["kind"] == "action"]
        assert [a["detail"] for a in acts] == [
            "kill_osd", f"kill_osd osd.{victim}",
            f"revive_osd osd.{victim}"]
        # the degraded phase actually served reads through shard
        # reconstruction (the previously-silent counter, ISSUE 8)
        degraded = sum(o.logger.get("degraded_reads")
                       for o in cluster.osds.values())
        assert degraded > 0
        # ...and the new counters reach the prometheus exposition
        # while the daemons live (the test_counter_schema lint only
        # sees process-wide registries; the per-OSD keys are pinned
        # here where an OSD exists)
        from ceph_tpu.utils import prometheus
        text = prometheus.render_text()
        assert "ceph_tpu_degraded_reads" in text
        assert "ceph_tpu_read_retries" in text
        assert "ceph_tpu_read_retry_attempts_bucket" in text
        assert "ceph_tpu_faults_fired" in text


def test_dropped_subwrite_batch_degrades_like_singletons(fast_death):
    """Satellite (ISSUE 9): a dropped MECSubWriteBatch must retry/
    degrade exactly like N dropped MECSubWrites. The chaos rule is
    written against the SINGLETON sub-write type — the registry's
    msg-type FAMILY matching must make it bite the batch frames the
    bulk-ingest path actually ships — and the client resend ladder
    re-drives every affected write: zero lost acked writes, every
    readback byte-exact."""
    from ceph_tpu.parallel import messages as M
    conf = g_conf()
    old_resend = conf["objecter_resend_interval"]
    conf.set("objecter_resend_interval", 0.3)
    try:
        with MiniCluster(n_osds=3) as cluster:
            reg = cluster.faults
            reg.reseed(11)
            cluster.create_ec_pool("bd", k=2, m=1, pg_num=8,
                                   backend="jax")
            io = cluster.client().open_ioctx("bd")
            io.op_timeout = 60.0
            payloads = {f"bd{i}": bytes(((i * 37 + j) & 0xFF)
                                        for j in range(8192))
                        for i in range(24)}
            # warm a few writes so the drop window hits MID-burst
            for oid in list(payloads)[:4]:
                io.write_full(oid, payloads[oid])
            rule = reg.add("msgr_drop", entity="osd.*",
                           msg_type=M.MECSubWrite.MSG_TYPE,
                           every=4, max_fires=3)
            import concurrent.futures
            with concurrent.futures.ThreadPoolExecutor(4) as pool:
                list(pool.map(
                    lambda oid: io.write_full(oid, payloads[oid]),
                    list(payloads)[4:]))
            rule.remove()
            # every acked write survives, byte-exact (zero lost)
            for oid, want in payloads.items():
                assert io.read(oid) == want, f"{oid} lost or wrong"
            # the rule REALLY fired, and on batch frames: family
            # matching mapped the singleton type onto type 67
            assert rule.fires >= 1
            fired_types = [e["detail"] for e in reg.fired()
                           if e["kind"] == "msgr_drop"]
            assert any(
                f"type={M.MECSubWriteBatch.MSG_TYPE}" in d
                for d in fired_types), fired_types
    finally:
        conf.set("objecter_resend_interval", old_resend)


def test_concurrent_degraded_reads_coalesce_into_fewer_flushes(
        fast_death):
    """The batched decode-on-read pin: N concurrent degraded reads of
    same-signature objects (same survivor set, same missing set —
    the post-failure steady state) must produce FEWER engine decode
    flushes than N. The engine thread is held busy while the reads
    stage, so their reconstructs pile up in the queue and the drain
    groups them by erasure signature."""
    n_objects = 6
    with MiniCluster(n_osds=3) as cluster:
        rados = cluster.client()
        # pg_num=1: every object shares one acting set, so one dead
        # data shard degrades them all with the SAME signature
        cluster.create_ec_pool("co", k=2, m=1, pg_num=1,
                               backend="jax")
        io = rados.open_ioctx("co")
        blobs = {f"co{i}": payload_for(f"co{i}", i, 16384)
                 for i in range(n_objects)}
        for oid, blob in blobs.items():
            io.write_full(oid, blob)

        osdmap = cluster.mon.osdmap
        pool_id = osdmap.pool_by_name["co"]
        _, acting, primary = osdmap.pg_to_up_acting(pool_id, 0)
        # kill the osd holding data position 1 (never the primary):
        # every full-object read now misses chunk 1 -> one shared
        # erasure signature across all degraded reads
        victim = acting[1] if acting[1] != primary else acting[0]
        victim_pos = acting.index(victim)
        assert victim_pos < 2, "victim must hold a data chunk"
        epoch = cluster.epoch()
        cluster.kill_osd(victim)
        cluster.wait_for_osd_down(victim, timeout=30)
        rados.wait_for_epoch(epoch + 1, timeout=10)

        engine = cluster.osds[primary].device_engine()
        f0 = engine.stats["decode_flushes"]
        o0 = engine.stats["decode_ops"]

        # hold the engine on aux work while every read stages its
        # reconstruct; the queue drain then coalesces them
        holder = threading.Thread(
            target=lambda: engine.run_sync(lambda: time.sleep(0.6)),
            daemon=True)
        results: dict[str, bytes] = {}

        def read_one(oid):
            results[oid] = io.read(oid)

        holder.start()
        time.sleep(0.05)            # engine is inside the sleep
        readers = [threading.Thread(target=read_one, args=(oid,),
                                    daemon=True) for oid in blobs]
        for t in readers:
            t.start()
        for t in readers:
            t.join(timeout=30)
        holder.join(timeout=30)

        # bit-exact through the batched route
        for oid, blob in blobs.items():
            assert results.get(oid) == blob, oid
        ops_delta = engine.stats["decode_ops"] - o0
        flush_delta = engine.stats["decode_flushes"] - f0
        assert ops_delta == n_objects, (ops_delta, flush_delta)
        assert 1 <= flush_delta < n_objects, (ops_delta, flush_delta)


def test_ec_read_error_names_unreachable_shards(fast_death):
    """The terminal ECReadError diagnostic (ISSUE 8 satellite): when
    the ladder exhausts its attempts the error must name the
    unreachable shard set and their OSDs, not just a count."""
    from ceph_tpu.osd.ec_backend import ECBackend
    with MiniCluster(n_osds=3) as cluster:
        rados = cluster.client()
        cluster.create_ec_pool("er", k=2, m=1, pg_num=1)
        io = rados.open_ioctx("er")
        io.op_timeout = 30.0
        io.write_full("victim_obj", b"x" * 8192)
        # EIO every shard of the object on every store: no readable
        # set can ever assemble, the ladder must exhaust AND say who
        reg = cluster.faults
        reg.add("store_eio", oid_prefix="victim_obj")
        # drop the ladder to 2 attempts with ~ms backoff so the test
        # measures the message, not the wait
        conf = g_conf()
        old = (ECBackend.MAX_READ_ATTEMPTS,
               conf["osd_ec_read_backoff_base"],
               conf["osd_ec_read_backoff_max"])
        ECBackend.MAX_READ_ATTEMPTS = 2
        conf.set("osd_ec_read_backoff_base", 0.001)
        conf.set("osd_ec_read_backoff_max", 0.004)
        try:
            with pytest.raises(Exception) as ei:
                io.read("victim_obj")
            msg = str(ei.value)
            assert "victim_obj" in msg
            assert "attempts" in msg
            assert "shards" in msg, msg
        finally:
            ECBackend.MAX_READ_ATTEMPTS = old[0]
            conf.set("osd_ec_read_backoff_base", old[1])
            conf.set("osd_ec_read_backoff_max", old[2])


def test_backoff_sleep_is_bounded_and_jittered(fast_death):
    """The retry ladder's backoff policy: exponential from the base,
    capped, full-jittered (never synchronizing concurrent retriers
    into a storm — the pathology the online-EC study measures)."""
    from ceph_tpu.osd import ec_backend as eb
    conf = g_conf()
    conf.set("osd_ec_read_backoff_base", 0.02)
    conf.set("osd_ec_read_backoff_max", 0.5)
    slept = []

    class _Probe(eb.ECBackend):
        def __init__(self):       # no cluster needed for the policy
            pass

    orig_sleep = eb.time.sleep
    eb.time.sleep = slept.append
    try:
        probe = _Probe()
        for attempt in range(12):
            probe._backoff_sleep(attempt)
    finally:
        eb.time.sleep = orig_sleep
    for attempt, s in enumerate(slept):
        ceil = min(0.5, 0.02 * (1 << attempt))
        assert ceil * 0.5 <= s <= ceil, (attempt, s)
    # capped: deep attempts never exceed the ceiling
    assert max(slept) <= 0.5
    # jittered: not all identical once the cap dominates
    assert len({round(s, 6) for s in slept[-6:]}) > 1


# -- tier-2: sustained thrash ------------------------------------------

@pytest.mark.slow
def test_sustained_thrash_qos_and_durability(fast_death):
    """The long variant: messenger fault windows + store latency +
    TWO kill/revive cycles under open-loop zipfian load. The QoS and
    durability bars must hold across the whole run, and the engine
    must not storm (no ENGINE_STALL / SLOW_OPS in the final brief)."""
    from ceph_tpu.parallel import messages as M
    with MiniCluster(n_osds=4) as cluster:
        reg = cluster.faults
        reg.reseed(23)
        # a lossy, slow window for the whole run. Drops are scoped to
        # heartbeats (grace absorbs them); the DATA path gets delay +
        # store-latency windows — a dropped sub-write has no
        # retransmit below the client resend ladder, so blanket drops
        # measure the resend backoff (seconds), not degraded serving
        reg.add("msgr_drop", entity="osd.*", p=0.05,
                msg_type=M.MPing.MSG_TYPE)
        reg.add("msgr_delay", entity="osd.*", delay_s=0.01, p=0.05)
        reg.add("store_latency", delay_s=0.005, p=0.1)
        cluster.create_ec_pool("th", k=2, m=1, pg_num=8)
        spec = LoadSpec(n_keys=32, obj_size=8192, read_frac=0.6,
                        concurrency=4, open_loop_rate=120.0,
                        phase_seconds=2.0, seed=23)
        gen = LoadGen(cluster, "th", spec)
        out = gen.run(victim_osd=3, clean_timeout=60.0)
        assert out["verify"]["lost_acked"] == []
        assert out["verify"]["wrong_bytes"] == []
        assert out["verify"]["corruptions"] == []
        assert out["qos"]["within_bar"], out["qos"]
        final = out["phases"][-1]["health"]
        assert final["status"] == "HEALTH_OK", final
        assert "ENGINE_STALL" not in final["checks"]
        assert "SLOW_OPS" not in final["checks"]

        # second cycle on a different victim, same registry run: the
        # cluster takes sustained repeated faults, not one blip
        epoch = cluster.epoch()
        cluster.kill_osd(1)
        cluster.wait_for_osd_down(1, timeout=30)
        cluster.client().wait_for_epoch(epoch + 1, timeout=10)
        gen._run_phase("degraded2", 1.5, on_action=gen._exec_action)
        cluster.revive_osd(1)
        cluster.wait_for_osds_up(timeout=15)
        cluster.wait_for_clean(timeout=60)
        gen._run_phase("recovered2", 1.0, on_action=gen._exec_action)
        v = gen.final_verify()
        assert v["lost_acked"] == [] and v["wrong_bytes"] == []
        assert gen.phase_reports[-1]["health"]["status"] == "HEALTH_OK"
        # the msgr window really fired (and deterministically per the
        # registry contract pinned in test_faults)
        kinds = {e["kind"] for e in reg.fired()}
        assert "msgr_drop" in kinds


def test_resend_racing_an_inflight_write_is_not_reexecuted(fast_death):
    """A client resend that races the ORIGINAL write's still-running
    execution must be dropped, for every mutating op and not only for
    append. Re-executing an idempotent write_full looks harmless, but
    it commits the same bytes under a NEW version after the client
    already holds the first reply; an interval change then cuts that
    orphan fan-out short and leaves the object split between two
    versions, neither on k shards — unreadable, and recovery loops
    forever (seen on the chip: chip_smoke.py's recovery phase, a
    5-object PG at v8). Here the shard commit acks are delayed past
    several resend periods; the PG's version must advance by exactly
    one write."""
    from ceph_tpu.parallel import messages as M
    conf = g_conf()
    old_resend = conf["objecter_resend_interval"]
    conf.set("objecter_resend_interval", 0.2)
    try:
        with MiniCluster(n_osds=3) as cluster:
            reg = cluster.faults
            cluster.create_ec_pool("dup", k=2, m=1, pg_num=1)
            io = cluster.client().open_ioctx("dup")
            io.op_timeout = 60.0
            io.write_full("warm", b"w" * 8192)
            osdmap = cluster.mon.osdmap
            pool_id = osdmap.pool_by_name["dup"]
            _, _, primary = osdmap.pg_to_up_acting(pool_id, 0)
            pg = cluster.osds[primary].pgs[(pool_id, 0)]
            v0 = pg.log.last_version
            rule = reg.add("msgr_delay", entity="osd.*",
                           msg_type=M.MECSubWriteReply.MSG_TYPE,
                           delay_s=1.2)
            payload = payload_for("dup0", 0, 16384)
            io.write_full("dup0", payload)
            rule.remove()
            assert rule.fires >= 1
            assert pg.log.last_version == v0 + 1, \
                "a racing resend was executed as a second write"
            assert io.read("dup0") == payload
    finally:
        conf.set("objecter_resend_interval", old_resend)
