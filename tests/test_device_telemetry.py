"""Device-path telemetry (utils/device_telemetry): the PerfCounters
registry fed by the TPU EC pipeline — compile accounting with
recompile detection, batch-occupancy histograms, the queue-wait vs
device-time flush split, calibration outcomes — plus the trace-span
chain from a client write through the engine flush and the
``device perf dump`` admin command."""

import json
import threading
import time

import numpy as np
import pytest

from ceph_tpu.models import registry as ec_registry
from ceph_tpu.osd.device_engine import DeviceEncodeEngine
from ceph_tpu.osd.ec_util import StripeInfo
from ceph_tpu.qa.cluster import MiniCluster
from ceph_tpu.utils import tracing
from ceph_tpu.utils.admin_socket import asok_command
from ceph_tpu.utils.config import g_conf
from ceph_tpu.utils.device_telemetry import telemetry
from ceph_tpu.utils.perf_counters import PerfCounters


@pytest.fixture(autouse=True)
def _pin_device_route(monkeypatch):
    """These tests gate the DEVICE flush machinery (codec._matvec
    fakes, held StripeBatcher.flush_async); keep the tiny test
    flushes off the bulk-ingest small-flush host route, which
    encodes with a direct host matvec and would never hit the
    gates."""
    monkeypatch.setenv("CEPH_TPU_HOST_FLUSH_BYTES", "0")


def _codec(backend="numpy", k=2, m=1):
    return ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": str(k), "m": str(m),
                     "backend": backend})


def _counters():
    return telemetry().snapshot()["counters"]


# -- satellite: histogram bucket edges --------------------------------

def test_hinc_bucket_edges_pinned():
    """Bucket 0 = non-positive only; bucket b >= 1 = [2^(b-1), 2^b);
    positive sub-1.0 observations land in bucket 1 (not the zero
    bucket, which ``int(0.5) == 0`` used to send them to)."""
    pc = PerfCounters("hinc-edges")
    pc.add_histogram("h")
    cases = [
        (0, 0), (-1, 0),          # non-positive -> bucket 0
        (0.5, 1),                 # sub-1.0 positive -> bucket 1
        (1, 1), (1.9, 1),         # [1, 2)
        (2, 2), (3, 2),           # [2, 4)
        (4, 3), (7, 3),           # [4, 8)
        (8, 4), (15, 4),          # [8, 16)
        (2 ** 40, 31),            # clamped to the last bucket
    ]
    for value, want_bucket in cases:
        before = pc.get("h")
        pc.hinc("h", value)
        after = pc.get("h")
        got = [i for i in range(len(after))
               if after[i] != before[i]]
        assert got == [want_bucket], (value, got, want_bucket)


# -- compile accounting -----------------------------------------------

def test_recompile_counter_stays_at_one_across_100_calls():
    """100 same-signature calls through a device entry point compile
    exactly once; the recompile counter does not move (the pow2
    bucketing working as designed)."""
    from ceph_tpu.ops import gf256, gf_jax
    mat = gf256.rs_matrix_isa(3, 2)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(3, 5000), dtype=np.uint8)
    gf_jax.matvec(mat, data)          # first call: the compile
    snap1 = telemetry().snapshot()
    sigs1 = {s: v["compiles"]
             for s, v in snap1["compiles_by_signature"].items()
             if s.startswith("gf_jax[2x3]")}
    assert sigs1 and all(n == 1 for n in sigs1.values()), sigs1
    for _ in range(100):
        gf_jax.matvec(mat, data)
    snap2 = telemetry().snapshot()
    sigs2 = {s: v["compiles"]
             for s, v in snap2["compiles_by_signature"].items()
             if s.startswith("gf_jax[2x3]")}
    assert sigs2 == sigs1, (sigs1, sigs2)
    assert snap2["counters"]["recompiles"] == \
        snap1["counters"]["recompiles"]
    # compile wall time was accounted
    assert snap2["counters"]["compile_time"]["avgcount"] >= 1


def test_note_compile_flags_recompiles():
    tel = telemetry()
    before = _counters()["recompiles"]
    tel.note_compile("test_sig_recompile", 0.1)
    assert _counters()["recompiles"] == before
    tel.note_compile("test_sig_recompile", 0.1)
    assert _counters()["recompiles"] == before + 1
    assert tel.compile_count("test_sig_recompile") == 2


# -- engine flush counters --------------------------------------------

def test_counters_across_staged_encode_decode_round_trip():
    """A staged encode + signature-batched decode round trip on the
    CPU backend moves the always-on counters: occupancy histograms
    match the scripted flush pattern, bytes/queue-wait/device-time
    all advance."""
    from ceph_tpu.osd import ec_util

    codec = _codec(k=2, m=1)
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    in_first = threading.Event()
    release = threading.Event()
    orig = codec._matvec
    calls = []

    def gated(mat, data):
        calls.append(1)
        if len(calls) == 1:
            in_first.set()
            release.wait(10)
        return orig(mat, data)

    codec._matvec = gated
    before = _counters()
    eng = DeviceEncodeEngine(lambda key, fn: fn())
    try:
        rng = np.random.default_rng(0)
        payloads = [rng.integers(0, 256, 2048, dtype=np.uint8)
                    for _ in range(6)]
        done = []
        eng.stage_encode("pg0", codec, sinfo, payloads[0],
                         lambda s, c, e: done.append(e))
        assert in_first.wait(10)      # flush 1 (1 op) holds the gate
        for p in payloads[1:]:        # flush 2 accumulates 5 ops
            eng.stage_encode("pg1", codec, sinfo, p,
                             lambda s, c, e: done.append(e))
        release.set()
        deadline = time.monotonic() + 10
        while len(done) < 6 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(done) == 6 and all(e is None for e in done)

        # decode leg of the round trip (one signature, 2 ops)
        host = _codec(k=2, m=1)
        full = ec_util.encode(sinfo, host, payloads[0])
        shards = {0: full[0], 2: full[2]}
        out = eng.decode_sync("pg0", codec, sinfo, shards, [0, 1])
        assert out is not None and \
            np.array_equal(np.asarray(out[1]), full[1])
    finally:
        eng.stop()

    after = _counters()
    # occupancy histogram: one 1-op flush (bucket 1) and one 5-op
    # flush (5 in [4,8) -> bucket 3), per the scripted pattern
    d_occ = [a - b for a, b in zip(after["encode_batch_ops"],
                                   before["encode_batch_ops"])]
    assert d_occ[1] == 1 and d_occ[3] == 1 and sum(d_occ) == 2, d_occ
    d_dec = [a - b for a, b in zip(after["decode_batch_ops"],
                                   before["decode_batch_ops"])]
    assert d_dec[1] == 1 and sum(d_dec) == 1, d_dec
    assert after["bytes_encoded"] - before["bytes_encoded"] == \
        2048 * 6
    assert after["bytes_decoded"] > before["bytes_decoded"]
    assert after["encode_queue_wait"]["avgcount"] - \
        before["encode_queue_wait"]["avgcount"] == 6
    assert after["decode_queue_wait"]["avgcount"] - \
        before["decode_queue_wait"]["avgcount"] == 1
    assert sum(after["flush_bytes"]) - sum(before["flush_bytes"]) == 2
    assert "flush_device_time" not in after
    assert "decode_flush_device_time" not in after
    d_bytes = [a - b for a, b in zip(after["flush_bytes"],
                                     before["flush_bytes"])]
    # flush sizes: 2048 (bucket 12) and 5*2048 = 10240 (bucket 14)
    assert d_bytes[12] == 1 and d_bytes[14] == 1, d_bytes


def test_lin_matvec_cache_hit_miss_accounting():
    """Clay's linearized-transform LRU reports hits/misses: the first
    decode of a signature is a miss (matrix build), repeats hit."""
    codec = ec_registry.instance().factory(
        "clay", {"k": "4", "m": "2", "backend": "numpy"})
    rng = np.random.default_rng(1)
    size = codec.sub_chunk_no * 8
    chunks = {i: rng.integers(0, 256, size, dtype=np.uint8)
              for i in range(6)}
    enc = codec.encode_chunks(list(range(6)),
                              {i: chunks[i] for i in range(4)})
    whole = {i: (chunks[i] if i < 4 else enc[i]) for i in range(6)}
    before = _counters()
    got = dict(whole)
    del got[1]
    codec.decode_chunks([1], got)       # miss: builds the matrix
    mid = _counters()
    codec.decode_chunks([1], got)       # hit: same signature
    after = _counters()
    assert mid["lin_matvec_misses"] > before["lin_matvec_misses"]
    assert after["lin_matvec_hits"] > mid["lin_matvec_hits"]


def test_calibration_outcome_recorded():
    """build_decode_matvec lands its decision in telemetry (on CPU the
    measurement is skipped and dense wins, recorded as such)."""
    from ceph_tpu.models.clay_device import build_decode_matvec
    codec = ec_registry.instance().factory(
        "clay", {"k": "4", "m": "2", "backend": "numpy"})
    mat = codec._lin_cached(
        ("dec", (2, 3, 4, 5), (0, 1)),
        lambda: codec._decode_matrix((2, 3, 4, 5), (0, 1)))
    fn = build_decode_matvec(codec, mat, label="test_decode")
    assert fn.path == "dense"
    snap = telemetry().snapshot()
    rows = {s: v for s, v in snap["calibrations"].items()
            if s.startswith("test_decode|")}
    assert rows, snap["calibrations"]
    assert all(v["winner"] == "dense" for v in rows.values())
    assert _counters()["calibrations"] >= 1


# -- prometheus exposition --------------------------------------------

def test_prometheus_exports_device_histograms():
    """The device histograms render as cumulative le-bucketed series
    (raw Python lists would be invalid exposition)."""
    from ceph_tpu.utils.prometheus import render_text
    telemetry().perf.hinc("encode_batch_ops", 3)
    text = render_text()
    assert "ceph_tpu_encode_batch_ops_bucket" in text
    assert 'le="+Inf"' in text
    assert "ceph_tpu_encode_batch_ops_count" in text
    assert "[" not in text.split("ceph_tpu_encode_batch_ops")[1][:200]


# -- cluster integration: asok + trace chain --------------------------

def test_device_perf_dump_and_trace_chain():
    """One client EC write against a device-backend pool: (a)
    ``device perf dump`` over the admin socket returns non-trivial
    counters; (b) with trace_all set, the write's trace covers
    client op -> shard sub-op -> engine flush -> kernel dispatch,
    queryable via dump_traces."""
    conf = g_conf()
    old = conf["trace_all"]
    conf.set("trace_all", True)
    tracing.tracer().clear()
    try:
        with MiniCluster(n_osds=3) as cluster:
            rados = cluster.client()
            cluster.create_ec_pool("tel", k=2, m=1, pg_num=1,
                                   backend="jax")
            io = rados.open_ioctx("tel")
            io.write_full("tel_obj", b"t" * 20_000)

            # (a) the admin command
            osd = next(iter(cluster.osds.values()))
            dump = asok_command(osd.asok.path, "device perf dump")
            counters = dump["counters"]
            assert counters["bytes_encoded"] > 0, counters
            assert sum(counters["encode_batch_ops"]) > 0
            assert counters["encode_queue_wait"]["avgcount"] > 0
            assert "compiles" in counters
            json.dumps(dump)          # the payload is JSON-clean

            # (b) the causal chain, queryable via the dump_traces
            # admin command (the blkin surface)
            spans = asok_command(osd.asok.path, "dump_traces")
            roots = [s for s in spans
                     if s["service"].startswith("client")
                     and "op=1" in s["name"]]
            assert roots, spans
            chain = asok_command(osd.asok.path, "dump_traces",
                                 trace_id=roots[-1]["trace_id"])
            by_name = {}
            for s in chain:
                by_name.setdefault(s["name"].split("(")[0], []).append(s)
            assert "handle_osd_op" in by_name
            assert "ec_sub_write" in by_name
            assert "engine_flush" in by_name, sorted(by_name)
            assert "kernel_dispatch" in by_name, sorted(by_name)
            eng = by_name["engine_flush"][-1]
            kd = by_name["kernel_dispatch"][-1]
            # kernel dispatch is a child of the engine flush span,
            # which is a child of the op span
            assert kd["parent_id"] == eng["span_id"]
            op_ids = {s["span_id"] for s in by_name["handle_osd_op"]}
            assert eng["parent_id"] in op_ids
            events = {e["event"].split(" ")[0]
                      for e in eng["events"]}
            assert "staged" in events and "batch_flush" in events
    finally:
        conf.set("trace_all", old)
        tracing.tracer().clear()


def test_tracing_off_allocates_no_spans():
    """With trace_enabled=false (the literal-NOOP escape hatch under
    the ISSUE-10 always-on default) the engine path allocates no Span
    objects (the NOOP discipline: tracing off must stay free)."""
    conf = g_conf()
    old_enabled = conf["trace_enabled"]
    conf.set("trace_enabled", False)
    assert not tracing.tracer().enabled
    made = []
    orig_init = tracing.Span.__init__

    def counting_init(self, *a, **kw):
        made.append(1)
        return orig_init(self, *a, **kw)

    tracing.Span.__init__ = counting_init
    try:
        with MiniCluster(n_osds=3) as cluster:
            rados = cluster.client()
            cluster.create_ec_pool("notrace", k=2, m=1, pg_num=1,
                                   backend="jax")
            io = rados.open_ioctx("notrace")
            io.write_full("quiet_obj", b"q" * 20_000)
            assert io.read("quiet_obj") == b"q" * 20_000
    finally:
        tracing.Span.__init__ = orig_init
        conf.set("trace_enabled", old_enabled)
    assert not made, f"{len(made)} Span objects allocated untraced"


# -- satellite: optracker at op ingress -------------------------------

def test_optracker_reports_in_flight_ec_ops():
    """The optracker is registered at op ingress (osd.py
    _handle_osd_op): an EC write held up inside the device engine is
    visible via dump_ops_in_flight, and lands in dump_historic_ops
    with its event timeline once committed."""
    from ceph_tpu.osd import ec_util

    hold = threading.Event()
    entered = threading.Event()
    orig = ec_util.StripeBatcher.flush_async

    def gated(self, with_crcs=False):
        entered.set()
        hold.wait(10)
        return orig(self, with_crcs)

    ec_util.StripeBatcher.flush_async = gated
    try:
        with MiniCluster(n_osds=3) as cluster:
            rados = cluster.client()
            cluster.create_ec_pool("trk", k=2, m=1, pg_num=1,
                                   backend="jax")
            io = rados.open_ioctx("trk")
            result = []
            t = threading.Thread(
                target=lambda: result.append(
                    io.write_full("held_obj", b"h" * 20_000)))
            t.start()
            assert entered.wait(10), "write never reached the engine"
            # the op is in flight while the engine holds its batch
            found = None
            deadline = time.monotonic() + 10
            while found is None and time.monotonic() < deadline:
                for osd in cluster.osds.values():
                    dump = asok_command(osd.asok.path,
                                        "dump_ops_in_flight")
                    ops = [o for o in dump["ops"]
                           if "held_obj" in o["desc"]]
                    if ops:
                        found = ops[0]
                        break
                time.sleep(0.02)
            assert found is not None, "in-flight EC op not reported"
            events = {e["event"] for e in found["events"]}
            assert "reached_pg" in events, found
            hold.set()
            t.join(timeout=15)
            assert not t.is_alive()
            # finished: moved to the historic ring
            historic = []
            for osd in cluster.osds.values():
                dump = asok_command(osd.asok.path,
                                    "dump_historic_ops")
                historic += [o for o in dump["ops"]
                             if "held_obj" in o["desc"]]
            assert historic, "committed op missing from historic ops"
            assert any(e["event"] == "done"
                       for e in historic[-1]["events"])
    finally:
        ec_util.StripeBatcher.flush_async = orig
        hold.set()


# -- dashboard panel --------------------------------------------------

def test_dashboard_device_panel():
    import urllib.request
    with MiniCluster(n_osds=2) as c:
        c.create_pool("ddash", pg_num=2, size=2)
        mgr = c.start_mgr()
        out = asok_command(mgr.asok.path, "dashboard on")
        assert out["code"] == 0
        st = asok_command(mgr.asok.path, "dashboard status")
        url = st["data"]["url"]
        dev = json.loads(urllib.request.urlopen(
            url + "api/device", timeout=10).read())
        assert "counters" in dev and "calibrations" in dev
        page = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "<h3>device</h3>" in page
        assert asok_command(mgr.asok.path, "dashboard off")["code"] == 0
