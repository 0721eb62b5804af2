"""The one seam that says what a hot thread is doing
(``utils/profiler.py`` ``push_stage``/``pop_stage``) also lands on the
clock of a running ``jax.profiler`` trace:

- a marked thread leaves annotations of its states' names, with its
  role, in plane ``/host:CPU``; with no trace running the marks leave
  nothing and the sampler's stage join is what it was;
- one encode flush and one decode flush through the engine leave
  exactly one annotation per flush phase, each with its batch's
  ``ops``;
- a read marks ``pg_process`` and ``shard_read_wait`` on its stage
  clock, intact or degraded, and its intervals still sum to its
  end-to-end latency.

All on the CPU backend, with the benchmark's own profiler options
(host tracer on, Python tracer off).
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

import jax

from ceph_tpu.models import registry as ec_registry
from ceph_tpu.osd.device_engine import DeviceEncodeEngine
from ceph_tpu.osd.ec_util import StripeInfo
from ceph_tpu.utils import profiler as prof_mod


@pytest.fixture(autouse=True)
def _clean_profiler():
    prof_mod.reset_for_tests()
    yield
    prof_mod.reset_for_tests()


class Traced:
    """``with Traced(tmp_path) as tr: ...`` then ``tr.marks()``: every
    event of ``/host:CPU`` that carries a ``role``, as
    ``(name, stats)`` in order of start."""

    def __init__(self, logdir) -> None:
        self.logdir = str(logdir)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        return self

    def __exit__(self, *exc) -> None:
        jax.profiler.stop_trace()

    def marks(self) -> list:
        from jax.profiler import ProfileData
        path, = glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*", "*.xplane.pb"))
        out = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    stats = dict(ev.stats)
                    if "role" in stats:
                        out.append((ev.start_ns, ev.name, stats))
        return [(name, stats) for _t, name, stats in sorted(
            out, key=lambda m: m[0])]


def marks_names(marks) -> list:
    return [name for name, _stats in marks]


def _in_thread(fn) -> None:
    thread = threading.Thread(target=fn)
    thread.start()
    thread.join(timeout=30)
    assert not thread.is_alive()


# -- (a) the seam -------------------------------------------------------

def test_marks_land_in_the_trace_with_the_threads_role(tmp_path):
    def work():
        prof_mod.thread_role("osd_wq")
        idle = prof_mod.push_stage("idle")
        time.sleep(0.002)
        prof_mod.pop_stage(idle)
        outer = prof_mod.push_stage("pg_process")
        inner = prof_mod.push_stage("commit_wait")
        time.sleep(0.002)
        prof_mod.pop_stage(inner)
        prof_mod.pop_stage(outer)

    with Traced(tmp_path) as tr:
        _in_thread(work)
    assert tr.marks() == [("idle", {"role": "osd_wq"}),
                          ("pg_process", {"role": "osd_wq"}),
                          ("commit_wait", {"role": "osd_wq"})]


def test_a_mark_takes_a_finer_name_a_role_and_its_batch(tmp_path):
    seen = {}

    def work():
        prof_mod.thread_role("engine_launch")
        ident = threading.get_ident()
        mark = prof_mod.push_stage("engine_stage_wait",
                                   span="flush_build", ops=3,
                                   bytes=4096)
        # the sampler joins on the stage, the trace shows the span
        seen["stage"] = prof_mod._thread_stage[ident]
        site = prof_mod.push_stage("client_wait", role="client")
        prof_mod.pop_stage(site)
        prof_mod.pop_stage(mark)
        seen["after"] = prof_mod._thread_stage.get(ident)

    with Traced(tmp_path) as tr:
        _in_thread(work)
    assert seen == {"stage": "engine_stage_wait", "after": None}
    assert tr.marks() == [
        ("flush_build", {"role": "engine_launch", "ops": 3,
                         "bytes": 4096}),
        ("client_wait", {"role": "client"})]


def test_a_thread_without_a_role_marks_as_other(tmp_path):
    def work():
        prof_mod.pop_stage(prof_mod.push_stage("mgr_tick"))

    with Traced(tmp_path) as tr:
        _in_thread(work)
    assert tr.marks() == [("mgr_tick", {"role": "other"})]


def test_without_a_trace_marks_leave_nothing_and_the_join_holds(
        tmp_path):
    """Marks made before a trace starts are not in it, also when they
    close inside it; the sampler's join is the plain dict it was."""
    ident = threading.get_ident()
    prof_mod.thread_role("osd_wq")
    before = prof_mod.push_stage("pg_process")
    prof_mod.pop_stage(prof_mod.push_stage("commit_wait"))
    assert prof_mod._thread_stage[ident] == "pg_process"
    with Traced(tmp_path) as tr:
        prof_mod.pop_stage(before)      # opened before the trace
        assert ident not in prof_mod._thread_stage
    assert tr.marks() == []
    assert prof_mod.profiler_if_exists() is None


def test_messenger_loop_marks_wire_between_waits(tmp_path):
    """The messenger's loop thread carries ``wire`` for the sampler
    for its whole life; the selector's marks close, so a trace shows
    the busy stretches between two waits for I/O."""
    from ceph_tpu.parallel.messenger import Messenger
    msgr = Messenger("osd.99")
    msgr.start()
    try:
        with Traced(tmp_path) as tr:
            for _ in range(3):
                done = threading.Event()
                msgr._loop.call_soon_threadsafe(done.set)
                assert done.wait(10)
                time.sleep(0.01)
        ident = msgr._thread.ident
        assert prof_mod._thread_stage[ident] == "wire"
    finally:
        msgr.shutdown()
    marks = tr.marks()
    assert len(marks) >= 3
    assert set(marks_names(marks)) == {"wire"}
    assert all(stats == {"role": "msgr"} for _n, stats in marks)


# -- (b) the engine's flush phases --------------------------------------

def _codec():
    return ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": "2", "m": "1",
                     "backend": "jax"})


def test_every_flush_leaves_one_mark_per_phase_with_its_batch(
        tmp_path, monkeypatch):
    """A 2-op encode flush, a 2-op decode flush and a 1-op encode
    flush, under one trace."""
    # the device route as the chip serves it: fused encode+crc
    monkeypatch.setenv("CEPH_TPU_HOST_FLUSH_BYTES", "0")
    monkeypatch.setenv("CEPH_TPU_FUSE_CRC", "1")
    codec = _codec()
    sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
    rng = np.random.default_rng(7)
    objs = [rng.integers(0, 256, 2048, dtype=np.uint8)
            for _ in range(2)]
    eng = DeviceEncodeEngine(lambda k, f: f(), flush_bytes=1 << 20)
    encoded, decoded = [], []
    enc_done, dec_done = threading.Event(), threading.Event()

    def on_encode(shards, crcs, err):
        assert err is None, err
        encoded.append(shards)
        if len(encoded) == len(objs):
            enc_done.set()

    def on_decode(out, err):
        assert err is None, err
        decoded.append(out)
        if len(decoded) == len(objs):
            dec_done.set()

    def stage_both_while_held(stage) -> None:
        """Hold the launch thread so both ops land in ONE flush."""
        gate = threading.Event()
        holder = threading.Thread(
            target=lambda: eng.run_sync(lambda: gate.wait(10)))
        holder.start()
        time.sleep(0.05)
        stage()
        gate.set()
        holder.join(timeout=30)

    try:
        # compile both programs before the trace
        eng.stage_encode("pg", codec, sinfo, objs[0],
                         lambda s, c, e: enc_done.set())
        assert enc_done.wait(120)
        enc_done.clear()
        warm = eng.decode_sync(
            "pg", codec, sinfo,
            {0: np.zeros(1024, np.uint8), 2: np.zeros(1024, np.uint8)},
            [1], timeout=120)
        assert warm is not None
        flushes0 = eng.stats["flushes"]
        dflushes0 = eng.stats["decode_flushes"]
        with Traced(tmp_path) as tr:
            stage_both_while_held(lambda: [
                eng.stage_encode("pg", codec, sinfo, obj, on_encode)
                for obj in objs])
            assert enc_done.wait(60)
            stage_both_while_held(lambda: [
                eng.stage_decode(
                    "pg", codec, sinfo,
                    {0: np.asarray(shards[0]),
                     2: np.asarray(shards[2])}, [1], on_decode)
                for shards in encoded])
            assert dec_done.wait(60)
            # a state that is open when the trace stops is not in it:
            # the second encode flush closes the first's retire_idle
            enc_done.clear()
            eng.stage_encode("pg", codec, sinfo, objs[0],
                             lambda s, c, e: enc_done.set())
            assert enc_done.wait(60)
    finally:
        eng.stop()
    assert eng.stats["flushes"] - flushes0 == 2
    assert eng.stats["decode_flushes"] - dflushes0 == 1
    for out, obj in zip(decoded, objs):
        assert np.array_equal(out[1], obj.reshape(-1, 2, 1024)
                              [:, 1, :].reshape(-1))
    marks = tr.marks()
    by_name: dict = {}
    for name, stats in marks:
        by_name.setdefault(name, []).append(stats)
    launch = {"role": "engine_launch", "ops": 2, "bytes": 4096}
    retire = dict(launch, role="engine_retire")
    one = {"ops": 1, "bytes": 2048}
    for phase in ("flush_build", "flush_window_wait", "flush_launch"):
        assert by_name.get(phase) == [launch, dict(launch, **one)], \
            (phase, by_name)
    for phase in ("flush_download", "flush_dispatch"):
        assert by_name.get(phase) == [retire, dict(retire, **one)], \
            (phase, by_name)
    for phase in ("decode_build", "decode_run", "decode_dispatch"):
        assert by_name.get(phase) == [launch], (phase, by_name)
    assert "idle" in by_name and "retire_idle" in by_name, \
        sorted(by_name)
    assert {s["role"] for s in by_name["retire_idle"]} == \
        {"engine_retire"}
    # the held launch thread's aux work shows as the scrub state
    assert {s["role"] for s in by_name["scrub"]} == {"engine_launch"}
    # a flush's launch and window wait nest inside its build, the
    # download inside the dispatch
    order = marks_names(marks)
    assert order.index("flush_build") < order.index(
        "flush_window_wait") < order.index("flush_launch")
    assert order.index("flush_dispatch") < order.index(
        "flush_download")


# -- (c) the read's stage split -----------------------------------------

OBJ_BYTES = 20_000


@pytest.fixture(scope="module")
def read_timelines():
    """One intact and one degraded read through a tiny cluster; the
    merged client/primary timeline of each."""
    from ceph_tpu.qa.cluster import MiniCluster
    from ceph_tpu.utils.config import g_conf
    from ceph_tpu.utils.dataplane import dataplane
    conf = g_conf()
    old = {key: conf[key] for key in ("osd_heartbeat_interval",
                                      "osd_heartbeat_grace")}
    conf.set("osd_heartbeat_interval", 0.3)
    conf.set("osd_heartbeat_grace", 1.5)
    try:
        with MiniCluster(n_osds=3) as cluster:
            rados = cluster.client()
            # pg_num=1: one acting set, so the victim is known
            cluster.create_ec_pool("rd", k=2, m=1, pg_num=1,
                                   backend="jax")
            io = rados.open_ioctx("rd")
            io.op_timeout = 120.0
            blob = bytes(range(256)) * (OBJ_BYTES // 256)
            io.write_full("obj", blob)
            out = {}

            def timed_read(kind: str) -> None:
                dataplane().reset()
                assert io.read("obj") == blob
                reads = [t for t in rados.dump_op_timelines()
                         if "shard_read_wait" in
                         {s["stage"] for s in t["stages"]}]
                out[kind] = (reads[-1], dataplane().perf.dump())

            timed_read("intact")
            osdmap = cluster.mon.osdmap
            _, acting, primary = osdmap.pg_to_up_acting(
                osdmap.pool_by_name["rd"], 0)
            victim = acting[1] if acting[1] != primary else acting[0]
            assert acting.index(victim) < 2   # it holds a data chunk
            epoch = cluster.epoch()
            cluster.kill_osd(victim)
            cluster.wait_for_osd_down(victim, timeout=30)
            rados.wait_for_epoch(epoch + 1, timeout=10)
            timed_read("degraded")
            yield out
    finally:
        for key, val in old.items():
            conf.set(key, val)


@pytest.mark.parametrize("kind,stages", [
    ("intact", ["client_submit", "objecter_encode", "send_queue_wait",
                "wire", "dispatch_queue_wait", "pg_process",
                "shard_read_wait", "commit_wait", "commit_reply"]),
    ("degraded", ["client_submit", "objecter_encode",
                  "send_queue_wait", "wire", "dispatch_queue_wait",
                  "pg_process", "shard_read_wait", "engine_stage_wait",
                  "device_finalize", "commit_wait", "commit_reply"])])
def test_a_read_marks_its_fan_out_and_still_sums(read_timelines, kind,
                                                 stages):
    timeline, counters = read_timelines[kind]
    assert [s["stage"] for s in timeline["stages"]] == stages
    assert all(s["dur_us"] >= 0 for s in timeline["stages"]), timeline
    total = sum(s["dur_us"] for s in timeline["stages"])
    assert abs(total - timeline["total_us"]) <= 1.0, timeline
    # the dataplane logger, which the benchmark reads, has both
    for stage in ("pg_process", "shard_read_wait"):
        assert counters[f"stage_{stage}"]["avgcount"] >= 1, stage
        assert counters[f"stage_{stage}"]["sum"] > 0, stage
