"""Cluster health engine + counter flight recorder (mgr/health.py,
utils/flight_recorder.py): scripted check transitions, the
ERR-transition auto-bundle firing exactly once, fixed-size ring +
rate derivation under an injected clock, recorder-off zero overhead,
the optracker top-K fix, prometheus label escaping, the asok ``log
dump`` path, and the MiniCluster stall/recompile scenario."""

import json
import time

from ceph_tpu.mgr import health as H
from ceph_tpu.qa.cluster import MiniCluster
from ceph_tpu.utils import flight_recorder as FR
from ceph_tpu.utils.admin_socket import asok_command
from ceph_tpu.utils.config import g_conf
from ceph_tpu.utils.perf_counters import collection


class FakeClock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _bare_engine(**kw) -> H.HealthEngine:
    """An engine with NO built-in checks (scripted tests must not see
    leftover process-global counter state from earlier tests)."""
    kw.setdefault("publish_perf", False)
    eng = H.HealthEngine(**kw)
    for name, _fn in H.BUILTIN_CHECKS:
        eng.unregister(name)
    return eng


# -- flight recorder ---------------------------------------------------

def test_ring_stays_fixed_size_and_rates_correct():
    clock = FakeClock()
    pc = collection().create("fr_test")
    pc.add_u64_counter("bytes")
    try:
        rec = FR.FlightRecorder(capacity=5, interval=1.0, clock=clock)
        for _ in range(12):
            clock.advance(1.0)
            pc.inc("bytes", 100)
            assert rec.sample()
        st = rec.stats()
        assert st["samples"] == 5 and st["capacity"] == 5
        assert len(rec.window()) == 5
        # +100/s exactly under the injected clock
        assert rec.rate("fr_test.bytes") == 100.0
        assert rec.delta("fr_test.bytes") == 400.0
        # windowed query trims to the asked span
        assert len(rec.window(2.5)) == 3
        # sub-interval sampling is gated
        assert not rec.sample()
        clock.advance(0.2)
        assert not rec.sample()
    finally:
        collection().remove("fr_test")


def test_recorder_off_is_zero_overhead(monkeypatch):
    rec = FR.FlightRecorder(capacity=5, enabled=False)

    def boom():
        raise AssertionError("disabled recorder touched the collection")

    monkeypatch.setattr(FR, "collection", boom)
    assert not rec.sample(force=True)
    assert rec.stats()["samples"] == 0
    assert rec.window() == []
    assert rec.rate("anything") is None


# -- health engine: scripted transitions + auto bundle -----------------

def test_scripted_transitions_and_err_bundle_fires_once():
    eng = _bare_engine()
    state = {"sev": None}
    eng.register("SCRIPTED", lambda ctx: None if state["sev"] is None
                 else H.check("SCRIPTED", state["sev"], "scripted"))

    assert eng.evaluate()["status"] == H.OK
    state["sev"] = H.WARN
    rep = eng.evaluate()
    assert rep["status"] == H.WARN
    assert rep["checks"]["SCRIPTED"]["severity"] == H.WARN
    assert eng.bundles_emitted == 0
    state["sev"] = H.ERR
    rep = eng.evaluate()
    assert rep["status"] == H.ERR
    assert eng.bundles_emitted == 1, \
        "entering HEALTH_ERR must auto-emit the diagnostic bundle"
    # staying in ERR re-emits nothing
    eng.evaluate()
    eng.evaluate()
    assert eng.bundles_emitted == 1
    state["sev"] = None
    rep = eng.evaluate()
    assert rep["status"] == H.OK and rep["checks"] == {}
    # a fresh ERR entry emits a fresh bundle
    state["sev"] = H.ERR
    eng.evaluate()
    assert eng.bundles_emitted == 2
    # transition history recorded the whole script
    hist = [(h["check"], h["from"], h["to"])
            for h in eng.history_dump()]
    assert ("SCRIPTED", H.OK, H.WARN) in hist
    assert ("SCRIPTED", H.WARN, H.ERR) in hist
    assert ("SCRIPTED", H.ERR, H.OK) in hist
    # the bundle is a self-contained JSON blob
    bundle = eng.last_bundle
    for key in ("report", "health_history", "log_recent", "ops",
                "device", "compile_cache"):
        assert key in bundle, key
    json.dumps(bundle, default=str)


def test_err_bundle_written_to_dir(tmp_path):
    g_conf().set("health_bundle_dir", str(tmp_path))
    try:
        eng = _bare_engine()
        eng.register("B", lambda ctx: H.check("B", H.ERR, "boom"))
        eng.evaluate()
        files = list(tmp_path.glob("health_bundle_*.json"))
        assert len(files) == 1
        assert json.loads(files[0].read_text())["reason"] == \
            "transition_to_HEALTH_ERR"
    finally:
        g_conf().set("health_bundle_dir", "")


# -- built-in device checks -------------------------------------------

def test_recompile_and_cache_miss_storm_checks():
    from ceph_tpu.utils.device_telemetry import telemetry
    telemetry().reset()
    tel = telemetry()
    eng = H.HealthEngine(publish_perf=False, bundle_on_err=False,
                         first_delta_absolute=True)
    rep = eng.evaluate()
    assert "DEVICE_RECOMPILE_STORM" not in rep["checks"]
    # the same signature compiling twice IS the storm signal
    tel.note_compile("storm_sig[1x1]", 0.01)
    tel.note_compile("storm_sig[1x1]", 0.01)
    rep = eng.evaluate()
    chk = rep["checks"]["DEVICE_RECOMPILE_STORM"]
    assert chk["severity"] == H.WARN
    assert any("storm_sig[1x1]" in d for d in chk["detail"])
    # cold-miss storm: a burst past the threshold raises; the
    # check clears once the window moves on
    tel.perf.inc("compile_cache_misses",
                 g_conf()["health_cache_miss_warn"])
    rep = eng.evaluate()
    assert rep["checks"]["COMPILE_CACHE_MISS_STORM"]["severity"] \
        == H.WARN
    rep = eng.evaluate()       # no new misses since last evaluate
    assert "COMPILE_CACHE_MISS_STORM" not in rep["checks"]
    telemetry().reset()


def test_engine_stall_check_raises_and_clears():
    from ceph_tpu.utils.device_telemetry import telemetry
    telemetry().reset()
    tel = telemetry()
    eng = H.HealthEngine(publish_perf=False, bundle_on_err=False)
    assert "ENGINE_STALL" not in eng.evaluate()["checks"]
    # saturated launch window, no retirement progress
    tel.note_engine_window(2)
    tel.note_engine_inflight(2)
    rep = eng.evaluate()
    assert rep["checks"]["ENGINE_STALL"]["severity"] == H.WARN
    # retirement progress clears the stall even while saturated
    tel.note_engine_retired()
    assert "ENGINE_STALL" not in eng.evaluate()["checks"]
    # drained window: no stall regardless of progress
    tel.note_engine_inflight(0)
    assert "ENGINE_STALL" not in eng.evaluate()["checks"]
    telemetry().reset()


def test_hbm_pressure_check_raises_and_clears():
    """ISSUE 7: the device engine's live-buffer gauges holding at
    warning level raise HBM_PRESSURE; reconciling them to zero (the
    retirement path) clears it."""
    from ceph_tpu.utils.device_telemetry import telemetry
    telemetry().reset()
    tel = telemetry()
    eng = H.HealthEngine(publish_perf=False, bundle_on_err=False)
    assert "HBM_PRESSURE" not in eng.evaluate()["checks"]
    limit = g_conf()["health_hbm_warn_bytes"]
    # scripted pressure: a window full of staged + in-flight bytes
    tel.note_hbm(staged_delta=limit // 2, inflight_delta=limit)
    rep = eng.evaluate()
    chk = rep["checks"]["HBM_PRESSURE"]
    assert chk["severity"] == H.WARN
    assert "live device buffer bytes" in chk["summary"]
    assert any("hbm_peak_live_bytes" in d for d in chk["detail"])
    # retirement reconciles the ledger: live -> 0 clears the check
    tel.note_hbm(staged_delta=-(limit // 2), inflight_delta=-limit,
                 retired=limit + limit // 2)
    assert tel.hbm_live_bytes() == 0
    assert "HBM_PRESSURE" not in eng.evaluate()["checks"]
    # the peak survives for forensics; the disable knob works
    assert tel.perf.get("hbm_peak_live_bytes") >= limit
    g_conf().set("health_hbm_warn_bytes", 0)
    try:
        tel.note_hbm(staged_delta=limit * 2)
        assert "HBM_PRESSURE" not in eng.evaluate()["checks"]
    finally:
        g_conf().set("health_hbm_warn_bytes", limit)
        tel.note_hbm(staged_delta=-limit * 2)
    telemetry().reset()


# -- optracker: true top-K slowest ------------------------------------

def test_optracker_topk_survives_mildly_slow_burst():
    from ceph_tpu.utils.optracker import OpTracker
    t = OpTracker(history_size=3, name="topk_test")
    record = t.create("record_slowest")
    record.start -= 100.0              # 100s old: the record holder
    record.finish()
    # a burst of mildly-slow ops that would FIFO-evict the record
    # under the old deque gating
    for i in range(10):
        op = t.create(f"mild{i}")
        op.start -= 5.0 + i * 0.1
        op.finish()
    slow = t.dump_slowest()
    assert slow["num_ops"] == 3
    descs = [o["desc"] for o in slow["ops"]]
    assert descs[0] == "record_slowest", descs
    # slowest first, strictly ordered
    ages = [o["age"] for o in slow["ops"]]
    assert ages == sorted(ages, reverse=True)


def test_all_slow_ops_aggregates_across_trackers():
    from ceph_tpu.utils.optracker import OpTracker, all_slow_ops
    t = OpTracker(complaint_time=0.0, name="agg_test")
    op = t.create("laggard")
    op.start -= 1.0
    try:
        slow = [s for s in all_slow_ops() if s[0] == "agg_test"]
        assert len(slow) == 1 and slow[0][1]["desc"] == "laggard"
    finally:
        op.finish()


# -- prometheus label escaping ----------------------------------------

def test_prometheus_escapes_hostile_daemon_names():
    import re

    from ceph_tpu.utils.prometheus import render_text
    hostile = 'bad"name\\x\ny'
    pc = collection().create(hostile)
    pc.add_u64_counter("evil")
    pc.inc("evil")
    try:
        text = render_text()
        assert 'daemon="bad\\"name\\\\x\\ny"' in text
        # every non-comment line still parses as one sample; an
        # OpenMetrics exemplar clause (`... # {trace_id="..."} v ts`,
        # ISSUE 10) may trail a histogram bucket sample — strip it
        # the way an exemplar-aware scraper does before matching. Other
        # families label by more than the daemon (the flow accounting's
        # `tenant`, once a test of the same process has served one)
        label = r'[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
        sample = re.compile(
            rf'^[a-zA-Z_][a-zA-Z0-9_]*(\{{{label}(,{label})*\}})? \S+$')
        for line in text.splitlines():
            if line and not line.startswith("#"):
                assert sample.match(line.split(" # ")[0]), line
    finally:
        collection().remove(hostile)


# -- dout ring over the asok ------------------------------------------

def test_log_dump_asok_honors_subsys_levels(tmp_path):
    from ceph_tpu.utils import dout
    from ceph_tpu.utils.admin_socket import (AdminSocket,
                                             register_common_commands)
    log = dout.Dout("hlth_test_subsys")
    dout.set_subsys_level("hlth_test_subsys", 1)
    log(1, "visible record")
    log(9, "debug-only record")
    asok = AdminSocket("health-test", directory=str(tmp_path))
    register_common_commands(asok)
    asok.start()
    try:
        out = asok_command(asok.path, "log dump")
        mine = [r for r in out["records"]
                if r["subsys"] == "hlth_test_subsys"]
        assert [r["level"] for r in mine] == [1]
        assert "visible record" in mine[0]["record"]
        # all=1 bypasses the level gate (the crash-dump view)
        out = asok_command(asok.path, "log dump", all=1)
        mine = [r for r in out["records"]
                if r["subsys"] == "hlth_test_subsys"]
        assert sorted(r["level"] for r in mine) == [1, 9]
    finally:
        asok.stop()


# -- the MiniCluster scenario (acceptance gate) -----------------------

def test_minicluster_stall_and_recompile_scenario():
    """Injecting a stall (blocked engine) and a forced recompile each
    flip the named check to WARN within one mgr tick; ``ceph health
    detail`` reports the structured check; the ERR-transition bundle
    carries counter history covering the event window."""
    from ceph_tpu.utils.device_telemetry import telemetry
    telemetry().reset()
    FR.reset_for_tests()
    with MiniCluster(n_osds=3) as c:
        c.create_pool("hp", pg_num=4, size=2)
        mgr = c.start_mgr(modules=("health",))
        mod = mgr.modules["health"]
        mod.recorder.sample(force=True)     # baseline sample
        tel = telemetry()
        # forced recompile: one signature compiles twice
        tel.note_compile("scenario_sig[8x3]", 0.01)
        tel.note_compile("scenario_sig[8x3]", 0.01)
        # blocked engine: launch window saturated, nothing retiring
        tel.note_engine_window(2)
        tel.note_engine_inflight(2)
        mod.recorder.sample(force=True)
        mod.tick()                          # ONE mgr tick
        rep = mod.engine.report()
        assert rep["checks"]["DEVICE_RECOMPILE_STORM"]["severity"] \
            == H.WARN
        assert rep["checks"]["ENGINE_STALL"]["severity"] == H.WARN
        # the mon merged the mgr report: health detail is structured
        deadline = time.monotonic() + 10
        detail = {}
        while time.monotonic() < deadline:
            code, outs, data = c.mon_cmd(prefix="health detail")
            assert code == 0
            detail = json.loads(data)
            if "DEVICE_RECOMPILE_STORM" in detail["checks"]:
                break
            mod.tick()
            time.sleep(0.2)
        assert detail["checks"]["DEVICE_RECOMPILE_STORM"][
            "severity"] == H.WARN
        assert detail["checks"]["ENGINE_STALL"]["severity"] == H.WARN
        assert detail["status"] == H.WARN
        # plain status carries the merged structured checks too
        code, _, data = c.mon_cmd(prefix="status")
        st = json.loads(data)
        assert "DEVICE_RECOMPILE_STORM" in st["health_checks"]
        assert st["health"].startswith("HEALTH_WARN")
        # the mgr asok serves the same structure
        out = asok_command(mgr.asok.path, "health detail")
        assert out["code"] == 0
        assert "ENGINE_STALL" in out["data"]["checks"]
        # ERR transition -> auto bundle, exactly once, with counter
        # history covering the event window
        mod.engine.register(
            "SCRIPTED_ERR",
            lambda ctx: H.check("SCRIPTED_ERR", H.ERR, "forced"))
        mod.recorder.sample(force=True)
        mod.tick()
        assert mod.engine.bundles_emitted == 1
        bundle = mod.engine.last_bundle
        series = bundle["counter_series"]
        assert len(series) >= 2
        recompiles = [s["counters"].get("device.recompiles", 0)
                      for s in series]
        assert max(recompiles) >= 1, \
            "bundle history must cover the recompile event"
        assert bundle["report"]["status"] == H.ERR
        mod.tick()                          # still ERR: no re-emit
        assert mod.engine.bundles_emitted == 1
        tel.reset()
