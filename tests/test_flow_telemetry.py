"""Tenant X-ray (ISSUE 20): the flows registry — per-flow cost
attribution planes, fairness windows with Jain's index, starvation
streak detection feeding the FLOW_STARVATION health check, SLO error-
budget burn rates, per-tenant prometheus series with escaped labels,
and the flows-off literal-NOOP contract (the kill switch must cost
one cached-bool read, materialize nothing, and tag nothing).
"""

import concurrent.futures
import json
import threading

import pytest

from ceph_tpu.mgr import health as H
from ceph_tpu.utils import flow_telemetry as FT
from ceph_tpu.utils import prometheus
from ceph_tpu.utils.config import g_conf
from ceph_tpu.utils.perf_counters import collection


@pytest.fixture
def flows(monkeypatch):
    """A fresh, explicitly-enabled registry per test (the env kill
    switch must not leak in from the session)."""
    monkeypatch.delenv("CEPH_TPU_FLOWS", raising=False)
    FT.reset_for_tests()
    FT.clear_current_flow()
    try:
        yield FT.telemetry()
    finally:
        FT.clear_current_flow()
        FT.reset_for_tests()


# -- plane 1: cost attribution ------------------------------------------

def test_op_attribution_and_flow_table(flows):
    flows.note_op("acme", bytes_in=1000)
    flows.note_op("acme", bytes_in=24)
    flows.note_op_done("acme", bytes_out=512, latency_s=0.004,
                       stages=[("queue_wait", 0.001),
                               ("commit_wait", 0.002),
                               ("queue_wait", 0.0005)])
    flows.note_op("globex", bytes_in=64)
    flows.note_op("", bytes_in=7)          # unattributed bucket
    c = flows.perf.dump()
    assert c["ops"] == 3
    assert c["bytes_in"] == 1088
    assert c["bytes_out"] == 512
    assert c["unattributed_ops"] == 1 and c["unattributed_bytes"] == 7
    table = flows.flow_table()["flows"]
    acme = table["acme"]
    assert acme["ops"] == 2
    assert acme["bytes_in"] == 1024 and acme["bytes_out"] == 512
    assert acme["p99_ms"] == pytest.approx(4.0, abs=0.01)
    # repeated stages accumulate; units are ms in the view
    assert acme["stage_wait_ms"]["queue_wait"] == pytest.approx(1.5)
    assert acme["stage_wait_ms"]["commit_wait"] == pytest.approx(2.0)
    att = flows.attribution()
    assert att["ops_total"] == 4 and att["ops_attributed"] == 3
    assert att["ops_pct"] == 75.0
    assert att["by_flow"]["acme"]["ops"] == 2


def test_fsync_amortized_by_txn_bytes_and_flush_group_shares(flows):
    flows.note_store_txn("acme", 300)
    flows.note_store_txn("globex", 100)
    flows.note_fsync()
    flows.note_fsync()                      # empty window: no shares
    table = flows.flow_table()["flows"]
    assert table["acme"]["fsync_share"] == pytest.approx(0.75)
    assert table["globex"]["fsync_share"] == pytest.approx(0.25)
    assert table["acme"]["store_txn_bytes"] == 300
    assert flows.perf.dump()["fsyncs"] == 2
    # one FlushGroup, occupancy split by contributed bytes
    flows.note_engine_staged("acme", 4096)
    flows.note_flush_group({"acme": 3 << 20, "globex": 1 << 20,
                            "": 1234})      # unattributed share drops
    table = flows.flow_table()["flows"]
    assert table["acme"]["flush_share"] == pytest.approx(0.75, abs=0.01)
    assert table["acme"]["engine_staged_bytes"] == 4096
    assert flows.perf.dump()["flush_groups"] == 1


def test_capture_flow_rides_the_wq_handoff(flows):
    """The producer thread's label survives the queue seam: capture
    at enqueue, re-install at grant (charging one seat credit),
    clear at done — the ShardedOpWQ contract."""
    with FT.flow_scope("acme"):
        fctx = FT.capture_flow("client")
    assert FT.current_flow() is None
    assert fctx == ("acme", "client")

    seen = {}

    def worker():
        FT.note_wq_grant(fctx)
        seen["flow"] = FT.current_flow()
        FT.note_wq_done(fctx)
        seen["after"] = FT.current_flow()

    t = threading.Thread(target=worker)
    t.start()
    t.join(5)
    assert seen == {"flow": "acme", "after": None}
    table = flows.flow_table()["flows"]
    assert table["acme"]["queue_credit"] == {"client": 1}
    assert flows.perf.dump()["queue_credit"] == 1


def test_flow_cap_drops_are_counted(flows):
    for i in range(FT._MAX_FLOWS + 5):
        flows.note_op(f"t{i:03d}", bytes_in=1)
    view = flows.flow_table()
    assert len(view["flows"]) == FT._MAX_FLOWS
    assert view["flows_dropped"] == 5


def test_txn_nbytes_estimates_payload():
    assert FT.txn_nbytes(b"12345") == 5

    class _Txn:
        ops = [("write", "oid", b"x" * 100),
               ("setattrs", "oid", {"k1": b"v1", "k2": b"v2"})]

    assert FT.txn_nbytes(_Txn()) == 100 + len("k1v1k2v2")


# -- plane 2: fairness + starvation -------------------------------------

def test_jain_index_math():
    assert FT.jain_index([1.0, 1.0, 1.0, 1.0]) == pytest.approx(1.0)
    # one of three served, two starved: (1)^2 / (3 * 1) = 1/3
    assert FT.jain_index([1.0, 0.0, 0.0]) == pytest.approx(1 / 3)
    assert FT.jain_index([]) == 1.0


def test_fairness_shares_and_service_ratios(flows):
    for _ in range(8):
        flows.note_demand("acme")
    for _ in range(2):
        flows.note_served("acme")
    for _ in range(2):
        flows.note_demand("globex")
        flows.note_served("globex")
    fair = flows.fairness()
    assert fair["flows"]["acme"]["service_ratio"] == pytest.approx(0.25)
    assert fair["flows"]["acme"]["demand_share"] == pytest.approx(0.8)
    assert fair["flows"]["acme"]["served_share"] == pytest.approx(0.5)
    assert fair["flows"]["globex"]["service_ratio"] == pytest.approx(1.0)
    assert 0 < fair["jain_index"] < 1


def test_starvation_streaks_advance_and_reset(flows):
    need = int(g_conf()["flow_starvation_windows"])
    for _ in range(need):
        flows.note_demand("acme", ops=4)
        flows.note_served("acme", ops=1)     # ratio 0.25 < floor 0.5
        flows.note_demand("globex", ops=4)
        flows.note_served("globex", ops=4)
        win = flows.roll_window()
        assert "acme" in win["starved"]
        assert "globex" not in win["starved"]
    assert flows.starved_flows() == {"acme": need}
    assert flows.perf.dump()["starved_windows"] == need
    # one healthy window clears the streak (consecutive, not total)
    flows.note_demand("acme", ops=2)
    flows.note_served("acme", ops=2)
    flows.roll_window()
    assert flows.starved_flows() == {}
    # idle flows (no windowed demand) never score starved
    flows.roll_window()
    assert flows.starved_flows() == {}


def test_flow_starvation_health_check_is_err(flows):
    """The detector feeds the health engine: a flow past the streak
    threshold raises FLOW_STARVATION at ERR severity (the bundle/
    autopsy trigger class), with per-flow evidence in the detail."""
    eng = H.HealthEngine(publish_perf=False, bundle_on_err=False)
    for name, _fn in H.BUILTIN_CHECKS:
        if name != "FLOW_STARVATION":
            eng.unregister(name)
    assert eng.evaluate()["status"] == H.OK
    for _ in range(int(g_conf()["flow_starvation_windows"])):
        flows.note_demand("acme", ops=4)
        flows.note_served("acme", ops=0)
        flows.roll_window()
    rep = eng.evaluate()
    assert rep["status"] == H.ERR
    chk = rep["checks"]["FLOW_STARVATION"]
    assert chk["severity"] == H.ERR
    assert "acme" in chk["summary"] or \
        any("acme" in d for d in chk["detail"])
    assert any("jain_index" in d for d in chk["detail"])


# -- plane 3: SLO burn ---------------------------------------------------

def test_slo_burn_rate_from_error_budget(flows):
    flows.set_slo("acme", p99_ms=10.0, error_budget=0.1)
    for _ in range(9):
        flows.note_op_done("acme", latency_s=0.001)
    flows.note_op_done("acme", latency_s=0.050)   # one breach
    row = flows.slo_table()["acme"]
    assert row["ops"] == 10 and row["breaches"] == 1
    assert row["error_rate"] == pytest.approx(0.1)
    assert row["burn_rate"] == pytest.approx(1.0)   # exactly at budget
    assert flows.perf.dump()["slo_breaches"] == 1
    # snapshot carries every plane for dump_flows
    snap = flows.snapshot()
    for section in ("glossary", "counters", "flows", "fairness",
                    "starvation", "slo", "attribution"):
        assert section in snap, section


# -- prometheus ----------------------------------------------------------

def test_prometheus_tenant_labels_escaped(flows):
    """Tenant names are user-controlled: quotes, backslashes and
    newlines must be escaped per the exposition spec or one hostile
    label corrupts the whole scrape."""
    evil = 'rgw:ac"me\\corp\nx'
    flows.note_op(evil, bytes_in=10)
    flows.note_demand(evil)
    flows.note_served(evil)
    text = prometheus.render_text()
    esc = 'rgw:ac\\"me\\\\corp\\nx'
    assert f'ceph_tpu_flows_ops_total{{tenant="{esc}"}} 1' in text
    assert "\nx\"" not in text          # no raw newline inside a label
    assert "# TYPE ceph_tpu_flows_ops_total counter" in text
    assert "# TYPE ceph_tpu_flows_served_share gauge" in text


def test_prometheus_flows_section_absent_without_registry(monkeypatch):
    """The exporter must not instantiate the registry as a side
    effect of a scrape."""
    monkeypatch.delenv("CEPH_TPU_FLOWS", raising=False)
    FT.reset_for_tests()
    text = prometheus.render_text()
    assert "ceph_tpu_flows_" not in text
    assert FT.telemetry_if_exists() is None


# -- the kill switch: flows off == literal NOOP --------------------------

def test_flows_off_is_literal_noop(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_FLOWS", "0")
    FT.reset_for_tests()
    try:
        assert not FT.enabled()
        # the attribution seam hands back None: call sites skip
        assert FT.flows_if_active() is None
        # context installs don't stick, captures don't materialize
        FT.set_current_flow("acme")
        assert FT.current_flow() is None
        assert FT.capture_flow("client") is None
        with FT.flow_scope("acme"):
            assert FT.current_flow() is None
        FT.note_wq_grant(None)
        FT.note_wq_done(None)
        # nothing materialized: no registry, no counters, no scrape
        assert FT.telemetry_if_exists() is None
        assert "flows" not in collection().dump()
        assert "ceph_tpu_flows_" not in prometheus.render_text()
    finally:
        monkeypatch.delenv("CEPH_TPU_FLOWS", raising=False)
        FT.reset_for_tests()


def test_flows_off_client_ops_carry_no_label(monkeypatch):
    """End-to-end NOOP pin: with the switch off, a tagged ioctx still
    submits ops but the wire field stays empty and no flows registry
    appears anywhere in the process."""
    from ceph_tpu.qa.cluster import MiniCluster

    monkeypatch.setenv("CEPH_TPU_FLOWS", "0")
    FT.reset_for_tests()
    try:
        with MiniCluster(n_osds=3) as cluster:
            cluster.create_ec_pool("noop", k=2, m=1, pg_num=4)
            io = cluster.client().open_ioctx("noop")
            io.op_timeout = 30.0
            io.set_flow("acme")
            io.write_full("o", b"dark" * 64)
            assert io.read("o") == b"dark" * 64
        assert FT.telemetry_if_exists() is None
        assert "flows" not in collection().dump()
    finally:
        monkeypatch.delenv("CEPH_TPU_FLOWS", raising=False)
        FT.reset_for_tests()


def test_multi_tenant_cluster_burst_attributes_flows(flows):
    """The labels survive the whole served path: a multi-tenant burst
    against a MiniCluster attributes per-tenant ops, bytes both ways
    and the store-txn bytes of its EC sub-writes, with >= 95 %
    coverage of ops and bytes — witness-armed, since the attribution
    seams run inside the op-wq workers and the engine's continuations
    and must not add a blocking edge the lock discipline forbids."""
    from ceph_tpu.analysis import lock_witness as lw
    from ceph_tpu.qa.cluster import MiniCluster

    env_armed = lw.env_enabled()
    if not env_armed:
        lw.enable()
    try:
        with MiniCluster(n_osds=3) as cluster:
            cluster.create_ec_pool("mt", k=2, m=1, pg_num=4,
                                   backend="jax")
            client = cluster.client()
            warm = client.open_ioctx("mt")
            warm.op_timeout = 30.0
            warm.set_flow("warmup")
            warm.write_full("warm", b"w" * 1024)
            flows.reset()
            tenants = ("acme", "globex", "initech")
            ios = []
            for t in tenants:
                tio = client.open_ioctx("mt")
                tio.op_timeout = 30.0
                tio.set_flow(t)
                ios.append(tio)

            def burst(i):
                tio = ios[i % len(ios)]
                tio.write_full(f"{tenants[i % 3]}_{i}", b"x" * 4096)
                assert tio.read(f"{tenants[i % 3]}_{i}") \
                    == b"x" * 4096

            with concurrent.futures.ThreadPoolExecutor(4) as pool:
                list(pool.map(burst, range(18)))

            tel = FT.telemetry()
            table = tel.flow_table()["flows"]
            for t in tenants:
                row = table.get(t)
                assert row is not None, (t, sorted(table))
                # each tenant: 6 writes + 6 reads attributed, bytes
                # both directions, and its EC sub-writes' store txn
                # bytes charged back to it on the serving OSDs
                assert row["ops"] >= 12, (t, row)
                assert row["bytes_in"] >= 6 * 4096, (t, row)
                assert row["bytes_out"] >= 6 * 4096, (t, row)
                assert row["store_txn_bytes"] > 0, (t, row)
            att = tel.attribution()
            assert att["ops_pct"] >= 95.0, att
            assert att["bytes_pct"] >= 95.0, att
    finally:
        if not env_armed:
            rep = lw.report()
            bad = lw.unacknowledged(rep)
            lw.disable()
            lw.reset()
            assert not bad, (
                "unacknowledged witness findings on the multi-tenant "
                "burst: " + json.dumps(bad, indent=1)[:2000])
