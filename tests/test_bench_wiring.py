"""Tier-1-safe smoke test for the BENCH pipeline wiring: bench.py must
import cleanly under JAX_PLATFORMS=cpu (the driver environment minus
the chip) and every metric line it emits must round-trip json.loads
INCLUDING the telemetry snapshot field — the schema the driver's
last-JSON-line reader and the BENCH history depend on."""

import json

import pytest


def test_bench_imports_cleanly():
    """Importing the module must not touch a device or run main()."""
    import bench
    assert callable(bench.main)
    assert bench.TOTAL_BUDGET < 870      # inside the driver timeout


def test_metric_line_roundtrips_with_telemetry(capsys):
    import bench

    # seed some real telemetry so the snapshot is non-trivial
    from ceph_tpu.utils.device_telemetry import telemetry
    telemetry().note_compile("bench_wiring_smoke", 0.01)

    bench.emit("smoke_metric", {"value": 1.23, "unit": "GB/s"})
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.strip()]
    rec = json.loads(lines[-1])
    assert rec["metric"] == "smoke_metric"
    assert rec["value"] == 1.23
    assert isinstance(rec["telemetry"], dict)
    assert rec["telemetry"].get("compiles", 0) >= 1
    # every metric line carries a structured health brief that
    # round-trips json.loads (HEALTH_OK-shaped on a clean CPU run)
    assert isinstance(rec["health"], dict)
    assert rec["health"]["status"] in ("HEALTH_OK", "HEALTH_WARN",
                                       "HEALTH_ERR")
    assert isinstance(rec["health"]["checks"], dict)
    # the combined (historical-schema) line carries both too
    combined = bench._combined(any_contended=False)
    rec2 = json.loads(json.dumps(combined))
    assert isinstance(rec2["telemetry"], dict)
    assert rec2["health"]["status"].startswith("HEALTH")
    bench._RESULTS.pop("smoke_metric", None)


def test_telemetry_snapshot_degrades_to_empty(monkeypatch):
    """A telemetry fault must never cost a metric line."""
    import bench

    import ceph_tpu.utils.device_telemetry as dt

    def boom():
        raise RuntimeError("telemetry down")

    monkeypatch.setattr(dt, "telemetry", boom)
    assert bench._telemetry_snapshot() == {}


def test_health_snapshot_degrades_to_ok_shape(monkeypatch):
    """A health-engine fault must never cost a metric line: the field
    degrades to a HEALTH_OK-shaped brief, not an exception."""
    import bench

    import ceph_tpu.mgr.health as hm

    def boom():
        raise RuntimeError("health engine down")

    monkeypatch.setattr(hm, "device_health_brief", boom)
    assert bench._health_snapshot() == {"status": "HEALTH_OK",
                                        "checks": {}}


class _StubIo:
    """Minimal io surface _bench drives (write_full/read/remove)."""

    def __init__(self):
        self.objects = {}

    def write_full(self, oid, data):
        self.objects[oid] = bytes(data)
        return 1

    def read(self, oid):
        return self.objects[oid]

    def remove(self, oid):
        self.objects.pop(oid, None)


def test_cluster_bench_line_carries_p50_p99_and_stage_breakdown():
    """ISSUE 6 satellites, pinned: cluster_bench metric lines carry
    p50_ms/p99_ms (from the same timed ops, zero extra budget) and a
    stage_breakdown — and the whole line round-trips json.loads."""
    from ceph_tpu.bench import cluster_bench
    from ceph_tpu.tools.rados_cli import _bench
    from ceph_tpu.utils.dataplane import dataplane

    # seed the stage registry so the breakdown is non-trivial
    dataplane().record_stages([("wire", 0.001),
                               ("commit_wait", 0.003)])
    dataplane().perf.hinc("op_total_us", 4000.0)
    dataplane().perf.tinc("op_total", 0.004)
    dataplane().perf.inc("ops_timed")

    out = _bench(_StubIo(), 0.05, "write", 1024, 2)
    cluster_bench.attach_stage_breakdown(out)
    rec = json.loads(json.dumps(out))
    assert rec["p99_ms"] >= rec["p50_ms"] > 0
    bd = rec["stage_breakdown"]
    assert bd["ops"] >= 1
    assert "wire" in bd["stages"]
    assert bd["stages"]["wire"]["share_pct"] >= 0
    assert "coverage_pct" in bd
    # ISSUE 14: the commit-path store brief rides the same line
    assert "store" in rec
    assert "txns" in rec["store"] and "fsyncs" in rec["store"]


def test_stage_breakdown_degrades_to_empty(monkeypatch):
    """A dataplane fault must never cost a cluster_bench line."""
    from ceph_tpu.bench import cluster_bench

    import ceph_tpu.utils.dataplane as dp

    def boom():
        raise RuntimeError("dataplane down")

    monkeypatch.setattr(dp, "dataplane", boom)
    out = cluster_bench.attach_stage_breakdown({"value": 1})
    assert out["stage_breakdown"] == {}
    json.loads(json.dumps(out))


def test_cost_fields_roofline_next_to_measured(capsys, monkeypatch):
    """ISSUE 7 satellite: device metric lines carry cost_flops /
    cost_bytes from the compiled cost analysis of the exact step —
    and the whole line still round-trips json. A CPU run has no
    roofline share of a device to give, so no roofline_GBps here."""
    import time

    import jax.numpy as jnp

    import bench

    monkeypatch.setattr(bench, "_T0", time.perf_counter())

    def step(x):
        return (x.astype(jnp.float32) * 2).sum()

    x = jnp.zeros((1 << 14,), jnp.uint8)
    fields = bench._cost_fields(step, (x,), 1 << 14,
                                "bench[wiring_smoke]")
    # CPU XLA reports cost analysis; if a backend ever stops, the
    # contract is graceful degradation to {}
    if fields:
        assert fields["cost_flops"] > 0
        assert fields["cost_bytes"] > 0
        assert "roofline_GBps" not in fields
        # the signature landed in the device cost table
        from ceph_tpu.utils.device_telemetry import telemetry
        snap = telemetry().snapshot()
        assert "bench[wiring_smoke]" in snap["costs_by_signature"]
    line = {"value": 1.0, "unit": "GB/s"}
    line.update(fields)
    bench.emit("cost_smoke", line)
    out = [ln for ln in capsys.readouterr().out.splitlines()
           if ln.strip()]
    rec = json.loads(out[-1])
    assert rec["metric"] == "cost_smoke"
    if fields:
        assert rec["cost_bytes"] == fields["cost_bytes"]
    bench._RESULTS.pop("cost_smoke", None)


def test_cost_fields_degrade_and_respect_deadline(monkeypatch):
    """A cost-model fault returns {} (never costs a metric line), and
    a nearly-spent global deadline skips the extra compile entirely
    (the test_measure_guard budget identity stays intact)."""
    import time

    import bench
    from ceph_tpu.ops import cost_model

    monkeypatch.setattr(bench, "_T0", time.perf_counter())

    def boom(*a, **k):
        raise RuntimeError("cost model down")

    monkeypatch.setattr(cost_model, "bench_fields", boom)
    assert bench._cost_fields(lambda x: x, (1,), 10, "sig") == {}
    # deadline nearly spent: the helper must not even try
    monkeypatch.setattr(
        bench, "_T0",
        time.perf_counter() - bench.TOTAL_BUDGET + 1.0)
    called = []
    monkeypatch.setattr(cost_model, "bench_fields",
                        lambda *a, **k: called.append(1) or {})
    assert bench._cost_fields(lambda x: x, (1,), 10, "sig") == {}
    assert not called, "cost analysis ran inside the compile tail"


def test_degraded_rows_emit_parseable_lines(capsys, monkeypatch):
    """ISSUE 8: the two degraded-mode serving rows. The GB/s row
    measures the exact signature-grouped decode matvec the batched
    decode-on-read route launches (bit-exactness gate inside), the
    p99 row times individual blocked launches of the same program —
    both must land parseable lines with the coalescing factor on
    them."""
    import time

    import bench

    monkeypatch.setitem(bench.BUDGETS, "degraded_read", (2.0, 0.0))
    monkeypatch.setitem(bench.BUDGETS, "degraded_p99", (1.0, 0.0))
    monkeypatch.setattr(bench, "_T0", time.perf_counter())
    monkeypatch.setattr(bench, "TOTAL_BUDGET", 60.0)

    contended = bench._bench_degraded_read(lambda *a, **k: None, {})
    assert isinstance(contended, bool)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.strip()]
    recs = {json.loads(ln)["metric"]: json.loads(ln) for ln in lines}
    read = recs["degraded_read_GBps"]
    assert "error" not in read, read
    assert read["value"] > 0
    assert read["unit"] == "GB/s"
    assert read["objects_per_flush"] == bench.DEGRADED_OBJECTS
    assert isinstance(read["telemetry"], dict)
    p99 = recs["degraded_p99_ms"]
    assert "error" not in p99, p99
    assert p99["value"] > 0
    assert p99["unit"] == "ms"
    assert p99["p50_ms"] <= p99["value"]
    assert p99["samples"] >= 1
    # the per-object floor is the flush latency amortized over the
    # coalesced batch — the number the QoS bar is derived from
    assert p99["per_object_p99_ms"] == pytest.approx(
        p99["value"] / bench.DEGRADED_OBJECTS, rel=0.01)
    # the combined historical line carries both families
    combined = bench._combined(any_contended=False)
    assert "degraded_read_value" in combined
    assert "degraded_p99_value" in combined
    json.loads(json.dumps(combined))
    bench._RESULTS.pop("degraded_read_GBps", None)
    bench._RESULTS.pop("degraded_p99_ms", None)


def test_multichip_metric_emits_parseable_line(capsys, monkeypatch):
    """The round-9 acceptance gate, ISSUE 12 edition: on >= 2
    devices (the conftest's 8 virtual CPU devices here) bench's
    multichip family measures the real sharded encode step AND its
    decode sibling, and BOTH emitted lines parse with a positive
    GB/s value, n_devices, and a telemetry snapshot."""
    import time

    import bench

    # shrink sampling so the smoke test stays seconds, not the
    # driver-scale budget; the deadline is re-anchored to NOW (the
    # module-level _T0 is the import time of the whole test session)
    monkeypatch.setitem(bench.BUDGETS, "multichip_encode", (2.0, 0.0))
    monkeypatch.setitem(bench.BUDGETS, "multichip_decode", (2.0, 0.0))
    monkeypatch.setattr(bench, "_T0", time.perf_counter())
    monkeypatch.setattr(bench, "TOTAL_BUDGET", 60.0)

    contended = bench._bench_multichip(lambda *a, **k: None, {})
    assert isinstance(contended, bool)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.strip()]
    recs = {json.loads(ln)["metric"]: json.loads(ln)
            for ln in lines}
    for row in ("multichip_encode_GBps", "multichip_decode_GBps"):
        rec = recs[row]
        assert "skipped" not in rec and "error" not in rec, rec
        assert rec["n_devices"] >= 2
        assert rec["value"] > 0
        assert rec["unit"] == "GB/s"
        assert rec["compile_path"] in ("pjit", "shard_map")
        assert isinstance(rec["telemetry"], dict)
    # the mesh steps dispatched through the accounted entry
    assert recs["multichip_decode_GBps"]["telemetry"].get(
        "mesh_dispatches", 0) >= 2
    # the warmup compiles are ledger-accounted under the bench labels
    from ceph_tpu.utils.device_telemetry import telemetry
    assert telemetry().compile_count("bench[multichip_encode]") >= 1
    assert telemetry().compile_count("bench[multichip_decode]") >= 1
    bench._RESULTS.pop("multichip_encode_GBps", None)
    bench._RESULTS.pop("multichip_decode_GBps", None)
