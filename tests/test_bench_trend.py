"""ISSUE 10 satellite: tools/bench_trend.py — cross-round bench
comparison with a >10% regression flag, runnable in tier-1 on round
files built under tmp_path in the checked-in format."""

import json
import os

from ceph_tpu.tools import bench_trend


def _round_file(tmp_path, name, metrics, rc=0):
    tail = "\n".join(
        json.dumps({"metric": m, "value": v, "unit": "GB/s",
                    "telemetry": {"nested": {"ok": 1}}})
        for m, v in metrics.items())
    path = tmp_path / name
    path.write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": rc, "tail": tail,
         "parsed": None}))
    return str(path)


def test_runs_on_checked_in_rounds(capsys, tmp_path):
    """Round files as the driver checks them in, found by
    default_files: parse every round (incl. an rc=124 timeout round
    with zero metrics, whose tail is a bare warning), print the table
    + one JSON line."""
    _round_file(tmp_path, "BENCH_r01.json",
                {"ec_encode_rs_k8m3_device_GBps": 100.0})
    _round_file(tmp_path, "BENCH_r02.json",
                {"ec_encode_rs_k8m3_device_GBps": 104.0,
                 "ec_decode_rs_k8m3_device_GBps": 40.0})
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"n": 3, "cmd": "bench", "rc": 124, "parsed": None,
         "tail": "WARNING: platform is experimental\n"}))
    files = bench_trend.default_files(str(tmp_path))
    assert [os.path.basename(f) for f in files] == [
        "BENCH_r01.json", "BENCH_r02.json", "BENCH_r03.json"]
    assert bench_trend.main(files) == 0
    out = capsys.readouterr().out
    json_line = [ln for ln in out.splitlines()
                 if ln.startswith('{"bench_trend"')]
    assert len(json_line) == 1
    report = json.loads(json_line[0])["bench_trend"]
    assert len(report["rounds"]) == len(files)
    # the r01 metric is present and tracked across rounds
    assert "ec_encode_rs_k8m3_device_GBps" in report["metrics"]
    row = report["metrics"]["ec_encode_rs_k8m3_device_GBps"]
    assert len(row["values"]) >= 2
    assert "delta_vs_best_pct" in row
    # a timeout round parses to zero metrics without crashing
    by_round = {r["round"]: r for r in report["rounds"]}
    assert by_round["BENCH_r03"]["metrics"] == 0
    assert by_round["BENCH_r03"]["rc"] == 124


def test_regression_flag_direction_aware(tmp_path):
    """>10% drop on a throughput metric regresses; >10% RISE on a
    latency metric regresses; gains never flag."""
    files = [
        _round_file(tmp_path, "BENCH_r01.json",
                    {"enc_GBps": 100.0, "lat_p99_ms": 10.0,
                     "steady_GBps": 50.0}),
        _round_file(tmp_path, "BENCH_r02.json",
                    {"enc_GBps": 80.0, "lat_p99_ms": 12.0,
                     "steady_GBps": 52.0}),
    ]
    report = bench_trend.trend(files, threshold_pct=10.0)
    assert report["metrics"]["enc_GBps"]["regressed"] is True
    assert report["metrics"]["lat_p99_ms"]["regressed"] is True
    assert report["metrics"]["steady_GBps"]["regressed"] is False
    assert sorted(report["regressions"]) == ["enc_GBps",
                                             "lat_p99_ms"]
    # deltas are signed better-positive in both directions
    assert report["metrics"]["enc_GBps"]["delta_vs_best_pct"] == -20.0
    assert report["metrics"]["lat_p99_ms"]["delta_vs_best_pct"] \
        == -20.0
    assert report["metrics"]["steady_GBps"]["delta_vs_best_pct"] > 0


def test_latest_vs_best_prior_not_just_previous(tmp_path):
    """The flag compares against the BEST earlier round: a metric
    that fell off its best two rounds ago still regresses even if
    flat since."""
    files = [
        _round_file(tmp_path, "BENCH_r01.json", {"x_GBps": 100.0}),
        _round_file(tmp_path, "BENCH_r02.json", {"x_GBps": 60.0}),
        _round_file(tmp_path, "BENCH_r03.json", {"x_GBps": 61.0}),
    ]
    report = bench_trend.trend(files)
    assert report["metrics"]["x_GBps"]["regressed"] is True
    assert report["metrics"]["x_GBps"]["best_prior"] == 100.0


def test_strict_exit_code(tmp_path, capsys):
    files = [
        _round_file(tmp_path, "BENCH_r01.json", {"x_GBps": 100.0}),
        _round_file(tmp_path, "BENCH_r02.json", {"x_GBps": 50.0}),
    ]
    assert bench_trend.main(files) == 0
    assert bench_trend.main(files + ["--strict"]) == 2
    capsys.readouterr()


def test_missing_rounds_tolerated(tmp_path):
    """A metric absent from some rounds compares over the rounds it
    appeared in; a garbled file reports an error row, not a crash."""
    bad = tmp_path / "BENCH_r02.json"
    bad.write_text("not json at all")
    files = [
        _round_file(tmp_path, "BENCH_r01.json", {"a_GBps": 10.0}),
        str(bad),
        _round_file(tmp_path, "BENCH_r03.json",
                    {"a_GBps": 10.5, "b_GBps": 3.0}),
    ]
    report = bench_trend.trend(files)
    assert report["metrics"]["a_GBps"]["regressed"] is False
    assert "regressed" not in report["metrics"]["b_GBps"]
    assert report["rounds"][1]["metrics"] == 0


def test_multichip_direction_pins(tmp_path):
    """ISSUE 12: the two multichip mesh rows carry explicit DIRECTION
    entries (higher is better) — a drop gates as a regression the
    moment numbers exist, and the name heuristic cannot silently
    reclassify them."""
    for row in ("multichip_encode_GBps", "multichip_decode_GBps",
                "multichip_scaling"):
        assert bench_trend.DIRECTIONS[row] == "higher"
        assert not bench_trend.lower_is_better(row)
    files = [
        _round_file(tmp_path, "BENCH_r01.json",
                    {"multichip_encode_GBps": 10.0,
                     "multichip_decode_GBps": 8.0}),
        _round_file(tmp_path, "BENCH_r02.json",
                    {"multichip_encode_GBps": 4.0,
                     "multichip_decode_GBps": 8.1}),
    ]
    report = bench_trend.trend(files)
    assert report["metrics"]["multichip_encode_GBps"]["regressed"]
    assert "multichip_encode_GBps" in report["regressions"]
    assert not report["metrics"]["multichip_decode_GBps"]["regressed"]


def test_multi_tenant_fairness_direction_pin(tmp_path):
    """ISSUE 20: the fairness row's value is a Jain index — unitless,
    no suffix the name heuristic could read — and it must gate DOWN
    as a regression (silently starving MORE tenants shrinks it)."""
    assert bench_trend.DIRECTIONS["multi_tenant_fairness"] == "higher"
    assert not bench_trend.lower_is_better("multi_tenant_fairness")
    files = [
        _round_file(tmp_path, "BENCH_r01.json",
                    {"multi_tenant_fairness": 0.67}),
        _round_file(tmp_path, "BENCH_r02.json",
                    {"multi_tenant_fairness": 0.34}),
    ]
    report = bench_trend.trend(files)
    assert report["metrics"]["multi_tenant_fairness"]["regressed"]
    assert "multi_tenant_fairness" in report["regressions"]


def test_tuned_vs_fixed_mode(capsys):
    """ISSUE 13: --tuned-vs-fixed runs the deterministic controller
    comparison (bench/tuner_sim) — human table + one machine line —
    and the tuned loop beats every fixed vector (the acceptance
    verdict test_tuner_scenario pins in depth). --strict turns a
    tuned loss into exit 2, same convention as a metric regression."""
    import json

    rc = bench_trend.main(["--tuned-vs-fixed", "--seed", "7",
                           "--strict"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "tuned control loop vs fixed knob vectors" in out
    line = [ln for ln in out.splitlines()
            if ln.startswith('{"tuner_sim"')][-1]
    doc = json.loads(line)["tuner_sim"]
    assert doc["tuned_beats_all"] is True
    assert set(doc["verdicts"]) == {"default", "read_opt",
                                    "burst_opt", "degraded_opt"}
    for v in doc["verdicts"].values():
        assert v["tuned_wins"]


def test_tuner_objective_uses_benchtrend_directions():
    """The tuner's revert judgment reuses THIS module's direction
    logic: p99 regresses up, throughput down."""
    assert bench_trend.lower_is_better("tuner_p99_ms")
    assert not bench_trend.lower_is_better("tuner_MBps")
