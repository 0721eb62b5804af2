"""The contended-plateau guard in bench measurement (round-5).

An early bench round recorded a 250x collapse with a tight spread and
no flag: under a persistently contended window the best slope IS the
contended slope and the low plateau self-confirms. The guard compares
the plateau against the persisted last-good slope and (a) extends
sampling hunting for a contention gap, (b) returns contended=True if
the budget runs out still slow — never a silent collapse.
Reference ethos: the benchmark ships its own validity recipe
(src/test/erasure-code/ceph_erasure_code_benchmark.cc:343-356).
"""

import json

import jax.numpy as jnp
import numpy as np

from ceph_tpu.bench import measure


def _step(x):
    return x + jnp.uint32(1)


def _x0():
    # large enough that a loop iteration costs real, measurable time —
    # tiny arrays give noise-dominated (sometimes negative) slopes and
    # the estimator rightly refuses them all
    return jnp.zeros((1 << 20,), jnp.uint32)


def test_clean_run_not_contended():
    # a ~1s budget samples several rounds: one noise-negative slope
    # (possible on a loaded CI host) must not fail the test
    slope, spread, n, contended = measure.stable_best_slope(
        _step, _x0(), min_traffic_bytes=1, counts=(2, 6),
        time_budget=1.0, stable_n=1, sleep=0.0)
    assert slope > 0
    assert not contended


def test_plateau_slower_than_expectation_is_flagged():
    # expectation: each iteration should take ~0 seconds (impossibly
    # fast last-good) -> every measured plateau looks >3x slower ->
    # the guard must extend, then flag contended rather than accept
    slope, spread, n, contended = measure.stable_best_slope(
        _step, _x0(), min_traffic_bytes=1, counts=(2, 6),
        time_budget=0.2, stable_n=1, sleep=0.0,
        expect_slope=1e-12, extended_budget=0.5)
    assert contended, "a plateau 3x+ slower than last-good must be flagged"


def test_expectation_met_is_clean():
    # expectation: 10 seconds per iteration (far slower than reality)
    # -> measured slope beats it -> clean
    slope, spread, n, contended = measure.stable_best_slope(
        _step, _x0(), min_traffic_bytes=1, counts=(2, 6),
        time_budget=1.0, stable_n=1, sleep=0.0,
        expect_slope=10.0)
    assert not contended


def test_contended_extension_keeps_sampling(monkeypatch):
    # the extended window must keep sampling past the base budget
    # (hunting for a contention gap), bounded by the hard deadline.
    # Asserted via elapsed wall time — robust to host load (a
    # sleep-call count was flaky when rounds slowed under load)
    monkeypatch.setattr(measure.time, "sleep", lambda s: None)
    t0 = measure.time.perf_counter()
    *_rest, contended = measure.stable_best_slope(
        _step, _x0(), min_traffic_bytes=1, counts=(2, 6),
        time_budget=0.05, stable_n=1, sleep=0.0,
        expect_slope=1e-12, extended_budget=1.5)
    elapsed = measure.time.perf_counter() - t0
    assert contended
    assert elapsed > 0.3, \
        f"extension must sample beyond the 0.05s base budget ({elapsed=})"


def test_last_good_roundtrip(tmp_path, monkeypatch):
    p = tmp_path / "last_good.json"
    monkeypatch.setattr(measure, "LAST_GOOD_PATH", str(p))
    assert measure.load_last_good() == {}
    measure.save_last_good({"m1": 100.0})
    measure.save_last_good({"m2": 7.5})
    got = measure.load_last_good()
    assert got == {"m1": 100.0, "m2": 7.5}
    # file is valid json on disk
    assert json.loads(p.read_text())["m2"] == 7.5
    # the merge ratchets UP only: a clean-but-slower plateau must not
    # erode the expectation a faster run established
    measure.save_last_good({"m1": 60.0})
    assert measure.load_last_good()["m1"] == 100.0
    measure.save_last_good({"m1": 140.0})
    assert measure.load_last_good()["m1"] == 140.0


def test_bench_budget_sum_bounded():
    """The r5 failure mode was rc=124: per-metric budgets worst-cased
    to ~1950 s against the driver's 870 s timeout, and the process
    was killed with every result unprinted. Round-9 re-derivation:
    sampling is hard-stopped by the global TOTAL_BUDGET deadline, and
    the only post-deadline tail is warmup compiles — one per BUDGETS
    metric plus the health probe, each at most COLD_COMPILE_S when
    the persistent compilation cache is fully cold (warm runs pay
    ~0). The fully-cold structural worst case must clear the 870 s
    driver timeout with >= 60 s slack, so an rc=124 needs the
    physics, not the configuration, to break."""
    import bench

    budget_sum = sum(tb + eb for tb, eb in bench.BUDGETS.values())
    # the global deadline must not be looser than the per-metric sum
    assert bench.TOTAL_BUDGET <= budget_sum, (bench.TOTAL_BUDGET,
                                              budget_sum)
    # one warmup per metric + the probe — the model must cover every
    # stable_best_slope site (BUDGETS gains an entry => this grows)
    assert bench.N_WARMUP_COMPILES >= len(bench.BUDGETS) + 1
    worst = bench.TOTAL_BUDGET + \
        bench.N_WARMUP_COMPILES * bench.COLD_COMPILE_S
    assert worst <= 870 - 60, (
        f"fully-cold worst case {worst}s leaves less than 60s slack "
        "under the 870s driver timeout (the r5 rc=124 class)")
    # the deep-scrub verify metric has its OWN sampling budget (it
    # must not ride free on another metric's share and push the
    # worst case past the driver timeout)
    assert "scrub_verify" in bench.BUDGETS
    tb, eb = bench.BUDGETS["scrub_verify"]
    assert 0 < tb and tb + eb <= 100, (tb, eb)
    # the round-9 mesh row is budgeted like every other metric, and
    # ISSUE 12's decode sibling rides the same identity: TOTAL_BUDGET
    # came down 425 -> 390 to absorb the extra warmup reservation its
    # BUDGETS entry adds (the single-chip subprocess that lands both
    # rows is bounded by these same budgets, so no structural term)
    for key in ("multichip_encode", "multichip_decode"):
        assert key in bench.BUDGETS, key
        tb, eb = bench.BUDGETS[key]
        assert 0 < tb and tb + eb <= 100, (key, tb, eb)
    # ISSUE 8: the two degraded-mode rows have their own budgets and
    # the global deadline identity absorbed them (TOTAL_BUDGET came
    # DOWN so the fully-cold worst case still clears 870s with the
    # two extra warmup compiles N_WARMUP_COMPILES now reserves)
    for key in ("degraded_read", "degraded_p99"):
        assert key in bench.BUDGETS, key
        tb, eb = bench.BUDGETS[key]
        assert 0 < tb and tb + eb <= 100, (key, tb, eb)
    # ISSUE 9: the load-generator cluster row is budgeted like every
    # other metric and the global deadline identity absorbed it
    # (TOTAL_BUDGET 460 -> 425 covers the extra warmup reservation
    # its BUDGETS entry adds, so the 870 s worst case is preserved)
    assert "load_gen" in bench.BUDGETS
    tb, eb = bench.BUDGETS["load_gen"]
    assert 0 < tb and tb + eb <= 100, (tb, eb)
    # ISSUE 20: the multi-tenant fairness row is budgeted like every
    # other metric and the deadline identity absorbed it (TOTAL_BUDGET
    # 320 -> 285 covers the extra warmup reservation its BUDGETS entry
    # adds, so the fully-cold 870 s worst case is preserved)
    assert "multi_tenant" in bench.BUDGETS
    tb, eb = bench.BUDGETS["multi_tenant"]
    assert 0 < tb and tb + eb <= 100, (tb, eb)


def test_deadline_caps_sampling(monkeypatch):
    """A stable_best_slope call handed an already-passed deadline must
    still return (one honest round), and an extension must never
    sample past the deadline."""
    monkeypatch.setattr(measure.time, "sleep", lambda s: None)
    t0 = measure.time.perf_counter()
    slope, _spread, _n, _c = measure.stable_best_slope(
        _step, _x0(), min_traffic_bytes=1, counts=(2, 6),
        time_budget=30.0, stable_n=1, sleep=0.0,
        expect_slope=1e-12, extended_budget=30.0,
        deadline=measure.time.perf_counter() + 0.3)
    elapsed = measure.time.perf_counter() - t0
    assert slope > 0
    assert elapsed < 10.0, \
        f"deadline must dominate the 60s configured budget ({elapsed=})"


def test_health_field_adds_no_bench_budget(capsys):
    """The health brief on metric lines is a pure counter read: it
    must not sample the flight recorder (mgr-tick territory), must
    not add a BUDGETS entry, and must leave the r5 rc=124 worst-case
    budget identity intact."""
    import bench
    from ceph_tpu.utils import flight_recorder as fr

    fr.reset_for_tests()
    before = fr.recorder().stats()["samples"]
    bench.emit("budget_probe", {"value": 0})
    bench._RESULTS.pop("budget_probe", None)
    capsys.readouterr()
    assert fr.recorder().stats()["samples"] == before, \
        "emitting a metric line must not sample the recorder"
    assert "health" not in bench.BUDGETS
    assert "recorder" not in bench.BUDGETS
    # the structural worst case still clears the driver timeout
    worst = bench.TOTAL_BUDGET + \
        bench.N_WARMUP_COMPILES * bench.COLD_COMPILE_S
    assert worst <= 870 - 60


def test_static_analysis_adds_no_bench_budget():
    """ISSUE 11: the analyzer gate rides tier-1's existing 870 s
    identity — no BUDGETS entry, no warmup-compile reservation, and
    the whole-package lint pass is bounded far below the slack the
    identity already guarantees. The lock witness is OFF by default
    (zero wrappers) outside the gate tests that arm it explicitly,
    so tier-1 wall is untouched (<10% bound holds trivially; the
    proxy cost itself is pinned in test_lock_witness.py)."""
    import time

    import bench
    from ceph_tpu.analysis import linters, lock_witness

    assert "analysis" not in bench.BUDGETS
    assert "lock_witness" not in bench.BUDGETS
    worst = bench.TOTAL_BUDGET + \
        bench.N_WARMUP_COMPILES * bench.COLD_COMPILE_S
    assert worst <= 870 - 60
    # witness armed only by env (conftest) or the gate tests' fixture
    assert lock_witness.enabled() == lock_witness.env_enabled()
    # the full lint pass over ~40k LoC stays a small fraction of the
    # tier-1 budget (it runs twice in tier-1: gate test + CLI test)
    t0 = time.perf_counter()
    linters.run_all()
    elapsed = time.perf_counter() - t0
    assert elapsed < 60, f"lint pass too slow for tier-1: {elapsed:.1f}s"


def test_repo_last_good_is_a_runtime_artefact(tmp_path, monkeypatch):
    """The expectation file belongs to the installation that measured
    it: the tree commits none (it is listed in .gitignore; the earlier
    installation's rows are gone), and without one the guard has no
    expectation until a bench run on the current chip records it."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rel = os.path.relpath(measure.LAST_GOOD_PATH, root)
    with open(os.path.join(root, ".gitignore")) as f:
        assert rel in f.read().split()
    monkeypatch.setattr(measure, "LAST_GOOD_PATH",
                        str(tmp_path / "absent.json"))
    assert measure.load_last_good() == {}


def test_present_last_good_arms_the_guard(tmp_path, monkeypatch):
    """A file that IS there is honoured: its GB/s, turned into
    seconds per iteration the way bench.py's ``expect`` does, is the
    expectation the guard holds the plateau to."""
    monkeypatch.setattr(measure, "LAST_GOOD_PATH",
                        str(tmp_path / "last_good.json"))
    traffic = _x0().nbytes
    for metric, gbps, want_contended in (
            ("impossibly_fast_GBps", 1e9, True),
            ("slower_than_reality_GBps", 1e-9, False)):
        measure.save_last_good({metric: gbps})
        expect = traffic / (measure.load_last_good()[metric] * 1e9)
        *_rest, contended = measure.stable_best_slope(
            _step, _x0(), min_traffic_bytes=1, counts=(2, 6),
            time_budget=1.0, stable_n=1, sleep=0.0,
            expect_slope=expect, extended_budget=0.5)
        assert contended is want_contended, metric
