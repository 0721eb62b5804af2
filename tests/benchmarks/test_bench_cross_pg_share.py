"""``encode_cross_pg_share`` is data alone (a layer-metric file read by
``stat_ratio``): loaded by name as the harness loads it, and read from
an engine-stats window, so a typo in the file fails here and not in
the driver's run."""

import os

import pytest

from bench_tiny import BENCH_DIR  # noqa: F401  (sets sys.path)

import spec

NAME = "encode_cross_pg_share"


def _read(engine_window: dict):
    met = spec.layer_metric(NAME)
    return spec.reader(met["reader"])({"engine_window": engine_window},
                                      **met.get("args", {}))


@pytest.mark.parametrize("window,want", [
    # two thirds of the window's ops left in flushes shared by PGs
    ({"ops": 900, "flushes": 320, "cross_pg_ops": 600}, 600 / 900),
    ({"ops": 16, "flushes": 1, "cross_pg_ops": 16}, 1.0),
    ({"ops": 40, "flushes": 40, "cross_pg_ops": 0}, 0.0),
    # a program without the counter (the parent commit) reads 0
    ({"ops": 40, "flushes": 30}, 0.0),
    # a window without an encode op is no reading, never 0
    ({"ops": 0, "flushes": 0, "cross_pg_ops": 0}, None),
    ({"decode_ops": 9, "decode_flushes": 7}, None),
], ids=["two_thirds", "all", "none", "parent_lacks_counter",
        "no_encode_ops", "degraded_window"])
def test_encode_cross_pg_share_reads_the_engine_counters(window, want):
    got = _read(window)
    assert got == (None if want is None else pytest.approx(want))


def test_encode_cross_pg_share_is_declared_as_the_file_says(
        registry_root):
    bm = spec.benchmark(registry_root)
    bench = os.path.join(registry_root, "benchmarks")
    entry = [m for m in bm["per_layer"] if m["name"] == NAME]
    assert len(entry) == 1
    entry, met = entry[0], spec.layer_metric(NAME, bench)
    assert met["reader"] == "stat_ratio"
    assert met["args"] == {"num": "cross_pg_ops", "den": "ops"}
    for key in ("layer", "unit", "moves"):
        assert entry[key] == met[key], key
    assert entry["layer"] == "engine"
    assert entry["moves"] == "write_p95_ms"
    assert entry["source"] == "program_counter"
    # the two RS write cells report it, whoever else does
    assert {"k8m3_write_4m", "k4m2_write_1m"} <= set(entry["workloads"])
    cells = {w["name"]: w for w in bm["workloads"]}
    for cell in entry["workloads"]:
        assert spec.traffic(cells[cell]["traffic"], bench)["op"] == \
            "write_full", cell
    # the counter the file names is one the engine starts at 0
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    eng = DeviceEncodeEngine(lambda k, f: f())
    try:
        for counter in met["args"].values():
            assert eng.stats[counter] == 0, counter
        assert eng.stats["decode_cross_pg_ops"] == 0
    finally:
        eng.stop()
