"""The two Clay cells of ISSUE 29 (``clay_k8m4d11_write_4m``,
``clay_k8m4d11_degraded_read_4m``) rehearsed on the CPU at a tiny size
through the command's own ``main``: NEW files only on top of
``bench_tiny.make_root`` (as it is): the committed configuration at
``backend=jax`` and 8 PGs, its reference module copied beside the
harness, and two cells on the tiny traffic that is there. Also: what
the committed files and entries state, and that the committed
reference module is the one the cells are judged by.

Both cells are in ``BENCHMARK.json``: the degraded cell since PR 29,
the write cell since PR 34, which registered it by entries alone (it
had waited in ``benchmarks/pending/``: the check could not admit it in
the PR whose parent wrote a twelfth as fast).
"""

import json
import os

import numpy as np
import pytest

import bench_tiny

import clay_reference
import reference
import spec

CONFIG = "clay_k8m4d11_13osd"
WRITE, DEGRADED = "clay_k8m4d11_write_4m", "clay_k8m4d11_degraded_read_4m"
NEW_METRICS = {
    "layered_encode_share": ("stat_ratio", WRITE, "write_MBps"),
    "layered_decode_share": ("stat_ratio", DEGRADED,
                             "degraded_read_MBps"),
    "signature_builds_per_flush.degraded": ("stat_ratio", DEGRADED,
                                            "degraded_read_p90_ms"),
    "signature_build_ms.degraded": ("host_span_ms", DEGRADED,
                                    "degraded_read_p90_ms")}


# -- what is committed ----------------------------------------------------

@pytest.fixture(scope="module")
def bm(registry_root):
    return spec.benchmark(registry_root)


def test_the_configuration_states_the_deployment(bm, registry_root):
    entry = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1
    conf = spec.configuration(entry[0], registry_root)
    assert conf["source"] == entry[0]["source"]
    assert len(conf["source"]) <= 200 and "clay" in conf["source"]
    assert conf["architecture"] is None
    assert spec.ec_profile(conf["pool"]) == {
        "plugin": "clay", "k": 8, "m": 4, "d": 11,
        "scalar_mds": "jerasure", "technique": "reed_sol_van",
        "backend": "pallas"}
    assert conf["pool"]["stripe_unit"] == 4096
    assert conf["deployment"]["n_osds"] == 13 == \
        conf["pool"]["k"] + conf["pool"]["m"] + 1
    assert conf["deployment"]["osd_heartbeat_grace"] == 20
    # no shape is cut: only hosts, store and data per run
    assert sorted(conf["reduced"]) == sorted(entry[0]["reduced"]) == [
        "data_per_run", "hosts", "object_store"]
    assert set(conf["assumed"]) == {"n_osds", "pg_num", "stripe_unit",
                                    "osd_heartbeat_grace"}
    assert len(conf["guarantees"]) == 4
    code = clay_reference.code(conf["pool"])
    assert (code.q, code.t, code.nu, code.sub_chunks) == (4, 3, 0, 64)
    assert conf["shape"]["sub_chunks"] == code.sub_chunks
    assert spec.reference_module(conf) .__name__.endswith(
        "clay_reference")
    assert not hasattr(clay_reference, "rebuild_read_bytes")


@pytest.mark.parametrize("cell,traffic,twin,metrics", [
    (WRITE, "write_4m", "k8m3_write_4m",
     {"write_MBps", "write_p95_ms", "setup_s"}),
    # the Clay degraded cell loses the same two OSDs in every run
    (DEGRADED, "degraded_read_4m_fixed_down", "k8m3_degraded_read_4m",
     {"degraded_read_MBps", "degraded_read_p90_ms", "setup_s"})])
def test_the_cells_are_entries_on_traffic_that_is_there(
        bm, registry_root, cell, traffic, twin, metrics):
    loaded = spec.Cell(cell, registry_root)
    assert loaded.entry["config"] == CONFIG
    assert loaded.entry["traffic"] == traffic and loaded.chips == 1
    assert len(loaded.entry["why"]) <= 200
    assert {m["name"] for m in loaded.end_to_end} == metrics
    # every per-layer metric its RS twin reports, and the new ones
    twin = spec.Cell(twin, registry_root)
    mine = {m["name"] for m in loaded.per_layer}
    assert {m["name"] for m in twin.per_layer} <= mine
    assert mine - {m["name"] for m in twin.per_layer} >= {
        name for name, (_, where, _) in NEW_METRICS.items()
        if where == cell}
    # registered: BENCHMARK.json has the cell, on every list its RS
    # twin is on and on those of its own metrics, read by name (a
    # metric a later PR lists it on is an addition); no pending file
    # shadows it
    assert loaded.entry in bm["workloads"]
    listed = {met["name"] for met in bm["end_to_end"] + bm["per_layer"]
              if cell in met.get("workloads", [])}
    assert listed >= {
        met["name"] for met in bm["end_to_end"] + bm["per_layer"]
        if twin.name in met.get("workloads", [])} | {
        name for name, (_, where, _) in NEW_METRICS.items()
        if where == cell}
    assert not os.path.exists(os.path.join(
        registry_root, "benchmarks", "pending", cell + ".json"))
    assert spec.benchmark(registry_root, pending=cell) == bm


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metrics_are_data_over_readers_that_are_there(
        bm, registry_root, name):
    reader, cell, moves = NEW_METRICS[name]
    entry = [m for m in bm["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    bench = os.path.join(registry_root, "benchmarks")
    entry, met = entry[0], spec.layer_metric(name, bench)
    assert met["reader"] == reader and cell in entry["workloads"]
    # a Clay pool's metric: every cell it lists is on a Clay pool
    for other in entry["workloads"]:
        assert spec.Cell(other, registry_root).config["pool"][
            "plugin"] == "clay", other
    assert entry["moves"] == met["moves"] == moves
    assert entry["layer"] == met["layer"] == "engine"
    assert entry["unit"] == met["unit"]
    read = spec.reader(reader, bench)
    if reader != "stat_ratio":
        return
    # the counters the file names are ones the engine starts at 0
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    eng = DeviceEncodeEngine(lambda k, f: f())
    try:
        for counter in met["args"].values():
            assert eng.stats[counter] == 0, counter
    finally:
        eng.stop()
    num, den = met["args"]["num"], met["args"]["den"]
    assert read({"engine_window": {num: 30, den: 40}},
                **met["args"]) == pytest.approx(0.75)
    # a program without the counter (the parent commit) reads 0 and
    # does not raise; a window without such an op is no reading
    assert read({"engine_window": {den: 40}}, **met["args"]) == 0.0
    assert read({"engine_window": {}}, **met["args"]) is None


def test_the_reference_is_the_published_code_not_the_rs_one():
    pool = spec.Cell(WRITE).config["pool"]
    data = np.random.default_rng(29).integers(
        0, 256, 3 * 8 * 4096 - 5, dtype=np.uint8).tobytes()
    mine = clay_reference.shards(data, pool)
    assert len(mine) == 12 and {len(s) for s in mine} == {3 * 4096}
    # systematic: the data shards are the RS reference's
    rs = reference.shards(data, pool)
    assert all(np.array_equal(mine[i], rs[i]) for i in range(8))
    assert not any(np.array_equal(mine[i], rs[i]) for i in range(8, 12))
    # every plane's uncoupled values are an RS codeword, and the code
    # is MDS: any 8 of the 12 shards rebuild the other four
    rng = np.random.default_rng(30)
    for _ in range(3):
        lost = sorted(int(c) for c in rng.choice(12, 4, replace=False))
        got = clay_reference.decode(
            {c: mine[c] for c in range(12) if c not in lost}, lost, pool)
        assert all(np.array_equal(got[c], mine[c]) for c in lost), lost
    # the two codes' matrices, as the file states them
    code = clay_reference.code(pool)
    assert code.pair == [[3, 2], [2, 3]]
    assert code.mds == reference.coding_matrix(8, 4)
    with pytest.raises(ValueError):
        clay_reference.Clay(8, 4, 12)


# -- the cells at tiny size -------------------------------------------------

@pytest.fixture(scope="module")
def clay_root(tmp_path_factory):
    """``make_root`` as it is (it copies every configuration's
    reference module beside the harness's data), then NEW files only:
    the committed configuration at tiny size, and the two cells on
    ``tiny_write`` / ``tiny_degraded``."""
    root = bench_tiny.make_root(str(tmp_path_factory.mktemp("clay")))
    bench = os.path.join(root, "benchmarks")
    assert os.path.isfile(os.path.join(bench, "clay_reference.py"))
    with open(os.path.join(bench, "configs", CONFIG + ".json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny_clay"
    conf["pool"].update(backend="jax", pg_num=8)
    # 13 daemons and the test run's other workers share the host: a
    # grace of 4 s has marked healthy OSDs down under that load
    conf["deployment"]["osd_heartbeat_grace"] = 8
    with open(os.path.join(bench, "configs", "tiny_clay.json"),
              "w") as f:
        json.dump(conf, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append(
        {"name": "tiny_clay", "source": "tests/benchmarks: tiny_clay",
         "file": "benchmarks/configs/tiny_clay.json", "reduced": [],
         "why": "CPU test size"})
    # both cells as BENCHMARK.json has them: on every list of the
    # committed cell they stand for
    like = {"tiny.clay_write": ("tiny_write", WRITE),
            "tiny.clay_degraded": ("tiny_degraded", DEGRADED)}
    bm["workloads"] += [
        {"name": cell, "config": "tiny_clay", "traffic": mix,
         "chips": 1, "why": "CPU test"}
        for cell, (mix, _) in like.items()]
    for metric in bm["end_to_end"] + bm["per_layer"]:
        cells = metric.get("workloads")
        if cells:
            cells += [cell for cell, (_, real) in like.items()
                      if real in cells]
    with open(path, "w") as f:
        json.dump(bm, f, indent=1)
    return root


_run = bench_tiny.last_line


def test_clay_write_cell_is_correct_by_its_own_reference(
        clay_root, cpu_env, capfd):
    cell = spec.Cell("tiny.clay_write", clay_root)
    assert cell.reference.__name__.endswith("clay_reference")
    last = _run(capfd, clay_root, "tiny.clay_write")
    assert last["correct"] is True, last["compared"]
    assert set(last["metrics"]) == {"write_MBps", "write_p95_ms",
                                    "setup_s"}
    cmp = last["compared"]
    assert cmp["encode_flushes"]["value"] >= 1
    for row in ("shards_unequal", "crcs_unequal", "readback_unequal",
                "host_flushes", "fused_fallbacks", "engine_errors",
                "compiled_in_window", "primaries_without_device"):
        assert cmp[row]["value"] == 0, row


def test_clay_write_cell_traced_reads_the_layered_share(
        clay_root, cpu_env, capfd):
    last = _run(capfd, clay_root, "tiny.clay_write", trace=1,
                seconds=3)
    assert last["correct"] is True, last["compared"]
    metrics = last["metrics"]
    assert metrics["layered_encode_share"]["value"] == 1.0
    assert metrics["encode_ops_per_flush"]["value"] >= 1
    assert "encode_cross_pg_share" in metrics
    assert "encode_roofline" not in metrics     # a CPU: no device plane


def test_clay_degraded_cell_reconstructs_through_the_decode_flush(
        clay_root, cpu_env, capfd):
    last = _run(capfd, clay_root, "tiny.clay_degraded", trace=1,
                seconds=3)
    assert last["correct"] is True, last["compared"]
    cmp = last["compared"]
    assert cmp["decode_flushes"]["value"] >= 1
    for row in ("reads_unequal", "shards_unequal", "crcs_unequal",
                "decode_errors", "compiled_in_window"):
        assert cmp[row]["value"] == 0, row
    metrics = last["metrics"]
    assert metrics["layered_decode_share"]["value"] == 1.0
    assert metrics["decode_ops_per_flush"]["value"] >= 1
    # every table was built when the primaries peered, or cached
    assert metrics["signature_builds_per_flush.degraded"]["value"] \
        == 0.0
    # the lookup is marked at every decode flush, so the span has a
    # reading whenever the trace has the host plane
    if "signature_build_ms.degraded" in metrics:
        assert 0 <= metrics["signature_build_ms.degraded"]["value"] < 50
