"""The undersized RBD deployment: ``rbd_ec_k8m3_11osd`` /
``rbd_k8m3_randwrite_4k_1down`` as registered, and its window kind
(``windows/rbd_write_down.py``) through the command's own ``main`` on
the tiny checkout: a tiny image (8 objects of 64 KiB) whose data pool
is a tiny k=8, m=3 pool on exactly k+m = 11 OSDs, OSD 5 killed after
the preload, 4 threads writing 4 KiB at random. Correct, with the lost
data chunks rebuilt on the engine's decode flush; a rebuild that
returns zeros is not correct; the overwrite controls are not correct
on the cell; a program without the route is refused before the
cluster starts."""

import json
import os
import shutil

import numpy as np
import pytest

import bench_tiny

import spec
from bench_tiny import ROOT

CELL, TINY, KIND = ("rbd_k8m3_randwrite_4k_1down", "tiny.rbd_down",
                    "rbd_write_down")
CONFIG, TRAFFIC = "rbd_ec_k8m3_11osd", "rbd_randwrite_4k_1down"
#: the healthy RBD cell: the new cell is on each of its lists but one
LIKE = "rbd_k8m3_randwrite_4k"
#: its list the new cell is not on: the roofline divides encode bytes
#: by all device-busy time, which the decode flushes share here
NOT_ON = ("encode_roofline",)
NEW = ("rmw_decode_share", "rmw_decode_ms.overwrite")
KIB = 1 << 10


def _bm(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _lists(bm: dict) -> dict:
    return {m["name"]: m.get("workloads", [])
            for m in bm["end_to_end"] + bm["per_layer"]}


def check_registered(root: str) -> None:
    """The cell, its configuration and its two metrics as
    ``BENCHMARK.json`` at ``root`` registers them, read by name."""
    bm = _bm(root)
    entries = [w for w in bm["workloads"] if w["name"] == CELL]
    assert len(entries) == 1, CELL
    assert entries[0] == dict(entries[0], config=CONFIG,
                              traffic=TRAFFIC, chips=1)
    confs = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert len(confs) == 1
    assert confs[0]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(confs[0]["reduced"]) == [
        "data_per_run", "hosts", "object_store"]
    lists = _lists(bm)
    like = [name for name, cells in lists.items() if LIKE in cells]
    assert "write_MBps" in like and "write_p95_ms" in like
    for name in like:
        assert (CELL in lists[name]) is (name not in NOT_ON), name
    bench = os.path.join(root, "benchmarks")
    for name in NEW:
        entry = [m for m in bm["per_layer"] if m["name"] == name]
        assert len(entry) == 1, name
        entry, met = entry[0], spec.layer_metric(name, bench)
        assert entry["workloads"] == [CELL]
        assert entry["layer"] == met["layer"] == "engine"
        assert (entry["unit"], entry["moves"]) == (met["unit"],
                                                    met["moves"])
    share = spec.layer_metric("rmw_decode_share", bench)
    assert (share["reader"], share["args"]) == (
        "stat_ratio", {"num": "rmw_decodes", "den": "overwrite_ops"})
    assert share["moves"] == "write_MBps"
    decode = spec.layer_metric("rmw_decode_ms.overwrite", bench)
    assert (decode["reader"], decode["args"]) == (
        "stage_sum_ms", {"stages": ["rmw_decode"]})
    assert decode["moves"] == "write_p95_ms"
    cell = spec.Cell(CELL, root)
    assert {m["name"] for m in cell.end_to_end} == {
        "write_MBps", "write_p95_ms", "setup_s"}
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    assert cell.traffic["op"] == KIND
    assert cell.traffic["down_osds"] == [5]
    assert "osds_down" not in cell.traffic
    dep, pool = cell.config["deployment"], cell.config["pool"]
    # exactly k+m OSDs: no spare to backfill onto
    assert dep["n_osds"] == pool["k"] + pool["m"] == 11
    twin = spec.Cell(LIKE, root)
    # the healthy RBD deployment but for the OSD count
    assert {k: v for k, v in dep.items()
            if k not in ("n_osds", "layout", "failure")} == {
        k: v for k, v in twin.config["deployment"].items()
        if k not in ("n_osds", "layout")}
    assert pool == twin.config["pool"]
    assert {k: v for k, v in cell.traffic.items()
            if k not in ("what", "op", "down_osds")} == {
        k: v for k, v in twin.traffic.items() if k not in ("what", "op")}
    # no other configuration names the same source
    assert [c["source"] for c in bm["configs"]].count(
        confs[0]["source"]) == 1


def test_the_cell_is_registered_by_name(registry_root):
    check_registered(registry_root)


def _removed(bm: dict) -> None:
    bm["workloads"] = [w for w in bm["workloads"] if w["name"] != CELL]
    for met in bm["end_to_end"] + bm["per_layer"]:
        if CELL in met.get("workloads", []):
            met["workloads"].remove(CELL)


def _renamed(bm: dict) -> None:
    for ent in bm["workloads"]:
        if ent["name"] == CELL:
            ent["name"] = CELL + "_v2"
    for met in bm["end_to_end"] + bm["per_layer"]:
        cells = met.get("workloads", [])
        if CELL in cells:
            cells[cells.index(CELL)] = CELL + "_v2"


def _dropped(name: str):
    def drop(bm: dict) -> None:
        met = next(m for m in bm["end_to_end"] + bm["per_layer"]
                   if m["name"] == name)
        met["workloads"].remove(CELL)
    return drop


def _on_the_roofline(bm: dict) -> None:
    next(m for m in bm["per_layer"]
         if m["name"] == "encode_roofline")["workloads"].append(CELL)


def _source_of_the_twin(bm: dict) -> None:
    twin = next(c for c in bm["configs"]
                if c["name"] == "rbd_ec_k8m3_12osd")
    next(c for c in bm["configs"]
         if c["name"] == CONFIG)["source"] = twin["source"]


def _lists_of_the_cell() -> list[str]:
    return sorted(name for name, cells in _lists(_bm(ROOT)).items()
                  if CELL in cells)


UNREGISTERED = {
    "removed": _removed, "renamed": _renamed,
    "on_encode_roofline": _on_the_roofline,
    "duplicate_source": _source_of_the_twin,
    **{"dropped_" + name: _dropped(name)
       for name in _lists_of_the_cell()}}


def test_the_cell_is_on_every_list_it_should_be():
    """What the upper reading below drops it from: the end-to-end
    write metrics, the healthy RBD cell's per-layer lists but the
    roofline, and its own two."""
    lists = _lists_of_the_cell()
    assert {"write_MBps", "write_p95_ms", "rmw_read_ms.overwrite",
            "overwrite_encode_share", "launch_active_threads.write",
            *NEW} <= set(lists)
    assert "encode_roofline" not in lists


@pytest.mark.parametrize("how", sorted(UNREGISTERED))
def test_the_registration_check_fails_where_the_cell_is_not_registered(
        tmp_path, how):
    root = bench_tiny.copy_root(str(tmp_path))
    bm = _bm(root)
    UNREGISTERED[how](bm)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f, indent=1)
    with pytest.raises((AssertionError, spec.SpecError)):
        check_registered(root)


def test_a_mix_that_names_osds_down_or_no_dead_is_refused(tmp_path):
    bench = tmp_path / "benchmarks"
    (bench / "traffic").mkdir(parents=True)
    shutil.copytree(os.path.join(bench_tiny.BENCH_DIR, "windows"),
                    bench / "windows",
                    ignore=shutil.ignore_patterns("__pycache__"))
    good = spec.traffic(TRAFFIC)
    for bad in ({"osds_down": 1}, {"down_osds": []},
                {"down_osds": [5, 5]}, {"down_osds": ["5"]},
                {"down_osds": [True]}, {"down_osds": 5}):
        (bench / "traffic" / "bad.json").write_text(
            json.dumps(dict(good, **bad)))
        with pytest.raises(spec.SpecError):
            spec.traffic("bad", str(bench))
    (bench / "traffic" / "ok.json").write_text(json.dumps(good))
    assert spec.traffic("ok", str(bench))["down_osds"] == [5]


# -- a tiny run on the CPU ------------------------------------------------

@pytest.fixture(scope="module")
def down_root(tmp_path_factory):
    """``make_root`` as it is, then NEW files only: a tiny copy of the
    configuration (11 OSDs kept) and of the traffic (OSD 5 kept), and
    the cell on every list the registered one is on."""
    root = bench_tiny.make_root(str(tmp_path_factory.mktemp("down")))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", CONFIG + ".json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny_rbd_down"
    conf["pool"].update(backend="jax", pg_num=8)
    conf["deployment"]["osd_heartbeat_grace"] = 4
    conf["deployment"]["replicated_pool"].update(pg_num=4)
    conf["deployment"]["image"].update(size_bytes=8 * 64 * KIB,
                                       order=16)
    with open(os.path.join(bench, "configs", "tiny_rbd_down.json"),
              "w") as f:
        json.dump(conf, f, indent=1)
    mix = spec.traffic(TRAFFIC)
    mix.update(object_bytes=64 * KIB, clients=4, payload_pool=8,
               warm_bursts=[1, 2, 4], check_sample=8)
    with open(os.path.join(bench, "traffic", "tiny_rbd_down.json"),
              "w") as f:
        json.dump(mix, f, indent=1)
    bm = _bm(root)
    bm["configs"].append(
        {"name": "tiny_rbd_down",
         "source": "tests/benchmarks: tiny_rbd_down",
         "file": "benchmarks/configs/tiny_rbd_down.json", "reduced": [],
         "why": "CPU test size"})
    bm["workloads"].append(
        {"name": TINY, "config": "tiny_rbd_down",
         "traffic": "tiny_rbd_down", "chips": 1, "why": "CPU test"})
    for metric in bm["end_to_end"] + bm["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f, indent=1)
    return root


def _run(capfd, root, **how) -> dict:
    return bench_tiny.last_line(capfd, root, TINY, seed=2_500_000_041,
                                **how)


def _over(compared: dict) -> set:
    return {name for name, row in compared.items()
            if (row["value"] > row["limit"] if row["rule"] == "<="
                else row["value"] < row["limit"])}


def test_the_cell_is_correct_and_rebuilds_on_the_decode_flush(
        down_root, cpu_env, capfd):
    last = _run(capfd, down_root, trace=1, seconds=2.5)
    cmp = last["compared"]
    assert last["correct"] is True, cmp
    assert last["failed"] == 0 and last["attempted"] > 4
    for row in ("readback_unequal", "shards_unequal", "crcs_unequal",
                "shards_missing", "ops_failed", "host_flushes",
                "fused_fallbacks", "engine_errors", "decode_errors",
                "decode_fallbacks", "compiled_in_window"):
        assert cmp[row] == {"value": 0, "limit": 0, "rule": "<="}, row
    assert cmp["overwrite_ops"]["value"] >= last["attempted"]
    assert cmp["overwrites_on_lost_data"]["value"] >= 1
    assert cmp["rmw_decodes"]["value"] >= cmp["rmw_decodes"]["limit"]
    metrics = {name: row["value"] for name, row in
               last["metrics"].items()}
    assert 0 < metrics["rmw_decode_share"] <= 1
    assert metrics["rmw_decode_ms.overwrite"] > 0
    assert metrics["overwrite_encode_share"] == 1.0
    assert metrics["rmw_read_ms.overwrite"] > 0
    assert "encode_roofline" not in metrics


def test_a_rebuild_that_returns_zeros_is_not_correct(
        down_root, cpu_env, capfd, monkeypatch):
    """The upper reading: the chunk an overwrite's read rebuilt is
    replaced by zeros. The spliced stripe is re-encoded from them, so
    the stored shards are not the reference's."""
    from ceph_tpu.osd.ec_backend import ECBackend
    real = ECBackend._decode

    def zeroed(self, pg, shards, want, overwrite=False):
        out = real(self, pg, shards, want, overwrite=overwrite)
        if not overwrite:
            return out
        return {i: v if i in shards else np.zeros_like(v)
                for i, v in out.items()}
    monkeypatch.setattr(ECBackend, "_decode", zeroed)
    last = _run(capfd, down_root)
    assert last["correct"] is False
    assert last["compared"]["shards_unequal"]["value"] > 0
    assert "shards_unequal" in _over(last["compared"])


def test_a_program_without_the_route_is_refused_before_the_cluster(
        down_root, cpu_env, capfd, monkeypatch):
    """The parent's engine: ``decode_sync`` takes no ``overwrite``. No
    cluster starts, no window runs."""
    import served
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    real = DeviceEncodeEngine.decode_sync

    def decode_sync(self, key, codec, sinfo, shards, want,
                    timeout=60.0, span=None, clock=None):
        return real(self, key, codec, sinfo, shards, want, timeout)
    monkeypatch.setattr(DeviceEncodeEngine, "decode_sync", decode_sync)
    started = []
    monkeypatch.setattr(served.Served, "start",
                        lambda self: started.append(1))
    rc, lines = bench_tiny.run_main(capfd, down_root, TINY)
    assert rc == 1 and lines == [] and started == []


@pytest.mark.parametrize("seed", [1, 2_500_000_000])
def test_the_overwrite_controls_are_not_correct_on_the_cell(down_root,
                                                            seed):
    """``control.py`` on the cell's tiny copy: every write control
    comes out not correct, an acknowledged overwrite that was dropped
    by the stored and read-back bytes."""
    import control
    results = control.run_controls(spec.Cell(TINY, down_root), seed)
    assert {r["control"] for r in results} == {
        "one_parity_short", "crc_not_kept", "overwrite_lost"}
    for res in results:
        assert res["correct"] is False, res
    lost = next(r["compared"] for r in results
                if r["control"] == "overwrite_lost")
    assert lost["readback_unequal"]["value"] >= 1
    assert lost["shards_unequal"]["value"] >= 1
