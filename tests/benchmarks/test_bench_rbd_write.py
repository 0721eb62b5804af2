"""The RBD deployment (ISSUE 35): ``rbd_ec_k8m3_12osd`` /
``rbd_k8m3_randwrite_4k`` as registered, and its window kind
(``windows/rbd_write.py``) through the command's own ``main`` on the
tiny checkout: a tiny image (8 objects of 64 KiB) whose data pool is
the tiny k=8, m=3 pool, 4 threads writing 4 KiB at random. Correct and
on the overwrite route; traced, it reads its two metrics; a program
whose engine has no overwrite route is refused before any window; a
lost overwrite is not correct."""

import json
import os

import pytest

import bench_tiny

import spec
from bench_tiny import ROOT
from windows.base import WindowBase

CELL, TINY, KIND = "rbd_k8m3_randwrite_4k", "tiny.rbd", "rbd_write"
CONFIG = "rbd_ec_k8m3_12osd"
KIB = 1 << 10
#: the accepted write metrics whose stages an overwrite marks
WRITE_LISTS = (
    "write_MBps", "write_p95_ms", "client_wire_ms.write",
    "osd_queue_ms.write", "engine_wait_ms.write",
    "commit_wait_ms.write", "encode_ops_per_flush", "encode_roofline",
    "device_idle_pct.write", "idle_parked_pct.write",
    "idle_flush_host_pct.write", "flush_build_ms.write",
    "flush_launch_ms.write", "flush_download_ms.write",
    "flush_dispatch_ms.write", "wq_active_threads.write")
NEW = ("rmw_read_ms.overwrite", "overwrite_encode_share")


def check_registered(root: str) -> None:
    """The cell as ``BENCHMARK.json`` at ``root`` registers it, read by
    name: whatever a later PR appends, and wherever, leaves it so."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    entries = [w for w in bm["workloads"] if w["name"] == CELL]
    assert len(entries) == 1, CELL
    entry = entries[0]
    assert entry == dict(entry, config=CONFIG,
                         traffic="rbd_randwrite_4k", chips=1)
    lists = {m["name"]: m.get("workloads", [])
             for m in bm["end_to_end"] + bm["per_layer"]}
    for name in WRITE_LISTS:
        assert CELL in lists.get(name, []), name
    # the overwrite route's own metrics: on the cell, and on no cell
    # whose traffic is not a sub-object overwrite
    for name in NEW:
        assert CELL in lists.get(name, []), name
        for other in lists[name]:
            mix = spec.Cell(other, root).traffic
            assert mix.get("extent_bytes", mix["object_bytes"]) < \
                mix["object_bytes"], (name, other)
    # the cross-PG share stays a write_full cells' metric
    assert CELL not in lists["encode_cross_pg_share"]
    cell = spec.Cell(CELL, root)
    assert {m["name"] for m in cell.end_to_end} == {
        "write_MBps", "write_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= set(
        WRITE_LISTS[2:] + NEW)
    assert set(cell.window.KEYS) == {"extent_bytes"}
    assert cell.traffic["extent_bytes"] == 4 * KIB
    dep = cell.config["deployment"]
    assert dep["image"]["size_bytes"] == 1 << 30
    assert 1 << dep["image"]["order"] == cell.traffic["object_bytes"]
    assert spec.ec_profile(cell.config["pool"]) == spec.ec_profile(
        spec.Cell("k8m3_write_4m", root).config["pool"])
    for seam in ("expected", "keeps_hinfo", "op_bytes"):
        assert getattr(cell.window, seam) is not getattr(WindowBase,
                                                         seam), seam


def test_the_cell_is_registered_as_the_issue_asks(registry_root):
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


def _moved(**to):
    def move(bm: dict) -> None:
        next(w for w in bm["workloads"] if w["name"] == CELL).update(to)
    return move


def _dropped(name: str):
    def drop(bm: dict) -> None:
        met = next(m for m in bm["end_to_end"] + bm["per_layer"]
                   if m["name"] == name)
        met["workloads"].remove(CELL)
    return drop


UNREGISTERED = {
    "removed": _removed, "renamed": _renamed,
    "other_config": _moved(config="rs_k8m3_12osd"),
    "other_traffic": _moved(traffic="write_4m"),
    **{"dropped_" + name: _dropped(name) for name in WRITE_LISTS + NEW}}


@pytest.mark.parametrize("how", sorted(UNREGISTERED))
def test_the_registration_check_fails_where_the_cell_is_not_registered(
        tmp_path, how):
    """The upper reading of ``check_registered``: on a copy whose cell
    was removed, renamed, moved to another configuration or traffic,
    or dropped from one of its metrics' lists, it fails."""
    root = bench_tiny.copy_root(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    UNREGISTERED[how](bm)
    with open(path, "w") as f:
        json.dump(bm, f, indent=1)
    with pytest.raises((AssertionError, spec.SpecError)):
        check_registered(root)


@pytest.fixture(scope="module")
def rbd_root(tmp_path_factory):
    """``make_root`` as it is, then NEW files only: a tiny copy of the
    configuration and of the traffic, and the cell on every list the
    registered one is on."""
    root = bench_tiny.make_root(str(tmp_path_factory.mktemp("rbd")))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", CONFIG + ".json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny_rbd"
    conf["pool"].update(backend="jax", pg_num=8)
    conf["deployment"]["osd_heartbeat_grace"] = 4
    conf["deployment"]["replicated_pool"].update(pg_num=4)
    conf["deployment"]["image"].update(size_bytes=8 * 64 * KIB,
                                       order=16)
    with open(os.path.join(bench, "configs", "tiny_rbd.json"),
              "w") as f:
        json.dump(conf, f, indent=1)
    with open(os.path.join(bench, "traffic",
                           "rbd_randwrite_4k.json")) as f:
        mix = json.load(f)
    mix.update(object_bytes=64 * KIB, clients=4, payload_pool=8,
               warm_bursts=[1, 2, 4], check_sample=8)
    with open(os.path.join(bench, "traffic", "tiny_rbd.json"),
              "w") as f:
        json.dump(mix, f, indent=1)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append(
        {"name": "tiny_rbd", "source": "tests/benchmarks: tiny_rbd",
         "file": "benchmarks/configs/tiny_rbd.json", "reduced": [],
         "why": "CPU test size"})
    bm["workloads"].append(
        {"name": TINY, "config": "tiny_rbd", "traffic": "tiny_rbd",
         "chips": 1, "why": "CPU test"})
    for metric in bm["end_to_end"] + bm["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY)
    with open(path, "w") as f:
        json.dump(bm, f, indent=1)
    return root


def _run(capfd, root, **how) -> dict:
    return bench_tiny.last_line(capfd, root, TINY, seed=2_500_000_035,
                                **how)


def _over(compared: dict) -> set:
    return {name for name, row in compared.items()
            if (row["value"] > row["limit"] if row["rule"] == "<="
                else row["value"] < row["limit"])}


def test_the_cell_is_correct_on_the_overwrite_route(rbd_root, cpu_env,
                                                    capfd):
    last = _run(capfd, rbd_root)
    cmp = last["compared"]
    assert last["correct"] is True, cmp
    assert last["failed"] == 0 and last["attempted"] > 4
    assert set(last["metrics"]) == {"write_MBps", "write_p95_ms",
                                    "setup_s"}
    for row in ("readback_unequal", "shards_unequal", "crcs_unequal",
                "shards_missing", "ops_failed", "host_flushes",
                "fused_fallbacks", "engine_errors",
                "compiled_in_window"):
        assert cmp[row] == {"value": 0, "limit": 0, "rule": "<="}, row
    # every acknowledged overwrite of the window was encoded by the
    # engine's overwrite route
    assert cmp["overwrite_ops"]["limit"] >= last["attempted"]
    assert cmp["overwrite_ops"]["value"] >= cmp["overwrite_ops"]["limit"]
    assert cmp["encode_flushes"]["value"] >= 1
    assert cmp["overwritten_compared"]["value"] >= 1


def test_the_cell_traced_reads_its_metrics(rbd_root, cpu_env, capfd):
    last = _run(capfd, rbd_root, trace=1, seconds=2.5)
    assert last["correct"] is True, last["compared"]
    metrics = last["metrics"]
    assert metrics["overwrite_encode_share"]["value"] == 1.0
    assert metrics["rmw_read_ms.overwrite"]["value"] > 0
    assert metrics["encode_ops_per_flush"]["value"] >= 1
    for name in ("client_wire_ms.write", "osd_queue_ms.write",
                 "engine_wait_ms.write", "commit_wait_ms.write"):
        assert metrics[name]["value"] > 0, name
    assert "encode_cross_pg_share" not in metrics


def _faulty_kind(monkeypatch, **members):
    real = spec.window_kind

    def window_kind(op, bench_dir=spec.BENCH_DIR):
        kind = real(op, bench_dir)
        if op != KIND:
            return kind
        return type("Window", (kind,), members)
    monkeypatch.setattr(spec, "window_kind", window_kind)


def _no_overwrite_counters(monkeypatch):
    """The engine counts no ``overwrite_ops``: ``prepare`` raises
    before the image is made."""
    import served
    real = served.Served.engine_stats
    monkeypatch.setattr(
        served.Served, "engine_stats", lambda self: {
            k: v for k, v in real(self).items()
            if not k.startswith("overwrite_")})
    return True


def _no_overwrite_op(monkeypatch):
    """The parent's engine stages no ``overwrite`` op: refused before
    the cluster starts."""
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    real = DeviceEncodeEngine.stage_encode

    def stage_encode(self, key, codec, sinfo, data, cont, span=None,
                     clock=None):
        return real(self, key, codec, sinfo, data, cont, span, clock)
    monkeypatch.setattr(DeviceEncodeEngine, "stage_encode",
                        stage_encode)
    return False


@pytest.mark.parametrize("parent_like", [_no_overwrite_counters,
                                         _no_overwrite_op],
                         ids=["in_prepare", "before_the_cluster"])
def test_a_program_without_the_overwrite_route_is_refused(
        rbd_root, cpu_env, capfd, monkeypatch, parent_like):
    """No window runs; the earlier refusal starts no cluster."""
    import served
    started = []
    real_start = served.Served.start
    monkeypatch.setattr(served.Served, "start", lambda self: (
        started.append(1), real_start(self))[1])
    starts = parent_like(monkeypatch)
    ran = []
    _faulty_kind(monkeypatch, run=lambda self, *a, **k: ran.append(1))
    rc, lines = bench_tiny.run_main(capfd, rbd_root, TINY)
    assert rc == 1 and lines == [] and ran == []
    assert bool(started) is starts


def test_a_lost_overwrite_is_not_correct(rbd_root, cpu_env, capfd,
                                         monkeypatch):
    """The newest acknowledged extent left out of what the objects
    hold (the control ``overwrite_lost``'s shape): the object that
    still holds it reads back, and is stored, otherwise."""
    def expected(self, name):
        last = self.acked.pop()
        try:
            return super(type(self), self).expected(name)
        finally:
            self.acked.append(last)

    def run(self, seconds, during=None):
        out = super(type(self), self).run(seconds, during)
        # the newest extent's object is in the comparison's sample
        self.check_names = [self.acked[-1][0]]
        return out
    _faulty_kind(monkeypatch, expected=expected, run=run)
    last = _run(capfd, rbd_root)
    assert last["correct"] is False
    assert _over(last["compared"]) == {"readback_unequal",
                                       "shards_unequal"}
