"""A tiny copy of the benchmark's data directories for CPU tests.

The copy holds the committed data files unchanged plus NEW files only:
one configuration, two traffic mixes, one per-layer metric over a
counter no committed metric reads, and their entries in a
``BENCHMARK.json`` of its own. Nothing that is there is edited, which is
what a later PR may do too; that the harness then finds and runs the new
cells is the test that it is driven by data.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: the harness's own code stays where it is; only data is copied
DATA = ("configs", "traffic", "layer_metrics", "readers", "peaks.json")

#: what the fake device reports (the accelerator check is skipped)
CPU_DEVICE = {"platform": "cpu", "kind": "cpu-test", "count": 1}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _dump(obj: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: str) -> str:
    """Build the tiny checkout below ``tmp`` and return its root."""
    bench = os.path.join(tmp, "benchmarks")
    os.makedirs(bench)
    for item in DATA:
        src = os.path.join(BENCH_DIR, item)
        if os.path.isdir(src):
            shutil.copytree(src, os.path.join(bench, item),
                            ignore=shutil.ignore_patterns(
                                "__pycache__"))
        else:
            shutil.copy(src, os.path.join(bench, item))
    before = _snapshot(bench)

    conf = _load(os.path.join(BENCH_DIR, "configs",
                              "rs_k8m3_12osd.json"))
    conf["name"] = "tiny_k8m3"
    conf["pool"].update(backend="jax", pg_num=8)
    conf["deployment"]["osd_heartbeat_grace"] = 4
    _dump(conf, os.path.join(bench, "configs", "tiny_k8m3.json"))

    for name, base, extra in (
            ("tiny_write", "write_4m", {}),
            ("tiny_degraded", "degraded_read_4m",
             {"preload_objects": 12, "payload_pool": 12,
              "max_objects": 12})):
        mix = _load(os.path.join(BENCH_DIR, "traffic", base + ".json"))
        mix.update(object_bytes=64 << 10, clients=4,
                   warm_bursts=[1, 2, 4], check_sample=4,
                   payload_pool=8)
        mix.update(extra)
        _dump(mix, os.path.join(bench, "traffic", name + ".json"))

    # a per-layer metric over a counter nothing committed reads,
    # through a reader that is already there
    _dump({"layer": "engine", "unit": "bytes/flush",
           "moves": "write_MBps", "reader": "stat_ratio",
           "args": {"num": "bytes", "den": "flushes"}},
          os.path.join(bench, "layer_metrics",
                       "tiny_bytes_per_flush.json"))

    table = _load(os.path.join(bench, "peaks.json"))
    bm = _load(os.path.join(ROOT, "BENCHMARK.json"))
    bm["configs"].append(
        {"name": "tiny_k8m3", "source": "tests/benchmarks",
         "file": "benchmarks/configs/tiny_k8m3.json", "reduced": [],
         "why": "CPU test size"})
    bm["workloads"] += [
        {"name": "tiny.write", "config": "tiny_k8m3",
         "traffic": "tiny_write", "chips": 1, "why": "CPU test"},
        {"name": "tiny.degraded", "config": "tiny_k8m3",
         "traffic": "tiny_degraded", "chips": 1, "why": "CPU test"}]
    for metric in bm["end_to_end"] + bm["per_layer"]:
        cells = metric.get("workloads")
        if cells and "k8m3_write_4m" in cells:
            cells.append("tiny.write")
        if cells and "k8m3_degraded_read_4m" in cells:
            cells.append("tiny.degraded")
    bm["per_layer"].append(
        {"name": "tiny_bytes_per_flush", "unit": "bytes/flush",
         "better": "higher", "source": "program_counter",
         "layer": "engine", "moves": "write_MBps",
         "workloads": ["tiny.write"]})
    _dump(bm, os.path.join(tmp, "BENCHMARK.json"))

    # the one exception: the test machine's "device" has to have peaks
    # for the rooflines' reader to be reachable at all
    after = _snapshot(bench)
    changed = [p for p in before if before[p] != after.get(p)]
    assert not changed, f"committed data files were edited: {changed}"
    table["device_kinds"][CPU_DEVICE["kind"]] = \
        table["device_kinds"]["TPU v5 lite"]
    _dump(table, os.path.join(bench, "peaks.json"))
    return tmp


def _snapshot(bench: str) -> dict:
    out = {}
    for base, _dirs, files in os.walk(bench):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, bench)] = f.read()
    return out
