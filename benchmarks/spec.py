"""Finds everything a cell is made of, by name, from data files.

``BENCHMARK.json`` at the root of the checkout names the cells, the
configurations and the metrics. What belongs to one of them sits in a
file of its own below ``benchmarks/`` and is found by that name:

    configs/<configuration>.json        the deployment and its pool
    traffic/<traffic>.json              the mix one generator reads
    layer_metrics/<metric>.json         a per-layer metric: its reader
                                        and the reader's arguments
    readers/<reader>.py                 a reader: ``read(ctx, **args)``
    peaks.json                          device kind -> published peaks

Nothing is listed in code: a later PR adds a cell, a configuration or a
per-layer metric by adding files and an entry to ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: what a traffic file may say; one generator reads all of them
TRAFFIC_KEYS = {
    "op": str,                # "write_full" | "read"
    "object_bytes": int,
    "clients": int,           # closed loop: one op in flight each
    "preload_objects": int,   # written during set-up (reads need them)
    "osds_down": int,         # killed during set-up, chosen by seed
    "degraded_share": float,  # with OSDs down: the share of reads sent
                              # to objects that lack a data shard
    "max_objects": int,       # a window that writes more fails loudly
    "payload_pool": int,      # distinct seeded buffers made in set-up
    "warm_bursts": list,      # concurrent ops per warm-up burst
    "check_sample": int,      # objects compared with the reference
    "op_timeout_s": float,
}


class SpecError(ValueError):
    """A data file is missing, malformed or inconsistent."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            obj = json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no such file: {os.path.relpath(path, ROOT)}")
    except json.JSONDecodeError as exc:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {exc}")
    if not isinstance(obj, dict):
        raise SpecError(f"{os.path.relpath(path, ROOT)}: not an object")
    return obj


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: list, name: str, what: str) -> dict:
    for ent in entries:
        if ent.get("name") == name:
            return ent
    known = ", ".join(e.get("name", "?") for e in entries)
    raise SpecError(f"no {what} named {name!r} (known: {known})")


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    mix = _load_json(os.path.join(bench_dir, "traffic", name + ".json"))
    for key, kind in TRAFFIC_KEYS.items():
        if key not in mix:
            raise SpecError(f"traffic {name}: key {key!r} is missing")
        val = mix[key]
        if kind is float and isinstance(val, int):
            val = mix[key] = float(val)
        if not isinstance(val, kind) or isinstance(val, bool):
            raise SpecError(f"traffic {name}: {key} = {val!r} is not "
                            f"{kind.__name__}")
    reports = mix.get("reports", {})
    tail = reports.get("tail", {})
    if not isinstance(reports.get("throughput"), str) or \
            not isinstance(tail.get("name"), str) or \
            not 0 < float(tail.get("quantile", 0)) < 1:
        raise SpecError(f"traffic {name}: reports has to name a "
                        "throughput metric and a tail with its "
                        "quantile")
    if mix["op"] not in ("write_full", "read"):
        raise SpecError(f"traffic {name}: op {mix['op']!r}")
    if mix["op"] == "read" and mix["preload_objects"] < 1:
        raise SpecError(f"traffic {name}: reads need preload_objects")
    if min(mix["object_bytes"], mix["clients"], mix["payload_pool"],
           mix["check_sample"]) < 1 or mix["osds_down"] < 0 or \
            not 0 <= mix["degraded_share"] <= 1:
        raise SpecError(f"traffic {name}: a size is out of range")
    return mix


def configuration(entry: dict, root: str = ROOT) -> dict:
    conf = _load_json(os.path.join(root, entry["file"]))
    for group, keys in (("deployment", ("n_osds", "store",
                                        "osd_heartbeat_grace")),
                       ("pool", ("plugin", "technique", "k", "m",
                                 "backend", "stripe_unit", "pg_num"))):
        missing = [k for k in keys if k not in conf.get(group, {})]
        if missing:
            raise SpecError(f"configuration {entry['name']}: "
                            f"{group} lacks {missing}")
    if not conf.get("guarantees"):
        raise SpecError(f"configuration {entry['name']}: states no "
                        "guarantees")
    return conf


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    table = _load_json(os.path.join(bench_dir, "peaks.json"))
    kinds = table.get("device_kinds", {})
    if device_kind not in kinds:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"peaks.json (known: {sorted(kinds)})")
    return kinds[device_kind]


def layer_metric(name: str, bench_dir: str = BENCH_DIR) -> dict:
    met = _load_json(os.path.join(bench_dir, "layer_metrics",
                                  name + ".json"))
    if not isinstance(met.get("reader"), str):
        raise SpecError(f"layer metric {name}: names no reader")
    if not isinstance(met.get("args", {}), dict):
        raise SpecError(f"layer metric {name}: args is not an object")
    return met


def reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of ``readers/<name>.py``."""
    path = os.path.join(bench_dir, "readers", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader file readers/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"readers/{name}.py has no read()")
    return mod.read


class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    def __init__(self, workload: str, root: str = ROOT) -> None:
        bench_dir = os.path.join(root, "benchmarks")
        bm = benchmark(root)
        self.entry = _by_name(bm["workloads"], workload, "workload")
        self.name = workload
        self.root = root
        self.chips = int(self.entry["chips"])
        self.config_entry = _by_name(bm["configs"],
                                     self.entry["config"],
                                     "configuration")
        self.config = configuration(self.config_entry, root)
        self.traffic = traffic(self.entry["traffic"], bench_dir)
        self.bench_dir = bench_dir
        #: the metrics this cell reports, in BENCHMARK.json's order
        self.end_to_end = [m for m in bm["end_to_end"]
                           if self._reports(m)]
        self.per_layer = [m for m in bm["per_layer"]
                          if self._reports(m)]

    def _reports(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells
