"""Finds everything a cell is made of, by name, from data files.

``BENCHMARK.json`` at the root of the checkout names the cells, the
configurations and the metrics. What belongs to one of them sits in a
file of its own below ``benchmarks/`` and is found by that name:

    configs/<configuration>.json        the deployment and its pool:
                                        every key of ``pool`` but
                                        ``stripe_unit`` and ``pg_num``
                                        is the erasure-code profile
    <reference>.py                      the configuration's plain
                                        reference (``"reference"`` in
                                        its file; default ``reference``)
    traffic/<traffic>.json              the mix; its ``op`` names ...
    windows/<op>.py                     ... the window kind that runs it
    layer_metrics/<metric>.json         a per-layer metric: its reader
                                        and the reader's arguments
    readers/<reader>.py                 a reader: ``read(ctx, **args)``
    peaks.json                          device kind -> published peaks
    pending/<cell>.json                 a cell that is built and not
                                        yet in ``BENCHMARK.json``: its
                                        entries as they would stand
                                        there (see ``benchmark``)

Nothing is listed in code: a later PR adds a cell, a configuration or a
per-layer metric by adding files and an entry to ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: what every traffic file says, whatever its window kind; the kind
#: declares the keys of its own (``Window.KEYS``)
TRAFFIC_KEYS = {
    "op": str,                # the window kind: windows/<op>.py
    "object_bytes": int,
    "clients": int,           # set-up writers; closed loop: one op in
                              # flight each
    "preload_objects": int,   # written during set-up
    "payload_pool": int,      # distinct seeded buffers made in set-up
    "warm_bursts": list,      # concurrent ops per warm-up burst
    "check_sample": int,      # objects compared with the reference
    "op_timeout_s": float,
}

#: the keys of a configuration's ``pool`` that are the pool's own and
#: not the erasure-code profile's
POOL_OWN_KEYS = ("stripe_unit", "pg_num")


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


def benchmark(root: str = ROOT, pending: str | None = None) -> dict:
    """``BENCHMARK.json``. With ``pending``, the name of a cell it
    does not have: with the entries of ``pending/<cell>.json`` added
    where that file exists, so that a builder can run and trace a
    cell that no check judges yet (its end-to-end metrics have no
    bound)."""
    bm = _load_json(os.path.join(root, "BENCHMARK.json"))
    if not _plain(pending) or pending in (
            w.get("name") for w in bm["workloads"]):
        return bm
    path = os.path.join(root, "benchmarks", "pending", pending + ".json")
    if os.path.isfile(path):
        more = _load_json(path)
        bm["workloads"] = bm["workloads"] + [more["workload"]]
        bm["end_to_end"] = bm["end_to_end"] + more["end_to_end"]
        bm["per_layer"] = bm["per_layer"] + more["per_layer"]
    return bm


def _by_name(entries: list, name: str, what: str) -> dict:
    for ent in entries:
        if ent.get("name") == name:
            return ent
    known = ", ".join(e.get("name", "?") for e in entries)
    raise SpecError(f"no {what} named {name!r} (known: {known})")


#: a file found by a name from a data file is named as the contract
#: names things: no slash, no way out of its directory
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


def _plain(name) -> bool:
    return isinstance(name, str) and bool(_NAME.fullmatch(name))


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_keys(mix: dict, keys: dict, name: str) -> None:
    for key, kind in keys.items():
        if key not in mix:
            raise SpecError(f"traffic {name}: key {key!r} is missing")
        val = mix[key]
        if kind is float and isinstance(val, int):
            val = mix[key] = float(val)
        if not isinstance(val, kind) or isinstance(val, bool):
            raise SpecError(f"traffic {name}: {key} = {val!r} is not "
                            f"{kind.__name__}")


def window_kind(op: str, bench_dir: str = BENCH_DIR):
    """The ``Window`` class of ``windows/<op>.py``: the keys the kind
    declares (``KEYS``), what it refuses (``check``), what it needs
    from set-up (``prepare``) and the window itself (``run``)."""
    path = os.path.join(bench_dir, "windows", str(op) + ".py")
    if not _plain(op) or not os.path.isfile(path):
        raise SpecError(f"no window kind {op!r}: no file "
                        f"windows/{op}.py")
    kind = getattr(_module(path, f"benchmarks_window_{op}"), "Window",
                   None)
    if kind is None or not isinstance(getattr(kind, "KEYS", None),
                                      dict):
        raise SpecError(f"windows/{op}.py has no Window with KEYS")
    return kind


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    mix = _load_json(os.path.join(bench_dir, "traffic", name + ".json"))
    _check_keys(mix, TRAFFIC_KEYS, name)
    kind = window_kind(mix["op"], bench_dir)
    _check_keys(mix, kind.KEYS, name)
    reports = mix.get("reports", {})
    tail = reports.get("tail", {})
    if not isinstance(reports.get("throughput"), str) or \
            not isinstance(tail.get("name"), str) or \
            not 0 < float(tail.get("quantile", 0)) < 1:
        raise SpecError(f"traffic {name}: reports has to name a "
                        "throughput metric and a tail with its "
                        "quantile")
    if min(mix["object_bytes"], mix["clients"], mix["payload_pool"],
           mix["check_sample"]) < 1 or mix["preload_objects"] < 0:
        raise SpecError(f"traffic {name}: a size is out of range")
    problem = kind.check(mix)
    if problem:
        raise SpecError(f"traffic {name}: {problem}")
    return mix


def configuration(entry: dict, root: str = ROOT) -> dict:
    conf = _load_json(os.path.join(root, entry["file"]))
    for group, keys in (("deployment", ("n_osds", "store",
                                        "osd_heartbeat_grace")),
                       ("pool", ("plugin", "k", "m", "backend",
                                 "stripe_unit", "pg_num"))):
        missing = [k for k in keys if k not in conf.get(group, {})]
        if missing:
            raise SpecError(f"configuration {entry['name']}: "
                            f"{group} lacks {missing}")
    if not conf.get("guarantees"):
        raise SpecError(f"configuration {entry['name']}: states no "
                        "guarantees")
    return conf


def ec_profile(pool: dict) -> dict:
    """The erasure-code profile of a configuration's ``pool``: every
    key but the pool's own, as it stands."""
    return {key: val for key, val in pool.items()
            if key not in POOL_OWN_KEYS}


def reference_module(config: dict, bench_dir: str = BENCH_DIR):
    """The configuration's plain reference: ``<reference>.py`` beside
    this file (``"reference"`` in the configuration's file, default
    ``reference``), which states ``shards(data, pool)``."""
    name = config.get("reference", "reference")
    path = os.path.join(bench_dir, str(name) + ".py")
    if not _plain(name) or not os.path.isfile(path):
        raise SpecError(f"configuration {config.get('name')}: no "
                        f"reference module {name}.py")
    mod = _module(path, f"benchmarks_reference_{name}")
    if not callable(getattr(mod, "shards", None)):
        raise SpecError(f"{name}.py has no shards(data, pool)")
    return mod


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
    mod = _module(path, f"benchmarks_reader_{name}")
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"readers/{name}.py has no read()")
    return mod.read


class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    def __init__(self, workload: str, root: str = ROOT) -> None:
        bench_dir = os.path.join(root, "benchmarks")
        bm = benchmark(root, pending=workload)
        self.entry = _by_name(bm["workloads"], workload, "workload")
        self.name = workload
        self.root = root
        self.chips = int(self.entry["chips"])
        self.config_entry = _by_name(bm["configs"],
                                     self.entry["config"],
                                     "configuration")
        self.config = configuration(self.config_entry, root)
        self.reference = reference_module(self.config, bench_dir)
        self.traffic = traffic(self.entry["traffic"], bench_dir)
        self.window = window_kind(self.traffic["op"], bench_dir)
        self.bench_dir = bench_dir
        #: the metrics this cell reports, in BENCHMARK.json's order
        self.end_to_end = [m for m in bm["end_to_end"]
                           if self._reports(m)]
        self.per_layer = [m for m in bm["per_layer"]
                          if self._reports(m)]

    def _reports(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells
