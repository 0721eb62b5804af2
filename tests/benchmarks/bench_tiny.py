"""A tiny copy of the benchmark's data directories for CPU tests.

The copy holds the committed data files unchanged plus NEW files only:
a tiny configuration of the pool that is there, one of ANOTHER plugin
(``shec``, with the profile key ``c`` beyond the six the harness once
named, and a reference module of its own), the same pool once more
without that module, three traffic mixes (a tiny recovery among them),
one per-layer metric over a counter no committed metric reads, and
their entries in a ``BENCHMARK.json`` of its own. Nothing that is there
is edited, which is what a later PR may do too; that the harness then
finds and runs the new cells is the test that it is driven by data.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: the harness's own code stays where it is; only data is copied,
#: and every configuration's plain reference (``*reference.py``)
DATA = ("configs", "traffic", "layer_metrics", "readers", "windows",
        "pending", "peaks.json")

#: the cell that waits in ``benchmarks/pending/`` (PERF.md section 7)
PENDING = "k8m3_recovery_4m"

#: what the fake device reports (the accelerator check is skipped)
CPU_DEVICE = {"platform": "cpu", "kind": "cpu-test", "count": 1}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _dump(obj: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def _copy_data(tmp: str) -> str:
    """``DATA`` and every reference module into ``tmp/benchmarks``."""
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
    for src in glob.glob(os.path.join(BENCH_DIR, "*reference.py")):
        shutil.copy(src, bench)
    return bench


def make_root(tmp: str) -> str:
    """Build the tiny checkout below ``tmp`` and return its root."""
    bench = _copy_data(tmp)
    before = _snapshot(bench)

    conf = _load(os.path.join(BENCH_DIR, "configs",
                              "rs_k8m3_12osd.json"))
    conf["name"] = "tiny_k8m3"
    conf["pool"].update(backend="jax", pg_num=8)
    conf["deployment"]["osd_heartbeat_grace"] = 4
    _dump(conf, os.path.join(bench, "configs", "tiny_k8m3.json"))

    # another plugin: the whole profile (``c``) has to reach it, and
    # only its own reference module states what its shards are
    shec = _load(os.path.join(BENCH_DIR, "configs",
                              "rs_k8m3_12osd.json"))
    shec.update(name="tiny_shec", reference="shec_reference")
    shec["pool"] = {"plugin": "shec", "technique": "single", "k": 6,
                    "m": 4, "c": 3, "backend": "jax",
                    "stripe_unit": 4096, "pg_num": 8}
    shec["deployment"]["osd_heartbeat_grace"] = 4
    _dump(shec, os.path.join(bench, "configs", "tiny_shec.json"))
    shutil.copy(os.path.join(HERE, "shec_reference.py"),
                os.path.join(bench, "shec_reference.py"))
    del shec["reference"]
    shec["name"] = "tiny_shec_by_rs"
    _dump(shec, os.path.join(bench, "configs", "tiny_shec_by_rs.json"))

    for name, base, extra in (
            ("tiny_write", "write_4m", {}),
            ("tiny_degraded", "degraded_read_4m",
             {"preload_objects": 12, "payload_pool": 12,
              "max_objects": 12}),
            ("tiny_recover", "recovery_4m",
             # no 64 MiB flush closes a tiny batch: a flush can hold
             # every rebuild that is in flight
             {"preload_objects": 24, "payload_pool": 12,
              "warm_bursts": [1, 2, 4, 8, 16, 32],
              "clean_timeout_s": 60, "poll_s": 0.05})):
        mix = _load(os.path.join(BENCH_DIR, "traffic", base + ".json"))
        mix.update(object_bytes=64 << 10, clients=4,
                   warm_bursts=[1, 2, 4], check_sample=4,
                   payload_pool=8)
        mix.update(extra)
        _dump(mix, os.path.join(bench, "traffic", name + ".json"))

    table = _load(os.path.join(bench, "peaks.json"))
    bm = _load(os.path.join(ROOT, "BENCHMARK.json"))
    bm["configs"] += [
        {"name": name, "source": "tests/benchmarks: " + name,
         "file": f"benchmarks/configs/{name}.json", "reduced": [],
         "why": "CPU test size"}
        for name in ("tiny_k8m3", "tiny_shec", "tiny_shec_by_rs")]
    # the pending cell is registered as the PR that admits it will do
    # it: by its entries alone (here with a bound that nothing judges)
    pending = _load(os.path.join(bench, "pending", PENDING + ".json"))
    bm["workloads"].append(pending["workload"])
    setup = next(m for m in bm["end_to_end"] if m["name"] == "setup_s")
    bm["end_to_end"].remove(setup)
    bm["end_to_end"] += [dict(met, bound=0.25)
                         for met in pending["end_to_end"]] + [setup]
    bm["per_layer"] += pending["per_layer"]
    like = {"tiny.write": ("tiny_k8m3", "tiny_write", "k8m3_write_4m"),
            "tiny.degraded": ("tiny_k8m3", "tiny_degraded",
                              "k8m3_degraded_read_4m"),
            "tiny.recover": ("tiny_k8m3", "tiny_recover", PENDING),
            "tiny.shec_write": ("tiny_shec", "tiny_write",
                                "k8m3_write_4m"),
            "tiny.shec_by_rs": ("tiny_shec_by_rs", "tiny_write",
                                "k8m3_write_4m")}
    for cell, (config, mix, real) in like.items():
        _register_like(bm, {"name": cell, "config": config,
                            "traffic": mix, "chips": 1,
                            "why": "CPU test"}, real)
    bm["per_layer"].append(_bytes_per_flush(bench, "tiny_bytes_per_flush",
                                            ["tiny.write"]))
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


def copy_root(tmp: str) -> str:
    """A copy below ``tmp`` of ``BENCHMARK.json`` and of the data files
    and reference modules ``make_root`` copies, unchanged; its root."""
    _copy_data(tmp)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    return tmp


def _register_like(bm: dict, cell: dict, like: str) -> None:
    """Register the workload entry ``cell`` as a later PR does: by
    appended names alone, on every list the cell ``like`` is on."""
    bm["workloads"].append(cell)
    for metric in bm["end_to_end"] + bm["per_layer"]:
        cells = metric.get("workloads")
        if cells and like in cells:
            cells.append(cell["name"])


def _bytes_per_flush(bench: str, name: str, cells: list) -> dict:
    """A per-layer metric over a counter nothing committed reads,
    through a reader that is already there: its data file written
    below ``bench``, and its ``per_layer`` entry on ``cells``."""
    _dump({"layer": "engine", "unit": "bytes/flush",
           "moves": "write_MBps", "reader": "stat_ratio",
           "args": {"num": "bytes", "den": "flushes"}},
          os.path.join(bench, "layer_metrics", name + ".json"))
    return {"name": name, "unit": "bytes/flush", "better": "higher",
            "source": "program_counter", "layer": "engine",
            "moves": "write_MBps", "workloads": list(cells)}


#: what ``with_an_addition`` brings, every name new: a cell of each
#: kind a later PR is named to add (PERF.md section 7), each on a
#: configuration of its own copied from one that is there, on a
#: traffic mix that is there; cell -> (configuration, copied from,
#: traffic, the cell it is like)
ADDED = {
    "added.write_4m": ("added_k8m3_11osd", "rs_k8m3_12osd", "write_4m",
                       "k8m3_write_4m"),
    "added.rbd_randwrite_4k_1down": (
        "added_rbd_ec_k8m3_11osd", "rbd_ec_k8m3_12osd",
        "rbd_randwrite_4k", "rbd_k8m3_randwrite_4k"),
    "added.clay_degraded_read_4m": (
        "added_clay_k8m4d11_12osd", "clay_k8m4d11_13osd",
        "degraded_read_4m", "clay_k8m4d11_degraded_read_4m")}
#: and two per-layer metrics: one on every cell that reports
#: ``write_MBps`` (a kind's metric), one on one cell and not on the
#: cell of the same traffic on another configuration (a cell's own)
ADDED_METRICS = {"added_bytes_per_flush.write": None,
                 "added_bytes_per_flush.clay": ["clay_k8m4d11_write_4m"]}


def with_an_addition(tmp: str) -> str:
    """``copy_root`` with ``ADDED`` and ``ADDED_METRICS`` added as a
    later PR adds them: new files, and names appended to the lists of
    ``BENCHMARK.json`` alone."""
    root = copy_root(tmp)
    bench = os.path.join(root, "benchmarks")
    bm = _load(os.path.join(root, "BENCHMARK.json"))
    for cell, (config, like_config, mix, like) in ADDED.items():
        conf = _load(os.path.join(BENCH_DIR, "configs",
                                  like_config + ".json"))
        conf.update(name=config, source="tests/benchmarks: an addition")
        _dump(conf, os.path.join(bench, "configs", config + ".json"))
        bm["configs"].append(
            {"name": config, "source": conf["source"],
             "file": f"benchmarks/configs/{config}.json",
             "reduced": [], "why": "an addition"})
        _register_like(bm, {"name": cell, "config": config,
                            "traffic": mix, "chips": 1,
                            "why": "an addition"}, like)
    writes = next(m for m in bm["end_to_end"]
                  if m["name"] == "write_MBps")["workloads"]
    for name, cells in ADDED_METRICS.items():
        bm["per_layer"].append(_bytes_per_flush(bench, name,
                                                cells or writes))
    _dump(bm, os.path.join(root, "BENCHMARK.json"))
    return root


def _snapshot(bench: str) -> dict:
    out = {}
    for base, _dirs, files in os.walk(bench):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, bench)] = f.read()
    return out


def run_main(capfd, root: str, workload: str, trace: int = 0,
             seed: int = 3_000_000_011, seconds: float = 1.5
             ) -> tuple[int, list[str]]:
    """The command's own ``main`` in this process, the look for a chip
    skipped: (exit code, the lines of standard output)."""
    import run
    out = run.Out()
    try:
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace",
                       str(trace)], root=root, device=CPU_DEVICE,
                      out=out)
    finally:
        out.restore()
    return rc, [ln for ln in capfd.readouterr().out.splitlines() if ln]


def last_line(capfd, root: str, workload: str, **how) -> dict:
    """``run_main`` that has to exit 0 and print one line: that line."""
    rc, lines = run_main(capfd, root, workload, **how)
    assert rc == 0 and len(lines) == 1
    return json.loads(lines[-1])
