"""The engine's per-layer metrics registered by entries alone: the ship
thread's groups a ship (``groups_per_ship.write``) and ms a flush
(``flush_ship_ms.write``), and the busy share of the launch thread
(``launch_active_threads.*``), each a data file over a reader that is
there. Declared as their files
say, read by name from ``BENCHMARK.json`` as it is and with an
addition; and read by a traced tiny run of the write and the degraded
kind on the CPU."""

import os

import pytest

import bench_tiny

import spec

WRITES = {"k8m3_write_4m", "k4m2_write_1m", "clay_k8m4d11_write_4m",
          "rbd_k8m3_randwrite_4k"}
DEGRADED = {"k8m3_degraded_read_4m", "clay_k8m4d11_degraded_read_4m"}
#: name -> (reader, its arguments, source, the cells, what it moves)
ENTRIES = {
    "groups_per_ship.write": (
        "stat_ratio", {"num": "ship_groups", "den": "ships"},
        "program_counter", WRITES, "write_MBps"),
    "flush_ship_ms.write": (
        "host_span_ms", {"spans": ["flush_ship"],
                         "per_counter": "flushes"},
        "program_span", WRITES, "write_p95_ms"),
    "launch_active_threads.write": (
        "role_active_threads", {"role": "engine_launch"},
        "program_span", WRITES, "write_MBps"),
    "launch_active_threads.degraded": (
        "role_active_threads", {"role": "engine_launch"},
        "program_span", DEGRADED, "degraded_read_MBps")}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_is_declared_as_its_file_says(registry_root, name):
    reader, args, source, cells, moves = ENTRIES[name]
    bm = spec.benchmark(registry_root)
    bench = os.path.join(registry_root, "benchmarks")
    entries = [m for m in bm["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    entry, met = entries[0], spec.layer_metric(name, bench)
    assert met["reader"] == reader and met["args"] == args
    assert callable(spec.reader(reader, bench))
    assert entry["layer"] == met["layer"] == "engine"
    assert entry["moves"] == met["moves"] == moves
    assert entry["unit"] == met["unit"]
    assert entry["source"] == source
    # every cell of its kind, whatever cells are appended, and each
    # one it lists is a cell that reports what it moves
    assert cells <= set(entry["workloads"])
    moved = next(m for m in bm["end_to_end"] if m["name"] == moves)
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert set(entry["workloads"]) <= {w["name"]
                                       for w in bm["workloads"]}


def test_the_counters_and_roles_are_the_engines_own(monkeypatch):
    """The counters ``groups_per_ship.write`` divides start at 0 in
    the engine, and the engine's launch thread states the role the
    busy shares read."""
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    from ceph_tpu.utils import profiler
    roles = []
    real = profiler.thread_role

    def thread_role(role):
        roles.append(role)
        return real(role)
    monkeypatch.setattr(profiler, "thread_role", thread_role)
    eng = DeviceEncodeEngine(lambda k, f: f())
    try:
        for counter in ENTRIES["groups_per_ship.write"][1].values():
            assert eng.stats[counter] == 0, counter
    finally:
        eng.stop()
    assert "engine_launch" in roles


def _engine_metrics(capfd, root, cell) -> dict:
    last = bench_tiny.last_line(capfd, root, cell, trace=1, seconds=2.5)
    assert last["correct"] is True, last["compared"]
    return {name: row["value"] for name, row in last["metrics"].items()
            if name in ENTRIES}


def test_a_traced_write_window_reads_the_ship_and_the_launch_thread(
        tiny_root, cpu_env, capfd):
    got = _engine_metrics(capfd, tiny_root, "tiny.write")
    assert set(got) == {name for name, ent in ENTRIES.items()
                        if ent[3] is WRITES}
    # a full-write group ships alone
    assert got["groups_per_ship.write"] == 1.0
    assert got["flush_ship_ms.write"] > 0
    # one launch thread: its busy share, never 0 (the window's length
    # is the host clock's, so an open mark may reach past it)
    assert 0 < got["launch_active_threads.write"] <= 1.05


def test_a_traced_degraded_window_reads_the_launch_thread(
        tiny_root, cpu_env, capfd):
    got = _engine_metrics(capfd, tiny_root, "tiny.degraded")
    assert set(got) == {"launch_active_threads.degraded"}
    assert 0 < got["launch_active_threads.degraded"] <= 1.05
