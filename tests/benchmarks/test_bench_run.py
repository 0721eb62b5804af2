"""The benchmark's command, rehearsed in-process on the CPU at a tiny
size through the same ``main`` the chip run uses: the form of the last
line, cells found from new data files alone, the timed path broken
underneath (``correct`` has to come out false), the control, and the
refusal to run without an accelerator.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_tiny
from bench_tiny import BENCH_DIR, CPU_DEVICE, ROOT

import control
import run
import spec
from loadgen import ClosedLoop, Payloads

E2E_KEYS = {"correct", "attempted", "failed", "metrics", "device",
            "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


_run = bench_tiny.run_main


def test_last_line_end_to_end(tiny_root, cpu_env, capfd):
    rc, lines = _run(capfd, tiny_root, "tiny.write", trace=0)
    assert rc == 0 and len(lines) == 1
    last = json.loads(lines[-1])
    assert set(last) == E2E_KEYS
    assert list(last)[-1] == "compared"
    assert last["correct"] is True, last["compared"]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["device"]) == DEVICE_KEYS
    cell = spec.Cell("tiny.write", tiny_root)
    assert set(last["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert set(last["metrics"]) == {"write_MBps", "write_p95_ms",
                                    "setup_s"}
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    for name, met in last["metrics"].items():
        assert set(met) == {"value", "unit"}
        assert met["value"] > 0, name
        assert met["unit"] == units[name]
    for name, row in last["compared"].items():
        assert set(row) == {"value", "limit", "rule"}, name
    assert last["compared"]["compiled_in_window"]["value"] == 0
    assert last["compared"]["encode_flushes"]["value"] > 0


def test_last_line_traced(tiny_root, cpu_env, capfd):
    rc, lines = _run(capfd, tiny_root, "tiny.write", trace=1,
                     seconds=2.5)
    assert rc == 0 and len(lines) == 1
    last = json.loads(lines[-1])
    assert set(last) == E2E_KEYS | {"breakdown"}
    assert list(last)[-1] == "compared"
    assert last["correct"] is True, last["compared"]
    assert set(last["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert last["device"]["window_s"] > 0
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    cell = spec.Cell("tiny.write", tiny_root)
    per_layer = {m["name"] for m in cell.per_layer}
    assert set(last["metrics"]) <= per_layer
    # host-clock and counter metrics read on any platform ...
    for name in ("client_wire_ms.write", "osd_queue_ms.write",
                 "engine_wait_ms.write", "commit_wait_ms.write",
                 "encode_ops_per_flush"):
        assert last["metrics"][name]["value"] > 0, name
    # ... the metric that only a dropped-in file defines is found ...
    assert last["metrics"]["tiny_bytes_per_flush"]["value"] >= 64 << 10
    # ... and a CPU has no device plane: the device metrics find
    # nothing to read and are left out, never reported as 0
    for name in ("encode_roofline", "device_idle_pct.write"):
        assert name in per_layer and name not in last["metrics"]


def test_degraded_cell_reads_through_the_decode_path(tiny_root, cpu_env,
                                                    capfd):
    rc, lines = _run(capfd, tiny_root, "tiny.degraded", trace=0)
    assert rc == 0 and len(lines) == 1
    last = json.loads(lines[-1])
    assert last["correct"] is True, last["compared"]
    assert set(last["metrics"]) == {"degraded_read_MBps",
                                    "degraded_read_p90_ms", "setup_s"}
    assert last["compared"]["decode_flushes"]["value"] >= 1
    assert last["compared"]["reads_unequal"] == {
        "value": 0, "limit": 0, "rule": "<="}


# -- the timed path broken underneath: correct has to read false -------

def _break_parity(monkeypatch):
    from ceph_tpu.osd.ec_backend import ECBackend
    real = ECBackend._finish_write

    def broken(self, pg, oid, data, version, shards, on_commit,
               crcs=None):
        shards = dict(shards)
        bad = np.array(shards[self.k], dtype=np.uint8)
        bad[0] ^= 1
        shards[self.k] = bad
        return real(self, pg, oid, data, version, shards, on_commit,
                    crcs=crcs)
    monkeypatch.setattr(ECBackend, "_finish_write", broken)


def _break_crc(monkeypatch):
    from ceph_tpu.osd.ec_backend import ECBackend
    real = ECBackend._finish_write

    def broken(self, pg, oid, data, version, shards, on_commit,
               crcs=None):
        assert crcs is not None, "the fused crc pass is off"
        crcs = dict(crcs)
        crcs[self.n - 1] ^= 1
        return real(self, pg, oid, data, version, shards, on_commit,
                    crcs=crcs)
    monkeypatch.setattr(ECBackend, "_finish_write", broken)


def _break_read(monkeypatch):
    from ceph_tpu.osd.ec_backend import ECBackend
    real = ECBackend._chunks_to_logical

    def broken(self, shards, size):
        out = bytearray(real(self, shards, size))
        out[len(out) // 2] ^= 1
        return bytes(out)
    monkeypatch.setattr(ECBackend, "_chunks_to_logical", broken)


def _break_route(monkeypatch):
    # the shipped threshold: a 64 KiB flush takes the host route
    monkeypatch.setenv("CEPH_TPU_HOST_FLUSH_BYTES", str(512 << 10))


@pytest.mark.parametrize("workload,fault,fails", [
    ("tiny.write", _break_parity, "shards_unequal"),
    ("tiny.write", _break_crc, "crcs_unequal"),
    ("tiny.write", _break_route, "host_flushes"),
    ("tiny.degraded", _break_read, "reads_unequal"),
], ids=["parity_altered", "crc_altered", "left_the_device_route",
        "read_answer_altered"])
def test_a_broken_timed_path_is_not_correct(tiny_root, cpu_env, capfd,
                                            monkeypatch, workload,
                                            fault, fails):
    fault(monkeypatch)
    rc, lines = _run(capfd, tiny_root, workload, trace=0)
    assert rc == 0 and len(lines) == 1
    last = json.loads(lines[-1])
    assert last["correct"] is False
    row = last["compared"][fails]
    assert row["value"] > row["limit"], last["compared"]


# -- the control --------------------------------------------------------

WRITE_CONTROLS = {"one_parity_short", "crc_not_kept"}
RECOVERY_CONTROLS = {"rebuilt_by_xor"}


@pytest.mark.parametrize("workload,controls,seed", [
    ("tiny.write", WRITE_CONTROLS, 1),
    ("tiny.write", WRITE_CONTROLS, 2_500_000_000),
    ("tiny.write", WRITE_CONTROLS, 77),
    ("tiny.degraded", {"not_reconstructed"}, 1),
    ("tiny.degraded", {"not_reconstructed"}, 2_500_000_000),
    ("tiny.degraded", {"not_reconstructed"}, 77),
    ("tiny.recover", RECOVERY_CONTROLS, 1),
    ("tiny.recover", RECOVERY_CONTROLS, 2_500_000_000),
    ("tiny.recover", RECOVERY_CONTROLS, 77),
    # another plugin's pool is held to its own reference module
    ("tiny.shec_write", WRITE_CONTROLS, 1),
    # one committed cell at its own size (the chip runs have all three)
    ("k4m2_write_1m", WRITE_CONTROLS, 1),
])
def test_control_comes_out_not_correct(tiny_root, workload, controls,
                                       seed):
    root = ROOT if workload.startswith("k") else tiny_root
    results = control.run_controls(spec.Cell(workload, root), seed)
    assert {r["control"] for r in results} == controls
    for res in results:
        assert res["correct"] is False, res
        over = [n for n, row in res["compared"].items()
                if row["rule"] == "<=" and row["value"] > row["limit"]]
        assert over, res


# -- the loop, on a fake client ------------------------------------------

class _FakeIo:
    def __init__(self, payloads, fail_every=0, corrupt=False):
        self.payloads = payloads
        self.fail_every = fail_every
        self.corrupt = corrupt
        self.calls = 0

    def write_full(self, name, data):
        self.calls += 1
        if self.fail_every and self.calls % self.fail_every == 0:
            raise TimeoutError("op timed out")

    def read(self, name):
        data = self.payloads.of(name)
        return data[:-1] + b"\x00" if self.corrupt else data


def _mix(**over):
    mix = spec.traffic("write_4m")
    # the fake client answers at once: far more ops than any real
    # window, so the bound on objects is out of the way unless asked for
    mix.update(object_bytes=1024, clients=2, payload_pool=4,
               max_objects=10**9)
    mix.update(over)
    return mix


def test_loop_counts_failed_ops_and_all_the_windows_time():
    mix = _mix()
    payloads = Payloads(5, 1024, 4)
    loop = ClosedLoop(_FakeIo(payloads, fail_every=5), mix, payloads, 5)
    loop.run(0.2)
    summary = loop.summary()
    assert summary["attempted"] == len(loop.ops()) > 10
    assert summary["failed"] == sum(1 for r in loop.ops() if not r.ok)
    assert summary["failed"] >= summary["attempted"] // 5 - 2 > 0
    assert summary["window_s"] >= 0.2
    good = summary["attempted"] - summary["failed"]
    assert summary["MBps"] == pytest.approx(
        good * 1024 / summary["window_s"] / 1e6)
    assert len(summary["latencies_ms"]) == good


def test_loop_stops_loudly_at_max_objects():
    mix = _mix(max_objects=7)
    payloads = Payloads(5, 1024, 4)
    loop = ClosedLoop(_FakeIo(payloads), mix, payloads, 5)
    loop.run(0.5)
    assert loop.overflow and len(loop.ops()) == 7


def test_loop_marks_unequal_reads():
    mix = _mix(op="read", preload_objects=4)
    payloads = Payloads(5, 1024, 4)
    names = [f"obj_{i}" for i in range(4)]
    loop = ClosedLoop(_FakeIo(payloads, corrupt=True), mix, payloads, 5,
                      read_names=names)
    loop.run(0.05)
    assert loop.ops() and all(r.equal is False for r in loop.ops())
    same = ClosedLoop(_FakeIo(payloads), mix, payloads, 5,
                      read_names=names)
    same.run(0.05)
    assert all(r.equal is True for r in same.ops())
    # every seed draws the same mix of names, in another order
    assert {r.name for r in same.ops()} <= set(names)


def test_loop_sends_the_stated_share_of_reads_to_degraded_objects():
    payloads = Payloads(5, 1024, 4)
    intact = [f"obj_{i}" for i in range(0, 8)]
    degraded = [f"obj_{i}" for i in range(8, 11)]
    shares = {}
    for share in (0.5, 1.0):
        mix = _mix(op="read", preload_objects=11, osds_down=2,
                   degraded_share=share)
        loop = ClosedLoop(_FakeIo(payloads), mix, payloads, 5,
                          read_names=intact, degraded_names=degraded)
        loop.run(0.1)
        names = [r.name for r in loop.ops()]
        assert len(names) > 200
        shares[share] = sum(n in degraded for n in names) / len(names)
    # three degraded objects of eleven, and still half of the reads
    assert 0.4 < shares[0.5] < 0.6 and shares[1.0] == 1.0
    # no OSD down: the share is not read; nothing degraded to send the
    # stated share to: refused before the window
    clean = ClosedLoop(_FakeIo(payloads),
                       _mix(op="read", preload_objects=8), payloads, 5,
                       read_names=intact)
    clean.run(0.02)
    assert {r.name for r in clean.ops()} <= set(intact)
    with pytest.raises(ValueError):
        ClosedLoop(_FakeIo(payloads),
                   _mix(op="read", preload_objects=8, osds_down=2,
                        degraded_share=0.5), payloads, 5,
                   read_names=intact)


# -- the command line ----------------------------------------------------

def test_no_accelerator_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", "k8m3_write_4m", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_unknown_workload_exits_nonzero_and_prints_no_result(capfd):
    out = run.Out()
    try:
        rc = run.main(["--workload", "nope", "--seed", "1", "--seconds",
                       "1"], device=CPU_DEVICE, out=out)
    finally:
        out.restore()
    assert rc != 0
    assert capfd.readouterr().out.strip() == ""
