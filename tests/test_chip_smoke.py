"""chip_smoke.py's contract, rehearsed on the CPU at a tiny size.

The chip run itself needs a TPU; what can break without one is the
shape of the last line, the refusal to run without an accelerator,
and the rule that a counted fallback fails the run. All three are
pinned here through the same ``run``/``final_line`` the chip run uses.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

#: k=8,m=3 needs 11 OSDs; 12 as in the chip run, the rest shrunk
TINY = chip_smoke.Size(backend="jax", obj_bytes=64 << 10, n_objects=12,
                       writers=4, n_oracle=3, n_extra=4, pg_num=8,
                       op_timeout=120.0, clean_timeout=120.0,
                       heartbeat_grace=4.0)


@pytest.fixture
def tiny_env(monkeypatch):
    """What the test, not the program, steers: no accelerator check,
    and the tiny flushes kept on the device route (a 64 KiB flush is
    below host_flush_bytes and would count as a host flush; the jax
    backend fuses the crc pass only when asked)."""
    from ceph_tpu.utils import faults
    from ceph_tpu.utils.device_telemetry import telemetry
    monkeypatch.setattr(
        chip_smoke, "accelerator",
        lambda chips: {"platform": "cpu", "kind": "cpu", "count": 8})
    monkeypatch.setenv("CEPH_TPU_HOST_FLUSH_BYTES", "0")
    monkeypatch.setenv("CEPH_TPU_FUSE_CRC", "1")
    faults.reset_for_tests(0)
    telemetry().reset()
    yield
    faults.reset_for_tests(0)


def _run(capfd, size=TINY) -> tuple[int, list[str]]:
    out = chip_smoke.Out()
    try:
        rc = chip_smoke.run(0, 1, out, size)
    finally:
        out.restore()
    lines = [ln for ln in capfd.readouterr().out.splitlines() if ln]
    return rc, lines


def test_last_line_is_the_contracts_object(tiny_env, capfd):
    rc, lines = _run(capfd)
    phases = [json.loads(ln) for ln in lines[:-1]]
    failed = [p for p in phases if p.get("failed")]
    assert rc == 0 and not failed, failed
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["ok"] is True
    assert last == chip_smoke.final_line(
        {"platform": "cpu", "kind": "cpu", "count": 8})
    assert lines[-1] == json.dumps(last)
    # every phase reported before it, and only the last line says ok
    names = [p["phase"] for p in phases if "phase" in p]
    assert names == ["warm", "write", "read", "oracle",
                     "degraded_read", "recovery", "second_pass"]
    assert sum('"ok": true' in ln for ln in lines) == 1
    by_name = {p["phase"]: p for p in phases if "phase" in p}
    assert by_name["write"]["host_flushes"] == 0
    assert by_name["write"]["device_bytes"] >= \
        TINY.n_objects * TINY.obj_bytes
    assert by_name["oracle"]["shards_compared"] == 3 * 11


def test_forced_engine_fallback_fails_the_run(tiny_env, capfd):
    from ceph_tpu.utils import faults
    faults.registry().add("engine_launch", every=3, max_fires=2)
    rc, lines = _run(capfd)
    assert rc != 0
    assert not any('"ok": true' in ln for ln in lines)
    failed = json.loads(lines[-1])["failed"]
    assert any("errors == 0" in f for f in failed), failed


def test_no_accelerator_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert '"ok": true' not in proc.stderr
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
