"""What the tests that run a tiny cell share."""

import pytest

import bench_tiny


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture
def cpu_env(monkeypatch):
    """What the test, not the program, steers: the tiny flushes stay on
    the device route (a 64 KiB flush is below host_flush_bytes; the jax
    backend fuses the crc pass only when asked)."""
    from ceph_tpu.utils import faults
    from ceph_tpu.utils.device_telemetry import telemetry
    monkeypatch.setenv("CEPH_TPU_HOST_FLUSH_BYTES", "0")
    monkeypatch.setenv("CEPH_TPU_FUSE_CRC", "1")
    faults.reset_for_tests(0)
    telemetry().reset()
    yield
    faults.reset_for_tests(0)
