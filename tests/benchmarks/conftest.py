"""What the tests that run a tiny cell share."""

import pytest

import bench_tiny


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module", params=["as_it_is", "with_an_addition"])
def registry_root(request, tmp_path_factory):
    """The root whose ``BENCHMARK.json`` a registration check reads: the
    checkout as it is, and a copy with configurations, cells and
    per-layer metrics added by new files and appended names alone, as a
    later PR adds them (``bench_tiny.ADDED``). A check that holds on the
    first and not on the second reads a position or a closed list, not
    a name."""
    if request.param == "as_it_is":
        return bench_tiny.ROOT
    return bench_tiny.with_an_addition(
        str(tmp_path_factory.mktemp("added")))


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
