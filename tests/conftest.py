"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

The tests run on the CPU platform: sharding/collective tests on 8
virtual CPU devices (``--xla_force_host_platform_device_count=8``, the
same trick ``__graft_entry__.dryrun_multichip`` uses), Pallas kernels in
interpret mode. The environment may have imported jax before this file
runs, so besides the env vars the platform is also pinned with
jax.config.update, before any backend is initialized. The chip itself
is reached only by ``chip_smoke.py`` (one chip; ``--chips 4`` for the
mesh), and tests/test_chip_compile.py compiles the main path's kernels
for a described chip without one.
"""

import os

# CEPH_TPU_TEST_TPU=1 keeps the real chip visible (the driver's
# backend=pallas cluster-suite gate); default CI forces the virtual
# CPU mesh.
if not os.environ.get("CEPH_TPU_TEST_TPU"):
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

# Lock-order witness (ISSUE 11): CEPH_TPU_LOCK_WITNESS=1 arms the
# pylockdep for the WHOLE session — every make_lock/make_rlock/
# make_condition site constructs a named, tracked proxy and the
# acquisition-order graph + blocking-under-lock findings serialize to
# a JSON report at teardown (CEPH_TPU_LOCK_WITNESS_REPORT, default
# lock_witness_report.json in the cwd). Off (the default) the seams
# return bare threading primitives — zero wrappers, zero cost; the
# tier-1 gate tests in test_lock_witness.py enable it per-test
# instead.
from ceph_tpu.analysis import lock_witness as _lock_witness

if _lock_witness.env_enabled():
    _lock_witness.enable()

# Lock timing (ISSUE 17): CEPH_TPU_LOCK_TIMING=1 arms the wait-vs-hold
# timing layer for the session — observations feed the `dispatch`
# telemetry registry. Independent of the witness; off by default.
if _lock_witness.timing_env_enabled():
    _lock_witness.enable_timing()


def pytest_sessionfinish(session, exitstatus):
    if _lock_witness.env_enabled() and _lock_witness.enabled():
        path = os.environ.get("CEPH_TPU_LOCK_WITNESS_REPORT",
                              "lock_witness_report.json")
        try:
            _lock_witness.save_report(path)
        except OSError:
            pass
