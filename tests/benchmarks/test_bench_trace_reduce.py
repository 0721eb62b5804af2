"""The reduction from a profiler trace to numbers, pinned on a trace
made by hand."""

import pytest

from bench_tiny import BENCH_DIR  # noqa: F401  (sets sys.path)

import trace_reduce

#: chip 0: ops [0,2) and [1,3) us overlap -> 3 us busy, then a gap of
#: 7 us, then [10,11); the Modules envelope [0,11) must not count.
#: chip 1: one op of 2 us. A host plane is ignored.
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 11000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%run.1 = s8[8,32]{1,0} custom-call(s8[16,128]{1,0} %a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2
    name: "%copy.2 = u8[3,64]{1,0} copy(u8[3,64]{1,0} %b)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_fused(1)" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%x = f32[] add(f32[] %a, f32[] %b)" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 900000000 } }
  event_metadata { key: 1 value { id: 1 name: "PjitFunction(fused)" } } }
"""


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(TRACE)


def test_union_counts_overlap_once():
    total, merged = trace_reduce.union_ns(
        [(0, 2), (1, 3), (10, 11), (5, 5)])
    assert total == 4
    assert merged == [(0, 3), (10, 11)]


def test_busy_is_the_union_of_op_events_per_chip(profile):
    red = trace_reduce.reduce(profile, window_s=20e-6)
    # chip 0: 3 us + 1 us; chip 1: 2 us; the mean over chips
    assert red["chips_busy"] == 2
    assert red["busy_s"] == pytest.approx((4e-6 + 2e-6) / 2)
    assert red["window_s"] == 20e-6
    assert red["events"] == 4


def test_idle_share_and_gaps(profile):
    red = trace_reduce.reduce(profile, window_s=20e-6)
    gaps = dict(red["idle_gaps"])
    assert gaps["gaps_under_100us"] == pytest.approx(7e-6)
    assert gaps["before_first_and_after_last_op"] == pytest.approx(
        20e-6 - 3e-6 - 7e-6)
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "idle", os.path.join(BENCH_DIR, "readers",
                             "device_idle_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read({"trace": red}) == pytest.approx(85.0)
    assert mod.read({"trace": None}) is None
    assert mod.read({"trace": {"busy_s": 0.0, "window_s": 1.0}}) is None


def test_top_ops_by_short_name(profile):
    red = trace_reduce.reduce(profile, window_s=20e-6)
    ops = dict(red["device_ops"])
    assert ops["%run.1 custom-call tpu_custom_call"] == \
        pytest.approx(2e-6 + 1e-6)        # its two events on chip 0
    assert ops["%copy.2 copy"] == pytest.approx(2e-6)
    assert ops["%x add"] == pytest.approx(2e-6)
    assert not any("jit_fused" in name for name in ops)


def test_envelope_lines_never_count_as_busy(profile):
    planes = trace_reduce.device_planes(profile)
    assert [p.name for p in planes] == ["/device:TPU:0",
                                       "/device:TPU:1"]
    lines = trace_reduce._op_lines(planes[0])
    assert [ln.name for ln in lines] == ["XLA Ops"]


def test_no_device_plane_reads_nothing():
    from jax.profiler import ProfileData
    host_only = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }')
    red = trace_reduce.reduce(host_only, window_s=1.0)
    assert red["busy_s"] == 0.0 and red["device_ops"] == []
