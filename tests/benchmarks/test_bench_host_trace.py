"""The reduction of a trace's host plane to thread states, and its three
readers, pinned on a trace made by hand (times in microseconds after
the lines' start):

- the launch thread: ``idle`` [0,8), ``flush_build`` [8,14) with
  ``flush_window_wait`` [9,9.5) and ``flush_launch`` [9.5,11) nested in
  it, ``idle`` [14,28), ``flush_build`` [28,31) with ``flush_launch``
  [29,30.5), ``idle`` [31,40);
- the retire thread: ``retire_idle`` [0,11), ``flush_dispatch`` [11,16)
  with ``flush_download`` [11,13), ``retire_idle`` [16,30.5),
  ``flush_dispatch`` [30.5,36) with ``flush_download`` [30.5,34),
  ``retire_idle`` [36,40);
- an op-wq worker: ``idle`` [0,5), ``pg_process`` [5,9), ``idle``
  [9,20), ``commit_wait`` [20,26) with a client's ``client_wait``
  [22,24) nested in it; a second worker in ``pg_process`` [0,40);
- a line of the runtime's own events, which carry no role;
- the chip: operations [10,12), [30,33) and [37,38).

Every host line is named ``python3``: only ``role`` tells them apart.
"""

import importlib.util
import os

import pytest

from bench_tiny import BENCH_DIR  # noqa: F401  (sets sys.path)

import host_trace

US = 1_000_000          # picoseconds


def _event(name_id: int, start_us: float, end_us: float, role_id: int,
           ops: int = 0, nbytes: int = 0) -> str:
    stats = f"stats {{ metadata_id: 1 ref_value: {role_id} }}"
    if ops:
        stats += (f" stats {{ metadata_id: 2 int64_value: {ops} }}"
                  f" stats {{ metadata_id: 3 int64_value: {nbytes} }}")
    return (f"events {{ metadata_id: {name_id} "
            f"offset_ps: {int(start_us * US)} "
            f"duration_ps: {int((end_us - start_us) * US)} {stats} }}")


#: event_metadata ids of the host plane
NAMES = {1: "idle", 2: "flush_build", 3: "flush_window_wait",
         4: "flush_launch", 5: "retire_idle", 6: "flush_dispatch",
         7: "flush_download", 8: "pg_process", 9: "commit_wait",
         10: "client_wait", 11: "PjitFunction(fused)",
         12: "decode_run"}
#: stat_metadata ids 11.. double as the roles' ref values
ROLES = {11: "engine_launch", 12: "engine_retire", 13: "osd_wq",
         14: "client"}
LAUNCH, RETIRE, WQ, CLIENT = 11, 12, 13, 14


def _host_plane(lines: list) -> str:
    body = "".join(
        f'lines {{ id: {i} name: "python3" timestamp_ns: 1000 '
        f'{" ".join(events)} }}\n'
        for i, events in enumerate(lines, 1))
    meta = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in NAMES.items())
    stat = "".join(
        f'stat_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in {1: "role", 2: "ops", 3: "bytes", **ROLES}.items())
    return f'planes {{ id: 3 name: "/host:CPU"\n{body}{meta}{stat} }}\n'


DEVICE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 40000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 30000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 37000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%gf_matvec.1 = u8[3,64]{1,0} custom-call(u8[8,64]{1,0} %a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "jit_fused(1)" } } }
"""

HOST = _host_plane([
    [_event(1, 0, 8, LAUNCH),
     _event(2, 8, 14, LAUNCH, 2, 8192),
     _event(3, 9, 9.5, LAUNCH, 2, 8192),
     _event(4, 9.5, 11, LAUNCH, 2, 8192),
     _event(1, 14, 28, LAUNCH),
     _event(2, 28, 31, LAUNCH, 1, 4096),
     _event(4, 29, 30.5, LAUNCH, 1, 4096),
     _event(1, 31, 40, LAUNCH)],
    [_event(5, 0, 11, RETIRE),
     _event(6, 11, 16, RETIRE, 2, 8192),
     _event(7, 11, 13, RETIRE, 2, 8192),
     _event(5, 16, 30.5, RETIRE),
     _event(6, 30.5, 36, RETIRE, 1, 4096),
     _event(7, 30.5, 34, RETIRE, 1, 4096),
     _event(5, 36, 40, RETIRE)],
    [_event(1, 0, 5, WQ), _event(8, 5, 9, WQ), _event(1, 9, 20, WQ),
     _event(9, 20, 26, WQ), _event(10, 22, 24, CLIENT)],
    [_event(8, 0, 40, WQ)],
    ["events { metadata_id: 11 offset_ps: 0 duration_ps: 40000000 }"],
])

WINDOW_S = 40e-6
BUSY_S = 6e-6


def _profile(text: str):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def reduced():
    return host_trace.HostTrace(_profile(DEVICE + HOST))


def _write_trace(root, text: str, cell: str = "trace_some.cell"):
    from jax.profiler import ProfileData
    logdir = root / ".bench_out" / cell / "plugins" / "profile" / "t1"
    logdir.mkdir(parents=True, exist_ok=True)
    (logdir / "hand.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))


@pytest.fixture
def reader(tmp_path):
    """``reader(name, text)``: the ``read`` of a copy of
    ``readers/<name>.py`` below a root of its own whose newest trace
    is ``text`` (none when ``text`` is None), as the tiny-root tests
    place them."""
    def load(name: str, text: str | None):
        copy = tmp_path / "benchmarks" / "readers" / (name + ".py")
        copy.parent.mkdir(parents=True, exist_ok=True)
        with open(os.path.join(BENCH_DIR, "readers", name + ".py")) as f:
            copy.write_text(f.read())
        if text is not None:
            _write_trace(tmp_path, text)
        spec = importlib.util.spec_from_file_location(name, str(copy))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
    return load


CTX = {"trace": {"window_s": WINDOW_S, "busy_s": BUSY_S},
       "engine_traced": {"flushes": 2, "decode_flushes": 0}}


# -- the reduction -------------------------------------------------------

def test_innermost_resolves_nesting_and_leaves_gaps_unmarked():
    assert host_trace.innermost(
        [(0, 10, "a"), (2, 6, "b"), (3, 4, "c"), (12, 15, "d")]) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "b"),
        (6, 10, "a"), (12, 15, "d")]
    # an empty annotation is nothing; a child ends with its parent
    assert host_trace.innermost([(5, 5, "x"), (0, 4, "a"),
                                 (3, 9, "b")]) == [(0, 3, "a"),
                                                   (3, 4, "b")]


def test_threads_are_told_apart_by_role_not_by_line_name(reduced):
    roles = sorted(role for role, _segments in reduced.threads)
    # the worker that calls into client code stays a worker; the
    # runtime's own line has no role and is no thread of ours
    assert roles == ["engine_launch", "engine_retire", "osd_wq",
                     "osd_wq"]
    assert reduced.describe()["threads_by_role"] == {
        "engine_launch": 1, "engine_retire": 1, "osd_wq": 2}


@pytest.mark.parametrize("name,innermost_us,began", [
    ("flush_build", 1 + 3 + 1 + 0.5, 2),
    ("flush_window_wait", 0.5, 1),
    ("flush_launch", 1.5 + 1.5, 2),
    ("flush_download", 2 + 3.5, 2),
    ("flush_dispatch", 3 + 2, 2),
    ("retire_idle", 11 + 14.5 + 4, 3),
    ("commit_wait", 2 + 2, 1),
    ("client_wait", 2, 1)])
def test_time_by_name_is_innermost_time(reduced, name, innermost_us,
                                        began):
    ns, count = reduced.by_name[name]
    assert ns == pytest.approx(innermost_us * 1e3)
    assert count == began
    assert reduced.span_ns([name]) == pytest.approx(innermost_us * 1e3)


def test_parked_is_both_engine_threads_waiting(reduced):
    assert reduced.parked() == [(1000 + 0, 1000 + 8000),
                                (1000 + 16000, 1000 + 28000),
                                (1000 + 36000, 1000 + 40000)]
    # the operation at [37,38) ran while the engine was parked
    assert reduced.parked_idle_ns() == pytest.approx(23e3)


def test_clock_check_counts_busy_time_inside_launch_to_download(
        reduced):
    assert reduced.program_spans() == [(1000 + 9500, 1000 + 13000),
                                       (1000 + 29000, 1000 + 34000)]
    # [10,12) and [30,33) lie inside, [37,38) outside: 5 of 6 us
    assert reduced.known() == [(1000, 1000 + 40000)]
    assert reduced.busy_outside_spans() == [(1000 + 37000,
                                             1000 + 38000)]
    assert reduced.busy_inside_spans_pct() == pytest.approx(500 / 6)
    line = reduced.describe()
    assert line["busy_inside_host_spans_pct"] == pytest.approx(83.333)
    assert line["largest_busy_outside"] == [[0.037, 1.0]]


def test_clock_check_counts_only_where_the_engines_states_are_known():
    """A phase that was open when the trace started is not in the
    trace, its program is: the operation at [10,12) ran before the
    launch thread's first mark and is left out of the check."""
    late = _host_plane([
        [_event(1, 14, 28, LAUNCH),
         _event(12, 28, 34, LAUNCH, 1, 4096),
         _event(1, 34, 36, LAUNCH)]])
    trace = host_trace.HostTrace(_profile(DEVICE + late))
    assert trace.known() == [(1000 + 14000, 1000 + 36000)]
    assert trace.busy_inside_spans_pct() == pytest.approx(100.0)
    # the retire thread made no mark and nothing was launched: it
    # waited, and the engine is parked while the launch thread idles
    assert trace.parked() == [(1000 + 14000, 1000 + 28000),
                              (1000 + 34000, 1000 + 36000)]
    # [37,38) ran while the launch thread's state was not known
    assert trace.parked_idle_ns() == pytest.approx(16e3)


def test_a_launch_without_a_retire_thread_reads_no_parked_share():
    stuck = _host_plane([
        [_event(1, 0, 8, LAUNCH), _event(4, 9.5, 11, LAUNCH, 1, 4096)]])
    trace = host_trace.HostTrace(_profile(DEVICE + stuck))
    assert trace.parked() is None and trace.parked_idle_ns() is None


def test_a_download_launched_before_the_trace_pairs_with_nothing():
    orphan = _host_plane([
        [_event(4, 3, 4, LAUNCH, 1, 4096)],
        [_event(7, 1, 2, RETIRE, 3, 12288),
         _event(7, 5, 6, RETIRE, 1, 4096)]])
    spans = host_trace.HostTrace(_profile(orphan)).program_spans()
    assert spans == [(1000 + 3000, 1000 + 6000)]


# -- the readers ---------------------------------------------------------

@pytest.mark.parametrize("spans,ms", [
    (["flush_build"], 5.5e-3 / 2), (["flush_launch"], 3e-3 / 2),
    (["flush_download"], 5.5e-3 / 2), (["flush_dispatch"], 5e-3 / 2),
    (["flush_build", "flush_launch"], 8.5e-3 / 2)])
def test_host_span_ms(reader, spans, ms):
    read = reader("host_span_ms", DEVICE + HOST)
    assert read(CTX, spans=spans, per_counter="flushes") == \
        pytest.approx(ms)
    # a name the trace lacks, or a counter that did not grow
    assert read(CTX, spans=["decode_run"],
                per_counter="flushes") is None
    assert read(CTX, spans=spans, per_counter="decode_flushes") is None


def test_parked_and_not_parked_partition_the_idle_share(reader):
    read = reader("idle_while_pct", DEVICE + HOST)
    parked = read(CTX, parked=True)
    working = read(CTX, parked=False)
    assert parked == pytest.approx(100 * 23 / 40)
    assert working == pytest.approx(100 * (34 - 23) / 40)
    idle = importlib.util.spec_from_file_location(
        "idle", os.path.join(BENCH_DIR, "readers",
                             "device_idle_pct.py"))
    mod = importlib.util.module_from_spec(idle)
    idle.loader.exec_module(mod)
    assert parked + working == pytest.approx(mod.read(CTX))


def test_role_active_threads(reader):
    read = reader("role_active_threads", DEVICE + HOST)
    # worker one: pg_process 4 + commit_wait 4 + client_wait 2 us;
    # worker two: 40 us; over a window of 40 us
    assert read(CTX, role="osd_wq") == pytest.approx(50 / 40)
    assert read(CTX, role="engine_retire") == pytest.approx(
        (5.5 + 5) / 40)
    assert read(CTX, role="msgr") is None


@pytest.mark.parametrize("text", [
    None,                                   # no trace at all
    DEVICE,                                 # no host plane
    DEVICE + 'planes { id: 3 name: "/host:CPU" }',
    # a program without the marks: only the runtime's own events
    DEVICE + _host_plane(
        [["events { metadata_id: 11 offset_ps: 0 "
          "duration_ps: 40000000 }"]])],
    ids=["no_trace", "no_host_plane", "empty_host_plane",
         "runtime_events_only"])
@pytest.mark.parametrize("name,args", [
    ("host_span_ms", {"spans": ["flush_build"],
                      "per_counter": "flushes"}),
    ("idle_while_pct", {"parked": True}),
    ("idle_while_pct", {"parked": False}),
    ("role_active_threads", {"role": "osd_wq"})])
def test_without_marks_every_reader_reads_none_never_zero(
        reader, text, name, args):
    assert reader(name, text)(CTX, **args) is None


def test_idle_shares_need_a_device_plane(reader):
    """The CPU runs of test_bench_run.py: host marks, no chip."""
    read = reader("idle_while_pct", HOST)
    no_chip = dict(CTX, trace={"window_s": WINDOW_S, "busy_s": 0.0})
    assert read(no_chip, parked=True) is None
    assert read(no_chip, parked=False) is None
    # the span metrics may read there, as the stage clocks do
    assert reader("host_span_ms", HOST)(
        no_chip, spans=["flush_launch"], per_counter="flushes") == \
        pytest.approx(3e-3 / 2)


def test_the_newest_trace_below_the_root_is_read_once(tmp_path,
                                                      capfd):
    _write_trace(tmp_path, DEVICE, cell="trace_old.cell")
    old = tmp_path / ".bench_out" / "trace_old.cell"
    os.utime(old / "plugins" / "profile" / "t1" / "hand.xplane.pb",
             (1, 1))
    _write_trace(tmp_path, DEVICE + HOST, cell="trace_new.cell")
    first = host_trace.of_root(str(tmp_path))
    assert first is not None and len(first.threads) == 4
    assert host_trace.of_root(str(tmp_path)) is first
    err = capfd.readouterr().err
    assert err.count("host_trace: ") == 1
    assert '"busy_inside_host_spans_pct": 83.333' in err
    assert host_trace.of_root(str(tmp_path / "nowhere")) is None
