"""From the host plane of a profiler trace to thread states.

The program marks what each of its hot threads is doing through one
seam (``ceph_tpu/utils/profiler.py`` ``push_stage``/``pop_stage``).
Every mark is a ``jax.profiler.TraceAnnotation``, so a traced window's
``.xplane.pb`` holds, in plane ``/host:CPU``, one line per thread with
the thread's states as events on the profiler's clock, the same clock
the device's ``XLA Ops`` are on. Every thread's line is named alike,
so a thread's role rides on each annotation as the argument ``role``
(``engine_launch``, ``engine_retire``, ``osd_wq``, ``msgr``,
``client``); events without that argument are the runtime's own and
are left out. A flush phase carries ``ops`` and ``bytes`` of its batch.

What is computed, all inside the traced window:

- per thread, the INNERMOST open annotation at each instant
  (annotations nest: ``flush_launch`` inside ``flush_build``); a thread
  with none open is ``unmarked``. A state that was open when the trace
  started is not in the trace, so a thread is ``unmarked`` until its
  next mark;
- per annotation name, the time it was the innermost one (so the
  phases of a flush add up, none counted twice) and how many began;
- the engine is PARKED while every ``engine_launch`` thread is in
  ``idle`` and every ``engine_retire`` thread in ``retire_idle``;
  device-idle time is split into parked and not parked;
- the clock check: the share of device-busy time that lies between
  the start of a ``flush_launch`` and the end of its ``flush_download``
  (paired first in, first out), or inside a ``decode_run``, counted
  where the engine's states are known: from the later of its threads'
  first marks to the earlier of their last ones (a phase that was open
  when the trace started or stopped is not in the trace, its program
  is). It is printed on standard error once per trace, with the
  largest pieces of busy time outside; far under 100 it says the two
  clocks do not agree and nothing here can be trusted.

A trace without a host plane, or without one marked thread, reads
None everywhere (never 0): the program then lacks the marks.
"""

from __future__ import annotations

import glob
import os
import sys

import trace_reduce

HOST_PLANE = "/host:CPU"
UNMARKED = "unmarked"
#: where ``run.py`` leaves the trace of a cell, below a checkout's root
TRACE_GLOB = os.path.join(".bench_out", "trace_*", "plugins", "profile",
                          "*", "*.xplane.pb")
LAUNCH_ROLE, RETIRE_ROLE = "engine_launch", "engine_retire"
#: (start of, end of) the host spans a device program runs between
LAUNCH, DOWNLOAD, DECODE = "flush_launch", "flush_download", "decode_run"


def is_idle(state: str) -> bool:
    """Whether a state is a thread waiting for work."""
    return state == UNMARKED or state == "idle" or \
        state.endswith("_idle")


def innermost(events: list[tuple[float, float, str]]
              ) -> list[tuple[float, float, str]]:
    """Flatten one thread's (start, end, name) annotations, which may
    nest, into consecutive (start, end, name) segments naming the
    innermost open one. Gaps between segments are ``unmarked``."""
    segments: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []          # (end, name)
    cursor = 0.0

    def advance(upto: float) -> None:
        """Emit segments until ``upto``, closing what ends before."""
        nonlocal cursor
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > cursor:
                segments.append((cursor, end, name))
                cursor = end
        if stack and upto > cursor:
            segments.append((cursor, upto, stack[-1][1]))
            cursor = upto

    for start, end, name in sorted(events,
                                   key=lambda ev: (ev[0], -ev[1])):
        if end <= start:
            continue
        advance(start)
        cursor = max(cursor, start)
        if stack:                  # a child never outlives its parent
            end = min(end, stack[-1][0])
        stack.append((end, name))
    advance(float("inf"))
    return segments


def intersect(a: list[tuple[float, float]], b: list[tuple[float, float]]
              ) -> list[tuple[float, float]]:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


class HostTrace:
    """The host states of one traced window; see the module's text."""

    def __init__(self, profile) -> None:
        #: [(role, [(start, end, state)])], one per marked thread
        self.threads: list[tuple[str, list]] = []
        #: name -> [ns it was the innermost annotation, how many began]
        self.by_name: dict[str, list] = {}
        #: the events the clock check pairs: name -> [(start, end,
        #: stats)] for flush_launch, flush_download and decode_run
        self.events: dict[str, list] = {}
        for plane in profile.planes:
            if plane.name != HOST_PLANE:
                continue
            for line in plane.lines:
                marked = []
                for ev in line.events:
                    stats = dict(ev.stats)
                    if "role" not in stats:
                        continue
                    start = float(ev.start_ns)
                    marked.append((start, start + float(ev.duration_ns),
                                   ev.name, stats))
                if not marked:
                    continue
                marked.sort(key=lambda ev: ev[0])
                segments = innermost([ev[:3] for ev in marked])
                # a thread's role is that of most of its marks (a
                # worker may now and then call into client code)
                roles = [str(ev[3]["role"]) for ev in marked]
                self.threads.append((max(set(roles), key=roles.count),
                                     segments))
                for start, end, name, stats in marked:
                    self.by_name.setdefault(name, [0.0, 0])[1] += 1
                    if name in (LAUNCH, DOWNLOAD, DECODE):
                        self.events.setdefault(name, []).append(
                            (start, end, stats))
                for start, end, name in segments:
                    self.by_name.setdefault(name, [0.0, 0])[0] += \
                        end - start
        #: per chip that ran anything, its merged busy intervals
        self.busy: list[list[tuple[float, float]]] = []
        for plane in trace_reduce.device_planes(profile):
            intervals = [
                (float(ev.start_ns),
                 float(ev.start_ns) + float(ev.duration_ns))
                for line in plane.lines
                if line.name in trace_reduce.OP_LINES
                for ev in line.events]
            if intervals:
                self.busy.append(trace_reduce.union_ns(intervals)[1])

    # -- what the readers ask ------------------------------------------
    def span_ns(self, names: list[str]) -> float | None:
        """Time the named annotations were innermost; None when the
        trace holds none of them."""
        seen = [self.by_name[n][0] for n in names if n in self.by_name]
        return sum(seen) if seen else None

    def state_intervals(self, role: str, wanted) -> list | None:
        """Merged intervals in which EVERY thread of ``role`` is in a
        state ``wanted(state)`` accepts; None without such a thread."""
        out = None
        for thread_role, segments in self.threads:
            if thread_role != role:
                continue
            mine = trace_reduce.union_ns(
                [(s, e) for s, e, state in segments if wanted(state)])[1]
            out = mine if out is None else intersect(out, mine)
        return out

    def parked(self) -> list | None:
        """Intervals in which the engine waits for work: launch
        threads in ``idle`` and retire threads in ``retire_idle``."""
        launch = self.state_intervals(LAUNCH_ROLE,
                                      lambda s: s == "idle")
        retire = self.state_intervals(RETIRE_ROLE,
                                      lambda s: s == "retire_idle")
        if launch is None:
            return None
        if retire is None:
            # a retire thread that made no mark in the window stayed
            # in one state, and with nothing launched (a window of
            # decode flushes, which run on the launch thread) that
            # state is its wait
            return None if LAUNCH in self.events else launch
        return intersect(launch, retire)

    def parked_idle_ns(self) -> float | None:
        """Time the engine was parked and no operation ran on the
        device (the mean over the chips that ran anything)."""
        parked = self.parked()
        if parked is None or not self.busy:
            return None
        total = _length(parked)
        return sum(total - _length(intersect(parked, busy))
                   for busy in self.busy) / len(self.busy)

    def active_thread_ns(self, role: str) -> float | None:
        """Thread-time of ``role`` in any state but an idle one."""
        seen, total = False, 0.0
        for thread_role, segments in self.threads:
            if thread_role == role:
                seen = True
                total += sum(e - s for s, e, state in segments
                             if not is_idle(state))
        return total if seen else None

    # -- the clock check -----------------------------------------------
    def program_spans(self) -> list[tuple[float, float]]:
        """Host intervals a device program can run in: from a
        ``flush_launch``'s start to the end of its ``flush_download``
        (first in, first out; a download that matches no open
        launch's ``ops`` and ``bytes`` was launched before the
        trace), and every ``decode_run``."""
        spans = [(s, e) for s, e, _st in self.events.get(DECODE, [])]
        marks = [(s, 0, e, st) for s, e, st in
                 self.events.get(LAUNCH, [])] + \
                [(s, 1, e, st) for s, e, st in
                 self.events.get(DOWNLOAD, [])]
        open_launches: list = []
        for start, is_download, end, stats in sorted(
                marks, key=lambda m: (m[0], m[1])):
            batch = (stats.get("ops"), stats.get("bytes"))
            if not is_download:
                open_launches.append((start, batch))
                continue
            # launches before the matching one had no download (a
            # route that computes on the host)
            for i, (launched, launched_batch) in \
                    enumerate(open_launches):
                if launched_batch == batch:
                    spans.append((launched, end))
                    del open_launches[:i + 1]
                    break
        return trace_reduce.union_ns(spans)[1]

    def known(self) -> list[tuple[float, float]]:
        """Where every marked engine thread's state is known: from
        the latest of their first marks to the earliest of their last
        ones (empty without an engine thread)."""
        engine = [segments for role, segments in self.threads
                  if role in (LAUNCH_ROLE, RETIRE_ROLE)]
        if not engine:
            return []
        lo = max(segments[0][0] for segments in engine)
        hi = min(segments[-1][1] for segments in engine)
        return [(lo, hi)] if hi > lo else []

    def busy_outside_spans(self) -> list[tuple[float, float]]:
        """Device-busy pieces, of any chip, where the engine's states
        are known but no program span is open."""
        known, spans = self.known(), self.program_spans()
        outside = []
        for busy in self.busy:
            for lo, hi in intersect(busy, known):
                at = lo
                for s, e in intersect([(lo, hi)], spans):
                    if s > at:
                        outside.append((at, s))
                    at = e
                if hi > at:
                    outside.append((at, hi))
        return outside

    def busy_inside_spans_pct(self) -> float | None:
        known = self.known()
        busy_ns = sum(_length(intersect(b, known)) for b in self.busy)
        if busy_ns <= 0:
            return None
        return 100.0 * (1.0 - _length(self.busy_outside_spans())
                        / busy_ns)

    def describe(self) -> dict:
        """The line for standard error: the clock check, the marked
        threads by role, and per annotation name the ms it was
        innermost and how many began."""
        roles: dict[str, int] = {}
        for role, _segments in self.threads:
            roles[role] = roles.get(role, 0) + 1
        inside = self.busy_inside_spans_pct()
        known = self.known()
        outside = sorted(self.busy_outside_spans(),
                         key=lambda p: p[0] - p[1])[:3]
        return {"busy_inside_host_spans_pct":
                None if inside is None else round(inside, 3),
                # [ms after the known interval's start, us long]
                "largest_busy_outside": [
                    [round((s - known[0][0]) / 1e6, 3),
                     round((e - s) / 1e3, 1)] for s, e in outside],
                "threads_by_role": roles,
                "ms_and_count_by_name": {
                    name: [round(ns / 1e6, 3), count]
                    for name, (ns, count) in sorted(
                        self.by_name.items())}}


def find_xplane(root: str) -> str | None:
    """The newest ``.xplane.pb`` below ``<root>/.bench_out/trace_*``."""
    found = glob.glob(os.path.join(root, TRACE_GLOB))
    return max(found, key=os.path.getmtime) if found else None


#: (path, mtime) of the trace last parsed, and what it reduced to.
#: ``spec.reader`` loads each reader file afresh, ``sys.path`` makes
#: this module one for all of them: a trace is parsed once a process
_cache: tuple = (None, None)


def of_root(root: str) -> HostTrace | None:
    """The host states of the newest trace below ``root``; None when
    there is no trace or it has no marked thread."""
    global _cache
    path = find_xplane(root)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if _cache[0] != key:
        import json
        reduced = HostTrace(trace_reduce.load(path))
        print("host_trace: " + json.dumps(reduced.describe()),
              file=sys.stderr)
        _cache = (key, reduced)
    reduced = _cache[1]
    return reduced if reduced.threads else None


def of_reader(reader_file: str) -> HostTrace | None:
    """For a reader at ``<root>/benchmarks/readers/<name>.py``."""
    return of_root(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file)))))
