"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler`` writes one ``<dir>/plugins/profile/<time>/*.xplane.pb``
per traced window. Its planes named ``/device:TPU:<n>`` are the chips;
each has lines of events with a start and a duration. On a TPU the line
``XLA Ops`` holds one event per operation the chip ran (the lines
``Steps`` and ``XLA Modules`` are envelopes around them and would count
the gaps inside a program as busy), so:

- busy time of a chip = the length of the UNION of its operation
  events' intervals (events that overlap count once);
- ``busy_s`` = the mean of that over the chips that ran anything;
- idle share = 1 - busy_s / window_s, the window being the traced one
  as the host timed it;
- the device operations that took most time, by the names the trace
  gives them (the program gives its kernels no stable names yet);
- the idle gaps, sorted into classes of length. Saying what the host
  was doing in a gap needs annotations inside the program: see the
  ``tracing`` list in PERF.md.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
#: lines whose events are single operations on the chip
OP_LINES = ("XLA Ops",)
#: idle-gap classes: (name, gaps shorter than this many seconds)
GAP_CLASSES = (("gaps_under_100us", 1e-4), ("gaps_100us_to_1ms", 1e-3),
               ("gaps_1ms_to_10ms", 1e-2), ("gaps_10ms_to_100ms", 1e-1),
               ("gaps_100ms_to_1s", 1.0), ("gaps_over_1s", float("inf")))


_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def short_name(name: str) -> str:
    """An operation's name as the trace gives it is its whole HLO
    line; keep ``<result> <opcode>[ <custom_call_target>]``."""
    lhs, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    opcode = _OPCODE.search(" " + rest)
    out = lhs + (" " + opcode.group(1) if opcode else "")
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    if target:
        out += " " + target.group(1)
    return out[:80]


def find_xplane(logdir: str) -> str:
    """The newest ``.xplane.pb`` below ``logdir``."""
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb below {logdir}")
    return max(found, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union_ns(intervals: list[tuple[float, float]]
             ) -> tuple[float, list[tuple[float, float]]]:
    """(total length, merged intervals) of a set of [start, end)."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return (sum(e - s for s, e in merged),
            [(s, e) for s, e in merged])


def _op_lines(plane) -> list:
    return [ln for ln in plane.lines if ln.name in OP_LINES]


def device_planes(profile) -> list:
    return [p for p in profile.planes
            if p.name.startswith(DEVICE_PLANE_PREFIX)]


def reduce(profile, window_s: float, top: int = 10) -> dict:
    """The numbers of one traced window; see the module's text.
    ``window_s`` is the traced window's length by the host's clock."""
    busy_per_chip: list[float] = []
    by_name: dict[str, float] = {}
    gap_s = {name: 0.0 for name, _ in GAP_CLASSES}
    n_events = 0
    for plane in device_planes(profile):
        intervals = []
        for line in _op_lines(plane):
            for ev in line.events:
                start, dur = float(ev.start_ns), float(ev.duration_ns)
                intervals.append((start, start + dur))
                name = short_name(ev.name)
                by_name[name] = by_name.get(name, 0.0) + dur
                n_events += 1
        if not intervals:
            continue
        busy_ns, merged = union_ns(intervals)
        busy_per_chip.append(busy_ns / 1e9)
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            gap = (s1 - e0) / 1e9
            for name, below in GAP_CLASSES:
                if gap < below:
                    gap_s[name] += gap
                    break
    busy_s = (sum(busy_per_chip) / len(busy_per_chip)
              if busy_per_chip else 0.0)
    # the window's head and tail (before the first and after the last
    # operation) are idle too; they carry no class of their own
    inner = sum(gap_s.values())
    edge = max(0.0, window_s - busy_s - inner) if busy_per_chip else 0.0
    gaps = [[name, sec] for name, sec in gap_s.items() if sec > 0]
    if edge > 0:
        gaps.append(["before_first_and_after_last_op", edge])
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s, "window_s": float(window_s),
            "chips_busy": len(busy_per_chip), "events": n_events,
            "device_ops": [[name, ns / 1e9] for name, ns in ops],
            "idle_gaps": gaps[:top]}


def describe(profile, events_per_line: int = 3) -> list[str]:
    """Plane, line and a few event names: what to look at by hand
    before trusting :func:`reduce` on a new runtime."""
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name!r}")
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            events = list(line.events)
            names = [e.name for e in events[:events_per_line]]
            out.append(f"  line {line.name!r}: {len(events)} events "
                       f"{names}")
    return out
