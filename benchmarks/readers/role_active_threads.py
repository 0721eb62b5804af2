"""Mean number of threads of one role that are in any state other than
an idle one (``idle``, ``*_idle``, no mark open) over the traced
window: thread-time in such states, from ``benchmarks/host_trace.py``,
over the window's length. No thread of the role in the trace: no
reading (never 0)."""


def read(ctx: dict, role: str) -> float | None:
    import host_trace
    device = ctx.get("trace")
    if not device or device["window_s"] <= 0:
        return None
    trace = host_trace.of_reader(__file__)
    if trace is None:
        return None
    active_ns = trace.active_thread_ns(role)
    if active_ns is None:
        return None
    return active_ns / 1e9 / device["window_s"]
