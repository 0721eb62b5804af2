"""Mean milliseconds per flush that the engine's threads spent in the
named annotations (``benchmarks/host_trace.py``: the time each was the
innermost open one on its thread, on the profiler's clock) inside the
traced window, over the growth of an engine counter (``flushes``,
``decode_flushes``) in that window. No such annotation in the trace, or
nothing flushed: no reading (never 0)."""


def read(ctx: dict, spans: list, per_counter: str) -> float | None:
    import host_trace
    flushed = ctx["engine_traced"].get(per_counter, 0)
    trace = host_trace.of_reader(__file__)
    if trace is None or flushed <= 0:
        return None
    total_ns = trace.span_ns(spans)
    if total_ns is None:
        return None
    return total_ns / 1e6 / flushed
