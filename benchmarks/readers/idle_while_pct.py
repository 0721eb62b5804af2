"""Share of the traced window in which no operation ran on the device
AND the engine was parked (``parked`` true: every launch thread in
``idle`` and every retire thread in ``retire_idle``: the chip waits for
client, messenger, OSD and commit path) or was not (``parked`` false:
every other instant of device-idle time: an engine thread works, or is
in no marked state). The two partition ``device_idle_pct`` of the same
run exactly. Without a device plane or without the engine's marks: no
reading (never 0)."""


def read(ctx: dict, parked: bool) -> float | None:
    import host_trace
    device = ctx.get("trace")
    if not device or device["window_s"] <= 0 or device["busy_s"] <= 0:
        return None
    trace = host_trace.of_reader(__file__)
    if trace is None:
        return None
    parked_ns = trace.parked_idle_ns()
    if parked_ns is None:
        return None
    window_s = device["window_s"]
    parked_pct = 100.0 * parked_ns / 1e9 / window_s
    if parked:
        return parked_pct
    return 100.0 * (1.0 - device["busy_s"] / window_s) - parked_pct
