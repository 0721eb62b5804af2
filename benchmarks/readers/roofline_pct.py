"""Share of the HBM roofline: the least time the chip could take for
the work the engine flushed inside the TRACED window (bytes from
``work.py``, counted from shapes, over the peak of the device's kind)
divided by the time an operation ran on the device in that window.
Nothing flushed or nothing traced: no reading (never 0)."""


def read(ctx: dict, work: str, ops_counter: str) -> float | None:
    from work import WORK
    trace = ctx.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    ops = ctx["engine_traced"].get(ops_counter, 0)
    if ops <= 0:
        return None
    nbytes = WORK[work](ops, ctx["traffic"]["object_bytes"],
                        ctx["config"]["pool"], ctx.get("reference"))
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
