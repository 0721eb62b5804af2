"""Mean milliseconds an op of the window spent in a list of stages of
the program's always-on stage clocks (host clock; consecutive
intervals, exact sums). ``ctx["stages"]`` maps a stage to the growth of
its (sum of seconds, count) over the window."""


def read(ctx: dict, stages: list) -> float | None:
    total, seen = 0.0, False
    for stage in stages:
        ent = ctx["stages"].get(stage)
        if not ent or not ent["count"]:
            continue
        seen = True
        total += ent["sum_s"] / ent["count"]
    return total * 1e3 if seen else None
