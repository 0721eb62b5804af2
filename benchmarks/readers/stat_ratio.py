"""The ratio of two engine counters' growth over the window, e.g.
ops per flush."""


def read(ctx: dict, num: str, den: str) -> float | None:
    stats = ctx["engine_window"]
    if not stats.get(den):
        return None
    return stats.get(num, 0) / stats[den]
