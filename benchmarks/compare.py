"""What decides ``correct``: the timed path's own output against the
plain reference, every number beside its limit.

The comparisons are exact, so every limit is 0 (or, for "the decode
path was used at all", a least count of 1). What is compared:

- the window kind's own rows (``windows/<op>.py``, ``judge_ops``). A
  closed loop: ``ops_failed``, ops of the window that raised or timed
  out; ``reads_unequal``, reads of the window whose bytes differ from
  the seeded bytes (the degraded cell: every read of the window). A
  recovery: the shards rebuilt, those that are not there, and how many
  of the rebuilt ones the sample compared;
- ``readback_unequal``: sampled objects (those the window wrote, or
  the preloaded ones), read back through the client after the window,
  that differ from the seeded bytes;
- ``shards_unequal`` / ``crcs_unequal``: shards of the sampled objects
  as the OSD stores hold them, and the crc each shard's ``hinfo``
  holds, that differ from ``shards(data, pool)`` of the
  configuration's own reference module / ``reference.crc32c``, which
  no codec changes; ``shards_missing``: shards of a sampled object that
  no live OSD holds, beyond those the window kind excuses;
- the route: growth over the window of the engine's ``host_flushes``,
  ``device_fused_fallbacks``, ``errors``, ``decode_errors``, programs
  compiled inside the window, primaries without a device engine, and
  what the kind adds (``judge_route``): at least one encode or decode
  flush, no reconstruct that fell back to the host. A run that left
  the device path measured something else.
"""

from __future__ import annotations

import sys

import reference


def sample_names(names: list[str], count: int, seed_words: list[int]
                 ) -> list[str]:
    """``count`` of ``names`` drawn from the seed."""
    import numpy as np
    names = sorted(set(names))
    if len(names) <= count:
        return names
    rng = np.random.default_rng(seed_words + [4])
    picked = {names[i] for i in rng.choice(len(names), size=count,
                                           replace=False)}
    return sorted(picked)


def compare_objects(observed: list[dict], payload_of, pool: dict,
                    absent_ok=None, ref=reference) -> dict:
    """Counts of what differs between ``observed`` (see
    ``Served.observe``) and the stored shards of each object's seeded
    bytes as ``ref``, the configuration's reference module, states
    them for the configuration's whole ``pool``. ``absent_ok(obs)``:
    how many of the object's shards the window kind excuses."""
    out = {"readback_unequal": 0, "shards_unequal": 0,
           "crcs_unequal": 0, "shards_missing": 0}
    examples = []
    for obs in observed:
        data = payload_of(obs["name"])
        if obs["read_back"] is not None and obs["read_back"] != data:
            out["readback_unequal"] += 1
            examples.append(f"{obs['name']}: read-back differs")
        want = ref.shards(data, pool)
        absent = len(want) - len(obs["shards"])
        out["shards_missing"] += max(
            0, absent - (absent_ok(obs) if absent_ok else 0))
        for pos, got in obs["shards"].items():
            if bytes(got) != want[pos].tobytes():
                out["shards_unequal"] += 1
                examples.append(f"{obs['name']} shard {pos}: bytes "
                                "differ from the reference encode")
            crc = reference.crc32c(want[pos], reference.HINFO_SEED)
            if obs["crcs"].get(pos) != crc:
                out["crcs_unequal"] += 1
                examples.append(
                    f"{obs['name']} shard {pos}: stored crc "
                    f"{obs['crcs'].get(pos)} != crc32c {crc}")
    out["examples"] = examples[:6]
    return out


class Compared:
    """The numbers compared, each beside its limit, in order."""

    def __init__(self) -> None:
        self.rows: list[tuple[str, float, float, str]] = []

    def at_most(self, name: str, value, limit=0) -> None:
        self.rows.append((name, value, limit, "<="))

    def at_least(self, name: str, value, limit) -> None:
        self.rows.append((name, value, limit, ">="))

    @property
    def correct(self) -> bool:
        return all(v <= lim if rule == "<=" else v >= lim
                   for _n, v, lim, rule in self.rows)

    def as_dict(self) -> dict:
        return {name: {"value": value, "limit": limit, "rule": rule}
                for name, value, limit, rule in self.rows}

    def print_last(self, file=None) -> None:
        file = file or sys.stderr       # as it is now, not at import
        for name, value, limit, rule in self.rows:
            ok = value <= limit if rule == "<=" else value >= limit
            print(f"compared {name}: {value} (limit {rule} {limit})"
                  f"{'' if ok else '  <-- NOT CORRECT'}", file=file)
        print(f"correct: {self.correct}", file=file)
        file.flush()


def judge(window, summary: dict, ops: list, observed: list,
          objects: dict, grown: dict, primaries: tuple[int, int]
          ) -> Compared:
    """Put every number beside its limit: the window kind's own
    (``window.judge_ops``), what the stores hold, the route, and what
    the kind asks of the route (``window.judge_route``)."""
    cmp = Compared()
    window.judge_ops(cmp, summary, ops, observed)
    for key in ("readback_unequal", "shards_unequal", "crcs_unequal",
                "shards_missing"):
        cmp.at_most(key, objects[key])
    eng = grown["engine"]
    cmp.at_most("host_flushes", eng.get("host_flushes", 0))
    cmp.at_most("fused_fallbacks", eng.get("device_fused_fallbacks", 0))
    cmp.at_most("engine_errors", eng.get("errors", 0))
    cmp.at_most("decode_errors", eng.get("decode_errors", 0))
    cmp.at_most("compiled_in_window", grown["compiles"])
    seen, missing = primaries
    cmp.at_least("primaries_seen", seen, 1)
    cmp.at_most("primaries_without_device", missing)
    window.judge_route(cmp, grown)
    return cmp
