"""The closed loop as a window kind: ``write_full`` and ``read``.

``clients`` threads, one op in flight each, for ``--seconds``
(``loadgen.ClosedLoop``, unchanged). With ``osds_down`` above 0 the
kind asks set-up to kill so many OSDs, to wait until the recovery that
can happen has happened, and to warm every decode bucket: the window
then starts from the state that lasts.

A window kind is one file ``windows/<op>.py`` with a ``Window`` class:
``KEYS`` (the traffic keys of its own, checked by ``spec.traffic``),
``check(mix)`` (a reason to refuse the mix, or None), ``prepare(note)``
(what it needs from set-up beyond the write warm-up and the preload),
``run(seconds, during)`` (the window: returns the summary and the op
records), ``values(summary)`` (the end-to-end readings under the names
``mix["reports"]`` gives them), what the comparison samples
(``check_names``, ``read_back``, ``absent_ok(obs)``), what it states
about its window (``windows/base.py``, each with the default a window
of whole-object ops has: ``expected(name)``, the bytes an object holds
once the window has closed; ``keeps_hinfo(obs)``, whether its shards
still keep their whole-shard crc; ``op_bytes()``, the bytes of user
data one engine op encodes or decodes) and the rows of its own that it
puts beside their limits (``judge_ops``, ``judge_route``).
"""

from __future__ import annotations

from loadgen import ClosedLoop, quantile
from windows.base import WindowBase


class Window(WindowBase):
    KEYS = {
        "osds_down": int,         # killed during set-up, chosen by seed
                                  # unless the mix names its victims
        "degraded_share": float,  # with OSDs down: the share of reads
                                  # sent to objects that lack a data
                                  # shard
        "max_objects": int,       # a window that writes more fails
                                  # loudly
    }
    OPS = ("write_full", "read")
    #: optional: the OSD ids set-up kills, the same in every run; a
    #: mix without it kills ``osds_down`` OSDs drawn from the seed
    VICTIMS = "victims"

    @classmethod
    def check(cls, mix: dict) -> str | None:
        if mix["op"] not in cls.OPS:
            return f"op {mix['op']!r} is no closed loop"
        if mix["op"] == "read" and mix["preload_objects"] < 1:
            return "reads need preload_objects"
        if mix["osds_down"] < 0 or not 0 <= mix["degraded_share"] <= 1:
            return "a size is out of range"
        victims = mix.get(cls.VICTIMS)
        if victims is not None and (
                not isinstance(victims, list)
                or not all(isinstance(v, int) and not isinstance(v, bool)
                           and v >= 0 for v in victims)
                or len(set(victims)) != len(victims)
                or len(victims) != mix["osds_down"]):
            return "victims has to name osds_down distinct OSD ids"
        return None

    def __init__(self, served, mix: dict, seed: int) -> None:
        self.served = served
        self.mix = mix
        self.seed = seed
        self.degraded = mix["osds_down"] > 0
        self.loop: ClosedLoop | None = None
        #: what the comparison samples once the window has closed
        self.check_names: list[str] = []
        self.read_back = not self.degraded

    def prepare(self, note) -> None:
        served = self.served
        if not self.degraded:
            return
        served.kill_osds(self.mix["osds_down"],
                         victims=self.mix.get(self.VICTIMS))
        served.settle()
        note(phase="osds_down_and_settled", victims=served.victims)
        served.warm_degraded_reads()
        note(phase="warm_reads", compiles=served.compiles(),
             compile_s=served.compile_seconds(),
             objects_by_lost_data_shards=served.degraded_objects)

    def absent_ok(self, obs: dict) -> int:
        """An object may lack the shards the dead OSDs held: the spare
        OSDs take over what positions they can, the rest stay holes."""
        del obs
        return self.mix["osds_down"]

    def run(self, seconds: float, during=None) -> tuple[dict, list]:
        served, mix = self.served, self.mix
        self.loop = loop = ClosedLoop(
            served.io, mix, served.payloads, self.seed,
            read_names=served.intact if self.degraded
            else served.preloaded,
            degraded_names=served.reconstructing)
        loop.run(seconds, during=during)
        if loop.overflow:
            raise RuntimeError(
                f"the window wrote max_objects = {mix['max_objects']} "
                "objects: the traffic file's bound on host memory; a "
                "benchmark PR has to raise it")
        ops = loop.ops()
        self.check_names = served.preloaded if self.degraded \
            else [r.name for r in ops if r.ok]
        return loop.summary(), ops

    def values(self, summary: dict) -> dict:
        """All acknowledged bytes over all the window's seconds; the
        tail over every op that was acknowledged (a failed op makes
        the run not correct)."""
        reports = self.mix["reports"]
        values = {reports["throughput"]: summary["MBps"]}
        if summary["latencies_ms"]:
            values[reports["tail"]["name"]] = quantile(
                summary["latencies_ms"], reports["tail"]["quantile"])
        return values

    def judge_ops(self, cmp, summary: dict, ops: list,
                  observed: list) -> None:
        del observed
        cmp.at_most("ops_failed", summary["failed"])
        cmp.at_least("ops_acknowledged",
                     summary["attempted"] - summary["failed"], 1)
        cmp.at_most("reads_unequal",
                    sum(1 for r in ops if r.equal is False))

    def judge_route(self, cmp, grown: dict) -> None:
        eng = grown["engine"]
        if self.degraded:
            cmp.at_least("decode_flushes",
                         eng.get("decode_flushes", 0), 1)
        else:
            cmp.at_least("encode_flushes", eng.get("flushes", 0), 1)
