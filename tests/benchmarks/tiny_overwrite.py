"""A window kind as a later PR would bring it: a closed loop of range
overwrites. Test-only: ``test_bench_overwrite.py`` copies it to the
tiny checkout's ``windows/``, where the harness finds it by the ``op``
of a traffic file; nothing that is there is edited.

``clients`` threads, one op in flight each, for ``--seconds``: each op
is ``io.write(name, extent_bytes of seeded bytes, offset)`` at an
offset aligned to ``extent_bytes``, on one of the first
``overwrite_objects`` preloaded objects, both drawn from the seed; no
two ops are in flight on one block, so the order of the acknowledgements
is the order of the writes to a block. The other preloaded objects are
not written to: the sample holds both kinds.

What the kind states about its window (``windows/base.py``): an object
holds its preloaded bytes with every acknowledged extent laid over them
in ack order (``expected``); a range overwrite drops the shards'
whole-shard crc, so an object that was overwritten keeps no ``hinfo``
and one that was not keeps it (``keeps_hinfo``); an op encodes the one
stripe its extent lies in (``op_bytes``). It asks nothing of the
engine's counters.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from loadgen import OpRecord, seed_words
from windows.base import WindowBase
from windows.closed_loop import Window as Loop


class Window(WindowBase):
    KEYS = {
        "extent_bytes": int,        # an op writes so many bytes, at an
                                    # offset aligned to them
        "overwrite_objects": int,   # the first so many preloaded
                                    # objects are written to
    }

    @staticmethod
    def check(mix: dict) -> str | None:
        extent, width = mix["extent_bytes"], mix["object_bytes"]
        if extent < 1 or width % extent:
            return "extent_bytes has to divide object_bytes"
        if not 1 <= mix["overwrite_objects"] <= mix["preload_objects"]:
            return "overwrite_objects is out of range"
        if mix["clients"] > mix["overwrite_objects"] * (width // extent):
            return "more clients than blocks: a client would never " \
                   "find a free one"
        return None

    def __init__(self, served, mix: dict, seed: int) -> None:
        self.served = served
        self.mix = mix
        self.seed = seed
        self.check_names: list[str] = []
        self.read_back = True
        #: (object, offset, bytes) of every acknowledged op, in ack order
        self.acked: list[tuple[str, int, bytes]] = []
        self._busy: set[tuple[str, int]] = set()
        self._lock = threading.Lock()

    # -- what the kind states about its window -----------------------------
    def expected(self, name: str) -> bytes:
        data = bytearray(self.served.payloads.of(name))
        for who, offset, extent in self.acked:
            if who == name:
                data[offset:offset + len(extent)] = extent
        return bytes(data)

    def overwritten(self) -> set[str]:
        return {who for who, _offset, _extent in self.acked}

    def keeps_hinfo(self, obs: dict) -> bool:
        return obs["name"] not in self.overwritten()

    def op_bytes(self) -> int:
        pool = self.served.pool
        return pool["k"] * pool["stripe_unit"]

    @staticmethod
    def absent_ok(obs: dict) -> int:
        del obs
        return 0

    # -- set-up ------------------------------------------------------------
    def prepare(self, note) -> None:
        """One overwrite of an object of its own: whatever the
        overwrite path compiles, it compiles here."""
        served, extent = self.served, self.mix["extent_bytes"]
        served.io.write_full("owwarm_0", served.payloads.of("owwarm_0"))
        served.io.write("owwarm_0", bytes(extent), extent)
        note(phase="warm_overwrite", compiles=served.compiles())

    # -- the window --------------------------------------------------------
    def _client(self, tid: int, deadline: float, names: list[str],
                out: list[OpRecord]) -> None:
        extent = self.mix["extent_bytes"]
        blocks = self.mix["object_bytes"] // extent
        rng = np.random.default_rng(seed_words(self.seed) + [11, tid])
        while time.monotonic() < deadline:
            block = (names[int(rng.integers(len(names)))],
                     int(rng.integers(blocks)) * extent)
            with self._lock:
                if block in self._busy:
                    continue
                self._busy.add(block)
            data = rng.bytes(extent)
            rec = OpRecord(block[0], time.monotonic())
            try:
                self.served.io.write(block[0], data, block[1])
                rec.end = time.monotonic()
                rec.ok = True
                with self._lock:
                    self.acked.append((*block, data))
            except Exception as exc:    # the op failed: it is counted
                rec.end = time.monotonic()
                rec.error = repr(exc)[:200]
            finally:
                with self._lock:
                    self._busy.discard(block)
            out.append(rec)

    def run(self, seconds: float, during=None) -> tuple[dict, list]:
        mix, served = self.mix, self.served
        names = served.preloaded[:mix["overwrite_objects"]]
        records: list[list[OpRecord]] = [[] for _ in
                                         range(mix["clients"])]
        t_start = time.monotonic()
        threads = [threading.Thread(
            target=self._client,
            args=(tid, t_start + seconds, names, records[tid]),
            name=f"bench-client-{tid}") for tid in range(mix["clients"])]
        for th in threads:
            th.start()
        try:
            if during is not None:
                during(t_start)
        finally:
            for th in threads:
                th.join()
        ops = [rec for recs in records for rec in recs]
        good = [rec for rec in ops if rec.ok]
        t_end = max((rec.end for rec in ops), default=time.monotonic())
        window_s = max(t_end - t_start, 1e-9)
        self.check_names = served.preloaded
        return {"attempted": len(ops),
                "failed": len(ops) - len(good),
                "window_s": window_s,
                "MBps": len(good) * mix["extent_bytes"] / window_s / 1e6,
                "latencies_ms": sorted((rec.end - rec.start) * 1e3
                                       for rec in good),
                "errors": [rec.error for rec in ops if not rec.ok][:4],
                "objects_overwritten": len(self.overwritten())}, ops

    # -- what the window says ----------------------------------------------
    #: a rate over the window and a tail of its acknowledged ops, as
    #: the closed loop reports them
    values = Loop.values

    def judge_ops(self, cmp, summary: dict, ops: list,
                  observed: list) -> None:
        del ops
        written = self.overwritten()
        cmp.at_most("ops_failed", summary["failed"])
        cmp.at_least("ops_acknowledged",
                     summary["attempted"] - summary["failed"], 1)
        cmp.at_least("overwritten_compared", sum(
            1 for obs in observed if obs["name"] in written), 1)
        cmp.at_least("untouched_compared", sum(
            1 for obs in observed if obs["name"] not in written), 1)

    def judge_route(self, cmp, grown: dict) -> None:
        """Nothing: an overwrite does not go through the engine yet.
        The kind of the deployment this one makes room for will ask
        for at least one encode flush."""
