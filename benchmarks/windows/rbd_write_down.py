"""``op: rbd_write_down``: ``rbd_write``'s window on a cluster that has
lost OSDs for good, on a pool with no spare to backfill onto.

Set-up (``prepare``): ``rbd_write``'s (the replicated pool, the image,
its preload and the overwrite warm-up, all while every OSD is up); it
records each PG's acting set and refuses one that leaves a position
without an OSD. Then it kills ``down_osds`` (the same OSDs in every
run, whatever the seed) and waits for the map's down mark (the
heartbeat grace) and until every PG is active again; it refuses the
state unless every PG of the pool kept ``min_size`` positions and lost
exactly the positions the dead OSDs held (nothing is left to recover:
no OSD is free to take a position over). Then it warms the degraded
route: one decode flush of each warm burst's size, of one stripe's
chunks, staged on the engine while its launch thread is held, so that
every decode program a window can meet is compiled before it; and
warm bursts of overwrites of objects whose PG lost a data position,
each of which has to rebuild that chunk on the engine.

It refuses a program without that route at once: before the cluster
starts, one whose engine's ``decode_sync`` takes no ``overwrite``; in
``prepare``, one whose engine counts no ``rmw_decodes``.

The window is ``rbd_write``'s. In a PG whose lost position is a data
position, every overwrite's ranged read of its stripe's k data chunks
lacks one, and the primary's op-wq worker waits while the engine's
decode flush rebuilds it; the spliced stripe is then encoded on the
overwrite route and range-written to the live positions.

What the kind states about its window, besides ``rbd_write``'s: an
object may lack exactly the position its PG lost (``absent_ok``), no
other. The route: ``rmw_decodes`` grew by at least the acknowledged
overwrites of the window on a PG that lost a data position, less those
whose stripe another op was writing at the same time (the extent cache
can then answer the read without the shards); no reconstruct fell back
to the host.
"""

from __future__ import annotations

import concurrent.futures
import threading

import numpy as np

from loadgen import seed_words
from windows.rbd_write import Window as RbdWrite
from windows.rbd_write import rng_bytes


class Window(RbdWrite):
    KEYS = dict(RbdWrite.KEYS,
                down_osds=list)     # OSD ids killed in set-up, the
                                    # same in every run

    @staticmethod
    def check(mix: dict) -> str | None:
        problem = RbdWrite.check(mix)
        if problem:
            return problem
        down = mix["down_osds"]
        if not down or not all(isinstance(v, int)
                               and not isinstance(v, bool) and v >= 0
                               for v in down) \
                or len(set(down)) != len(down):
            return "down_osds has to name distinct OSD ids"
        if "osds_down" in mix:
            # control.py reads osds_down as a degraded-read mix's
            return "name the dead OSDs under down_osds alone"
        return None

    def __init__(self, served, mix: dict, seed: int) -> None:
        import inspect
        from ceph_tpu.osd.device_engine import DeviceEncodeEngine
        super().__init__(served, mix, seed)
        if "overwrite" not in inspect.signature(
                DeviceEncodeEngine.decode_sync).parameters:
            raise RuntimeError(
                "this program's engine counts no decode an overwrite "
                "staged (its decode_sync takes no overwrite): refused "
                "before the cluster starts")
        #: data object -> the positions its PG lost
        self._lost: dict[str, tuple[int, ...]] = {}
        #: global stripe -> the ops writing it now (tokens)
        self._stripes: dict[int, set] = {}
        self._shared: set = set()
        #: window: acknowledged overwrites of an object whose PG lost
        #: a data position, and those of them that had their stripe
        #: to themselves
        self.on_lost_data = 0
        self.decodes_due = 0

    # -- what the kind states about its window -----------------------------
    def absent_ok(self, obs: dict) -> int:
        """The positions the object's PG lost, and no other."""
        pool = self.served.pool
        absent = set(range(pool["k"] + pool["m"])) - set(obs["shards"])
        lost = self._lost.get(obs["name"], ())
        return len(lost) if absent == set(lost) else 0

    # -- set-up ------------------------------------------------------------
    def prepare(self, note) -> None:
        served, mix = self.served, self.mix
        if "rmw_decodes" not in served.engine_stats():
            raise RuntimeError(
                "the device engine counts no decode an overwrite's "
                "read staged (no rmw_decodes counter)")
        super().prepare(note)
        osdmap = served.cluster.mon.osdmap
        pgs = osdmap.pgs_of_pool(served.pool_id)
        healthy = {ps: list(osdmap.pg_to_up_acting(served.pool_id, ps)[1])
                   for ps in pgs}
        empty = sorted(ps for ps, acting in healthy.items()
                       if any(o < 0 for o in acting))
        if empty:
            raise RuntimeError(f"with every OSD up, PGs {empty} have a "
                               "position without an OSD")
        down = sorted(mix["down_osds"])
        served.kill_osds(len(down), victims=down)
        served.settle()
        holes = self._holes(healthy, set(down))
        osdmap = served.cluster.mon.osdmap  # the map of this epoch
        for name in self.check_names:
            self._lost[name] = holes[osdmap.object_to_pg(
                served.pool_id, name)]
        note(phase="osds_down_and_settled", victims=served.victims,
             pgs=len(pgs), pgs_lost_data=sum(
                 1 for lost in holes.values() if self._data(lost)),
             objects_lost_data=sum(
                 1 for lost in self._lost.values() if self._data(lost)))
        self._warm_decodes()
        before = served.engine_stats()["rmw_decodes"]
        rng = np.random.default_rng(seed_words(self.seed) + [14])
        blocks = self._blocks_lost_data()
        for n in mix["warm_bursts"]:
            drawn = [int(b) for b in rng.choice(blocks, size=n,
                                                replace=False)]
            with concurrent.futures.ThreadPoolExecutor(n) as pool:
                list(pool.map(lambda b: self._write(b, rng_bytes(
                    self.seed + 1, b, mix["extent_bytes"])), drawn))
        if served.engine_stats()["rmw_decodes"] <= before:
            raise RuntimeError("the warm overwrites of stripes that lack "
                               "a data chunk rebuilt none on the engine")
        note(phase="warm_degraded_overwrites",
             compiles=served.compiles(),
             compile_s=served.compile_seconds())

    def _data(self, lost: tuple[int, ...]) -> bool:
        """Whether positions lost include a data position."""
        return any(pos < self.served.pool["k"] for pos in lost)

    def _holes(self, healthy: dict, down: set
               ) -> dict[int, tuple[int, ...]]:
        """PG -> the positions it lost, exactly those the dead OSDs
        held; refuses a PG that lost another, or kept fewer than
        ``min_size``."""
        served = self.served
        osdmap = served.cluster.mon.osdmap
        min_size = osdmap.pools[served.pool_id].min_size
        out = {}
        for ps, before in healthy.items():
            acting = osdmap.pg_to_up_acting(served.pool_id, ps)[1]
            lost = [pos for pos, o in enumerate(acting) if o < 0]
            held = [pos for pos, o in enumerate(before) if o in down]
            if lost != held or len(acting) - len(lost) < min_size:
                raise RuntimeError(
                    f"PG {ps}: acting {acting} after the kill, "
                    f"{before} before; min_size {min_size}")
            out[ps] = tuple(lost)
        return out

    def _blocks_lost_data(self) -> np.ndarray:
        """The image's blocks in objects whose PG lost a data
        position."""
        per = self.mix["object_bytes"] // self.mix["extent_bytes"]
        return np.array([i * per + j
                         for i, name in enumerate(self.check_names)
                         if self._data(self._lost[name])
                         for j in range(per)])

    def _warm_decodes(self) -> None:
        """One decode flush of ``n`` ops for each warm burst ``n``:
        one stripe's k chunks, one lost, staged on the engine while
        its launch thread is held, so that they leave as one flush of
        one erasure signature and compile the program a window's
        flush of so many overwrites' rebuilds runs."""
        served = self.served
        backend = next(
            pg.backend for osd in served.cluster.osds.values()
            for pg in osd.pgs.values() if pg.pool == served.pool_id)
        codec, sinfo = backend.device_codec, backend.sinfo
        k, m = served.pool["k"], served.pool["m"]
        shards = {pos: np.zeros(sinfo.chunk_size, dtype=np.uint8)
                  for pos in range(1, k + 1)}
        engine = served._engine()
        for n in self.mix["warm_bursts"]:
            gate, entered = threading.Event(), threading.Event()
            done = threading.Semaphore(0)
            errors: list = []

            def hold() -> None:
                entered.set()
                gate.wait(60)

            def cont(out, err) -> None:
                if err is not None or out is None:
                    errors.append(err)
                done.release()

            held = threading.Thread(
                target=lambda: engine.run_sync(hold, timeout=120),
                name="bench-warm-gate")
            held.start()
            entered.wait(60)
            before = served.engine_stats()["decode_flushes"]
            try:
                for i in range(n):
                    engine.stage_decode(("warm", i), codec, sinfo,
                                        dict(shards), list(range(k)),
                                        cont)
            finally:
                gate.set()
                held.join()
            for _ in range(n):
                if not done.acquire(timeout=60):
                    raise RuntimeError("a warm decode never came back")
            flushes = served.engine_stats()["decode_flushes"] - before
            if errors or flushes != 1:
                raise RuntimeError(
                    f"warm decodes of {n} (k={k}, m={m}): {flushes} "
                    f"flushes, errors {errors[:2]}")

    # -- the window --------------------------------------------------------
    def _write(self, block: int, data: bytes) -> None:
        """``rbd_write``'s, noting whether another op wrote the same
        stripe while this one was in flight."""
        extent, obj = self.mix["extent_bytes"], self.mix["object_bytes"]
        stripe = block * extent // (self.served.pool["k"]
                                    * self.served.pool["stripe_unit"])
        token = object()
        with self._lock:
            writers = self._stripes.setdefault(stripe, set())
            writers.add(token)
            if len(writers) > 1:
                self._shared.update(writers)
        try:
            super()._write(block, data)
        finally:
            with self._lock:
                writers.discard(token)
                if not writers:
                    del self._stripes[stripe]
                alone = token not in self._shared
                self._shared.discard(token)
        name = self.image._data._piece(block * extent // obj)
        if self._data(self._lost.get(name, ())):
            with self._lock:
                self.on_lost_data += 1
                self.decodes_due += alone

    def run(self, seconds: float, during=None) -> tuple[dict, list]:
        self.on_lost_data = self.decodes_due = 0
        summary, ops = super().run(seconds, during)
        summary.update(overwrites_on_lost_data=self.on_lost_data,
                       rebuilds_due=self.decodes_due)
        return summary, ops

    # -- what the window says ----------------------------------------------
    def judge_route(self, cmp, grown: dict) -> None:
        super().judge_route(cmp, grown)
        eng = grown["engine"]
        cmp.at_least("overwrites_on_lost_data", self.on_lost_data, 1)
        cmp.at_least("rmw_decodes", eng.get("rmw_decodes", 0),
                     self.decodes_due)
        cmp.at_most("decode_fallbacks", grown.get("decode_fallbacks", 0))
