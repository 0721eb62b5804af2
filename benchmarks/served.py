"""The system under test, brought up as a configuration file says.

One process: mon + N OSDs + clients (``MiniCluster``, the vstart role),
one EC pool from the configuration's profile, entered only through the
client's own calls (``create_ec_pool``, ``open_ioctx``, ``write_full``,
``read``). From the program the benchmark takes the system, its engine
counters, its stage clocks and its compile table; everything that turns
them into numbers lives in this directory. Copied from ``chip_smoke.py``
(``ServedPool``) and cut to what a cell needs.
"""

from __future__ import annotations

import concurrent.futures
import json
import threading
import time

import numpy as np

import spec
from loadgen import Payloads, seed_words

POOL = "bench"

#: from "every byte of a burst is counted as staged" to "the gate
#: opens": see ``Served._wait_staged``
STAGED_SETTLE_S = 0.25


class Served:
    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.config = config
        self.mix = mix
        self.seed = seed
        self.pool = config["pool"]
        self.cluster = None
        self.io = None
        self.rados = None
        self.pool_id = -1
        self.payloads: Payloads | None = None
        self.preloaded: list[str] = []
        self.victims: list[int] = []
        #: configuration options as they were before ``start`` and
        #: ``set_options`` changed them
        self._conf_before: dict = {}
        #: lost data shards -> preloaded objects that lack so many
        self.degraded_objects: dict[int, int] = {}
        #: preloaded objects that lack a data shard / lack none
        self.reconstructing: list[str] = []
        self.intact: list[str] = []

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        from ceph_tpu.qa.cluster import MiniCluster
        from ceph_tpu.utils.config import g_conf
        dep, pool = self.config["deployment"], self.pool
        if dep["store"] != "memstore":
            raise ValueError("only memstore deployments fit a run")
        self.set_options(
            osd_heartbeat_grace=float(dep["osd_heartbeat_grace"]))
        self.cluster = MiniCluster(n_osds=dep["n_osds"],
                                   store=dep["store"]).start()
        # the whole erasure-code profile, as the file states it
        self.cluster.create_ec_pool(POOL, pg_num=pool["pg_num"],
                                    **spec.ec_profile(pool))
        self.rados = self.cluster.client()
        self.io = self.rados.open_ioctx(POOL)
        self.io.op_timeout = self.mix["op_timeout_s"]
        osdmap = self.cluster.mon.osdmap
        self.pool_id = osdmap.pool_by_name[POOL]
        unit = osdmap.pools[self.pool_id].stripe_unit
        if unit != pool["stripe_unit"]:
            raise RuntimeError(
                f"the pool's stripe_unit is {unit}, the configuration "
                f"states {pool['stripe_unit']}")

    def set_options(self, **options) -> None:
        """Set options of the program's configuration; ``stop`` puts
        back what they were before the first change."""
        from ceph_tpu.utils.config import g_conf
        conf = g_conf()
        for name, value in options.items():
            self._conf_before.setdefault(name, conf[name])
            conf.set(name, value)

    def stop(self) -> None:
        """Stop and join every daemon; frees the stores."""
        from ceph_tpu.utils.config import g_conf
        try:
            if self.cluster is not None:
                self.cluster.stop()
        finally:
            self.cluster = None
            for name, value in self._conf_before.items():
                g_conf().set(name, value)
            self._conf_before = {}

    # -- views of the program's counters ----------------------------------
    def _engine(self):
        engines = {}
        for osd in self.cluster.osds.values():
            handle = osd.device_engine()
            engines[id(getattr(handle, "engine", handle))] = handle
        if len(engines) != 1:
            raise RuntimeError(f"{len(engines)} device engines, "
                               "expected the one shared engine")
        return next(iter(engines.values()))

    def engine_stats(self) -> dict:
        stats = dict(self._engine().stats)
        return {k: v for k, v in stats.items()
                if isinstance(v, (int, float))}

    @staticmethod
    def compiles_by_signature() -> dict[str, int]:
        from ceph_tpu.utils.device_telemetry import telemetry
        table = telemetry().snapshot()["compiles_by_signature"]
        return {sig: ent["compiles"] for sig, ent in table.items()}

    @classmethod
    def compiles(cls) -> int:
        """Programs compiled by this process so far."""
        return sum(cls.compiles_by_signature().values())

    @staticmethod
    def compile_seconds() -> float:
        from ceph_tpu.utils.device_telemetry import telemetry
        return float(telemetry().snapshot_brief().get(
            "compile_time_s", 0.0))

    @staticmethod
    def stage_sums() -> dict:
        """stage -> (sum of seconds, count) of the always-on stage
        clocks, process-wide."""
        from ceph_tpu.utils.dataplane import dataplane
        out = {}
        for key, ent in dataplane().perf.dump().items():
            if key.startswith("stage_") and isinstance(ent, dict) \
                    and "avgcount" in ent:
                out[key[len("stage_"):]] = (float(ent["sum"]),
                                            int(ent["avgcount"]))
        return out

    @staticmethod
    def decode_fallbacks() -> int:
        """Reconstructs that left the engine for the host codec."""
        from ceph_tpu.utils.device_telemetry import telemetry
        return int(telemetry().perf.get("engine_decode_fallbacks"))

    def snapshot(self) -> dict:
        by_signature = self.compiles_by_signature()
        return {"engine": self.engine_stats(),
                "compiles": sum(by_signature.values()),
                "compiled": by_signature,
                "decode_fallbacks": self.decode_fallbacks(),
                "stages": self.stage_sums()}

    @staticmethod
    def growth(before: dict, after: dict) -> dict:
        eng = {k: after["engine"][k] - before["engine"].get(k, 0)
               for k in after["engine"]}
        stages = {}
        for stage, (s1, c1) in after["stages"].items():
            s0, c0 = before["stages"].get(stage, (0.0, 0))
            stages[stage] = {"sum_s": s1 - s0, "count": c1 - c0}
        return {"engine": eng, "stages": stages,
                "compiles": after["compiles"] - before["compiles"],
                "compiled": sorted(
                    sig for sig, n in after["compiled"].items()
                    if n > before["compiled"].get(sig, 0)),
                "decode_fallbacks": after["decode_fallbacks"]
                - before["decode_fallbacks"]}

    def primaries_without_device(self) -> tuple[int, int]:
        """(primaries looked at, those whose ECBackend has no device
        engine)."""
        osdmap = self.cluster.mon.osdmap
        seen = missing = 0
        for ps in osdmap.pgs_of_pool(self.pool_id):
            _, _, primary = osdmap.pg_to_up_acting(self.pool_id, ps)
            osd = self.cluster.osds.get(primary)
            pg = osd.pgs.get((self.pool_id, ps)) if osd else None
            if pg is None:
                continue
            seen += 1
            if getattr(pg.backend, "device", None) is None:
                missing += 1
        return seen, missing

    # -- set-up traffic ----------------------------------------------------
    def make_payloads(self) -> None:
        self.payloads = Payloads(self.seed, self.mix["object_bytes"],
                                 self.mix["payload_pool"])

    def _locate(self, name: str) -> tuple[int, list[int]]:
        ps, acting, _ = self.cluster.mon.osdmap.object_locator(
            self.pool_id, name)
        return ps, list(acting)

    def _same_pg_names(self, prefix: str, count: int) -> list[str]:
        """``count`` names ``<prefix>_<i>`` that fall into one PG."""
        osdmap = self.cluster.mon.osdmap
        target, names, i = None, [], 0
        while len(names) < count:
            name = f"{prefix}_{i}"
            i += 1
            # the hash alone: a full CRUSH mapping per candidate name
            # costs set-up seconds
            ps = osdmap.object_to_pg(self.pool_id, name)
            if target is None:
                target = ps
            if ps == target:
                names.append(name)
        return names

    def _burst(self, op, names: list[str], gated: bool) -> None:
        """``len(names)`` ops at once. ``gated``: the engine's thread
        is held (``run_sync``, the call deep scrub uses) until all of
        them are staged, so that they leave as ONE flush and compile
        that flush's bucket: batches form by chance otherwise, and a
        bucket first met inside the window would compile there."""
        engine = self._engine() if gated else None
        gate, entered, held = threading.Event(), threading.Event(), None

        def hold() -> None:
            entered.set()
            gate.wait()

        if engine is not None and hasattr(engine, "run_sync"):
            held = threading.Thread(
                target=lambda: engine.run_sync(hold, timeout=120),
                name="bench-warm-gate")
            held.start()
            # run_sync drains the engine first: nothing is staged or
            # in flight once the engine's thread sits in hold()
            entered.wait(60)
        try:
            with concurrent.futures.ThreadPoolExecutor(
                    len(names)) as pool:
                futs = [pool.submit(op, n) for n in names]
                if held is not None:
                    self._wait_staged(len(names))
                    gate.set()
                for fut in futs:
                    fut.result()
        finally:
            gate.set()
            if held is not None:
                held.join()

    def _wait_staged(self, n_ops: int, limit_s: float = 10.0) -> None:
        """Until the engine holds ``n_ops`` objects' bytes staged (a
        degraded read stages at least its k surviving shards, an
        object's worth), or the count has stopped growing for
        seconds. The gauge moves BEFORE the op is copied into the
        staging buffer and put on the engine's queue (milliseconds for
        4 MiB), so the gate opens a little after it reads full: opened
        at once, the last op of every burst left in a flush of its
        own."""
        from ceph_tpu.utils.device_telemetry import telemetry
        want = n_ops * self.mix["object_bytes"]
        t0 = last_change = time.monotonic()
        last = 0
        while True:
            now = time.monotonic()
            live = telemetry().hbm_live_bytes()
            if live != last:
                last, last_change = live, now
            if live >= want:
                time.sleep(STAGED_SETTLE_S)
                return
            if now - t0 > limit_s or \
                    (live > 0 and now - last_change > 3.0):
                return
            time.sleep(0.01)

    def _write(self, name: str) -> None:
        self.io.write_full(name, self.payloads.of(name))

    def _read(self, name: str) -> None:
        self.io.read(name)      # the window's reads are the ones judged

    def _gated(self, op, n: int, names: list[str],
               tries: int = 4) -> None:
        """A gated burst of ``n`` ops that left as ONE flush, tried
        again on fresh names from ``names`` when it did not (the first
        ops a PG sees can queue behind its peering)."""
        for attempt in range(tries):
            chunk = names[attempt * n:(attempt + 1) * n] or names[:n]
            before, t0 = self.engine_stats(), time.monotonic()
            self._burst(op, chunk, gated=True)
            after = self.engine_stats()
            flushes = sum(after[key] - before[key]
                          for key in ("flushes", "decode_flushes"))
            print(f"warm-up: burst of {n}, try {attempt}: {flushes} "
                  f"flush(es), {time.monotonic() - t0:.2f} s",
                  flush=True)
            if flushes <= 1:
                return

    def warm_writes(self) -> None:
        """Every flush bucket a window of this mix can meet, then one
        ungated burst over many PGs (connections, PG state)."""
        for n in self.mix["warm_bursts"]:
            self._gated(self._write, n,
                        self._same_pg_names(f"warm{n}", 3 * n), tries=3)
        self._burst(self._write,
                    [f"warmall_{i}"
                     for i in range(self.mix["clients"])], gated=False)

    def preload(self) -> None:
        names = [f"obj_{i}" for i in range(self.mix["preload_objects"])]
        with concurrent.futures.ThreadPoolExecutor(
                self.mix["clients"]) as pool:
            list(pool.map(self._write, names))
        self.preloaded = names

    def draw_osds(self, n: int, salt: int = 5) -> list[int]:
        """``n`` distinct OSDs chosen from the seed, in drawn order."""
        rng = np.random.default_rng(seed_words(self.seed) + [salt])
        return [int(v) for v in rng.choice(
            self.config["deployment"]["n_osds"], size=n, replace=False)]

    def kill_osds(self, n: int, victims: list[int] | None = None
                  ) -> None:
        """Kill ``n`` OSDs chosen from the seed (or ``victims``) and
        return when the map marks them down (there is no ``osd down``
        command: the wait is the heartbeat grace). The program's map
        leaves a down OSD out of CRUSH at once, so a spare OSD takes
        over a lost position and recovery starts with the new map."""
        self.victims = sorted(self.draw_osds(n) if victims is None
                              else victims)
        epoch = self.cluster.epoch()
        for victim in self.victims:
            self.cluster.kill_osd(victim)
        for victim in self.victims:
            self.cluster.wait_for_osd_down(victim, timeout=120)
        self.rados.wait_for_epoch(epoch + 1, timeout=60)

    def settle(self, timeout: float = 240.0) -> None:
        """Until the recovery that can happen has happened. Reads
        racing it would be measured at whatever share of it the window
        caught; a window that wants the state that lasts (the OSDs
        down, the spares filled, every other lost position a hole)
        starts from here."""
        self.cluster.wait_for_clean(timeout=timeout)

    def revive_osds(self) -> None:
        """Start the killed OSDs again on the stores they left, and
        wait until the map has them up and every PG is clean."""
        for victim in self.victims:
            self.cluster.revive_osd(victim)
        self.cluster.wait_for_osds_up(timeout=120)
        self.victims = []
        self.settle()

    def held(self, ps: int, pos: int, osd_id: int) -> set[str]:
        """The objects OSD ``osd_id`` holds at position ``pos`` of the
        PG: nothing when it is not alive or has no such collection."""
        from ceph_tpu.osd.pg import pg_cid
        from ceph_tpu.store.object_store import StoreError
        osd = self.cluster.osds.get(osd_id)
        if osd is None:
            return set()
        try:
            return set(osd.store.list_objects(
                pg_cid(self.pool_id, ps, pos)))
        except StoreError:
            return set()

    def lost_data_shards(self, names: list[str]) -> dict[str, int]:
        """For each object, how many of its k data shards no live OSD
        of the acting set holds now: a hole in the acting set, or a
        spare that has taken the position over and not been filled."""
        osdmap = self.cluster.mon.osdmap
        held: dict[int, list[set[str]]] = {}
        out = {}
        for name in names:
            ps = osdmap.object_to_pg(self.pool_id, name)
            if ps not in held:
                _, acting, _ = osdmap.pg_to_up_acting(self.pool_id, ps)
                held[ps] = [self.held(ps, pos, osd) for pos, osd in
                            enumerate(list(acting)[:self.pool["k"]])]
            out[name] = sum(1 for have in held[ps] if name not in have)
        return out

    def warm_degraded_reads(self) -> None:
        """Every decode bucket: for each number of lost data shards
        the pool now has, gated bursts of reads inside the PG that
        holds most such objects: they share one erasure signature
        (which shards are present, which are wanted), and a decode
        flush batches the ops of one signature, of whatever PG."""
        by_kind: dict[int, dict[int, list[str]]] = {}
        osdmap = self.cluster.mon.osdmap
        self.reconstructing, self.intact = [], []
        for name, lost in self.lost_data_shards(self.preloaded).items():
            if lost:
                by_kind.setdefault(lost, {}).setdefault(
                    osdmap.object_to_pg(self.pool_id, name),
                    []).append(name)
                self.reconstructing.append(name)
            else:
                self.intact.append(name)
        self.degraded_objects = {
            lost: sum(len(v) for v in pgs.values())
            for lost, pgs in by_kind.items()}
        for lost in sorted(by_kind):
            names = max(by_kind[lost].values(), key=len)
            for n in self.mix["warm_bursts"]:
                self._gated(self._read, n,
                            [names[i % len(names)] for i in range(n)])
        self._burst(self._read,
                    self.preloaded[:self.mix["clients"]], gated=False)

    # -- what the stores hold, for the comparison -------------------------
    def observe(self, names: list[str], read_back: bool) -> list[dict]:
        """For each object: the bytes the client reads back (when
        asked), and every shard AS THE OSD STORES HOLD IT with the crc
        its ``hinfo`` holds, by the map as it is now: a shard that
        recovery rebuilt on a spare OSD is among them, a position that
        stayed a hole is absent; ``unmapped`` counts the positions to
        which the map assigns no OSD at all."""
        from ceph_tpu.osd.pg import pg_cid
        back: dict[str, bytes] = {}
        if read_back:
            def read(name: str) -> bytes:
                try:
                    return self.io.read(name)
                except Exception as exc:
                    # an acknowledged write that cannot be read back
                    # is an unequal answer, not a crash of the run
                    print(f"read-back of {name} failed: {exc!r}",
                          flush=True)
                    return b""
            with concurrent.futures.ThreadPoolExecutor(
                    self.mix["clients"]) as pool:
                back = dict(zip(names, pool.map(read, names)))
        out = []
        for name in names:
            ps, acting = self._locate(name)
            shards: dict[int, bytes] = {}
            crcs: dict[int, int] = {}
            for pos, osd_id in enumerate(acting):
                osd = self.cluster.osds.get(osd_id)
                if osd is None:
                    continue
                cid = pg_cid(self.pool_id, ps, pos)
                shards[pos] = bytes(osd.store.read(cid, name))
                hinfo = json.loads(osd.store.getattr(cid, name,
                                                     "hinfo"))
                crcs[pos] = int(hinfo["hashes"][pos])
            out.append({"name": name, "read_back": back.get(name),
                        "shards": shards, "crcs": crcs,
                        "unmapped": sum(1 for o in acting if o < 0)})
        return out
