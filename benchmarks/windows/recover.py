"""Recovery as a window: OSDs die, and the window lasts from the map
that marks them down until every PG is clean again.

No client sends anything in the window, and ``--seconds`` does not end
it: the work is fixed (the shards the dead OSDs held are rebuilt onto
the spares), the time is the result. Set-up preloads the objects, then
rehearses: with recovery held back (``osd_max_backfills`` 0) it kills
OTHER OSDs drawn from the seed, warms every decode bucket with gated
bursts of reconstructing reads (a recovery decode and a degraded read
of the same number of lost shards run the same programs), and starts
the OSDs again on the stores they left, so nothing has moved. Then the
recovery options are set as the traffic file states them and the
victims die; detection (the heartbeat grace) is set-up, not recovery.

What the window reports (``mix["reports"]`` names them):

- ``throughput``: bytes of the shards whose holder changed between
  the map before the kill and the map at clean, summed from the stores
  that hold them now, over all the window's seconds;
- ``tail``: per PG that had a shard to rebuild, the seconds from the
  window's start until it was clean and stayed so (polled every
  ``poll_s``), at the stated quantile;
- ``length``: the window's seconds, printed and put beside the limit
  ``clean_timeout_s``; it is the throughput over a byte count that
  hangs on the seed, and is no metric.
"""

from __future__ import annotations

import threading
import time

from loadgen import quantile


class Window:
    KEYS = {
        "osds_down": int,           # die at the window's start
        "clean_timeout_s": float,   # not clean by then: the run fails
        "poll_s": float,            # how often every PG is looked at
        "recovery_options": dict,   # the program's options, set for
                                    # the window as stated here
    }

    @staticmethod
    def check(mix: dict) -> str | None:
        if mix["osds_down"] < 1 or mix["preload_objects"] < 1:
            return "recovery needs osds_down and preload_objects"
        if mix["clean_timeout_s"] <= 0 or mix["poll_s"] <= 0:
            return "a time is out of range"
        for name, value in mix["recovery_options"].items():
            if not isinstance(value, (int, float)) or \
                    isinstance(value, bool):
                return f"recovery option {name} = {value!r}"
        if not isinstance(mix["reports"].get("length"), str):
            return "reports has to name the window's length"
        return None

    def __init__(self, served, mix: dict, seed: int) -> None:
        self.served = served
        self.mix = mix
        self.seed = seed
        self.check_names: list[str] = []
        self.read_back = True
        #: PG -> the OSD of every position, before the kill
        self._before: dict[int, list[int]] = {}
        self._epoch = -1
        self._acting: dict[int, list[int]] = {}

    # -- set-up ------------------------------------------------------------
    def prepare(self, note) -> None:
        served, n = self.served, self.mix["osds_down"]
        drawn = served.draw_osds(2 * n, salt=6)
        rehearsal, victims = drawn[:n], drawn[n:]
        t0 = time.monotonic()
        served.set_options(osd_max_backfills=0)
        served.kill_osds(n, victims=rehearsal)
        t_down = time.monotonic()
        served.warm_degraded_reads()
        t_warm = time.monotonic()
        served.revive_osds()
        note(phase="rehearsed", osds=sorted(rehearsal),
             down_s=round(t_down - t0, 2),
             warm_s=round(t_warm - t_down, 2),
             revive_s=round(time.monotonic() - t_warm, 2),
             compiles=served.compiles(),
             compile_s=served.compile_seconds(),
             objects_by_lost_data_shards=served.degraded_objects)
        served.set_options(**self.mix["recovery_options"])
        self._before = self._holders()
        t0 = time.monotonic()
        served.kill_osds(n, victims=victims)
        note(phase="osds_down", victims=served.victims,
             down_s=round(time.monotonic() - t0, 2))

    @staticmethod
    def absent_ok(obs: dict) -> int:
        """Every position the map at clean gives an OSD has to hold
        its shard. What is excused is a position to which the map
        assigns NO OSD: with 11 of 12 OSDs in every PG and one dead,
        the program's CRUSH gives up on the one OSD left for 0 to 3 of
        64 PGs, whichever OSD dies; recovery has nowhere to rebuild
        that shard, and the pool serves the PG one shard short."""
        return obs["unmapped"]

    def _holders(self) -> dict[int, list[int]]:
        osdmap = self.served.cluster.mon.osdmap
        out = {}
        for ps in osdmap.pgs_of_pool(self.served.pool_id):
            _, acting, _ = osdmap.pg_to_up_acting(self.served.pool_id,
                                                  ps)
            out[ps] = list(acting)
        return out

    # -- the window --------------------------------------------------------
    def _dirty(self) -> set[int]:
        """The PGs that are not clean, by ``MiniCluster``'s own rules
        (``_dirty_pgs``): a PG is clean when its primary has it, and
        every PG object of it is active, on the map's acting set, with
        nothing missing on any shard. The map's acting sets are worked
        out once an epoch: a CRUSH mapping of every PG at every poll
        would take the interpreter lock from the recovery it times."""
        cluster, pool = self.served.cluster, self.served.pool_id
        osdmap = cluster.mon.osdmap
        if osdmap.epoch != self._epoch:
            self._epoch, self._acting = osdmap.epoch, self._holders()
        dirty = set()
        osds = list(cluster.osds.values())
        for ps, acting in self._acting.items():
            primary = next((o for o in acting if o >= 0), -1)
            posd = cluster.osds.get(primary)
            if posd is not None and (pool, ps) not in posd.pgs:
                dirty.add(ps)
                continue
            for osd in osds:
                pg = osd.pgs.get((pool, ps))
                if pg is None:
                    continue
                if pg.state != pg.ACTIVE or list(pg.acting) != acting \
                        or pg.missing_dirty():
                    dirty.add(ps)
                    break
        return dirty

    def _poll(self, t_start: float, out: dict) -> None:
        poll_s = self.mix["poll_s"]
        deadline = t_start + self.mix["clean_timeout_s"]
        clean_at: dict[int, float] = {}
        try:
            while True:
                now = time.monotonic()
                dirty = self._dirty()
                for ps in self._acting:
                    if ps in dirty:
                        clean_at.pop(ps, None)
                    else:
                        clean_at.setdefault(ps, now)
                if not dirty:
                    out["t_end"], out["clean_at"] = now, clean_at
                    return
                if now > deadline:
                    out["error"] = TimeoutError(
                        f"{len(dirty)} PGs not clean "
                        f"{self.mix['clean_timeout_s']} s after the "
                        f"OSDs were marked down: {sorted(dirty)[:8]}")
                    return
                time.sleep(max(0.0, now + poll_s - time.monotonic()))
        except Exception as exc:     # reported by the caller's thread
            out["error"] = exc

    def run(self, seconds: float, during=None) -> tuple[dict, list]:
        """The window opens here: the map has just marked the victims
        down (``prepare``'s last step). ``seconds`` only places the
        traced sub-window."""
        del seconds
        served = self.served
        t_start = time.monotonic()
        out: dict = {}
        poller = threading.Thread(target=self._poll,
                                  args=(t_start, out),
                                  name="bench-recovery-poll")
        poller.start()
        try:
            if during is not None:
                during(t_start)
        finally:
            poller.join()
        if "error" in out:
            raise out["error"]
        # the program's own word for it
        served.settle(timeout=self.mix["clean_timeout_s"])
        confirm_s = time.monotonic() - out["t_end"]
        window_s = max(out["t_end"] - t_start, 1e-9)
        rebuilt = self._rebuilt()
        osdmap = served.cluster.mon.osdmap
        moved_of = {name: rebuilt.get(osdmap.object_to_pg(
            served.pool_id, name), {}).get("moved", [])
            for name in served.preloaded}
        pg_s = sorted(out["clean_at"][ps] - t_start
                      for ps, pgr in rebuilt.items() if pgr["bytes"])
        nbytes = sum(pgr["bytes"] for pgr in rebuilt.values())
        self.check_names = served.preloaded
        summary = {
            "attempted": sum(p["expected"] for p in rebuilt.values()),
            "failed": sum(max(0, p["expected"] - p["shards"])
                          for p in rebuilt.values()),
            "window_s": window_s,
            "MBps": nbytes / window_s / 1e6,
            "latencies_ms": [s * 1e3 for s in pg_s],
            "errors": [],
            "rebuilt_bytes": nbytes,
            "rebuilt_shards": sum(p["shards"]
                                  for p in rebuilt.values()),
            "pgs_rebuilt": len(pg_s),
            "positions_unmapped": sum(p["unmapped"]
                                      for p in rebuilt.values()),
            "pg_clean_s": pg_s,
            "confirm_s": confirm_s,
            "absent": [a for p in rebuilt.values()
                       for a in p["absent"]][:8],
            #: preloaded object -> the positions recovery rebuilt
            "rebuilt_positions": moved_of}
        return summary, []

    def _rebuilt(self) -> dict[int, dict]:
        """PG -> what the stores hold now at the positions whose
        holder changed between the map before the kill and the map at
        clean: shards and bytes, and the shards that have to be there
        (the objects a position of the PG that did not move holds)."""
        from ceph_tpu.osd.pg import pg_cid
        served, cluster = self.served, self.served.cluster
        out = {}
        for ps, after in self._holders().items():
            before = self._before[ps]
            changed = [pos for pos, osd in enumerate(after)
                       if osd != before[pos]]
            moved = [pos for pos in changed if after[pos] >= 0]
            if not changed:
                continue
            stayed = next(pos for pos, osd in enumerate(after)
                          if pos not in changed and osd in cluster.osds)
            names = {n for n in served.held(ps, stayed, after[stayed])
                     if not n.startswith("_")}      # not the PG's meta
            got = {"shards": 0, "bytes": 0, "moved": moved,
                   "unmapped": len(changed) - len(moved),
                   "expected": len(names) * len(moved), "absent": []}
            for pos in moved:
                have = served.held(ps, pos, after[pos])
                got["absent"] += [f"{n}@{pos}" for n in names - have]
                for name in names & have:
                    got["shards"] += 1
                    got["bytes"] += cluster.osds[after[pos]].store.stat(
                        pg_cid(served.pool_id, ps, pos), name)
            out[ps] = got
        return out

    # -- what the window says ----------------------------------------------
    def values(self, summary: dict) -> dict:
        reports = self.mix["reports"]
        values = {reports["throughput"]: summary["MBps"]}
        if summary["pg_clean_s"]:
            values[reports["tail"]["name"]] = quantile(
                summary["pg_clean_s"], reports["tail"]["quantile"])
        return values

    def judge_ops(self, cmp, summary: dict, ops: list,
                  observed: list) -> None:
        del ops
        compared = sum(
            1 for obs in observed
            for pos in summary["rebuilt_positions"].get(obs["name"], ())
            if pos in obs["shards"])
        cmp.at_most(self.mix["reports"]["length"], summary["window_s"],
                    self.mix["clean_timeout_s"])
        cmp.at_least("shards_rebuilt", summary["rebuilt_shards"], 1)
        cmp.at_most("shards_not_rebuilt", summary["failed"])
        cmp.at_least("rebuilt_shards_compared", compared, 1)

    def judge_route(self, cmp, grown: dict) -> None:
        cmp.at_most("decode_fallbacks", grown["decode_fallbacks"])
        cmp.at_least("decode_flushes",
                     grown["engine"].get("decode_flushes", 0), 1)
