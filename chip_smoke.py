#!/usr/bin/env python3
"""chip_smoke.py - the served EC path, end to end, on the chip.

    python chip_smoke.py [--seed N]        one chip: the served pool
    python chip_smoke.py --chips 4         four chips: the mesh route only

One process (mon + 12 OSDs + client threads in-process, every OSD
attached to the one shared device engine) drives a ``jerasure k=8 m=3
backend=pallas`` pool through the normal entry points - ``MiniCluster``,
``create_ec_pool``, ``RadosClient.open_ioctx``, ``write_full``/``read``
- with ``rados bench``'s default traffic (4 MiB objects, 16 writers,
256 objects = 1 GiB of seeded data): warm, write, read back, host
oracle on sampled shards and crcs, degraded read with two OSDs down,
recovery, and a second write pass that must compile nothing it has
compiled before. The engine's own counters decide the outcome: any
host-routed flush in the write phase, any fused fallback, any encode
or decode error, any signature compiled twice, or any unequal
comparison fails the run.

Every phase prints one JSON line. On success the LAST line of standard
output is the one object

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and nothing else follows it: the script keeps a private duplicate of
fd 1 for its own lines and points fd 1 at stderr before JAX is
imported, so none of the process's daemon threads, nor the runtime's
teardown, can write after it. Without an accelerator the script exits
non-zero and prints no result. The rates on the phase lines are
information, not benchmark results.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import sys
import time
import traceback

POOL = "smoke"


@dataclasses.dataclass(frozen=True)
class Size:
    """The deployment and traffic of the one-chip run. The defaults
    are the size the chip run uses; tests pass a smaller one."""

    n_osds: int = 12
    k: int = 8
    m: int = 3
    pg_num: int = 64
    backend: str = "pallas"
    obj_bytes: int = 4 << 20
    n_objects: int = 256
    writers: int = 16
    #: objects sampled for the host oracle
    n_oracle: int = 8
    #: objects written while two OSDs are down, and again in the
    #: second write pass
    n_extra: int = 32
    #: a cold compile must slow an op, never fail it
    op_timeout: float = 600.0
    clean_timeout: float = 600.0
    #: ``osd_heartbeat_grace`` of this deployment (set around the run,
    #: restored after it). 12 daemons and 16 clients of 4 MiB ops live
    #: under ONE interpreter lock here: at the repo's 4 s default,
    #: scaled for small test clusters, beacons miss the mon's 2 x 4 s
    #: window when recovery starts and the mon marks healthy OSDs
    #: down. 20 s is upstream's default; failure detection of the
    #: killed OSDs waits that long.
    heartbeat_grace: float = 20.0


class Out:
    """The script's own standard output. fd 1 is duplicated for the
    script's lines and then pointed at stderr, so whatever else writes
    to standard output in this process (daemon threads, a CLI helper,
    the runtime's C code at teardown) lands on stderr instead of after
    the last line."""

    def __init__(self) -> None:
        sys.stdout.flush()
        self._real = os.dup(1)
        os.dup2(2, 1)
        self._py_stdout = sys.stdout
        sys.stdout = sys.stderr
        self._file = os.fdopen(os.dup(self._real), "w")

    def line(self, obj: dict) -> None:
        self._file.write(json.dumps(obj) + "\n")
        self._file.flush()

    def close(self) -> None:
        """Done printing; fd 1 stays on stderr for the rest of the
        process's life."""
        self._file.close()

    def restore(self) -> None:
        """Put fd 1 and ``sys.stdout`` back (in-process callers)."""
        self.close()
        os.dup2(self._real, 1)
        os.close(self._real)
        sys.stdout = self._py_stdout


def accelerator(chips: int) -> dict:
    """The device as JAX reports it; raises unless it is ``chips``
    TPU chips' worth (one chip: at least one)."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"JAX found no accelerator (platform {dev.platform!r})")
    if chips > 1 and len(devices) != chips:
        raise RuntimeError(
            f"--chips {chips} needs {chips} devices, JAX reports "
            f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def final_line(device: dict) -> dict:
    """The contract's object: these keys and no others."""
    return {"ok": True,
            "device": {"platform": device["platform"],
                       "kind": device["kind"],
                       "count": device["count"]}}


class Phases:
    """Collects failed checks and prints one line per phase."""

    def __init__(self, out: Out) -> None:
        self.out = out
        self.failed: list[str] = []
        self._phase_failed: list[str] = []

    def check(self, name: str, ok: bool, detail=None) -> None:
        if not ok:
            entry = name if detail is None else f"{name}: {detail}"
            self.failed.append(entry)
            self._phase_failed.append(entry)

    def done(self, phase: str, t0: float, **info) -> None:
        self.out.line({"phase": phase,
                       "seconds": round(time.monotonic() - t0, 2),
                       "failed": self._phase_failed, **info})
        self._phase_failed = []


def payload(seed: int, name: str, nbytes: int) -> bytes:
    """Object ``name``'s bytes: a pure function of the seed, made
    again for every comparison instead of being kept."""
    import zlib

    import numpy as np
    rng = np.random.default_rng([seed, zlib.crc32(name.encode())])
    return rng.bytes(nbytes)


def _gbps(nbytes: int, seconds: float) -> float:
    return round(nbytes / max(seconds, 1e-9) / 1e9, 3)


# -- the one-chip run --------------------------------------------------

class ServedPool:
    """One MiniCluster with the smoke pool, and the helpers the
    phases share."""

    def __init__(self, seed: int, size: Size, cluster) -> None:
        from ceph_tpu.models import registry as ec_registry
        from ceph_tpu.osd.ec_util import StripeInfo
        self.seed = seed
        self.size = size
        self.cluster = cluster
        cluster.create_ec_pool(POOL, k=size.k, m=size.m,
                               plugin="jerasure", pg_num=size.pg_num,
                               backend=size.backend)
        self.rados = cluster.client()
        self.io = self.rados.open_ioctx(POOL)
        self.io.op_timeout = size.op_timeout
        osdmap = cluster.mon.osdmap
        self.pool_id = osdmap.pool_by_name[POOL]
        stripe_unit = osdmap.pools[self.pool_id].stripe_unit
        self.sinfo = StripeInfo(stripe_width=size.k * stripe_unit,
                                chunk_size=stripe_unit)
        #: the independent reference: the numpy GF(2^8) codec
        self.oracle = ec_registry.instance().factory(
            "jerasure", {"plugin": "jerasure", "k": str(size.k),
                         "m": str(size.m), "backend": "numpy"})
        self.written: list[str] = []

    # engine + telemetry views ----------------------------------------
    def engine_stats(self) -> dict:
        """The shared engine's counters (one engine, N handles)."""
        engines = {}
        for osd in self.cluster.osds.values():
            handle = osd.device_engine()
            engines[id(getattr(handle, "engine", handle))] = handle
        if len(engines) != 1:
            raise RuntimeError(
                f"{len(engines)} device engines, expected the one "
                "shared engine")
        stats = dict(next(iter(engines.values())).stats)
        stats.pop("per_slot_flushes", None)
        return stats

    def stats_since(self, before: dict) -> tuple[dict, dict]:
        """(counters now, their growth since ``before``)."""
        now = self.engine_stats()
        return now, {key: now[key] - before[key] for key in now}

    @staticmethod
    def compiles() -> dict:
        """signature -> times compiled in this process (the
        ``device perf dump`` table)."""
        from ceph_tpu.utils.device_telemetry import telemetry
        table = telemetry().snapshot()["compiles_by_signature"]
        return {sig: ent["compiles"] for sig, ent in table.items()}

    # traffic -----------------------------------------------------------
    def data(self, name: str) -> bytes:
        return payload(self.seed, name, self.size.obj_bytes)

    def write(self, names: list[str], writers: int | None = None
              ) -> None:
        def one(name: str) -> None:
            self.io.write_full(name, self.data(name))
        with concurrent.futures.ThreadPoolExecutor(
                writers or self.size.writers) as pool:
            list(pool.map(one, names))
        self.written.extend(names)

    def read_unequal(self, names: list[str]) -> list[str]:
        """Read every object; names whose bytes differ from the
        seeded data."""
        def one(name: str) -> bool:
            return self.io.read(name) == self.data(name)
        with concurrent.futures.ThreadPoolExecutor(
                self.size.writers) as pool:
            same = list(pool.map(one, names))
        return [n for n, ok in zip(names, same) if not ok]

    # the host oracle -----------------------------------------------------
    def oracle_unequal(self, name: str, only_osds=None) -> list[str]:
        """Compare object ``name``'s shards AS THE OSD STORES HOLD
        THEM, and each shard's stored crc, with a host re-encode by
        the numpy codec and ``utils.checksum.crc32c``. Returns what
        differs. ``only_osds`` restricts to shards held by those
        OSDs."""
        import numpy as np

        from ceph_tpu.osd import ec_util
        from ceph_tpu.osd.pg import pg_cid
        from ceph_tpu.utils import checksum
        osdmap = self.cluster.mon.osdmap
        ps, acting, _primary = osdmap.object_locator(self.pool_id,
                                                     name)
        raw = self.data(name)
        sw = self.sinfo.stripe_width
        raw += b"\x00" * (-len(raw) % sw)
        want = ec_util.encode(self.sinfo, self.oracle,
                              np.frombuffer(raw, dtype=np.uint8))
        bad = []
        for pos, osd_id in enumerate(acting):
            if only_osds is not None and osd_id not in only_osds:
                continue
            osd = self.cluster.osds.get(osd_id)
            if osd is None:
                bad.append(f"{name} shard {pos}: osd.{osd_id} absent")
                continue
            cid = pg_cid(self.pool_id, ps, pos)
            shard = want[pos].tobytes()
            if bytes(osd.store.read(cid, name)) != shard:
                bad.append(f"{name} shard {pos} on osd.{osd_id}: "
                           "bytes differ from the host encode")
            hinfo = json.loads(osd.store.getattr(cid, name, "hinfo"))
            crc = checksum.crc32c(shard, ec_util.HINFO_SEED)
            if hinfo["hashes"][pos] != crc:
                bad.append(f"{name} shard {pos} on osd.{osd_id}: "
                           f"stored crc {hinfo['hashes'][pos]:#x} != "
                           f"host crc32c {crc:#x}")
        return bad

    def primaries_without_device(self) -> tuple[int, list[str]]:
        """(primaries looked at, those whose ECBackend has no device
        engine)."""
        osdmap = self.cluster.mon.osdmap
        seen, missing = 0, []
        for ps in osdmap.pgs_of_pool(self.pool_id):
            _, _, primary = osdmap.pg_to_up_acting(self.pool_id, ps)
            osd = self.cluster.osds.get(primary)
            pg = osd.pgs.get((self.pool_id, ps)) if osd else None
            if pg is None:
                continue
            seen += 1
            if getattr(pg.backend, "device", None) is None:
                missing.append(f"pg {self.pool_id}.{ps} on "
                               f"osd.{primary}")
        return seen, missing


def run_one_chip(seed: int, size: Size, ph: Phases) -> None:
    from ceph_tpu.ops import backend as backend_mod
    from ceph_tpu.qa.cluster import MiniCluster
    from ceph_tpu.utils.config import g_conf

    host_backends = [b for b in backend_mod.available_backends()
                     if b in ("native", "numpy")]
    ph.out.line({"host_backend": host_backends[0],
                 "backends": backend_mod.available_backends()})
    conf = g_conf()
    grace = conf["osd_heartbeat_grace"]
    conf.set("osd_heartbeat_grace", size.heartbeat_grace)
    try:
        with MiniCluster(n_osds=size.n_osds) as cluster:
            serve(seed, size, ph, cluster)
    finally:
        conf.set("osd_heartbeat_grace", grace)


def serve(seed: int, size: Size, ph: Phases, cluster) -> None:
    """The phases of the one-chip run on a started cluster."""
    import numpy as np

    from ceph_tpu.utils.device_telemetry import telemetry

    obj = size.obj_bytes
    sp = ServedPool(seed, size, cluster)

    # 1. warm: walk the shape buckets (1..writers concurrent ops)
    t0 = time.monotonic()
    burst, bursts = 1, []
    while burst < size.writers:
        bursts.append(burst)
        burst *= 2
    bursts += [size.writers, size.writers]
    for i, burst in enumerate(bursts):
        sp.write([f"warm_{i}_{j}" for j in range(burst)],
                 writers=burst)
    brief = telemetry().snapshot_brief()
    ph.done("warm", t0, compiles=brief.get("compiles", 0),
            compile_seconds=brief.get("compile_time_s", 0.0),
            signatures=sorted(sp.compiles()))

    # 2. write
    names = [f"obj_{i}" for i in range(size.n_objects)]
    before = sp.engine_stats()
    t0 = time.monotonic()
    sp.write(names)
    dt = time.monotonic() - t0
    after, d = sp.stats_since(before)
    ph.check("write: device flushes > 0", d["flushes"] > 0, d)
    ph.check("write: device bytes >= bytes written",
             d["bytes"] >= size.n_objects * obj, d)
    ph.check("write: host_flushes == 0", d["host_flushes"] == 0,
             d)
    seen, missing = sp.primaries_without_device()
    ph.check("write: every primary's ECBackend.device is set",
             seen > 0 and not missing, missing or "no primary")
    ph.done("write", t0, objects=size.n_objects,
            bytes=size.n_objects * obj,
            GBps_info=_gbps(size.n_objects * obj, dt),
            flushes=d["flushes"], ops=d["ops"],
            device_bytes=d["bytes"],
            host_flushes=d["host_flushes"],
            max_batch_ops=after["max_batch_ops"],
            primaries_checked=seen)

    # 3. read back
    t0 = time.monotonic()
    bad = sp.read_unequal(sp.written)
    dt = time.monotonic() - t0
    ph.check("read: every object equals its seeded data",
             not bad, bad[:8])
    ph.done("read", t0, objects=len(sp.written),
            GBps_info=_gbps(len(sp.written) * obj, dt))

    # 4. oracle: stored shards + crcs vs the numpy codec
    t0 = time.monotonic()
    rng = np.random.default_rng([seed, 4])
    sample = [names[i] for i in sorted(rng.choice(
        len(names), size=min(size.n_oracle, len(names)),
        replace=False))]
    bad = [b for name in sample for b in sp.oracle_unequal(name)]
    ph.check("oracle: stored shards and crcs equal the host "
             "encode", not bad, bad[:8])
    ph.done("oracle", t0, objects=sample,
            shards_compared=len(sample) * (size.k + size.m))

    # 5. degraded read: two OSDs down
    t0 = time.monotonic()
    victims = sorted(int(v) for v in np.random.default_rng(
        [seed, 5]).choice(size.n_osds, size=2, replace=False))
    epoch = cluster.epoch()
    for victim in victims:
        cluster.kill_osd(victim)
    for victim in victims:
        cluster.wait_for_osd_down(victim,
                                  timeout=size.clean_timeout)
    sp.rados.wait_for_epoch(epoch + 1, timeout=60)
    t_read = time.monotonic()
    before = sp.engine_stats()
    bad = sp.read_unequal(sp.written)
    dt = time.monotonic() - t_read
    after, d = sp.stats_since(before)
    ph.check("degraded: every object equals its seeded data",
             not bad, bad[:8])
    ph.check("degraded: decode_flushes > 0",
             d["decode_flushes"] > 0, d)
    ph.check("degraded: decode_errors == 0",
             d["decode_errors"] == 0, d)
    ph.done("degraded_read", t0, down=victims,
            seconds_to_detect=round(t_read - t0, 2),
            objects=len(sp.written),
            GBps_info=_gbps(len(sp.written) * obj, dt),
            decode_flushes=d["decode_flushes"],
            decode_ops=d["decode_ops"],
            decode_bytes=d["decode_bytes"],
            max_decode_batch_ops=after["max_decode_batch_ops"])

    # 6. recovery: write while degraded, revive, wait for clean
    t0 = time.monotonic()
    extra = [f"rec_{i}" for i in range(size.n_extra)]
    before = sp.engine_stats()
    sp.write(extra)
    for victim in victims:
        cluster.revive_osd(victim)
    cluster.wait_for_osds_up(timeout=size.clean_timeout)
    cluster.wait_for_clean(timeout=size.clean_timeout)
    t_clean = time.monotonic() - t0
    bad = sp.read_unequal(sp.written)
    ph.check("recovery: every object equals its seeded data",
             not bad, bad[:8])
    bad = [b for name in extra
           for b in sp.oracle_unequal(name, only_osds=victims)]
    ph.check("recovery: the revived OSDs' shards equal the host "
             "encode", not bad, bad[:8])
    _after, d = sp.stats_since(before)
    ph.done("recovery", t0, revived=victims,
            objects_written_degraded=len(extra),
            seconds_to_clean=round(t_clean, 2),
            decode_flushes=d["decode_flushes"],
            decode_ops=d["decode_ops"])

    # 7. second pass: nothing seen before compiles again
    t0 = time.monotonic()
    seen_before = sp.compiles()
    sp.write([f"again_{i}" for i in range(size.n_extra)])
    now = sp.compiles()
    twice = {s: n for s, n in now.items() if n > 1}
    ph.check("second pass: no signature compiled twice",
             not twice, twice)
    bad = sp.read_unequal(sp.written[-size.n_extra:])
    ph.check("second pass: every object equals its seeded data",
             not bad, bad[:8])
    ph.done("second_pass", t0,
            new_buckets=sorted(set(now) - set(seen_before)),
            signatures=len(now))

    # the whole run
    stats = sp.engine_stats()
    counters = telemetry().snapshot()["counters"]
    for key in ("device_fused_fallbacks", "errors",
                "decode_errors"):
        ph.check(f"run: {key} == 0", stats[key] == 0, stats[key])
    for key in ("fused_fallbacks", "engine_decode_fallbacks",
                "recompiles"):
        ph.check(f"run: device perf dump {key} == 0",
                 counters.get(key, 0) == 0, counters.get(key))
    ph.out.line({"engine_stats": stats,
                 "device_perf": telemetry().snapshot_brief(),
                 "compile_cache": compile_cache_report(
                     sp.compiles())})


def compile_cache_report(compiled: dict) -> dict:
    """Cold vs warm compile seconds of the signatures this process
    compiled, from the persistent cache's ledger: ``cold`` is the
    first-ever compile of a signature in this cache directory,
    ``warm`` the best later one the disk cache served."""
    from ceph_tpu.utils import compile_cache
    from ceph_tpu.utils.device_telemetry import telemetry
    ledger = compile_cache.ledger()
    mine = [ledger[sig] for sig in compiled if sig in ledger]
    brief = telemetry().snapshot_brief()
    warm = [e["warm_s"] for e in mine if "warm_s" in e]
    return {"dir": compile_cache.enabled_dir(),
            "signatures": len(compiled),
            "hits": brief.get("compile_cache_hits", 0),
            "misses": brief.get("compile_cache_misses", 0),
            "cold_seconds": round(sum(e.get("cold_s", 0.0)
                                      for e in mine), 3),
            "warm_seconds": round(sum(warm), 3) if warm else None}


# -- the four-chip run: the mesh route and its oracle, nothing else ------

def run_mesh(seed: int, ph: Phases) -> None:
    import jax
    import numpy as np

    from ceph_tpu.models import registry as ec_registry
    from ceph_tpu.ops import gf256
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    from ceph_tpu.parallel import mesh as mesh_mod
    from ceph_tpu.parallel import sharded_codec

    #: the served pool's profile and stripe unit, on a 64 MiB batch
    k, m, chunk, batch_bytes = 8, 3, 4096, 64 << 20
    devices = jax.devices()
    mesh = mesh_mod.make_mesh(devices=devices, chunk_count=k + m)
    profile = {"plugin": "jerasure", "k": str(k), "m": str(m)}
    oracle = ec_registry.instance().factory(
        "jerasure", {**profile, "backend": "numpy"})
    mat = np.asarray(oracle.coding_matrix, dtype=np.uint8)
    sinfo = ec_util.StripeInfo(stripe_width=k * chunk,
                               chunk_size=chunk)
    n_stripes = batch_bytes // sinfo.stripe_width
    rng = np.random.default_rng([seed, 40])
    data = rng.integers(0, 256, size=(n_stripes, k, chunk),
                        dtype=np.uint8)
    want = ec_util.encode(sinfo, oracle, data.reshape(-1))

    def spans(arr) -> int:
        return len({s.device for s in arr.addressable_shards})

    # sharded encode step
    t0 = time.monotonic()
    step = sharded_codec.make_encode_step(mesh, mat, place=False)
    batch_dev = sharded_codec.shard_stripe_batch(mesh, data)
    chunks_dev, _csum = step(batch_dev)
    chunks_dev.block_until_ready()
    ph.check("mesh encode: batch and result span every device",
             spans(batch_dev) == spans(chunks_dev) == len(devices),
             (spans(batch_dev), spans(chunks_dev)))
    chunks = np.asarray(chunks_dev)
    bad = [i for i in range(k + m) if not np.array_equal(
        chunks[:, i, :].reshape(-1), want[i])]
    ph.check("mesh encode: chunks equal the host encode", not bad,
             bad)
    ph.done("mesh_encode", t0, mesh=dict(mesh.shape),
            compile_path=step.compile_path, bytes=int(data.nbytes),
            devices_spanned=spans(chunks_dev))

    # sharded degraded-read step: two data chunks lost
    t0 = time.monotonic()
    lost = [1, 5]
    present = [i for i in range(k + m) if i not in lost][:k]
    step = sharded_codec.make_degraded_read_step(
        mesh, gf256.systematic_generator(mat), present, lost)
    survivors = np.stack(
        [want[i].reshape(n_stripes, chunk) for i in present], axis=1)
    rec_dev, _full = step(
        sharded_codec.shard_stripe_batch(mesh, survivors))
    rec_dev.block_until_ready()
    ph.check("mesh degraded read: result spans every device",
             spans(rec_dev) == len(devices), spans(rec_dev))
    rec = np.asarray(rec_dev)
    bad = [c for j, c in enumerate(lost) if not np.array_equal(
        rec[:, j, :].reshape(-1), want[c])]
    ph.check("mesh degraded read: chunks equal the lost data", not bad,
             bad)
    ph.done("mesh_degraded_read", t0, lost=lost, present=present,
            devices_spanned=spans(rec_dev))

    # one engine flush above mesh_flush_bytes
    t0 = time.monotonic()
    codec = ec_registry.instance().factory(
        "jerasure", {**profile, "backend": "pallas"})
    ops = [np.ascontiguousarray(
        data[i * 128:(i + 1) * 128].reshape(-1)) for i in range(4)]
    got: dict[int, tuple] = {}
    done = concurrent.futures.Future()

    def cont(i: int):
        def fn(shards, _crcs, err) -> None:
            got[i] = (shards, err)
            if len(got) == len(ops):
                done.set_result(None)
        return fn

    # every op alone is above mesh_flush_bytes, so however the engine
    # batches them, each flush must take the mesh route
    engine = DeviceEncodeEngine(lambda _key, fn: fn())
    mesh_mod.set_default_mesh(mesh)
    try:
        for i, buf in enumerate(ops):
            engine.stage_encode(("smoke", i), codec, sinfo, buf,
                                cont(i))
        done.result(timeout=600)
    finally:
        mesh_mod.set_default_mesh(None)
        engine.stop()
    stats = dict(engine.stats)
    ph.check("engine: the flush took the mesh route",
             stats["mesh_flushes"] >= 1
             and stats["mesh_flushes"] == stats["flushes"], stats)
    ph.check("engine: no fallback, no error",
             stats["device_fused_fallbacks"] == 0
             and stats["errors"] == 0, stats)
    bad = []
    for i, buf in enumerate(ops):
        shards, err = got[i]
        ref = ec_util.encode(sinfo, oracle, buf)
        if shards is None or any(
                not np.array_equal(shards[c], ref[c])
                for c in range(k + m)):
            bad.append(f"op {i}: {err!r}")
    ph.check("engine: mesh-flushed shards equal the host encode",
             not bad, bad)
    ph.done("mesh_engine_flush", t0, ops=len(ops),
            bytes=sum(b.nbytes for b in ops),
            mesh_flushes=stats["mesh_flushes"],
            flushes=stats["flushes"])


# -- entry ---------------------------------------------------------------

def run(seed: int, chips: int, out: Out, size: Size = Size()) -> int:
    """Run the phases, print their lines through ``out`` and, only if
    every check held, the final line. Returns the exit code."""
    ph = Phases(out)
    try:
        device = accelerator(chips)
    except Exception as exc:
        # no accelerator (or a runtime that cannot start): no result
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 2
    try:
        out.line({"device": device, "seed": seed, "chips": chips})
        if chips > 1:
            run_mesh(seed, ph)
        else:
            run_one_chip(seed, size, ph)
    except Exception as exc:
        traceback.print_exc(file=sys.stderr)
        ph.check("run: no exception", False, repr(exc))
    # the cluster is stopped and joined here; nothing of ours prints
    # after the line below
    if ph.failed:
        out.line({"failed": ph.failed})
        return 1
    out.line(final_line(device))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    out = Out()          # before JAX is imported
    try:
        return run(args.seed, args.chips, out)
    finally:
        out.close()


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: ~60 daemon threads and the runtime's
    # own exit hooks have nothing left to say that belongs on stdout
    os._exit(code)
