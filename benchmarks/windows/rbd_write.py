"""``op: rbd_write``: an RBD image on the EC pool, written at random in
small blocks (``rbd bench --io-type write --io-pattern rand``).

Set-up (``prepare``) makes the configuration's replicated pool and, in
it, the image (``deployment.image``) whose data pool is the cell's EC
pool, through the program's own ``services/rbd.py``; preloads every
data object of the image (``object_bytes``, the image's object size)
from the seed with ``write_full``, so that every write of the window
is a true read-modify-write of existing bytes, as on a filled volume;
and sends warm bursts of ``warm_bursts`` overwrites. It refuses a
program without the overwrite route at once: before the cluster starts,
one whose engine takes no ``overwrite`` op or whose RBD makes no image
with a data pool; in ``prepare``, one whose engine counts no
``overwrite_ops``. Such a program encodes an overwrite outside the
engine, and the cell holds every overwrite to the device.

The window: ``clients`` threads on ONE image handle in a closed loop,
each op ``Image.write`` of ``extent_bytes`` seeded bytes at a uniformly
random ``extent_bytes``-aligned offset of the image; no two ops are in
flight on one block, so the order of the acknowledgements is the
order of the writes to a block.

What the kind states about its window (``windows/base.py``): a data
object holds its preloaded bytes with every acknowledged extent (warm
bursts included) laid over them in ack order (``expected``); an object
written to keeps no ``hinfo``, one not written to keeps it
(``keeps_hinfo``); an engine op encodes the one stripe an aligned
extent lies in (``op_bytes``). The route: at least one encode flush,
and ``overwrite_ops`` grew by at least the overwrites acknowledged in
the window.
"""

from __future__ import annotations

import concurrent.futures
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
    }

    @staticmethod
    def check(mix: dict) -> str | None:
        extent, width = mix["extent_bytes"], mix["object_bytes"]
        if extent < 1 or width % extent:
            return "extent_bytes has to divide object_bytes"
        return None

    def __init__(self, served, mix: dict, seed: int) -> None:
        import inspect
        from ceph_tpu.osd.device_engine import DeviceEncodeEngine
        from ceph_tpu.services.rbd import RBD
        if "overwrite" not in inspect.signature(
                DeviceEncodeEngine.stage_encode).parameters or \
                "data_pool" not in inspect.signature(
                    RBD.create).parameters:
            raise RuntimeError(
                "this program has no overwrite route (its engine "
                "stages no overwrite, or its RBD image has no data "
                "pool): refused before the cluster starts")
        self.served = served
        self.mix = mix
        self.seed = seed
        self.check_names: list[str] = []
        self.read_back = True
        self.image = None
        #: data object -> index of its preloaded payload
        self._preload: dict[str, int] = {}
        #: (object, offset, bytes) of every acknowledged op, warm-up
        #: included, in ack order
        self.acked: list[tuple[str, int, bytes]] = []
        #: acknowledged ops of the window alone
        self.acked_in_window = 0
        self._busy: set[int] = set()
        self._lock = threading.Lock()

    # -- what the kind states about its window -----------------------------
    def expected(self, name: str) -> bytes:
        data = bytearray(self.served.payloads.of(
            f"rbdpre_{self._preload[name]}"))
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
        from ceph_tpu.client.striper import FileLayout
        from ceph_tpu.services.rbd import RBD
        served, mix = self.served, self.mix
        if "overwrite_ops" not in served.engine_stats():
            raise RuntimeError(
                "the device engine states no overwrite route (no "
                "overwrite_ops counter): this program encodes a range "
                "overwrite outside the engine")
        dep = served.config["deployment"]
        rep, img = dep["replicated_pool"], dep["image"]
        obj = mix["object_bytes"]
        if obj != 1 << img["order"] or img["size_bytes"] % obj:
            raise ValueError("object_bytes has to be the image's "
                             "object size (1 << order), and divide "
                             "its size")
        served.cluster.create_pool(rep["name"], pg_num=rep["pg_num"],
                                   size=rep["size"])
        header_io = served.rados.open_ioctx(rep["name"])
        header_io.op_timeout = mix["op_timeout_s"]
        self.image = RBD(header_io).create(
            img["name"], img["size_bytes"],
            layout=FileLayout(obj, img["stripe_count"], obj),
            data_pool=served.io.pool_name)
        self.image.data_io.op_timeout = mix["op_timeout_s"]
        names = [self.image._data._piece(i)
                 for i in range(img["size_bytes"] // obj)]
        self._preload = {name: i for i, name in enumerate(names)}
        with concurrent.futures.ThreadPoolExecutor(
                mix["clients"]) as pool:
            list(pool.map(lambda name: served.io.write_full(
                name, served.payloads.of(
                    f"rbdpre_{self._preload[name]}")), names))
        self.check_names = names
        note(phase="image_preloaded", objects=len(names))
        before = served.engine_stats()["overwrite_flushes"]
        rng = np.random.default_rng(seed_words(self.seed) + [12])
        for n in mix["warm_bursts"]:
            blocks = self._draw(rng, n)
            with concurrent.futures.ThreadPoolExecutor(n) as pool:
                list(pool.map(lambda b: self._write(b, rng_bytes(
                    self.seed, b, mix["extent_bytes"])), blocks))
        if served.engine_stats()["overwrite_flushes"] <= before:
            raise RuntimeError("the warm overwrites flushed nothing "
                               "through the overwrite route")
        note(phase="warm_overwrites", compiles=served.compiles(),
             compile_s=served.compile_seconds())

    def _draw(self, rng, n: int) -> list[int]:
        """``n`` distinct blocks of the image."""
        blocks = self.image.size() // self.mix["extent_bytes"]
        return [int(b) for b in rng.choice(blocks, size=n,
                                           replace=False)]

    def _write(self, block: int, data: bytes) -> None:
        """``Image.write`` of one block; its extent is recorded once
        acknowledged."""
        extent, obj = self.mix["extent_bytes"], self.mix["object_bytes"]
        self.image.write(block * extent, data)
        name = self.image._data._piece(block * extent // obj)
        with self._lock:
            self.acked.append((name, block * extent % obj, data))

    # -- the window --------------------------------------------------------
    def _client(self, tid: int, deadline: float,
                out: list[OpRecord]) -> None:
        extent = self.mix["extent_bytes"]
        blocks = self.image.size() // extent
        rng = np.random.default_rng(seed_words(self.seed) + [11, tid])
        while time.monotonic() < deadline:
            block = int(rng.integers(blocks))
            with self._lock:
                if block in self._busy:
                    continue
                self._busy.add(block)
            data = rng.bytes(extent)
            rec = OpRecord(str(block), time.monotonic())
            try:
                self._write(block, data)
                rec.end = time.monotonic()
                rec.ok = True
            except Exception as exc:    # the op failed: it is counted
                rec.end = time.monotonic()
                rec.error = repr(exc)[:200]
            finally:
                with self._lock:
                    self._busy.discard(block)
            out.append(rec)

    def run(self, seconds: float, during=None) -> tuple[dict, list]:
        mix = self.mix
        warm = len(self.acked)
        records: list[list[OpRecord]] = [[] for _ in
                                         range(mix["clients"])]
        t_start = time.monotonic()
        threads = [threading.Thread(
            target=self._client,
            args=(tid, t_start + seconds, records[tid]),
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
        self.acked_in_window = len(self.acked) - warm
        t_end = max((rec.end for rec in ops), default=time.monotonic())
        window_s = max(t_end - t_start, 1e-9)
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

    def judge_route(self, cmp, grown: dict) -> None:
        eng = grown["engine"]
        cmp.at_least("encode_flushes", eng.get("flushes", 0), 1)
        cmp.at_least("overwrite_ops", eng.get("overwrite_ops", 0),
                     self.acked_in_window)


def rng_bytes(seed: int, block: int, n: int) -> bytes:
    """A warm-up write's bytes: from the seed and the block."""
    return np.random.default_rng(
        seed_words(seed) + [13, block]).bytes(n)
