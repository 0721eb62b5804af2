"""The one traffic generator: seeded payloads and a closed loop.

A traffic file (``traffic/<name>.json``, keys in ``spec.TRAFFIC_KEYS``)
says what the clients do; this file does it. ``rados bench`` is a
closed loop and so is this: each of ``clients`` threads sends its next
op when the last one is acknowledged. Payloads are made from the seed
during set-up, in one bulk draw, and an object's bytes are a function of
its name: the generator shares the interpreter lock with the daemons,
and drawing 4 MiB per op inside the window would be measured as server
time.
"""

from __future__ import annotations

import math
import threading
import time
import zlib

import numpy as np


def seed_words(seed: int) -> list[int]:
    """``--seed`` may be any whole number a little over 2**31."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed is a whole number >= 0")
    return [seed & 0xFFFFFFFF, seed >> 32]


class Payloads:
    """``pool`` distinct buffers of ``object_bytes`` from the seed; the
    buffer of ``<prefix>_<i>`` is buffer ``(i + h(prefix)) % pool``."""

    def __init__(self, seed: int, object_bytes: int, pool: int) -> None:
        rng = np.random.default_rng(seed_words(seed) + [0xEC])
        blob = rng.bytes(object_bytes * pool)
        self.buffers = [blob[i * object_bytes:(i + 1) * object_bytes]
                        for i in range(pool)]
        self._salt = zlib.crc32(repr(seed_words(seed)).encode())

    def of(self, name: str) -> bytes:
        prefix, _, num = name.rpartition("_")
        idx = int(num) + zlib.crc32(prefix.encode(), self._salt)
        return self.buffers[idx % len(self.buffers)]


class OpRecord:
    """One op of the window."""

    __slots__ = ("name", "start", "end", "ok", "equal", "error")

    def __init__(self, name: str, start: float) -> None:
        self.name = name
        self.start = start
        self.end = start
        self.ok = False
        #: reads: the bytes that came back equal the seeded ones
        self.equal: bool | None = None
        self.error = ""


class ClosedLoop:
    """``clients`` threads, one op in flight each, for ``seconds``.
    No op starts after the deadline; those in flight are waited for,
    and the window lasts until the last of them is acknowledged."""

    def __init__(self, io, mix: dict, payloads: Payloads, seed: int,
                 read_names: list[str] | None = None,
                 degraded_names: list[str] | None = None) -> None:
        self.io = io
        self.mix = mix
        self.payloads = payloads
        self.seed = seed
        #: reads draw from ``degraded_names`` (objects that lack a data
        #: shard) with probability ``degraded_share`` and from
        #: ``read_names`` otherwise: every seed sends the same mix of
        #: work, whatever share of the objects its dead OSDs degraded
        self.read_names = read_names or []
        self.degraded_names = degraded_names or []
        share = mix["degraded_share"] if mix["osds_down"] else 0.0
        if mix["op"] == "read":
            if share > 0 and not self.degraded_names:
                raise ValueError("degraded_share > 0 and no object "
                                 "lacks a data shard")
            if share < 1 and not self.read_names:
                raise ValueError("no object to read")
        self._share = share
        self.records: list[list[OpRecord]] = [
            [] for _ in range(mix["clients"])]
        self.t_start = 0.0
        self.t_end = 0.0
        self._written = 0
        self._lock = threading.Lock()
        self.overflow = False

    def _client(self, tid: int, deadline: float) -> None:
        mix, io, out = self.mix, self.io, self.records[tid]
        writing = mix["op"] == "write_full"
        rng = np.random.default_rng(seed_words(self.seed) + [7, tid])
        i = 0
        while time.monotonic() < deadline:
            if writing:
                with self._lock:
                    if self._written >= mix["max_objects"]:
                        self.overflow = True
                        return
                    self._written += 1
                name = f"w{tid}_{i}"
                data = self.payloads.of(name)
            else:
                names = self.degraded_names \
                    if rng.random() < self._share else self.read_names
                name = names[int(rng.integers(len(names)))]
                data = None
            i += 1
            rec = OpRecord(name, time.monotonic())
            try:
                if writing:
                    io.write_full(name, data)
                    rec.end = time.monotonic()
                else:
                    got = io.read(name)
                    rec.end = time.monotonic()
                    rec.equal = got == self.payloads.of(name)
                rec.ok = True
            except Exception as exc:    # the op failed: it is counted
                rec.end = time.monotonic()
                rec.error = repr(exc)[:200]
            out.append(rec)

    def run(self, seconds: float, during=None) -> None:
        """Run the window. ``during(t_start)`` runs on the caller's
        thread while the clients work (the traced sub-window)."""
        self.t_start = time.monotonic()
        deadline = self.t_start + seconds
        threads = [threading.Thread(target=self._client,
                                    args=(tid, deadline),
                                    name=f"bench-client-{tid}")
                   for tid in range(self.mix["clients"])]
        for th in threads:
            th.start()
        try:
            if during is not None:
                during(self.t_start)
        finally:
            for th in threads:
                th.join()
        ends = [r.end for recs in self.records for r in recs]
        self.t_end = max(ends) if ends else time.monotonic()

    # -- what the window did ---------------------------------------------
    def ops(self) -> list[OpRecord]:
        return [r for recs in self.records for r in recs]

    def summary(self) -> dict:
        ops = self.ops()
        good = [r for r in ops if r.ok]
        window_s = max(self.t_end - self.t_start, 1e-9)
        lat_ms = sorted((r.end - r.start) * 1e3 for r in good)
        return {"attempted": len(ops),
                "failed": len(ops) - len(good),
                "window_s": window_s,
                "MBps": len(good) * self.mix["object_bytes"]
                / window_s / 1e6,
                "latencies_ms": lat_ms,
                "errors": [r.error for r in ops if not r.ok][:4]}


def quantile(sorted_values: list[float], q: float) -> float:
    """The smallest value with at least ``q`` of the samples at or
    below it."""
    if not sorted_values:
        raise ValueError("no sample")
    idx = max(0, math.ceil(q * len(sorted_values) - 1e-9) - 1)
    return sorted_values[min(idx, len(sorted_values) - 1)]
