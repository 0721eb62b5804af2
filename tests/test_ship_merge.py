"""Group commit at the engine's ship thread: overwrite flush groups that
are already ready when a ship starts ship as one, with one ``ship_fn``
call per bucket (one sub-write batch a peer), items in flush order.

Order and counts only, on the CPU: every op flushes alone
(``flush_bytes`` is one op) and defers its name into one or two
buckets; a ``plug`` op of its own full-write group holds the ship
thread inside its ship until the test lets it go, so the groups behind
it queue.
"""

import queue
import threading

import numpy as np
import pytest

from ceph_tpu.models import registry as ec_registry
from ceph_tpu.osd import device_engine
from ceph_tpu.osd.device_engine import (DeviceEncodeEngine, FlushGroup,
                                        ship_groups)
from ceph_tpu.osd.ec_util import StripeInfo

OP_BYTES = 2048


@pytest.fixture(autouse=True)
def _pin_device_route(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_HOST_FLUSH_BYTES", "0")


class _Rig:
    def __init__(self) -> None:
        self.codec = ec_registry.instance().factory(
            "jerasure", {"plugin": "jerasure", "k": "2", "m": "1",
                         "backend": "jax"})
        self.sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
        self.lock = threading.Lock()
        #: ("ship", bucket, [names]) per ship_fn call, ("ran", name)
        #: per barrier run, in the order they happened
        self.log: list = []
        self.groups: dict[str, FlushGroup] = {}
        #: op name -> gate its continuation waits for
        self.holds: dict[str, threading.Event] = {}
        self.plug_gate = threading.Event()
        self.plugged = threading.Event()
        self.shipped = threading.Condition(self.lock)
        #: key -> its FIFO, like the OSD's op-wq shards
        self._fifo: dict = {}
        self.eng = DeviceEncodeEngine(self._dispatch,
                                      flush_bytes=OP_BYTES, window=3)

    def _dispatch(self, key, fn) -> None:
        q = self._fifo.get(key)
        if q is None:
            q = self._fifo[key] = queue.SimpleQueue()
            threading.Thread(
                target=lambda: [f() for f in iter(q.get, None)],
                daemon=True, name=f"wq-{key}").start()
        q.put(fn)

    def _ship(self, bucket: str, items: list) -> None:
        if "plug" in items:
            self.plugged.set()
            assert self.plug_gate.wait(30)
        with self.lock:
            self.log.append(("ship", bucket, list(items)))
            self.shipped.notify_all()

    def write(self, pg: str, name: str, overwrite: bool = True,
              buckets=("peer",)) -> None:
        hold = self.holds.get(name)

        def cont(shards, crcs, err):
            assert err is None, err
            if hold is not None:
                assert hold.wait(30)
            group = device_engine.current_group()
            self.groups[name] = group
            for bucket in buckets:
                group.defer(bucket, lambda items, b=bucket:
                            self._ship(b, items), name)
        self.eng.stage_encode(pg, self.codec, self.sinfo,
                              np.zeros(OP_BYTES, dtype=np.uint8), cont,
                              overwrite=overwrite)

    def plug(self) -> None:
        """A full-write group whose ship holds the ship thread."""
        self.write("pgP", "plug", overwrite=False)
        assert self.plugged.wait(30)

    def barrier(self, pg: str, name: str) -> None:
        def fn():
            with self.lock:
                self.log.append(("ran", name))
                self.shipped.notify_all()
        self.eng.stage_barrier(pg, fn)

    def ready(self, names, timeout: float = 30.0) -> None:
        """Until every named op's group is ready to ship."""
        for _ in range(int(timeout / 0.01)):
            if all(n in self.groups and self.groups[n].ready.is_set()
                   for n in names):
                return
            threading.Event().wait(0.01)
        raise AssertionError(f"never ready: {names}")

    def until(self, n: int, timeout: float = 30.0) -> list:
        """The log once it holds ``n`` entries."""
        with self.lock:
            assert self.shipped.wait_for(lambda: len(self.log) >= n,
                                         timeout), self.log
            return list(self.log)

    def close(self) -> None:
        self.plug_gate.set()
        for gate in self.holds.values():
            gate.set()
        self.eng.stop()
        for q in self._fifo.values():
            q.put(None)


@pytest.fixture
def rig():
    r = _Rig()
    try:
        yield r
    finally:
        r.close()


def _ships(rig) -> tuple[int, int]:
    return rig.eng.stats["ships"], rig.eng.stats["ship_groups"]


def test_ready_overwrite_groups_ship_once_a_bucket_in_flush_order(rig):
    rig.plug()
    for pg, name in (("pgA", "o1"), ("pgB", "o2"), ("pgA", "o3")):
        rig.write(pg, name, buckets=("peer1", "local"))
    rig.ready(["o1", "o2", "o3"])
    rig.plug_gate.set()
    log = rig.until(3)
    assert log == [("ship", "peer", ["plug"]),
                   ("ship", "peer1", ["o1", "o2", "o3"]),
                   ("ship", "local", ["o1", "o2", "o3"])]
    assert all(rig.groups[n].event.is_set() for n in ("o1", "o2", "o3"))
    assert _ships(rig) == (2, 4)


def test_a_full_write_group_ships_alone_and_ends_the_merge(rig):
    rig.plug()
    rig.write("pgA", "o1")
    rig.write("pgB", "o2")
    rig.write("pgA", "f3", overwrite=False)
    rig.write("pgB", "o4")
    rig.write("pgC", "o5")
    rig.ready(["o1", "o2", "f3", "o4", "o5"])
    rig.plug_gate.set()
    assert [items for _s, _b, items in rig.until(4)] == [
        ["plug"], ["o1", "o2"], ["f3"], ["o4", "o5"]]
    assert _ships(rig) == (4, 6)


def test_a_group_that_is_not_ready_is_not_waited_for(rig):
    """The ready groups before it ship at once; it heads the next
    ship once it is ready, with the ready groups behind it."""
    rig.holds["o3"] = threading.Event()
    rig.plug()
    rig.write("pgA", "o1")
    rig.write("pgB", "o2")
    rig.write("pgC", "o3")                  # its continuation is held
    rig.write("pgD", "o4")
    rig.ready(["o1", "o2", "o4"])
    rig.plug_gate.set()
    assert rig.until(2)[1] == ("ship", "peer", ["o1", "o2"])
    threading.Event().wait(0.3)
    assert len(rig.log) == 2 and not rig.groups["o4"].event.is_set()
    rig.holds["o3"].set()
    assert rig.until(3)[2] == ("ship", "peer", ["o3", "o4"])
    assert _ships(rig) == (3, 5)


def test_merged_groups_run_their_callbacks_in_order_after_the_ship():
    """Group by group in flush order, each group is marked shipped and
    runs its after-flush callbacks in registration order, a callback
    registered while they run queued behind them, all after every
    item of the merged ship has gone."""
    groups = [FlushGroup(1, overwrite=True) for _ in range(3)]
    out: list = []
    for i, group in enumerate(groups):
        group.defer("peer", out.extend, f"item{i}")
        group.defer("local", out.extend, f"local{i}")
    g0, g1, g2 = groups

    def first():
        out.append(("g0 first", g0.event.is_set(), g1.event.is_set()))
        g0.after_flush(lambda: out.append("g0 registered by first"))
        g1.after_flush(lambda: out.append("g1 registered by first"))
    g0.after_flush(first)
    g0.after_flush(lambda: out.append("g0 second"))
    g1.after_flush(lambda: out.append("g1 first"))
    g2.after_flush(lambda: out.append("g2 first"))
    for group in groups:
        group.done()
    ship_groups(groups)
    assert out == ["item0", "item1", "item2", "local0", "local1",
                   "local2", ("g0 first", True, False), "g0 second",
                   "g0 registered by first", "g1 first",
                   "g1 registered by first", "g2 first"]
    g1.after_flush(lambda: out.append("late"))
    assert out[-1] == "late"                # shipped: runs at once


def test_a_barrier_fenced_on_a_merged_group_runs_after_the_merged_ship(
        rig):
    rig.plug()
    rig.write("pgA", "o1")
    rig.write("pgB", "o2")
    rig.barrier("pgB", "barrier")           # fenced on o2's group
    rig.write("pgB", "o3")                  # dispatched behind it
    rig.ready(["o1", "o2"])
    rig.plug_gate.set()
    assert rig.until(4) == [("ship", "peer", ["plug"]),
                            ("ship", "peer", ["o1", "o2"]),
                            ("ran", "barrier"),
                            ("ship", "peer", ["o3"])]


@pytest.mark.parametrize("overwrite", [False, True],
                         ids=["full_writes_queued", "one_at_a_time"])
def test_ships_count_one_group_each_when_nothing_merges(rig, overwrite):
    if not overwrite:                       # queued, ready, never merged
        rig.plug()
        for i in range(3):
            rig.write(f"pg{i}", f"f{i}", overwrite=False)
        rig.ready(["f0", "f1", "f2"])
        rig.plug_gate.set()
        assert [items for _s, _b, items in rig.until(4)] == [
            ["plug"], ["f0"], ["f1"], ["f2"]]
        assert _ships(rig) == (4, 4)
        return
    for i in range(3):                      # each shipped before the next
        rig.write(f"pg{i}", f"o{i}")
        rig.until(i + 1)
    assert [items for _s, _b, items in rig.log] == [["o0"], ["o1"],
                                                    ["o2"]]
    assert _ships(rig) == (3, 3)
