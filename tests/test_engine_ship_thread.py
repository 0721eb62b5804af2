"""A retired flush's deferred fan-out ships on the engine's own ship
thread, in flush order, and never on a continuation's thread.

It used to ship on the op-wq worker that ran the flush's last
continuation, each group from its predecessor's after-flush callbacks:
with the chain busy one worker shipped dozens of groups in one nested
cascade while its shard's queue waited (PERF.md section 6, PR 27).

Order and thread only, on the CPU: every op flushes alone
(``flush_bytes`` is one op), defers one item into its group and records
where and when the group ships it; the dispatcher holds the FIRST
flush's continuation for as long as the test wants.
"""

import queue
import threading
import time

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
    def __init__(self, fifo: bool = False) -> None:
        #: per-key FIFO threads like the OSD's op-wq shards (a key's
        #: dispatches run in dispatch order) instead of one thread a
        #: dispatch
        self._fifo: dict | None = {} if fifo else None
        self.ran: list = []              # names, in the order they ran
        self.codec = ec_registry.instance().factory(
            "jerasure", {"plugin": "jerasure", "k": "2", "m": "1",
                         "backend": "jax"})
        self.sinfo = StripeInfo(stripe_width=2 * 1024, chunk_size=1024)
        self.shipped: list = []          # (name, thread name)
        self.conts: list = []            # thread names of continuations
        self.events: dict[str, threading.Event] = {}
        self.gate = threading.Event()    # holds the FIRST wrapper
        self._held = False
        self.eng = DeviceEncodeEngine(self._dispatch,
                                      flush_bytes=OP_BYTES, window=3)

    def _dispatch(self, key, fn) -> None:
        first, self._held = not self._held, True

        def run():
            if first:
                assert self.gate.wait(30)
            fn()
        if self._fifo is None:
            threading.Thread(target=run, daemon=True,
                             name=f"wq-{key}").start()
            return
        q = self._fifo.get(key)
        if q is None:
            q = self._fifo[key] = queue.SimpleQueue()
            threading.Thread(
                target=lambda: [f() for f in iter(q.get, None)],
                daemon=True, name=f"wq-{key}").start()
        q.put(run)

    def _note(self, name: str) -> None:
        self.shipped.append((name, threading.current_thread().name))
        self.events.setdefault(name, threading.Event()).set()

    def wait(self, name: str, timeout: float) -> bool:
        return self.events.setdefault(
            name, threading.Event()).wait(timeout)

    def names(self) -> list:
        return [n for n, _t in self.shipped]

    def write(self, pg: str, name: str) -> None:
        def cont(shards, crcs, err):
            assert err is None, err
            self.conts.append(threading.current_thread().name)
            self.ran.append(name)
            device_engine.current_group().defer(
                "peer", lambda items: [self._note(n) for n in items],
                name)
        self.eng.stage_encode(pg, self.codec, self.sinfo,
                              np.zeros(OP_BYTES, dtype=np.uint8), cont)

    def barrier(self, pg: str, name: str) -> None:
        def fn():
            self.ran.append(name)
            self._note(name)
        self.eng.stage_barrier(pg, fn)

    def close(self) -> None:
        self.gate.set()
        self.eng.stop()
        for q in (self._fifo or {}).values():
            q.put(None)


@pytest.fixture
def rig():
    r = _Rig()
    try:
        yield r
    finally:
        r.close()


def test_groups_ship_on_the_ship_thread_not_on_a_continuations(rig):
    rig.gate.set()
    for i in range(4):
        rig.write(f"pg{i}", f"w{i}")
    assert rig.wait("w3", 30), rig.shipped
    assert rig.names() == ["w0", "w1", "w2", "w3"]
    assert {t for _n, t in rig.shipped} == {"ec-device-ship"}
    assert all(t.startswith("wq-") for t in rig.conts), rig.conts


def test_a_later_group_that_is_ready_first_still_ships_in_flush_order(
        rig):
    rig.write("pgA", "a")               # its continuation is held
    rig.write("pgB", "b")               # ready long before "a"
    rig.write("pgC", "c")
    assert not rig.wait("b", 0.5), rig.shipped
    assert rig.shipped == []
    rig.gate.set()
    assert rig.wait("c", 30), rig.shipped
    assert rig.names() == ["a", "b", "c"]


def test_an_open_group_holds_no_continuation_thread(rig):
    """While the first group is open, the later flushes'
    continuations have all run to their end: nothing waits on a
    dispatcher's thread for a predecessor to ship."""
    rig.write("pgA", "a")               # held
    rig.write("pgB", "b")
    rig.write("pgC", "c")
    for _ in range(100):
        if len(rig.conts) == 2:
            break
        time.sleep(0.05)
    assert sorted(rig.conts) == ["wq-pgB", "wq-pgC"]
    time.sleep(0.1)                     # past group.done()
    alive = [t.name for t in threading.enumerate()
             if t.name in ("wq-pgB", "wq-pgC")]
    assert alive == [], alive
    assert rig.shipped == []


def test_a_barrier_runs_after_the_last_group_shipped(rig):
    rig.write("pgA", "a")               # held: the group stays open
    rig.eng.stage_barrier("pgA", lambda: rig._note("barrier"))
    assert not rig.wait("barrier", 0.5), rig.shipped
    rig.gate.set()
    assert rig.wait("barrier", 30), rig.shipped
    assert rig.names() == ["a", "barrier"]


@pytest.mark.parametrize("ship_is_late", [True, False],
                         ids=["ship_groups_behind", "ship_prompt"])
def test_a_keys_continuations_stay_behind_its_barrier(ship_is_late):
    """Per-key order is submission order however far behind the ship
    thread is: a barrier waits for the last group's ship, and its
    key's continuations retired meanwhile are dispatched behind it,
    not past it; other keys do not wait."""
    rig = _Rig(fifo=True)
    try:
        if not ship_is_late:
            rig.gate.set()
        rig.write("pgA", "a")           # held: every ship waits for it
        rig.write("pgB", "b1")
        rig.barrier("pgB", "barrier")
        rig.write("pgB", "b2")          # staged after the barrier
        rig.write("pgC", "c")           # another key: not fenced
        if ship_is_late:
            for _ in range(200):
                if "c" in rig.ran:
                    break
                time.sleep(0.05)
            assert sorted(rig.ran) == ["b1", "c"], rig.ran
            assert not rig.wait("barrier", 0.3), rig.shipped
            assert sorted(rig.ran) == ["b1", "c"], rig.ran
            rig.gate.set()
        assert rig.wait("b2", 30) and rig.wait("c", 30), rig.shipped
        for seen in (rig.ran, rig.names()):     # run order, wire order
            assert seen.index("b1") < seen.index("barrier") \
                < seen.index("b2"), seen
        assert sorted(rig.names()) == ["a", "b1", "b2", "barrier", "c"]
    finally:
        rig.close()


def test_after_flush_callbacks_run_in_registration_order():
    """A callback registered while the group's callbacks run queues
    behind them (it does not run at once on its caller's thread)."""
    group = FlushGroup(1)
    out: list = []

    def first():
        group.after_flush(lambda: out.append("registered by first"))
        out.append("first")
    group.after_flush(first)
    group.after_flush(lambda: out.append("second"))
    group.done()
    ship_groups([group])
    assert out == ["first", "second", "registered by first"]


def test_stop_returns_after_the_last_group_shipped(rig):
    for i in range(3):
        rig.write(f"pg{i}", f"w{i}")
    threading.Timer(0.3, rig.gate.set).start()
    rig.eng.stop()
    assert rig.names() == ["w0", "w1", "w2"]
    assert not rig.eng._ship_thread.is_alive()


@pytest.mark.parametrize("nkeys", [1, 3])
def test_a_group_is_ready_after_its_last_wrapper_and_ships_once(nkeys):
    group = FlushGroup(nkeys)
    out: list = []
    group.defer("x", out.extend, 1)
    group.defer("x", out.extend, 2)
    group.defer("y", out.extend, 3)
    group.after_flush(lambda: out.append("after"))
    for _ in range(nkeys - 1):
        group.done()
        assert not group.ready.is_set()
    group.done()
    assert group.ready.is_set() and not group.event.is_set()
    assert out == []                    # done() never ships
    ship_groups([group])
    assert out == [1, 2, 3, "after"] and group.event.is_set()
    group.after_flush(lambda: out.append("late"))
    assert out[-1] == "late"            # already shipped: runs now
