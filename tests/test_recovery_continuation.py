"""A recovery round closes by continuation (ISSUE 29): the op-wq
worker that builds and sends a round's pushes does not wait for the
replies. Before, it blocked up to ``2 * SUBOP_TIMEOUT`` on them, could
not handle the pushes other OSDs' rounds had sent to ITS shard index
meanwhile, and rounds waited for each other in rings that only the
timeout broke (the degraded cell's settle ran past its 240 s)."""

import os
import threading
import time

import pytest

from ceph_tpu.osd.pg_backend import SUBOP_TIMEOUT, SubOpWait


def test_when_done_fires_once_with_the_last_entry():
    wait, fired = SubOpWait({"a", "b", "c"}), []
    wait.when_done(lambda: fired.append(threading.get_ident()))
    wait.complete("a", "ra")
    wait.drop("b")
    assert fired == []
    wait.complete("c", "rc")
    assert fired == [threading.get_ident()]
    # late or repeated entries change the results, not the count
    wait.complete("c", "again")
    wait.drop("a")
    assert len(fired) == 1
    assert wait.snapshot() == {"a": "ra", "c": "again"}


def test_when_done_runs_at_once_when_nothing_is_pending():
    wait, fired = SubOpWait({"a"}), []
    wait.complete("a", 1)
    wait.when_done(lambda: fired.append(1))
    assert fired == [1]
    empty = SubOpWait(set())
    empty.when_done(lambda: fired.append(2))
    assert fired == [1, 2]


def test_the_blocking_wait_is_what_it_was():
    wait = SubOpWait({1, 2})
    threading.Timer(0.05, lambda: wait.complete(1, "x")).start()
    t0 = time.monotonic()
    assert wait.wait(0.3) == {1: "x"}       # 2 never comes
    assert 0.25 < time.monotonic() - t0 < 2.0
    wait.complete(2, "y")
    assert wait.wait(5.0) == {1: "x", 2: "y"}


@pytest.fixture
def fast_death():
    from ceph_tpu.utils.config import g_conf
    conf = g_conf()
    old = {k: conf[k] for k in ("osd_heartbeat_interval",
                                "osd_heartbeat_grace")}
    conf.set("osd_heartbeat_interval", 0.25)
    conf.set("osd_heartbeat_grace", 2.0)
    yield
    for k, v in old.items():
        conf.set(k, v)


def test_a_client_op_is_served_while_a_round_waits_for_its_replies(
        fast_death, monkeypatch):
    """Push replies are withheld: the round stays open until its
    timer closes it, and a client write to the SAME PG (the same wq
    shard) is served meanwhile instead of queueing behind it."""
    from ceph_tpu.osd.osd import OSD
    from ceph_tpu.qa.cluster import MiniCluster
    withheld = []
    monkeypatch.setattr(
        OSD, "_handle_pg_push",
        lambda self, msg, conn: withheld.append(msg.oid))
    with MiniCluster(n_osds=4) as c:
        rados = c.client()
        c.create_ec_pool("ec", k=2, m=1, pg_num=1)
        io = rados.open_ioctx("ec")
        for i in range(3):
            io.write_full(f"o{i}", os.urandom(20_000))
        _, acting, primary = c.mon.osdmap.pg_to_up_acting(1, 0)
        victim = next(o for o in acting if o != primary)
        epoch = c.epoch()
        c.kill_osd(victim)
        c.wait_for_osd_down(victim, timeout=30)
        rados.wait_for_epoch(epoch + 1, timeout=10)
        # the spare took the position over: the primary's round has
        # sent its pushes, and nothing answers them
        deadline = time.monotonic() + 20
        while not withheld and time.monotonic() < deadline:
            time.sleep(0.05)
        assert withheld, "no recovery round pushed anything"
        pg = c.osds[primary].pgs[(1, 0)]
        assert pg.recovery_in_flight
        t0 = time.monotonic()
        io.write_full("during", b"x" * 9000)
        served_in = time.monotonic() - t0
        assert io.read("during") == b"x" * 9000
        assert served_in < SUBOP_TIMEOUT, served_in
        # the timer closes the round: the flag clears, what was not
        # acknowledged stays missing, and the next tick tries again
        deadline = time.monotonic() + 4 * SUBOP_TIMEOUT
        rounds = len(withheld)
        while time.monotonic() < deadline and len(withheld) == rounds:
            time.sleep(0.1)
        assert len(withheld) > rounds, "no second round after the timer"
        assert pg.missing_dirty()
