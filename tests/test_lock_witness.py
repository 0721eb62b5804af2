"""Lock-order witness gates (ISSUE 11, runtime half).

The pylockdep's contract, pinned:

- off = zero wrappers (the ``make_*`` seams return bare threading
  primitives — the zero-Spans pattern);
- the scripted AB-BA shape (two daemons messaging each other under
  their own locks — the PR 9 loopback deadlock, reconstructed) is
  reported as a cycle WITHOUT the test hanging, even though the
  deadlock never fires in-run;
- blocking-under-lock detection covers device barriers, fsync, the
  blocking asok round-trip, and Condition.wait under a foreign lock
  (the PR 4 / PR 6 shutdown-race shape);
- a full witness-enabled MiniCluster write burst reports ZERO
  unacknowledged cycles and ZERO unacknowledged blocking violations
  against analysis/baseline.json's justified witness section;
- witness state is fixed-memory and the proxy overhead is bounded.
"""

import json
import os
import threading
import time

import pytest

from ceph_tpu.analysis import linters
from ceph_tpu.analysis import lock_witness as lw


@pytest.fixture
def witness():
    if lw.env_enabled():
        # CEPH_TPU_LOCK_WITNESS=1 arms the witness session-wide
        # (conftest owns it and serializes the whole-session report at
        # teardown); these per-test gates assume isolated state and
        # run in the default (off) session — tier-1 — instead.
        pytest.skip("witness armed session-wide by env")
    lw.enable()
    try:
        yield lw
    finally:
        lw.disable()
        lw.reset()


def _run_bounded(fn, timeout=15.0):
    """Watchdog: run fn on a worker; fail (don't hang the suite) if it
    wedges."""
    done = []
    err = []

    def body():
        try:
            fn()
            done.append(1)
        except BaseException as exc:   # noqa: BLE001 — reraised below
            err.append(exc)

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(timeout)
    if err:
        raise err[0]
    assert done, f"scenario wedged (>{timeout}s) — watchdog tripped"


# -- off = zero wrappers ------------------------------------------------

def test_witness_off_returns_bare_primitives():
    assert not lw.enabled()
    assert type(lw.make_lock("x")) is type(threading.Lock())
    assert type(lw.make_rlock("x")) is type(threading.RLock())
    cond = lw.make_condition("x")
    assert type(cond) is threading.Condition
    # and no blocking hooks are patched in
    import ceph_tpu.utils.admin_socket as asok_mod
    assert not hasattr(os.fsync, "__wrapped__")
    assert not hasattr(asok_mod.asok_command, "__wrapped__")


def test_enable_disable_roundtrip(witness):
    assert lw.enabled()
    assert isinstance(lw.make_lock("a"), lw.WitnessLock)
    assert isinstance(lw.make_rlock("a"), lw.WitnessLock)
    assert isinstance(lw.make_condition("a"), lw.WitnessCondition)
    assert hasattr(os.fsync, "__wrapped__")
    lw.disable()
    assert type(lw.make_lock("x")) is type(threading.Lock())
    assert not hasattr(os.fsync, "__wrapped__")


# -- AB-BA ---------------------------------------------------------------

class _Daemon:
    """Minimal reconstruction of the PR 9 loopback shape: a daemon
    whose handler runs under its own lock and SYNCHRONOUSLY calls into
    its peer (dispatch-on-the-sending-thread — exactly what the real
    messenger now forbids by dispatching on the receiver's loop)."""

    def __init__(self, name: str) -> None:
        self.lock = lw.make_lock(f"daemon.{name}")
        self.peer: "_Daemon | None" = None

    def tick(self) -> None:
        """Heartbeat: under MY lock, message the peer."""
        with self.lock:
            self.peer.handle()

    def handle(self) -> None:
        with self.lock:
            pass


def test_scripted_abba_reported_without_hanging(witness):
    """The PR 9 regression: both daemons tick (sequentially — the
    deadlock never FIRES in this run) and the witness still reports
    the A->B / B->A cycle from the order graph alone."""
    a, b = _Daemon("alpha"), _Daemon("beta")
    a.peer, b.peer = b, a

    def scenario():
        a.tick()     # daemon.alpha -> daemon.beta
        b.tick()     # daemon.beta -> daemon.alpha

    _run_bounded(scenario)
    rep = lw.report()
    keys = [c["key"] for c in rep["cycles"]]
    assert "cycle:daemon.alpha|daemon.beta" in keys, keys
    cyc = next(c for c in rep["cycles"]
               if c["key"] == "cycle:daemon.alpha|daemon.beta")
    # both directed edges present, each with a stack sample
    dirs = {(e["from"], e["to"]) for e in cyc["edges"]}
    assert ("daemon.alpha", "daemon.beta") in dirs
    assert ("daemon.beta", "daemon.alpha") in dirs
    assert all(e["stacks"] for e in cyc["edges"])
    # and it is NOT acknowledged by the checked-in baseline
    assert any(u.get("key") == cyc["key"]
               for u in lw.unacknowledged(rep))


def test_consistent_order_is_not_a_cycle(witness):
    a = lw.make_lock("ord.a")
    b = lw.make_lock("ord.b")
    for _ in range(3):
        with a:
            with b:
                pass
    assert lw.report()["cycles"] == []


def test_rlock_reentry_is_not_an_edge(witness):
    r = lw.make_rlock("re.lock")
    with r:
        with r:
            pass
    rep = lw.report()
    assert rep["cycles"] == [] and rep["edges"] == 0


def test_distinct_instances_same_class_nesting_flagged(witness):
    """Two PG locks share the name 'pg.lock' (lockdep keys by class);
    nesting two DIFFERENT instances is the two-PG-deadlock shape and
    must surface as a self-cycle."""
    p1, p2 = lw.make_lock("same.class"), lw.make_lock("same.class")
    with p1:
        with p2:
            pass
    keys = [c["key"] for c in lw.report()["cycles"]]
    assert "cycle:same.class|same.class" in keys


# -- blocking-under-lock -------------------------------------------------

def test_fsync_under_lock_flagged(witness, tmp_path):
    fd = os.open(str(tmp_path / "f"), os.O_CREAT | os.O_WRONLY)
    try:
        lock = lw.make_lock("store.meta")
        with lock:
            os.fsync(fd)
    finally:
        os.close(fd)
    rep = lw.report()
    assert any(v["kind"] == "fsync" and v["lock"] == "store.meta"
               for v in rep["blocking"])


def test_fsync_outside_lock_clean(witness, tmp_path):
    fd = os.open(str(tmp_path / "f"), os.O_CREAT | os.O_WRONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    assert lw.report()["blocking"] == []


def test_device_barrier_under_lock_flagged(witness):
    import jax
    import jax.numpy as jnp
    x = jnp.zeros((8,), jnp.uint8)
    with lw.make_lock("engine.window"):
        jax.block_until_ready(x)
    rep = lw.report()
    assert any(v["kind"] == "device_barrier"
               and v["lock"] == "engine.window"
               for v in rep["blocking"])


def test_asok_roundtrip_under_lock_flagged(witness):
    from ceph_tpu.utils.admin_socket import AdminSocket, asok_command
    asok = AdminSocket("witness-test")
    asok.start()
    try:
        with lw.make_lock("mgr.tick"):
            out = asok_command(asok.path, "help")
        assert isinstance(out, dict)
    finally:
        asok.stop()
    rep = lw.report()
    assert any(v["kind"] == "socket_send" and v["lock"] == "mgr.tick"
               for v in rep["blocking"])


def test_cond_wait_under_foreign_lock_flagged(witness):
    other = lw.make_lock("shutdown.gate")
    cv = lw.make_condition("engine.inflight")

    def scenario():
        with other:               # the PR 4 shape: holding the
            with cv:              # shutdown lock while waiting on
                cv.wait(0.05)     # the engine's condition
    _run_bounded(scenario)
    rep = lw.report()
    assert any(v["kind"] == "cond_wait_under_lock"
               and v["lock"] == "shutdown.gate"
               for v in rep["blocking"])


def test_cond_wait_on_own_lock_only_is_clean(witness):
    cv = lw.make_condition("solo.cv")

    def scenario():
        with cv:
            cv.wait(0.05)
    _run_bounded(scenario)
    assert lw.report()["blocking"] == []


def test_cond_wait_for_wakes_and_checks(witness):
    cv = lw.make_condition("wf.cv")
    state = {"ready": False}

    def producer():
        time.sleep(0.05)
        with cv:
            state["ready"] = True
            cv.notify_all()

    t = threading.Thread(target=producer, daemon=True)
    t.start()

    def scenario():
        with cv:
            assert cv.wait_for(lambda: state["ready"], timeout=5)
    _run_bounded(scenario)
    t.join(2)


# -- fixed memory / report ----------------------------------------------

def test_edge_memory_is_capped(witness, monkeypatch):
    monkeypatch.setattr(lw, "MAX_EDGES", 4)
    anchor = lw.make_lock("cap.anchor")
    for i in range(10):
        child = lw.make_lock(f"cap.child{i}")
        with anchor:
            with child:
                pass
    rep = lw.report()
    assert rep["edges"] <= 4
    assert rep["edges_dropped"] > 0


def test_report_serializes_and_acks_filter(witness, tmp_path):
    a, b = _Daemon("ser.a"), _Daemon("ser.b")
    a.peer, b.peer = b, a
    a.tick()
    b.tick()
    path = str(tmp_path / "report.json")
    lw.save_report(path)
    rep = json.load(open(path))
    assert rep["cycles"] and rep["enabled"]
    key = rep["cycles"][0]["key"]
    acked = lw.unacknowledged(
        rep, {"witness": [{"key": key, "justification": "t"}]})
    assert key not in [u.get("key") for u in acked]


def test_witness_overhead_bounded(witness):
    """Proxy cost must stay linear and small: 100k witnessed
    acquire/release pairs in well under the tier-1 noise floor (the
    <10%-of-tier-1-wall bound holds because ONLY the gate tests
    enable the witness at all).

    ISSUE 13 de-flake: the old absolute <5 s wall bound flaked on
    the 1-core CI box whenever the suite's other threads stole the
    core mid-loop. The measured quantity is the witness's RELATIVE
    overhead, so assert it as a paired ratio against a bare
    threading.Lock driven through the identical loop in the same
    scheduling weather (directional: witnessed slower, but bounded),
    with a widened absolute ceiling kept as the runaway backstop."""
    import threading

    def drive(lock) -> float:
        t0 = time.perf_counter()
        for _ in range(100_000):
            with lock:
                pass
        return time.perf_counter() - t0

    bare_s = drive(threading.Lock())
    witnessed_s = drive(lw.make_lock("bench.lock"))
    # measured ~8-12x on the CI box; 60x flags a superlinear proxy
    # while staying far from scheduler noise
    assert witnessed_s < 60.0 * max(bare_s, 1e-4), \
        f"witness overhead ratio blown: {witnessed_s:.3f}s vs " \
        f"bare {bare_s:.3f}s"
    assert witnessed_s < 20.0, \
        f"witnessed acquire runaway: {witnessed_s:.2f}s"


# -- the cluster gate ----------------------------------------------------

def test_minicluster_write_burst_clean(witness):
    """Acceptance: a full witness-enabled MiniCluster scenario — boot,
    EC pool, write burst, reads, wait_for_clean, teardown — reports
    zero unacknowledged cycles and zero unacknowledged
    blocking-under-lock violations."""
    from ceph_tpu.qa.cluster import MiniCluster

    def scenario():
        with MiniCluster(n_osds=3) as c:
            c.create_ec_pool("wit", k=2, m=1)
            ioctx = c.client().open_ioctx("wit")
            payload = bytes(range(256)) * 16
            for i in range(32):
                ioctx.write_full(f"obj-{i}", payload)
            for i in range(32):
                assert ioctx.read(f"obj-{i}") == payload
            c.wait_for_clean(timeout=30)

    _run_bounded(scenario, timeout=120.0)
    rep = lw.report()
    # real lock traffic was observed (the gate isn't vacuous)
    assert rep["edges"] > 0
    bad = lw.unacknowledged(rep)
    assert not bad, (
        "unacknowledged witness findings (fix them or add a JUSTIFIED "
        "entry to analysis/baseline.json 'witness'): "
        + json.dumps(bad, indent=1)[:2000])


def test_minicluster_durable_group_commit_burst_clean(witness,
                                                      tmp_path):
    """ISSUE 15 satellite: the witness-armed burst over the NEW
    commit-path seams — a durable (blockstore) cluster under a
    concurrent streamed write burst drives queue_transaction_group,
    the deferred cross-PG barrier, the shared leader-follower fsync
    rounds, and batched MOSDOp framing. Group commit must not fsync
    under a per-PG or store lock the op path also takes: zero
    unacknowledged cycles, zero unacknowledged blocking-under-lock
    violations."""
    import concurrent.futures

    from ceph_tpu.qa.cluster import MiniCluster

    def scenario():
        with MiniCluster(n_osds=3, store="blockstore",
                         data_dir=str(tmp_path / "wit")) as c:
            c.create_ec_pool("gwit", k=2, m=1, pg_num=4)
            ioctx = c.client().open_ioctx("gwit")
            payload = bytes(range(256)) * 8
            with concurrent.futures.ThreadPoolExecutor(8) as pool:
                list(pool.map(
                    lambda i: ioctx.write_full(f"g-{i}", payload),
                    range(32)))
            for i in range(32):
                assert ioctx.read(f"g-{i}") == payload
            c.wait_for_clean(timeout=30)

    _run_bounded(scenario, timeout=120.0)
    rep = lw.report()
    assert rep["edges"] > 0
    bad = lw.unacknowledged(rep)
    assert not bad, (
        "unacknowledged witness findings on the group-commit paths: "
        + json.dumps(bad, indent=1)[:2000])


def test_witness_baseline_entries_are_justified():
    """No silent allowlisting: every acknowledged witness finding
    carries a written justification."""
    baseline = linters.load_baseline()
    for ent in baseline.get("witness", ()):
        assert ent.get("justification", "").strip(), ent
        assert not ent["justification"].startswith("TODO"), ent
