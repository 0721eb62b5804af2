"""ISSUE 15 acceptance: the commit path CLOSED — group-commit stores,
the streaming objecter, and real-wire bulk framing, gated on the very
instruments PR 14 built.

- projection honesty: the group-commit what-if from a pre-fix replay
  brackets the measured post-fix ``store_fsyncs_per_op`` — the
  instrument stays trustworthy after the fix it predicted;
- deterministic fsync accounting: a txn group pays ONE barrier set
  (counted, not timed — no scheduler luck on the 1-core box);
- the streaming objecter forms real batches under concurrency and
  every op acks; a dropped batched submit (chaos rule written against
  the SINGLETON MOSDOp type, family-matched onto MOSDOpBatch)
  degrades exactly like N singleton drops with zero lost acked
  writes;
- the end-to-end throughput bar is core-gated like PR 13's
  bulk-ingest bar: full ratio on >= 4 cores, directional below.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import os
import threading

import pytest

from ceph_tpu.store.object_store import Transaction, create_store
from ceph_tpu.utils import faults
from ceph_tpu.utils.config import g_conf
from ceph_tpu.utils.store_telemetry import telemetry


@pytest.fixture(autouse=True)
def _fresh_registries():
    telemetry().reset()
    faults.reset_for_tests(seed=0)
    yield
    telemetry().reset()
    faults.reset_for_tests(seed=0)


# -- projection honesty (the instrument survives its own fix) ----------

def test_group_commit_projection_brackets_measured(tmp_path):
    """PR 14's what-if ledger projected fsyncs-saved from singleton
    arrivals; PR 15 landed the fix. Replay the SAME txn schedule both
    ways on a durable store: the pre-fix projection must price the
    post-fix reality — projected fsyncs/op == measured fsyncs/op for
    the window that forms the same groups (counting, deterministic)."""
    n = 12
    payload = b"p" * 2048

    def burst(store, grouped: bool) -> None:
        pairs = [(Transaction().write("c", f"o{i}", 0, payload), None)
                 for i in range(n)]
        if grouped:
            store.queue_transaction_group(pairs)
        else:
            for txn, cb in pairs:
                store.queue_transaction(txn, cb)

    # pre-fix replay: singleton commits, arrivals recorded
    pre = create_store("blockstore", str(tmp_path / "pre"))
    pre.mount()
    pre.queue_transaction(Transaction().create_collection("c"))
    telemetry().reset()
    burst(pre, grouped=False)
    tel = telemetry()
    brief_pre = tel.snapshot_brief()
    assert brief_pre["txns"] == n
    fsyncs_per_txn_pre = brief_pre["fsyncs_per_txn"]
    assert fsyncs_per_txn_pre >= 2.0   # data + wal per singleton txn
    # a window wide enough to group the whole burst projects the
    # whole win: groups == 1, saved == (n-1) txn-barrier sets
    row = tel.group_commit_projection(windows_s=(30.0,))[0]
    assert row["fsync_model"] == "measured"
    assert row["groups"] == 1 and row["max_group"] == n
    projected_fsyncs_per_op = (
        brief_pre["fsyncs"] - row["fsyncs_saved"]) / n
    pre.umount()

    # post-fix: the same schedule through the group-commit path
    post = create_store("blockstore", str(tmp_path / "post"))
    post.mount()
    post.queue_transaction(Transaction().create_collection("c"))
    telemetry().reset()
    burst(post, grouped=True)
    brief_post = telemetry().snapshot_brief()
    post.umount()
    assert brief_post["txns"] == n
    measured = brief_post["fsyncs"] / brief_post["txns"]
    # the honesty bracket: the projection called the measured number
    assert measured == pytest.approx(projected_fsyncs_per_op,
                                     rel=0.01), \
        (measured, projected_fsyncs_per_op)
    # and the headline gate: >= 2x down vs the pre-fix replay
    assert measured <= fsyncs_per_txn_pre / 2.0


# -- deterministic barrier accounting ----------------------------------

def test_txn_group_pays_one_barrier_set(tmp_path):
    """8 txns, one group: exactly one data fdatasync + one kv.wal
    fsync (blockstore), and the group counters land."""
    store = create_store("blockstore", str(tmp_path / "bs"))
    store.mount()
    store.queue_transaction(Transaction().create_collection("c"))
    telemetry().reset()
    fired = []
    pairs = [(Transaction().write("c", f"g{i}", 0, b"d" * 1024),
              lambda i=i: fired.append(i)) for i in range(8)]
    store.queue_transaction_group(pairs)
    assert fired == list(range(8))     # sweep in submission order
    tel = telemetry()
    sites = tel.fsync_sites()
    assert sites["blockstore.data"]["count"] == 1
    assert sites["kv.wal"]["count"] == 1
    snap = tel.perf.dump()
    assert snap["store_group_commits"] == 1
    assert snap["txns"] == 8
    store.umount()


def test_deferred_groups_share_one_barrier(tmp_path):
    """The cross-thread receiver leg: K txn groups queued defer=True
    (one per PG of a batched sub-write frame) pay ONE shared barrier
    at ``barrier()`` — and acks stay parked until it."""
    store = create_store("blockstore", str(tmp_path / "bs"))
    store.mount()
    boot = Transaction()
    for pg in range(4):
        boot.create_collection(f"pg{pg}")
    store.queue_transaction(boot)
    telemetry().reset()
    fired = []
    for pg in range(4):                # 4 "PG groups", 2 txns each
        pairs = [(Transaction().write(f"pg{pg}", f"o{i}", 0,
                                      b"x" * 512),
                  lambda pg=pg, i=i: fired.append((pg, i)))
                 for i in range(2)]
        store.queue_transaction_group(pairs, defer=True)
    assert fired == [] and store.barrier_pending()
    store.barrier()
    assert len(fired) == 8 and not store.barrier_pending()
    sites = telemetry().fsync_sites()
    # ONE barrier set for all four groups, not one per group
    assert sites["blockstore.data"]["count"] == 1
    assert sites["kv.wal"]["count"] == 1
    snap = telemetry().perf.dump()
    assert snap["store_group_commits"] == 4
    assert snap["txns"] == 8
    store.umount()


def test_faults_family_covers_client_batches():
    """A chaos rule naming MOSDOp/MOSDOpReply bites the streaming
    objecter's batched twins (the family map pin, same contract as
    the ISSUE-9 sub-write family)."""
    from ceph_tpu.parallel import messages as M
    from ceph_tpu.utils.faults import _msg_type_matches
    assert _msg_type_matches(M.MOSDOp.MSG_TYPE,
                             M.MOSDOpBatch.MSG_TYPE)
    assert _msg_type_matches(M.MOSDOpReply.MSG_TYPE,
                             M.MOSDOpReplyBatch.MSG_TYPE)
    assert not _msg_type_matches(M.MOSDOp.MSG_TYPE,
                                 M.MECSubWriteBatch.MSG_TYPE)


# -- cluster-level: streaming + group commit end to end ----------------

def _write_burst(io, n_objs: int, payload_of, concurrency: int = 6):
    with concurrent.futures.ThreadPoolExecutor(concurrency) as pool:
        list(pool.map(
            lambda i: io.write_full(f"s{i}", payload_of(i)),
            range(n_objs)))


def test_streaming_objecter_forms_batches_and_all_ops_ack():
    """A concurrent write burst through a MiniCluster: real
    MOSDOpBatch frames form (the measured twin of the PR-14
    ``objecter_batch_ops`` ledger), every op acks, every byte reads
    back."""
    from ceph_tpu.qa.cluster import MiniCluster
    with MiniCluster(n_osds=3) as c:
        c.create_ec_pool("st", k=2, m=1, pg_num=4, backend="jax")
        io = c.client().open_ioctx("st")
        payload_of = (lambda i: bytes(((i * 31 + j) & 0xFF)
                                      for j in range(4096)))
        _write_burst(io, 48, payload_of)
        for i in range(48):
            assert io.read(f"s{i}") == payload_of(i), i
        snap = telemetry().perf.dump()
        assert snap["objecter_stream_batches"] >= 1
        assert snap["store_group_commits"] >= 1


def _payload_of(i: int) -> bytes:
    return bytes(((i * 13 + j) & 0xFF) for j in range(4096))


@contextlib.contextmanager
def _fast_resend_pool(pool: str, seed: int):
    """A 3-OSD cluster with the fault registry reseeded, a device-
    backed EC pool and a client whose resend ladder runs at 0.3 s:
    yields (fault registry, ioctx)."""
    from ceph_tpu.qa.cluster import MiniCluster
    conf = g_conf()
    old_resend = conf["objecter_resend_interval"]
    conf.set("objecter_resend_interval", 0.3)
    try:
        with MiniCluster(n_osds=3) as cluster:
            cluster.faults.reseed(seed)
            cluster.create_ec_pool(pool, k=2, m=1, pg_num=4,
                                   backend="jax")
            io = cluster.client().open_ioctx(pool)
            io.op_timeout = 60.0
            io.write_full("warm", b"w")     # admission warm-up
            yield cluster.faults, io
    finally:
        conf.set("objecter_resend_interval", old_resend)


def test_dropped_batched_submit_zero_lost_acked_writes():
    """Degraded-serving parity for the new client leg: a drop rule
    written against the SINGLETON MOSDOp type fires on the batched
    frames too (family map), and the per-op singleton resend ladder
    re-drives every affected write — zero lost acked writes, every
    readback byte-exact."""
    from ceph_tpu.parallel import messages as M
    with _fast_resend_pool("dz", seed=7) as (reg, io):
        rule = reg.add("msgr_drop", entity="client.*",
                       msg_type=M.MOSDOp.MSG_TYPE,
                       every=5, max_fires=3)
        _write_burst(io, 32, _payload_of, concurrency=8)
        rule.remove()
        for i in range(32):
            assert io.read(f"s{i}") == _payload_of(i), \
                f"s{i} lost or wrong"
        assert rule.fires >= 1
        # the chaos path forced the real wire; batching still
        # happened during the faulted burst
        assert telemetry().perf.dump()[
            "objecter_stream_batches"] >= 1


def test_dropped_op_and_reply_frames_zero_lost_acked_writes():
    """Both directions of the client leg under the msgr fault family:
    op frames (singleton + batch) AND reply-batch frames are dropped
    mid-burst. A write whose REPLY was lost is already applied when
    the resend arrives; the OSD answers it from its completed-op
    cache — every acked write reads back byte-exact and an append is
    applied once, not once per resend."""
    from ceph_tpu.parallel import messages as M
    with _fast_resend_pool("dr", seed=11) as (reg, io):
        io.write_full("log", b"")
        rules = [
            reg.add("msgr_drop", entity="client.*",
                    msg_type=M.MOSDOp.MSG_TYPE,
                    every=4, max_fires=3),
            reg.add("msgr_drop", entity="client.*",
                    msg_type=M.MOSDOpBatch.MSG_TYPE,
                    every=3, max_fires=3),
            reg.add("msgr_drop", entity="osd.*",
                    msg_type=M.MOSDOpReplyBatch.MSG_TYPE,
                    every=5, max_fires=2),
        ]
        _write_burst(io, 24, _payload_of, concurrency=8)
        for r in rules:
            r.remove()
        assert sum(r.fires for r in rules) >= 1
        # the family map carries the singleton reply type onto the
        # batched one: whichever frame acks an append, one in three
        # is lost AFTER the append was applied
        replies = reg.add("msgr_drop", entity="osd.*",
                          msg_type=M.MOSDOpReply.MSG_TYPE,
                          every=3, max_fires=4)
        for i in range(12):
            io.append("log", bytes([i]) * 7)
        replies.remove()
        assert replies.fires >= 1
        for i in range(24):
            assert io.read(f"s{i}") == _payload_of(i), \
                f"s{i} lost or wrong"
        assert io.read("log") == b"".join(
            bytes([i]) * 7 for i in range(12))


def test_group_commit_fsync_reduction_end_to_end(tmp_path):
    """The tier-1, counting form of the bench gate: the same cluster
    write burst with CEPH_TPU_GROUP_COMMIT=0 vs =1 on a durable
    store — the grouped run must pay <= half the fsyncs per txn (the
    >= 2x ``store_fsyncs_per_op`` drop, without wall-clock luck)."""
    from ceph_tpu.qa.cluster import MiniCluster

    def run(flag: str, sub: str) -> float:
        os.environ["CEPH_TPU_GROUP_COMMIT"] = flag
        try:
            telemetry().reset()
            with MiniCluster(n_osds=3, store="blockstore",
                             data_dir=str(tmp_path / sub)) as c:
                c.create_ec_pool("gb", k=2, m=1, pg_num=4,
                                 backend="jax")
                io = c.client().open_ioctx("gb")
                # enough in-flight adjacency for the groups to form
                # (the same shape the load_gen bench row sustains)
                _write_burst(io, 96, lambda i: b"z" * 8192,
                             concurrency=16)
            brief = telemetry().snapshot_brief()
            assert brief["txns"] > 0 and brief["fsyncs"] > 0
            return brief["fsyncs"] / brief["txns"]
        finally:
            os.environ.pop("CEPH_TPU_GROUP_COMMIT", None)

    # two attempts absorb a cold/unlucky first boot on the 1-core box
    for attempt in range(2):
        per_txn_off = run("0", f"off{attempt}")
        per_txn_on = run("1", f"on{attempt}")
        if per_txn_on <= per_txn_off / 2.0:
            return
    raise AssertionError(
        f"group commit never halved fsyncs/txn: "
        f"{per_txn_on:.2f} vs {per_txn_off:.2f}")


def test_streamed_pipeline_not_slower_core_gated(tmp_path):
    """The core-gated throughput form (PR-13 bulk-ingest pattern):
    paired A/B of the full new pipeline (stream + group commit) vs
    the pre-15 client leg on a durable store. >= 4 cores holds a
    1.2x win; on the 1-core CI box the same measured ratio gates
    DIRECTIONALLY at 0.9x (a real regression to per-op machinery
    shows up far below either bar). Paired samples with retries
    absorb scheduler weather."""
    import time
    from ceph_tpu.qa.cluster import MiniCluster
    cores = len(os.sched_getaffinity(0))
    bar = 1.2 if cores >= 4 else 0.9
    conf = g_conf()

    def run(stream: bool, group: str, sub: str) -> float:
        os.environ["CEPH_TPU_GROUP_COMMIT"] = group
        old = conf["objecter_stream"]
        conf.set("objecter_stream", stream)
        try:
            with MiniCluster(n_osds=3, store="blockstore",
                             data_dir=str(tmp_path / sub)) as c:
                c.create_ec_pool("tb", k=2, m=1, pg_num=4,
                                 backend="jax")
                io = c.client().open_ioctx("tb")
                io.write_full("warm", b"w" * 1024)
                t0 = time.perf_counter()
                _write_burst(io, 32, lambda i: b"q" * 16384,
                             concurrency=8)
                return 32 * 16384 / (time.perf_counter() - t0)
        finally:
            conf.set("objecter_stream", old)
            os.environ.pop("CEPH_TPU_GROUP_COMMIT", None)

    pairs = []
    for attempt in range(3):
        base = run(False, "0", f"b{attempt}")
        new = run(True, "1", f"n{attempt}")
        pairs.append((base, new))
        if new >= bar * base:
            return
    raise AssertionError(
        f"streamed pipeline never reached {bar}x its paired "
        f"baseline ({cores} cores): "
        f"{[(round(b / 1e6, 2), round(n / 1e6, 2)) for b, n in pairs]}")
