"""Messenger tests — framing, typed dispatch, replies on the same
connection, crc protection, reconnects, failure injection.

Mirrors src/test/msgr/ patterns (two endpoints exchanging typed
messages with injected faults)."""

import threading
import time

import pytest

from ceph_tpu.parallel import messages as M
from ceph_tpu.parallel.messenger import Messenger


class Sink:
    """Collects dispatched messages; signals arrival."""

    def __init__(self) -> None:
        self.got: list = []
        self.ev = threading.Event()

    def __call__(self, msg, conn) -> None:
        self.got.append((msg, conn))
        self.ev.set()

    def wait(self, n=1, timeout=5.0) -> bool:
        deadline = time.time() + timeout
        while len(self.got) < n:
            if time.time() > deadline:
                return False
            self.ev.wait(0.05)
            self.ev.clear()
        return True


@pytest.fixture
def pair():
    a, b = Messenger("osd.0"), Messenger("osd.1")
    a.bind(); b.bind()
    yield a, b
    a.shutdown(); b.shutdown()


def test_message_payload_roundtrip():
    m = M.MECSubWrite(tid=7, pool=1, ps=3, shard=2, epoch=9,
                      oid="obj", version=42, txn_bytes=b"\x00\x01")
    out = M.decode_message(M.MECSubWrite.MSG_TYPE, m.encode_payload())
    assert (out.tid, out.pool, out.ps, out.shard, out.epoch,
            out.oid, out.version, out.txn_bytes) == \
        (7, 1, 3, 2, 9, "obj", 42, b"\x00\x01")


def test_message_forward_compat_trailing_fields():
    # a "newer" MPing with an extra appended field decodes on this reader
    class MPingV2(M.MPing):
        MSG_TYPE = 0  # not registered
        FIELDS = M.MPing.FIELDS + [("new_field", "str")]

    newer = MPingV2(osd_id=3, epoch=8, stamp=1.5, new_field="future")
    old = M.MPing.decode_payload(newer.encode_payload())
    assert (old.osd_id, old.epoch, old.stamp) == (3, 8, 1.5)


def test_send_and_dispatch(pair):
    a, b = pair
    sink = Sink()
    b.set_dispatcher(sink)
    a.send_message(M.MPing(osd_id=0, epoch=5, stamp=1.0), b.addr)
    assert sink.wait()
    msg, conn = sink.got[0]
    assert isinstance(msg, M.MPing) and msg.epoch == 5
    assert conn.peer_name == "osd.0"
    assert conn.peer_addr == a.addr


def test_reply_rides_same_connection(pair):
    a, b = pair
    replies = Sink()
    a.set_dispatcher(replies)

    def on_ping(msg, conn):
        conn.send_message(
            M.MPingReply(osd_id=1, epoch=msg.epoch, stamp=msg.stamp))

    b.set_dispatcher(on_ping)
    a.send_message(M.MPing(osd_id=0, epoch=3, stamp=2.5), b.addr)
    assert replies.wait()
    msg, _ = replies.got[0]
    assert isinstance(msg, M.MPingReply) and msg.stamp == 2.5


def test_many_messages_in_order(pair):
    a, b = pair
    # this test pins the TCP path's connection-sharing (no cold-start
    # stampede); in-process loopback would bypass sockets entirely
    a._loopback = b._loopback = False
    sink = Sink()
    b.set_dispatcher(sink)
    for i in range(200):
        a.send_message(M.MOSDOp(tid=i, client="client.1", oid=f"o{i}",
                                data=b"x" * 100), b.addr)
    assert sink.wait(200)
    tids = [m.tid for m, _ in sink.got]
    assert tids == list(range(200))  # one connection => FIFO
    # a cold-start burst must share ONE connection, not stampede
    assert a.get_connection_count() == 1


def test_large_payload(pair):
    a, b = pair
    sink = Sink()
    b.set_dispatcher(sink)
    blob = bytes(range(256)) * 4096  # 1 MiB
    a.send_message(M.MECSubWrite(tid=1, txn_bytes=blob), b.addr)
    assert sink.wait()
    assert sink.got[0][0].txn_bytes == blob


def test_dispatcher_exception_does_not_kill_connection(pair):
    a, b = pair
    calls = []

    def bad(msg, conn):
        calls.append(msg)
        if len(calls) == 1:
            raise RuntimeError("bug in dispatch")

    b.set_dispatcher(bad)
    a.send_message(M.MPing(osd_id=0), b.addr)
    a.send_message(M.MPing(osd_id=1), b.addr)
    deadline = time.time() + 5
    while len(calls) < 2 and time.time() < deadline:
        time.sleep(0.02)
    assert len(calls) == 2


def test_reconnect_after_peer_restart(tmp_path):
    a = Messenger("client.1")
    b = Messenger("osd.9")
    a.start()
    addr = b.bind()
    sink = Sink()
    b.set_dispatcher(sink)
    a.send_message(M.MPing(osd_id=9), addr)
    assert sink.wait()
    b.shutdown()
    # peer restarts on the same port
    host, port = addr.rsplit(":", 1)
    b2 = Messenger("osd.9")
    sink2 = Sink()
    b2.set_dispatcher(sink2)
    for _ in range(50):
        try:
            b2.bind(host, int(port))
            break
        except OSError:
            time.sleep(0.1)
    # lossy semantics: first send may die with the stale conn; retry loop
    # (the upper layers do exactly this on timeout)
    for i in range(20):
        a.send_message(M.MPing(osd_id=9, epoch=i), addr)
        if sink2.wait(1, timeout=0.3):
            break
    assert sink2.got
    a.shutdown(); b2.shutdown()


def test_unknown_message_type_dropped(pair):
    a, b = pair
    sink = Sink()
    b.set_dispatcher(sink)

    class MBogus(M.Message):
        MSG_TYPE = 9999
        FIELDS = [("x", "u32")]

    # unregister before sending: the in-process receiver must not know
    # the type (sender and receiver share this registry)
    M._REGISTRY.pop(9999, None)
    a.send_message(MBogus(x=1), b.addr)
    a.send_message(M.MPing(osd_id=2), b.addr)
    assert sink.wait()
    assert all(isinstance(m, M.MPing) for m, _ in sink.got)


def test_scatter_gather_parts_equal_joined_payload():
    """ISSUE 15 (real-wire bulk framing), every type since ISSUE 31:
    a message's scatter-gather parts concatenate to EXACTLY
    encode_payload() — the wire bytes are unchanged, only the copies
    are gone."""
    from ceph_tpu.utils.encoding import SCATTER_MIN
    big, small = b"T" * SCATTER_MIN, b"U" * (SCATTER_MIN - 1)
    batch = M.MECSubWriteBatch(
        tid=3, epoch=7, tids=[1, 2], pools=[0, 0], pss=[1, 2],
        shards=[0, 1], oids=["a", "b"], versions=[5, 6],
        txns=[big, small], traces=["", "t"], stages="s")
    parts = batch.encode_payload_parts()
    assert b"".join(parts) == batch.encode_payload()
    # a large payload rides by REFERENCE, a part of its own; a small
    # one is joined into the run of fields around it
    assert any(p is big for p in parts)
    assert len(parts) == 3 and small in parts[2]
    for _, cls in sorted(M._REGISTRY.items()):
        msg = cls(**{name: {"bytes": big, "bytes_list": [small, big],
                            "bytes_map": {"k": big}}[kind]
                     for name, kind in cls.FIELDS
                     if kind.startswith("bytes")})
        parts = msg.encode_payload_parts()
        assert b"".join(parts) == msg.encode_payload(), cls.__name__
        assert sum(p is big for p in parts) == sum(
            kind.startswith("bytes") for _, kind in cls.FIELDS)
    # a message of small fields stays ONE part: one crc call, one write
    assert len(M.MPing(osd_id=1).encode_payload_parts()) == 1
    assert len(M.MOSDOp(tid=1, oid="o", data=small[:4096])
               .encode_payload_parts()) == 1


def test_batch_frames_survive_real_tcp(monkeypatch):
    """The off-loopback contract: scatter-gather framed batches cross
    a real kernel TCP socket with crc intact and decode equal."""
    monkeypatch.setenv("CEPH_TPU_MSGR_LOOPBACK", "0")
    a, b = Messenger("osd.7"), Messenger("osd.8")
    a.bind(); b.bind()
    try:
        sink = Sink()
        b.set_dispatcher(sink)
        batch = M.MECSubWriteBatch(
            tid=11, epoch=2, tids=[21, 22], pools=[1, 1],
            pss=[0, 3], shards=[0, 2], oids=["x", "y"],
            versions=[1, 2], txns=[b"\x01" * 65536, b"\x02" * 1234],
            traces=["", ""], stages="")
        opb = M.MOSDOpBatch(
            tid=12, client="client.1", epoch=2, pool=1, ps=3,
            tids=[31], oids=["z"], ops=[1], offsets=[0],
            lengths=[16], datas=[b"\x03" * 16], traces=[""],
            stages=[""])
        a.send_message(batch, b.addr)
        a.send_message(opb, b.addr)
        assert sink.wait(n=2)
        got_batch = next(m for m, _ in sink.got
                         if isinstance(m, M.MECSubWriteBatch))
        assert got_batch.txns == batch.txns
        assert got_batch.oids == ["x", "y"]
        got_opb = next(m for m, _ in sink.got
                       if isinstance(m, M.MOSDOpBatch))
        assert got_opb.datas == [b"\x03" * 16]
        # and the framing ledger saw them as TCP batch frames
        from ceph_tpu.utils.msgr_telemetry import telemetry
        assert telemetry().perf.dump()["tcp_batch_frames"] >= 2
    finally:
        a.shutdown(); b.shutdown()


def test_failure_injection_drops_but_system_recovers():
    from ceph_tpu.utils.config import g_conf
    g_conf().set("ms_inject_socket_failures", 5)
    try:
        a, b = Messenger("osd.5"), Messenger("osd.6")
        a.bind(); b.bind()
        sink = Sink()
        b.set_dispatcher(sink)
        for i in range(100):
            a.send_message(M.MPing(osd_id=i), b.addr)
        time.sleep(1.0)
        # with 1/5 injected failures many messages are lost, but the
        # connection keeps re-establishing and traffic still flows
        assert len(sink.got) > 20
        a.shutdown(); b.shutdown()
    finally:
        g_conf().set("ms_inject_socket_failures", 0)
