"""The wire encoding's sections hand their body's parts through and are
read as a window of the same buffer (ISSUE 31): the bytes are those of
the joined form, a bulk message costs two payload-size buffers between
sender and receiver, and nothing is aliased between peers.

``RefEncoder`` / ``RefDecoder`` are the plain reference: the encoding
as it stood before, kept here verbatim (a section joins its body and
length-prefixes it; a sub-decoder reads a slice of its own)."""

import argparse
import random
import struct
import tracemalloc

import pytest

from ceph_tpu.parallel import crush
from ceph_tpu.parallel import messages as M
from ceph_tpu.parallel import osdmap as osdmap_mod
from ceph_tpu.parallel.messenger import Messenger
from ceph_tpu.store import object_store as object_store_mod
from ceph_tpu.store.memstore import MemStore
from ceph_tpu.store.object_store import Transaction
from ceph_tpu.tools import objectstore_tool
from ceph_tpu.utils.encoding import (SCATTER_MIN, DecodeError, Decoder,
                                     Encoder)
from ceph_tpu.utils.msgr_telemetry import telemetry


class RefEncoder:
    def __init__(self) -> None:
        self._parts: list[bytes] = []

    def u8(self, v): self._parts.append(struct.pack("<B", v)); return self
    def u16(self, v): self._parts.append(struct.pack("<H", v)); return self
    def u32(self, v): self._parts.append(struct.pack("<I", v)); return self
    def u64(self, v): self._parts.append(struct.pack("<Q", v)); return self
    def i32(self, v): self._parts.append(struct.pack("<i", v)); return self
    def i64(self, v): self._parts.append(struct.pack("<q", v)); return self
    def f64(self, v): self._parts.append(struct.pack("<d", v)); return self
    def bool(self, v): return self.u8(1 if v else 0)

    def bytes(self, v):
        self.u32(len(v)); self._parts.append(bytes(v)); return self

    def str(self, v): return self.bytes(v.encode())

    def list(self, vals, item_fn):
        self.u32(len(vals))
        for v in vals:
            item_fn(self, v)
        return self

    def map(self, d, key_fn, val_fn):
        self.u32(len(d))
        for k in sorted(d):
            key_fn(self, k)
            val_fn(self, d[k])
        return self

    def str_map(self, d):
        return self.map(d, RefEncoder.str, RefEncoder.str)

    def section(self, version, body, compat=1):
        payload = body.getvalue()
        self.u8(version)
        self.u8(compat)
        self.bytes(payload)
        return self

    def getparts(self): return list(self._parts)
    def getvalue(self): return b"".join(self._parts)


class RefDecoder:
    def __init__(self, buf, off=0):
        self._buf = buf
        self._off = off

    def _take(self, n):
        if self._off + n > len(self._buf):
            raise DecodeError("short buffer")
        v = self._buf[self._off:self._off + n]
        self._off += n
        return v

    def u8(self): return struct.unpack("<B", self._take(1))[0]
    def u32(self): return struct.unpack("<I", self._take(4))[0]
    def u64(self): return struct.unpack("<Q", self._take(8))[0]
    def bytes(self): return self._take(self.u32())
    def str(self): return self.bytes().decode()

    def section(self, max_supported):
        version = self.u8()
        compat = self.u8()
        body = self.bytes()
        if compat > max_supported:
            raise DecodeError("compat")
        return version, RefDecoder(body)

    def eof(self): return self._off >= len(self._buf)


_REF_ENC = {kind: getattr(RefEncoder, kind) for kind in M._ENC
            if hasattr(RefEncoder, kind)}
_REF_ENC.update({
    "bytes_map": lambda e, v: e.map(v, RefEncoder.str, RefEncoder.bytes),
    "i32_list": lambda e, v: e.list(v, RefEncoder.i32),
    "u64_list": lambda e, v: e.list(v, RefEncoder.u64),
    "str_list": lambda e, v: e.list(v, RefEncoder.str),
    "bytes_list": lambda e, v: e.list(v, RefEncoder.bytes),
})


def ref_payload(msg: M.Message) -> bytes:
    body = RefEncoder()
    for name, kind in msg.FIELDS:
        _REF_ENC[kind](body, getattr(msg, name))
    return RefEncoder().section(1, body).getvalue()


# -- seeded values: lengths on both sides of the scatter threshold -----

_SIZES = (0, 1, 300, SCATTER_MIN - 1, SCATTER_MIN, 3 * SCATTER_MIN + 5)


def _blob(rng: random.Random) -> bytes:
    return rng.randbytes(rng.choice(_SIZES))


def _value(kind: str, rng: random.Random):
    if kind == "bytes":
        return _blob(rng)
    if kind == "str":
        return "s" * rng.choice((0, 3, 40))
    if kind == "bool":
        return rng.random() < 0.5
    if kind == "f64":
        return rng.random()
    if kind in ("u8", "u16", "u32", "u64"):
        return rng.getrandbits(int(kind[1:]))
    if kind in ("i32", "i64"):
        bits = int(kind[1:])
        return rng.getrandbits(bits) - (1 << (bits - 1))
    if kind == "str_map":
        return {f"k{i}": _value("str", rng) for i in range(3)}
    if kind == "bytes_map":
        return {f"k{i}": _blob(rng) for i in range(3)}
    if kind.endswith("_list"):
        return [_value(kind[:-5], rng) for _ in range(rng.choice((0, 4)))]
    raise KeyError(kind)


def seeded_message(cls, seed: int = 31) -> M.Message:
    rng = random.Random(f"{cls.__name__}/{seed}")
    return cls(**{name: _value(kind, rng) for name, kind in cls.FIELDS})


def every_op_transaction(rng: random.Random) -> Transaction:
    big, small = rng.randbytes(3 * SCATTER_MIN + 5), rng.randbytes(300)
    t = Transaction()
    t.create_collection("c")
    t.touch("c", "o")
    t.write("c", "o", 0, big)
    t.write("c", "o", len(big), small)
    t.zero("c", "o", 4, 100)
    t.truncate("c", "o", 1 << 20)
    t.setattr("c", "o", "hinfo", small)
    t.setattr("c", "o", "bulk", rng.randbytes(SCATTER_MIN))
    t.rmattr("c", "o", "bulk")
    t.omap_set("c", "o", {"k1": small, "k2": big, "k3": b""})
    t.omap_rm("c", "o", ["k1"])
    t.omap_rmrange("c", "o", "k")
    t.touch("c", "gone")
    t.remove("c", "gone")
    t.create_collection("d")
    t.remove_collection("d")
    assert {op[0] for op in t.ops} == set(range(1, 13))   # every OP_*
    return t


def _check_message(cls, monkeypatch, tmp_path) -> None:
    for seed in (31, 32, 33):
        msg = seeded_message(cls, seed)
        want = ref_payload(msg)
        parts = msg.encode_payload_parts()
        assert b"".join(parts) == want == msg.encode_payload()
        # small fields ride in joined runs; what is large rides alone,
        # by reference
        large = [p for p in parts if len(p) >= SCATTER_MIN]
        assert len(parts) <= 2 * len(large) + 1
        out = M.decode_message(cls.MSG_TYPE, want)
        assert type(out) is cls
        for name, _ in cls.FIELDS:
            assert getattr(out, name) == getattr(msg, name), name


def _check_transaction(cls, monkeypatch, tmp_path) -> None:
    txn = every_op_transaction(random.Random(31))
    new = txn.encode()
    assert bytes(txn.encode_parts()) == new
    assert any(p is txn.ops[2][4] for p in txn.encode_parts().parts)
    with monkeypatch.context() as mp:
        mp.setattr(object_store_mod, "Encoder", RefEncoder)
        want = txn.encode()
    assert new == want
    assert Transaction.decode(want).ops == txn.ops
    # nested in a message as its parts: the same bytes as nested joined
    by_parts = M.MECSubWriteBatch(txns=[txn.encode_parts()], tids=[1])
    by_bytes = M.MECSubWriteBatch(txns=[want], tids=[1])
    assert by_parts.encode_payload() == ref_payload(by_bytes)


def _check_osdmap(cls, monkeypatch, tmp_path) -> None:
    m = osdmap_mod.OSDMap()
    m.crush = crush.build_flat_map(6)
    for o in range(6):
        m.add_osd(o, addr=f"127.0.0.1:{6800 + o}")
        m.mark_up(o, f"127.0.0.1:{6800 + o}")
    m.create_pool("ecpool", pg_num=8, rule="data", size=5, min_size=4,
                  ec_profile={"plugin": "jerasure", "k": "4", "m": "1"})
    m.blocklist["client.1:77"] = 12.5
    new = m.encode()
    with monkeypatch.context() as mp:
        mp.setattr(osdmap_mod, "Encoder", RefEncoder)
        want = m.encode()
    assert new == want
    assert osdmap_mod.OSDMap.decode(want).encode() == want


def _check_export(cls, monkeypatch, tmp_path) -> None:
    store = MemStore()
    rng = random.Random(31)
    txn = Transaction().create_collection("pg_1_0")
    for i, n in enumerate(_SIZES):
        txn.touch("pg_1_0", f"o{i}")
        txn.write("pg_1_0", f"o{i}", 0, rng.randbytes(n))
        txn.setattr("pg_1_0", f"o{i}", "v", bytes([i]))
        txn.omap_set("pg_1_0", f"o{i}", {"k": b"v" * i})
    store.queue_transaction(txn)
    new_file, ref_file = tmp_path / "new.export", tmp_path / "ref.export"
    args = argparse.Namespace(cid="pg_1_0", file=str(new_file))
    assert objectstore_tool.op_export(store, args) == 0
    with monkeypatch.context() as mp:
        mp.setattr(objectstore_tool, "Encoder", RefEncoder)
        args.file = str(ref_file)
        assert objectstore_tool.op_export(store, args) == 0
    assert new_file.read_bytes() == ref_file.read_bytes()
    back = MemStore()
    assert objectstore_tool.op_import(back, args) == 0
    for oid in store.list_objects("pg_1_0"):
        assert back.read("pg_1_0", oid) == store.read("pg_1_0", oid)
        assert back.getattrs("pg_1_0", oid) == store.getattrs("pg_1_0", oid)
        assert back.omap_get("pg_1_0", oid) == store.omap_get("pg_1_0", oid)


_CASES = [(cls.__name__, _check_message, cls)
          for _, cls in sorted(M._REGISTRY.items())]
_CASES += [("Transaction", _check_transaction, None),
           ("OSDMap", _check_osdmap, None),
           ("objectstore_tool_export", _check_export, None)]


@pytest.mark.parametrize("check,cls", [c[1:] for c in _CASES],
                         ids=[c[0] for c in _CASES])
def test_encoding_equals_the_joined_form_byte_for_byte(
        check, cls, monkeypatch, tmp_path):
    """Every registered message type with seeded fields, a
    Transaction of every op code, an OSDMap and an objectstore_tool
    export: the encoding is the plain reference's byte for byte and
    decodes to equal fields."""
    check(cls, monkeypatch, tmp_path)


# -- what a reader raises or skips, against the plain reference --------

def _framed(version: int, compat: int, length: int, body: bytes) -> bytes:
    return struct.pack("<BBI", version, compat, length) + body


def _unknown_tail():
    body = Encoder().u32(42).str("known").u64(7).str("from the future")
    buf = Encoder().section(3, body, compat=1).u32(99).getvalue()
    return buf, [("section", 1), ("sub", "u32"), ("sub", "str"),
                 ("outer", "u32")], [3, 42, "known", 99]


def _compat_above_reader():
    return _framed(5, 4, 4, b"\x01\x00\x00\x00"), [("section", 3)], \
        ["DecodeError"]


def _body_shorter_than_its_length():
    return _framed(1, 1, 100, b"\x00" * 50), [("section", 1)], \
        ["DecodeError"]


def _field_past_the_window():
    # the field's length reaches into the bytes that FOLLOW the section:
    # a reader that took the outer buffer for the body would succeed
    body = struct.pack("<I", 20) + b"ab"
    buf = _framed(1, 1, len(body), body) + b"x" * 64
    return buf, [("section", 1), ("sub", "bytes"), ("outer", "u8")], \
        [1, "DecodeError", ord("x")]


@pytest.mark.parametrize("case", [
    _unknown_tail, _compat_above_reader, _body_shorter_than_its_length,
    _field_past_the_window], ids=lambda f: f.__name__.lstrip("_"))
def test_reader_raises_or_skips_as_the_reference_does(case):
    buf, script, want = case()

    def outcome(decoder_cls) -> list:
        outer, sub, got = decoder_cls(buf), None, []
        for who, what in script:
            try:
                if who == "section":
                    version, sub = outer.section(what)
                    got.append(version)
                else:
                    got.append(getattr(sub if who == "sub" else outer,
                                       what)())
            except DecodeError:
                got.append("DecodeError")
        return got

    assert outcome(Decoder) == outcome(RefDecoder) == want


# -- the count the mechanism is about ----------------------------------

def _peak_over(fn) -> tuple:
    """``fn()`` and the most memory it held at once beyond what was
    held when it began."""
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        base = tracemalloc.get_traced_memory()[0]
        out = fn()
        return out, tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()


def _bulk_message(name: str):
    """The message and the sender's bulk object inside it."""
    data4m = random.Random(1).randbytes(4 << 20)
    shard = data4m[:512 << 10]
    if name == "MOSDOp_4m":
        return M.MOSDOp(tid=1, client="c", oid="o", op=1, data=data4m), \
            data4m
    if name == "MOSDOpReply_4m":
        return M.MOSDOpReply(tid=1, data=data4m), data4m
    if name == "MECSubReadReply_512k":
        return M.MECSubReadReply(tid=1, oid="o", data=shard,
                                 attrs={"hinfo": b"h" * 60}), shard
    txn = Transaction().write("pg_1_0s3", "obj", 0, shard)
    txn.setattr("pg_1_0s3", "obj", "hinfo", b"h" * 60)
    return M.MECSubWriteBatch(
        tid=1, tids=[2], pools=[1], pss=[0], shards=[3], oids=["obj"],
        versions=[9], txns=[txn.encode_parts()], traces=[""],
        flows=[""]), shard


@pytest.mark.parametrize("name", [
    "MOSDOp_4m", "MOSDOpReply_4m", "MECSubReadReply_512k",
    "MECSubWriteBatch_one_512k_txn"])
def test_bulk_message_costs_two_payload_buffers(name):
    """Serialize + decode allocates one payload-size buffer a side
    (the join; the receiver's own ``bytes``), where a section that
    joined its body and sliced it out again made it four; and no
    decoded field is the sender's object or the payload."""
    msg, bulk = _bulk_message(name)
    size = len(bulk)
    payload, ser_peak = _peak_over(
        lambda: b"".join(msg.encode_payload_parts()))
    out, dec_peak = _peak_over(
        lambda: M.decode_message(msg.MSG_TYPE, payload))
    slack = 64 << 10
    assert size <= ser_peak <= size + slack
    assert size <= dec_peak <= size + slack
    decoded = out.txns if isinstance(out, M.MECSubWriteBatch) \
        else [out.data]
    for got in decoded:
        assert type(got) is bytes
        assert got is not payload and got is not bulk
    if isinstance(out, M.MECSubWriteBatch):
        written = Transaction.decode(out.txns[0]).ops[0][4]
        assert written == bulk and written is not bulk
    else:
        assert out.data == bulk


# -- the per-type serialize + decode counter ---------------------------

class _Sink:
    def __init__(self) -> None:
        self.got: list = []

    def __call__(self, msg, conn) -> None:
        self.got.append(msg)

    def wait(self, n: int, timeout: float = 5.0) -> bool:
        import time
        deadline = time.monotonic() + timeout
        while len(self.got) < n and time.monotonic() < deadline:
            time.sleep(0.01)
        return len(self.got) >= n


_DATA_PATH_TYPES = [M.MOSDOp, M.MOSDOpReply, M.MECSubWriteBatch,
                    M.MECSubWriteBatchReply, M.MECSubRead,
                    M.MECSubReadReply, M.MPGPush]


def _loopback_rows() -> dict:
    by_type = telemetry().snapshot()["by_type"]
    rows = {t: by_type.get(str(t.MSG_TYPE), {}) for t in _DATA_PATH_TYPES}
    return {t: (r.get("loopback", 0), r.get("loopback_bytes", 0),
                r.get("loopback_codec_s", 0.0)) for t, r in rows.items()}


@pytest.mark.parametrize("loopback", [True, False],
                         ids=["loopback", "tcp"])
def test_loopback_codec_counter_counts_the_loopback_alone(
        loopback, monkeypatch):
    """A loopback round trip of each data-path type adds one count,
    its payload bytes and some serialize + decode seconds to its row
    of the telemetry's snapshot; the TCP path adds nothing there."""
    monkeypatch.setenv("CEPH_TPU_MSGR_LOOPBACK", "1" if loopback else "0")
    a, b = Messenger("osd.31"), Messenger("osd.32")
    a.bind(); b.bind()
    try:
        sink = _Sink()
        b.set_dispatcher(sink)
        before = _loopback_rows()
        msgs = [seeded_message(t) for t in _DATA_PATH_TYPES]
        for m in msgs:
            a.send_message(m, b.addr)
        assert sink.wait(len(msgs))
        after = _loopback_rows()
    finally:
        a.shutdown(); b.shutdown()
    for m in msgs:
        n0, bytes0, s0 = before[type(m)]
        n1, bytes1, s1 = after[type(m)]
        if loopback:
            assert (n1 - n0, bytes1 - bytes0) == \
                (1, len(m.encode_payload())), type(m).__name__
            assert s1 > s0
        else:
            assert (n1, bytes1, s1) == (n0, bytes0, s0)
