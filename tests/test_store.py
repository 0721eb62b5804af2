"""ObjectStore tests — MemStore + BlockStore behavioral parity, txn
atomicity/durability, checksum-on-read, WAL replay, EIO injection.

Mirrors src/test/objectstore/ store_test.cc patterns: same scenarios run
against every backend (the reference parameterizes over store types)."""

import os

import pytest

from ceph_tpu.store import (
    BlockStore,
    EIOError,
    MemStore,
    Transaction,
    create_store,
)
from ceph_tpu.store.kv import FileDB, WriteBatch
from ceph_tpu.store.object_store import NoSuchCollection, NoSuchObject


@pytest.fixture(params=["memstore", "blockstore", "kstore"])
def store(request, tmp_path):
    s = create_store(request.param, str(tmp_path / "store"))
    s.mount()
    yield s
    s.umount()


CID = "pg_1.0s0"


def test_create_write_read(store):
    t = Transaction()
    t.create_collection(CID)
    t.write(CID, "obj", 0, b"hello world")
    committed = []
    store.queue_transaction(t, on_commit=lambda: committed.append(1))
    assert committed == [1]
    assert store.read(CID, "obj") == b"hello world"
    assert store.read(CID, "obj", 6, 5) == b"world"
    assert store.stat(CID, "obj") == 11


def test_overwrite_and_extend(store):
    store.queue_transaction(
        Transaction().create_collection(CID).write(CID, "o", 0, b"AAAAAAAA"))
    store.queue_transaction(Transaction().write(CID, "o", 4, b"BBBB"))
    store.queue_transaction(Transaction().write(CID, "o", 10, b"CC"))
    # gap [8,10) reads as zeros
    assert store.read(CID, "o") == b"AAAABBBB\x00\x00CC"


def test_zero_truncate_remove(store):
    store.queue_transaction(
        Transaction().create_collection(CID).write(CID, "o", 0, b"X" * 16))
    store.queue_transaction(Transaction().zero(CID, "o", 4, 8))
    assert store.read(CID, "o") == b"XXXX" + b"\x00" * 8 + b"XXXX"
    store.queue_transaction(Transaction().truncate(CID, "o", 6))
    assert store.read(CID, "o") == b"XXXX\x00\x00"
    store.queue_transaction(Transaction().remove(CID, "o"))
    with pytest.raises(NoSuchObject):
        store.read(CID, "o")


def test_attrs_and_omap(store):
    t = Transaction().create_collection(CID)
    t.touch(CID, "o")
    t.setattr(CID, "o", "hinfo", b"\x01\x02")
    t.omap_set(CID, "o", {"k1": b"v1", "k2": b"v2"})
    store.queue_transaction(t)
    assert store.getattr(CID, "o", "hinfo") == b"\x01\x02"
    assert store.getattrs(CID, "o") == {"hinfo": b"\x01\x02"}
    assert store.omap_get(CID, "o") == {"k1": b"v1", "k2": b"v2"}
    store.queue_transaction(
        Transaction().rmattr(CID, "o", "hinfo").omap_rm(CID, "o", ["k1"]))
    assert store.getattrs(CID, "o") == {}
    assert store.omap_get(CID, "o") == {"k2": b"v2"}


def test_listing(store):
    t = Transaction().create_collection(CID).create_collection("pg_1.1s0")
    t.touch(CID, "b").touch(CID, "a").touch("pg_1.1s0", "z")
    store.queue_transaction(t)
    assert store.list_collections() == [CID, "pg_1.1s0"]
    assert store.list_objects(CID) == ["a", "b"]
    with pytest.raises(NoSuchCollection):
        store.list_objects("nope")


def test_missing_collection_rejected(store):
    with pytest.raises(NoSuchCollection):
        store.queue_transaction(Transaction().write("nope", "o", 0, b"x"))


def test_remove_then_recreate_in_one_txn(store):
    store.queue_transaction(
        Transaction().create_collection(CID)
        .write(CID, "o", 0, b"old").setattr(CID, "o", "a", b"1"))
    t = Transaction().remove(CID, "o").write(CID, "o", 0, b"new")
    store.queue_transaction(t)
    assert store.read(CID, "o") == b"new"
    assert store.getattrs(CID, "o") == {}  # attrs did not survive remove


def test_eio_injection(store):
    store.queue_transaction(
        Transaction().create_collection(CID).write(CID, "o", 0, b"data"))
    store.inject_data_error(CID, "o")
    with pytest.raises(EIOError):
        store.read(CID, "o")
    store.clear_data_error(CID, "o")
    assert store.read(CID, "o") == b"data"


# -- BlockStore-specific durability/corruption ------------------------

def test_blockstore_remount_preserves_state(tmp_path):
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mount()
    s.queue_transaction(
        Transaction().create_collection(CID)
        .write(CID, "o", 0, b"persistent").setattr(CID, "o", "v", b"7"))
    s.umount()
    s2 = BlockStore(path)
    s2.mount()
    assert s2.read(CID, "o") == b"persistent"
    assert s2.getattr(CID, "o", "v") == b"7"
    s2.umount()


def test_blockstore_wal_replay_without_clean_close(tmp_path):
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mount()
    s.queue_transaction(
        Transaction().create_collection(CID).write(CID, "o", 0, b"walled"))
    # simulate crash: drop handles without umount/compact
    s._data.close()
    s._db._wal.close()
    s2 = BlockStore(path)
    s2.mount()
    assert s2.read(CID, "o") == b"walled"
    s2.umount()


def test_blockstore_torn_wal_tail_ignored(tmp_path):
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mount()
    s.queue_transaction(
        Transaction().create_collection(CID).write(CID, "o", 0, b"good"))
    s._data.close()
    s._db._wal.close()
    # corrupt: append a torn/garbage record to the WAL
    with open(os.path.join(path, "db", "wal"), "ab") as f:
        f.write(b"\x40\x00\x00\x00\xde\xad\xbe\xefpartial")
    s2 = BlockStore(path)
    s2.mount()
    assert s2.read(CID, "o") == b"good"  # good prefix replayed
    s2.umount()


def test_blockstore_bitrot_detected_on_read(tmp_path):
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mount()
    s.queue_transaction(
        Transaction().create_collection(CID)
        .write(CID, "o", 0, b"S" * 4096))
    s.umount()
    # flip one byte in the data file (silent media corruption)
    with open(os.path.join(path, "data"), "r+b") as f:
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0xFF]))
    s2 = BlockStore(path)
    s2.mount()
    with pytest.raises(EIOError):
        s2.read(CID, "o")
    s2.umount()


def test_blockstore_wal_commit_after_torn_tail_survives(tmp_path):
    # regression: a torn tail must be truncated on mount, or commits
    # appended after it are lost on the NEXT replay
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mount()
    s.queue_transaction(
        Transaction().create_collection(CID).write(CID, "o1", 0, b"one"))
    s._data.close()
    s._db._wal.close()
    with open(os.path.join(path, "db", "wal"), "ab") as f:
        f.write(b"\x40\x00\x00\x00\xde\xad\xbe\xefpartial")  # torn record
    s2 = BlockStore(path)
    s2.mount()
    s2.queue_transaction(Transaction().write(CID, "o2", 0, b"two"))
    s2._data.close()
    s2._db._wal.close()
    s3 = BlockStore(path)
    s3.mount()
    assert s3.read(CID, "o1") == b"one"
    assert s3.read(CID, "o2") == b"two"  # the post-tear commit
    s3.umount()


def test_remove_collection_same_txn_leaves_no_phantom(store):
    t = Transaction().create_collection(CID)
    t.write(CID, "ghost", 0, b"boo")
    t.remove_collection(CID)
    store.queue_transaction(t)
    assert CID not in store.list_collections()
    # recreate: the ghost must not resurrect
    store.queue_transaction(Transaction().create_collection(CID))
    assert store.list_objects(CID) == []


def test_failed_txn_applies_nothing(store):
    store.queue_transaction(Transaction().create_collection(CID))
    t = Transaction().write(CID, "o", 0, b"x").rmattr(CID, "missing", "a")
    with pytest.raises(NoSuchObject):
        store.queue_transaction(t)
    assert not store.exists(CID, "o")  # all-or-nothing


def test_filedb_compact_and_iterate(tmp_path):
    db = FileDB(str(tmp_path / "db"))
    db.submit(WriteBatch().put("a/1", b"x").put("a/2", b"y").put("b/1", b"z"))
    db.submit(WriteBatch().delete("a/2"))
    assert [k for k, _ in db.iterate("a/")] == ["a/1"]
    db.compact()
    assert db.get("a/1") == b"x" and db.get("a/2") is None
    db.close()
    db2 = FileDB(str(tmp_path / "db"))
    assert db2.get("b/1") == b"z"
    db2.close()


def test_kstore_remount_preserves_state(tmp_path):
    """kv-only store durability: data/attrs/omap survive remount via
    the FileDB log (src/os/kstore role)."""
    from ceph_tpu.store.kstore import STRIPE
    path = str(tmp_path / "ks")
    s = create_store("kstore", path)
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    t.touch(CID, "o")
    big = bytes(range(256)) * ((STRIPE * 2 + 999) // 256)
    t.write(CID, "o", 0, big)                 # spans 3 stripe records
    t.setattr(CID, "o", "v", b"\x07")
    t.omap_set(CID, "o", {"k": b"v"})
    done = []
    s.queue_transaction(t, on_commit=lambda: done.append(1))
    assert done
    # partial overwrite + truncate in one txn sees its own writes
    t2 = Transaction()
    t2.write(CID, "o", STRIPE - 10, b"X" * 20)
    t2.truncate(CID, "o", STRIPE + 5)
    s.queue_transaction(t2)
    expect = bytearray(big[:STRIPE + 5])
    expect[STRIPE - 10:STRIPE + 5] = b"X" * 15
    assert s.read(CID, "o") == bytes(expect)
    s.umount()
    s2 = create_store("kstore", path)
    s2.mount()
    assert s2.read(CID, "o") == bytes(expect)
    assert s2.getattr(CID, "o", "v") == b"\x07"
    assert s2.omap_get(CID, "o") == {"k": b"v"}
    s2.umount()


def test_kstore_slash_oids_do_not_cross(tmp_path):
    """Regression: rgw-style oids containing '/' ('b/k' vs 'b/k/s')
    must not share key prefixes — removing one object's attrs/omap
    must not touch the other's."""
    s = create_store("kstore", str(tmp_path / "ks2"))
    s.mount()
    t = Transaction().create_collection(CID)
    for oid in ("b/k", "b/k/s"):
        t.touch(CID, oid)
        t.write(CID, oid, 0, oid.encode())
        t.setattr(CID, oid, "tag", oid.encode())
        t.omap_set(CID, oid, {"m": oid.encode()})
    s.queue_transaction(t)
    assert sorted(s.list_objects(CID)) == ["b/k", "b/k/s"]
    assert s.getattrs(CID, "b/k") == {"tag": b"b/k"}
    s.queue_transaction(Transaction().remove(CID, "b/k"))
    assert s.list_objects(CID) == ["b/k/s"]
    assert s.read(CID, "b/k/s") == b"b/k/s"
    assert s.getattrs(CID, "b/k/s") == {"tag": b"b/k/s"}
    assert s.omap_get(CID, "b/k/s") == {"m": b"b/k/s"}
    s.umount()


def test_memstore_commits_from_many_threads_create_objects_safely():
    """Op-wq workers and the engine's ship thread commit to one store:
    a txn's validation walks every collection while another creates
    objects. Many threads, a short switch interval, a time bound; every
    txn applies and none raises."""
    import sys
    import threading

    store = MemStore()
    store.mount()
    setup = Transaction()
    for c in range(4):
        setup.create_collection(f"c{c}")
    store.queue_transaction(setup)
    errors, done = [], []

    def writer(t):
        try:
            for i in range(300):
                txn = Transaction()
                txn.write(f"c{i % 4}", f"t{t}_{i}", 0, b"x")
                if i % 2:
                    store.queue_transaction(txn)
                else:
                    store.queue_transaction_group([(txn, None)])
            done.append(t)
        except Exception as exc:            # pragma: no cover
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(th.is_alive() for th in threads)
    assert len(done) == 16
    assert sum(len(store.list_objects(f"c{c}")) for c in range(4)) \
        == 16 * 300
