"""An RBD image with a data pool (ISSUE 35): header and directory in
the replicated pool, ``rbd_data.<image>.<objno:016x>`` in the EC pool,
one OSD op per extent of a write, the image's size from its header,
and 16 threads writing disjoint extents of one handle (``rbd bench``'s
shape) read back byte for byte."""

import threading

import numpy as np
import pytest

from ceph_tpu.client.rados import IoCtx
from ceph_tpu.client.striper import FileLayout
from ceph_tpu.qa.cluster import MiniCluster
from ceph_tpu.services.rbd import RBD, DataObjects, Image

#: rbd_default_order 22: 4 MiB objects, one stripe unit each
ORDER22 = FileLayout(stripe_unit=1 << 22, stripe_count=1,
                     object_size=1 << 22)
BLOCK = 4096


@pytest.fixture(scope="module")
def pools():
    with MiniCluster(n_osds=6) as c:
        c.create_pool("rbd", pg_num=4, size=3)
        c.create_ec_pool("ec", k=4, m=2, pg_num=8, backend="jax")
        rados = c.client()
        yield c, rados.open_ioctx("rbd"), rados.open_ioctx("ec")


def test_header_in_the_replicated_pool_data_in_the_ec_pool(pools):
    _c, rbd_io, ec_io = pools
    img = RBD(rbd_io).create("disk", 3 << 22, layout=ORDER22,
                             data_pool="ec")
    assert isinstance(img._data, DataObjects)
    assert img.data_io.pool_name == "ec"
    blob = np.random.default_rng(1).bytes(5 * BLOCK)
    img.write((1 << 22) - 2 * BLOCK, blob)          # spans two objects
    assert img.read((1 << 22) - 2 * BLOCK, len(blob)) == blob
    assert img.read(0, BLOCK) == bytes(BLOCK)       # never written
    assert "rbd_header.disk" in rbd_io.list_objects()
    assert "rbd_directory" in rbd_io.list_objects()
    assert not [o for o in rbd_io.list_objects()
                if o.startswith("rbd_data.disk")]
    # no stream meta object, no generation xattr: the data alone
    assert sorted(o for o in ec_io.list_objects()
                  if o.startswith("rbd_data.disk")) == [
        "rbd_data.disk.0000000000000000",
        "rbd_data.disk.0000000000000001"]
    assert "gc_tag" not in ec_io.getxattrs(
        "rbd_data.disk.0000000000000000")
    # the size is the header's, also for another handle
    again = RBD(rbd_io).open("disk")
    assert again.size() == 3 << 22 and again.read(
        (1 << 22) - 2 * BLOCK, len(blob)) == blob
    assert RBD(rbd_io).list() == ["disk"]
    RBD(rbd_io).remove("disk")
    assert not [o for o in ec_io.list_objects()
                if o.startswith("rbd_data.disk")]


def test_a_4k_write_is_one_osd_op(pools, monkeypatch):
    c, rbd_io, _ec_io = pools
    img = RBD(rbd_io).create("one", 2 << 22, layout=ORDER22,
                             data_pool="ec")
    img.write(BLOCK, bytes(BLOCK))          # the object exists
    sent = []
    real = IoCtx._submit

    def counted(self, oid, op, **kw):
        sent.append((self.pool_name, oid, op))
        return real(self, oid, op, **kw)

    monkeypatch.setattr(IoCtx, "_submit", counted)

    def osd_ops() -> int:
        return sum(osd.logger.get("op") for osd in c.osds.values())

    before = osd_ops()
    img.write(3 * BLOCK, b"\x5a" * BLOCK)
    assert sent == [("ec", "rbd_data.one.0000000000000000",
                     sent[0][2])]
    assert osd_ops() - before == 1
    monkeypatch.undo()
    assert img.read(3 * BLOCK, BLOCK) == b"\x5a" * BLOCK
    RBD(rbd_io).remove("one")


def test_16_threads_on_one_handle_read_back_byte_for_byte(pools):
    _c, rbd_io, _ec_io = pools
    size = 2 << 22
    img = RBD(rbd_io).create("bench", size, layout=ORDER22,
                             data_pool="ec")
    rng = np.random.default_rng(35)
    blocks = rng.permutation(size // BLOCK)[:16 * 6].reshape(16, 6)
    data = {int(b): rng.bytes(BLOCK) for b in blocks.ravel()}
    errors = []

    def writer(mine):
        try:
            for b in mine:
                img.write(int(b) * BLOCK, data[int(b)])
        except Exception as exc:            # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(row,))
               for row in blocks]
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    assert not errors and not any(th.is_alive() for th in threads)
    want = bytearray(size)
    for b, buf in data.items():
        want[b * BLOCK:(b + 1) * BLOCK] = buf
    assert img.read(0, size) == bytes(want)
    assert Image(rbd_io, "bench").read(0, size) == bytes(want)
    RBD(rbd_io).remove("bench")


def test_a_shrink_discards_the_tail_of_a_data_pool_image(pools):
    _c, rbd_io, ec_io = pools
    img = RBD(rbd_io).create("shrink", 2 << 22, layout=ORDER22,
                             data_pool="ec")
    img.write(0, b"\x11" * (2 << 22))
    img.resize(BLOCK)                       # into object 0
    img.resize(2 << 22)                     # and back
    assert img.read(0, BLOCK) == b"\x11" * BLOCK
    assert img.read(BLOCK, 2 * BLOCK) == bytes(2 * BLOCK)
    assert img.read((1 << 22) + BLOCK, BLOCK) == bytes(BLOCK)
    assert "rbd_data.shrink.0000000000000001" not in \
        ec_io.list_objects()
    RBD(rbd_io).remove("shrink")


def test_a_rollback_to_a_larger_snapshot_takes_its_size(pools):
    _c, rbd_io, _ec_io = pools
    img = RBD(rbd_io).create("roll", 2 << 22, layout=ORDER22,
                             data_pool="ec")
    img.write(0, b"\x22" * (2 << 22))
    img.snap_create("s")
    img.resize(1 << 22)
    img.snap_rollback("s")
    assert img.size() == 2 << 22 and img._data.size == 2 << 22
    assert img.read((1 << 22) + BLOCK, BLOCK) == b"\x22" * BLOCK
    # the handle's shrink sees the rolled-back size: object 1 goes
    img.resize(1 << 22)
    img.resize(2 << 22)
    assert img.read((1 << 22) + BLOCK, BLOCK) == bytes(BLOCK)
    RBD(rbd_io).remove("roll")
