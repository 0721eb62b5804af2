"""Integration: full in-process cluster — mon + OSDs + client.

The qa/standalone/erasure-code/test-erasure-code.sh role: boot daemons,
create pools (replicated + every EC plugin), write/read/remove through
the real client stack, kill OSDs and verify degraded reads and
recovery (thrash-lite).
"""

import concurrent.futures
import os

import pytest

from ceph_tpu.client.rados import RadosError
from ceph_tpu.qa.cluster import MiniCluster


@pytest.fixture(scope="module")
def cluster():
    with MiniCluster(n_osds=4) as c:
        yield c


@pytest.fixture(scope="module")
def rados(cluster):
    return cluster.client()


def test_replicated_pool_io(cluster, rados):
    cluster.create_pool("rep", pg_num=4, size=3)
    io = rados.open_ioctx("rep")
    payload = os.urandom(100_000)
    v = io.write_full("obj1", payload)
    assert v >= 1
    assert io.read("obj1") == payload
    assert io.stat("obj1") == len(payload)
    # ranged read
    assert io.read("obj1", length=100, offset=50) == payload[50:150]
    # overwrite
    io.write_full("obj1", b"short")
    assert io.read("obj1") == b"short"
    io.remove("obj1")
    with pytest.raises(RadosError):
        io.read("obj1")


def test_replicated_many_objects(cluster, rados):
    cluster.create_pool("rep_many", pg_num=8, size=2)
    io = rados.open_ioctx("rep_many")
    blobs = {f"o{i}": os.urandom(1000 + i) for i in range(20)}
    for oid, blob in blobs.items():
        io.write_full(oid, blob)
    assert io.list_objects() == sorted(blobs)
    for oid, blob in blobs.items():
        assert io.read(oid) == blob


def test_ec_pool_io(cluster, rados):
    cluster.create_ec_pool("ecpool", k=2, m=1, plugin="jerasure",
                           pg_num=4)
    io = rados.open_ioctx("ecpool")
    payload = os.urandom(300_000)
    io.write_full("big", payload)
    assert io.read("big") == payload
    assert io.stat("big") == len(payload)
    # small object (sub-stripe, exercises padding)
    io.write_full("small", b"x")
    assert io.read("small") == b"x"
    # empty object
    io.write_full("empty", b"")
    assert io.read("empty") == b""
    io.remove("small")
    with pytest.raises(RadosError):
        io.stat("small")


def test_ec_rmw_write(cluster, rados):
    io = rados.open_ioctx("ecpool")
    io.write_full("rmw", b"A" * 10_000)
    io.write("rmw", b"B" * 100, offset=5000)
    data = io.read("rmw")
    assert data[:5000] == b"A" * 5000
    assert data[5000:5100] == b"B" * 100
    assert data[5100:] == b"A" * 4900
    io.append("rmw", b"C" * 50)
    assert io.read("rmw")[-50:] == b"C" * 50
    assert io.stat("rmw") == 10_050


def test_ec_isa_and_shec_pools(cluster, rados):
    for name, plugin, kw in (
            ("isa_pool", "isa", {}),
            ("shec_pool", "shec", {"c": 1}),
    ):
        cluster.create_ec_pool(name, k=2, m=1, plugin=plugin, pg_num=2,
                               **kw)
        io = rados.open_ioctx(name)
        payload = os.urandom(50_000)
        io.write_full("obj", payload)
        assert io.read("obj") == payload


@pytest.mark.parametrize("kind", ["ec", "replicated"])
def test_per_pg_ordering_under_concurrent_connections(cluster, kind):
    """Several independent client connections hammer ONE PG
    (pg_num=1) with appends: the PG must keep every append atomic
    (uniform runs in the object) and each connection's own ops in
    issue order."""
    pool_name = f"ord_{kind}"
    if kind == "ec":
        cluster.create_ec_pool(pool_name, k=2, m=1, pg_num=1)
    else:
        cluster.create_pool(pool_name, pg_num=1, size=3)
    setup_io = cluster.client().open_ioctx(pool_name)
    setup_io.op_timeout = 30.0
    setup_io.write_full("log", b"")
    n_conns, per_conn = 4, 6

    def hammer(c):
        client = cluster.client()
        io = client.open_ioctx(pool_name)
        io.op_timeout = 30.0
        for s in range(per_conn):
            io.append("log", bytes([16 * c + s]) * 5)
        client.shutdown()

    with concurrent.futures.ThreadPoolExecutor(n_conns) as pool:
        list(pool.map(hammer, range(n_conns)))
    data = setup_io.read("log")
    assert len(data) == n_conns * per_conn * 5
    runs = []
    for off in range(0, len(data), 5):
        run = data[off:off + 5]
        assert run == run[:1] * 5, (off, run)   # atomic append
        runs.append(run[0])
    for c in range(n_conns):
        seq = [b % 16 for b in runs if b // 16 == c]
        assert seq == sorted(seq), (c, seq)
        assert len(seq) == per_conn
