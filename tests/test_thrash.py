"""Thrash test — the qa/suites/rados/thrash-erasure-code role: random
OSD kills/revives while a client workload runs; afterward every
acknowledged write must read back intact (no lost writes), recovery
must converge, and a scrub must be clean."""

import os
import time

import pytest

pytestmark = pytest.mark.slow  # tier-2: heavy cluster workload (tier-1 runs -m 'not slow')

from ceph_tpu.client.rados import RadosError
from ceph_tpu.qa.cluster import MiniCluster
from ceph_tpu.qa.thrasher import Thrasher
from ceph_tpu.utils.config import g_conf


@pytest.fixture
def fast_death():
    conf = g_conf()
    old = {k: conf[k] for k in ("osd_heartbeat_interval",
                                "osd_heartbeat_grace")}
    conf.set("osd_heartbeat_interval", 0.25)
    conf.set("osd_heartbeat_grace", 1.0)
    yield
    for k, v in old.items():
        conf.set(k, v)


def test_thrash_ec_and_replicated(fast_death):
    with MiniCluster(n_osds=4) as cluster:
        rados = cluster.client()
        cluster.create_ec_pool("ec", k=2, m=1, pg_num=4)
        cluster.create_pool("rep", pg_num=4, size=3)
        io_ec = rados.open_ioctx("ec")
        io_rep = rados.open_ioctx("rep")

        def payload(pool, i):
            return (f"{pool}-{i}-".encode() * 997)[:8192 + i]

        # seed some objects before the storm
        acked: dict[tuple[str, int], bool] = {}
        for i in range(4):
            io_ec.write_full(f"pre{i}", payload("ec", i))
            io_rep.write_full(f"pre{i}", payload("rep", i))
            acked[("ec", i)] = acked[("rep", i)] = True

        thrasher = Thrasher(cluster, min_live=3, interval=1.2,
                            seed=7).start()
        deadline = time.monotonic() + 12.0
        i = 4
        while time.monotonic() < deadline:
            for pool, io in (("ec", io_ec), ("rep", io_rep)):
                try:
                    io.write_full(f"pre{i}", payload(pool, i))
                    acked[(pool, i)] = True
                except RadosError:
                    pass       # unacked: allowed to be lost
            i += 1
        thrasher.stop()
        assert thrasher.kills >= 2, "thrasher never killed anything"

        import os
        # on the real chip every recovery reconstruct is a device
        # launch, and the first of each shape a compile: a thrash
        # round's worth of objects gets minutes there, not seconds
        clean_timeout = 300 if os.environ.get("CEPH_TPU_TEST_TPU") \
            else 60
        cluster.wait_for_clean(timeout=clean_timeout)
        # every acknowledged write reads back intact
        for (pool, j), _ in sorted(acked.items()):
            io = io_ec if pool == "ec" else io_rep
            assert io.read(f"pre{j}") == payload(pool, j), \
                f"lost acked write {pool}/pre{j}"
        assert cluster.scrub_pool("ec")["inconsistent"] == {}
        assert cluster.scrub_pool("rep")["inconsistent"] == {}
