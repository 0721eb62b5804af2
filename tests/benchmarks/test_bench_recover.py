"""The two things ISSUE 28 made room for, rehearsed on the CPU at a
tiny size through the command's own ``main``: a recovery window (OSDs
die, the window lasts from the down mark until every PG is clean, the
rebuilt shards are compared on the spare OSDs that hold them now), and
a pool of another plugin that is held to a reference module of its own
and gets its whole erasure-code profile. A file of its own, so that
the test run can give it a worker of its own: a recovery at any size
waits out two heartbeat graces.
"""

import json

import numpy as np
import pytest

import bench_tiny

import shec_reference
import spec


def _run(capfd, root, workload, **how) -> dict:
    """The result line of one run."""
    rc, lines = bench_tiny.run_main(capfd, root, workload, **how)
    assert rc == 0 and len(lines) == 1
    return json.loads(lines[-1])


def _options():
    from ceph_tpu.utils.config import g_conf
    conf = g_conf()
    return {name: conf[name] for name in (
        "osd_max_backfills", "osd_recovery_max_single_start",
        "osd_heartbeat_grace")}


# -- the recovery window -------------------------------------------------

def test_recovery_cell_rebuilds_onto_the_spare(tiny_root, cpu_env,
                                               capfd):
    options = _options()
    last = _run(capfd, tiny_root, "tiny.recover")
    assert last["correct"] is True, last["compared"]
    assert list(last)[-1] == "compared"
    assert set(last["metrics"]) == {"recovery_MBps",
                                    "recovery_pg_p90_s", "setup_s"}
    cell = spec.Cell("tiny.recover", tiny_root)
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    for name, met in last["metrics"].items():
        assert met["value"] > 0 and met["unit"] == units[name], name
    cmp = last["compared"]
    # shards were rebuilt, all that had to be, and the sample looked at
    # some of them where they are now
    assert cmp["shards_rebuilt"]["value"] >= 1
    assert last["attempted"] >= cmp["shards_rebuilt"]["value"]
    assert last["failed"] == 0 == cmp["shards_not_rebuilt"]["value"]
    assert cmp["rebuilt_shards_compared"]["value"] >= 1
    assert cmp["shards_missing"] == {"value": 0, "limit": 0,
                                     "rule": "<="}
    # through the decode flush, with every bucket warmed beforehand
    assert cmp["decode_flushes"]["value"] >= 1
    assert cmp["decode_fallbacks"]["value"] == 0
    assert cmp["compiled_in_window"]["value"] == 0
    # the window's length stands beside the limit and is no metric
    assert 0 < cmp["clean_s"]["value"] <= cmp["clean_s"]["limit"] == 60
    assert last["metrics"]["recovery_pg_p90_s"]["value"] <= \
        cmp["clean_s"]["value"]
    assert "clean_s" not in last["metrics"]
    # the rehearsal's hold on recovery and the mix's options are undone
    assert _options() == options


def _break_rebuilt(monkeypatch):
    """A shard altered where recovery produces it: the push carries a
    wrong chunk under the right version and crc."""
    from ceph_tpu.osd.ec_backend import ECBackend
    real = ECBackend._push_from_chunk

    def broken(self, pg, oid, shard, version, chunk, attrs, tid):
        bad = np.array(chunk, dtype=np.uint8)
        bad[len(bad) // 2] ^= 1
        return real(self, pg, oid, shard, version, bad, attrs, tid)
    monkeypatch.setattr(ECBackend, "_push_from_chunk", broken)


def test_a_wrong_rebuilt_shard_is_not_correct(tiny_root, cpu_env, capfd,
                                              monkeypatch):
    _break_rebuilt(monkeypatch)
    last = _run(capfd, tiny_root, "tiny.recover")
    assert last["correct"] is False
    cmp = last["compared"]
    assert cmp["shards_unequal"]["value"] >= \
        cmp["rebuilt_shards_compared"]["value"] >= 1
    # the crc the push carries is the right shard's: only the bytes
    # tell (and a client's read, where the shard is a data shard)
    assert cmp["crcs_unequal"]["value"] == 0


def test_recovery_cell_traced(tiny_root, cpu_env, capfd):
    last = _run(capfd, tiny_root, "tiny.recover", trace=1, seconds=3)
    assert last["correct"] is True, last["compared"]
    assert set(last["device"]) >= {"busy_s", "window_s"}
    cell = spec.Cell("tiny.recover", tiny_root)
    per_layer = {m["name"] for m in cell.per_layer}
    assert per_layer == {
        "decode_ops_per_flush.recovery", "decode_roofline.recovery",
        "decode_run_ms.recovery", "decode_host_ms.recovery",
        "device_idle_pct.recovery", "idle_parked_pct.recovery",
        "idle_flush_host_pct.recovery", "wq_active_threads.recovery"}
    assert set(last["metrics"]) <= per_layer
    assert last["metrics"]["decode_ops_per_flush.recovery"]["value"] \
        >= 1
    # a CPU has no device plane: nothing under a device metric's name
    for name in ("decode_roofline.recovery",
                 "device_idle_pct.recovery"):
        assert name not in last["metrics"]


# -- another plugin, as new files only ----------------------------------

def test_another_plugins_pool_is_correct_by_its_own_reference(
        tiny_root, cpu_env, capfd):
    """``plugin=shec c=3``: the profile key ``c`` is none of the six
    the harness once passed by name. Had it not reached the plugin,
    the plugin's default (2) would have built another matrix and the
    shards would differ from the reference module's."""
    cell = spec.Cell("tiny.shec_write", tiny_root)
    assert cell.config["pool"]["c"] == 3
    assert cell.reference.__name__.endswith("shec_reference")
    assert shec_reference.coding_matrix(6, 4, 2) != \
        shec_reference.coding_matrix(6, 4, 3)
    last = _run(capfd, tiny_root, "tiny.shec_write")
    assert last["correct"] is True, last["compared"]
    assert last["compared"]["encode_flushes"]["value"] >= 1
    assert last["compared"]["host_flushes"]["value"] == 0
    assert set(last["metrics"]) == {"write_MBps", "write_p95_ms",
                                    "setup_s"}


def test_the_same_pool_judged_by_the_rs_reference_is_not(
        tiny_root, cpu_env, capfd):
    cell = spec.Cell("tiny.shec_by_rs", tiny_root)
    assert cell.config["pool"] == spec.Cell(
        "tiny.shec_write", tiny_root).config["pool"]
    assert cell.reference.__name__.endswith("_reference")
    assert "reference" not in cell.config
    last = _run(capfd, tiny_root, "tiny.shec_by_rs")
    assert last["correct"] is False
    cmp = last["compared"]
    assert cmp["shards_unequal"]["value"] > 0
    assert cmp["crcs_unequal"]["value"] > 0
    # what the client reads is right; what the parity shards hold is
    # not what the wrong reference says
    assert cmp["readback_unequal"]["value"] == 0


def test_the_tiny_root_adds_files_and_edits_none(tiny_root):
    """``make_root`` itself asserts that no copied file changed; what
    it added is all the new cells are made of."""
    import os
    bench = os.path.join(tiny_root, "benchmarks")
    for added in ("configs/tiny_shec.json", "configs/tiny_shec_by_rs.json",
                  "shec_reference.py", "traffic/tiny_recover.json"):
        assert os.path.isfile(os.path.join(bench, added)), added
        assert not os.path.exists(os.path.join(bench_tiny.BENCH_DIR,
                                               added)), added
    for copied in ("windows/recover.py", "reference.py",
                   "traffic/recovery_4m.json"):
        with open(os.path.join(bench, copied), "rb") as f, \
                open(os.path.join(bench_tiny.BENCH_DIR, copied),
                     "rb") as g:
            assert f.read() == g.read(), copied
