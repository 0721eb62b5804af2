"""Room for an overwrite deployment (ISSUE 34), proved on the CPU by
NEW files only on top of ``bench_tiny.make_root``: a window kind of
its own (``tiny_overwrite.py``, copied to the tiny checkout's
``windows/``), a traffic file and the cell ``tiny.overwrite`` on
``tiny_k8m3``: 12 preloaded objects of 64 KiB, 4 clients in a closed
loop of 4 KiB overwrites at aligned offsets drawn from the seed, on 8
of the objects. The kind states what an object holds after the window
(``expected``), whether its shards still keep their ``hinfo``
(``keeps_hinfo``) and how many bytes an op encodes (``op_bytes``); the
harness asks the kind, and each seam has an upper reading: left at its
default, or fed a wrong answer, the run comes out not ``correct`` by
the row named.
"""

import json
import os
import shutil

import pytest

import bench_tiny

import compare
import spec
from windows.base import WindowBase

CELL, KIND = "tiny.overwrite", "tiny_overwrite"
KIB = 1 << 10


@pytest.fixture(scope="module")
def overwrite_root(tmp_path_factory):
    """``make_root`` as it is, then NEW files only: the kind, its
    traffic file, and the cell's entries on the lists of the tiny write
    cell (it reports the same metrics)."""
    root = bench_tiny.make_root(str(tmp_path_factory.mktemp("ow")))
    bench = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(bench_tiny.HERE, KIND + ".py"),
                os.path.join(bench, "windows", KIND + ".py"))
    with open(os.path.join(bench, "traffic", "tiny_write.json")) as f:
        mix = json.load(f)
    for key in spec.window_kind("write_full").KEYS:
        del mix[key]            # a closed loop's keys are not this kind's
    mix.update(op=KIND, object_bytes=64 * KIB, clients=4,
               preload_objects=12, payload_pool=12, warm_bursts=[1],
               check_sample=12, extent_bytes=4 * KIB,
               overwrite_objects=8)
    with open(os.path.join(bench, "traffic", KIND + ".json"), "w") as f:
        json.dump(mix, f, indent=1)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["workloads"].append(
        {"name": CELL, "config": "tiny_k8m3", "traffic": KIND,
         "chips": 1, "why": "CPU test"})
    for metric in bm["end_to_end"] + bm["per_layer"]:
        if "tiny.write" in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bm, f, indent=1)
    return root


def _run(capfd, root, **how) -> dict:
    return bench_tiny.last_line(capfd, root, CELL, **how)


def test_the_kind_is_found_by_its_traffic_files_op(overwrite_root):
    cell = spec.Cell(CELL, overwrite_root)
    assert cell.window.__name__ == "Window"
    assert issubclass(cell.window, WindowBase)
    assert set(cell.window.KEYS) == {"extent_bytes", "overwrite_objects"}
    assert cell.traffic["extent_bytes"] == 4 * KIB
    assert {m["name"] for m in cell.end_to_end} == {
        "write_MBps", "write_p95_ms", "setup_s"}
    # the three seams are the kind's own, not the defaults
    for seam in ("expected", "keeps_hinfo", "op_bytes"):
        assert getattr(cell.window, seam) is not getattr(WindowBase,
                                                         seam), seam
    # what the kind refuses
    bench = os.path.join(overwrite_root, "benchmarks")
    good = spec.traffic(KIND, bench)
    for key, bad in (("extent_bytes", 3000), ("overwrite_objects", 13),
                     ("overwrite_objects", 0), ("clients", 10 ** 6)):
        assert cell.window.check(dict(good, **{key: bad})), key
    assert cell.window.check(good) is None


def test_overwrite_cell_is_correct_through_the_commands_main(
        overwrite_root, cpu_env, capfd):
    last = _run(capfd, overwrite_root)
    cmp = last["compared"]
    assert last["correct"] is True, cmp
    assert last["failed"] == 0 and last["attempted"] > 8
    assert set(last["metrics"]) == {"write_MBps", "write_p95_ms",
                                    "setup_s"}
    for row in ("readback_unequal", "shards_unequal", "crcs_unequal",
                "shards_missing", "ops_failed", "compiled_in_window"):
        assert cmp[row] == {"value": 0, "limit": 0, "rule": "<="}, row
    # the sample holds overwritten and untouched objects
    assert cmp["overwritten_compared"]["value"] >= 1
    assert cmp["untouched_compared"]["value"] == 4
    # the kind asks nothing of the engine: no row of its own on the
    # route, and the rows every cell has stay where they were
    assert "encode_flushes" not in cmp and "decode_flushes" not in cmp
    assert list(cmp)[-2:] == ["primaries_seen",
                              "primaries_without_device"]


def test_overwrite_cell_traced_reads_what_there_is_to_read(
        overwrite_root, cpu_env, capfd, monkeypatch):
    # what the roofline's reader is given, as the harness gives it
    asked = []
    real_reader = spec.reader

    def reader(name, bench_dir=spec.BENCH_DIR):
        read = real_reader(name, bench_dir)
        if name != "roofline_pct":
            return read

        def recorded(ctx, **args):
            asked.append((ctx, args))
            return read(ctx, **args)
        return recorded
    monkeypatch.setattr(spec, "reader", reader)
    last = _run(capfd, overwrite_root, trace=1, seconds=2.5)
    assert last["correct"] is True, last["compared"]
    metrics = last["metrics"]
    # the stage clocks see an overwrite as they see a write ...
    for name in ("client_wire_ms.write", "osd_queue_ms.write",
                 "commit_wait_ms.write"):
        assert metrics[name]["value"] > 0, name
    # ... and so do the engine's counters: an overwrite is encoded on
    # the engine's overwrite route
    assert metrics["encode_ops_per_flush"]["value"] >= 1
    # the roofline reads the engine's traced overwrites at one
    # stripe's bytes each; a CPU's trace has no device plane, so the
    # reading is left out here for that alone, and with a device's
    # busy time the same window reads a share of the roofline
    (ctx, args), = asked
    assert args == {"work": "encode_hbm_bytes", "ops_counter": "ops"}
    assert ctx["engine_traced"]["ops"] >= 1
    pool = ctx["config"]["pool"]
    assert ctx["op_bytes"] == pool["k"] * pool["stripe_unit"]
    assert last["device"]["busy_s"] == 0.0
    assert "encode_roofline" not in metrics
    busy = dict(ctx, trace=dict(ctx["trace"],
                                busy_s=last["device"]["window_s"]))
    assert 0 < real_reader("roofline_pct")(busy, **args) <= 100


# -- upper readings: a seam fed a wrong answer is not correct -----------

def _faulty_kind(monkeypatch, **members):
    """The kind as the file has it, with ``members`` in the place of
    its own: ``spec.Cell`` finds the faulty one by the same name."""
    real = spec.window_kind

    def window_kind(op, bench_dir=spec.BENCH_DIR):
        kind = real(op, bench_dir)
        if op != KIND:
            return kind
        return type("Window", (kind,), members)
    monkeypatch.setattr(spec, "window_kind", window_kind)


def _one_extent_left_out(monkeypatch):
    def expected(self, name):
        last = self.acked.pop()         # the newest write to its block
        try:
            return super(type(self), self).expected(name)
        finally:
            self.acked.append(last)
    _faulty_kind(monkeypatch, expected=expected)


def _hinfo_seam_at_its_default(monkeypatch):
    _faulty_kind(monkeypatch, keeps_hinfo=WindowBase.keeps_hinfo)


def _stale_hinfo_put_back(monkeypatch):
    """After the window, one shard of an overwritten object gets a
    ``hinfo`` again: the crc of bytes it no longer holds."""
    from ceph_tpu.osd.pg import pg_cid
    from ceph_tpu.store.object_store import Transaction

    def run(self, seconds, during=None):
        out = super(type(self), self).run(seconds, during)
        served = self.served
        name = sorted(self.overwritten())[0]
        ps, acting = served._locate(name)
        cid = pg_cid(served.pool_id, ps, 0)
        txn = Transaction()
        txn.setattr(cid, name, "hinfo", json.dumps(
            {"hashes": [7] * len(acting)}).encode())
        served.cluster.osds[acting[0]].store.queue_transaction(
            txn, lambda: None)
        return out
    _faulty_kind(monkeypatch, run=run)


@pytest.mark.parametrize("fault,fails", [
    (_one_extent_left_out, ("readback_unequal", "shards_unequal")),
    (_hinfo_seam_at_its_default, ("crcs_unequal",)),
    (_stale_hinfo_put_back, ("crcs_unequal",)),
], ids=["extent_left_out_of_expected", "keeps_hinfo_at_default",
        "stale_hinfo_on_one_shard"])
def test_a_seam_fed_a_wrong_answer_is_not_correct(
        overwrite_root, cpu_env, capfd, monkeypatch, fault, fails):
    fault(monkeypatch)
    last = _run(capfd, overwrite_root)
    assert last["correct"] is False
    for row in fails:
        assert last["compared"][row]["value"] >= 1, last["compared"]
    # nothing else fails: the fault is the seam's alone
    over = {name for name, row in last["compared"].items()
            if (row["value"] > row["limit"] if row["rule"] == "<="
                else row["value"] < row["limit"])}
    assert over == set(fails), last["compared"]


@pytest.mark.parametrize("seed", [1, 2_500_000_000, 77])
def test_control_loses_an_overwrite_and_is_not_correct(overwrite_root,
                                                       seed):
    """``control.py`` on a cell of the new kind: every control is not
    correct, and the dropped overwrite by the rows the seams feed."""
    import control
    results = control.run_controls(spec.Cell(CELL, overwrite_root), seed)
    assert {r["control"] for r in results} == {
        "one_parity_short", "crc_not_kept", "overwrite_lost"}
    for res in results:
        assert res["correct"] is False, res
    lost = next(r["compared"] for r in results
                if r["control"] == "overwrite_lost")
    assert lost["readback_unequal"]["value"] >= 1
    assert lost["shards_unequal"]["value"] >= 1
    assert lost["crcs_unequal"]["value"] == 0


# -- the seams' defaults, and the reader that counts from op_bytes ------

def test_the_defaults_are_what_the_harness_did():
    class Served:
        class payloads:
            @staticmethod
            def of(name):
                return name.encode()
    for op in ("write_full", "read", "recover"):
        kind = spec.window_kind(op)
        assert issubclass(kind, WindowBase), op
        for seam in ("expected", "keeps_hinfo", "op_bytes"):
            assert getattr(kind, seam) is getattr(WindowBase, seam)
    mix = spec.traffic("write_1m")
    window = spec.window_kind("write_full")(Served, mix, 1)
    assert window.expected("w0_1") == b"w0_1"
    assert window.keeps_hinfo({"name": "w0_1"}) is True
    assert window.op_bytes() == mix["object_bytes"] == 1 << 20


def _obs(shards, crcs, data=None):
    return {"name": "obj_0", "read_back": data,
            "shards": {i: s.tobytes() for i, s in enumerate(shards)},
            "crcs": dict(enumerate(crcs)), "unmapped": 0}


def test_compare_objects_counts_a_crc_by_what_the_kind_says():
    import reference
    pool = {"k": 4, "m": 2, "stripe_unit": 4096}
    data = bytes(range(256)) * 128
    shards = reference.shards(data, pool)
    crcs = reference.shard_crcs(shards)

    def count(crcs, keeps=None):
        return compare.compare_objects(
            [_obs(shards, crcs, data)], lambda name: data, pool,
            keeps_hinfo=keeps)["crcs_unequal"]
    # a kind that says the crc is kept (the default): absent or wrong
    # is counted, and an absent one no longer raises
    assert count(crcs) == 0
    assert count([None] * 6) == 6
    assert count(crcs[:5] + [crcs[5] ^ 1]) == 1
    assert count([None] * 6, keeps=lambda obs: True) == 6
    # a kind that says it is dropped: one that is still there is stale
    assert count([None] * 6, keeps=lambda obs: False) == 0
    assert count([None] * 5 + [crcs[5]], keeps=lambda obs: False) == 1
    assert count(crcs, keeps=lambda obs: False) == 6


def test_roofline_counts_an_ops_bytes_not_the_objects():
    read = spec.reader("roofline_pct")
    args = {"work": "encode_hbm_bytes", "ops_counter": "ops"}
    ctx = {"trace": {"busy_s": 0.02, "window_s": 5.0},
           "engine_traced": {"ops": 4000},
           "peaks": spec.peaks("TPU v5 lite"),
           "config": {"pool": {"k": 8, "m": 3, "stripe_unit": 4096}},
           "traffic": {"object_bytes": 4 << 20}}
    whole = read(dict(ctx, op_bytes=4 << 20), **args)
    stripe = read(dict(ctx, op_bytes=32 * KIB), **args)
    assert stripe == pytest.approx(whole / 128)
    # 4000 overwrites of one stripe in 20 ms of device time:
    # counted by the object the share would pass 100 %
    assert whole > 100 > stripe > 0
    with pytest.raises(KeyError):
        read(ctx, **args)       # the traffic's object_bytes is not read
