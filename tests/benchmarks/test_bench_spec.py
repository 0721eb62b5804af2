"""``BENCHMARK.json`` and the data files it names keep to the contract's
form, and every pairing in it is consistent."""

import json
import os
import re
import shutil

import pytest

from bench_tiny import BENCH_DIR, ROOT

import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


@pytest.fixture(scope="module")
def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) \
        <= 64 << 10
    assert isinstance(bm["run_seconds"], int)
    assert 1 <= bm["run_seconds"] <= 51
    # a full check with the most cells a benchmark may have fits
    runs = 2 + 14 * 24
    assert runs * (bm["run_seconds"] + 60) + 24 * 2 * 90 + 1200 \
        <= 43200
    assert bm["command"] == ["python3", "benchmarks/run.py"]
    assert bm["paths"] == ["benchmarks", "tests/benchmarks"]
    for path in bm["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


def test_names_units_and_text(bm):
    entries = (bm["configs"] + bm["workloads"] + bm["end_to_end"]
               + bm["per_layer"])
    for ent in entries:
        assert NAME.match(ent["name"]), ent["name"]
        for key in ("why", "layer", "source"):
            if key in ent:
                text = ent[key]
                assert 1 <= len(text) <= 200, (ent["name"], key)
                assert "\n" not in text and "\t" not in text
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bm[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for met in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(met["unit"]), met
        assert met["better"] in ("lower", "higher")
        assert met["source"] in SOURCES
    for cell in bm["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips",
                             "why"}
        assert NAME.match(cell["config"]) and NAME.match(
            cell["traffic"])
        assert cell["chips"] in (1, 4)
    pairs = [(c["config"], c["traffic"]) for c in bm["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for c in bm["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(bm["workloads"]) // 2)


def test_configurations(bm):
    used = {c["config"] for c in bm["workloads"]}
    files = set()
    for conf in bm["configs"]:
        assert set(conf) == {"name", "source", "file", "reduced", "why"}
        assert conf["name"] in used
        assert conf["file"].startswith("benchmarks/configs/")
        assert conf["file"] not in files
        files.add(conf["file"])
        assert len(conf["reduced"]) <= 16
        loaded = spec.configuration(conf, ROOT)
        assert loaded["name"] == conf["name"]
        assert loaded["source"] == conf["source"]
        # every cut of scale the entry lists is explained in the file
        assert set(conf["reduced"]) == set(loaded["reduced"])
        assert loaded["guarantees"]
    sources = [c["source"] for c in bm["configs"]]
    assert len(sources) == len(set(sources))


def test_end_to_end_metrics(bm):
    names = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in names
    for met in bm["end_to_end"]:
        assert set(met) <= {"name", "unit", "better", "bound",
                            "source", "workloads"}
        assert 0.01 <= met["bound"] <= 0.25
        assert met["source"] in ("host_clock", "device_trace")
    setup = next(m for m in bm["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.25


def test_every_cell_reports_enough(bm):
    for cell in bm["workloads"]:
        loaded = spec.Cell(cell["name"], ROOT)
        e2e = {m["name"] for m in loaded.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        assert loaded.per_layer, cell["name"]
        # the traffic file names the cell's own end-to-end metrics
        reports = loaded.traffic["reports"]
        assert reports["throughput"] in e2e
        assert reports["tail"]["name"] in e2e


def test_per_layer_pairings(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    cells = {c["name"] for c in bm["workloads"]}
    layers = set()
    for met in bm["per_layer"]:
        assert set(met) <= {"name", "unit", "better", "source", "layer",
                            "moves", "workloads"}
        assert met["moves"] in e2e, met["name"]
        moved = e2e[met["moves"]]
        moved_cells = set(moved.get("workloads", cells))
        listed = set(met.get("workloads", moved_cells))
        assert listed and listed <= cells
        # every cell that reports the metric reports what it moves
        assert listed <= moved_cells, met["name"]
        layers.add(met["layer"])
        if "_roofline" in met["name"]:
            assert met["unit"] == "%" and \
                met["source"] == "device_trace"
        data = spec.layer_metric(met["name"])
        assert data["layer"] == met["layer"]
        assert data["unit"] == met["unit"]
        assert data["moves"] == met["moves"]
        assert callable(spec.reader(data["reader"]))
    # PERF.md names each of them, letter for letter
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, layer


@pytest.fixture
def scratch_bench(tmp_path):
    """A benchmarks directory with the window kinds and no traffic."""
    bench = tmp_path / "benchmarks"
    (bench / "traffic").mkdir(parents=True)
    shutil.copytree(os.path.join(BENCH_DIR, "windows"),
                    bench / "windows",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return bench


def _refused(bench, mix) -> bool:
    (bench / "traffic" / "bad.json").write_text(json.dumps(mix))
    try:
        spec.traffic("bad", str(bench))
    except spec.SpecError:
        return True
    return False


def test_traffic_files_are_valid_and_refuse_nonsense(scratch_bench):
    for name in os.listdir(os.path.join(BENCH_DIR, "traffic")):
        spec.traffic(name[:-len(".json")])
    good = spec.traffic("write_4m")
    assert not _refused(scratch_bench, good)
    for key, bad in (("op", "append"), ("clients", 0),
                     ("object_bytes", "4M"), ("reports", {})):
        assert _refused(scratch_bench, dict(good, **{key: bad})), key
    with pytest.raises(spec.SpecError):
        spec.traffic("absent", str(scratch_bench))


class _KillingSetup:
    """What a closed loop's ``prepare`` asks of set-up, recording the
    kills it is asked for."""

    victims: list = []
    degraded_objects: dict = {}

    def __init__(self) -> None:
        self.killed: list = []

    def kill_osds(self, n, victims=None) -> None:
        self.killed.append((n, victims))

    def settle(self) -> None:
        pass

    def warm_degraded_reads(self) -> None:
        pass

    def compiles(self) -> int:
        return 0

    def compile_seconds(self) -> float:
        return 0.0


def test_a_closed_loop_mix_may_name_the_osds_it_kills(scratch_bench):
    """``victims``: the same OSDs down in every run, whatever the seed;
    a mix without it kills ``osds_down`` OSDs drawn from the seed."""
    fixed = spec.traffic("degraded_read_4m_fixed_down")
    drawn = spec.traffic("degraded_read_4m")
    assert fixed["victims"] == [6, 9] and "victims" not in drawn
    # the failure is the one difference between the two mixes
    assert {k: v for k, v in fixed.items() if k not in ("what", "victims")
            } == {k: v for k, v in drawn.items() if k != "what"}
    assert not _refused(scratch_bench, fixed)
    for bad in ([6], [6, 6], [6, "9"], [6, -1], 6, [True, 9], [6, 9, 1]):
        assert _refused(scratch_bench, dict(fixed, victims=bad)), bad
    kind = spec.window_kind("read")
    for mix, want in ((fixed, [6, 9]), (drawn, None)):
        for seed in (1, 3000000001):
            setup = _KillingSetup()
            kind(setup, mix, seed).prepare(lambda **kw: None)
            assert setup.killed == [(2, want)], (seed, setup.killed)


def test_a_window_kind_declares_and_checks_the_keys_of_its_own(
        scratch_bench):
    """``op`` names the kind; the kind, not one fixed table, says which
    further keys its traffic file has to have and what it refuses."""
    write, recover = spec.traffic("write_4m"), spec.traffic("recovery_4m")
    kinds = {op: spec.window_kind(op)
             for op in ("write_full", "read", "recover")}
    assert kinds["write_full"].KEYS == kinds["read"].KEYS
    assert set(kinds["recover"].KEYS) == {
        "osds_down", "clean_timeout_s", "poll_s", "recovery_options"}
    assert "degraded_share" in kinds["read"].KEYS
    assert "degraded_share" not in recover
    for kind in kinds.values():
        for entry in ("check", "prepare", "run", "values", "absent_ok",
                      "judge_ops", "judge_route",
                      # what a kind states about its window
                      "expected", "keeps_hinfo", "op_bytes"):
            assert callable(getattr(kind, entry)), entry
    # windows/base.py holds the defaults and is itself no kind
    with pytest.raises(spec.SpecError):
        spec.window_kind("base")
    assert not _refused(scratch_bench, recover)
    # a key of the kind's own is missing, of the wrong type or out of
    # range; a closed loop's key is no recovery's and the other way
    for mix, key, bad in (
            (recover, "clean_timeout_s", None), (recover, "poll_s", 0),
            (recover, "osds_down", 0), (recover, "preload_objects", 0),
            (recover, "recovery_options", [4, 2]),
            (recover, "recovery_options", {"osd_max_backfills": "2"}),
            (recover, "reports", dict(recover["reports"], length=None)),
            (write, "degraded_share", None), (write, "max_objects", "x"),
            (write, "degraded_share", 1.5),
            (dict(write, op="read"), "preload_objects", 0)):
        changed = {k: v for k, v in mix.items() if k != key}
        if bad is not None:
            changed[key] = bad
        assert _refused(scratch_bench, changed), (key, bad)
    # an unknown kind: no file windows/<op>.py; a file that is no kind
    assert _refused(scratch_bench, dict(write, op="overwrite_4k"))
    (scratch_bench / "windows" / "empty.py").write_text("KEYS = {}\n")
    assert _refused(scratch_bench, dict(write, op="empty"))
    with pytest.raises(spec.SpecError):
        spec.window_kind("../run")
    # a later PR adds a kind as a new file: it is found by its name
    (scratch_bench / "windows" / "overwrite_4k.py").write_text(
        "from windows.closed_loop import Window as Loop\n"
        "class Window(Loop):\n"
        "    KEYS = dict(Loop.KEYS, extent_bytes=int)\n"
        "    OPS = ('overwrite_4k',)\n")
    new = dict(write, op="overwrite_4k", extent_bytes=4096)
    assert not _refused(scratch_bench, new)
    del new["extent_bytes"]
    assert _refused(scratch_bench, new)


def test_the_whole_pool_is_the_erasure_code_profile(tmp_path, bm):
    """Every key of ``pool`` but ``stripe_unit`` and ``pg_num`` goes
    to the plugin as it stands, whatever the plugin; the two
    ``jerasure`` files give the profile they gave when six keys were
    named in code."""
    for conf in bm["configs"]:
        pool = spec.configuration(conf, ROOT)["pool"]
        profile = spec.ec_profile(pool)
        assert profile == {key: val for key, val in pool.items()
                           if key not in ("stripe_unit", "pg_num")}
        assert {"plugin", "k", "m", "backend"} <= set(profile)
        assert profile["backend"] == "pallas", conf["name"]
        if pool["plugin"] == "jerasure":
            assert profile == {
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": pool["k"], "m": pool["m"], "backend": "pallas"}
    clay = {"plugin": "clay", "k": 8, "m": 4, "d": 11,
            "scalar_mds": "jerasure", "backend": "pallas",
            "stripe_unit": 4096, "pg_num": 64}
    assert spec.ec_profile(clay) == {
        "plugin": "clay", "k": 8, "m": 4, "d": 11,
        "scalar_mds": "jerasure", "backend": "pallas"}
    # a plugin without ``technique`` is a configuration; one without
    # a backend, or with no guarantees, is not
    conf = {"name": "c", "deployment": {"n_osds": 12,
                                        "store": "memstore",
                                        "osd_heartbeat_grace": 20},
            "pool": clay, "guarantees": ["exact"]}
    path = tmp_path / "c.json"
    entry = {"name": "c", "file": str(path)}
    path.write_text(json.dumps(conf))
    assert spec.configuration(entry, str(tmp_path))["pool"] == clay
    for broken in (dict(conf, pool={k: v for k, v in clay.items()
                                    if k != "backend"}),
                   dict(conf, guarantees=[])):
        path.write_text(json.dumps(broken))
        with pytest.raises(spec.SpecError):
            spec.configuration(entry, str(tmp_path))


def test_a_configuration_names_its_reference_module(tmp_path):
    import reference
    default = spec.reference_module({"name": "c"})
    pool = {"k": 4, "m": 2, "stripe_unit": 4096}
    data = bytes(range(256)) * 64
    assert [s.tobytes() for s in default.shards(data, pool)] == \
        [s.tobytes() for s in reference.encode(data, 4, 2, 4096)]
    bench = tmp_path / "benchmarks"
    bench.mkdir()
    (bench / "mine.py").write_text(
        "def shards(data, pool):\n    return [data] * pool['n']\n")
    (bench / "hollow.py").write_text("x = 1\n")
    mine = spec.reference_module({"name": "c", "reference": "mine"},
                                 str(bench))
    assert mine.shards(b"ab", {"n": 3}) == [b"ab"] * 3
    for name in ("hollow", "absent", "../spec", 7):
        with pytest.raises(spec.SpecError):
            spec.reference_module({"name": "c", "reference": name},
                                  str(bench))
    for cell in ("k8m3_write_4m", "k8m3_recovery_4m"):
        assert spec.Cell(cell, ROOT).reference.__file__ == \
            os.path.join(BENCH_DIR, "reference.py")


def test_the_pending_cell_is_entries_and_nothing_else(bm):
    """``k8m3_recovery_4m`` is built and not registered (PERF.md
    section 7). Its entries keep to the contract's form, every file
    they name is there, and the command finds the cell by its name;
    ``BENCHMARK.json`` does not have it, so no check judges it."""
    name = "k8m3_recovery_4m"
    with open(os.path.join(BENCH_DIR, "pending", name + ".json")) as f:
        pending = json.load(f)
    assert name not in {c["name"] for c in bm["workloads"]}
    cell = pending["workload"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == name and cell["chips"] == 1
    assert cell["config"] in {c["name"] for c in bm["configs"]}
    assert 1 <= len(cell["why"]) <= 200
    assert pending["why_pending"]
    e2e = {m["name"] for m in pending["end_to_end"]}
    for met in pending["end_to_end"]:
        assert set(met) == {"name", "unit", "better", "source",
                            "workloads"}          # no bound yet
        assert NAME.match(met["name"]) and UNIT.match(met["unit"])
        assert met["source"] == "host_clock"
        assert met["workloads"] == [name]
    for met in pending["per_layer"]:
        assert set(met) == {"name", "unit", "better", "source", "layer",
                            "moves", "workloads"}
        assert NAME.match(met["name"]) and UNIT.match(met["unit"])
        assert met["moves"] in e2e and met["workloads"] == [name]
        assert met["source"] in SOURCES
        data = spec.layer_metric(met["name"])
        assert (data["layer"], data["unit"], data["moves"]) == \
            (met["layer"], met["unit"], met["moves"])
        assert callable(spec.reader(data["reader"]))
        if "_roofline" in met["name"]:
            assert met["unit"] == "%" and \
                met["source"] == "device_trace"
    known = {m["name"] for m in bm["end_to_end"] + bm["per_layer"]}
    new = [m["name"] for m in pending["end_to_end"]
           + pending["per_layer"]]
    assert len(new) == len(set(new)) and not set(new) & known
    loaded = spec.Cell(name, ROOT)
    assert {m["name"] for m in loaded.end_to_end} == e2e | {"setup_s"}
    assert {m["name"] for m in loaded.per_layer} == \
        {m["name"] for m in pending["per_layer"]}
    reports = loaded.traffic["reports"]
    assert {reports["throughput"], reports["tail"]["name"]} == e2e
    # an accepted cell is not touched by a pending file of its name
    assert spec.benchmark(ROOT, pending="k8m3_write_4m") == bm
    assert spec.benchmark(ROOT, pending="../x") == bm
    # a cell that is registered has no pending file: this one waits
    # alone
    assert os.listdir(os.path.join(BENCH_DIR, "pending")) == [
        name + ".json"]


def test_every_layer_metric_file_names_a_reader_that_is_there():
    for name in os.listdir(os.path.join(BENCH_DIR, "layer_metrics")):
        data = spec.layer_metric(name[:-len(".json")])
        assert callable(spec.reader(data["reader"])), name


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")


def test_unknown_workload_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.Cell("no_such_cell", ROOT)
