"""``BENCHMARK.json`` and the data files it names keep to the contract's
form, and every pairing in it is consistent."""

import json
import os
import re

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
        if met["name"].endswith("_roofline"):
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


def test_traffic_files_are_valid_and_refuse_nonsense(tmp_path):
    for name in os.listdir(os.path.join(BENCH_DIR, "traffic")):
        spec.traffic(name[:-len(".json")])
    bench = tmp_path / "benchmarks"
    (bench / "traffic").mkdir(parents=True)
    good = spec.traffic("write_4m")
    for key, bad in (("op", "append"), ("clients", 0),
                     ("object_bytes", "4M"), ("reports", {})):
        mix = dict(good, **{key: bad})
        (bench / "traffic" / "bad.json").write_text(json.dumps(mix))
        with pytest.raises(spec.SpecError):
            spec.traffic("bad", str(bench))
    with pytest.raises(spec.SpecError):
        spec.traffic("absent", str(bench))


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")


def test_unknown_workload_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.Cell("no_such_cell", ROOT)
