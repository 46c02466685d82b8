"""BENCHMARK.json and the files it names: everything is found by name.

Loads every cell's configuration, traffic mix, driver and limits, and every
per-layer metric's reader, the way ``bench/run.py`` does; checks names,
units and the cross references the benchmark's contract asks for.
"""

import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402

MANIFEST = run.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]
E2E = [m["name"] for m in MANIFEST["end_to_end"]]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _entry(kind, name):
    return next(e for e in MANIFEST[kind] if e["name"] == name)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(MANIFEST) == KEYS["top"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in cmd[1:]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])


def test_run_seconds_fits_the_check_with_24_cells():
    s = MANIFEST["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    runs = 2 + 14 * 24
    assert runs * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_unique_names():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[kind]]
        assert len(names) == len(set(names))
    metrics = E2E + PER_LAYER
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_entry(name):
    c = _entry("configs", name)
    assert set(c) == KEYS["config"]
    assert NAME.match(name) and _line(c["source"]) and _line(c["why"])
    assert c["source"].startswith("https://")
    assert any(w["config"] == name for w in MANIFEST["workloads"])
    assert c["file"].startswith("bench/configs/")
    with open(os.path.join(ROOT, c["file"])) as f:
        data = json.load(f)
    assert data["name"] == name
    # Every key the manifest says was cut is cut in the file, with a why.
    assert len(c["reduced"]) <= 16
    assert sorted(c["reduced"]) == sorted(data["reduced"])
    for key in c["reduced"]:
        assert NAME.match(key) and key in data
        assert not re.search(r"(_dim|_rank|width|hidden|size)$", key)
    files = [e["file"] for e in MANIFEST["configs"]]
    assert files.count(c["file"]) == 1


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    w = _entry("workloads", name)
    assert set(w) == KEYS["workload"]
    assert NAME.match(name) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and _line(w["why"])
    cell = run.resolve(MANIFEST, name)
    drv = cell.driver
    for fn in ("setup", "window", "check"):
        assert callable(getattr(drv, fn))
    assert cell.limits, "every cell has output limits"
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"


def test_at_most_half_the_cells_on_four_chips():
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("name", E2E)
def test_end_to_end_metric(name):
    m = _entry("end_to_end", name)
    assert set(m) - {"workloads"} == KEYS["end_to_end"]
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    for cell in m.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("name", PER_LAYER)
def test_per_layer_metric(name):
    m = _entry("per_layer", name)
    assert set(m) - {"workloads"} == KEYS["per_layer"]
    assert NAME.match(name) and UNIT.match(m["unit"]) and _line(m["layer"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert m["moves"] in E2E and m["moves"] != "setup_s"
    assert m["workloads"], "every per-layer metric lists its cells"
    moved = _entry("end_to_end", m["moves"])
    for cell in m["workloads"]:
        assert cell in CELLS
        assert run.reports(moved, cell), (
            f"{cell} does not report {m['moves']}, which {name} moves")
    assert callable(run.metric_reader(name))


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    for layer in layers:
        assert layer.strip() == layer


def test_peaks_table_names_its_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    assert "TPU v5 lite" in table
    for kind, peaks in table.items():
        assert peaks["source"] and peaks["hbm_bytes_per_s"] > 0
    with pytest.raises(KeyError):
        run.peaks_for("no such device")


def test_files_under_paths_are_named_from_name_characters():
    for top in MANIFEST["paths"]:
        for dirpath, _dirs, files in os.walk(os.path.join(ROOT, top)):
            if "__pycache__" in dirpath:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert PATH.match(rel), rel
