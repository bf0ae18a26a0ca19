"""BENCHMARK.json holds to the benchmark's contract, and every name in it
leads to its files: configuration, traffic mix, cell settings, metric
reader, driver and reference."""
import json
import os
import re

import pytest

from bench_cells import ROOT, read

SPEC = read("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
METRIC_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(SPEC) == TOP_KEYS
    assert len(json.dumps(SPEC)) <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd:
        if os.sep in w:
            assert any(w.startswith(p + "/") for p in SPEC["paths"]), w


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert _line(entry["source"]) and _line(entry["why"])
    assert any(entry["file"].startswith(p + "/") for p in SPEC["paths"])
    cfg = read(entry["file"])
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    driver = os.path.join(ROOT, "bench", "drivers", cfg["driver"] + ".py")
    reference = os.path.join(ROOT, "bench", "reference",
                             cfg["reference"] + ".py")
    assert os.path.isfile(driver) and os.path.isfile(reference)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda e: e["name"])
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    assert NAME.match(cell["traffic"]) and NAME.match(cell["config"])
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    read(f"bench/traffic/{cell['traffic']}.json")
    read(f"bench/cells/{cell['name']}.json")
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    layer = [m["name"] for m in SPEC["per_layer"]
             if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("m", SPEC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == METRIC_KEYS
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                       m["name"] + ".py"))


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert set(m) - {"workloads"} == LAYER_KEYS
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES and _line(m["layer"])
    assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                       m["name"] + ".py"))
    moved = [e for e in SPEC["end_to_end"] if e["name"] == m["moves"]]
    assert moved and m["moves"] != "setup_s"
    cells = {w["name"] for w in SPEC["workloads"]}
    for c in m.get("workloads", cells):
        assert c in cells
        assert c in moved[0].get("workloads", cells), (m["name"], c)


def test_setup_s_everywhere():
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25


def test_run_seconds_fit_a_full_check():
    # a full check of 24 cells: 2 + 14 * 24 runs of run_seconds + 60 s,
    # 2 * 90 s of compile a cell and 1200 s spare, within 43200 s
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
