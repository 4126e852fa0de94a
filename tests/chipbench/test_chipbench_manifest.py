"""BENCHMARK.json keeps the benchmark contract's rules, and every name in it
finds its file."""

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_are_exactly_the_contracts():
    assert set(M) == KEYS["top"]
    for c in M["configs"]:
        assert set(c) == KEYS["config"]
    for w in M["workloads"]:
        assert set(w) == KEYS["workload"]
    for kind in ("end_to_end", "per_layer"):
        for m in M[kind]:
            assert set(m) - {"workloads"} == KEYS[kind], m["name"]


def test_names_units_and_lines():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in M["workloads"]:
        assert NAME.match(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
    for c in M["configs"]:
        assert _line(c["why"]) and _line(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in M["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert _line(m["layer"])


def test_paths_command_and_run_length():
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    for p in M["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert len(M["command"]) <= 32
    files = [w for w in M["command"] if "/" in w]
    assert files and all(any(f.startswith(p + "/") for p in M["paths"])
                         for f in files)


def test_every_name_finds_its_file():
    for c in M["configs"]:
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / c["file"]).parent.joinpath(
            cfg["network"] + ".py").is_file()
    for w in M["workloads"]:
        assert (ROOT / "chipbench/traffic" / f"{w['traffic']}.json").is_file()
    for m in M["end_to_end"] + M["per_layer"]:
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_every_cell_reports_what_its_metrics_move(cell):
    from chipbench import harness
    mets = harness.metrics_of(M, cell)
    e2e = {m["name"] for m in mets["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert mets["per_layer"]
    for m in M["per_layer"]:
        for w in m.get("workloads", []):
            assert m["moves"] in {x["name"] for x in
                                  harness.metrics_of(M, w)["end_to_end"]}
    # a layer named twice is named alike, letter for letter
    layers = {}
    for m in M["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)
