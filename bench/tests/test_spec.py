"""BENCHMARK.json keeps its contract, and the harness finds every piece
a cell names by that name alone."""
import json
import re

import pytest

from bench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_keys_and_names():
    assert set(BENCH) == KEYS["top"]
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[part]]
        assert len(names) == len(set(names))
        for e in BENCH[part]:
            assert set(e) - {"workloads"} == KEYS[part], e["name"]
            assert NAME.match(e["name"]), e["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in CELLS


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")


def test_run_seconds_fit_a_full_check():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_loads_and_reports_enough(cell):
    c = spec.load_cell(cell)
    names = [m.name for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    assert c.chips == 1
    spec.driver(c.kind)
    for m in c.per_layer:
        assert m.moves in names, m.name
        assert callable(spec.metric_reader(m.name))


def test_config_files_state_what_was_cut():
    for c in BENCH["configs"]:
        data = json.loads((spec.ROOT / c["file"]).read_text())
        assert data["reduced"] == c["reduced"] == []
        assert c["file"].startswith("bench/")


def test_a_missing_reader_is_an_error():
    with pytest.raises(KeyError):
        spec.metric_reader("no_such_metric.decode")
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")
