"""BENCHMARK.json against the shape the harness reads, and every name in it
against the files that serve it."""

import json
import re

import pytest
from conftest import REPO, SPEC

from bench import harness
from bench.model import load_config

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in SPEC["workloads"]}
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench", "tests/bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_lines():
    names = ([c["name"] for c in SPEC["configs"]] + list(CELLS)
             + [m["name"] for m in METRICS])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for entry in SPEC["configs"] + SPEC["workloads"] + SPEC["per_layer"]:
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200, entry
                assert "\n" not in entry[key] and "\t" not in entry[key]


def test_configs_files_and_reductions():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert conf["published"][key] != conf["config"][key]
        assert load_config(REPO / "bench", c["name"])[0].n_layers == 16


def test_cells_have_their_files():
    configs = {c["name"] for c in SPEC["configs"]}
    for name, w in CELLS.items():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        cell = harness.load_cell(name)
        assert cell.engine["gap_limit"] is not None
        assert cell.end_to_end and cell.per_layer
    pairs = [(w["config"], w["traffic"]) for w in CELLS.values()]
    assert len(pairs) == len(set(pairs))


def test_metrics_have_readers_and_cells():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in METRICS:
        assert callable(harness.reader(m["name"]))
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))
    layers = {m["layer"] for m in SPEC["per_layer"]}
    perf = (REPO / "PERF.md").read_text()
    for layer in layers:
        assert f"**{layer}**" in perf, layer


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    c = harness.load_cell(cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


def test_a_full_check_fits_its_time():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
