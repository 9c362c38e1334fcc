"""BENCHMARK.json against the layout the harness reads: every name
finds its file, every cell reports what it must."""
import json
import re

import pytest

from bench import spec

BM = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys_and_command():
    assert set(BM) == {"command", "paths", "run_seconds", *KEYS}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries(section):
    names = [e["name"] for e in BM[section]]
    assert len(names) == len(set(names))
    optional = {"workloads"} if "unit" in KEYS[section] else set()
    for e in BM[section]:
        assert KEYS[section] <= set(e) <= KEYS[section] | optional, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        for text in ("why", "layer", "source"):
            if text in e:
                assert 1 <= len(e[text]) <= 200 and "\n" not in e[text]


def test_every_name_finds_its_file():
    for c in BM["configs"]:
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in BM["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.traffic["loop"] in ("open", "closed")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "qps"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_per_layer_metrics_move_a_metric_of_their_cells():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", ()):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", [cell])


def test_bounds():
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    setup = next(m for m in BM["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.25


def test_full_check_fits_at_24_cells():
    rs = BM["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
