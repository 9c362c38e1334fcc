"""Where the benchmark finds each piece, by the names in
``BENCHMARK.json``.

* a cell is an entry of ``workloads``; its ``config`` names an entry of
  ``configs`` whose ``file`` holds the deployment, and its ``traffic``
  names ``bench/traffic/<traffic>.json``;
* a per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``
  (its ``read(ctx)``), for the cells its ``workloads`` key lists; where
  that file is missing, a metric split by suffix (``<base>.open``,
  ``<base>.closed``) shares the reader ``bench/metrics/<base>.py``.

Adding a configuration, a traffic mix, a cell or a per-layer metric
adds files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # entries of BENCHMARK.json's end_to_end it reports
    per_layer: list  # entries of per_layer it reports


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_cell(name: str) -> Cell:
    bm = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in bm["configs"] if c["name"] == w["config"])
    e2e = [m for m in bm["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [
        m for m in bm["per_layer"]
        if m["moves"] in moved and _reports(m, name)
    ]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(ROOT / cfg_entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e, per_layer=layer,
    )


def metric_reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``, else of
    ``bench/metrics/<base>.py`` for ``<base>.<suffix>``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
