"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own under ``bench/``, so a new one is added as files:

* ``BENCHMARK.json``: the cells and metrics, with the configuration file
  of each configuration;
* ``bench/workloads/<cell>.json``: engine settings, check limits, any
  traffic parameters that override the mix's for this cell;
* ``bench/traffic/<mix>.json``: parameters for ``bench/gen.py``;
* ``bench/metrics/<metric>.py``: ``UNIT`` and ``read(record)``, which
  returns the metric's value or None when the run has nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _find(root: Path, rel: str) -> Path:
    """``rel`` under ``root``, else under this checkout (so a tree that
    only adds files still finds the ones it shares)."""
    path = Path(root) / rel
    return path if path.exists() else ROOT / rel


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict          # the BENCHMARK.json workload entry
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list     # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bm = _json(root / "BENCHMARK.json")
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    conf = next(c for c in bm["configs"] if c["name"] == entry["config"])
    workload = _json(_find(root, f"bench/workloads/{name}.json"))
    traffic = {**_json(_find(root, f"bench/traffic/{entry['traffic']}.json")),
               **workload.get("traffic", {})}
    return Cell(name=name, entry=entry, workload=workload,
                config=_json(_find(root, conf["file"])), traffic=traffic,
                end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if _applies(m, name)])


def load_reader(metric: str, root: Path = ROOT):
    """The module ``bench/metrics/<metric>.py`` (names may hold dots)."""
    path = _find(root, f"bench/metrics/{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(entries: list, rec: dict, root: Path = ROOT) -> dict:
    """{name: {"value", "unit"}} for every entry whose reader finds a value."""
    out = {}
    for m in entries:
        value = load_reader(m["name"], root).read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
