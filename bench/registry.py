"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own under ``bench/``, so a new one is added as files:

* ``BENCHMARK.json``: the cells and metrics, with the configuration file
  of each configuration;
* ``bench/workloads/<cell>.json``: engine settings, check limits, any
  traffic parameters that override the mix's for this cell;
* ``bench/traffic/<mix>.json``: parameters for ``bench/gen.py``;
* ``bench/metrics/<metric>.py``: ``UNIT`` and ``read(record)``, which
  returns the metric's value or None when the run has nothing to read;
* ``bench/families/<family>.py``: what the harness knows of one block
  family (see ``load_family``); a configuration file names its family
  under ``"family"``, and one without the key is ``dense``.
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
    family: object       # the configuration's family module


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
    config = _json(_find(root, conf["file"]))
    return Cell(name=name, entry=entry, workload=workload, config=config,
                traffic=traffic,
                end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if _applies(m, name)],
                family=load_family(config.get("family", "dense"), root))


def _load_module(prefix: str, name: str, path: Path):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(name: str, root: Path = ROOT):
    """The module ``bench/families/<name>.py``: the model-specific half of
    the harness, for every configuration of one block family.  It gives

    * ``sizes(conf)``: a frozen, hashable sizes object from the
      configuration file, with at least ``vocab``; jitted functions take
      it as a static argument;
    * ``model_config(conf)``: the program's model configuration;
    * ``program_params(seed, s, cfg)``: the program's parameter tree for
      ``seed``, made on the device;
    * ``bench_weights(seed, s)``: the benchmark's own float32 draw of the
      same weights, which the reference reads;
    * ``served_gaps(w, tokens, start, served, *, s, rows, control=False)``:
      the plain float32 reference at ``Precision.HIGHEST``, imported from
      nothing of the program: per served token, how far its logit lies
      below the reference's best; with ``control``, also the gaps of the
      tokens the fp8 control puts first;
    * ``prefill_flops(s, prompt_len)`` and
      ``decode_step_work(s, contexts) -> (flops, bytes)``: the yardstick's
      counts, from the architecture's sizes alone (any per-sequence state
      a step reads, such as SSM or convolution state, is the family's to
      count)."""
    path = _find(root, f"bench/families/{name}.py")
    if not path.exists():
        raise FileNotFoundError(
            f"no family {name!r}: bench/families/{name}.py is neither "
            f"under {Path(root)} nor under {ROOT}")
    return _load_module("bench_family", name, path)


def load_reader(metric: str, root: Path = ROOT):
    """The module ``bench/metrics/<metric>.py`` (names may hold dots)."""
    return _load_module("bench_metric", metric,
                        _find(root, f"bench/metrics/{metric}.py"))


def read_metrics(entries: list, rec: dict, root: Path = ROOT) -> dict:
    """{name: {"value", "unit"}} for every entry whose reader finds a value."""
    out = {}
    for m in entries:
        value = load_reader(m["name"], root).read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
