"""Finds a cell's configuration, traffic mix, limits and per-layer metric
readers by the names ``BENCHMARK.json`` gives them.

Adding a configuration, a mix, a metric or a cell means adding a file and
an entry; nothing here names one.

  <paths>/configs/<config>.json   -- the file named by the config entry
  chipbench/traffic/<mix>.json    -- parameters for traffic.Generator
  chipbench/limits/<cell>.json    -- the limits that decide ``correct``
  chipbench/metrics/<metric>.py   -- ``read(view) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: Optional[str] = None     # per-layer: the end-to-end metric


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list        # [Metric] this cell reports with --trace 0
    per_layer: list         # [(Metric, reader)] it reports with --trace 1


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str, e2e_names: Optional[set]) -> bool:
    """Does a metric entry belong to this cell?  With no ``workloads``
    list, an end-to-end metric belongs to every cell, and a per-layer one
    to every cell that reports the end-to-end metric it moves."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return e2e_names is None or entry["moves"] in e2e_names


def load_reader(name: str, here: pathlib.Path = HERE) -> Callable:
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, bench: Optional[dict] = None,
              root: pathlib.Path = ROOT) -> Cell:
    """The cell called ``name`` with everything it needs, from
    ``BENCHMARK.json`` at ``root`` (or the given dict)."""
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    here = root / HERE.name
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(here / "traffic" / f"{w['traffic']}.json")
    limits = load_json(here / "limits" / f"{name}.json")

    def metric(e):
        return Metric(name=e["name"], unit=e["unit"], moves=e.get("moves"))

    e2e = [metric(e) for e in bench["end_to_end"]
           if _applies(e, name, None)]
    e2e_names = {m.name for m in e2e}
    per_layer = [(metric(e), load_reader(e["name"], here))
                 for e in bench["per_layer"]
                 if _applies(e, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)
