"""What a cell is, read from data: ``BENCHMARK.json`` names the cell's
configuration and traffic; ``bench/configs/<config>.json`` holds the model
as it is run, ``bench/traffic/<traffic>.json`` the load and its generator
kind, and ``bench/cells/<cell>.json`` the limits that decide ``correct``.
A new cell is new files and entries; no code names a cell."""

from __future__ import annotations

import dataclasses
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict                 # compared number -> its limit
    end_to_end: tuple            # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def load(name: str, root: pathlib.Path = ROOT,
         bench: dict | None = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` (or of ``bench``, a
    benchmark of the same form) with its data files; ``KeyError`` for a
    cell the benchmark does not have."""
    if bench is None:
        bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name, set()))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _reports(m, name, e2e_names))
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / config["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads(
            (root / "bench" / "cells" / f"{name}.json").read_text())["limits"],
        end_to_end=e2e, per_layer=per_layer)
