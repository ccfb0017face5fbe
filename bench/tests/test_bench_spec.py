"""BENCHMARK.json against the rules every later change is held to, and
each name it uses found in the data and reader files."""

import json
import pathlib
import re

import pytest

from bench import harness, spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])


def test_run_seconds_fits_a_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_keys():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in metrics])
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len(set(CELLS)) == len(CELLS)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    # at most half the cells, rounded down, on four chips; one always may
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_data_and_readers_exist(cell):
    c = spec.load(cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.reader(m["name"]))
    assert callable(harness.kind(c.traffic["kind"]))
    # an exact comparison has the limit 0
    assert set(c.limits) and all(v >= 0 for v in c.limits.values())


@pytest.mark.parametrize("cfg", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_config_files(cfg):
    body = json.loads((ROOT / cfg["file"]).read_text())
    assert cfg["file"].startswith("bench/")
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    assert len(cfg["reduced"]) <= 16 and all(NAME.match(k)
                                             for k in cfg["reduced"])
    # the precision of every path its cells run (the traffic's kind)
    kinds = {spec.load(w["name"]).traffic["kind"]
             for w in BENCH["workloads"] if w["config"] == cfg["name"]}
    assert kinds and kinds <= set(body["precision"])
