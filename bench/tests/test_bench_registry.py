"""Configurations, traffic, loops, deployments and metrics are found by
name, unknown names are refused, and BENCHMARK.json keeps to the shape the
harness reads."""
import json
import os
import re

import pytest

from bench import registry

BENCH = registry.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = registry.cell(name)
    cfg, traffic = cell["config"], cell["traffic"]
    registry.load_module("deploy", cfg["deployment"])
    loop = registry.load_module("loops", traffic["loop"])
    assert callable(loop.run) and callable(loop.end_to_end)
    assert {"missing", "dist_gap", "id_gap"} <= set(cfg["limits"])
    assert "setup_s" in {m["name"] for m in cell["end_to_end"]}
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(registry.load_module("metrics", m["name"]).read)


@pytest.mark.parametrize("kind,name", [
    ("configs", "no-such-config"), ("traffic", "no-such-traffic"),
    ("loops", "no_such_loop"), ("metrics", "no_such_metric.open"),
    ("deploy", "nowhere"), ("configs", "../BENCHMARK"),
    ("traffic", "a/b"), ("loops", "")])
def test_unknown_names_refused(kind, name):
    with pytest.raises(LookupError):
        if kind in ("configs", "traffic"):
            registry.load_json(kind, name)
        else:
            registry.load_module(kind, name)


def test_unknown_workload_refused():
    with pytest.raises(LookupError):
        registry.cell("no-such-cell")
    with pytest.raises(LookupError):
        registry.cell("hbm easy")


def test_metrics_move_what_their_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)
        assert os.path.isfile(os.path.join(registry.BENCH, "metrics",
                                           m["name"] + ".py"))


def test_benchmark_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]]
    names += CELLS + [m["name"] for m in BENCH["end_to_end"]]
    names += [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used and c["file"].startswith("bench/")
        with open(os.path.join(registry.ROOT, c["file"])) as f:
            json.load(f)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024
