"""BENCHMARK.json is data the harness resolves by name: each cell's
configuration, runner, traffic and limits, each per-layer metric's
reader; names and units in the allowed characters; and every per-layer
metric's `moves` reported by each cell it lists."""
import json
import os
import re

import pytest

from lib import common

ROOT = os.path.dirname(common.BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_cells_resolve(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        conf = configs[cell["config"]]
        assert conf["file"] == f"bench/configs/{cell['config']}.json"
        data = common.load_json("configs", cell["config"] + ".json")
        assert data["reduced"] == conf["reduced"]
        common.load_module("runners", data["runner"] + ".py")
        assert os.path.exists(os.path.join(
            common.BENCH, "configs", data["name"] + ".py"))
        common.load_json("traffic", cell["traffic"] + ".json")
        limits = common.load_json("limits", cell["name"] + ".json")
        assert limits and all(v > 0 for v in limits.values())
        assert cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200


def test_per_layer_readers_resolve(bench):
    for m in bench["per_layer"]:
        reader = common.load_module("metrics", m["name"] + ".py")
        assert callable(reader.read)


def test_names_and_units(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [x["name"] for x in
             metrics + bench["workloads"] + bench["configs"]]
    names += [c["traffic"] for c in bench["workloads"]]
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_moves_reported_by_every_listed_cell(bench):
    cells = [c["name"] for c in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)


def test_every_cell_reports_enough(bench):
    cells = [c["name"] for c in bench["workloads"]]
    for cell in cells:
        e2e = [m["name"] for m in bench["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"]), cell


def test_bounds(bench):
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
