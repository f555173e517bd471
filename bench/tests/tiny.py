"""Tiny copies of the cells for the benchmark's CPU tests: the same
problem, federation and traffic, at sizes a test can hold."""
import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*rel):
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


# each traffic mix's cell and configuration
TRAFFIC = {"solve-t120": ("rhpo-wine.solve", "rhpo-whitewine"),
           "grid64-t120": ("rhpo-wine.grid64", "rhpo-whitewine")}


def tiny_cell(traffic_name: str):
    """(benchmark, cell, config, traffic, limits) shaped as
    `run.load_cell` returns them, shrunk to a CPU test's size."""
    bench = _load("..", "BENCHMARK.json")
    name, config_name = TRAFFIC[traffic_name]
    cell = {"name": name, "config": config_name,
            "traffic": traffic_name, "chips": 1}
    config = copy.deepcopy(_load("configs", config_name + ".json"))
    traffic = copy.deepcopy(_load("traffic", traffic_name + ".json"))
    # the traffic's own T=120: over fewer iterations the control's
    # rounding has not yet grown past the cells' limits at this size
    config["problem"].update(n_samples=160, hidden=4)
    return bench, cell, config, traffic, _load("limits", name + ".json")
