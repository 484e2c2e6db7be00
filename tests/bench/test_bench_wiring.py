"""Every cell of ``BENCHMARK.json`` is wired: each per-layer metric that
applies to it has a reader, and its driver, run once on the files of each
configuration its cells name, cut to the tests' tiny size, is correct
against that configuration's own reference and returns each end-to-end
metric that applies to the configuration's cells (``setup_s`` is the
harness's own)."""
import copy
import json
import os

import pytest

from bench_helpers import ROOT, TINY

from bench import run as harness

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
SPECS = {cell: harness.load_cell(cell) for cell in CELLS}
DRIVERS = sorted({s["traffic"]["driver"] for s in SPECS.values()})


@pytest.mark.parametrize("cell", CELLS)
def test_every_per_layer_metric_has_a_reader(cell):
    for m in SPECS[cell]["per_layer"]:
        path = os.path.join(ROOT, "bench", "layer_metrics", m["name"] + ".py")
        assert os.path.isfile(path), f"{cell}: no reader for {m['name']}"
        reader = harness.load_module(path, "t_" + m["name"].replace(".", "_"))
        assert callable(reader.read), m["name"]


@pytest.mark.parametrize("name", DRIVERS)
def test_the_driver_returns_every_end_to_end_metric(name):
    by_config = {}
    for cell, s in SPECS.items():
        if s["traffic"]["driver"] == name:
            by_config.setdefault(s["cell"]["config"], {})[cell] = s
    driver = harness.load_module(
        os.path.join(ROOT, "bench", "drivers", name + ".py"),
        "bench_driver_" + name)
    for conf, specs in by_config.items():
        want = {cell: {m["name"] for m in s["end_to_end"]} - {"setup_s"}
                for cell, s in specs.items()}
        assert all(want.values()), f"a {name} cell reports only setup_s"
        first = next(iter(specs.values()))
        config = copy.deepcopy(first["config"])
        traffic = copy.deepcopy(first["traffic"])
        TINY[name](config, traffic)
        res = driver.run(config, traffic,
                         harness.Context(2**31 + 23, 1.0, False, ""))
        assert harness.checks_pass(res["checks"]) and \
            not res["check_errors"], (conf, res["checks"],
                                      res["check_errors"])
        got = {k for k, v in res["end_to_end"].items() if v is not None}
        for cell, metrics in want.items():
            assert metrics <= got, f"{cell}: {name} never returns " \
                f"{metrics - got}"
