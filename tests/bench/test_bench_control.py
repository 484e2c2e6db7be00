"""The controls of ``correct``, at sizes a test run holds: each, put in
the program's place and driven through the harness as a run is, must come
out not correct.

The sweep's control is held to the cell's own limit (0: exact).  The
served-token gap shrinks with the model's depth and width, so at the size
a test holds the serve control is held to a limit set from readings at
that size (CPU, 6 seeds, 200 served tokens, 8 layers of width 256): the
program read 0-0.011, the control 0.141-0.348; 0.08 lies between.  At the
cells' own sizes ``bench/control.py`` reads both sides on the chip."""
import json
import os

import pytest

from bench_helpers import CPU, PEAKS, ROOT, spec_of

from bench import control
from bench import run as harness
from bench.drivers import serve, sweep


def _limit(traffic, key):
    with open(os.path.join(ROOT, "bench", "traffic", traffic)) as f:
        return json.load(f)["check"][key]


TEST_SIZE_LIMIT = 0.08


def _deeper(tiny_serve):
    config, traffic = tiny_serve
    config.update(hidden_size=256, num_hidden_layers=8,
                  intermediate_size=512, vocab_size=1024)
    traffic["check"].update(tokens=200, max_logit_gap=TEST_SIZE_LIMIT)
    return config, traffic


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_float8_reference_is_not_correct(tiny_serve, seed):
    config, traffic = _deeper(tiny_serve)
    spec = spec_of(config, traffic, ["tokens_per_s"])
    res = harness.execute(spec, serve, harness.Context(seed, 3.0, False, ""),
                          CPU, PEAKS, interpret=True, control="fp8")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_program_is_correct_at_the_test_size_limit(tiny_serve, seed):
    config, traffic = _deeper(tiny_serve)
    spec = spec_of(config, traffic, ["tokens_per_s"])
    res = harness.execute(spec, serve, harness.Context(seed, 3.0, False, ""),
                          CPU, PEAKS, interpret=True)
    assert res["correct"], res["checks"]


def test_seven_way_l2_is_not_correct(tiny_sweep):
    config, traffic = tiny_sweep
    assert traffic["check"]["limit"] == _limit("paper.json", "limit") == 0
    spec = spec_of(config, traffic, ["accesses_per_s"])
    res = harness.execute(spec, sweep, harness.Context(2**31 + 5, 0.5,
                                                       False, ""),
                          CPU, PEAKS, control=True)
    assert not res["correct"], res["checks"]
    assert control.sweep_readings(config, traffic, 2**31 + 5) > 0
