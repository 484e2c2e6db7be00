"""Each driver's window at tiny sizes, called as functions, and the
command's refusal of a machine without an accelerator."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

from bench_helpers import CPU, PEAKS, ROOT, spec_of

from bench import run as harness
from bench.drivers import serve, sweep

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks",
        "check_errors"]


def _ctx(seed, seconds=1.0):
    return harness.Context(seed, seconds, False, "")


def test_serve_window(tiny_serve):
    config, traffic = tiny_serve
    traffic["check"]["max_logit_gap"] = 0.1
    spec = spec_of(config, traffic, ["tokens_per_s", "itl_p95_ms",
                                     "setup_s"])
    res = harness.execute(spec, serve, _ctx(2**31 + 11), CPU, PEAKS,
                          interpret=True)
    assert list(res) == KEYS
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"]) == ["max_logit_gap"]
    assert res["device"]["platform"] == "cpu"


def test_serve_replay_matches_the_engine(tiny_serve):
    """The model-free replay lists the class sets the engine's decode
    step is then called with: nothing compiles in the window."""
    config, traffic = tiny_serve
    traffic["check"]["max_logit_gap"] = 0.1
    ctx = _ctx(7, 2.0)
    serve.run(config, traffic, ctx, interpret=True)
    line = [ln for ln in ctx.lines if "compiles in window" in ln][0]
    assert "compiles in window 0 " in line, line


def test_sweep_window(tiny_sweep):
    config, traffic = tiny_sweep
    spec = spec_of(config, traffic, ["accesses_per_s", "setup_s"])
    res = harness.execute(spec, sweep, _ctx(2**31 + 3), CPU, PEAKS)
    assert list(res) == KEYS
    assert res["correct"], res["checks"]
    assert res["checks"] == {"mismatched_fields": {"value": 0, "limit": 0}}
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert res["metrics"]["accesses_per_s"]["value"] > 0


def test_sweep_counts_a_fallback_as_failed(tiny_sweep):
    from repro.core.sweep import run_sweep
    config, traffic = tiny_sweep

    def bisected(cells, **kw):
        res = run_sweep(cells, **kw)
        res.stats["bisections"] = 1
        return res

    res = sweep.run(config, traffic, _ctx(5), sweep_fn=bisected)
    assert res["failed"] == res["attempted"] > 0


def test_the_command_refuses_a_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "sweep.paper", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "needs 1 accelerator" in p.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths
    (no program) exits non-zero and prints no result."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sweep.paper",
         "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v99")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_checks_fail_closed():
    assert not harness.checks_pass({})
    assert not harness.checks_pass({"x": {"value": None, "limit": 1}})
    assert harness.checks_pass({"x": {"value": 1, "limit": 1}})
    assert not harness.checks_pass({"x": {"value": 2, "limit": 1}})


def test_sweep_mismatches_names_the_fields():
    import numpy as np
    want = {f: 1 for f in sweep.FIELDS}
    want["ppn"] = np.arange(3)

    @dataclasses.dataclass
    class R:
        ppn: np.ndarray

    got = R(np.arange(3))
    for f in sweep.FIELDS:
        setattr(got, f, 1)
    assert sweep.mismatches(got, want) == []
    got.walks = 2
    got.ppn = np.array([0, 1, 5])
    assert sweep.mismatches(got, want) == ["walks", "ppn"]
