"""``chip_smoke.py`` at tiny sizes on the CPU, and where the compile cache
lives.

The smoke script's phase functions run here in interpret mode on reduced
sizes; its checks must pass on an honest run and fail once one result is
perturbed.  Run as a script without a TPU, it must refuse.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from benchmarks import tlb_suite  # noqa: E402
from repro.compile_cache import DEFAULT_DIR, enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.sweep import run_sweep  # noqa: E402
from repro.models import RunConfig  # noqa: E402


@pytest.fixture(scope="module")
def cpu():
    return jax.devices("cpu")[0]


@pytest.fixture(scope="module")
def dyn_sweep():
    plan = tlb_suite.dynamic_plan(("dyn-kv-churn",), trace_len=1024,
                                  max_pages=1 << 10)
    return plan.cells, run_sweep(plan.cells, cache=False)


def test_sweep_phase_passes_at_tiny_size(dyn_sweep, cpu):
    cells, sweep = dyn_sweep
    assert chip_smoke.check_sweep(sweep, cells, chip_smoke.run_method_dynamic,
                                  cpu) == []
    kinds = {cells[i].spec.kind for i in chip_smoke.cells_to_check(cells)}
    assert kinds == {c.spec.kind for c in cells}


@pytest.mark.parametrize("perturb", ["counter", "ppn", "fallback"])
def test_sweep_check_fails_on_one_perturbed_result(dyn_sweep, cpu, perturb):
    cells, sweep = dyn_sweep
    i = chip_smoke.cells_to_check(cells)[-1]
    results = list(sweep.results)
    stats = dict(sweep.stats)
    r = results[i]
    if perturb == "counter":
        results[i] = dataclasses.replace(r, walks=r.walks + 1)
    elif perturb == "ppn":
        ppn = np.array(r.ppn)
        ppn[len(ppn) // 2] += 1
        results[i] = dataclasses.replace(r, ppn=ppn)
    else:
        stats["backend_fallbacks"] = 1
    bad = chip_smoke.check_sweep(type(sweep)(results, stats), cells,
                                 chip_smoke.run_method_dynamic, cpu)
    assert len(bad) == 1, bad


@pytest.fixture(scope="module")
def served():
    return chip_smoke.serve(
        get_config("internlm2-1.8b", reduced=True), seed=0, n_requests=3,
        prompt_len=21, new_tokens=4, page_size=8, num_pages=64,
        max_batch=2, max_seq=64, interpret=True,
        rc=RunConfig(attn_q_chunk=32, attn_kv_chunk=32, scan_chunk=16))


def test_serve_phase_passes_at_tiny_size(served):
    assert chip_smoke.check_serve(served) == []
    eng = served["engine"]
    assert set(served["rows"]) == {0, 1, 2}   # every request
    for rid, rows in served["rows"].items():
        # the prefill row, then one row per decode step
        assert len(rows) == eng.requests[rid].max_new_tokens
    assert "tpu_custom_call" not in served["hlo"]   # interpret mode


def test_serve_check_fails_on_one_perturbed_logit(served):
    rows = served["rows"][2]
    kept = rows[-1]
    try:
        rows[-1] = kept.copy()
        rows[-1][int(np.argmax(kept))] += 1.0 * np.sqrt(np.mean(kept ** 2))
        assert len(chip_smoke.check_serve(served)) == 1
    finally:
        rows[-1] = kept


def test_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_compile_cache_dir(monkeypatch):
    """The environment's directory wins and the code sets none; else the
    cache lands at the repository root."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
        assert DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
