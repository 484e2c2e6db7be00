"""The program's spans (``repro.spans``) on the profiler's clock: a tiny
sweep traced as the benchmark traces, inside a ``bench.`` annotation and
reduced by ``bench/trace_reduce.py``, shows every ``repro.sweep.*`` span
among the host events where its in-memory record puts it."""
import time

import jax
import numpy as np

from bench import trace_reduce
from repro import spans
from repro.core import base_spec, colt_spec, demand_mapping, generate_trace
from repro.core.sweep import SweepCell, run_sweep

SWEEP = ("repro.sweep.batch", "repro.sweep.pack",
         "repro.sweep.pack.maps", "repro.sweep.pack.fills",
         "repro.sweep.pack.clusters", "repro.sweep.pack.stacks",
         "repro.sweep.upload", "repro.sweep.scan", "repro.sweep.readback")


def test_spans_agree_with_the_profiler(tmp_path):
    """Every sweep span shows among the trace's host events, starting
    within 1 ms of its in-memory record."""
    m = demand_mapping(1 << 11, seed=3)
    m2 = demand_mapping(1 << 10, seed=4)
    tr = generate_trace("multiscale", 0, 700, seed=5, mapping=m)
    tr2 = generate_trace("zipf", 0, 500, seed=6, mapping=m2)
    cells = [SweepCell(s, w, t) for w, t in ((m, tr), (m2, tr2))
             for s in (base_spec(), colt_spec())]
    run_sweep(cells, cache=False)           # compiled before the trace
    mark = max((r.id for r in spans.recorded()), default=0)
    with jax.profiler.trace(str(tmp_path)):
        t0_ns = time.time_ns()
        with jax.profiler.TraceAnnotation("bench.test"):
            run_sweep(cells, cache=False)
    mine = [r for r in spans.recorded() if r.id > mark]
    trace = trace_reduce.load(str(tmp_path))
    host = {}
    for name, s, _ in trace.host:
        host.setdefault(name, []).append(s)
    for name in SWEEP:
        want = sorted((r.start_ns - t0_ns) / 1e9 for r in mine
                      if r.name == name)
        got = sorted(host.get(name, []))
        assert len(got) == len(want) >= 1, name
        np.testing.assert_allclose(got, want, atol=1e-3, rtol=0,
                                   err_msg=name)
