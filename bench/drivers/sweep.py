"""Sweep driver: batches of TLB-simulation cells through ``run_sweep``.

The traffic file names paper-benchmark analogues (access pattern and
footprint), the trace length and the batch size; the configuration names
the compared roster.  From the seed the driver draws every analogue's
demand-paged mapping and trace (``bench/tlb_worlds.py``); the cells are
the analogues in turn, each with its share of the roster (see
:func:`plain_cells`), and the window runs them in batches of ``batch`` cells,
cycling in that fixed order, one ``run_sweep`` call per batch with the
result cache off.

Set-up builds the cells and runs every distinct batch once, so each batch
shape is compiled and loaded before the window.  The window starts batches
until ``--seconds`` have passed and lets the last one finish: the rate is
the simulated accesses of every batch begun in the window over the time
from the window's start to the end of the last one.  A traced run traces
``trace_seconds`` of the first batch from half-way through it (half of
the set-up batch's time), where the lane program scans.

``correct`` compares every cell of one batch the window ran (drawn from
the seed where the window ran more than one): every counter and the
translated PPN of every access, against the plain reference
(``bench/reference/tlb.py``) run on the host's CPU, a few cells at a time
in threads.  A batch that fell back to another backend, was bisected or
handed a cell to the program's oracle counts all its cells as failed.
"""
from __future__ import annotations

import os
import threading
import time
import types
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

NEEDS_HOST_CPU = True
REF_THREADS = 12        # reference cells run at once on the host's CPU

# the SimResult fields the reference reproduces bit for bit
FIELDS = ("accesses", "l1_hits", "l2_regular_hits", "l2_coalesced_hits",
          "walks", "aligned_probes", "pred_correct", "cycles",
          "coverage_mean")


def worlds(traffic: dict, roster: list, seed: int) -> List[dict]:
    """Every analogue's mapping and trace, drawn from the seed."""
    from bench import tlb_worlds as W
    out = []
    for name, pattern, footprint in traffic["benchmarks"]:
        tag = zlib.crc32(name.encode())
        ppn = W.demand_mapping(footprint,
                               np.random.default_rng([seed, tag, 0]))
        tr = W.trace(pattern, ppn, traffic["trace_len"],
                     np.random.default_rng([seed, tag, 1]))
        out.append(dict(name=name, ppn=ppn, trace=tr,
                        specs=W.roster_specs(roster, ppn)))
    return out


def plain_cells(config: dict, traffic: dict, ws: List[dict]) -> list:
    """(world index, spec keywords) of every cell.  Analogue ``a`` takes
    ``methods_per_benchmark`` methods of the roster in turn, starting at
    method ``a * methods_per_benchmark`` (all of them where the file
    gives no number)."""
    n = len(config["roster"])
    per = traffic.get("methods_per_benchmark", n)
    return [(a, w["specs"][(a * per + j) % n])
            for a, w in enumerate(ws) for j in range(per)]


def cells(config: dict, traffic: dict, seed: int):
    """The worlds, the program's sweep cells, and beside each cell what
    the reference needs."""
    from repro.core.page_table import make_mapping
    from repro.core.simulator import MethodSpec
    from repro.core.sweep import SweepCell
    ws = worlds(traffic, config["roster"], seed)
    plain = plain_cells(config, traffic, ws)
    maps = [make_mapping(w["ppn"], name=w["name"]) for w in ws]
    prog = [SweepCell(MethodSpec(**spec), maps[a], ws[a]["trace"])
            for a, spec in plain]
    return ws, prog, plain


def batches(n_cells: int, size: int) -> List[range]:
    return [range(lo, min(lo + size, n_cells))
            for lo in range(0, n_cells, size)]


def run(config: dict, traffic: dict, ctx, sweep_fn=None,
        control: bool = False) -> dict:
    """Set up, run the window, check.  Tests only: ``sweep_fn`` stands in
    for the program's ``run_sweep``, and ``control`` puts the reference
    with a 7-way L2 (see :func:`reference`) in the program's place in the
    check."""
    import jax
    from repro.compile_cache import CompileLog
    from repro.core.sweep import run_sweep

    sweep_fn = sweep_fn or run_sweep
    ws, prog, plain = cells(config, traffic, ctx.seed)
    plan = batches(len(prog), traffic["batch"])
    ctx.log(f"[sweep] {len(ws)} analogues, {len(prog)} cells in "
            f"{len(plan)} batches of up to {traffic['batch']}")
    first = None
    for b in plan:                  # every batch shape, compiled and run
        t0 = time.perf_counter()
        with CompileLog() as log:
            sweep_fn([prog[i] for i in b], cache=False)
        first = first or time.perf_counter() - t0
        ctx.log(f"[sweep] set-up batch {b.start}:{b.stop} "
                f"{time.perf_counter() - t0:.3f} s, {log.count} compiles")

    ran = []                        # (batch index, results, stats)
    tracer = None
    if ctx.trace:
        # a whole batch's trace is far too large to keep: trace a slice
        # of the first batch from half-way through it, where it scans
        tracer = threading.Thread(target=_trace_slice, args=(
            ctx, first / 2, traffic["trace_seconds"]), daemon=True)
    ctx.window_opens()
    t0 = time.perf_counter()
    accesses = 0
    with CompileLog() as log:
        k = 0
        if tracer:
            tracer.start()
        while time.perf_counter() - t0 < ctx.seconds:
            b = plan[k % len(plan)]
            res = sweep_fn([prog[i] for i in b], cache=False)
            ran.append((k % len(plan), res.results, dict(res.stats)))
            accesses += sum(int(prog[i].trace.shape[0]) for i in b)
            k += 1
    window = time.perf_counter() - t0
    if tracer:
        tracer.join()
    failed = sum(len(plan[j]) for j, _, st in ran
                 if st["backend_fallbacks"] or st["bisections"]
                 or st["oracle_fallbacks"])
    attempted = sum(len(plan[j]) for j, _, _ in ran)
    stats = {key: sum(st[key] for _, _, st in ran)
             for key in ("cache_hits", "backend_fallbacks", "bisections",
                         "oracle_fallbacks")}
    ctx.log(f"[sweep] window {window!r} s: {len(ran)} batches, {attempted} "
            f"cells, {accesses} accesses; compiles in window {log.count} "
            f"({log.seconds:.3f} s); {stats}; backend "
            f"{ran[0][2]['backend'] if ran else None}")
    memory_peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0)
                          for d in jax.local_devices()))
    checks, errors = check(traffic, ws, plan, plain, ran, ctx.seed, stats,
                           ctx.log, control)
    return dict(
        end_to_end={"accesses_per_s": accesses / window},
        attempted=attempted, failed=failed, checks=checks,
        check_errors=errors, compiles_in_window=log.count,
        memory_peak_bytes=memory_peak,
        records={"driver": "sweep", "window_s": window,
                 "block": ran[0][2]["block"] if ran else None})


def _trace_slice(ctx, after: float, seconds: float) -> None:
    """Trace ``seconds`` of the window from ``after`` seconds into it,
    marked by the span ``bench.sweep.slice``."""
    import jax
    time.sleep(after)
    ctx.start_trace()
    with jax.profiler.TraceAnnotation("bench.sweep.slice"):
        time.sleep(seconds)
    ctx.stop_trace()


def reference(ws: List[dict], plain: list, idx, ways_less: int = 0
              ) -> Dict[int, dict]:
    """The plain reference's result of every cell in ``idx``, on the
    host's CPU.  ``ways_less`` takes ways from the L2 the configuration
    states: the control, one step less than the stated hierarchy."""
    import jax
    from bench.reference import tlb
    cpu = jax.devices("cpu")[0]

    def one(i):
        w, spec = plain[i]
        if ways_less:
            spec = dict(spec, l2_ways=spec.get("l2_ways", 8) - ways_less)
        with jax.default_device(cpu):
            return i, tlb.run(tlb.MethodSpec(**spec), ws[w]["ppn"],
                              ws[w]["trace"])

    n = min(REF_THREADS, os.cpu_count() or 1)
    with ThreadPoolExecutor(n) as pool:
        return dict(pool.map(one, idx))


def mismatches(got, want: dict) -> List[str]:
    """The fields, and ``ppn``, in which a program result differs from
    the reference's (the arithmetic of ``chip_smoke.oracle_mismatches``)."""
    bad = [f for f in FIELDS if getattr(got, f) != want[f]]
    if not np.array_equal(np.asarray(got.ppn), want["ppn"]):
        bad.append("ppn")
    return bad


def check(traffic, ws, plan, plain, ran, seed, stats, log,
          control: bool = False):
    """Mismatched fields over every cell of one batch the window ran,
    drawn from the seed.  With ``control`` the reference with a 7-way L2
    stands in for the program's results."""
    errors = []
    if stats["cache_hits"]:
        errors.append(f"{stats['cache_hits']} results came from a cache")
    if not ran:
        errors.append("no batch ran in the window")
        return {"mismatched_fields": {"value": None,
                                      "limit": traffic["check"]["limit"]}
                }, errors
    k = int(np.random.default_rng([seed, 4]).integers(len(ran)))
    j, results, _ = ran[k]
    idx = list(plan[j])
    t0 = time.perf_counter()
    want = reference(ws, plain, idx)
    got = (reference(ws, plain, idx, ways_less=1) if control else
           {i: results[i - plan[j].start] for i in idx})
    bad_fields = 0
    for i in idx:
        w, spec = plain[i]
        g = got[i]
        bad = mismatches(types.SimpleNamespace(**g) if control else g,
                         want[i])
        if bad:
            bad_fields += len(bad)
            log(f"[check] cell {i} ({ws[w]['name']}, {spec['name']}) "
                f"differs in {', '.join(bad)}")
    log(f"[check] every cell of batch {k} ({len(idx)} cells) against the "
        f"reference on the CPU in {time.perf_counter() - t0:.1f} s; "
        f"{bad_fields} fields differ")
    return {"mismatched_fields": {"value": bad_fields,
                                  "limit": traffic["check"]["limit"]}
            }, errors
