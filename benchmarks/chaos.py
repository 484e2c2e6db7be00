"""Chaos bench: seeded fault schedules over the serving + sweep runtimes.

Every row answers one question: did the runtime ABSORB this fault kind?
``status`` is ``recovered`` when the faulted run finished with results
bit-exact (sweeps) or token-exact (serving) against the fault-free run,
``lost`` otherwise — CI greps for at least one ``recovered`` row per fault
kind.  The ``tlb-parity`` rows additionally report the paper's
coalescing-vs-blast-radius trade: a |K|=k coalesced entry covers up to
2^k translations, so one parity flip invalidates more reach than a Base
entry loss (``detail`` shows the invalidated-entry and extra-walk bill of
detect-invalidate-rewalk recovery vs idealized ECC).
"""
from __future__ import annotations

import dataclasses
import os
import tempfile

import numpy as np

from repro.core.baselines import base_spec, colt_spec, kaligned_for_histogram
from repro.core.sweep import SweepCell, run_sweep
from repro.robustness import (FaultPlan, EngineCrash, KVCorruption, PageLoss,
                              backend_fault_injection, corrupt_cache_entry,
                              make_parity_world, run_engine_with_recovery)
from .tlb_suite import (MULTITENANT_MAX_PAGES, NESTED_MAX_PAGES,
                        _scenario_world)

_COUNTERS = ("accesses", "l1_hits", "l2_regular_hits", "l2_coalesced_hits",
             "walks", "aligned_probes", "pred_correct", "cycles",
             "shootdowns")

CHAOS_SEED = 1908


def _same(a, b) -> bool:
    return all(getattr(a, f) == getattr(b, f) for f in _COUNTERS)


def _suite(hist):
    return [base_spec(), colt_spec(),
            kaligned_for_histogram(hist, psi=3)]


def _parity_rows(trace_len, max_pages, backend):
    """tlb-parity: parity-flip faults over live scenario worlds, swept
    under both recovery policies on the batched backends."""
    rows = []
    for name, cap in (("mt-serve-mix", MULTITENANT_MAX_PAGES),
                      ("nested-vm-mix", NESTED_MAX_PAGES)):
        d = _scenario_world(name, trace_len, min(max_pages, cap))
        pw = make_parity_world(d.world, d.trace, seed=CHAOS_SEED, n_faults=3)
        if pw is None:
            continue
        specs = _suite(d.meta["contiguity_histogram"])
        cells = []
        for par in ("parity", "ecc"):
            cells += [SweepCell(dataclasses.replace(s, par_policy=par),
                                pw, d.trace) for s in specs]
        cells += [SweepCell(s, d.world, d.trace) for s in specs]  # fault-free
        res = run_sweep(cells, cache=False, backend=backend)
        n = len(specs)
        for j, s in enumerate(specs):
            flip, ecc, free = res[j], res[n + j], res[2 * n + j]
            ok = _same(ecc, free)        # ECC = fault-free by construction
            rows.append({
                "fault": "tlb-parity", "scenario": name, "cell": s.name,
                "status": "recovered" if ok else "lost",
                "detail": (f"inval={flip.shootdowns - free.shootdowns} "
                           f"extra_walks={flip.walks - free.walks} "
                           f"per {len(pw.faults)} flips")})
    return rows


def _backend_rows(trace_len, max_pages, backend):
    """backend-failure: a Pallas batch that raises recovers on XLA; a cell
    failing EVERY backend bisects down to the pure-python oracle."""
    d = _scenario_world("mt-serve-mix", trace_len,
                        min(max_pages, MULTITENANT_MAX_PAGES))
    specs = _suite(d.meta["contiguity_histogram"])
    cells = [SweepCell(s, d.world, d.trace) for s in specs]
    clean = run_sweep(cells, cache=False, backend=backend)

    rows = []
    with backend_fault_injection(n_failures=1, backends=("pallas",)):
        res = run_sweep(cells, cache=False, backend="pallas")
    ok = (res.stats["backend_fallbacks"] >= 1
          and all(_same(a, b) for a, b in zip(res, clean)))
    rows.append({"fault": "backend-failure", "scenario": "mt-serve-mix",
                 "cell": "pallas->xla fallback",
                 "status": "recovered" if ok else "lost",
                 "detail": f"fallbacks={res.stats['backend_fallbacks']}"})

    cursed = cells[0]
    with backend_fault_injection(
            n_failures=10_000, backends=("pallas", "xla"),
            predicate=lambda sub, bk: any(c is cursed for c in sub)):
        res = run_sweep(cells, cache=False, backend=backend)
    ok = (res.stats["bisections"] >= 1
          and all(_same(a, b) for a, b in zip(res, clean)))
    rows.append({"fault": "backend-failure", "scenario": "mt-serve-mix",
                 "cell": "bisect to oracle",
                 "status": "recovered" if ok else "lost",
                 "detail": (f"bisections={res.stats['bisections']} "
                            f"oracle={res.stats['oracle_fallbacks']}")})
    return rows


def _cache_rows(trace_len, max_pages, backend):
    """cache-corruption: damaged .npz entries are quarantined (surfaced in
    stats) and recomputed to identical results."""
    d = _scenario_world("mt-serve-mix", trace_len,
                        min(max_pages, MULTITENANT_MAX_PAGES))
    specs = _suite(d.meta["contiguity_histogram"])
    cells = [SweepCell(s, d.world, d.trace) for s in specs]
    rows = []
    # This row is ABOUT the cache path: exercise it in a private temp dir
    # even when the harness globally bypasses the shared sweep cache.
    no_cache = os.environ.pop("REPRO_SWEEP_NO_CACHE", None)
    try:
        rows += _cache_rows_cached(cells, backend)
    finally:
        if no_cache is not None:
            os.environ["REPRO_SWEEP_NO_CACHE"] = no_cache
    return rows


def _cache_rows_cached(cells, backend):
    rows = []
    with tempfile.TemporaryDirectory() as cdir:
        first = run_sweep(cells, cache=True, cache_dir=cdir, backend=backend)
        entries = sorted(p for p in os.listdir(cdir) if p.endswith(".npz"))
        for mode, entry in zip(("truncate", "garbage", "schema"), entries):
            corrupt_cache_entry(os.path.join(cdir, entry), mode)
        again = run_sweep(cells, cache=True, cache_dir=cdir, backend=backend)
        ok = (again.stats["cache_quarantined"] == 3
              and all(_same(a, b) for a, b in zip(again, first)))
        rows.append({"fault": "cache-corruption", "scenario": "mt-serve-mix",
                     "cell": "truncate+garbage+schema",
                     "status": "recovered" if ok else "lost",
                     "detail": (f"quarantined="
                                f"{again.stats['cache_quarantined']} "
                                f"hits={again.stats['cache_hits']}")})
    return rows


def _serve_rows():
    """engine-crash / kv-corruption / page-loss: a full serve under a
    seeded fault plan, token-exact against the fault-free run."""
    import time

    from repro.configs import get_config
    from repro.models import Model, RunConfig
    from repro.serve import EngineConfig, ServingEngine

    cfg = get_config("internlm2-1.8b", reduced=True)
    rc = RunConfig(attn_q_chunk=32, attn_kv_chunk=32, scan_chunk=16)
    model = Model(cfg, rc)
    params = model.init(0)
    ec = EngineConfig(page_size=8, num_pages=256, max_batch=3, max_seq=64)

    def make_engine():
        return ServingEngine(model, params, ec)

    rng = np.random.default_rng(2024)
    requests = [(list(rng.integers(0, cfg.vocab, size=12)), 5)
                for _ in range(4)]
    rows = []
    t0 = time.time()
    with tempfile.TemporaryDirectory() as ck:
        baseline, _ = run_engine_with_recovery(
            make_engine, requests, None, ck, max_steps=64)
    plans = [
        ("engine-crash", FaultPlan(CHAOS_SEED, (EngineCrash(step=3),))),
        ("kv-corruption", FaultPlan(CHAOS_SEED,
                                    (KVCorruption(step=2, n_pages=2),))),
        ("page-loss", FaultPlan(CHAOS_SEED, (PageLoss(step=1, n_pages=3),))),
    ]
    for kind, plan in plans:
        with tempfile.TemporaryDirectory() as ck:
            out, rep = run_engine_with_recovery(
                make_engine, requests, plan, ck, max_steps=64,
                snapshot_every=2)
        ok = out == baseline
        rows.append({"fault": kind, "scenario": "serve-tiny",
                     "cell": f"{len(requests)} reqs",
                     "status": "recovered" if ok else "lost",
                     "detail": (f"crashes={rep['crashes']} "
                                f"preempted={rep['preempted']} "
                                f"pages_lost={rep['pages_lost']} "
                                f"wall={time.time() - t0:.0f}s")})
    return rows


def bench_chaos(trace_len=60_000, quick=True, max_pages=MULTITENANT_MAX_PAGES,
                backend="auto"):
    rows = []
    rows += _parity_rows(trace_len, max_pages, backend)
    rows += _backend_rows(trace_len, max_pages, backend)
    rows += _cache_rows(trace_len, max_pages, backend)
    rows += _serve_rows()
    return rows
