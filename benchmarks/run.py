"""Benchmark harness entry point: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) followed by
the full result tables; writes results/benchmarks.json.

    PYTHONPATH=src python -m benchmarks.run [--full | --smoke]

Tiers:

* default — the quick suite (8 benchmarks, 120-150k-access traces)
* ``--full`` — all 16 benchmarks, long traces
* ``--smoke`` — tiny traces and footprints, TLB benches only; exercises the
  whole batched-sweep path end-to-end in seconds (the CI tier).  With
  ``--budget-s N`` the run exits non-zero if it exceeds the time budget.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List

from . import _env  # noqa: F401  (must precede jax-importing modules)
from . import chaos, paged_kernel, roofline_summary, tlb_suite
from repro.compile_cache import enable_compile_cache
from repro.core.sweep import resolve_backend
from repro.scenarios import clear_materialized_cache

SMOKE_TRACE_LEN = 4096
SMOKE_MAX_PAGES = 1 << 15


def _fmt_table(rows: List[Dict[str, Any]]) -> str:
    if not rows:
        return "(empty)"
    cols = list(rows[0].keys())
    w = {c: max(len(str(c)), *(len(str(r.get(c, ""))) for r in rows))
         for c in cols}
    out = ["  ".join(str(c).ljust(w[c]) for c in cols)]
    for r in rows:
        out.append("  ".join(str(r.get(c, "")).ljust(w[c]) for c in cols))
    return "\n".join(out)


BENCHES: List = [
    # (name, paper artifact, fn)
    ("tlb_synthetic", "Table 4 (synth) / Fig 1", tlb_suite.bench_synthetic),
    ("tlb_demand", "Figure 8 / Table 4 (real)", tlb_suite.bench_demand),
    ("tlb_coverage", "Table 5", tlb_suite.bench_coverage),
    ("tlb_predictor", "Table 6", tlb_suite.bench_predictor),
    ("tlb_k_sweep", "Figure 9", tlb_suite.bench_k_sweep),
    ("tlb_cpi", "Figures 10/11", tlb_suite.bench_cpi),
    ("tlb_scenarios", "Workload-derived + adversarial scenarios (registry)",
     tlb_suite.bench_scenarios),
    ("tlb_scenario_contiguity", "Scenario contiguity (Figs 2-3 analogue)",
     tlb_suite.bench_scenario_contiguity),
    ("tlb_dynamic", "Dynamic mapping worlds: mid-trace remaps + shootdowns",
     tlb_suite.bench_dynamic),
    ("tlb_multitenant",
     "Multi-tenant address spaces: ASID tags vs flush-on-switch",
     tlb_suite.bench_multitenant),
    ("tlb_nested",
     "Nested guest→host worlds: shootdown vs hw-coherence",
     tlb_suite.bench_nested),
    ("tlb_accelerator",
     "Accelerator-scale methods: subregion / cache-TLB / dead-protect",
     tlb_suite.bench_accelerator),
    ("tlb_chaos",
     "Chaos harness: fault injection + recovery (recovered vs lost work)",
     chaos.bench_chaos),
    ("dma_fragmentation", "TPU adaptation: descriptor model",
     paged_kernel.bench_dma_vs_fragmentation),
    ("dma_k_ablation", "TPU adaptation: |K| ablation",
     paged_kernel.bench_kernel_classes_ablation),
    ("engine_end_to_end", "TPU adaptation: serving engine",
     paged_kernel.bench_engine_end_to_end),
    ("roofline_summary", "EXPERIMENTS §Roofline (from dry-run artifacts)",
     roofline_summary.bench_roofline_summary),
]


def _derived_metric(name: str, rows: List[Dict[str, Any]]) -> str:
    try:
        if name == "tlb_synthetic":
            mixed = next(r for r in rows if r["mapping"] == "mixed")
            return (f"mixed:|K|=3 rel={mixed.get('|K|=3', '')};"
                    f"anchor rel={mixed['Anchor-Static']}")
        if name == "tlb_demand":
            import numpy as np
            ks = [r["|K|=2"] for r in rows]
            an = [r["Anchor-Static"] for r in rows]
            return (f"mean |K|=2 rel={np.mean(ks):.3f};"
                    f"mean anchor rel={np.mean(an):.3f};"
                    f"reduction vs anchor="
                    f"{1 - np.mean(ks) / max(np.mean(an), 1e-9):.3f}")
        if name == "tlb_predictor":
            import numpy as np
            return "mean acc |K|=2 = {:.3f}".format(
                np.mean([r["|K|=2"] for r in rows]))
        if name == "dma_fragmentation":
            mid = rows[len(rows) // 2]
            return (f"frag=0.5: desc_red={mid['desc_reduction']},"
                    f"speedup={mid['speedup']}")
        if name == "tlb_scenarios":
            import numpy as np
            kv = next(r for r in rows if r["scenario"] == "kv-churn")
            ks = [r["|K|=2"] for r in rows]
            return (f"kv-churn:|K|=2 rel={kv.get('|K|=2', '')};"
                    f"mean |K|=2 rel={np.mean(ks):.3f} over {len(rows)}"
                    " scenarios")
        if name == "tlb_dynamic":
            rel = [r for r in rows if r["metric"] == "rel_misses"]
            sd = [r for r in rows if r["metric"] == "shootdowns"]
            import numpy as np
            return (f"mean |K|=2 rel={np.mean([r['|K|=2'] for r in rel]):.3f}"
                    f" over {len(rel)} dynamic scenarios;"
                    f" total shootdowns |K|=2="
                    f"{sum(r['|K|=2'] for r in sd)}")
        if name == "tlb_multitenant":
            import numpy as np
            rel = [r for r in rows if r["metric"] == "rel_misses"]
            tag = np.mean([r["|K|=3"] for r in rel if r["policy"] == "tag"])
            flush = np.mean([r["|K|=3"] for r in rel
                             if r["policy"] == "flush"])
            return (f"mean |K|=3 rel: tag={tag:.3f} vs flush={flush:.3f}"
                    f" over {len(rel) // 2} scenarios")
        if name == "tlb_nested":
            import numpy as np
            cyc = [r for r in rows if r["metric"] == "stall_cycles"]
            sd = np.mean([r["|K|=3"] for r in cyc
                          if r["policy"] == "shootdown"])
            hw = np.mean([r["|K|=3"] for r in cyc
                          if r["policy"] == "hw-coherence"])
            return (f"mean |K|=3 stall cycles: shootdown={sd:.0f} vs"
                    f" hw-coherence={hw:.0f}"
                    f" ({1 - hw / max(sd, 1e-9):.1%} saved)"
                    f" over {len(cyc) // 2} scenarios")
        if name == "tlb_accelerator":
            import numpy as np
            rel = [r for r in rows if r["metric"] == "rel_misses"]
            ka = np.mean([r["|K|=3"] for r in rel])
            best = min(("Subregion", "Cache-TLB", "Dead-Protect"),
                       key=lambda k: np.mean([r[k] for r in rel]))
            return (f"mean rel misses over {len(rel)} concurrencies:"
                    f" |K|=3={ka:.3f};"
                    f" best accel={best}="
                    f"{np.mean([r[best] for r in rel]):.3f}")
        if name == "engine_end_to_end":
            return f"buddy desc_red={rows[0]['desc_reduction']}"
    except Exception as e:    # derived metrics must never kill the run
        return f"derive-error:{e}"
    return ""


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    tier = ap.add_mutually_exclusive_group()
    tier.add_argument("--full", action="store_true",
                      help="all 16 benchmarks, long traces")
    tier.add_argument("--smoke", action="store_true",
                      help="tiny traces, TLB benches only (CI tier)")
    ap.add_argument("--only", help="comma list of bench names")
    ap.add_argument("--budget-s", type=float, default=None,
                    help="exit non-zero if total wall-clock exceeds this")
    ap.add_argument("--no-cache", action="store_true",
                    help="bypass the on-disk sweep cache")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "xla", "pallas"),
                    help="sweep execution backend (results are bit-exact "
                         "across backends; 'auto' = xla; 'pallas' runs "
                         "interpreted and is refused on a TPU)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.no_cache:
        os.environ["REPRO_SWEEP_NO_CACHE"] = "1"
    only = set(args.only.split(",")) if args.only else None
    t_start = time.time()
    results: Dict[str, Any] = {}
    csv_lines = ["name,us_per_call,derived"]
    for name, artifact, fn in BENCHES:
        if only and name not in only:
            continue
        if args.smoke and not name.startswith("tlb_"):
            continue
        kwargs = {}
        varnames = fn.__code__.co_varnames[:fn.__code__.co_argcount]
        if "quick" in varnames:
            kwargs["quick"] = not args.full
        if "backend" in varnames:
            kwargs["backend"] = args.backend
        if args.smoke:
            if "trace_len" in varnames:
                kwargs["trace_len"] = SMOKE_TRACE_LEN
            if "max_pages" in varnames:
                kwargs["max_pages"] = SMOKE_MAX_PAGES
        t0 = time.time()
        rows = fn(**kwargs)
        dt = time.time() - t0
        # worlds are memoized per-process so one bench builds each once;
        # drop them between benches or --full retains every mapping+trace
        # (hundreds of MB) until exit.  Smoke worlds are tiny — keep them,
        # so benches sharing scenarios (tlb_scenarios /
        # tlb_scenario_contiguity) build each world once per process.
        if not args.smoke:
            clear_materialized_cache()
        results[name] = {"artifact": artifact, "rows": rows,
                         "wall_s": round(dt, 1)}
        n_calls = max(len(rows), 1)
        csv_lines.append(
            f"{name},{dt * 1e6 / n_calls:.0f},{_derived_metric(name, rows)}")
        print(f"\n=== {name}  [{artifact}]  ({dt:.1f}s) ===")
        print(_fmt_table(rows))

    total = time.time() - t_start
    print("\n--- CSV (name,us_per_call,derived) ---")
    for line in csv_lines:
        print(line)
    os.makedirs("results", exist_ok=True)
    tier_name = "smoke" if args.smoke else ("full" if args.full else "quick")
    payload = {"tier": tier_name,
               # record what actually ran ('auto' resolves per platform)
               "backend": resolve_backend(args.backend),
               "total_wall_s": round(total, 1), "sections": results}
    with open("results/benchmarks.json", "w") as f:
        json.dump(payload, f, indent=1)
    print(f"\nwrote results/benchmarks.json  (tier={tier_name}, "
          f"total {total:.1f}s)")
    if args.budget_s is not None and total > args.budget_s:
        print(f"ERROR: exceeded time budget: {total:.1f}s > "
              f"{args.budget_s:.0f}s", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
