"""Faithful-reproduction benchmarks: one per paper table/figure.

* ``bench_synthetic``  — Table 4 (synthetic mappings) + Figure 1 structure
* ``bench_demand``     — Figure 8 / Table 4 "Real Mapping" row (demand-paged
                         mapping from the buddy-allocator OS model)
* ``bench_coverage``   — Table 5 (relative translation coverage)
* ``bench_predictor``  — Table 6 (alignment-predictor accuracy)
* ``bench_k_sweep``    — Figure 9 (|K| = 2/3/4 relative to Anchor)
* ``bench_cpi``        — Figures 10/11 (translation cycles per access)
* ``bench_accelerator``— Beyond the paper: accelerator-lineage methods
                         (subregion / cache-TLB / dead-protect) on the
                         concurrency-diluted ``accel-gather`` streams

All traces are synthetic access-pattern analogues of the paper's benchmarks
(no Pin offline); see repro.core.traces.BENCHMARKS and EXPERIMENTS.md for the
fidelity discussion.

Every bench routes through :func:`repro.core.sweep.run_sweep`: all of its
(method, mapping, trace) cells run as lanes of ONE batched vmapped simulation
compiled once per shape bucket, instead of one ``run_method`` compile+scan
per cell.  ``max_pages`` caps mapping footprints so the ``--smoke`` tier can
exercise the identical sweep path in seconds.

Mappings and traces come from the scenario registry
(:mod:`repro.scenarios`): the paper benches use the synthetic families
(``synth-*``, ``paper-*``); ``bench_scenarios``/``bench_scenario_contiguity``
additionally sweep the workload-derived and adversarial scenarios — the
repo's own serving/training stacks as translation workloads.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Sequence, Tuple

from repro.core import (BENCHMARKS, SimResult, base_spec, cluster_spec,
                        colt_spec, kaligned_for_mapping, rmm_spec, thp_spec)
from repro.core.baselines import (anchor_spec, cache_tlb_spec,
                                  dead_protect_spec, kaligned_for_histogram,
                                  subregion_spec)
from repro.core.page_table import contiguity_histogram
from repro.core.sweep import SweepCell, run_sweep
from repro.kvcache.block_table import choose_kernel_classes
from repro.scenarios import get_scenario, list_scenarios

QUICK_BENCHES = ("mcf", "bwaves", "gups", "graph500", "omnetpp", "gromacs",
                 "xalancbmk", "libquantum")
ANCHOR_GRID_QUICK = (4, 6, 8, 10)
MAX_PAGES_DEFAULT = 1 << 19

# scenario lanes swept by bench_scenarios; quick keeps the python-driven
# churn cheap, full runs every workload + adversarial scenario registered
SCENARIO_LANES_QUICK = ("kv-churn", "kv-churn-page", "kv-gather",
                        "train-pipeline", "adv-numa")
SCENARIO_SEEDS = dict(map_seed=0, trace_seed=8)

# dynamic worlds swept by bench_dynamic (every registered dynamic scenario)
DYNAMIC_MAX_PAGES = 1 << 16     # per-epoch records are E× the static cost

# multi-tenant worlds swept by bench_multitenant: per-tenant records are
# n_tenants× the static cost, and the python scheduling drivers cap cheap
MULTITENANT_MAX_PAGES = 1 << 15

# nested worlds swept by bench_nested: every (guest epoch × host epoch)
# union segment materializes a composed view, so records scale with the
# product of both event streams
NESTED_MAX_PAGES = 1 << 15


def _scenario_world(name: str, trace_len: int, max_pages: int):
    data = get_scenario(name).materialize(n_pages=max_pages,
                                          trace_len=trace_len,
                                          **SCENARIO_SEEDS)
    return data


def _paper_world(name: str, trace_len: int, cap, trace_seed: int):
    """(mapping, trace) of a paper-benchmark analogue via the registry."""
    n_pages = min(BENCHMARKS[name][1], cap) if cap else BENCHMARKS[name][1]
    d = get_scenario(f"paper-{name}").materialize(
        n_pages=n_pages, trace_len=trace_len, trace_seed=trace_seed)
    return d.mapping, d.trace


class SweepPlan:
    """Accumulates tagged sweep cells; one ``run_sweep`` serves all rows.

    ``group="anchor"`` cells are reduced to the best (fewest walks) result
    per (row, label) — the Anchor-Static exhaustive-grid policy of §4.1.
    ``backend`` selects the sweep execution backend (``auto``/``xla``/
    ``pallas``); results are bit-exact across backends.
    """

    def __init__(self):
        self.cells: List[SweepCell] = []
        self.tags: List[Tuple[str, str, str]] = []

    def add(self, spec, mapping, trace, row: str, label: str,
            group: str = "plain") -> None:
        self.cells.append(SweepCell(spec, mapping, trace))
        self.tags.append((row, label, group))

    def add_anchor_static(self, mapping, trace, row: str,
                          grid: Iterable[int],
                          label: str = "Anchor-Static") -> None:
        for d in grid:
            self.add(anchor_spec(d), mapping, trace, row, label,
                     group="anchor")

    def run(self, cache: bool = True, backend: str = "auto"
            ) -> Dict[str, Dict[str, SimResult]]:
        sweep = run_sweep(self.cells, cache=cache, backend=backend)
        out: Dict[str, Dict[str, SimResult]] = {}
        for (row, label, group), r in zip(self.tags, sweep.results):
            cols = out.setdefault(row, {})
            if group == "anchor" and label in cols:
                if r.walks < cols[label].walks:
                    cols[label] = r
            else:
                cols[label] = r
        return out


def _add_suite(plan: SweepPlan, m, tr, row: str, anchor_grid,
               psis: Sequence[int] = (2, 3, 4), k_mapping=None,
               k_hist=None, transform=None) -> None:
    """Add the full method suite over world ``m`` (static, dynamic or
    multi-tenant) — the ONE definition of the compared-method roster.

    ``k_mapping`` is the static mapping Algorithm 3 reads the contiguity
    histogram from; defaults to ``m`` (pass the epoch-0 snapshot when ``m``
    is a :class:`~repro.core.page_table.DynamicMapping`).  ``k_hist``
    supplies the histogram directly instead (e.g. the merged per-tenant
    histogram of a multi-tenant world).  ``transform`` post-processes every
    spec (e.g. setting ``ctx_policy``) without forking the roster.
    """
    tx = transform if transform is not None else (lambda s: s)
    k_src = k_mapping if k_mapping is not None else m
    plan.add(tx(base_spec()), m, tr, row, "Base")
    plan.add(tx(thp_spec()), m, tr, row, "THP")
    plan.add(tx(rmm_spec()), m, tr, row, "RMM")
    plan.add(tx(colt_spec()), m, tr, row, "COLT")
    plan.add(tx(cluster_spec()), m, tr, row, "Cluster")
    for d in anchor_grid:
        plan.add(tx(anchor_spec(d)), m, tr, row, "Anchor-Static",
                 group="anchor")
    for psi in psis:
        theta = 1.0 if psi > 2 else 0.9
        spec = (kaligned_for_histogram(k_hist, psi=psi, theta=theta)
                if k_hist is not None
                else kaligned_for_mapping(k_src, psi=psi, theta=theta))
        plan.add(tx(spec), m, tr, row, f"|K|={psi}")


def bench_synthetic(trace_len=150_000, n_pages=1 << 19, quick=True,
                    max_pages=MAX_PAGES_DEFAULT, backend="auto"):
    """Table 4 synthetic-mapping rows."""
    n_pages = min(n_pages, max_pages)
    plan = SweepPlan()
    order = []
    for kind in ("small", "medium", "large", "mixed"):
        d = get_scenario(f"synth-{kind}").materialize(
            n_pages=n_pages, trace_len=trace_len, map_seed=1, trace_seed=2)
        _add_suite(plan, d.mapping, d.trace, kind, ANCHOR_GRID_QUICK)
        order.append(kind)
    res = plan.run(backend=backend)
    rows = []
    for kind in order:
        cols = res[kind]
        base = cols["Base"].walks
        rows.append({"mapping": kind,
                     **{k: round(v.walks / max(base, 1), 4)
                        for k, v in cols.items()}})
    return rows


def demand_plan(trace_len=150_000, quick=True, max_pages=None
                ) -> Tuple[SweepPlan, Tuple[str, ...]]:
    """The ``tlb_demand`` cells: the full method suite over each paper
    analogue's demand mapping.  Returns ``(plan, benchmark names)``.

    Footprints are only capped in quick/smoke tiers; ``--full`` runs the
    declared paper-scale footprints (up to 4GB of virtual address space).
    """
    cap = max_pages if max_pages is not None else (
        MAX_PAGES_DEFAULT if quick else None)
    benches = QUICK_BENCHES if quick else tuple(BENCHMARKS)
    plan = SweepPlan()
    for name in benches:
        m, tr = _paper_world(name, trace_len, cap, trace_seed=3)
        _add_suite(plan, m, tr, name, ANCHOR_GRID_QUICK, psis=(2,))
    return plan, benches


def bench_demand(trace_len=150_000, quick=True, max_pages=None,
                 backend="auto"):
    """Figure 8: per-benchmark relative misses on the demand mapping."""
    plan, benches = demand_plan(trace_len, quick, max_pages)
    res = plan.run(backend=backend)
    rows = []
    for name in benches:
        cols = res[name]
        base = cols["Base"].walks
        rows.append({"benchmark": name,
                     **{k: round(v.walks / max(base, 1), 4)
                        for k, v in cols.items()}})
    return rows


def bench_coverage(trace_len=120_000, quick=True,
                   max_pages=MAX_PAGES_DEFAULT, backend="auto"):
    """Table 5: relative TLB translation coverage (covered PTEs / 1024)."""
    benches = QUICK_BENCHES[:6] if quick else tuple(BENCHMARKS)
    plan = SweepPlan()
    for name in benches:
        m, tr = _paper_world(name, trace_len, max_pages, trace_seed=4)
        plan.add(base_spec(), m, tr, name, "Base")
        plan.add(colt_spec(), m, tr, name, "COLT")
        plan.add_anchor_static(m, tr, name, grid=(6, 8, 10))
        plan.add(kaligned_for_mapping(m, psi=2), m, tr, name, "|K|=2")
    res = plan.run(backend=backend)
    rows = []
    for name in benches:
        cols = res[name]
        denom = max(cols["Base"].coverage_mean, 1.0)
        rows.append({"benchmark": name, "Base": 1.0,
                     **{k: round(cols[k].coverage_mean / denom, 2)
                        for k in ("COLT", "Anchor-Static", "|K|=2")}})
    return rows


def bench_predictor(trace_len=120_000, quick=True,
                    max_pages=MAX_PAGES_DEFAULT, backend="auto"):
    """Table 6: predictor accuracy per benchmark for |K| = 2, 3, 4."""
    benches = QUICK_BENCHES[:6] if quick else tuple(BENCHMARKS)
    plan = SweepPlan()
    for name in benches:
        m, tr = _paper_world(name, trace_len, max_pages, trace_seed=5)
        for psi in (2, 3, 4):
            plan.add(kaligned_for_mapping(m, psi=psi, theta=1.0), m, tr,
                     name, f"|K|={psi}")
    res = plan.run(backend=backend)
    return [{"benchmark": name,
             **{k: round(v.predictor_accuracy, 3)
                for k, v in res[name].items()}}
            for name in benches]


def bench_k_sweep(trace_len=150_000, n_pages=1 << 19,
                  max_pages=MAX_PAGES_DEFAULT, backend="auto"):
    """Figure 9: misses of |K| modes relative to Anchor-Static (mixed)."""
    d = get_scenario("synth-mixed").materialize(
        n_pages=min(n_pages, max_pages), trace_len=trace_len,
        map_seed=1, trace_seed=6)
    m, tr = d.mapping, d.trace
    plan = SweepPlan()
    plan.add_anchor_static(m, tr, "mixed", grid=ANCHOR_GRID_QUICK)
    for psi in (1, 2, 3, 4):
        plan.add(kaligned_for_mapping(m, psi=psi, theta=1.0), m, tr,
                 "mixed", f"|K|={psi}")
    res = plan.run(backend=backend)["mixed"]
    anch = res["Anchor-Static"]
    return [{"|K|": psi,
             "rel_misses_vs_anchor": round(
                 res[f"|K|={psi}"].walks / max(anch.walks, 1), 4)}
            for psi in (1, 2, 3, 4)]


def bench_cpi(trace_len=120_000, quick=True, max_pages=MAX_PAGES_DEFAULT,
              backend="auto"):
    """Figures 10/11: translation cycles per access."""
    benches = ("gups", "mcf", "graph500") if quick else tuple(BENCHMARKS)
    plan = SweepPlan()
    for name in benches:
        m, tr = _paper_world(name, trace_len, max_pages, trace_seed=7)
        plan.add(base_spec(), m, tr, name, "Base")
        plan.add(thp_spec(), m, tr, name, "THP")
        plan.add(colt_spec(), m, tr, name, "COLT")
        plan.add_anchor_static(m, tr, name, grid=(6, 8, 10))
        for psi in (2, 3):
            plan.add(kaligned_for_mapping(m, psi=psi, theta=1.0), m, tr,
                     name, f"|K|={psi}")
    res = plan.run(backend=backend)
    return [{"benchmark": name,
             **{k: round(v.cpi, 3) for k, v in res[name].items()}}
            for name in benches]


# ---------------------------------------------------------------------------
# Workload-derived / adversarial scenario sweeps (ROADMAP: "open a new
# workload") — the repo's own serving and training stacks as translation
# workloads, plus adversarial contiguity generators.
# ---------------------------------------------------------------------------


def _scenario_names(quick: bool) -> Tuple[str, ...]:
    if quick:
        return SCENARIO_LANES_QUICK
    return tuple(sc.name for sc in list_scenarios("workload")
                 ) + tuple(sc.name for sc in list_scenarios("adversarial"))


def bench_scenarios(trace_len=120_000, quick=True,
                    max_pages=MAX_PAGES_DEFAULT, backend="auto"):
    """Per-scenario relative misses, full method suite through run_sweep.

    Each row is one registered scenario (workload-derived or adversarial):
    mappings and traces recorded from the in-repo systems, swept exactly
    like the paper benches.
    """
    names = _scenario_names(quick)
    plan = SweepPlan()
    for name in names:
        d = _scenario_world(name, trace_len, max_pages)
        _add_suite(plan, d.mapping, d.trace, name, ANCHOR_GRID_QUICK,
                   psis=(2, 3))
    res = plan.run(backend=backend)
    rows = []
    for name in names:
        cols = res[name]
        base = cols["Base"].walks
        rows.append({"scenario": name,
                     **{k: round(v.walks / max(base, 1), 4)
                        for k, v in cols.items()}})
    return rows


def dynamic_plan(names: Sequence[str], trace_len=120_000,
                 max_pages=MAX_PAGES_DEFAULT) -> SweepPlan:
    """The ``tlb_dynamic`` cells of the named dynamic scenarios: the full
    method suite over each world's epoch stream."""
    plan = SweepPlan()
    for name in names:
        d = _scenario_world(name, trace_len, min(max_pages,
                                                 DYNAMIC_MAX_PAGES))
        # K is chosen by Algorithm 3 from the epoch-0 histogram — what the
        # OS saw at launch; the events then degrade it, which is the point
        _add_suite(plan, d.world, d.trace, name, ANCHOR_GRID_QUICK,
                   psis=(2, 3), k_mapping=d.mapping)
    return plan


def bench_dynamic(trace_len=120_000, quick=True, max_pages=MAX_PAGES_DEFAULT,
                  backend="auto"):
    """Dynamic mapping worlds: mid-trace remaps with shootdown-correct TLBs.

    Every registered ``dynamic`` scenario (live event streams instead of
    frozen snapshots) is swept with the full method suite through ONE
    ``run_sweep`` call: lanes are epoch-segmented, and each epoch turnover
    invalidates every cached entry covering a remapped page (translation
    coherence).  Two rows per scenario: relative misses (Base = 1.0) and
    the per-method invalidated-entry counts — time-varying reach is where
    large-reach designs pay for their coverage.
    """
    names = tuple(sc.name for sc in list_scenarios("dynamic"))
    res = dynamic_plan(names, trace_len, max_pages).run(backend=backend)
    rows = []
    for name in names:
        cols = res[name]
        base = cols["Base"].walks
        rows.append({"scenario": name, "metric": "rel_misses",
                     **{k: round(v.walks / max(base, 1), 4)
                        for k, v in cols.items()}})
        rows.append({"scenario": name, "metric": "shootdowns",
                     **{k: v.shootdowns for k, v in cols.items()}})
    return rows


def bench_multitenant(trace_len=120_000, quick=True,
                      max_pages=MAX_PAGES_DEFAULT, backend="auto"):
    """Multi-tenant address spaces: ASID-tagged TLBs under context-switch
    pressure, each scenario swept under BOTH context-switch policies.

    Every registered ``multitenant`` scenario (tenants drawn from
    different contiguity families, scheduled by the serving stack's own
    KVScheduler; see :mod:`repro.scenarios.multitenant`) runs the full
    9-method suite twice — ``ctx_policy="flush"`` (untagged hardware wipes
    the TLB every switch) and ``"tag"`` (ASID-tagged entries survive;
    recycled ASIDs pay targeted invalidation) — through ONE ``run_sweep``
    call per policy set.  K for the K-bit Aligned rows comes from the
    *merged* per-tenant contiguity histogram (Algorithm 3 over what an OS
    aggregating per-process stats would see).  Rows: per (scenario,
    policy) relative misses (Base = 1.0) and invalidated-entry counts —
    switch-heavy schedules are where large-reach designs pay for their
    coverage twice, once per tenant.
    """
    names = tuple(sc.name for sc in list_scenarios("multitenant"))
    rows = []
    for name in names:
        d = _scenario_world(name, trace_len, min(max_pages,
                                                 MULTITENANT_MAX_PAGES))
        # one plan (= one run_sweep) PER world: MT lanes are segmented on
        # that world's switch schedule, so batching all scenarios together
        # would pad every lane to the union (n_segments, seg_len) grid —
        # the smoke tier paid ~3x padded steps for the mixed batch
        plan = SweepPlan()
        for policy in ("flush", "tag"):
            _add_suite(
                plan, d.world, d.trace, f"{name}::{policy}",
                ANCHOR_GRID_QUICK, psis=(2, 3, 4),
                k_hist=d.meta["contiguity_histogram"],
                transform=lambda s, p=policy: dataclasses.replace(
                    s, ctx_policy=p))
        res = plan.run(backend=backend)
        for policy in ("flush", "tag"):
            cols = res[f"{name}::{policy}"]
            base = cols["Base"].walks
            rows.append({"scenario": name, "policy": policy,
                         "metric": "rel_misses",
                         **{k: round(v.walks / max(base, 1), 4)
                            for k, v in cols.items()}})
            rows.append({"scenario": name, "policy": policy,
                         "metric": "shootdowns",
                         **{k: v.shootdowns for k, v in cols.items()}})
    return rows


def bench_nested(trace_len=120_000, quick=True,
                 max_pages=MAX_PAGES_DEFAULT, backend="auto"):
    """Nested guest→host translation worlds, each scenario swept under BOTH
    translation-coherence policies.

    Every registered ``nested`` scenario (per-VM guest page tables composed
    over a host layer the hypervisor rewrites mid-trace, VM schedules from
    the serving stack's KVScheduler; see :mod:`repro.scenarios.nested`)
    runs the full method suite twice — ``coh_policy="shootdown"`` (every
    host/guest remap storm pays the fixed IPI cost plus per-entry
    invalidation) and ``"hw-coherence"`` (a coherence-participating TLB
    drops the same entries for only the per-entry cost) — through ONE
    ``run_sweep`` call per world.  Both policies invalidate identical
    entry sets, so walks/hits are bit-identical and only cycles move: the
    ``rel_misses`` rows are policy-invariant by construction while the
    ``stall_cycles`` rows isolate exactly the coherence tax.  K for the
    K-bit Aligned rows comes from the merged *composed* contiguity
    histogram (what Algorithm 3 sees through both levels).  Rows: per
    (scenario, policy) relative misses (Base = 1.0), invalidated-entry
    counts, and total translation stall cycles.
    """
    names = tuple(sc.name for sc in list_scenarios("nested"))
    rows = []
    for name in names:
        d = _scenario_world(name, trace_len, min(max_pages,
                                                 NESTED_MAX_PAGES))
        # one plan (= one run_sweep) per world, as in bench_multitenant:
        # nested lanes segment on that world's union grid (guest epochs ∪
        # host epochs ∪ VM switches) and batching worlds would pad all
        # lanes to the union shape
        plan = SweepPlan()
        for policy in ("shootdown", "hw-coherence"):
            _add_suite(
                plan, d.world, d.trace, f"{name}::{policy}",
                ANCHOR_GRID_QUICK, psis=(2, 3),
                k_hist=d.meta["contiguity_histogram"],
                transform=lambda s, p=policy: dataclasses.replace(
                    s, coh_policy=p))
        res = plan.run(backend=backend)
        for policy in ("shootdown", "hw-coherence"):
            cols = res[f"{name}::{policy}"]
            base = cols["Base"].walks
            rows.append({"scenario": name, "policy": policy,
                         "metric": "rel_misses",
                         **{k: round(v.walks / max(base, 1), 4)
                            for k, v in cols.items()}})
            rows.append({"scenario": name, "policy": policy,
                         "metric": "shootdowns",
                         **{k: v.shootdowns for k, v in cols.items()}})
            rows.append({"scenario": name, "policy": policy,
                         "metric": "stall_cycles",
                         **{k: v.cycles for k, v in cols.items()}})
    return rows


def bench_accelerator(trace_len=120_000, quick=True,
                      max_pages=MAX_PAGES_DEFAULT, backend="auto"):
    """Accelerator-scale translation: the three accelerator-lineage kinds
    against the paper's best CPU-scale scheme on concurrency-diluted
    gather streams.

    Every registered ``accelerator`` scenario (the kv-gather DMA recording
    interleaved as 64/256/1024 concurrent streams; see
    :mod:`repro.scenarios.accelerator`) is swept with Base, |K|=3 Aligned
    (Algorithm 3 over the scenario's contiguity histogram — the histogram
    is concurrency-invariant, so K is identical across rows), and the
    three accelerator-lineage methods: Subregion (bitmap windows),
    Cache-TLB (cache-backed reach), Dead-Protect (dead-fill bypass).  Two
    rows per scenario: relative misses (Base = 1.0) and translation
    cycles per access — cache-backed reach trades walks for slower side
    hits, so the two metrics deliberately disagree.
    """
    names = tuple(sc.name for sc in list_scenarios("accelerator"))
    plan = SweepPlan()
    for name in names:
        d = _scenario_world(name, trace_len, max_pages)
        m, tr = d.mapping, d.trace
        plan.add(base_spec(), m, tr, name, "Base")
        plan.add(kaligned_for_histogram(d.meta["contiguity_histogram"],
                                        psi=3, theta=1.0),
                 m, tr, name, "|K|=3")
        plan.add(subregion_spec(), m, tr, name, "Subregion")
        plan.add(cache_tlb_spec(), m, tr, name, "Cache-TLB")
        plan.add(dead_protect_spec(), m, tr, name, "Dead-Protect")
    res = plan.run(backend=backend)
    rows = []
    for name in names:
        cols = res[name]
        base = cols["Base"].walks
        rows.append({"scenario": name, "metric": "rel_misses",
                     **{k: round(v.walks / max(base, 1), 4)
                        for k, v in cols.items()}})
        rows.append({"scenario": name, "metric": "cycles_per_access",
                     **{k: round(v.cpi, 3) for k, v in cols.items()}})
    return rows


_HIST_BUCKETS = ((1, 1), (2, 15), (16, 63), (64, 255), (256, 511),
                 (512, 100_000_000))


def bench_scenario_contiguity(trace_len=120_000, quick=True,
                              max_pages=MAX_PAGES_DEFAULT):
    """Per-scenario contiguity histograms (the Figs 2–3 measurement, run on
    our own workloads): % of mapped pages living in chunks of each size
    bucket, plus the K Algorithm 3 picks from the histogram."""
    names = _scenario_names(quick)
    rows = []
    for name in names:
        d = _scenario_world(name, trace_len, max_pages)
        hist = d.meta.get("contiguity_histogram") or \
            contiguity_histogram(d.mapping)
        total = sum(s * f for s, f in hist.items()) or 1
        row = {"scenario": name,
               "mapped_pages": int((d.mapping.ppn >= 0).sum()),
               "chunks": int(sum(hist.values()))}
        for lo, hi in _HIST_BUCKETS:
            pct = 100.0 * sum(s * f for s, f in hist.items()
                              if lo <= s <= hi) / total
            label = f"{lo}" if lo == hi else \
                (f"{lo}+" if hi >= 100_000_000 else f"{lo}-{hi}")
            row[f"pages in {label}"] = round(pct, 1)
        row["K (Alg 3)"] = str(choose_kernel_classes(hist, psi=3) or [0])
        rows.append(row)
    return rows
