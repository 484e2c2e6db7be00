"""Multi-tenant address spaces: ASID-tagged coalesced TLBs under
context-switch pressure.

The contract this file pins down:

* **Parity** — the switch-segmented sweep lanes are bit-exact
  (hit/miss/evict/shootdown counters AND every translated PPN) against the
  pure-python oracle :func:`repro.core.simulator.run_method_multitenant`
  for all 8 method kinds × both context-switch policies × both backends.
* **Isolation** — no access EVER translates through another tenant's
  entry: ``result.ppn[t] == tenant_at(t).ppn[trace[t]]`` for every method
  and policy (the multi-tenant analogue of the dynamic worlds' no-stale
  property).
* **ASID semantics** — a recycled ASID never serves the departed tenant's
  translations; tags beat flushes when resident working sets fit; the
  cache key distinguishes schedules and policies.

Heaviest variants (scenario-scale traces) are ``@pytest.mark.slow`` with
small fast stand-ins, per the repo convention.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import demand_mapping
from repro.core.baselines import (anchor_spec, base_spec, cluster_spec,
                                  colt_spec, kaligned_spec, rmm_spec,
                                  thp_spec)
from repro.core.page_table import (MultiTenantMapping,
                                   build_multitenant_mapping, make_mapping)
from repro.core.simulator import (LAT_CTX_SWITCH, run_method,
                                  run_method_multitenant)
from repro.core.sweep import SweepCell, cell_key, run_sweep
from repro.scenarios import clear_materialized_cache, get_scenario, \
    list_scenarios

COUNTERS = ("accesses", "l1_hits", "l2_regular_hits", "l2_coalesced_hits",
            "walks", "aligned_probes", "pred_correct", "cycles",
            "coverage_mean", "shootdowns")

ALL_KINDS = [base_spec(), thp_spec(), colt_spec(), cluster_spec(), rmm_spec(),
             anchor_spec(6), kaligned_spec([9, 6, 4]),
             kaligned_spec([6, 4], use_predictor=False, name="ka-nopred")]
POLICIES = ("flush", "tag")


def _with_policy(specs, policy):
    return [dataclasses.replace(s, ctx_policy=policy) for s in specs]


def _assert_equal(got, want, ctx):
    for f in COUNTERS:
        assert getattr(got, f) == getattr(want, f), (ctx, f)
    np.testing.assert_array_equal(got.ppn, want.ppn, err_msg=str(ctx))


def _assert_isolated(world: MultiTenantMapping, trace, result, ctx):
    """Every access translates in the tenant scheduled at that step."""
    bounds = list(world.boundaries) + [len(trace)]
    for s in range(world.n_segments):
        lo, hi = bounds[s], bounds[s + 1]
        m = world.tenants[world.tenant_ids[s]]
        np.testing.assert_array_equal(
            result.ppn[lo:hi], np.asarray(m.ppn)[trace[lo:hi]],
            err_msg=f"cross-tenant translation in segment {s} ({ctx})")


# ---------------------------------------------------------------------------
# Worlds
# ---------------------------------------------------------------------------


def _segment_trace(world: MultiTenantMapping, total: int, seed: int):
    """Random per-segment accesses, each mapped in its segment's tenant."""
    rng = np.random.default_rng(seed)
    bounds = list(world.boundaries) + [total]
    parts = []
    for s in range(world.n_segments):
        m = world.tenants[world.tenant_ids[s]]
        mv = np.flatnonzero(m.ppn >= 0)
        parts.append(mv[rng.integers(0, mv.size, bounds[s + 1] - bounds[s])])
    return np.concatenate(parts).astype(np.int64)


@pytest.fixture(scope="module")
def hand_world():
    """Three tenants with different contiguity (demand / fully contiguous /
    THP-ish), schedule with revisits AND an ASID recycle (tenant 2 takes
    tenant 0's ASID after it departs)."""
    ta = demand_mapping(1 << 10, seed=1)
    tb = make_mapping(np.arange(1 << 10, dtype=np.int64) + 3, name="contig")
    tc = demand_mapping(1 << 9, seed=7, thp=True)
    mt = build_multitenant_mapping(
        [ta, tb, tc],
        [(0, 0, 0), (60, 1, 1), (130, 0, 0), (200, 1, 1),
         (260, 2, 0), (330, 1, 1), (400, 2, 0)],
        name="mt-hand")
    assert sum(mt.recycled) >= 1      # the tenant-2 takeover of ASID 0
    trace = _segment_trace(mt, 470, seed=5)
    return mt, trace


@pytest.fixture(scope="module")
def hand_cells(hand_world):
    """8 kinds × both policies over the hand world — one 16-lane batch."""
    mt, trace = hand_world
    specs = _with_policy(ALL_KINDS, "flush") + _with_policy(ALL_KINDS, "tag")
    return specs, [SweepCell(s, mt, trace) for s in specs]


@pytest.fixture(scope="module")
def hand_oracle(hand_world, hand_cells):
    mt, trace = hand_world
    specs, _ = hand_cells
    return [run_method_multitenant(s, mt, trace) for s in specs]


@pytest.fixture(scope="module")
def hand_sweep_xla(hand_cells):
    _, cells = hand_cells
    return run_sweep(cells, cache=False, backend="xla")


# ---------------------------------------------------------------------------
# Parity: lanes == oracle, both policies, both backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("j", range(2 * len(ALL_KINDS)),
                         ids=lambda j: (POLICIES[j // len(ALL_KINDS)] + "-"
                                        + ALL_KINDS[j % len(ALL_KINDS)].name))
def test_lane_matches_oracle_xla(hand_cells, hand_oracle, hand_sweep_xla, j):
    specs, _ = hand_cells
    _assert_equal(hand_sweep_xla.results[j], hand_oracle[j],
                  (specs[j].name, specs[j].ctx_policy, "xla"))


def test_lane_matches_oracle_pallas(hand_cells, hand_oracle):
    """The Pallas kernel runs the same switch pass in-kernel (interpret
    mode on CPU): bit-exact for every kind × policy."""
    specs, cells = hand_cells
    sweep = run_sweep(cells, cache=False, backend="pallas", block_size=4)
    for j, s in enumerate(specs):
        _assert_equal(sweep.results[j], hand_oracle[j],
                      (s.name, s.ctx_policy, "pallas"))


@pytest.mark.parametrize("tb", [1, 8])
@pytest.mark.parametrize("form", ["platform", "one_hot"])
def test_block_size_invariance(request, hand_cells, hand_oracle, tb, form):
    """Block boundaries never straddle a switch; results are identical for
    any block size, in the platform's state-access form and in the one-hot
    form a TPU lowering picks."""
    if form == "one_hot":
        request.getfixturevalue("one_hot_state_access")
    _, cells = hand_cells
    sweep = run_sweep(cells, cache=False, backend="xla", block_size=tb)
    for j, want in enumerate(hand_oracle):
        _assert_equal(sweep.results[j], want, ("tb", tb, j))


def test_isolation_no_cross_tenant_translation(hand_world, hand_cells,
                                               hand_sweep_xla, hand_oracle):
    """THE multi-tenant correctness property: under either policy no
    method ever returns another tenant's translation — from the oracle or
    from the engine."""
    mt, trace = hand_world
    specs, _ = hand_cells
    for j, s in enumerate(specs):
        _assert_isolated(mt, trace, hand_oracle[j],
                         (s.name, s.ctx_policy, "oracle"))
        _assert_isolated(mt, trace, hand_sweep_xla.results[j],
                         (s.name, s.ctx_policy, "xla"))


# ---------------------------------------------------------------------------
# ASID semantics
# ---------------------------------------------------------------------------


def test_recycled_asid_never_serves_dead_tenant():
    """Tenant C inherits tenant A's ASID; under the tag policy C's first
    access must WALK (A's entry for the same vpn is invalidated by the
    recycle), and must translate through C's page table."""
    ta = make_mapping(np.full(8, 100, np.int64) + np.arange(8), name="A")
    tc = make_mapping(np.full(8, 200, np.int64) + np.arange(8), name="C")
    mt = build_multitenant_mapping([ta, tc], [(0, 0, 0), (4, 1, 0)],
                                   name="recycle")
    assert mt.recycled == (False, True)
    trace = np.array([0, 1, 0, 1, 0, 1, 0, 1], np.int64)
    spec = dataclasses.replace(base_spec(), ctx_policy="tag")
    r = run_method_multitenant(spec, mt, trace)
    # A: walks at t=0,1 then L1 hits; C: must walk again at t=4,5
    assert r.walks == 4
    np.testing.assert_array_equal(
        r.ppn, np.array([100, 101, 100, 101, 200, 201, 200, 201]))
    # engine agrees
    sweep = run_sweep([SweepCell(spec, mt, trace)], cache=False,
                      backend="xla")
    _assert_equal(sweep.results[0], r, "recycle")


def test_tag_retains_resident_tenants_flush_refaults():
    """Two tiny tenants alternating: their working sets fit every
    structure, so ASID tags keep both resident (walks = cold misses only)
    while flush-on-switch refaults every quantum."""
    ta = make_mapping(np.arange(32, dtype=np.int64) * 3 + 50, name="A")
    tb = make_mapping(np.arange(32, dtype=np.int64) * 5 + 900, name="B")
    sched = [(i * 32, i % 2, i % 2) for i in range(8)]
    mt = build_multitenant_mapping([ta, tb], sched, name="pingpong")
    trace = np.tile(np.arange(32, dtype=np.int64), 8)
    flush = run_method_multitenant(
        dataclasses.replace(base_spec(), ctx_policy="flush"), mt, trace)
    tag = run_method_multitenant(
        dataclasses.replace(base_spec(), ctx_policy="tag"), mt, trace)
    assert tag.walks == 64            # cold misses only: 2 tenants x 32
    assert flush.walks == 256         # every quantum refaults its 32 pages
    assert tag.cycles < flush.cycles
    assert flush.shootdowns > 0 and tag.shootdowns == 0
    # both policies charge the same 7 x LAT_CTX_SWITCH, so the entire cycle
    # gap is the refault walks (base: 7-cycle miss chain + 50-cycle walk)
    assert flush.cycles - tag.cycles == (flush.walks - tag.walks) * (7 + 50)
    assert LAT_CTX_SWITCH > 0


def test_single_segment_multitenant_equals_static():
    """A one-tenant, one-segment MultiTenantMapping is just that tenant's
    static world."""
    m = demand_mapping(1 << 10, seed=3)
    mt = build_multitenant_mapping([m], [(0, 0, 0)], name="solo")
    mv = np.flatnonzero(m.ppn >= 0)
    trace = mv[np.random.default_rng(0).integers(0, mv.size, 300)]
    for spec in (base_spec(), kaligned_spec([6, 4])):
        want = run_method(spec, m, trace)
        got = run_method_multitenant(spec, mt, trace)
        for f in COUNTERS[:-1]:
            assert getattr(got, f) == getattr(want, f), f
        np.testing.assert_array_equal(got.ppn, want.ppn)


def test_mt_cell_key_sensitive_to_schedule_and_policy(hand_world):
    """Same tenants but a different schedule, different ASID assignment,
    or different ctx_policy must never collide in the sweep cache."""
    mt, trace = hand_world
    base = SweepCell(base_spec(), mt, trace)
    other_sched = build_multitenant_mapping(
        list(mt.tenants),
        [(0, 0, 0), (100, 1, 1), (200, 2, 2)], name="other")
    other_asids = MultiTenantMapping(
        mt.tenants, mt.boundaries, mt.tenant_ids,
        tuple((a + 1) % 3 for a in mt.asids), name="reasid")
    keys = {cell_key(base),
            cell_key(SweepCell(base_spec(), other_sched, trace)),
            cell_key(SweepCell(base_spec(), other_asids, trace)),
            cell_key(SweepCell(
                dataclasses.replace(base_spec(), ctx_policy="tag"),
                mt, trace)),
            cell_key(SweepCell(base_spec(), mt.tenants[0], trace))}
    assert len(keys) == 5
    # and it IS stable across rebuilds of an identical world
    rebuilt = build_multitenant_mapping(
        list(mt.tenants),
        [(b, t, a) for b, t, a in zip(mt.boundaries, mt.tenant_ids,
                                      mt.asids)], name="rebuilt")
    assert cell_key(SweepCell(base_spec(), rebuilt, trace)) == cell_key(base)


def test_mixed_batch_static_dynamic_multitenant(hand_world):
    """One run_sweep over static + multi-tenant cells: the partition keeps
    static lanes off the segmented timeline and results stay exact."""
    mt, trace = hand_world
    m = demand_mapping(1 << 10, seed=9)
    mv = np.flatnonzero(m.ppn >= 0)
    st_trace = mv[np.random.default_rng(2).integers(0, mv.size, 400)]
    cells = [SweepCell(base_spec(), m, st_trace),
             SweepCell(kaligned_spec([6, 4]), m, st_trace),
             SweepCell(dataclasses.replace(base_spec(), ctx_policy="tag"),
                       mt, trace)]
    sweep = run_sweep(cells, cache=False)
    assert sweep.stats["n_batches"] == 2
    for idx in (0, 1):
        want = run_method(cells[idx].spec, m, st_trace)
        for f in COUNTERS[:-1]:
            assert getattr(sweep.results[idx], f) == getattr(want, f), f
    want = run_method_multitenant(cells[2].spec, mt, trace)
    _assert_equal(sweep.results[2], want, "mt lane in mixed batch")


# ---------------------------------------------------------------------------
# Scenario plumbing
# ---------------------------------------------------------------------------

MT_SCENARIOS = ("mt-serve-mix", "mt-churn", "mt-flush-vs-tag")


def test_mt_scenarios_registered():
    names = {sc.name for sc in list_scenarios("multitenant")}
    assert set(MT_SCENARIOS) <= names


@pytest.mark.parametrize("name", MT_SCENARIOS)
def test_mt_scenario_valid_per_segment(name):
    """Every trace entry is mapped in the tenant scheduled at that step;
    the schedule actually switches; mt-churn actually recycles ASIDs."""
    d = get_scenario(name).materialize(n_pages=1 << 12, trace_len=2000,
                                       trace_seed=8)
    mt = d.multitenant
    assert mt is not None and d.world is mt
    assert mt.n_switches() > 0, "no context switch: world is single-tenant"
    bounds = list(mt.boundaries) + [len(d.trace)]
    for s in range(mt.n_segments):
        m = mt.tenants[mt.tenant_ids[s]]
        seg = d.trace[bounds[s]: bounds[s + 1]]
        assert (seg < m.n_pages).all() and (m.ppn[seg] >= 0).all(), \
            f"segment {s} accesses pages unmapped in its tenant"
    if name == "mt-churn":
        assert sum(mt.recycled) > 0, "mt-churn never recycled an ASID"
        assert d.meta["sched_events"].get("admit", 0) > 0


@pytest.mark.parametrize("name", MT_SCENARIOS)
def test_mt_scenario_deterministic(name):
    a = get_scenario(name).materialize(n_pages=1 << 12, trace_len=1500,
                                       map_seed=5)
    clear_materialized_cache()
    b = get_scenario(name).materialize(n_pages=1 << 12, trace_len=1500,
                                       map_seed=5)
    np.testing.assert_array_equal(a.trace, b.trace)
    assert a.multitenant.boundaries == b.multitenant.boundaries
    assert a.multitenant.asids == b.multitenant.asids
    for ma, mb in zip(a.multitenant.tenants, b.multitenant.tenants):
        np.testing.assert_array_equal(ma.ppn, mb.ppn)


def test_mt_scenario_parity_fast():
    """Scenario-world parity, fast tier: one scenario, a subset of kinds,
    both policies, xla backend."""
    d = get_scenario("mt-flush-vs-tag").materialize(
        n_pages=1 << 12, trace_len=900, trace_seed=8)
    mt, trace = d.multitenant, np.asarray(d.trace)
    kinds = [base_spec(), colt_spec(), kaligned_spec([6, 4])]
    specs = _with_policy(kinds, "flush") + _with_policy(kinds, "tag")
    sweep = run_sweep([SweepCell(s, mt, trace) for s in specs], cache=False)
    for s, got in zip(specs, sweep.results):
        want = run_method_multitenant(s, mt, trace)
        _assert_equal(got, want, (s.name, s.ctx_policy, "scenario-fast"))
        _assert_isolated(mt, trace, got, s.name)


@pytest.mark.slow
@pytest.mark.parametrize("name", MT_SCENARIOS)
def test_mt_scenario_parity_full(name):
    """Scenario-world parity, slow lane: every scenario, all 8 kinds,
    both policies, both backends."""
    d = get_scenario(name).materialize(n_pages=1 << 12, trace_len=2000,
                                       trace_seed=8)
    mt, trace = d.multitenant, np.asarray(d.trace)
    specs = _with_policy(ALL_KINDS, "flush") + _with_policy(ALL_KINDS, "tag")
    cells = [SweepCell(s, mt, trace) for s in specs]
    oracle = [run_method_multitenant(s, mt, trace) for s in specs]
    for backend in ("xla", "pallas"):
        sweep = run_sweep(cells, cache=False, backend=backend)
        for s, got, want in zip(specs, sweep.results, oracle):
            _assert_equal(got, want, (name, s.name, s.ctx_policy, backend))
            _assert_isolated(mt, trace, got, (name, s.name, backend))
