"""Backend parity matrix for the time-blocked sweep engine.

The per-lane program (:mod:`repro.core.lane_program`) has two execution
backends — the time-blocked XLA scan and the Pallas TLB-sweep kernel — and
one tunable execution detail, the block size.  None of them may change a
single counter: every combination of

    backend ∈ {xla (TB = 1, 3, 8), pallas (interpret)}
  × method kind ∈ all 11 (base/thp/colt/cluster/rmm/anchor/kaligned ±pred
                          + subregion/cache-tlb/dead-protect)
  × world ∈ {static demand mapping, dynamic remap world}

must be bit-exact — including shootdown counters and every translated
PPN — against the pure-python oracles ``run_method`` /
``run_method_dynamic``.  A hypothesis property test additionally drives
random block sizes (block boundaries are an execution detail), and the
trace-bucket tests pin that trace padding never leaks into results or
cache keys.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import demand_mapping, generate_trace
from repro.core.baselines import (anchor_spec, base_spec, cache_tlb_spec,
                                  cluster_spec, colt_spec, dead_protect_spec,
                                  kaligned_spec, rmm_spec, subregion_spec,
                                  thp_spec)
from repro.core.lane_program import TRACE_FLOOR, bucket_trace_len
from repro.core.page_table import MappingEvent, build_dynamic_mapping
from repro.core.simulator import run_method, run_method_dynamic
from repro.core.sweep import SweepCell, cell_key, run_sweep

COUNTERS = ("accesses", "l1_hits", "l2_regular_hits", "l2_coalesced_hits",
            "walks", "aligned_probes", "pred_correct", "cycles",
            "coverage_mean", "shootdowns")

ALL_KINDS = [base_spec(), thp_spec(), colt_spec(), cluster_spec(), rmm_spec(),
             anchor_spec(6), kaligned_spec([9, 6, 4]),
             kaligned_spec([6, 4], use_predictor=False, name="ka-nopred"),
             subregion_spec(), cache_tlb_spec(), dead_protect_spec()]


def _assert_equal(got, want, ctx):
    for f in COUNTERS:
        assert getattr(got, f) == getattr(want, f), (ctx, f)
    np.testing.assert_array_equal(got.ppn, want.ppn, err_msg=str(ctx))


@pytest.fixture(scope="module")
def worlds():
    """One static and one dynamic world, both small enough for the python
    oracles and the interpret-mode kernel."""
    m = demand_mapping(1 << 10, seed=11)
    tr = generate_trace("multiscale", 0, 400, seed=4, mapping=m)
    n = 1 << 10
    ppn0 = np.arange(n, dtype=np.int64) + 7          # contiguous: huge runs
    ev1 = [MappingEvent("remap", 0, 128, ppn=100_000)]
    ev2 = [MappingEvent("split", 128, 64,
                        ppn=np.arange(200_000, 200_000 + 64 * 3, 3)),
           MappingEvent("unmap", 768, 32)]
    dyn = build_dynamic_mapping(ppn0, [(150, ev1), (370, ev2)], name="hot")
    rng = np.random.default_rng(3)
    dtr = rng.integers(0, 512, size=520).astype(np.int64)
    return m, tr, dyn, dtr


@pytest.fixture(scope="module")
def cells(worlds):
    """Mixed batch: 8 static + 8 dynamic lanes (run_sweep partitions them
    into a static-only and a dynamic batch internally)."""
    m, tr, dyn, dtr = worlds
    return [SweepCell(s, m, tr) for s in ALL_KINDS] + \
           [SweepCell(s, dyn, dtr) for s in ALL_KINDS]


@pytest.fixture(scope="module")
def oracles(worlds):
    m, tr, dyn, dtr = worlds
    return ([run_method(s, m, tr) for s in ALL_KINDS],
            [run_method_dynamic(s, dyn, dtr) for s in ALL_KINDS])


@pytest.mark.parametrize("tb", [1, 3, 8])
@pytest.mark.parametrize("form", ["platform", "one_hot"])
def test_xla_blocked_parity(request, cells, oracles, tb, form):
    """The time-blocked XLA backend is bit-exact vs the pure-python oracles
    for several block sizes, including the degenerate TB=1 (whose timeline
    equals the step-at-a-time engine), in the platform's state-access form
    and in the one-hot form a TPU lowering picks."""
    if form == "one_hot":
        request.getfixturevalue("one_hot_state_access")
    static_want, dyn_want = oracles
    sweep = run_sweep(cells, cache=False, backend="xla", block_size=tb)
    assert sweep.stats["backend"] == "xla"
    assert sweep.stats["block"] == tb
    assert sweep.stats["n_batches"] == 2          # static-only + dynamic
    for i, spec in enumerate(ALL_KINDS):
        _assert_equal(sweep.results[i], static_want[i],
                      (spec.name, "static", tb))
        _assert_equal(sweep.results[len(ALL_KINDS) + i], dyn_want[i],
                      (spec.name, "dynamic", tb))


def test_pallas_parity(cells, oracles):
    """The Pallas TLB-sweep kernel (interpret mode on CPU) is bit-exact vs
    the same oracles — all 8 method kinds, static AND dynamic worlds,
    including the in-kernel shootdown pass."""
    static_want, dyn_want = oracles
    sweep = run_sweep(cells, cache=False, backend="pallas", block_size=4)
    assert sweep.stats["backend"] == "pallas"
    for i, spec in enumerate(ALL_KINDS):
        _assert_equal(sweep.results[i], static_want[i],
                      (spec.name, "static", "pallas"))
        _assert_equal(sweep.results[len(ALL_KINDS) + i], dyn_want[i],
                      (spec.name, "dynamic", "pallas"))


def test_backend_name_validated():
    with pytest.raises(ValueError):
        run_sweep([], backend="cuda")


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_auto_backend_is_xla(monkeypatch, platform):
    """``auto`` never picks the Pallas kernel: no chip compiles it."""
    from repro.core import sweep as sweep_mod
    monkeypatch.setattr(sweep_mod.jax, "default_backend", lambda: platform)
    assert sweep_mod.resolve_backend("auto") == "xla"
    assert sweep_mod.resolve_backend(None) == "xla"


def test_pallas_refused_on_tpu_before_any_batch(monkeypatch, cells):
    """On a TPU ``backend='pallas'`` raises the compiler's refusal before
    a batch runs, so the recovery ladder cannot turn it into XLA runs
    reported as Pallas."""
    from repro.core import sweep as sweep_mod
    batches = []
    monkeypatch.setattr(sweep_mod, "_BACKEND_FAULT_HOOK",
                        lambda sub, backend: batches.append(backend))
    monkeypatch.setattr(sweep_mod.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="refuses the tlb_sweep"):
        run_sweep(cells, cache=False, backend="pallas")
    assert batches == []


def test_ref_backend_parity(worlds, oracles):
    """The step-at-a-time pure-JAX reference
    (``kernels/tlb_sweep/ref.py``) — the third leg of the parity matrix,
    with no time blocking at all — matches the oracles too."""
    from repro.core.lane_program import (C_COAL, C_COV, C_CYC, C_L1, C_PRED,
                                         C_PROBE, C_REG, C_SHOOT, C_WALK,
                                         init_batched_state, pack_lanes)
    from repro.kernels.tlb_sweep.ref import run_lanes_ref
    m, tr, dyn, dtr = worlds
    static_want, dyn_want = oracles
    fields = {C_L1: "l1_hits", C_REG: "l2_regular_hits",
              C_COAL: "l2_coalesced_hits", C_WALK: "walks",
              C_PROBE: "aligned_probes", C_PRED: "pred_correct",
              C_CYC: "cycles", C_SHOOT: "shootdowns"}
    assert C_COV not in fields          # sampled, compared via the mean
    for world, trace, wants in ((m, tr, static_want), (dyn, dtr, dyn_want)):
        cells = [SweepCell(s, world, trace) for s in ALL_KINDS]
        lanes, stacks, (L, sets, ways), seg_bounds = pack_lanes(cells)
        st0 = init_batched_state(
            L, sets, ways, lanes["pred0"],
            with_ctlb=bool(np.asarray(lanes["has_ctlb"]).any()),
            with_dp=bool(np.asarray(lanes["use_dead"]).any()))
        stF, ppns = run_lanes_ref(lanes, stacks, st0, seg_bounds)
        counters = np.asarray(stF["counters"])
        cov = np.asarray(stF["cov_samples"])
        for i, (spec, want) in enumerate(zip(ALL_KINDS, wants)):
            for c, f in fields.items():
                assert counters[i, c] == getattr(want, f), (spec.name, f)
            assert float(np.mean(cov[i])) == want.coverage_mean, spec.name
            np.testing.assert_array_equal(
                np.asarray(ppns)[i, : trace.shape[0]], want.ppn,
                err_msg=spec.name)


# ---------------------------------------------------------------------------
# State access: the one-hot form equals the point form
# ---------------------------------------------------------------------------


def _lane_planes():
    """One lane's state planes, every kind's structures at full geometry."""
    from repro.core.lane_program import init_batched_state
    st = init_batched_state(1, 128, 8, np.zeros(1, np.int32),
                            with_ctlb=True, with_dp=True)
    return {k: v[0] for k, v in st.items() if v.ndim > 1}


@pytest.mark.parametrize("plane", sorted(_lane_planes()))
def test_one_hot_access_equals_point_access(plane):
    """Each one-hot primitive returns what its point form returns, for a
    row read at every row and a write at every index depth, with the
    write's predicate true and false."""
    import jax.numpy as jnp
    from repro.core.lane_program import ONE_HOT_ACCESS, POINT_ACCESS
    shape = _lane_planes()[plane].shape
    r = np.random.default_rng(len(shape) * 1000 + shape[0])
    arr = jnp.asarray(r.integers(-2**30, 2**30, shape, dtype=np.int32))
    for i in range(shape[0]):
        np.testing.assert_array_equal(ONE_HOT_ACCESS.read(arr, i),
                                      POINT_ACCESS.read(arr, i))
    for depth in range(1, len(shape) + 1):
        for _ in range(4):
            idx = tuple(jnp.int32(r.integers(0, n)) for n in shape[:depth])
            value = jnp.asarray(r.integers(-2**30, 2**30, shape[depth:],
                                           dtype=np.int32))
            for pred in (True, False):
                key = idx[0] if depth == 1 else idx
                want = POINT_ACCESS.write(arr, key, value, jnp.bool_(pred))
                got = ONE_HOT_ACCESS.write(arr, key, value, jnp.bool_(pred))
                np.testing.assert_array_equal(got, want,
                                              err_msg=str((depth, pred)))


@pytest.mark.parametrize("form", ["platform", "one_hot"])
def test_cpu_lowering_picks_the_point_form(request, form):
    """Compiled for the CPU, the sweep's vmapped step keeps the point
    form's scatters; the one-hot form has none."""
    import jax
    from repro.core import sweep as sweep_mod
    from repro.core.lane_program import (STEP_KEYS, init_batched_state,
                                         pack_lanes)
    if form == "one_hot":
        request.getfixturevalue("one_hot_state_access")
    m = demand_mapping(1 << 9, seed=2)
    tr = generate_trace("zipf", 0, 100, seed=7, mapping=m)
    lanes, stacks, (L, sets, ways), _ = pack_lanes(
        [SweepCell(base_spec(), m, tr)])
    st0 = init_batched_state(L, sets, ways, lanes["pred0"])
    x = (np.zeros(L, np.int32), stacks["maps"][0, :L],
         stacks["fills"][0, :L], np.zeros(L, np.int32), np.ones(L, bool))
    text = jax.jit(jax.vmap(sweep_mod._lane_step)).lower(
        {k: lanes[k] for k in STEP_KEYS}, st0, *x).compile().as_text()
    assert jax.default_backend() == "cpu"
    assert ("scatter(" in text) == (form == "platform")


# ---------------------------------------------------------------------------
# Property: block boundaries are an execution detail
# ---------------------------------------------------------------------------


@given(st.integers(1, 50))
@settings(max_examples=4, deadline=None)
def test_block_boundaries_never_change_results(tb):
    """For ANY block size — aligned or not with the trace length or the
    epoch boundaries — the sweep returns the same counters and PPNs."""
    m = demand_mapping(1 << 9, seed=2)
    tr = generate_trace("zipf", 0, 333, seed=7, mapping=m)
    specs = [base_spec(), colt_spec(), kaligned_spec([6, 4])]
    sweep = run_sweep([SweepCell(s, m, tr) for s in specs],
                      cache=False, backend="xla", block_size=tb)
    for s, got in zip(specs, sweep.results):
        _assert_equal(got, run_method(s, m, tr), (s.name, tb))


# ---------------------------------------------------------------------------
# Trace buckets: padded length is invisible to results and cache keys
# ---------------------------------------------------------------------------


def test_trace_bucket_pow2_with_floor():
    assert bucket_trace_len(1) == TRACE_FLOOR
    assert bucket_trace_len(TRACE_FLOOR) == TRACE_FLOOR
    assert bucket_trace_len(TRACE_FLOOR + 1) == 2 * TRACE_FLOOR
    assert bucket_trace_len(4096) == 4096
    assert bucket_trace_len(5000) == 8192
    # long paper traces use linear 16k buckets, not pow2 (padding stays
    # under ~13%, where pow2 could double the scan)
    assert bucket_trace_len(150_000) == 163_840
    assert bucket_trace_len(1 << 17) == 1 << 17


def test_padded_length_changes_nothing(worlds):
    """The same cell simulated under different padded trace lengths (alone:
    the 256 floor bucket; next to a much longer trace: a 2048 bucket) keeps
    its cell_key AND produces identical results."""
    m, tr, _, _ = worlds
    spec = kaligned_spec([8, 6, 4])
    cell_alone = SweepCell(spec, m, tr)
    long_tr = generate_trace("zipf", 0, 1800, seed=9, mapping=m)
    alone = run_sweep([cell_alone], cache=False, backend="xla")
    cell_again = SweepCell(spec, m, tr)
    padded = run_sweep([cell_again, SweepCell(base_spec(), m, long_tr)],
                       cache=False, backend="xla")
    assert cell_key(cell_alone) == cell_key(cell_again)
    got, want = padded.results[0], alone.results[0]
    for f in COUNTERS:
        assert getattr(got, f) == getattr(want, f), f
    np.testing.assert_array_equal(got.ppn, want.ppn)
