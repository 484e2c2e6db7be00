"""The shared per-lane TLB program: one definition, two backends.

The batched sweep engine runs every ``(method, mapping, trace)`` cell as a
*lane* of one compiled program.  This module is the single source of truth
for what a lane **is**, consumed by both execution backends:

* the XLA backend (:mod:`repro.core.sweep`) — a time-blocked
  ``jax.lax.scan`` whose body advances every lane by ``TB`` trace steps;
* the Pallas backend (:mod:`repro.kernels.tlb_sweep`) — a kernel whose grid
  maps lanes to program instances and keeps all TLB state in scratch for
  the whole trace.

Three layers live here:

1. **Packing** (:func:`pack_lanes`, :func:`init_batched_state`): dedup
   worlds/traces, precompute the per-``(world, epoch)`` map/fill/cluster
   records, pad every method onto one array layout, and bucket shapes
   (power-of-two trace lengths with a small floor, lane counts padded to a
   shared bucket and to a device multiple) so distinct sweeps reuse
   compiled executables.
2. **The step** (:func:`step_access`): one translation of one lane — the
   union of every method kind's datapath (L1, dual-probe THP, COLT window
   cover, the K-aligned probe chain with predictor, RMM ranges, clustered
   side-TLB, Algorithm-1 fills, LRU, latency and counters), selected per
   lane by data.  Its state access has a one-hot form besides the point
   form, and the XLA backend lowers that one for a TPU, because under
   ``vmap`` point access becomes gathers and scatters whose TPU layouts
   pad and copy whole state planes every step.  :func:`shoot_lane` is the
   epoch-turnover translation coherence pass.  Both operate on a plain
   dict of arrays for ONE lane; backends decide where that state lives
   (scan carry vs kernel scratch).
3. **The block plan** (:func:`build_block_plan`): the static timeline both
   backends execute — every epoch segment padded to a multiple of the block
   size, one shootdown flag per segment-entry block.  Block boundaries are
   an execution detail: results are bit-exact for every block size
   (enforced by ``tests/test_backends.py``).

Bit-exactness contract: for any packing, any block size and either backend,
every lane must match :func:`repro.core.simulator.run_method` /
:func:`~repro.core.simulator.run_method_dynamic` counter-for-counter.
"""
from __future__ import annotations

import dataclasses
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from .page_table import (DynamicMapping, Mapping, MultiTenantMapping,
                         NestedMapping, ParityWorld, cluster_bitmap,
                         huge_page_backed, next_pow2 as _next_pow2)
from .plane_layout import (FILL_REC_WIDTH, MAP_REC_WIDTH, PLANE_FIELDS,
                           PLANE_WIDTH)
from ..spans import span
from .simulator import (CLUS_SETS, CLUS_WAYS, CTLB_SETS, CTLB_WAYS, DP_TABLE,
                        HUGE, INVALID, KSUBR, L1_SETS, L1_WAYS,
                        L1H_SETS, L1H_WAYS, LAT_COAL, LAT_CTLB,
                        LAT_CTX_SWITCH, LAT_EXTRA_PROBE, LAT_INVALIDATE,
                        LAT_L2_REG, LAT_SHOOTDOWN, LAT_WALK, N_COV_SAMPLES,
                        NEG, REGULAR, RMM_ENTRIES, SUBR_PAGES, MethodSpec,
                        miss_chain_cycles)

BIG = 2**30  # victim score for padded ways: never evictable

# Shape buckets: pad so repeated sweeps of similar size reuse the same
# compiled executable instead of specializing on exact lane/trace/page
# counts.  Traces are padded to the next power of two with a small floor
# (a ~200-step smoke trace costs a 256-step scan, not a 4096-step one);
# lane counts are padded to the next power of two up to LANE_SHARE_MAX and
# to multiples of LANE_BUCKET beyond it, then to a device multiple so the
# pmap path always shards.  K slots are padded to a fixed minimum so
# sweeps with |K| = 1..KMIN_SLOTS share one executable (inert ``-1``
# classes probe inertly).
TRACE_FLOOR = 256
LANE_FLOOR = 32
LANE_BUCKET = 32
LANE_SHARE_MAX = 64
KMIN_SLOTS = 4
# fill-record counts vary the most across suites (one record per distinct
# (world, epoch, fill profile)); a higher floor folds the common bench
# sizes onto {32, 64}
FILL_REC_FLOOR = 32

# packed-field indices, derived from the one layout table
# (:mod:`repro.core.plane_layout`).  Every structure carries the ASID its
# entry was filled under as its last non-sidecar field: probes require an
# ASID match (trivially true on single-address-space worlds, where
# everything is ASID 0), and the context-switch pass
# (:func:`switch_lane`) clears by it.  L2 AUX holds per-kind sidecar
# data: the subregion contiguity bitmap (bit j = page tag+j shares the
# entry's VA->PA delta); 0 for other kinds.
TAG, KCLS, CONTIG, PPN, LRU, L2_ASID, AUX = range(PLANE_WIDTH["l2"])
assert PLANE_FIELDS["l2"] == ("tag", "kcls", "contig", "ppn", "lru",
                              "asid", "aux")
# dirty record: [P+1] = prefix sum of the epoch's dirty-vpn bitmap
# counters: [9] = l1_hits, reg_hits, coal_hits, walks, probes, pred_correct,
#                 cycles, cov, shootdowns
N_COUNTERS = 9
(C_L1, C_REG, C_COAL, C_WALK, C_PROBE, C_PRED, C_CYC, C_COV,
 C_SHOOT) = range(9)

# The per-lane scalars consumed by step_access/shoot_lane (plus the
# ``kvals`` vector).  Both backends build their lane dicts from this ONE
# tuple — sweep.py slices the packed lanes with it, the Pallas ops pack
# their params row from it — so adding a lane parameter is a one-list
# change.
STEP_KEYS = ("kvals", "use_pred", "is_colt", "is_thp", "has_rmm",
             "has_cluster", "set_mask", "n_ways", "k_hat", "miss_chain",
             "sample_every", "is_subr", "has_ctlb", "use_dead", "coh_hw")


TRACE_LINEAR_BUCKET = 1 << 14


def bucket_trace_len(n: int) -> int:
    """Trace-length bucket: power of two with a small floor up to 16k (a
    ~200-step smoke trace pays a 256-step scan, not a 4096-step one), then
    multiples of 16k — pow2 padding would cost up to +100% inert steps on
    the 120–150k-access paper traces, where run time dominates."""
    if n <= TRACE_LINEAR_BUCKET:
        return max(TRACE_FLOOR, _next_pow2(n))
    return -(-n // TRACE_LINEAR_BUCKET) * TRACE_LINEAR_BUCKET


def bucket_lane_count(n: int, device_count: int = 1) -> int:
    """Lane-count bucket, always a multiple of the device count (so the
    pmap path shards every batch).  Bench-sized batches (>= 8 cells) pad to
    {LANE_FLOOR, LANE_SHARE_MAX} power-of-two buckets so the common suite
    sizes share one compiled executable; beyond LANE_SHARE_MAX they are
    chunked by run_sweep, and the remainder chunks land back in these
    buckets.  Tiny batches (a user comparing a handful of specs) stay
    near-exact — inert pad lanes are cheap per step but not free over a
    100k-step trace."""
    if n >= 8:
        L = max(_next_pow2(n), LANE_FLOOR) if n <= LANE_SHARE_MAX \
            else -(-n // LANE_BUCKET) * LANE_BUCKET
    else:
        L = max(_next_pow2(n), 4)
    if device_count > 1:
        L = -(-L // device_count) * device_count
    return L


# Record-count padding budget: stacks are padded to power-of-two record
# counts (with a floor) so sweeps of similar shape share one compiled
# executable — the big cold-time lever for smoke/CI tiers — but never at
# more than this many padded bytes per stack, so paper-scale footprints
# (where run time dominates anyway) degrade gracefully to exact counts.
REC_FLOOR = 8
REC_PAD_BUDGET = 64 << 20


def _pad_stack(recs: List[np.ndarray], floor: int = REC_FLOOR,
               budget: int = REC_PAD_BUDGET) -> np.ndarray:
    """Stack ``recs`` padded with zero records to a shared count bucket."""
    n = len(recs)
    b = max(floor, _next_pow2(n))
    rec_bytes = recs[0].nbytes
    while b > n and b * rec_bytes > budget:
        b //= 2
    b = max(b, n)
    pad = [np.zeros_like(recs[0])] * (b - n)
    return np.stack(recs + pad)


# ---------------------------------------------------------------------------
# Precomputed per-vpn records (fill policy is trace-independent)
# ---------------------------------------------------------------------------


def _map_record(m: Mapping, P: int) -> np.ndarray:
    """[P, 4] int32: ppn, run_start, run_len, ppn[run_start] (RMM fill)."""
    n = m.n_pages
    rec = np.zeros((P, MAP_REC_WIDTH), np.int32)
    rec[:, 0] = -1
    rec[:n, 0] = m.ppn
    rec[:n, 1] = m.run_start
    rec[:n, 2] = m.run_len
    rec[:n, 3] = m.ppn[np.clip(m.run_start, 0, n - 1)]
    return rec


def _fill_profile_key(spec: MethodSpec):
    if spec.kind in ("kaligned", "anchor"):
        return ("ka", spec.K)
    if spec.kind in ("colt", "thp"):
        return (spec.kind,)
    if spec.kind == "subregion":
        return ("subr",)
    return ("reg",)


def _fill_profile(m: Mapping, key, P: int) -> np.ndarray:
    """[P, 5] int32 fill record (tag, k, contig, ppn, aux): what
    Algorithm 1 / COLT / THP / the subregion policy / the regular policy
    would install on a walk at each vpn."""
    n = m.n_pages
    vpn = np.arange(n, dtype=np.int64)
    ppn = m.ppn
    rs, rl = m.run_start, m.run_len

    def contig_at(v):
        v = np.clip(v, 0, n - 1)
        return np.where(ppn[v] >= 0, rs[v] + rl[v] - v, 0)

    tag = vpn.copy()
    kcls = np.full(n, REGULAR, np.int64)
    contig = np.ones(n, np.int64)
    fppn = ppn.copy()
    aux = np.zeros(n, np.int64)
    if key[0] == "ka":
        chosen = np.zeros(n, bool)
        for k in key[1]:                    # descending; first cover wins
            vk = vpn & ~((1 << k) - 1)
            sc = np.minimum(contig_at(vk), 1 << k)
            take = (sc > (vpn - vk)) & ~chosen
            tag = np.where(take, vk, tag)
            kcls = np.where(take, k, kcls)
            contig = np.where(take, sc, contig)
            fppn = np.where(take, ppn[np.clip(vk, 0, n - 1)], fppn)
            chosen |= take
    elif key[0] == "colt":
        w8 = vpn & ~np.int64(7)
        re = rs + rl
        tag = np.maximum(rs, w8)
        contig = np.maximum(np.minimum(re, w8 + 8) - tag, 1)
        kcls = np.where(contig > 1, 3, REGULAR)
        fppn = ppn[np.clip(tag, 0, n - 1)]
    elif key[0] == "thp":
        huge = huge_page_backed(m)
        hv = vpn >> 9
        tag = np.where(huge, hv, vpn)
        kcls = np.where(huge, HUGE, REGULAR)
        contig = np.where(huge, 512, 1)
        fppn = ppn[np.clip(np.where(huge, hv << 9, vpn), 0, n - 1)]
    elif key[0] == "subr":
        # subregion entries: one entry covers the aligned SUBR_PAGES
        # window around vpn; bit j of the bitmap says page base+j shares
        # this vpn's VA->PA delta (so base_ppn + j translates it).
        base = vpn & ~np.int64(SUBR_PAGES - 1)
        delta = ppn - vpn
        bitmap = np.zeros(n, np.int64)
        for j in range(SUBR_PAGES):
            pj = np.clip(base + j, 0, n - 1)
            ok = (base + j < n) & (ppn[pj] >= 0) & (ppn[pj] - pj == delta)
            bitmap |= ok.astype(np.int64) << j
        mapped = ppn >= 0
        popc = sum((bitmap >> j) & 1 for j in range(SUBR_PAGES))
        tag = np.where(mapped, base, tag)
        kcls = np.where(mapped, KSUBR, kcls)
        contig = np.where(mapped, popc, contig)
        fppn = np.where(mapped, ppn - (vpn - base), fppn)
        aux = np.where(mapped, bitmap, 0)

    rec = np.zeros((P, FILL_REC_WIDTH), np.int32)
    rec[:n, 0] = tag
    rec[:n, 1] = kcls
    rec[:n, 2] = contig
    rec[:n, 3] = fppn
    rec[:n, 4] = aux
    rec[n:, 1] = REGULAR
    return rec


# ---------------------------------------------------------------------------
# Lane packing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _WorldPlan:
    """One world decomposed into its schedule-segment sequence.

    ``sources`` are the distinct Mappings records are built from (epoch
    snapshots of a dynamic world; tenant address spaces of a multi-tenant
    one; deduped composed guest-over-host views of a nested one; the
    single mapping of a static one).  Per schedule segment ``i``:
    ``src_idx[i]`` is the live source, ``asids[i]`` the live ASID,
    ``switch[i]`` whether entering it changes the address space,
    ``recycled[i]`` whether its ASID was last held by a different tenant,
    and ``dirty[i]`` the vpn dirty bitmap the coherence pass must sweep on
    entering it (``None`` when nothing turned stale — dynamic worlds dirty
    by guest vpn, nested worlds by composed diff so host-level remaps
    surface too).  ``parity[i]`` marks segments spliced in by a
    :class:`~repro.core.page_table.ParityWorld` fault: their dirty set is
    a soft error, not a remap, so lanes whose spec runs ``par_policy=
    "ecc"`` (in-place correction) skip the invalidation pass for exactly
    those segments while remap coherence stays untouched.
    """

    sources: Tuple[Mapping, ...]
    bounds: Tuple[int, ...]
    src_idx: Tuple[int, ...]
    asids: Tuple[int, ...]
    switch: Tuple[bool, ...]
    recycled: Tuple[bool, ...]
    dirty: Tuple[Optional[np.ndarray], ...]
    parity: Tuple[bool, ...]


def _world_plan(world) -> _WorldPlan:
    if isinstance(world, ParityWorld):
        p = _world_plan(world.base)
        bounds = list(p.bounds)
        src_idx = list(p.src_idx)
        asids = list(p.asids)
        switch = list(p.switch)
        recycled = list(p.recycled)
        dirty = list(p.dirty)
        parity = [False] * len(bounds)
        for t, vpn in world.faults:
            # the segment live at fault time; collisions with base bounds
            # are excluded by the ParityWorld constructor
            i = int(np.searchsorted(np.asarray(bounds), t,
                                    side="right") - 1)
            d = np.zeros(p.sources[src_idx[i]].n_pages, bool)
            d[vpn] = True
            bounds.insert(i + 1, t)
            src_idx.insert(i + 1, src_idx[i])
            asids.insert(i + 1, asids[i])
            switch.insert(i + 1, False)
            recycled.insert(i + 1, False)
            dirty.insert(i + 1, d)
            parity.insert(i + 1, True)
        return _WorldPlan(p.sources, tuple(bounds), tuple(src_idx),
                          tuple(asids), tuple(switch), tuple(recycled),
                          tuple(dirty), tuple(parity))
    if isinstance(world, DynamicMapping):
        n = world.n_epochs
        dirty = (None,) + tuple(
            world.dirty(e) if world.dirty_count(e) else None
            for e in range(1, n))
        return _WorldPlan(world.epochs, world.boundaries, tuple(range(n)),
                          (0,) * n, (False,) * n, (False,) * n, dirty,
                          (False,) * n)
    if isinstance(world, MultiTenantMapping):
        n = world.n_segments
        return _WorldPlan(world.tenants, world.boundaries, world.tenant_ids,
                          world.asids,
                          tuple(world.switches(s) for s in range(n)),
                          world.recycled, (None,) * n, (False,) * n)
    if isinstance(world, NestedMapping):
        segs = world.plan_segments()
        sources: List[Mapping] = []
        src_of: Dict[int, int] = {}
        src_idx: List[int] = []
        for ns in segs:
            if id(ns.mapping) not in src_of:      # composed views memoized
                src_of[id(ns.mapping)] = len(sources)
                sources.append(ns.mapping)
            src_idx.append(src_of[id(ns.mapping)])
        n = len(segs)
        return _WorldPlan(tuple(sources), tuple(ns.lo for ns in segs),
                          tuple(src_idx), tuple(ns.asid for ns in segs),
                          tuple(ns.switch for ns in segs),
                          tuple(ns.recycled for ns in segs),
                          tuple(ns.dirty for ns in segs), (False,) * n)
    return _WorldPlan((world,), (0,), (0,), (0,), (False,), (False,),
                      (None,), (False,))


def pack_lanes(cells: Sequence["SweepCellLike"], device_count: int = 1):
    """Dedup worlds/traces/fill-profiles; pack per-lane params to arrays.

    Every world is a schedule-segment *sequence* (a static ``Mapping`` is
    one segment; a :class:`~repro.core.page_table.DynamicMapping` one per
    epoch; a :class:`~repro.core.page_table.MultiTenantMapping` one per
    scheduling quantum); map/fill/cluster records are built per ``(world,
    source mapping)`` and lanes carry a per-segment record index, so
    static, dynamic and multi-tenant lanes share one compiled program (a
    tenant scheduled many times reuses ONE record set).  The segment
    grid — the sorted union of every lane's boundaries — is returned as a
    static tuple; a batch with no segmented lane collapses to one segment
    and never runs the shootdown/switch pass.  Returns ``(lanes, stacks,
    (L, max_sets, max_ways), seg_bounds)``.

    Spans ``repro.sweep.pack.maps``, ``.fills``, ``.clusters`` and
    ``.stacks`` (dirty records, the trace stack and the padded stacks)
    time its sections; the lane parameters are the rest.
    """
    worlds: List = []
    world_index: Dict[int, int] = {}
    traces: List[np.ndarray] = []
    trace_index: Dict[int, int] = {}
    for c in cells:
        if id(c.mapping) not in world_index:
            world_index[id(c.mapping)] = len(worlds)
            worlds.append(c.mapping)
        if id(c.trace) not in trace_index:
            trace_index[id(c.trace)] = len(traces)
            traces.append(c.trace)

    plans: Dict[int, _WorldPlan] = {w: _world_plan(m)
                                    for w, m in enumerate(worlds)}

    P = _next_pow2(max(m.n_pages for p in plans.values()
                       for m in p.sources))
    T = bucket_trace_len(max(t.shape[0] for t in traces))

    with span("repro.sweep.pack.maps"):
        # map records: one per (world, source mapping)
        map_recs: List[np.ndarray] = []
        map_rec_id: Dict[Tuple[int, int], int] = {}
        for w, p in plans.items():
            for e, m in enumerate(p.sources):
                map_rec_id[(w, e)] = len(map_recs)
                map_recs.append(_map_record(m, P))

    with span("repro.sweep.pack.fills"):
        # fill records: one per (world, source, fill profile)
        fill_recs: List[np.ndarray] = []
        fill_rec_id: Dict[Tuple[int, int, tuple], int] = {}
        for c in cells:
            w = world_index[id(c.mapping)]
            key = _fill_profile_key(c.spec)
            for e, m in enumerate(plans[w].sources):
                fk = (w, e, key)
                if fk not in fill_rec_id:
                    fill_rec_id[fk] = len(fill_recs)
                    fill_recs.append(_fill_profile(m, key, P))

    with span("repro.sweep.pack.clusters"):
        # cluster bitmaps: one per (world, source).  The stack is always P
        # wide (not 1) so suites with and without cluster lanes share an
        # executable; the budget guard below shrinks it back for
        # paper-scale footprints.
        need_clus = any(c.spec.side == "cluster" for c in cells)
        clus_wide = need_clus or P * 4 * REC_FLOOR <= REC_PAD_BUDGET
        clus_recs: List[np.ndarray] = [
            np.zeros(P if clus_wide else 1, np.int32)]
        clus_rec_id: Dict[Tuple[int, int], int] = {}
        if need_clus:
            for c in cells:
                if c.spec.side != "cluster":
                    continue
                w = world_index[id(c.mapping)]
                for e, m in enumerate(plans[w].sources):
                    if (w, e) not in clus_rec_id:
                        rec = np.zeros(P, np.int32)
                        rec[: m.n_pages] = cluster_bitmap(m)
                        clus_rec_id[(w, e)] = len(clus_recs)
                        clus_recs.append(rec)

    with span("repro.sweep.pack.stacks"):
        # dirty records (prefix sums): one per (world, segment) whose plan
        # carries a dirty bitmap (dynamic epochs e >= 1 with churn; nested
        # segments whose composed view diverged at either level)
        dirty_recs: List[np.ndarray] = [np.zeros(P + 1, np.int32)]
        dirty_rec_id: Dict[Tuple[int, int], int] = {}
        for w, p in plans.items():
            for e, d in enumerate(p.dirty):
                if d is None:
                    continue
                dc = np.zeros(P + 1, np.int32)
                nd = min(int(d.shape[0]), P)   # beyond P no entry can cover
                np.cumsum(d[:nd], out=dc[1: nd + 1])
                dc[nd + 1:] = dc[nd]
                dirty_rec_id[(w, e)] = len(dirty_recs)
                dirty_recs.append(dc)

        n_tr = len(traces)
        if n_tr * T * 4 * 2 <= REC_PAD_BUDGET:
            n_tr = max(REC_FLOOR, _next_pow2(n_tr))
        trace_stack = np.zeros((n_tr, T), np.int32)
        for i, t in enumerate(traces):
            trace_stack[i, : t.shape[0]] = t
        stacks = dict(maps=_pad_stack(map_recs),
                      fills=_pad_stack(fill_recs, floor=FILL_REC_FLOOR),
                      clus=_pad_stack(clus_recs),
                      dirty=_pad_stack(dirty_recs), trace=trace_stack)

    # segment grid: union of all schedule boundaries, static per compile
    grid = sorted({int(b) for w in range(len(worlds))
                   for b in plans[w].bounds[1:]})
    seg_bounds = tuple([0] + grid + [T])
    n_segs = len(seg_bounds) - 1

    L = bucket_lane_count(len(cells), device_count)
    max_sets = max(c.spec.l2_sets for c in cells)
    max_ways = max(c.spec.l2_ways for c in cells)
    maxk = max([len(c.spec.K) for c in cells] + [KMIN_SLOTS])

    lanes = dict(
        is_colt=np.zeros(L, bool), is_thp=np.zeros(L, bool),
        is_subr=np.zeros(L, bool), has_ctlb=np.zeros(L, bool),
        use_dead=np.zeros(L, bool), coh_hw=np.zeros(L, bool),
        has_rmm=np.zeros(L, bool),
        has_cluster=np.zeros(L, bool), use_pred=np.zeros(L, bool),
        kvals=np.full((L, maxk), -1, np.int32),
        set_mask=np.zeros(L, np.int32), n_ways=np.ones(L, np.int32),
        k_hat=np.zeros(L, np.int32), miss_chain=np.zeros(L, np.int32),
        pred0=np.zeros(L, np.int32), asid0=np.zeros(L, np.int32),
        seg_map=np.zeros((L, n_segs), np.int32),
        seg_fill=np.zeros((L, n_segs), np.int32),
        seg_clus=np.zeros((L, n_segs), np.int32),
        seg_shoot=np.zeros((L, n_segs), bool),
        seg_dirty=np.zeros((L, n_segs), np.int32),
        seg_asid=np.zeros((L, n_segs), np.int32),
        seg_switch=np.zeros((L, n_segs), bool),
        seg_fall=np.zeros((L, n_segs), bool),
        seg_fasid=np.zeros((L, n_segs), bool),
        trace_id=np.zeros(L, np.int32), t_real=np.zeros(L, np.int32),
        sample_every=np.ones(L, np.int32),
    )
    for i, c in enumerate(cells):
        s = c.spec
        w = world_index[id(c.mapping)]
        p = plans[w]
        key = _fill_profile_key(s)
        lanes["is_colt"][i] = s.kind == "colt"
        lanes["is_thp"][i] = s.kind == "thp"
        lanes["is_subr"][i] = s.kind == "subregion"
        lanes["has_ctlb"][i] = s.kind == "cache-tlb"
        lanes["use_dead"][i] = s.kind == "dead-protect"
        lanes["coh_hw"][i] = s.coh_policy == "hw-coherence"
        lanes["has_rmm"][i] = s.side == "rmm"
        lanes["has_cluster"][i] = s.side == "cluster"
        lanes["use_pred"][i] = s.use_predictor
        lanes["kvals"][i, : len(s.K)] = s.K
        lanes["set_mask"][i] = s.l2_sets - 1
        lanes["n_ways"][i] = s.l2_ways
        lanes["k_hat"][i] = s.index_shift
        lanes["miss_chain"][i] = miss_chain_cycles(s)
        lanes["pred0"][i] = s.K[0] if s.K else 0
        lanes["asid0"][i] = p.asids[0]
        lanes["trace_id"][i] = trace_index[id(c.trace)]
        lanes["t_real"][i] = c.trace.shape[0]
        lanes["sample_every"][i] = max(c.trace.shape[0] // N_COV_SAMPLES, 1)
        for seg in range(n_segs):
            lo = seg_bounds[seg]
            e = int(np.searchsorted(p.bounds, lo, side="right") - 1)
            src = p.src_idx[e]
            lanes["seg_map"][i, seg] = map_rec_id[(w, src)]
            lanes["seg_fill"][i, seg] = fill_rec_id[(w, src, key)]
            lanes["seg_clus"][i, seg] = clus_rec_id.get((w, src), 0)
            lanes["seg_asid"][i, seg] = p.asids[e]
            # `turned` = this grid segment starts at one of the LANE's own
            # boundaries (the union grid also cuts at other lanes')
            turned = seg > 0 and e >= 1 and lo == p.bounds[e]
            # a parity-fault dirty set is a soft error, not a remap: ecc
            # lanes correct it in place and skip the invalidation pass
            ecc_skip = p.parity[e] and s.par_policy == "ecc"
            if turned and (w, e) in dirty_rec_id and not ecc_skip:
                lanes["seg_shoot"][i, seg] = True
                lanes["seg_dirty"][i, seg] = dirty_rec_id[(w, e)]
            if turned:
                lanes["seg_switch"][i, seg] = p.switch[e]
                lanes["seg_fall"][i, seg] = (p.switch[e]
                                             and s.ctx_policy == "flush")
                lanes["seg_fasid"][i, seg] = (p.recycled[e]
                                              and s.ctx_policy == "tag")
    return lanes, stacks, (L, max_sets, max_ways), seg_bounds


def needs_switch_pass(lanes) -> bool:
    """True when some lane's schedule actually switches, flushes or
    relabels an ASID — knowable statically at pack time.  Backends compile
    the segment-entry switch pass only then, so static and dynamic-only
    batches (whose flags are all False by construction) pay nothing for
    the multi-tenant machinery."""
    return bool(np.asarray(lanes["seg_switch"]).any()
                or np.asarray(lanes["seg_fall"]).any()
                or np.asarray(lanes["seg_fasid"]).any()
                or (np.asarray(lanes["seg_asid"])
                    != np.asarray(lanes["asid0"])[:, None]).any())


def init_batched_state(L: int, max_sets: int, max_ways: int, pred0,
                       asid0=None, *, with_ctlb: bool = False,
                       with_dp: bool = False):
    """``with_ctlb``/``with_dp`` size the cache-backed tier and the
    dead-entry counter table: full geometry when some lane in the batch
    is ``cache-tlb``/``dead-protect``, degenerate ``(1, 1)``-style arrays
    otherwise (the step indexes them shape-generically and its lane flags
    gate every read/write, so absent kinds pay one inert element)."""
    def packed(shape, init_tag):
        a = np.zeros(shape, np.int32)
        a[..., 0] = init_tag
        return a

    l2 = np.zeros((L, max_sets, max_ways, PLANE_WIDTH["l2"]), np.int32)
    l2[..., TAG] = -1
    l2[..., KCLS] = INVALID
    l2[..., PPN] = -1
    cs, cw = (CTLB_SETS, CTLB_WAYS) if with_ctlb else (1, 1)
    return dict(
        t=np.zeros(L, np.int32),
        l1=packed((L, L1_SETS, L1_WAYS, PLANE_WIDTH["l1"]), -1),
        l1h=packed((L, L1H_SETS, L1H_WAYS, PLANE_WIDTH["l1h"]), -1),
        l2=l2,
        rmm=packed((L, RMM_ENTRIES, PLANE_WIDTH["rmm"]), -1),
        clus=packed((L, CLUS_SETS, CLUS_WAYS, PLANE_WIDTH["clus"]), -1),
        ctlb=packed((L, cs, cw, PLANE_WIDTH["ctlb"]), -1),
        dp=np.zeros((L, DP_TABLE if with_dp else 1), np.int32),
        pred=np.asarray(pred0, np.int32).copy(),
        asid=(np.zeros(L, np.int32) if asid0 is None
              else np.asarray(asid0, np.int32).copy()),
        counters=np.zeros((L, N_COUNTERS), np.int32),
        cov_samples=np.zeros((L, N_COV_SAMPLES), np.int32),
    )


# ---------------------------------------------------------------------------
# State access: how the step reads a row of a state plane and writes a cell
# ---------------------------------------------------------------------------
#
# Every read and write of a lane's state planes in :func:`step_access` goes
# through one pair of primitives, in one of two forms with identical
# results:
#
# * the point form indexes the plane (``plane[i]``, ``.at[idx].set``);
# * the one-hot form compares an iota with the index on each indexed axis
#   and reads by a masked sum, writes by a ``where``.  Under ``vmap`` the
#   point form becomes batched gathers and scatters, whose TPU layouts pad
#   the (ways, fields) minor pair to an 8x128 tile and copy whole planes
#   between layouts every step; the one-hot form is elementwise and keeps
#   the planes in one unpadded layout.
#
# The forms agree only for indices in range, since point indexing wraps
# negative indices and clamps large ones and a mask matches nothing.  Every
# index the step uses is in range by construction:
#
# * a set index is ``x & (n - 1)``, so it lies in ``[0, n)`` whatever the
#   sign of ``x``, with ``n`` at most the plane's row count (``s1``,
#   ``s1h``, ``sc``, ``sct``, ``sct_v`` and ``dp_idx`` take ``n`` from the
#   plane; ``s2``, ``s2h`` and so ``fill_set`` and ``touch_set`` take the
#   lane's ``l2_sets``, at most the batch's ``max_sets``);
# * a way or entry comes from an ``argmin``/``argmax`` over that plane's
#   axis;
# * a field is a constant below the plane's width;
# * the coverage slot is ``jnp.minimum(t // se, N_COV_SAMPLES - 1)`` with
#   ``t >= 0`` and ``se >= 1``.


def _point_read(plane, i):
    return plane[i]


def _point_write(arr, idx, value, pred):
    """Conditional point/row write (same trick as the oracle)."""
    old = arr[idx]
    return arr.at[idx].set(jnp.where(pred, value, old))


def _one_hot_read(plane, i):
    hit = jnp.arange(plane.shape[0], dtype=jnp.int32) == i
    hit = hit.reshape((-1,) + (1,) * (plane.ndim - 1))
    return jnp.where(hit, plane, 0).sum(axis=0, dtype=plane.dtype)


def _one_hot_write(arr, idx, value, pred):
    """``_point_write`` as a masked ``where``; ``value`` broadcasts over
    the axes ``idx`` leaves unindexed."""
    idx = idx if isinstance(idx, tuple) else (idx,)
    lead = arr.shape[:len(idx)]
    hit = pred
    for ax, i in enumerate(idx):
        hit = hit & (jax.lax.broadcasted_iota(jnp.int32, lead, ax) == i)
    hit = hit.reshape(lead + (1,) * (arr.ndim - len(idx)))
    return jnp.where(hit, jnp.asarray(value, arr.dtype), arr)


class StateAccess(NamedTuple):
    """A row read ``read(plane, i)`` and a conditional write
    ``write(arr, idx, value, pred)`` of the step's state planes."""

    read: Callable
    write: Callable


POINT_ACCESS = StateAccess(_point_read, _point_write)
ONE_HOT_ACCESS = StateAccess(_one_hot_read, _one_hot_write)


# ---------------------------------------------------------------------------
# The per-access step: the union of every kind's datapath, selected per lane
# ---------------------------------------------------------------------------


def step_access(lane, st, vpn, mrec, frec, bm, active,
                access: StateAccess = POINT_ACCESS):
    """One translation of ONE lane; returns ``(new_state, out_ppn)``.

    * ``lane`` — dict of per-lane scalars (+ the ``kvals`` vector);
    * ``st`` — the lane's state dict (packed L1/L1H/L2/RMM/CLUS arrays,
      ``t``, ``pred``, ``counters``, ``cov_samples``);
    * ``vpn`` — the accessed virtual page;
    * ``mrec``/``frec`` — the 4-wide map/fill records at ``vpn`` (gathered
      by the caller from the live epoch's record stack);
    * ``bm`` — the cluster bitmap word at ``vpn``;
    * ``active`` — False for padded steps: no state writes, no counters;
    * ``access`` — the form of every state-plane read and write
      (:data:`POINT_ACCESS` or :data:`ONE_HOT_ACCESS`; same results).

    The caller owns all gathers from the big record stacks — that is what
    lets the time-blocked backend hoist them to one bulk gather per block
    and the Pallas backend serve them from VMEM-resident per-segment
    blocks.
    """
    read, write = access
    maxk = lane["kvals"].shape[0]
    kvals = lane["kvals"]
    use_pred = lane["use_pred"]
    is_colt, is_thp = lane["is_colt"], lane["is_thp"]
    is_subr = lane["is_subr"]
    is_generic = ~is_colt & ~is_thp & ~is_subr
    has_rmm, has_cluster = lane["has_rmm"], lane["has_cluster"]
    has_ctlb, use_dead = lane["has_ctlb"], lane["use_dead"]
    set_mask = lane["set_mask"]
    k_hat = lane["k_hat"]
    n_ways_total = st["l2"].shape[1]
    way_idx = jnp.arange(n_ways_total, dtype=jnp.int32)
    way_ok = way_idx < lane["n_ways"]

    def probe_order(pred_k):
        """[pred_k, remaining K desc] when predicting, else K as packed
        (padded positions stay -1 and probe inertly)."""
        order = [jnp.where(use_pred, pred_k, kvals[0])]
        not_pred = kvals != pred_k
        csum = jnp.cumsum(not_pred.astype(jnp.int32))
        for pos in range(1, maxk):
            sel = not_pred & (csum == pos)
            spec_k = jnp.where(sel.any(), kvals[jnp.argmax(sel)],
                               jnp.int32(-1))
            order.append(jnp.where(use_pred, spec_k, kvals[pos]))
        return order

    t = st["t"]
    ppn_true, rs_v, rl_v, rmm_fill_ppn = (mrec[0], mrec[1], mrec[2], mrec[3])
    fill_tag, fill_k, fill_contig, fill_ppn, fill_aux = (
        frec[0], frec[1], frec[2], frec[3], frec[4])
    new = dict(st)

    cur = st["asid"]

    # ---------------- L1 (regular + gated 2MB array) ----------------
    s1 = vpn & jnp.int32(L1_SETS - 1)
    l1row = read(st["l1"], s1)
    l1_ways_hit = (l1row[:, 0] == vpn) & (l1row[:, 3] == cur)
    l1_hit = l1_ways_hit.any()
    l1_way = jnp.argmax(l1_ways_hit)
    hv = vpn >> 9
    s1h = hv & jnp.int32(L1H_SETS - 1)
    l1hrow = read(st["l1h"], s1h)
    h_ways_hit = (l1hrow[:, 0] == hv) & (l1hrow[:, 3] == cur)
    l1h_hit = is_thp & h_ways_hit.any()
    l1h_way = jnp.argmax(h_ways_hit)
    l1_served = l1_hit | l1h_hit
    l1_out_ppn = jnp.where(l1_hit, l1row[l1_way, 1],
                           l1hrow[l1h_way, 1] + (vpn & 511))

    # ---------------- L2 probes (all kinds, selected) ---------------
    s2 = (vpn >> k_hat) & set_mask
    row = read(st["l2"], s2)            # [W, 7]
    tags, kcls, contig, pbase = (row[:, TAG], row[:, KCLS],
                                 row[:, CONTIG], row[:, PPN])
    valid = (kcls != INVALID) & (row[:, L2_ASID] == cur)

    # colt branch
    diff = vpn - tags
    cover = valid & (diff >= 0) & (diff < contig)
    colt_hit = cover.any()
    colt_way = jnp.argmax(cover)
    colt_reg = colt_hit & (contig[colt_way] == 1)
    colt_coal = colt_hit & (contig[colt_way] > 1)
    colt_ppn = pbase[colt_way] + (vpn - tags[colt_way])

    # thp branch (dual-set probe on the same packed array)
    s2h = hv & set_mask
    row_h = read(st["l2"], s2h)
    huge_ways = (row_h[:, KCLS] == HUGE) & (row_h[:, TAG] == hv) & \
        (row_h[:, L2_ASID] == cur)
    reg_ways = (kcls == REGULAR) & (tags == vpn) & valid
    huge_hit = huge_ways.any()
    hw = jnp.argmax(huge_ways)
    rw = jnp.argmax(reg_ways)
    thp_reg = reg_ways.any() | huge_hit
    thp_ppn = jnp.where(reg_ways.any(), pbase[rw],
                        row_h[hw, PPN] + (vpn - (hv << 9)))
    thp_touch_ways = jnp.where(reg_ways.any(), reg_ways, huge_ways)
    thp_touch_set = jnp.where(reg_ways.any(), s2, s2h)

    # subregion branch: one entry covers the aligned SUBR_PAGES window;
    # the AUX bitmap says which offsets share the entry's VA->PA delta
    sub_base = vpn & ~jnp.int32(SUBR_PAGES - 1)
    sub_off = vpn & jnp.int32(SUBR_PAGES - 1)
    sub_cover = valid & (kcls == KSUBR) & (tags == sub_base) & \
        (((row[:, AUX] >> sub_off) & 1) == 1)
    subr_hit = sub_cover.any()
    subr_way = jnp.argmax(sub_cover)
    subr_reg = subr_hit & (contig[subr_way] == 1)
    subr_coal = subr_hit & (contig[subr_way] > 1)
    subr_ppn = pbase[subr_way] + sub_off

    # generic branch: regular probe + padded aligned-probe chain
    gen_reg = reg_ways.any()
    probes_used = jnp.int32(0)
    hit_k = jnp.int32(-1)
    gen_coal = jnp.bool_(False)
    coal_ppn = jnp.int32(-1)
    coal_way = jnp.int32(0)
    first_probe_k = jnp.int32(-1)
    for pos, k_val in enumerate(probe_order(st["pred"])):
        sh = jnp.maximum(k_val, 0)
        vk = jnp.where(k_val >= 0,
                       vpn & ~((jnp.int32(1) << sh) - 1),
                       jnp.int32(-10))
        m_ways = (kcls == k_val) & (tags == vk) & valid & \
                 (contig > (vpn - vk))
        m_hit = m_ways.any() & (k_val >= 0) & ~gen_reg & ~gen_coal
        probes_used = probes_used + jnp.where(
            ~gen_reg & ~gen_coal & (k_val >= 0), 1, 0)
        coal_ppn = jnp.where(m_hit, pbase[jnp.argmax(m_ways)]
                             + (vpn - vk), coal_ppn)
        coal_way = jnp.where(m_hit, jnp.argmax(m_ways), coal_way)
        hit_k = jnp.where(m_hit, k_val, hit_k)
        if pos == 0:
            first_probe_k = k_val
        gen_coal = gen_coal | m_hit

    # per-lane branch selection
    reg_hit = jnp.where(is_colt, colt_reg,
                        jnp.where(is_thp, thp_reg,
                                  jnp.where(is_subr, subr_reg, gen_reg)))
    coal_hit = jnp.where(is_generic, gen_coal,
                         (colt_coal & is_colt) | (subr_coal & is_subr))
    l2_hit = reg_hit | coal_hit
    l2_ppn_val = jnp.where(
        is_colt, colt_ppn,
        jnp.where(is_thp, thp_ppn,
                  jnp.where(is_subr, subr_ppn,
                            jnp.where(gen_reg, pbase[rw], coal_ppn))))
    pred_ok = jnp.where(use_pred & gen_coal
                        & (hit_k == first_probe_k), 1, 0)
    touch_set = jnp.where(is_thp, thp_touch_set, s2)
    tw = jnp.where(
        is_colt, colt_way,
        jnp.where(is_thp, jnp.argmax(thp_touch_ways),
                  jnp.where(is_subr, subr_way,
                            jnp.where(gen_reg, rw, coal_way))))
    probes_used = jnp.where(is_generic, probes_used, 0)

    # ---------------- side structures (gated) -----------------------
    d_r = vpn - st["rmm"][:, 0]
    in_rng = (d_r >= 0) & (d_r < st["rmm"][:, 1]) & \
        (st["rmm"][:, 4] == cur)
    rmm_hit = has_rmm & in_rng.any()
    sw = jnp.argmax(in_rng)
    rmm_ppn_val = st["rmm"][sw, 2] + d_r[sw]

    cwd = vpn >> 3
    sc = cwd & jnp.int32(CLUS_SETS - 1)
    crow = read(st["clus"], sc)         # [5, 4]
    bit = (crow[:, 1] >> (vpn & 7)) & 1
    c_ways = (crow[:, 0] == cwd) & (bit == 1) & (crow[:, 3] == cur)
    cl_hit = has_cluster & c_ways.any()

    # cache-backed tier (Victima lineage): probed only past an L1+L2 miss
    ctlb_sets = st["ctlb"].shape[0]     # degenerate (1, 1) when unused
    sct = vpn & jnp.int32(ctlb_sets - 1)
    trow = read(st["ctlb"], sct)
    t_ways = (trow[:, 0] == vpn) & (trow[:, 3] == cur)
    ctlb_hit = has_ctlb & ~l1_served & ~l2_hit & t_ways.any()
    ctlb_way = jnp.argmax(t_ways)

    side_hit = rmm_hit | cl_hit | ctlb_hit
    side_ppn = jnp.where(rmm_hit, rmm_ppn_val,
                         jnp.where(ctlb_hit, trow[ctlb_way, 1], ppn_true))

    hit_any = l1_served | l2_hit | side_hit
    walk = ~hit_any
    wr = walk & active  # gate for every state write below

    # ---------------- latency (per-lane miss chain) -----------------
    cyc = jnp.where(
        l1_served, 0,
        jnp.where(reg_hit, LAT_L2_REG,
                  jnp.where(coal_hit,
                            LAT_COAL + LAT_EXTRA_PROBE *
                            jnp.maximum(probes_used - 1, 0),
                            jnp.where(side_hit,
                                      jnp.where(ctlb_hit, LAT_CTLB,
                                                LAT_COAL),
                                      lane["miss_chain"]
                                      + LAT_WALK))))

    # ---------------- L2 fill (precomputed record; LRU victim) ------
    # dead-protect: a walk whose vpn's counter is still 0 (never
    # re-referenced) bypasses the L2 fill; the counter saturates at 3
    dp_n = st["dp"].shape[0]            # degenerate (1,) when unused
    dp_idx = vpn & jnp.int32(dp_n - 1)
    dp_ctr = read(st["dp"], dp_idx)
    dp_bypass = use_dead & walk & (dp_ctr == 0)
    new["dp"] = write(st["dp"], dp_idx, jnp.minimum(dp_ctr + 1, 3),
                          use_dead & wr)

    served_huge = is_thp & (fill_k == HUGE)
    fill_set = jnp.where(served_huge, s2h, s2)
    frow = jnp.where(served_huge, row_h, row)   # the row at fill_set
    valid_row = frow[:, KCLS] != INVALID
    score = jnp.where(way_ok,
                      jnp.where(valid_row, frow[:, LRU],
                                jnp.int32(NEG)),
                      jnp.int32(BIG))
    victim = jnp.argmin(score)
    fill_wr = wr & ~dp_bypass
    evicted_contig = jnp.where(valid_row[victim],
                               frow[victim, CONTIG], 0)
    fill_vec = jnp.stack([fill_tag, fill_k, fill_contig, fill_ppn, t, cur,
                          fill_aux])
    l2n = write(st["l2"], (fill_set, victim), fill_vec, fill_wr)
    new["l2"] = write(l2n, (touch_set, tw, LRU), t,
                          l2_hit & ~walk & ~l1_served & active)
    cov_delta = jnp.where(fill_wr, fill_contig - evicted_contig, 0)

    # Victima move: a valid L2 victim drops into the cache-backed tier
    mv = fill_wr & has_ctlb & valid_row[victim]
    ev_tag = frow[victim, TAG]
    sct_v = ev_tag & jnp.int32(ctlb_sets - 1)
    vrow_ct = read(st["ctlb"], sct_v)
    vrow_t = vrow_ct[:, 0] >= 0
    victim_t = jnp.argmin(jnp.where(vrow_t, vrow_ct[:, 2], jnp.int32(NEG)))
    ctlb_vec = jnp.stack([ev_tag, frow[victim, PPN], t,
                          frow[victim, L2_ASID]])
    ctn = write(st["ctlb"], (sct_v, victim_t), ctlb_vec, mv)
    new["ctlb"] = write(ctn, (sct, ctlb_way, 2), t,
                            ctlb_hit & active)
    cov_delta = cov_delta + jnp.where(
        mv, 1 - vrow_t[victim_t].astype(jnp.int32), 0)

    # ---------------- side fills (gated) ----------------------------
    rmm_len = st["rmm"][:, 1]
    victim_r = jnp.argmin(jnp.where(rmm_len > 0, st["rmm"][:, 3],
                                    jnp.int32(NEG)))
    ev_len = jnp.where(rmm_len[victim_r] > 0, rmm_len[victim_r], 0)
    rmm_wr = wr & has_rmm
    rmm_vec = jnp.stack([rs_v, rl_v, rmm_fill_ppn, t, cur])
    rmmn = write(st["rmm"], victim_r, rmm_vec, rmm_wr)
    new["rmm"] = write(rmmn, (sw, 3), t, rmm_hit & active)
    cov_delta = cov_delta + jnp.where(rmm_wr, rl_v - ev_len, 0)

    clusterable = bm != (jnp.int32(1) << (vpn & 7))
    fill_c = wr & clusterable & has_cluster
    vrow = crow[:, 1] != 0
    victim_c = jnp.argmin(jnp.where(vrow, crow[:, 2],
                                    jnp.int32(NEG)))
    cl_vec = jnp.stack([cwd, bm, t, cur])
    cln = write(st["clus"], (sc, victim_c), cl_vec, fill_c)
    hit_cway = jnp.argmax((crow[:, 0] == cwd) & (crow[:, 3] == cur))
    new["clus"] = write(cln, (sc, hit_cway, 2), t,
                            cl_hit & active)

    # ---------------- L1 fills --------------------------------------
    do1h = ~l1_served & served_huge & active
    vrh = l1hrow[:, 0] >= 0
    vich = jnp.argmin(jnp.where(vrh, l1hrow[:, 2], jnp.int32(NEG)))
    l1h_vec = jnp.stack([hv, fill_ppn, t, cur])
    l1hn = write(st["l1h"], (s1h, vich), l1h_vec, do1h)
    new["l1h"] = write(
        l1hn, (s1h, l1h_way, 2), t,
        is_thp & l1_served & h_ways_hit.any() & ~l1_hit & active)

    do1 = ~l1_served & ~served_huge & active
    vr1 = l1row[:, 0] >= 0
    vic1 = jnp.argmin(jnp.where(vr1, l1row[:, 2], jnp.int32(NEG)))
    l1_vec = jnp.stack([vpn, ppn_true, t, cur])
    l1n = write(st["l1"], (s1, vic1), l1_vec, do1)
    new["l1"] = write(l1n, (s1, l1_way, 2), t, l1_hit & active)

    # ---------------- predictor update (gated) ----------------------
    upd = use_pred & active
    new["pred"] = jnp.where(
        upd & gen_coal, hit_k,
        jnp.where(upd & walk & (fill_k >= 0), fill_k, st["pred"]))

    # ---------------- accounting (one packed add) -------------------
    act = active
    delta = jnp.stack([
        (l1_served & act).astype(jnp.int32),
        (reg_hit & ~l1_served & act).astype(jnp.int32),
        ((coal_hit | side_hit) & ~reg_hit & ~l1_served
         & act).astype(jnp.int32),
        (walk & act).astype(jnp.int32),
        jnp.where(coal_hit & ~l1_served & act, probes_used, 0),
        # dead-protect rides C_PRED: bypassed fills count as predictions
        jnp.where(~l1_served & act, pred_ok, 0)
        + (dp_bypass & act).astype(jnp.int32),
        jnp.where(act, cyc, 0),
        cov_delta,
        jnp.int32(0),
    ])
    new["counters"] = st["counters"] + delta
    new["t"] = t + act.astype(jnp.int32)
    se = lane["sample_every"]
    slot = jnp.minimum(t // se, N_COV_SAMPLES - 1)
    new["cov_samples"] = write(st["cov_samples"], slot,
                                   new["counters"][C_COV],
                                   (t % se == se - 1) & active)

    out_ppn = jnp.where(
        l1_served, l1_out_ppn,
        jnp.where(l2_hit, l2_ppn_val,
                  jnp.where(side_hit, side_ppn, ppn_true)))
    return new, out_ppn


def shoot_lane(lane, st, dc, do):
    """Translation coherence on epoch turnover (gated by ``do``): drop
    every entry — in every structure — whose covered vpn range contains a
    dirty vpn of the entered epoch (``dc`` = the epoch's dirty-bitmap
    prefix sums, ``[P+1]``), charge the coherence cost, and release the
    dropped reach.  Both ``coh_policy`` values drop the identical entry
    set; they differ only in cycles — IPI-style ``shootdown`` pays the
    ``LAT_SHOOTDOWN`` broadcast stall plus ``LAT_INVALIDATE`` per entry,
    directory-tracked ``hw-coherence`` (``lane['coh_hw']``) pays only the
    targeted per-entry invalidations."""
    is_thp, is_subr = lane["is_thp"], lane["is_subr"]
    Pn = dc.shape[0] - 1

    def rng_dirty(lo, ln):
        lo_ = jnp.clip(lo, 0, Pn)
        hi_ = jnp.clip(lo + ln, 0, Pn)
        return (dc[hi_] - dc[lo_]) > 0

    new = dict(st)
    l2 = st["l2"]
    tagv, kv, cgv = l2[..., TAG], l2[..., KCLS], l2[..., CONTIG]
    # k == HUGE is a 2MB entry (tag = vpn >> 9) only on THP lanes;
    # K-bit Aligned lanes use k = 9 as a plain alignment class.
    # Subregion entries cover their whole SUBR_PAGES window (conservative:
    # a dirty page under a cleared bitmap bit still drops the entry — a
    # cleared bit can only miss, never serve stale).
    huge2 = is_thp & (kv == HUGE)
    subr2 = is_subr & (kv == KSUBR)
    stale2 = (kv != INVALID) & do & rng_dirty(
        jnp.maximum(jnp.where(huge2, tagv << 9, tagv), 0),
        jnp.where(huge2, 512,
                  jnp.where(subr2, SUBR_PAGES,
                            jnp.where(kv == REGULAR, 1,
                                      jnp.maximum(cgv, 1)))))
    new["l2"] = l2.at[..., KCLS].set(jnp.where(stale2, INVALID, kv))
    n_inv = stale2.sum(dtype=jnp.int32)
    cov_loss = jnp.where(stale2, cgv, 0).sum(dtype=jnp.int32)

    l1 = st["l1"]
    t1 = l1[..., 0]
    stale1 = (t1 >= 0) & do & rng_dirty(jnp.maximum(t1, 0), 1)
    new["l1"] = l1.at[..., 0].set(jnp.where(stale1, -1, t1))
    n_inv = n_inv + stale1.sum(dtype=jnp.int32)

    l1h = st["l1h"]
    th = l1h[..., 0]
    staleh = (th >= 0) & do & rng_dirty(jnp.maximum(th, 0) << 9, 512)
    new["l1h"] = l1h.at[..., 0].set(jnp.where(staleh, -1, th))
    n_inv = n_inv + staleh.sum(dtype=jnp.int32)

    rmm = st["rmm"]
    rs0, rl0 = rmm[:, 0], rmm[:, 1]
    staler = (rl0 > 0) & do & rng_dirty(jnp.maximum(rs0, 0), rl0)
    rmm2 = rmm.at[:, 0].set(jnp.where(staler, -1, rs0))
    rmm2 = rmm2.at[:, 1].set(jnp.where(staler, 0, rl0))
    new["rmm"] = rmm2.at[:, 2].set(jnp.where(staler, -1, rmm[:, 2]))
    n_inv = n_inv + staler.sum(dtype=jnp.int32)
    cov_loss = cov_loss + jnp.where(staler, rl0, 0).sum(
        dtype=jnp.int32)

    cl = st["clus"]
    ct, cb = cl[..., 0], cl[..., 1]
    stalec = (cb != 0) & do & rng_dirty(jnp.maximum(ct, 0) << 3, 8)
    new["clus"] = cl.at[..., 1].set(jnp.where(stalec, 0, cb))
    n_inv = n_inv + stalec.sum(dtype=jnp.int32)

    # cache-backed tier holds 4KB translations: tag-range-1 stale pass
    # (the dead-entry counter table holds predictions, nothing to drop)
    ctb = st["ctlb"]
    tt = ctb[..., 0]
    stalet = (tt >= 0) & do & rng_dirty(jnp.maximum(tt, 0), 1)
    new["ctlb"] = ctb.at[..., 0].set(jnp.where(stalet, -1, tt))
    n_inv = n_inv + stalet.sum(dtype=jnp.int32)
    cov_loss = cov_loss + stalet.sum(dtype=jnp.int32)

    cnt = st["counters"]
    add = (jnp.zeros_like(cnt)
           .at[C_SHOOT].set(n_inv)
           .at[C_CYC].set(jnp.where(do & ~lane["coh_hw"],
                                    LAT_SHOOTDOWN, 0)
                          + n_inv * LAT_INVALIDATE)
           .at[C_COV].set(-cov_loss))
    new["counters"] = cnt + add
    return new


def switch_lane(st, new_asid, do_switch, flush_all, flush_asid):
    """Context switch at segment entry (multi-tenant worlds).

    Sets the live ASID from per-``(lane, segment)`` data (``new_asid``
    equals the current ASID when this lane has no boundary here, so the
    unconditional write is a no-op), charges ``LAT_CTX_SWITCH`` when the
    address space changed (``do_switch``), and bulk-clears entries —
    every structure under ``flush_all`` (the untagged-hardware policy),
    or only entries tagged ``new_asid`` under ``flush_asid`` (an ASID
    recycled from a departed tenant: its stale entries must not serve
    the newcomer).  Flushes drop valid bits in bulk — no per-entry
    invalidation-port cycles, unlike coherence shootdowns — and the
    dropped entries are counted in the shootdown counter; the real cost
    surfaces as refill walks.  Static/dynamic lanes carry all-False
    flags and ASID 0 everywhere, making this pass a no-op for them."""
    new = dict(st)

    def kill(valid, asid_col):
        return valid & (flush_all | (flush_asid & (asid_col == new_asid)))

    l2 = st["l2"]
    kv = l2[..., KCLS]
    k2 = kill(kv != INVALID, l2[..., L2_ASID])
    new["l2"] = l2.at[..., KCLS].set(jnp.where(k2, INVALID, kv))
    n_inv = k2.sum(dtype=jnp.int32)
    cov_loss = jnp.where(k2, l2[..., CONTIG], 0).sum(dtype=jnp.int32)

    l1 = st["l1"]
    t1 = l1[..., 0]
    k1 = kill(t1 >= 0, l1[..., 3])
    new["l1"] = l1.at[..., 0].set(jnp.where(k1, -1, t1))
    n_inv = n_inv + k1.sum(dtype=jnp.int32)

    l1h = st["l1h"]
    th = l1h[..., 0]
    kh = kill(th >= 0, l1h[..., 3])
    new["l1h"] = l1h.at[..., 0].set(jnp.where(kh, -1, th))
    n_inv = n_inv + kh.sum(dtype=jnp.int32)

    rmm = st["rmm"]
    rl0 = rmm[:, 1]
    kr = kill(rl0 > 0, rmm[:, 4])
    rmm2 = rmm.at[:, 0].set(jnp.where(kr, -1, rmm[:, 0]))
    rmm2 = rmm2.at[:, 1].set(jnp.where(kr, 0, rl0))
    new["rmm"] = rmm2.at[:, 2].set(jnp.where(kr, -1, rmm[:, 2]))
    n_inv = n_inv + kr.sum(dtype=jnp.int32)
    cov_loss = cov_loss + jnp.where(kr, rl0, 0).sum(dtype=jnp.int32)

    cl = st["clus"]
    cb = cl[..., 1]
    kc = kill(cb != 0, cl[..., 3])
    new["clus"] = cl.at[..., 1].set(jnp.where(kc, 0, cb))
    n_inv = n_inv + kc.sum(dtype=jnp.int32)

    # cache-backed tier is ASID-tagged like everything else; the
    # dead-entry counter table is a predictor and survives switches
    ctb = st["ctlb"]
    tt = ctb[..., 0]
    kt = kill(tt >= 0, ctb[..., 3])
    new["ctlb"] = ctb.at[..., 0].set(jnp.where(kt, -1, tt))
    n_inv = n_inv + kt.sum(dtype=jnp.int32)
    cov_loss = cov_loss + kt.sum(dtype=jnp.int32)

    new["asid"] = new_asid
    cnt = st["counters"]
    add = (jnp.zeros_like(cnt)
           .at[C_SHOOT].set(n_inv)
           .at[C_CYC].set(jnp.where(do_switch, LAT_CTX_SWITCH, 0))
           .at[C_COV].set(-cov_loss))
    new["counters"] = cnt + add
    return new


# ---------------------------------------------------------------------------
# The block plan: the static time-blocked timeline both backends execute
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Static execution timeline for one packed batch.

    Every epoch segment ``[seg_bounds[s], seg_bounds[s+1])`` is padded to a
    whole number of ``tb``-step blocks, so a block never straddles a
    segment boundary and the per-segment record ids stay constant within a
    block.  Padded slots (``tpos >= blk_hi``) are fully inert.  The first
    block of every segment ``s > 0`` carries the shootdown flag; whether a
    given lane actually shoots there stays per-lane data
    (``lanes['seg_shoot']``).
    """

    tb: int                   # block size (trace steps per block)
    n_blocks: int             # total blocks across all segments
    blk_seg: np.ndarray       # [NB]    segment id of each block
    blk_shoot: np.ndarray     # [NB]    block enters a segment with s > 0
    blk_hi: np.ndarray        # [NB]    end bound of the block's segment
    tpos: np.ndarray          # [NB*TB] original t per padded slot
    slot_of_t: np.ndarray     # [T]     padded slot per original t


def build_block_plan(seg_bounds: Tuple[int, ...], tb: int) -> BlockPlan:
    T = seg_bounds[-1]
    blk_seg, blk_shoot, blk_hi, tpos = [], [], [], []
    slot_of_t = np.zeros(T, np.int32)
    for s, (lo, hi) in enumerate(zip(seg_bounds, seg_bounds[1:])):
        nb = -(-(hi - lo) // tb)
        for b in range(nb):
            blk_seg.append(s)
            blk_shoot.append(b == 0 and s > 0)
            blk_hi.append(hi)
            for j in range(tb):
                t = lo + b * tb + j
                if t < hi:
                    slot_of_t[t] = len(tpos)
                tpos.append(t)
    return BlockPlan(
        tb=tb, n_blocks=len(blk_seg),
        blk_seg=np.asarray(blk_seg, np.int32),
        blk_shoot=np.asarray(blk_shoot, bool),
        blk_hi=np.asarray(blk_hi, np.int32),
        tpos=np.asarray(tpos, np.int32),
        slot_of_t=slot_of_t)


class SweepCellLike:  # pragma: no cover - typing aid only
    """Anything with ``.spec``, ``.mapping``, ``.trace`` (see SweepCell)."""

    spec: MethodSpec
    mapping: object
    trace: np.ndarray
