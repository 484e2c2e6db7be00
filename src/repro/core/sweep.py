"""Batched sweep engine: every method × trace as one compiled program.

:func:`repro.core.simulator.run_method` simulates one ``(spec, mapping,
trace)`` triple per call and re-compiles for every distinct ``MethodSpec``
and every distinct array shape.  A paper-scale sweep (7+ methods × 16
benchmarks × several |K| / seed settings) pays that compile cost hundreds of
times.  This module instead *pads every method onto one common array layout*
so that all of ``base/thp/colt/cluster/rmm/anchor/kaligned`` run as lanes of
a single program, compiled once per shape bucket and reused across traces
and seeds.  The per-lane program itself — packing rules, the union step
datapath, the shootdown pass, the time-blocked execution plan — lives in
:mod:`repro.core.lane_program`; this module executes it and orchestrates
caching.  Two backends consume that one definition:

* ``backend='xla'`` (the CPU/GPU fast path): one ``jax.lax.scan`` whose
  carry is the packed state of ALL lanes and whose body advances every lane
  by a **block** of ``TB`` trace steps — the per-step map/fill/cluster/trace
  gathers are hoisted into one bulk gather per block and the intra-block
  dependency chain is unrolled, so a block costs a handful of fused memory
  ops instead of ``TB × (~10 gathers + ~5 scatters)`` of vmapped
  point-scatter dispatches.  Epoch-turnover shootdowns run under a
  ``lax.cond`` on the (static-timeline) segment-entry blocks, so static
  batches never pay them.  Lowered for a TPU, the step reads and writes
  its state planes in the one-hot form (:func:`_lane_step`).
* ``backend='pallas'`` (:mod:`repro.kernels.tlb_sweep`): a Pallas kernel
  whose grid maps lanes to program instances, keeps all TLB state in
  scratch for the whole trace, and streams trace blocks in.  It runs only
  in the Pallas interpreter: the TPU compiler refuses it (see
  :data:`PALLAS_TPU_REFUSAL`).

``backend='auto'`` picks ``xla`` on every platform.  Both
backends are bit-exact against the pure-python oracles
:func:`~repro.core.simulator.run_method` /
:func:`~repro.core.simulator.run_method_dynamic` for every block size
(``tests/test_backends.py``), so results and cache entries never depend on
the execution strategy.

Dynamic worlds (:class:`~repro.core.page_table.DynamicMapping`) run as
**epoch-segmented lanes**: records are precomputed per ``(world, epoch)``,
the block timeline is split at the static union of all lanes' epoch
boundaries, and the first block of every segment runs a vectorized
shootdown pass — gated per lane by whether its epoch turned over — that
invalidates every entry whose covered vpn range contains a page whose
translation died.  ``run_sweep`` partitions each batch so purely-static
cells never ride a multi-segment timeline.

When JAX exposes several (virtual) host devices, lanes are sharded across
them with ``pmap`` — lane batches are padded to a device multiple so every
run shards (``benchmarks/_env.py`` turns the devices on for benchmarks).

:func:`run_sweep` is the orchestrator: it dedups mappings/traces, packs
lanes, consults an on-disk result cache under ``results/sweep_cache`` keyed
by ``(spec, mapping hash, trace hash, code fingerprint)``, simulates only
the missing cells, and returns per-cell
:class:`~repro.core.simulator.SimResult` objects bit-identical to the
per-call oracle.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import subprocess
import zipfile
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .lane_program import (
    C_COAL, C_CYC, C_L1, C_PRED, C_PROBE, C_REG, C_SHOOT, C_WALK,
    LANE_SHARE_MAX, STEP_KEYS, build_block_plan,
    init_batched_state as _init_batched_state, needs_switch_pass,
    ONE_HOT_ACCESS, POINT_ACCESS, StateAccess, pack_lanes as _pack_lanes,
    shoot_lane, step_access, switch_lane)
from .page_table import (DynamicMapping, Mapping, MultiTenantMapping,
                         NestedMapping, ParityWorld)
from .simulator import MethodSpec, SimResult
from ..spans import span

# Default trace-steps-per-block of the time-blocked XLA backend.  Override
# per call with ``run_sweep(..., block_size=...)`` or globally with the
# ``REPRO_SWEEP_BLOCK`` env var.  Measured on CPU: run time keeps improving
# up to ~32 steps per block (the per-block record gathers amortize), while
# the inner-scan block body keeps compile time flat in the block size.
DEFAULT_BLOCK = 32


def _block_size(block_size: Optional[int]) -> int:
    if block_size is None:
        block_size = int(os.environ.get("REPRO_SWEEP_BLOCK", DEFAULT_BLOCK))
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    return block_size


#: The first refusal of the TPU compiler (jax 0.9.0, v5e) for the
#: ``tlb_sweep`` kernel.  Behind it the kernel also reads scalars out of
#: vector blocks and brings whole-footprint map/fill records into VMEM.
PALLAS_TPU_REFUSAL = (
    "the TPU compiler refuses the tlb_sweep Pallas kernel: \"The Pallas TPU "
    "lowering currently requires that the last two dimensions of your "
    "block shape are divisible by 8 and 128 respectively, or be equal to "
    "the respective dimensions of the overall array\" (the per-lane "
    "params block (1, 17) on an [L, 17] array); use backend='xla' on a TPU")


def resolve_backend(backend: Optional[str]) -> str:
    """Resolve the ``backend`` knob to the backend that actually runs.

    ``'auto'``/``None`` is ``xla`` on every platform: the Pallas kernel
    compiles for no chip (:data:`PALLAS_TPU_REFUSAL`), so on a TPU
    ``'pallas'`` raises here, before any batch runs, rather than letting
    the recovery ladder turn every batch into an unreported XLA run.
    Public so harnesses recording what ran (``benchmarks/run.py``) resolve
    it the same way ``run_sweep`` does."""
    if backend in (None, "auto"):
        return "xla"
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown sweep backend {backend!r} "
                         "(want 'auto', 'xla' or 'pallas')")
    if backend == "pallas" and jax.default_backend() == "tpu":
        raise ValueError(PALLAS_TPU_REFUSAL)
    return backend


@dataclasses.dataclass(frozen=True)
class SweepCell:
    """One cell of a sweep: simulate ``spec`` over ``(mapping, trace)``.

    * ``spec``    — a :class:`~repro.core.simulator.MethodSpec` (build one
      with the factories in :mod:`repro.core.baselines`); its static config
      becomes per-lane *data* in the batched engine, so cells with different
      specs still share one compiled program.
    * ``mapping`` — a contiguity-annotated
      :class:`~repro.core.page_table.Mapping`, a
      :class:`~repro.core.page_table.DynamicMapping` whose epoch boundaries
      segment the trace (mid-trace remaps with shootdown-correct
      invalidation), **or** a
      :class:`~repro.core.page_table.MultiTenantMapping` whose schedule
      segments it (ASID-tagged context switching; the flush-vs-tag policy
      is ``spec.ctx_policy``), **or** a
      :class:`~repro.core.page_table.NestedMapping` whose segment grid is
      the union of its VM schedule, guest epochs and host epochs (two-level
      translation; the shootdown-vs-hw-coherence knob is
      ``spec.coh_policy``), **or** a
      :class:`~repro.core.page_table.ParityWorld` wrapping any of those
      plus a schedule of mid-trace TLB parity-flip faults (soft-error
      recovery; the detect-invalidate-rewalk vs in-place-correction knob
      is ``spec.par_policy``); get one from a registered scenario
      (:mod:`repro.scenarios`) or the generators in
      :mod:`repro.core.mappings`.
    * ``trace``   — 1-D integer array of VPNs (every entry must be a mapped
      page of the epoch/tenant live at that step).

    Mappings/traces shared between cells (by object identity) are packed and
    hashed once, so build each world once and reuse it across specs.
    """

    spec: MethodSpec
    mapping: ("Mapping | DynamicMapping | MultiTenantMapping | "
              "NestedMapping | ParityWorld")
    trace: np.ndarray

    def __post_init__(self):
        assert self.trace.ndim == 1
        world = self.mapping
        if isinstance(world, ParityWorld):
            assert all(0 < t < self.trace.shape[0]
                       for t, _ in world.faults), \
                "fault steps must fall inside the trace"
            world = world.base
        if isinstance(world, (DynamicMapping, MultiTenantMapping)):
            assert all(0 < b < self.trace.shape[0]
                       for b in world.boundaries[1:]), \
                "segment boundaries must fall inside the trace"
        elif isinstance(world, NestedMapping):
            assert all(0 < ns.lo < self.trace.shape[0]
                       for ns in world.plan_segments()[1:]), \
                "segment boundaries must fall inside the trace"

    @property
    def epochs(self) -> Tuple[Mapping, ...]:
        world = self.mapping
        if isinstance(world, ParityWorld):
            world = world.base
        if isinstance(world, DynamicMapping):
            return world.epochs
        if isinstance(world, MultiTenantMapping):
            return world.tenants
        if isinstance(world, NestedMapping):
            # distinct composed guest-over-host views, schedule order
            seen, out = set(), []
            for ns in world.plan_segments():
                if id(ns.mapping) not in seen:
                    seen.add(id(ns.mapping))
                    out.append(ns.mapping)
            return tuple(out)
        return (world,)

    @property
    def boundaries(self) -> Tuple[int, ...]:
        world, faults = self.mapping, ()
        if isinstance(world, ParityWorld):
            faults = tuple(t for t, _ in world.faults)
            world = world.base
        if isinstance(world, (DynamicMapping, MultiTenantMapping)):
            base = world.boundaries
        elif isinstance(world, NestedMapping):
            base = tuple(ns.lo for ns in world.plan_segments())
        else:
            base = (0,)
        return tuple(sorted(set(base) | set(faults)))

    @property
    def is_segmented(self) -> bool:
        """True when the lane rides a multi-segment timeline (mid-trace
        remap epochs, multi-tenant scheduling quanta, or the union grid
        of a nested guest/host world)."""
        return len(self.boundaries) > 1


@dataclasses.dataclass
class SweepResult:
    """Per-cell results (aligned with the request list) plus run stats."""

    results: List[SimResult]
    stats: Dict[str, float]

    def __iter__(self):
        return iter(self.results)

    def __getitem__(self, i):
        return self.results[i]


# ---------------------------------------------------------------------------
# The XLA backend: one scan over TB-step blocks, body vmapped over lanes
# ---------------------------------------------------------------------------

def _platform_read(plane, i):
    return jax.lax.platform_dependent(plane, i, default=POINT_ACCESS.read,
                                      tpu=ONE_HOT_ACCESS.read)


def _platform_write(arr, idx, value, pred):
    return jax.lax.platform_dependent(arr, idx, value, pred,
                                      default=POINT_ACCESS.write,
                                      tpu=ONE_HOT_ACCESS.write)


def _lane_step(lane, st, *x):
    """:func:`~repro.core.lane_program.step_access` in the state-access form
    of the platform the program is lowered for: one-hot on a TPU, whose
    layouts for the point form's batched gathers and scatters copy whole
    padded state planes every step; the point form elsewhere, where it is
    the faster one.  Each primitive chooses for itself, so the step is
    traced once and not once per form."""
    return step_access(lane, st, *x,
                       access=StateAccess(_platform_read, _platform_write))


def _run_lanes_impl(lanes, stacks, st0, seg_bounds, tb, with_switch):
    """Time-blocked batched simulation of every lane.

    One ``lax.scan`` over the :class:`~repro.core.lane_program.BlockPlan`
    timeline: the body gathers the block's trace/map/fill/cluster records
    for ALL lanes in bulk, then advances the ``tb`` sequentially-dependent
    accesses with the shared :func:`~repro.core.lane_program.step_access`.
    Segment-entry blocks run the vectorized shootdown under ``lax.cond`` —
    skipped entirely at runtime on non-boundary blocks (and absent from the
    timeline of static batches)."""
    plan = build_block_plan(seg_bounds, tb)
    map_stack = stacks["maps"]
    fill_stack = stacks["fills"]
    clus_map = stacks["clus"]
    dirty_stack = stacks["dirty"]
    trace_stack = stacks["trace"]
    Pc = clus_map.shape[1]
    NB = plan.n_blocks
    L = lanes["t_real"].shape[0]
    lane_params = {k: lanes[k] for k in STEP_KEYS}

    xs = dict(tt=jnp.asarray(plan.tpos.reshape(NB, tb)),
              seg=jnp.asarray(plan.blk_seg),
              shoot=jnp.asarray(plan.blk_shoot),
              hi=jnp.asarray(plan.blk_hi))

    def lane_blk(lane, st, vpn_b, mrec_b, frec_b, bm_b, act_b):
        # the tb accesses are a sequential dependency chain over the
        # pre-gathered records; an inner scan keeps the compiled body one
        # step wide (unrolling it multiplies compile time for no run-time
        # gain on XLA — the win is the hoisted per-block gathers)
        def inner(st, x):
            return _lane_step(lane, st, *x)

        return jax.lax.scan(inner, st, (vpn_b, mrec_b, frec_b, bm_b, act_b))

    def blk_body(st_all, x):
        seg = x["seg"]

        def do_entry(s):
            # context switch first (set ASID, charge, policy flush), then
            # the translation-coherence shootdown — the oracle's order.
            # ``with_switch`` is static: batches with no multi-tenant lane
            # (all switch flags False by construction) never compile the
            # switch pass at all.
            if with_switch:
                s = jax.vmap(switch_lane)(
                    s, lanes["seg_asid"][:, seg],
                    lanes["seg_switch"][:, seg],
                    lanes["seg_fall"][:, seg], lanes["seg_fasid"][:, seg])
            do = lanes["seg_shoot"][:, seg]
            dcs = dirty_stack[lanes["seg_dirty"][:, seg]]
            return jax.vmap(shoot_lane)(lane_params, s, dcs, do)

        st_all = jax.lax.cond(x["shoot"], do_entry, lambda s: s, st_all)

        vpns = trace_stack[lanes["trace_id"][:, None], x["tt"][None, :]]
        mrecs = map_stack[lanes["seg_map"][:, seg, None], vpns]
        frecs = fill_stack[lanes["seg_fill"][:, seg, None], vpns]
        bms = clus_map[lanes["seg_clus"][:, seg, None],
                       jnp.clip(vpns, 0, Pc - 1)]
        act = (x["tt"][None, :] < x["hi"]) & \
              (x["tt"][None, :] < lanes["t_real"][:, None])
        return jax.vmap(lane_blk)(lane_params, st_all, vpns, mrecs, frecs,
                                  bms, act)

    stF, pp = jax.lax.scan(blk_body, st0, xs)        # pp: [NB, L, tb]
    pp = jnp.moveaxis(pp, 1, 0).reshape(L, NB * tb)
    return stF, pp[:, plan.slot_of_t]


_run_lanes_jit = jax.jit(_run_lanes_impl, static_argnums=(3, 4, 5))
_run_lanes_pmap = jax.pmap(_run_lanes_impl, in_axes=(0, None, 0),
                           static_broadcasted_argnums=(3, 4, 5))


def _simulate_lanes(lanes, stacks, st0, seg_bounds, backend="xla",
                    tb=DEFAULT_BLOCK):
    """Run one packed batch on the selected backend.

    ``xla``: dispatch to ``pmap`` over virtual host devices when available
    (lane batches are padded to a device multiple by
    :func:`~repro.core.lane_program.bucket_lane_count`, so benchmark runs
    always shard), else a single jitted scan.  ``pallas``: the
    :mod:`repro.kernels.tlb_sweep` kernel (interpret mode off-TPU).
    Returns ``(final_state, ppns)`` with at least ``counters`` and
    ``cov_samples`` in the state dict.

    Spans: ``repro.sweep.upload`` (the single-device XLA path only; the
    ``pmap`` and Pallas calls transfer their inputs inside
    ``repro.sweep.scan``), ``repro.sweep.scan`` up to the outputs being
    ready, and ``repro.sweep.readback``."""
    if backend == "pallas":
        from ..kernels.tlb_sweep import run_lanes_pallas
        with span("repro.sweep.scan"):
            stF, ppns = jax.block_until_ready(
                run_lanes_pallas(lanes, stacks, st0, seg_bounds, tb))
        with span("repro.sweep.readback"):
            return jax.device_get(stF), np.asarray(jax.device_get(ppns))
    with_switch = needs_switch_pass(lanes)
    dev = jax.local_device_count()
    L = lanes["t_real"].shape[0]
    if dev > 1 and L % dev == 0:
        def shard(x):
            return x.reshape((dev, L // dev) + x.shape[1:])

        with span("repro.sweep.scan"):
            stF, ppns = jax.block_until_ready(_run_lanes_pmap(
                {k: shard(v) for k, v in lanes.items()}, stacks,
                {k: shard(v) for k, v in st0.items()}, seg_bounds, tb,
                with_switch))
        unshard = lambda x: np.asarray(x).reshape((L,) + x.shape[2:])  # noqa: E731
        with span("repro.sweep.readback"):
            return ({k: unshard(v) for k, v in jax.device_get(stF).items()},
                    unshard(jax.device_get(ppns)))
    with span("repro.sweep.upload"):
        lanes, stacks, st0 = jax.block_until_ready(
            jax.device_put((lanes, stacks, st0)))
    with span("repro.sweep.scan"):
        stF, ppns = jax.block_until_ready(_run_lanes_jit(
            lanes, stacks, st0, seg_bounds, tb, with_switch))
    with span("repro.sweep.readback"):
        return jax.device_get(stF), np.asarray(jax.device_get(ppns))


# ---------------------------------------------------------------------------
# On-disk result cache
# ---------------------------------------------------------------------------

_GIT_DESCRIBE: Optional[str] = None
_CODE_FINGERPRINT: Optional[str] = None

# Everything that defines the simulation semantics: the engine sources AND
# both backend implementations.  Paths are relative to src/repro/.
_FINGERPRINT_SOURCES = (
    "core/simulator.py",
    "core/sweep.py",
    "core/lane_program.py",
    "core/page_table.py",
    "core/plane_layout.py",
    "kernels/tlb_sweep/tlb_sweep.py",
    "kernels/tlb_sweep/ops.py",
)


def _git_describe() -> str:
    global _GIT_DESCRIBE
    if _GIT_DESCRIBE is None:
        try:
            _GIT_DESCRIBE = subprocess.run(
                ["git", "describe", "--always", "--dirty"],
                capture_output=True, text=True, timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            ).stdout.strip() or "nogit"
        except (OSError, subprocess.SubprocessError):
            _GIT_DESCRIBE = "nogit"
    return _GIT_DESCRIBE


def _code_fingerprint() -> str:
    """git describe + a content hash of the engine AND kernel sources, so
    uncommitted edits to the simulation semantics — including the Pallas
    TLB-sweep kernel — invalidate the cache too (a dirty tree always yields
    the same '<sha>-dirty' describe string)."""
    global _CODE_FINGERPRINT
    if _CODE_FINGERPRINT is None:
        h = hashlib.sha256(_git_describe().encode())
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for fname in _FINGERPRINT_SOURCES:
            try:
                with open(os.path.join(pkg, fname), "rb") as f:
                    h.update(f.read())
            except OSError:
                h.update(b"?")
        _CODE_FINGERPRINT = h.hexdigest()
    return _CODE_FINGERPRINT


def _array_digest(a: np.ndarray) -> str:
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def cell_key(cell: SweepCell, _digests: Optional[Dict[int, str]] = None
             ) -> str:
    """Stable cache key: spec config + world/trace content + code version.

    The key is a SHA-256 over (a) ``repr(spec)`` — every static knob of the
    method, (b) the *content* of the world and ``trace`` (dtype, shape,
    bytes — not object identity, so deterministically regenerated worlds hit
    the cache across processes), and (c) :func:`_code_fingerprint` — git
    describe plus a hash of the engine sources, so editing the simulation
    semantics invalidates stale results even in a dirty tree.  For a
    :class:`~repro.core.page_table.DynamicMapping` world, (b) folds in the
    event stream: every epoch snapshot's ``ppn`` plus the boundary
    positions, so two worlds differing only in when (or what) they remap
    never collide.  Execution knobs (backend, block size, lane/trace
    padding) are deliberately NOT part of the key: results are bit-exact
    across all of them, so any backend may serve any cached cell.

    ``_digests`` is an id-keyed memo so sweeps that share one mapping/trace
    across many specs hash each array once (valid while the arrays are kept
    alive by the caller, as run_sweep does).
    """
    def digest(a: np.ndarray) -> str:
        if _digests is None:
            return _array_digest(a)
        d = _digests.get(id(a))
        if d is None:
            d = _digests[id(a)] = _array_digest(a)
        return d

    h = hashlib.sha256()
    h.update(repr(cell.spec).encode())
    world = cell.mapping
    if isinstance(world, ParityWorld):
        # the fault schedule is semantic content: when and which vpn flips
        # decides which entries die — then fold the wrapped base world
        # exactly as if it were the cell's mapping
        h.update(repr(("parity", tuple(world.faults))).encode())
        world = world.base
    if isinstance(world, DynamicMapping):
        h.update(repr(tuple(world.boundaries)).encode())
        for m in world.epochs:
            h.update(digest(m.ppn).encode())
    elif isinstance(world, MultiTenantMapping):
        mt = world
        # the full schedule: when, who, under which ASID — and the recycle
        # flags explicitly (normally derived from the former, but the
        # constructor accepts an override, which must not collide)
        h.update(repr((tuple(mt.boundaries), tuple(mt.tenant_ids),
                       tuple(mt.asids), tuple(mt.recycled))).encode())
        for m in mt.tenants:
            h.update(digest(m.ppn).encode())
    elif isinstance(world, NestedMapping):
        nm = world
        # both levels fold in: the VM schedule, every guest's event stream
        # AND the host's — two worlds differing only in a host-side remap
        # (which guests never observe directly) must never collide
        h.update(repr((tuple(nm.boundaries), tuple(nm.guest_ids),
                       tuple(nm.asids), tuple(nm.recycled))).encode())
        for g in nm.guests:
            h.update(repr(tuple(g.boundaries)).encode())
            for m in g.epochs:
                h.update(digest(m.ppn).encode())
        h.update(repr(tuple(nm.host.boundaries)).encode())
        for m in nm.host.epochs:
            h.update(digest(m.ppn).encode())
    else:
        h.update(digest(world.ppn).encode())
    h.update(digest(cell.trace).encode())
    h.update(_code_fingerprint().encode())
    return h.hexdigest()[:32]


_COUNTER_FIELDS = ("accesses", "l1_hits", "l2_regular_hits",
                   "l2_coalesced_hits", "walks", "aligned_probes",
                   "pred_correct", "cycles", "shootdowns")


def _cache_load(path: str) -> Tuple[Optional[SimResult], bool]:
    """Load one cache entry: ``(result, corrupt)``.

    A *missing* entry is the normal cold-cache case — ``(None, False)``.
    An entry that exists but fails to parse (truncated write, bit rot,
    wrong schema from an older layout) is CORRUPT — ``(None, True)`` — and
    the caller must quarantine it and surface the count: silently
    recomputing would hide an integrity problem in the cache directory.
    """
    if not os.path.exists(path):
        return None, False
    try:
        with np.load(path, allow_pickle=False) as z:
            counters = z["counters"]
            return SimResult(
                name=str(z["name"]),
                **{f: int(counters[i]) for i, f in enumerate(_COUNTER_FIELDS)},
                coverage_mean=float(z["coverage_mean"]),
                ppn=z["ppn"],
            ), False
    except (OSError, KeyError, ValueError, IndexError, EOFError,
            zipfile.BadZipFile):
        return None, True


def _quarantine_cache_entry(path: str) -> None:
    """Move a corrupt entry aside (never delete: keep it inspectable)."""
    try:
        os.replace(path, path + ".quarantined")
    except OSError:
        pass                         # raced away or unwritable: recompute


def _cache_store(path: str, r: SimResult) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}.npz"
    np.savez_compressed(
        tmp, name=np.str_(r.name),
        counters=np.array([getattr(r, f) for f in _COUNTER_FIELDS], np.int64),
        coverage_mean=np.float64(r.coverage_mean), ppn=r.ppn)
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------

DEFAULT_CACHE_DIR = os.path.join("results", "sweep_cache")

#: Chaos hook: :mod:`repro.robustness.faults` installs a callable here to
#: inject deterministic backend compile/runtime failures —
#: ``hook(cells, backend)`` raising makes the batch fail exactly as a real
#: backend fault would, upstream of any recovery.  ``None`` in production.
_BACKEND_FAULT_HOOK = None


def _oracle_result(cell: SweepCell) -> SimResult:
    """Pure-python oracle for one cell — the last-resort executor a failing
    lane is bisected down to (bit-exact with the batched backends by the
    parity suite, so recovery never changes results)."""
    from .simulator import (run_method_dynamic, run_method_multitenant,
                            run_method_nested, run_method_parity)
    w = cell.mapping
    if isinstance(w, ParityWorld):
        return run_method_parity(cell.spec, w, cell.trace)
    if isinstance(w, NestedMapping):
        return run_method_nested(cell.spec, w, cell.trace)
    if isinstance(w, MultiTenantMapping):
        return run_method_multitenant(cell.spec, w, cell.trace)
    return run_method_dynamic(cell.spec, w, cell.trace)


def _run_batch(sub: List[SweepCell], backend: str, tb: int
               ) -> List[SimResult]:
    """Pack and simulate one batch; per-cell results in ``sub`` order.

    Span ``repro.sweep.batch``, with the real trace steps ``steps_real``
    (summed over lanes), the steps the scan runs ``steps_scanned`` (lanes
    x the trace bucket) and ``bytes_uploaded`` (lanes, stacks and initial
    state); inside it ``repro.sweep.pack`` around packing and the initial
    state."""
    with span("repro.sweep.batch") as batch:
        if _BACKEND_FAULT_HOOK is not None:
            _BACKEND_FAULT_HOOK(sub, backend)
        with span("repro.sweep.pack"):
            lanes, stacks, (L, max_sets, max_ways), seg_bounds = _pack_lanes(
                sub, device_count=jax.local_device_count())
            st0 = _init_batched_state(
                L, max_sets, max_ways, lanes["pred0"], lanes["asid0"],
                with_ctlb=any(c.spec.kind == "cache-tlb" for c in sub),
                with_dp=any(c.spec.kind == "dead-protect" for c in sub))
        batch.set(steps_real=int(lanes["t_real"].sum()),
                  steps_scanned=L * stacks["trace"].shape[1],
                  bytes_uploaded=sum(a.nbytes for d in (lanes, stacks, st0)
                                     for a in d.values()))
        stF, ppns = _simulate_lanes(lanes, stacks, st0, seg_bounds,
                                    backend=backend, tb=tb)
    counters = np.asarray(stF["counters"])
    cov_samples = np.asarray(stF["cov_samples"])
    out = []
    for j, c in enumerate(sub):
        t_real = c.trace.shape[0]
        cnt = counters[j]
        out.append(SimResult(
            name=c.spec.name, accesses=t_real,
            l1_hits=int(cnt[C_L1]),
            l2_regular_hits=int(cnt[C_REG]),
            l2_coalesced_hits=int(cnt[C_COAL]),
            walks=int(cnt[C_WALK]),
            aligned_probes=int(cnt[C_PROBE]),
            pred_correct=int(cnt[C_PRED]),
            cycles=int(cnt[C_CYC]),
            coverage_mean=float(np.mean(cov_samples[j])),
            ppn=ppns[j, :t_real],
            shootdowns=int(cnt[C_SHOOT]),
        ))
    return out


def _run_batch_resilient(sub: List[SweepCell], backend: str, tb: int,
                         fstats: Dict[str, int]) -> List[SimResult]:
    """One batch with the recovery ladder: backend → xla fallback →
    bisection → per-cell oracle.

    A failing Pallas compile/run retries the WHOLE batch on the XLA
    backend first (bit-exact by construction, so the fallback result is
    identical).  A batch that still fails is bisected so one poisoned
    lane cannot take its batchmates down; a single cell that fails every
    backend is handed to the pure-python oracle.  Only the oracle itself
    raising propagates — the run then fails loudly rather than returning
    partial results.  Recovery counts surface in ``fstats``.
    """
    try:
        return _run_batch(sub, backend, tb)
    except Exception:
        if backend == "pallas":
            fstats["backend_fallbacks"] += 1
            try:
                return _run_batch(sub, "xla", tb)
            except Exception:
                pass
        if len(sub) == 1:
            fstats["oracle_fallbacks"] += 1
            return [_oracle_result(sub[0])]
        fstats["bisections"] += 1
        mid = len(sub) // 2
        return (_run_batch_resilient(sub[:mid], backend, tb, fstats)
                + _run_batch_resilient(sub[mid:], backend, tb, fstats))


def run_sweep(cells: Sequence[SweepCell], *, cache: bool = True,
              cache_dir: str = DEFAULT_CACHE_DIR,
              backend: str = "auto",
              block_size: Optional[int] = None) -> SweepResult:
    """Simulate every cell, batched into one compiled time-blocked program.

    Results are bit-identical to per-cell :func:`run_method` /
    :func:`run_method_dynamic` calls (enforced by ``tests/test_sweep.py``
    and ``tests/test_backends.py``) regardless of ``backend`` and
    ``block_size``.  With ``cache`` enabled, previously simulated cells
    (same spec, mapping/trace *content* and code version — see
    :func:`cell_key`) are loaded from ``cache_dir`` and skipped; set the
    ``REPRO_SWEEP_NO_CACHE`` env var or ``cache=False`` to bypass.

    * ``backend`` — ``'auto'`` (= ``'xla'``), ``'xla'`` (time-blocked
      vmapped scan), or ``'pallas'`` (the :mod:`repro.kernels.tlb_sweep`
      kernel in interpret mode; refused on a TPU, see
      :func:`resolve_backend`).
    * ``block_size`` — trace steps per block (default ``DEFAULT_BLOCK``,
      or the ``REPRO_SWEEP_BLOCK`` env var).  Execution detail only: block
      boundaries never change results.

    Usage — compare two methods on a workload-derived scenario::

        from repro.core.baselines import base_spec, kaligned_for_mapping
        from repro.core.sweep import SweepCell, run_sweep
        from repro.scenarios import get_scenario

        d = get_scenario("kv-churn").materialize(n_pages=1 << 15,
                                                 trace_len=100_000)
        specs = [base_spec(), kaligned_for_mapping(d.mapping, psi=3)]
        sweep = run_sweep([SweepCell(s, d.mapping, d.trace) for s in specs])
        for r in sweep:                      # SimResult per cell, in order
            print(r.name, r.misses, r.cpi)
        print(sweep.stats)                   # n_cells / cache_hits / ...

    Lanes are padded onto one array layout (max L2 geometry of the batch,
    inert ``K=-1`` alignment slots, power-of-two lane/trace shape buckets),
    so heterogeneous specs, footprints and trace lengths all reuse one
    compiled executable per shape bucket — see
    :mod:`repro.core.lane_program` for the padding rules.  Batches mixing
    static and dynamic worlds are partitioned so purely-static cells never
    execute the epoch-segmented machinery.

    Each packed batch is a span ``repro.sweep.batch`` (see
    :func:`_run_batch`).
    """
    backend = resolve_backend(backend)
    tb = _block_size(block_size)
    cache = cache and not os.environ.get("REPRO_SWEEP_NO_CACHE")
    cells = list(cells)
    results: List[Optional[SimResult]] = [None] * len(cells)
    todo: List[int] = []
    hits = 0
    digests: Dict[int, str] = {}   # id-keyed; cells keep the arrays alive
    keys = [cell_key(c, digests) if cache else "" for c in cells]
    fstats = dict(cache_quarantined=0, backend_fallbacks=0,
                  bisections=0, oracle_fallbacks=0)
    for i, c in enumerate(cells):
        if cache:
            path = os.path.join(cache_dir, keys[i] + ".npz")
            r, corrupt = _cache_load(path)
            if corrupt:
                _quarantine_cache_entry(path)
                fstats["cache_quarantined"] += 1
            if r is not None:
                results[i] = r
                hits += 1
                continue
        todo.append(i)

    # Partition: static cells never ride a multi-segment timeline installed
    # by segmented (dynamic/multi-tenant) cells sharing the sweep (and vice
    # versa the segmented batch stays small).  Groups larger than the
    # lane-sharing bucket are chunked at its size, so a 5-row and an 8-row
    # suite execute the SAME compiled programs instead of specializing on
    # their exact lane counts.  Each chunk is one packed batch.
    groups = [[i for i in todo if not cells[i].is_segmented],
              [i for i in todo if cells[i].is_segmented]]
    batches = [g[k: k + LANE_SHARE_MAX]
               for g in groups if g
               for k in range(0, len(g), LANE_SHARE_MAX)]
    for group in batches:
        sub = [cells[i] for i in group]
        for j, r in enumerate(_run_batch_resilient(sub, backend, tb, fstats)):
            i = group[j]
            results[i] = r
            if cache:
                _cache_store(os.path.join(cache_dir, keys[i] + ".npz"), r)

    tb_eff = tb
    if backend == "pallas":
        # the kernel caps its own block size (its body is unrolled); report
        # what actually ran, not what was requested
        from ..kernels.tlb_sweep.ops import effective_block
        tb_eff = effective_block(tb)
    stats = dict(n_cells=len(cells), cache_hits=hits,
                 simulated=len(todo), n_batches=len(batches),
                 backend=backend, block=tb_eff, **fstats)
    return SweepResult(results=results, stats=stats)  # type: ignore[arg-type]
