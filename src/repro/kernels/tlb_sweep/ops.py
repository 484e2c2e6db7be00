"""Public op: run packed sweep lanes through the Pallas TLB-sweep kernel.

:func:`run_lanes_pallas` has the same contract as the XLA backend's
``_simulate_lanes`` path in :mod:`repro.core.sweep`: it takes the packed
``(lanes, stacks, st0, seg_bounds)`` produced by
:func:`repro.core.lane_program.pack_lanes` plus the block size, and returns
``(final_state, ppns)`` where ``final_state`` carries the per-lane
``counters`` and ``cov_samples`` and ``ppns`` is the ``[L, T]`` translated
PPN array in trace order.  Results are bit-exact vs the XLA backend and the
pure-python oracles for every block size (``tests/test_backends.py``).

Host-side work here mirrors what the serving scheduler does for
``paged_attention``: build the static block plan, pre-gather each trace
into its padded block timeline, and pack the per-lane scalars — the kernel
then only streams blocks and records.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np

from ...core.lane_program import build_block_plan, needs_switch_pass
from .. import default_interpret
from .tlb_sweep import N_PARAM_FIELDS, PARAM_KEYS, make_tlb_sweep_call

_CALL_CACHE: Dict[Tuple[int, ...], object] = {}

# The kernel unrolls the intra-block dependency chain in its body, so its
# compile time scales with the block size; beyond ~8 steps the bigger body
# buys nothing (the HBM round-trip is already gone — state lives in
# scratch).  Blocking is an execution detail (results are bit-exact for
# every size), so the kernel caps its own block rather than inheriting the
# XLA backend's larger default.
MAX_KERNEL_BLOCK = 8


def effective_block(tb: int) -> int:
    """The block size the kernel actually runs for a requested ``tb`` —
    the single place the capping rule lives (``run_sweep`` reports it in
    its stats)."""
    return min(tb, MAX_KERNEL_BLOCK)


def pack_params(lanes: Dict[str, np.ndarray]) -> np.ndarray:
    """[L, N_PARAM_FIELDS] int32 per-lane scalar block for the kernel."""
    cols = [np.asarray(lanes[k], np.int32) for k in PARAM_KEYS]
    params = np.stack(cols, axis=1)
    assert params.shape[1] == N_PARAM_FIELDS
    return params


def run_lanes_pallas(lanes, stacks, st0, seg_bounds, tb: int,
                     interpret: Optional[bool] = None):
    """Simulate one packed batch with the Pallas kernel.

    ``interpret`` defaults to :func:`repro.kernels.default_interpret`;
    ``st0`` fixes the padded L2 geometry (state itself is initialized
    in-kernel, in scratch).
    """
    if interpret is None:
        interpret = default_interpret()
    tb = effective_block(tb)
    lanes = {k: np.asarray(v) for k, v in lanes.items()}
    stacks = {k: np.asarray(v) for k, v in stacks.items()}
    plan = build_block_plan(tuple(seg_bounds), tb)

    trace = stacks["trace"]
    T = trace.shape[1]
    # pre-gather each trace into the padded block timeline (blocks never
    # straddle an epoch segment; padded slots are masked in-kernel)
    trace_pad = np.ascontiguousarray(
        trace[:, np.clip(plan.tpos, 0, T - 1)], dtype=np.int32)

    sets, ways = np.asarray(st0["l2"]).shape[1:3]
    # cache-backed-tier / dead-entry-table geometry rides along from the
    # batched init (degenerate 1s when no lane uses them)
    ctlb_sets, ctlb_ways = np.asarray(st0["ctlb"]).shape[1:3]
    dp_n = np.asarray(st0["dp"]).shape[1]
    geo = (sets, ways, ctlb_sets, ctlb_ways, dp_n)
    call = _CALL_CACHE.get(geo)
    if call is None:
        call = _CALL_CACHE[geo] = make_tlb_sweep_call(*geo)

    i32 = lambda a: np.asarray(a, np.int32)  # noqa: E731
    ppn_pad, counters, cov = call(
        i32(lanes["trace_id"]), i32(lanes["seg_map"]),
        i32(lanes["seg_fill"]), i32(lanes["seg_clus"]),
        i32(lanes["seg_dirty"]), i32(plan.blk_seg), i32(plan.blk_shoot),
        i32(plan.blk_hi),
        pack_params(lanes), i32(lanes["kvals"]), i32(lanes["seg_shoot"]),
        i32(lanes["seg_asid"]), i32(lanes["seg_switch"]),
        i32(lanes["seg_fall"]), i32(lanes["seg_fasid"]),
        trace_pad, i32(plan.tpos),
        i32(stacks["maps"]), i32(stacks["fills"]), i32(stacks["clus"]),
        i32(stacks["dirty"]),
        tb=tb, n_blocks=plan.n_blocks, interpret=bool(interpret),
        with_switch=needs_switch_pass(lanes))

    ppns = np.asarray(jax.device_get(ppn_pad))[:, plan.slot_of_t]
    stF = dict(counters=np.asarray(jax.device_get(counters)),
               cov_samples=np.asarray(jax.device_get(cov)))
    return stF, ppns
