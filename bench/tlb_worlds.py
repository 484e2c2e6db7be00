"""The sweep's traffic: paper-benchmark analogues and the compared roster.

Copied from the program so that the yardstick cannot move with it: the
access-pattern generators of ``src/repro/core/traces.py``, the demand-paged
buddy mapping of ``src/repro/core/mappings.py``, Algorithm 3 with Table 1
(``src/repro/core/determine_k.py``) and the roster of
``benchmarks/tlb_suite.py``'s ``_add_suite`` (Base, THP, RMM, COLT,
Cluster, Anchor-Static at each distance of the grid, |K|=psi Aligned).
A mapping is a vpn -> ppn array (-1 where unmapped); a trace is an array
of vpns.  Everything is drawn from a seed.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

UNMAPPED = -1


# ---------------------------------------------------------------------------
# access patterns
# ---------------------------------------------------------------------------

def _seq(n_pages, length, rng):
    starts = rng.integers(0, n_pages, size=max(1, length // 4096))
    out = (np.arange(length) % 4096)[None, :]
    segs = (starts[:, None] + out) % n_pages
    return segs.reshape(-1)[:length]


def _strided(n_pages, length, rng, stride=7, streams=4):
    base = rng.integers(0, n_pages, size=streams)
    idx = np.arange(length)
    return (base[idx % streams] + (idx // streams) * stride) % n_pages


def _random(n_pages, length, rng):
    return rng.integers(0, n_pages, size=length)


def _zipf(n_pages, length, rng, a=1.2):
    raw = np.minimum(rng.zipf(a, size=length) - 1, n_pages - 1)
    return rng.permutation(n_pages)[raw]


def _bfs(n_pages, length, rng, hood=64, p_jump=0.05):
    jumps = rng.random(length) < p_jump
    targets = rng.integers(0, n_pages, size=length)
    offs = rng.integers(-hood, hood + 1, size=length)
    cur = int(rng.integers(0, n_pages))
    if jumps.any():
        centres = targets[np.searchsorted(np.flatnonzero(jumps),
                                          np.arange(length),
                                          side="right") - 1]
    else:
        centres = np.full(length, cur)
    centres[:int(np.argmax(jumps))] = cur
    return ((centres + offs) % n_pages).astype(np.int64)


def _blocked(n_pages, length, rng, block=256, dwell=2048):
    n_blocks = max(1, -(-length // dwell))
    bases = rng.integers(0, max(1, n_pages - block), size=n_blocks)
    within = rng.integers(0, block, size=length)
    return (np.repeat(bases, dwell)[:length] + within) % n_pages


def _multiscale(n_pages, length, rng, seg=2000, min_region=256):
    n_seg = max(1, length // seg)
    lo, hi = np.log2(min_region), np.log2(max(n_pages, min_region + 1))
    sizes = np.minimum((2.0 ** rng.uniform(lo, hi, size=n_seg))
                       .astype(np.int64), n_pages)
    bases = (rng.random(n_seg) * np.maximum(n_pages - sizes, 1)
             ).astype(np.int64)
    offs = rng.random(length)
    seg_idx = np.minimum(np.arange(length) // seg, n_seg - 1)
    return bases[seg_idx] + (offs * sizes[seg_idx]).astype(np.int64)


def _mixed_phase(n_pages, length, rng):
    gens = [_seq, _strided, _random, _zipf, _blocked]
    per = length // len(gens)
    out = np.concatenate([g(n_pages, per, rng) for g in gens])
    if out.shape[0] < length:
        out = np.concatenate([out, _seq(n_pages, length - out.shape[0],
                                        rng)])
    return out[:length]


PATTERNS = {"sequential": _seq, "strided": _strided, "random": _random,
            "zipf": _zipf, "bfs": _bfs, "blocked": _blocked,
            "multiscale": _multiscale, "mixed_phase": _mixed_phase}


def trace(pattern: str, ppn: np.ndarray, length: int, rng) -> np.ndarray:
    """A vpn trace of ``pattern`` over the mapped pages of ``ppn``."""
    mv = np.flatnonzero(ppn >= 0).astype(np.int64)
    idx = PATTERNS[pattern](mv.shape[0], length, rng)
    return mv[np.asarray(idx, np.int64) % mv.shape[0]]


# ---------------------------------------------------------------------------
# demand-paged mapping from a churned buddy allocator
# ---------------------------------------------------------------------------

class _Buddy:
    def __init__(self, n_frames: int, max_order: int):
        self.max_order = max_order
        block = 1 << max_order
        n_frames = (n_frames // block) * block
        self.free: List[set] = [set() for _ in range(max_order + 1)]
        for base in range(0, n_frames, block):
            self.free[max_order].add(base)

    def alloc(self, order: int) -> Optional[int]:
        for o in range(order, self.max_order + 1):
            if self.free[o]:
                base = min(self.free[o])
                self.free[o].discard(base)
                while o > order:
                    o -= 1
                    self.free[o].add(base + (1 << o))
                return base
        return None

    def free_block(self, base: int, order: int) -> None:
        while order < self.max_order:
            buddy = base ^ (1 << order)
            if buddy not in self.free[order]:
                break
            self.free[order].discard(buddy)
            base = min(base, buddy)
            order += 1
        self.free[order].add(base)


def demand_mapping(n_pages: int, rng, churn: float = 0.3) -> np.ndarray:
    """A process's pages faulted in left to right over a buddy allocator
    that other allocations have churned; each extent sits at its
    order-aligned VA boundary."""
    buddy = _Buddy(4 * n_pages, max_order=11)
    held: List[Tuple[int, int]] = []
    for _ in range(int(churn * n_pages / 8)):
        order = int(rng.choice([0, 1, 2, 3], p=[0.5, 0.25, 0.15, 0.1]))
        base = buddy.alloc(order)
        if base is not None:
            held.append((base, order))
    rng.shuffle(held)
    for base, order in held[: len(held) // 2]:
        buddy.free_block(base, order)
    blocks: List[Tuple[int, int]] = []
    mapped = 0
    while mapped < n_pages:
        want = n_pages - mapped
        order = int(rng.integers(0, min(int(np.log2(max(want, 1))), 11) + 1))
        base = None
        while base is None and order >= 0:
            base = buddy.alloc(order)
            if base is None:
                order -= 1
        if base is None:
            raise RuntimeError("buddy allocator exhausted")
        n = min(1 << order, want)
        blocks.append((base, n))
        mapped += n
    vp = 0
    spans = []
    for base, n in blocks:
        a = 1 << int(np.ceil(np.log2(n))) if n > 1 else 1
        vp = (vp + a - 1) & ~(a - 1)
        spans.append((vp, base, n))
        vp += n
    ppn = np.full(vp, UNMAPPED, dtype=np.int64)
    for v, base, n in spans:
        ppn[v:v + n] = base + np.arange(n)
    return ppn


# ---------------------------------------------------------------------------
# Algorithm 3 (paper section 3.3) and the roster
# ---------------------------------------------------------------------------

# Table 1: contiguity-chunk size range -> alignment k
SIZE_RANGE_TABLE = ((2, 16, 4), (17, 64, 6), (65, 128, 7), (129, 256, 8),
                    (257, 512, 9), (513, 1024, 10), (1025, 1 << 62, 11))


def contiguity_histogram(ppn: np.ndarray) -> Dict[int, int]:
    """Chunk size -> count over the maximal VA-and-PA-contiguous runs."""
    mapped = ppn != UNMAPPED
    cont = np.zeros(ppn.shape[0], bool)
    cont[1:] = mapped[1:] & mapped[:-1] & (ppn[1:] == ppn[:-1] + 1)
    run_id = np.cumsum(mapped & ~cont) - 1
    sizes, counts = np.unique(np.bincount(run_id[mapped]),
                              return_counts=True)
    return {int(n): int(c) for n, c in zip(sizes, counts)}


def determine_k(hist: Dict[int, int], theta: float, psi: int) -> List[int]:
    weight: Dict[int, int] = {}
    total = 0
    for size, freq in hist.items():
        if size < 2 or freq <= 0:
            continue
        total += size * freq
        k = next(k for lo, hi, k in SIZE_RANGE_TABLE if lo <= size <= hi)
        weight[k] = weight.get(k, 0) + size * freq
    K: List[int] = []
    if not total:
        return K
    covered = 0
    threshold = total * theta * (1.0 - 1e-12)
    for k, cov in sorted(weight.items(), key=lambda kv: (-kv[1], -kv[0])):
        K.append(k)
        covered += cov
        if covered >= threshold or len(K) >= psi:
            break
    return sorted(K, reverse=True)


def roster_specs(roster: List[dict], ppn: np.ndarray) -> List[dict]:
    """The method specs (as keyword dicts) of the roster over one
    mapping."""
    out = []
    for m in roster:
        kind = m["kind"]
        if kind == "base":
            out.append(dict(name="Base", kind="base"))
        elif kind == "thp":
            out.append(dict(name="THP", kind="thp"))
        elif kind == "rmm":
            out.append(dict(name="RMM", kind="rmm", side="rmm"))
        elif kind == "colt":
            out.append(dict(name="COLT", kind="colt", index_shift=3))
        elif kind == "cluster":
            out.append(dict(name="Cluster", kind="cluster", l2_sets=128,
                            l2_ways=6, side="cluster"))
        elif kind == "anchor":
            d = m["distance_bits"]
            out.append(dict(name=f"Anchor(d=2^{d})", kind="anchor", K=(d,),
                            index_shift=d))
        elif kind == "kaligned":
            K = determine_k(contiguity_histogram(ppn), theta=m["theta"],
                            psi=m["psi"]) or [4]
            K = tuple(sorted(set(K[: m["psi"]]), reverse=True))
            out.append(dict(name=f"|K|={len(K)} Aligned", kind="kaligned",
                            K=K, index_shift=max(K), use_predictor=True))
        else:
            raise ValueError(f"unknown roster kind {kind!r}")
    return out
