"""TPU-adaptation benchmarks: DMA-descriptor model for the coalesced kernel.

The paper's metric is TLB misses; the TPU analogue is HBM DMA descriptors
issued per decode step.  We measure (a) descriptor-count reduction as a
function of pool fragmentation, (b) the modeled decode-attention memory time
t = bytes/BW + n_desc * t_issue (v5e: 819 GB/s, ~1 µs effective per
descriptor chain on the sparse-core/DMA path), and (c) the serving engine's
end-to-end descriptor metrics with Algorithm-3-chosen K.

(b) is a cost model, not a wall-clock measurement — this container has no
TPU.  Kernel correctness is interpret-mode-validated in tests/test_kernels.
"""
from __future__ import annotations

import time

import numpy as np

from repro.kvcache.allocator import PagedKVAllocator
from repro.kvcache.block_table import choose_kernel_classes, dma_descriptor_count

HBM_BW = 819e9
T_DESC = 1e-6          # effective per-descriptor issue cost (conservative)


def _fragmented_tables(frag: float, B: int, pages_per_seq: int,
                       n_pages: int, seed: int = 0):
    """Pool with tunable fragmentation.

    Fill the pool with single-page holders, then free ~60% of it: a
    ``1-frag`` share as aligned 64-page runs (buddy-coalescible → large
    contiguity) and a ``frag`` share as every-other singles whose buddies
    stay in use (the paper's §2 fragmentation mechanism).  New sequences
    then allocate from whatever contiguity survives.
    """
    rng = np.random.default_rng(seed)
    alloc = PagedKVAllocator(n_pages)
    for i in range(n_pages):
        alloc.allocate(20_000 + i, 1)
    n_free = int(0.6 * n_pages)
    freed = 0
    run = 64
    # contiguous component at two scales (64-page and 16-page runs), so the
    # surviving contiguity is MIXED — the regime Algorithm 3 targets
    n64 = int((1 - frag) * n_free / 2 / 64)
    n16 = int((1 - frag) * n_free / 2 / 16)
    if n64 + n16:
        starts = rng.choice(n_pages // run, size=min(n64 + n16,
                                                     n_pages // run),
                            replace=False) * run
        for idx, s in enumerate(starts):
            span = 64 if idx < n64 else 16
            for j in range(span):
                alloc.free(20_000 + s + j)
            freed += span
    i = 0
    while freed < n_free and i < n_pages:
        rid = 20_000 + i
        if rid in alloc.seqs:
            alloc.free(rid)
            freed += 1
        i += 2
    tables = []
    for b in range(B):
        if alloc.allocate(b, pages_per_seq) is None:
            break
        tables.append(alloc.block_table(b, pages_per_seq))
    return np.stack(tables) if tables else np.zeros((0, 1), np.int64), alloc


def bench_dma_vs_fragmentation(B=24, pages_per_seq=64, page_size=64,
                               kv_bytes_per_page=64 * 8 * 128 * 2 * 2):
    """Descriptor reduction and modeled decode memory time vs fragmentation."""
    rows = []
    for frag in (0.0, 0.25, 0.5, 0.75, 1.0):
        bt, alloc = _fragmented_tables(frag, B, pages_per_seq, 4096,
                                       seed=int(frag * 10))
        if bt.shape[0] == 0:
            continue
        hist = alloc.contiguity_histogram()
        K = choose_kernel_classes(hist, psi=3)
        st = dma_descriptor_count(bt, K)
        bytes_total = st["pages"] * kv_bytes_per_page
        t_base = bytes_total / HBM_BW + st["descriptors_page_granular"] * T_DESC
        t_coal = bytes_total / HBM_BW + st["descriptors_coalesced"] * T_DESC
        rows.append({
            "fragmentation": frag, "K": str(K),
            "pages": st["pages"],
            "desc_base": st["descriptors_page_granular"],
            "desc_coalesced": st["descriptors_coalesced"],
            "desc_reduction": round(st["reduction"], 4),
            "t_model_base_us": round(t_base * 1e6, 1),
            "t_model_coalesced_us": round(t_coal * 1e6, 1),
            "speedup": round(t_base / t_coal, 3),
        })
    return rows


def bench_kernel_classes_ablation(B=24, pages_per_seq=64):
    """|K| ablation on a mixed pool (paper Fig 9, kernel edition)."""
    bt, alloc = _fragmented_tables(0.75, B, pages_per_seq, 4096, seed=3)
    hist = alloc.contiguity_histogram()
    rows = []
    for psi in (1, 2, 3, 4):
        K = choose_kernel_classes(hist, psi=psi, theta=1.0)
        st = dma_descriptor_count(bt, K)
        rows.append({"psi": psi, "K": str(K),
                     "desc_reduction": round(st["reduction"], 4)})
    return rows


def bench_engine_end_to_end(quick=True):
    """Serving engine: tokens/step metrics with the real model + kernel
    (the kernel runs interpreted off-TPU, so wall_s there is the
    interpreter's, not the kernel's)."""
    from repro.configs import get_config
    from repro.models import Model, RunConfig
    from repro.serve import EngineConfig, ServingEngine

    cfg = get_config("internlm2-1.8b", reduced=True)
    rc = RunConfig(attn_q_chunk=32, attn_kv_chunk=32, scan_chunk=16)
    model = Model(cfg, rc)
    params = model.init(0)
    rows = []
    for policy in ("buddy_best", "page"):
        ec = EngineConfig(page_size=8, num_pages=256, max_batch=4,
                          max_seq=128, alloc_policy=policy)
        eng = ServingEngine(model, params, ec)
        rng = np.random.default_rng(0)
        for i in range(6):
            eng.add_request(list(rng.integers(0, cfg.vocab, size=24)),
                            max_new_tokens=4)
        t0 = time.time()
        m = eng.run_to_completion()
        rows.append({"alloc_policy": policy, "K": str(m["K"]),
                     "tokens": m["tokens"],
                     "desc_reduction": round(m["descriptor_reduction"], 4),
                     "wall_s": round(time.time() - t0, 1)})
    return rows
