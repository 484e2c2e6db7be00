"""Plain reference of the simulated TLB hierarchy (paper section 4).

A copy of the program's per-call oracle (``run_method`` and the
``_simulate`` scan of ``src/repro/core/simulator.py``, with the page-table
helpers it reads from ``src/repro/core/page_table.py``), kept here so the
comparison that decides ``correct`` cannot move with the program.  It
covers the paper's roster on static mappings: Base, THP, COLT, Cluster,
RMM, Anchor and K-bit Aligned.  It imports nothing of the program and
runs one cell at a time, on whatever device JAX is told to use.

TLB geometry and latencies follow the paper's Table 2: a 64-entry 4-way
L1 (plus a 32-entry 4-way 2MB array for THP), a 1024-entry 8-way L2, a
32-entry range TLB for RMM, a 320-entry 5-way clustered TLB beside a
768-entry L2 for Cluster; an L2 regular hit costs 7 cycles, a coalesced
hit 8 (+7 per extra aligned probe), a walk 50 after the failed lookups.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

REGULAR = -1
HUGE = 9            # k-class used for 2MB entries (2^9 pages)
INVALID = -2
NEG = -(2 ** 30)
UNMAPPED = -1

LAT_L2_REG = 7
LAT_COAL = 8
LAT_EXTRA_PROBE = 7
LAT_WALK = 50

N_COV_SAMPLES = 64

L1_SETS, L1_WAYS = 16, 4       # 64-entry 4-way
L1H_SETS, L1H_WAYS = 8, 4      # 32-entry 4-way 2MB array
RMM_ENTRIES = 32
CLUS_SETS, CLUS_WAYS = 64, 5   # 320-entry 5-way clustered TLB

KINDS = ("base", "thp", "colt", "cluster", "rmm", "anchor", "kaligned")

#: the counters a cell reports, as the program's ``SimResult`` names them
FIELDS = ("accesses", "l1_hits", "l2_regular_hits", "l2_coalesced_hits",
          "walks", "aligned_probes", "pred_correct", "cycles",
          "coverage_mean")


@dataclasses.dataclass(frozen=True)
class MethodSpec:
    """One compared method's static configuration."""

    name: str
    kind: str                      # one of KINDS
    K: Tuple[int, ...] = ()        # alignment classes, descending
    l2_sets: int = 128
    l2_ways: int = 8
    index_shift: int = 0           # k_hat of the paper's Fig 7
    use_predictor: bool = False
    side: Optional[str] = None     # None | "rmm" | "cluster"

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown method kind {self.kind!r}")
        if tuple(sorted(self.K, reverse=True)) != tuple(self.K):
            raise ValueError(f"K must be descending: {self.K}")


def miss_chain_cycles(spec: MethodSpec) -> int:
    """Cycles burned on the failed lookup chain before a walk."""
    if spec.K and spec.kind in ("kaligned", "anchor"):
        return LAT_COAL + LAT_EXTRA_PROBE * (len(spec.K) - 1)
    if spec.kind == "colt" or spec.side is not None:
        return LAT_COAL
    return LAT_L2_REG


def _full(shape, val):
    return jnp.full(shape, val, dtype=jnp.int32)


def _init_state(spec: MethodSpec):
    st = dict(
        t=jnp.int32(0),
        l1_tags=_full((L1_SETS, L1_WAYS), -1),
        l1_ppn=_full((L1_SETS, L1_WAYS), -1),
        l1_lru=_full((L1_SETS, L1_WAYS), 0),
        l2_tags=_full((spec.l2_sets, spec.l2_ways), -1),
        l2_k=_full((spec.l2_sets, spec.l2_ways), INVALID),
        l2_contig=_full((spec.l2_sets, spec.l2_ways), 0),
        l2_ppn=_full((spec.l2_sets, spec.l2_ways), -1),
        l2_lru=_full((spec.l2_sets, spec.l2_ways), 0),
        pred=jnp.int32(spec.K[0] if spec.K else 0),
        l1_hits=jnp.int32(0), reg_hits=jnp.int32(0), coal_hits=jnp.int32(0),
        walks=jnp.int32(0), probes=jnp.int32(0), pred_correct=jnp.int32(0),
        cycles=jnp.int32(0), cov=jnp.int32(0),
        cov_samples=_full((N_COV_SAMPLES,), 0),
    )
    if spec.kind == "thp":
        st.update(l1h_tags=_full((L1H_SETS, L1H_WAYS), -1),
                  l1h_ppn=_full((L1H_SETS, L1H_WAYS), -1),
                  l1h_lru=_full((L1H_SETS, L1H_WAYS), 0))
    if spec.side == "rmm":
        st.update(rmm_start=_full((RMM_ENTRIES,), -1),
                  rmm_len=_full((RMM_ENTRIES,), 0),
                  rmm_ppn=_full((RMM_ENTRIES,), -1),
                  rmm_lru=_full((RMM_ENTRIES,), 0))
    if spec.side == "cluster":
        st.update(cl_tags=_full((CLUS_SETS, CLUS_WAYS), -1),
                  cl_bm=_full((CLUS_SETS, CLUS_WAYS), 0),
                  cl_lru=_full((CLUS_SETS, CLUS_WAYS), 0))
    return st


def _cond_set(arr, idx, value, pred):
    """In-place conditional point write: arr[idx] = pred ? value : arr[idx]."""
    old = arr[idx]
    return arr.at[idx].set(jnp.where(pred, value, old))


@functools.partial(jax.jit, static_argnums=(0,))
def _simulate(spec: MethodSpec, ppn_map, run_start, run_len, huge_ok,
              clus_bm, trace):
    n_pages = ppn_map.shape[0]
    Ks = spec.K
    k_hat = spec.index_shift
    set_mask = jnp.int32(spec.l2_sets - 1)
    T = trace.shape[0]
    sample_every = max(T // N_COV_SAMPLES, 1)

    def contig_at(v):
        """Per-PTE contiguity field from the page table (0 = unmapped)."""
        v = jnp.clip(v, 0, n_pages - 1)
        mapped = ppn_map[v] >= 0
        return jnp.where(mapped, run_start[v] + run_len[v] - v, 0)

    def l2_set(vpn):
        return (vpn >> k_hat) & set_mask

    def probe_order(pred_k):
        """Traced list of |K| alignment values: predictor's k first, then the
        remaining K in descending order (§3.2 speculation)."""
        if not Ks:
            return []
        if not spec.use_predictor:
            return [jnp.int32(k) for k in Ks]
        kk = jnp.array(Ks, jnp.int32)
        order = [pred_k]
        not_pred = kk != pred_k
        csum = jnp.cumsum(not_pred.astype(jnp.int32))
        for pos in range(1, len(Ks)):
            sel = not_pred & (csum == pos)
            order.append(jnp.where(sel.any(), kk[jnp.argmax(sel)],
                                   jnp.int32(-1)))
        return order

    def step(st, vpn):
        t = st["t"]
        ppn_true = ppn_map[vpn]
        new = dict(st)

        # ---------------- L1 ------------------------------------------------
        s1 = vpn & jnp.int32(L1_SETS - 1)
        l1_ways_hit = st["l1_tags"][s1] == vpn
        l1_hit = l1_ways_hit.any()
        l1_way = jnp.argmax(l1_ways_hit)
        l1_ppn_val = st["l1_ppn"][s1, l1_way]
        if spec.kind == "thp":
            hv = vpn >> 9
            s1h = hv & jnp.int32(L1H_SETS - 1)
            h_ways_hit = st["l1h_tags"][s1h] == hv
            l1h_hit = h_ways_hit.any()
            l1h_way = jnp.argmax(h_ways_hit)
            l1h_ppn_val = st["l1h_ppn"][s1h, l1h_way] + (vpn & 511)
            l1_served = l1_hit | l1h_hit
            l1_out_ppn = jnp.where(l1_hit, l1_ppn_val, l1h_ppn_val)
        else:
            l1_served = l1_hit
            l1_out_ppn = l1_ppn_val

        # ---------------- L2 probes -----------------------------------------
        s2 = l2_set(vpn)
        tags = st["l2_tags"][s2]
        kcls = st["l2_k"][s2]
        contig = st["l2_contig"][s2]
        pbase = st["l2_ppn"][s2]
        valid = kcls != INVALID

        probes_used = jnp.int32(0)
        pred_ok = jnp.int32(0)
        hit_k = jnp.int32(-1)
        coal_hit = jnp.bool_(False)
        coal_ppn = jnp.int32(-1)
        coal_way = jnp.int32(0)

        if spec.kind == "colt":
            diff = vpn - tags
            cover = valid & (diff >= 0) & (diff < contig)
            l2_hit = cover.any()
            way = jnp.argmax(cover)
            reg_hit = l2_hit & (contig[way] == 1)
            coal_hit = l2_hit & (contig[way] > 1)
            l2_ppn_val = pbase[way] + (vpn - tags[way])
            touch_ways = cover
            touch_set = s2
        elif spec.kind == "thp":
            hv = vpn >> 9
            s2h = hv & set_mask
            tags_h = st["l2_tags"][s2h]
            kcls_h = st["l2_k"][s2h]
            huge_ways = (kcls_h == HUGE) & (tags_h == hv)
            reg_ways = (kcls == REGULAR) & (tags == vpn) & valid
            huge_hit = huge_ways.any()
            hw = jnp.argmax(huge_ways)
            rw = jnp.argmax(reg_ways)
            reg_hit = reg_ways.any() | huge_hit   # 2MB hit = plain L2 hit (7cyc)
            l2_hit = reg_hit
            l2_ppn_val = jnp.where(
                reg_ways.any(), pbase[rw],
                st["l2_ppn"][s2h, hw] + (vpn - (hv << 9)))
            touch_ways = jnp.where(reg_ways.any(), reg_ways, huge_ways)
            touch_set = jnp.where(reg_ways.any(), s2, s2h)
        else:
            reg_ways = (kcls == REGULAR) & (tags == vpn) & valid
            reg_hit = reg_ways.any()
            rw = jnp.argmax(reg_ways)
            first_probe_k = jnp.int32(-1)
            for pos, k_val in enumerate(probe_order(st["pred"])):
                vk = jnp.where(k_val >= 0,
                               vpn & ~((jnp.int32(1) << k_val) - 1),
                               jnp.int32(-10))
                m_ways = (kcls == k_val) & (tags == vk) & valid & \
                         (contig > (vpn - vk))
                m_hit = m_ways.any() & (k_val >= 0) & ~reg_hit & ~coal_hit
                probes_used = probes_used + jnp.where(
                    ~reg_hit & ~coal_hit & (k_val >= 0), 1, 0)
                coal_ppn = jnp.where(m_hit, pbase[jnp.argmax(m_ways)]
                                     + (vpn - vk), coal_ppn)
                coal_way = jnp.where(m_hit, jnp.argmax(m_ways), coal_way)
                hit_k = jnp.where(m_hit, k_val, hit_k)
                if pos == 0:
                    first_probe_k = k_val
                coal_hit = coal_hit | m_hit
            l2_hit = reg_hit | coal_hit
            l2_ppn_val = jnp.where(reg_hit, pbase[rw], coal_ppn)
            if spec.use_predictor:
                pred_ok = jnp.where(coal_hit & (hit_k == first_probe_k), 1, 0)
            touch_ways = jnp.zeros_like(reg_ways).at[
                jnp.where(reg_hit, rw, coal_way)].set(True)
            touch_set = s2

        # ---------------- side structures (probed with L2) ------------------
        side_hit = jnp.bool_(False)
        side_ppn = jnp.int32(-1)
        if spec.side == "rmm":
            d_r = vpn - st["rmm_start"]
            in_rng = (d_r >= 0) & (d_r < st["rmm_len"])
            side_hit = in_rng.any()
            sw = jnp.argmax(in_rng)
            side_ppn = st["rmm_ppn"][sw] + d_r[sw]
        if spec.side == "cluster":
            cwd = vpn >> 3
            sc = cwd & jnp.int32(CLUS_SETS - 1)
            crow = st["cl_tags"][sc]
            bit = (st["cl_bm"][sc] >> (vpn & 7)) & 1
            c_ways = (crow == cwd) & (bit == 1)
            side_hit = c_ways.any()
            # the clustered entry stores per-page offsets; by construction its
            # translation equals the page table's.
            side_ppn = ppn_true

        hit_any = l1_served | l2_hit | side_hit
        walk = ~hit_any

        # ---------------- latency (Table 2, §3.5) ---------------------------
        miss_chain = miss_chain_cycles(spec)
        cyc = jnp.where(
            l1_served, 0,
            jnp.where(reg_hit, LAT_L2_REG,
                      jnp.where(coal_hit,
                                LAT_COAL + LAT_EXTRA_PROBE *
                                jnp.maximum(probes_used - 1, 0),
                                jnp.where(side_hit, LAT_COAL,
                                          miss_chain + LAT_WALK))))

        # ---------------- fill selection (Algorithm 1) ----------------------
        if spec.kind in ("kaligned", "anchor"):
            fill_k = jnp.int32(REGULAR)
            fill_tag, fill_contig, fill_ppn = vpn, jnp.int32(1), ppn_true
            chosen = jnp.bool_(False)
            for k in Ks:                      # descending; first cover wins
                kk = jnp.int32(k)
                vk = vpn & ~((jnp.int32(1) << kk) - 1)
                sc_ = jnp.minimum(contig_at(vk), jnp.int32(1) << kk)
                take = (sc_ > (vpn - vk)) & ~chosen
                fill_k = jnp.where(take, kk, fill_k)
                fill_tag = jnp.where(take, vk, fill_tag)
                fill_contig = jnp.where(take, sc_, fill_contig)
                fill_ppn = jnp.where(
                    take, ppn_map[jnp.clip(vk, 0, n_pages - 1)], fill_ppn)
                chosen = chosen | take
            fill_set = s2
        elif spec.kind == "colt":
            w8 = vpn & ~jnp.int32(7)
            rs_ = run_start[vpn]
            re_ = rs_ + run_len[vpn]
            fill_tag = jnp.maximum(rs_, w8)
            fill_contig = jnp.maximum(jnp.minimum(re_, w8 + 8) - fill_tag, 1)
            fill_k = jnp.where(fill_contig > 1, jnp.int32(3),
                               jnp.int32(REGULAR))
            fill_ppn = ppn_map[jnp.clip(fill_tag, 0, n_pages - 1)]
            fill_set = s2
        elif spec.kind == "thp":
            is_huge = huge_ok[vpn]
            hv = vpn >> 9
            fill_tag = jnp.where(is_huge, hv, vpn)
            fill_k = jnp.where(is_huge, jnp.int32(HUGE), jnp.int32(REGULAR))
            fill_contig = jnp.where(is_huge, 512, 1)
            base_v = jnp.where(is_huge, hv << 9, vpn)
            fill_ppn = ppn_map[jnp.clip(base_v, 0, n_pages - 1)]
            fill_set = jnp.where(is_huge, hv & set_mask, s2)
        else:
            fill_tag, fill_contig, fill_ppn = vpn, jnp.int32(1), ppn_true
            fill_k = jnp.int32(REGULAR)
            fill_set = s2

        # ---------------- L2 fill (LRU victim) ------------------------------
        lru_row = st["l2_lru"][fill_set]
        valid_row = st["l2_k"][fill_set] != INVALID
        victim = jnp.argmin(jnp.where(valid_row, lru_row, jnp.int32(NEG)))
        evicted_contig = jnp.where(valid_row[victim],
                                   st["l2_contig"][fill_set, victim], 0)
        idx = (fill_set, victim)
        new["l2_tags"] = _cond_set(st["l2_tags"], idx, fill_tag, walk)
        new["l2_k"] = _cond_set(st["l2_k"], idx, fill_k, walk)
        new["l2_contig"] = _cond_set(st["l2_contig"], idx, fill_contig, walk)
        new["l2_ppn"] = _cond_set(st["l2_ppn"], idx, fill_ppn, walk)
        new["l2_lru"] = _cond_set(st["l2_lru"], idx, t, walk)
        cov_delta = jnp.where(walk, fill_contig - evicted_contig, 0)

        # LRU touch on the hitting way
        tw = jnp.argmax(touch_ways) if spec.kind in ("colt", "thp") else \
            jnp.argmax(touch_ways)
        new["l2_lru"] = _cond_set(new["l2_lru"], (touch_set, tw), t,
                                  l2_hit & ~walk & ~l1_served)

        # ---------------- side fills ----------------------------------------
        if spec.side == "rmm":
            victim_r = jnp.argmin(jnp.where(st["rmm_len"] > 0, st["rmm_lru"],
                                            jnp.int32(NEG)))
            ev_len = jnp.where(st["rmm_len"][victim_r] > 0,
                               st["rmm_len"][victim_r], 0)
            rs_, rl_ = run_start[vpn], run_len[vpn]
            new["rmm_start"] = _cond_set(st["rmm_start"], victim_r, rs_, walk)
            new["rmm_len"] = _cond_set(st["rmm_len"], victim_r, rl_, walk)
            new["rmm_ppn"] = _cond_set(
                st["rmm_ppn"], victim_r,
                ppn_map[jnp.clip(rs_, 0, n_pages - 1)], walk)
            lru1 = _cond_set(st["rmm_lru"], victim_r, t, walk)
            new["rmm_lru"] = _cond_set(lru1, sw if spec.side == "rmm" else 0,
                                       t, side_hit)
            cov_delta = cov_delta + jnp.where(walk, rl_ - ev_len, 0)
        if spec.side == "cluster":
            cwd = vpn >> 3
            sc = cwd & jnp.int32(CLUS_SETS - 1)
            bm = clus_bm[vpn]
            clusterable = bm != (jnp.int32(1) << (vpn & 7))
            fill_c = walk & clusterable
            vrow = st["cl_bm"][sc] != 0
            victim_c = jnp.argmin(jnp.where(vrow, st["cl_lru"][sc],
                                            jnp.int32(NEG)))
            cidx = (sc, victim_c)
            new["cl_tags"] = _cond_set(st["cl_tags"], cidx, cwd, fill_c)
            new["cl_bm"] = _cond_set(st["cl_bm"], cidx, bm, fill_c)
            lru1 = _cond_set(st["cl_lru"], cidx, t, fill_c)
            hit_cway = jnp.argmax((st["cl_tags"][sc] == cwd))
            new["cl_lru"] = _cond_set(lru1, (sc, hit_cway), t, side_hit)

        # ---------------- L1 fill --------------------------------------------
        if spec.kind == "thp":
            served_huge = huge_ok[vpn]
            hv = vpn >> 9
            s1h = hv & jnp.int32(L1H_SETS - 1)
            do1h = ~l1_served & served_huge
            vrh = st["l1h_tags"][s1h] >= 0
            vich = jnp.argmin(jnp.where(vrh, st["l1h_lru"][s1h],
                                        jnp.int32(NEG)))
            hidx = (s1h, vich)
            new["l1h_tags"] = _cond_set(st["l1h_tags"], hidx, hv, do1h)
            new["l1h_ppn"] = _cond_set(
                st["l1h_ppn"], hidx,
                ppn_map[jnp.clip(hv << 9, 0, n_pages - 1)], do1h)
            lru1 = _cond_set(st["l1h_lru"], hidx, t, do1h)
            new["l1h_lru"] = _cond_set(lru1, (s1h, l1h_way), t,
                                       l1_served & h_ways_hit.any() & ~l1_hit)
            do1 = ~l1_served & ~served_huge
        else:
            do1 = ~l1_served
        vr1 = st["l1_tags"][s1] >= 0
        vic1 = jnp.argmin(jnp.where(vr1, st["l1_lru"][s1], jnp.int32(NEG)))
        iidx = (s1, vic1)
        new["l1_tags"] = _cond_set(st["l1_tags"], iidx, vpn, do1)
        new["l1_ppn"] = _cond_set(st["l1_ppn"], iidx, ppn_true, do1)
        lru1 = _cond_set(st["l1_lru"], iidx, t, do1)
        new["l1_lru"] = _cond_set(lru1, (s1, l1_way), t, l1_hit)

        # ---------------- predictor update (§3.2) ---------------------------
        if spec.use_predictor and Ks:
            new["pred"] = jnp.where(
                coal_hit, hit_k,
                jnp.where(walk & (fill_k >= 0), fill_k, st["pred"]))

        # ---------------- accounting -----------------------------------------
        new["t"] = t + 1
        new["l1_hits"] = st["l1_hits"] + l1_served
        new["reg_hits"] = st["reg_hits"] + (reg_hit & ~l1_served)
        new["coal_hits"] = st["coal_hits"] + \
            ((coal_hit | side_hit) & ~reg_hit & ~l1_served)
        new["walks"] = st["walks"] + walk
        new["probes"] = st["probes"] + jnp.where(coal_hit & ~l1_served,
                                                 probes_used, 0)
        new["pred_correct"] = st["pred_correct"] + \
            jnp.where(~l1_served, pred_ok, 0)
        new["cycles"] = st["cycles"] + cyc
        new["cov"] = st["cov"] + cov_delta
        slot = jnp.minimum(t // sample_every, N_COV_SAMPLES - 1)
        new["cov_samples"] = _cond_set(new["cov_samples"], slot, new["cov"],
                                       t % sample_every == sample_every - 1)

        out_ppn = jnp.where(l1_served, l1_out_ppn,
                            jnp.where(l2_hit, l2_ppn_val,
                                      jnp.where(side_hit, side_ppn, ppn_true)))
        return new, out_ppn

    st0 = _init_state(spec)
    stF, ppns = jax.lax.scan(step, st0, trace)
    return stF, ppns


# ---------------------------------------------------------------------------
# page-table views the simulation reads
# ---------------------------------------------------------------------------

def compute_runs(ppn: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Contiguity chunks: for each vpn, the start and length of the maximal
    run contiguous in both VA and PA that holds it (0 length if
    unmapped)."""
    ppn = np.asarray(ppn, dtype=np.int64)
    n = ppn.shape[0]
    mapped = ppn != UNMAPPED
    cont_with_prev = np.zeros(n, dtype=bool)
    cont_with_prev[1:] = mapped[1:] & mapped[:-1] & (ppn[1:] == ppn[:-1] + 1)
    new_run = ~cont_with_prev
    run_id = np.cumsum(new_run) - 1
    starts = np.flatnonzero(new_run)
    run_start = starts[run_id]
    run_len = np.bincount(run_id)[run_id]
    run_len = np.where(mapped, run_len, 0)
    run_start = np.where(mapped, run_start, np.arange(n))
    return run_start.astype(np.int64), run_len.astype(np.int64)


def huge_page_backed(ppn, run_start, run_len) -> np.ndarray:
    """vpn lies in a 512-page window that is contiguous and whose physical
    base is 512-aligned (a promotable 2MB page)."""
    n = ppn.shape[0]
    base = np.arange(n, dtype=np.int64) & ~np.int64(511)
    ok = base + 512 <= n
    b = np.minimum(base, n - 1)
    contig_at_base = np.where(ppn[b] != UNMAPPED,
                              run_start[b] + run_len[b] - b, 0)
    aligned_pa = (ppn[b] & 511) == 0
    return ok & (contig_at_base >= 512) & aligned_pa


def cluster_bitmap(ppn, cluster_bits: int = 3) -> np.ndarray:
    """Bit j of a vpn's entry: page j of its 8-page virtual window maps
    into the same aligned physical cluster as the vpn itself."""
    n = ppn.shape[0]
    w = 1 << cluster_bits
    pad = (-n) % w
    padded = np.concatenate([ppn, np.full(pad, UNMAPPED, np.int64)])
    win = padded.reshape(-1, w)
    pclus = np.where(win != UNMAPPED, win >> cluster_bits, -2)
    same = pclus[:, :, None] == pclus[:, None, :]
    bits = (same & (pclus[:, None, :] >= 0)) << np.arange(w)[None, None, :]
    bm = bits.sum(axis=2).astype(np.int64).reshape(-1)[:n]
    return np.where(ppn != UNMAPPED, bm, 0)


def run(spec: MethodSpec, ppn: np.ndarray, trace: np.ndarray) -> dict:
    """Simulate ``spec`` over the mapping ``ppn`` (vpn -> ppn, -1 where
    unmapped) and the vpn ``trace``: every counter in ``FIELDS`` and the
    translated PPN of each access."""
    ppn = np.asarray(ppn, np.int64)
    rs, rl = compute_runs(ppn)
    huge = (jnp.asarray(huge_page_backed(ppn, rs, rl)) if spec.kind == "thp"
            else jnp.zeros((1,), bool))
    cbm = (jnp.asarray(cluster_bitmap(ppn), jnp.int32)
           if spec.side == "cluster" else jnp.zeros((1,), jnp.int32))
    tr = jnp.asarray(trace, jnp.int32)
    stF, ppns = _simulate(spec, jnp.asarray(ppn, jnp.int32),
                          jnp.asarray(rs, jnp.int32),
                          jnp.asarray(rl, jnp.int32), huge, cbm, tr)
    stF = jax.device_get(stF)
    return dict(
        accesses=int(tr.shape[0]), l1_hits=int(stF["l1_hits"]),
        l2_regular_hits=int(stF["reg_hits"]),
        l2_coalesced_hits=int(stF["coal_hits"]), walks=int(stF["walks"]),
        aligned_probes=int(stF["probes"]),
        pred_correct=int(stF["pred_correct"]), cycles=int(stF["cycles"]),
        coverage_mean=float(np.mean(np.asarray(stF["cov_samples"]))),
        ppn=np.asarray(jax.device_get(ppns)))
