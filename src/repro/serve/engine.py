"""Serving engine: continuous batching over a coalesced paged KV cache.

This is where the paper's pieces meet end-to-end:

* the buddy :class:`PagedKVAllocator` produces mixed-contiguity block tables
  under admission/finish churn (the OS of §2);
* Algorithm 3 (``choose_kernel_classes``) picks the kernel classes K from the
  allocator's live contiguity histogram, re-evaluated when fragmentation
  drifts (the paper re-runs it every 5B instructions; we use a utilization
  delta trigger);
* each decode step runs the coalesced paged-attention kernel; descriptor
  tables are rebuilt only for sequences whose block tables changed
  (the paper's "aligned entries are filled by the OS after the walk");
* scheduler: FCFS admission with KV-capacity admission control, preempt-and-
  requeue on pool exhaustion (vLLM-style), per-step DMA-descriptor metrics
  (the TPU analogue of TLB-miss counts).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import default_interpret
from ..kernels.paged_attention.ops import build_descriptors, dma_stats
from ..kernels.paged_attention.paged_attention import (kv_pool_shape,
                                                       to_pool_layout)
from ..kvcache.allocator import PagedKVAllocator
from ..kvcache.block_table import choose_kernel_classes
from ..models.model import Model, block_period, n_superblocks, _mixer_kind
from .scheduler import KVScheduler


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: List[int]
    max_new_tokens: int
    generated: List[int] = dataclasses.field(default_factory=list)
    state: str = "waiting"          # waiting | running | done | preempted


@dataclasses.dataclass
class EngineConfig:
    page_size: int = 16
    num_pages: int = 512
    max_batch: int = 4
    max_seq: int = 512              # logical pages per seq = max_seq/page_size
    psi: int = 3                    # |K| bound for Algorithm 3
    refresh_util_delta: float = 0.15
    alloc_policy: str = "buddy_best"
    interpret: Optional[bool] = None  # None: repro.kernels.default_interpret
    greedy: bool = True


class ServingEngine:
    def __init__(self, model: Model, params: Any, ec: EngineConfig):
        cfg = model.cfg
        assert cfg.family != "encoder", "encoder models don't decode"
        self.model = model
        self.params = params
        self.ec = ec
        self.cfg = cfg
        self.nsb = n_superblocks(cfg)
        self.period = block_period(cfg)
        self.allocator = PagedKVAllocator(ec.num_pages,
                                          alloc_policy=ec.alloc_policy)
        self.sched = KVScheduler(self.allocator, ec.max_batch)
        self.K: List[int] = []
        self._k_util = 0.0
        self.requests: Dict[int, Request] = {}
        self._next_id = 0
        self.metrics: Dict[str, float] = {
            "steps": 0, "tokens": 0, "dma_descriptors": 0,
            "dma_descriptors_page_granular": 0, "preemptions": 0,
            "kv_quarantined_pages": 0}
        self.interpret = (default_interpret() if ec.interpret is None
                          else ec.interpret)
        # one compiled program per shape: prefill per prompt length, decode
        # per class set K (the static knobs of the paged-attention passes);
        # decode updates the KV pools in place of the donated old state
        self._prefill_fn = jax.jit(model.prefill)
        self._decode_fn = jax.jit(
            model.decode_step_paged, donate_argnames=("state",),
            static_argnames=("page_size", "K_classes", "interpret"))
        # (request id, logits, index of its row) for every token the last
        # step() chose: a request's prefill row first, then its decode row
        self._step_logits: List[Tuple[int, jax.Array, Tuple[int, ...]]] = []
        self._init_state()

    # ------------------------------------------------------------------
    def _init_state(self):
        cfg, ec = self.cfg, self.ec
        B = ec.max_batch
        dt = jnp.dtype(self.model.rc.compute_dtype)
        state: Dict[str, Any] = {}
        for j in range(self.period):
            mk = _mixer_kind(cfg, j)
            if mk == "attn":
                shape = (self.nsb, *kv_pool_shape(
                    ec.num_pages, ec.page_size, cfg.n_kv_heads, cfg.head_dim))
                state[f"pos{j}"] = {"pool_k": jnp.zeros(shape, dt),
                                    "pool_v": jnp.zeros(shape, dt)}
            else:
                from ..models.model import init_decode_state
                full = init_decode_state(cfg, self.model.rc, B, 8, dt)
                state[f"pos{j}"] = full[f"pos{j}"]
        self.state = state

    # ------------------------------------------------------------------
    def add_request(self, prompt: List[int], max_new_tokens: int = 16) -> int:
        # An oversized request can never be served: its block table would
        # silently truncate past max_seq pages, and one whose page need
        # exceeds the whole pool live-locks admission forever (the FCFS
        # head retries every step, preempting the rest of the batch).
        # Reject at the door instead.
        total = len(prompt) + max_new_tokens
        if total > self.ec.max_seq:
            raise ValueError(
                f"request needs {total} tokens (prompt {len(prompt)} + "
                f"max_new_tokens {max_new_tokens}) but max_seq is "
                f"{self.ec.max_seq}")
        need = -(-total // self.ec.page_size)
        if need > self.ec.num_pages:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.ec.num_pages}: it could never be admitted")
        rid = self._next_id
        self._next_id += 1
        self.requests[rid] = Request(rid, list(prompt), max_new_tokens)
        self.sched.enqueue(rid)
        return rid

    # scheduling state lives in the model-free KVScheduler core (shared with
    # the scenario recorder in repro.scenarios.workload)
    @property
    def waiting(self):
        return self.sched.waiting

    @property
    def running(self) -> List[int]:
        return self.sched.running

    def _maybe_refresh_k(self):
        util = self.allocator.utilization()
        if not self.K or abs(util - self._k_util) > self.ec.refresh_util_delta:
            hist = self.allocator.contiguity_histogram()
            self.K = choose_kernel_classes(hist, psi=self.ec.psi) or [0]
            self._k_util = util

    def _need_pages(self, rid: int) -> int:
        req = self.requests[rid]
        return -(-(len(req.prompt) + req.max_new_tokens) // self.ec.page_size)

    def _admit(self):
        self.sched.admit(self._need_pages, on_admit=self._on_admit,
                         on_preempt=self._on_preempt)

    def _on_admit(self, rid: int) -> None:
        self.requests[rid].state = "running"
        self._prefill(rid)

    def _on_preempt(self, rid: int) -> None:
        """Recompute-style preemption bookkeeping.

        The victim keeps its ``generated`` list (the user must receive every
        token produced); on re-admission :meth:`_prefill` recomputes the KV
        for ``prompt + generated`` and decoding continues from there.  (An
        earlier version folded the generated tokens into ``prompt`` and
        cleared the list, silently dropping them from the final output.)
        """
        self.requests[rid].state = "preempted"
        self.metrics["preemptions"] += 1

    def _slot_of(self, rid: int) -> int:
        return self.sched.slot_of(rid)

    def _prefill(self, rid: int):
        """Run prompt (+ any recompute-preempted generation) through the
        model and write KV into the pages."""
        req = self.requests[rid]
        toks_list = req.prompt + req.generated
        toks = jnp.asarray(toks_list, jnp.int32)[None]
        logits, states = self._prefill_fn(self.params, toks)
        bt = self.allocator.block_table(rid, self.max_pages)
        T = self.ec.page_size
        S = len(toks_list)
        n_full = -(-S // T)
        slot = self._slot_of(rid)
        for j in range(self.period):
            if _mixer_kind(self.cfg, j) != "attn":
                # recurrent states: copy into the batch slot
                st = states[f"pos{j}"]
                for key, val in st.items():
                    cur = self.state[f"pos{j}"][key]
                    upd = val[:, 0]
                    self.state[f"pos{j}"][key] = cur.at[:, slot].set(
                        upd.astype(cur.dtype))
                continue
            k = states[f"pos{j}"]["k"][:, 0]     # [nsb, maxS, KVH, D]
            v = states[f"pos{j}"]["v"][:, 0]
            pool_k = self.state[f"pos{j}"]["pool_k"]
            pool_v = self.state[f"pos{j}"]["pool_v"]
            pad = n_full * T - S
            kpad = jnp.pad(k[:, :S], ((0, 0), (0, pad), (0, 0), (0, 0)))
            vpad = jnp.pad(v[:, :S], ((0, 0), (0, pad), (0, 0), (0, 0)))
            pages = jnp.asarray(bt[:n_full], jnp.int32)
            kpages = to_pool_layout(kpad).reshape(self.nsb, n_full, T, -1)
            vpages = to_pool_layout(vpad).reshape(self.nsb, n_full, T, -1)
            self.state[f"pos{j}"]["pool_k"] = pool_k.at[:, pages].set(
                kpages.astype(pool_k.dtype))
            self.state[f"pos{j}"]["pool_v"] = pool_v.at[:, pages].set(
                vpages.astype(pool_v.dtype))
        # seed first generated token greedily from the last prompt position
        last = logits[0, S - 1]
        self._step_logits.append((rid, last, ()))
        nxt = int(jnp.argmax(last[: self.cfg.vocab]))
        req.generated.append(nxt)

    @property
    def max_pages(self) -> int:
        return self.ec.max_seq // self.ec.page_size

    def _reap_finished(self) -> None:
        """Release running requests that hit their token budget (a
        re-admitted preemption victim may reach it at prefill, before any
        decode step — decoding it again would append an extra token)."""
        for rid in list(self.running):
            req = self.requests[rid]
            if len(req.generated) >= req.max_new_tokens:
                req.state = "done"
                self.sched.release(rid)

    # ------------------------------------------------------------------
    def last_logits(self, rid: int) -> List[jax.Array]:
        """The padded-vocab logits rows from which the last :meth:`step`
        chose request ``rid``'s tokens, in order: the prefill row if it was
        admitted in that step, then its decode row."""
        return [lg[at] for r, lg, at in self._step_logits if r == rid]

    def step(self) -> bool:
        """One engine iteration: admit, decode one token for all running."""
        self._step_logits = []
        self._admit()
        self._reap_finished()
        if not self.running:
            return bool(self.waiting)
        self._maybe_refresh_k()
        ec = self.ec
        B = ec.max_batch
        toks = np.zeros((B, 1), np.int32)
        lens = np.zeros((B,), np.int32)
        tables = np.full((B, self.max_pages), -1, np.int32)
        active = np.zeros((B,), bool)
        for rid in self.running:
            slot = self._slot_of(rid)
            req = self.requests[rid]
            toks[slot, 0] = req.generated[-1]
            lens[slot] = len(req.prompt) + len(req.generated) - 1
            tables[slot] = self.allocator.block_table(rid, self.max_pages)
            active[slot] = True

        descriptors = build_descriptors(tables, self.K)
        st = dma_stats(tables, self.K)
        self.metrics["dma_descriptors"] += st["descriptors_coalesced"]
        self.metrics["dma_descriptors_page_granular"] += st["pages"]

        logits, self.state = self._decode_fn(
            self.params, self.state, jnp.asarray(toks), jnp.asarray(lens),
            tables, descriptors, page_size=ec.page_size,
            K_classes=tuple(self.K), interpret=self.interpret)

        nxt = np.asarray(jnp.argmax(logits[:, 0, : self.cfg.vocab], axis=-1))
        finished = []
        for rid in list(self.running):
            slot = self._slot_of(rid)
            req = self.requests[rid]
            self._step_logits.append((rid, logits, (slot, 0)))
            req.generated.append(int(nxt[slot]))
            self.metrics["tokens"] += 1
            if len(req.generated) >= req.max_new_tokens:
                req.state = "done"
                finished.append(rid)
        for rid in finished:
            self.sched.release(rid)
        self.metrics["steps"] += 1
        return bool(self.running or self.waiting)

    def decode_hlo(self) -> str:
        """HLO text of the compiled decode step for the current classes
        ``K`` (a compiled Pallas kernel shows as ``tpu_custom_call``)."""
        B = self.ec.max_batch
        tables = np.full((B, self.max_pages), -1, np.int32)
        return self._decode_fn.lower(
            self.params, self.state, np.zeros((B, 1), np.int32),
            np.zeros((B,), np.int32), tables,
            build_descriptors(tables, self.K), page_size=self.ec.page_size,
            K_classes=tuple(self.K), interpret=self.interpret
        ).compile().as_text()

    def run_to_completion(self, max_steps: int = 10_000) -> Dict[str, float]:
        for _ in range(max_steps):
            if not self.step():
                break
        m = dict(self.metrics)
        # max_steps exhaustion must never be silent: `stalled` counts the
        # requests still waiting/running when the loop gave up (0 = drained)
        m["stalled"] = len(self.waiting) + len(self.running)
        pg = m["dma_descriptors_page_granular"]
        m["descriptor_reduction"] = 1.0 - m["dma_descriptors"] / max(pg, 1)
        m["K"] = list(self.K)
        return m

    # ------------------------------------------------------------------
    # Robustness: crash-restart checkpoints and KV-page quarantine
    # ------------------------------------------------------------------
    def snapshot(self, ckpt_dir: str, step: int = None) -> int:
        """Checkpoint the complete engine state (KV pool pytree via the
        atomic :class:`~repro.checkpoint.checkpointer.Checkpointer`; the
        request/scheduler/allocator bookkeeping rides in ``extras``).  A
        fresh engine built from the same (model, params, config) that
        :meth:`restore`\\ s this checkpoint continues token-exactly —
        ``tests/test_robustness.py`` proves it against the fault-free run."""
        from ..checkpoint.checkpointer import Checkpointer
        extras = dict(
            requests={str(r): dict(prompt=[int(t) for t in q.prompt],
                                   max_new_tokens=int(q.max_new_tokens),
                                   generated=[int(t) for t in q.generated],
                                   state=q.state)
                      for r, q in self.requests.items()},
            scheduler=self.sched.snapshot_state(),
            allocator=self.allocator.snapshot_state(),
            K=list(self.K), k_util=self._k_util, next_id=self._next_id,
            metrics=dict(self.metrics))
        step = int(self.metrics["steps"]) if step is None else int(step)
        Checkpointer(ckpt_dir).save(step, self.state, extras, blocking=True)
        return step

    def restore(self, ckpt_dir: str, step: int = None) -> int:
        """Reload a :meth:`snapshot` into this engine (crash-restart)."""
        from ..checkpoint.checkpointer import Checkpointer
        tree, extras = Checkpointer(ckpt_dir).restore(step, target=self.state)
        self.state = jax.tree.map(jnp.asarray, tree)
        self.requests = {
            int(r): Request(int(r), [int(t) for t in d["prompt"]],
                            int(d["max_new_tokens"]),
                            [int(t) for t in d["generated"]], d["state"])
            for r, d in extras["requests"].items()}
        self.sched.restore_state(extras["scheduler"])
        self.allocator.restore_state(extras["allocator"])
        self.K = [int(k) for k in extras["K"]]
        self._k_util = float(extras["k_util"])
        self._next_id = int(extras["next_id"])
        self.metrics = dict(extras["metrics"])
        return int(extras["metrics"]["steps"])

    def quarantine_pages(self, pages) -> List[int]:
        """Corrupted-KV-page recovery: recompute-preempt every owning
        request (its ``generated`` tokens are kept, so re-prefill rebuilds
        the exact KV the corrupted pages held), then retire the poisoned
        physical pages from the pool permanently so re-admission cannot
        land on them.  Returns the preempted request ids."""
        owners = self.allocator.owners_of(pages)
        for rid in owners:
            self.sched.preempt(rid, self._on_preempt)
        retired = self.allocator.retire_pages(pages)
        self.metrics["kv_quarantined_pages"] += len(retired)
        return owners
