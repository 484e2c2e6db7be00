"""Serve driver: closed-loop clients over the program's ``ServingEngine``.

The traffic file gives the clients, the engine's settings, and the decks
of prompt and output lengths.  Request ``i`` of a run takes its sizes from
round ``i // D`` of the decks (``D`` requests a round), each round a fresh
seeded shuffle, so every seed serves the same sizes in another order; the
seed also draws the prompts' tokens and the weights.  A client sends its
next request as soon as the step that finished its last one returns.
Client ``c`` of ``C`` first sends a request cut to ``(c + 1) / C`` of its
output length, so completions are staggered from the first step on, as in
a loop that has run for a while.

Set-up makes the weights, builds the engine, runs every prompt length of
the deck through the engine's own admission path, replays the schedule
without the model to list the kernel class sets ``K`` the window can reach
and compiles the decode step for each, then admits the first requests.
The window steps the engine until ``--seconds`` have passed.  Tokens are
stamped when ``step()`` returns, which is when the host has them.

``correct`` compares the served tokens of a sample of the requests that
finished in the window (the longest of them, and others drawn from the
seed until the sample holds ``check.tokens`` tokens) with the plain
reference's logits at the same positions: the widest gap by which a
served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import math
import time
from statistics import NormalDist
from typing import Dict, List

import numpy as np

NEEDS_HOST_CPU = False
REF_BUCKET = 512        # the reference's sequence lengths, in tokens
POS_BUCKET = 128        # and its number of positions read


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def prompt_deck(spec: dict) -> List[int]:
    return [n for n, count in zip(spec["lengths"], spec["counts"])
            for _ in range(count)]


def output_deck(spec: dict, n: int) -> List[int]:
    """``n`` output lengths: the quantiles of a log-normal with the given
    median whose central 90% spans [min, max], clipped to that range."""
    lo, hi, med = spec["min"], spec["max"], spec["median"]
    sigma = math.log(max(hi / med, med / lo)) / NormalDist().inv_cdf(0.95)
    return [int(min(hi, max(lo, round(
        med * math.exp(sigma * NormalDist().inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


class Traffic:
    """The seeded request stream: sizes by index, tokens in order."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.clients = traffic["clients"]
        self.prompts = prompt_deck(traffic["prompts"])
        self.outputs = output_deck(traffic["outputs"], len(self.prompts))
        self.seed = seed
        self.vocab = vocab
        self._rounds: Dict[int, list] = {}
        self._tokens = np.random.default_rng([seed, 1])

    def size(self, i: int) -> tuple:
        """(prompt length, output length) of request ``i``."""
        r, j = divmod(i, len(self.prompts))
        if r not in self._rounds:
            rng = np.random.default_rng([self.seed, 2, r])
            self._rounds[r] = list(zip(rng.permutation(self.prompts),
                                       rng.permutation(self.outputs)))
        s, o = self._rounds[r][j]
        if i < self.clients:          # the first requests, staggered
            o = max(1, -(-o * (i + 1) // self.clients))
        return int(s), int(o)

    def tokens(self, n: int) -> List[int]:
        return self._tokens.integers(0, self.vocab, n).tolist()


# ---------------------------------------------------------------------------
# the schedule without the model: the K sets the window can reach
# ---------------------------------------------------------------------------

def replay(snapshot: dict, ec, traffic: Traffic, n_steps: int) -> dict:
    """Replay the engine's admission, completion and class choice over
    ``n_steps`` steps of the closed loop from the allocator state
    ``snapshot``: every class set the decode step is called with, and the
    preemptions.

    Mirrors ``ServingEngine.step``: admit (a request's first token comes
    from its prefill), reap, re-choose K once the pool's utilisation has
    drifted by ``refresh_util_delta``, decode a token for every running
    request and release the finished ones; then each client whose request
    finished sends its next one, in request order, as :func:`run` does."""
    from repro.kvcache.allocator import PagedKVAllocator
    from repro.kvcache.block_table import choose_kernel_classes
    from repro.serve.scheduler import KVScheduler

    alloc = PagedKVAllocator(ec.num_pages, alloc_policy=ec.alloc_policy)
    alloc.restore_state(snapshot)
    sched = KVScheduler(alloc, ec.max_batch)
    need: List[int] = []
    budget: List[int] = []
    made: List[int] = []

    def send():
        s, o = traffic.size(len(need))
        need.append(-(-(s + o) // ec.page_size))
        budget.append(o)
        made.append(0)
        sched.enqueue(len(need) - 1)

    def on_admit(rid):
        made[rid] += 1

    for _ in range(traffic.clients):
        send()
    K: List[int] = []
    k_util = 0.0
    ks: List[tuple] = []
    for _ in range(n_steps):
        done = []
        sched.admit(need.__getitem__, on_admit=on_admit)
        for rid in list(sched.running):
            if made[rid] >= budget[rid]:
                sched.release(rid)
                done.append(rid)
        if sched.running:
            util = alloc.utilization()
            if not K or abs(util - k_util) > ec.refresh_util_delta:
                K = choose_kernel_classes(alloc.contiguity_histogram(),
                                          psi=ec.psi) or [0]
                k_util = util
            if tuple(K) not in ks:
                ks.append(tuple(K))
            for rid in list(sched.running):
                made[rid] += 1
                if made[rid] >= budget[rid]:
                    sched.release(rid)
                    done.append(rid)
        for _rid in done:
            send()
    return {"k_sets": ks, "preemptions": sched.preemptions,
            "requests": len(need)}


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def model_config(config: dict):
    """The program's configuration object for a dense decoder file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab=config["vocab_size"], rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"])


def build(config: dict, traffic: dict, seed: int, interpret=None):
    """Weights from the seed, and an engine over them."""
    import jax
    from bench import weights
    from repro.models import Model, RunConfig
    from repro.serve import EngineConfig, ServingEngine

    dt = config["torch_dtype"]
    model = Model(model_config(config), RunConfig(
        param_dtype=dt, compute_dtype=config["assumed"]["compute_dtype"]))
    params = weights.make(config, seed, jax.eval_shape(model.init), dt)
    eng = ServingEngine(model, params, EngineConfig(
        **traffic["engine"], interpret=interpret))
    return params, eng


def warm_up(eng, traffic: Traffic, n_steps: int, log) -> dict:
    """Compile every program the window can call."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention.ops import build_descriptors

    # each prompt length through the engine's own path, page writes and
    # all; a one-token request finishes in the step that admits it
    lengths = sorted(set(traffic.prompts))
    for n in lengths:
        eng.add_request(traffic.tokens(n), max_new_tokens=1)
        while eng.step():
            pass
    plan = replay(eng.allocator.snapshot_state(), eng.ec, traffic, n_steps)
    B = eng.ec.max_batch
    tables = np.full((B, eng.max_pages), -1, np.int32)
    for K in plan["k_sets"]:
        # every slot idle, so the step's KV writes are dropped
        logits, eng.state = eng._decode_fn(
            eng.params, eng.state, jnp.zeros((B, 1), jnp.int32),
            jnp.zeros((B,), jnp.int32), tables,
            build_descriptors(tables, list(K)), page_size=eng.ec.page_size,
            K_classes=K, interpret=eng.interpret)
        np.asarray(jnp.argmax(logits[:, 0, : eng.cfg.vocab], axis=-1))
    jax.block_until_ready(eng.state)
    log(f"[serve] warmed {len(lengths)} prompt lengths and the decode step "
        f"for K in {plan['k_sets']}; the replay of {n_steps} steps sent "
        f"{plan['requests']} requests with {plan['preemptions']} "
        f"preemptions")
    return plan


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(config: dict, traffic_spec: dict, ctx, interpret=None,
        fault=None, observe=None, control=None) -> dict:
    """Set up, run the window, check.  Tests and ``bench/control.py``
    only: ``fault`` may break the engine once set-up is done,
    ``observe(params, served)`` sees what the check saw, and ``control``
    (``"fp8"``) puts the reference at that precision in the program's
    place in the check (see :func:`check`)."""
    import jax
    from repro.compile_cache import CompileLog

    params, eng = build(config, traffic_spec, ctx.seed, interpret)
    traffic = Traffic(traffic_spec, config["vocab_size"], ctx.seed)
    n_steps = int(traffic_spec["max_steps_per_second"] * ctx.seconds) + 1
    warm_up(eng, traffic, n_steps, ctx.log)
    if fault is not None:
        fault(eng)

    sent = 0

    def send() -> int:
        """The next request of the stream; its engine id."""
        nonlocal sent
        s, o = traffic.size(sent)
        sent += 1
        return eng.add_request(traffic.tokens(s), max_new_tokens=o)

    live = {send() for _ in range(traffic.clients)}
    eng.step()                           # admits the first requests
    t_prev: Dict[int, float] = {}
    now = time.perf_counter()
    for rid in live:
        if eng.requests[rid].generated:
            t_prev[rid] = now
    finished: List[int] = []

    def reap(in_window: bool):
        for rid in sorted(live):
            if eng.requests[rid].state == "done":
                live.discard(rid)
                t_prev.pop(rid, None)
                if in_window:
                    finished.append(rid)
                live.add(send())

    reap(False)
    jax.block_until_ready(eng.state)

    steps: List[dict] = []
    gaps: List[float] = []
    tokens = 0
    tracing = ctx.trace
    ctx.window_opens()
    t0 = time.perf_counter()
    if tracing:
        ctx.start_trace()
    with CompileLog() as log:
        while True:
            before = {rid: len(eng.requests[rid].generated) for rid in live}
            running = set(eng.running)
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.serve.step"):
                eng.step()
            te = time.perf_counter()
            rec = {"t0": ts, "t1": te, "prefill": [], "decode_ctx": [],
                   "traced": tracing}
            for rid, n0 in before.items():
                req = eng.requests[rid]
                n1 = len(req.generated)
                if n1 == n0:
                    continue
                n_dec = n1 - n0
                if rid not in running:          # admitted in this step
                    rec["prefill"].append(len(req.prompt) + n0)
                    n_dec -= 1
                # a decoded token attends over the prompt and every token
                # generated before it
                rec["decode_ctx"] += [len(req.prompt) + g
                                      for g in range(n1 - n_dec, n1)]
                # tokens that arrive together are 0 s apart
                if rid in t_prev:
                    gaps.append(te - t_prev[rid])
                gaps += [0.0] * (n1 - n0 - 1)
                t_prev[rid] = te
                tokens += n1 - n0
            steps.append(rec)
            reap(True)
            if tracing and te - t0 >= traffic_spec["trace_seconds"]:
                jax.block_until_ready(eng.state)
                ctx.stop_trace()
                tracing = False
            if te - t0 >= ctx.seconds:
                break
    window = steps[-1]["t1"] - t0
    ctx.stop_trace()
    ctx.log(f"[serve] window {window!r} s: {len(steps)} steps, {tokens} "
            f"tokens, {len(finished)} requests finished, {len(gaps)} "
            f"inter-token gaps; compiles in window {log.count} "
            f"({log.seconds:.3f} s); preemptions "
            f"{eng.metrics['preemptions']}; K {eng.K}; DMA descriptors "
            f"{eng.metrics['dma_descriptors']} of "
            f"{eng.metrics['dma_descriptors_page_granular']} page-granular")
    memory_peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0)
                          for d in jax.local_devices()))
    served = {rid: (list(eng.requests[rid].prompt),
                    list(eng.requests[rid].generated),
                    eng.requests[rid].max_new_tokens) for rid in finished}
    in_flight = len(live)
    del eng, live
    gc.collect()
    checks, errors = check(config, traffic_spec, params, served, ctx.seed,
                           ctx.log, control)
    if observe is not None:
        observe(params, served)
    return dict(
        end_to_end={"tokens_per_s": tokens / window,
                    "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3
                    if gaps else None},
        attempted=len(finished) + in_flight, failed=0,
        checks=checks, check_errors=errors, compiles_in_window=log.count,
        memory_peak_bytes=memory_peak,
        records={"driver": "serve", "steps": steps, "config": config,
                 "engine": traffic_spec["engine"], "window_s": window})


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def sample(served: dict, want_tokens: int, seed: int) -> List[int]:
    """The longest finished request, then others in seeded order until
    the sample holds ``want_tokens`` served tokens."""
    if not served:
        return []
    rids = sorted(served)
    longest = max(rids, key=lambda r: (len(served[r][1]), -r))
    picked = [longest]
    total = len(served[longest][1])
    for r in np.random.default_rng([seed, 3]).permutation(rids):
        if total >= want_tokens:
            break
        if r != longest:
            picked.append(int(r))
            total += len(served[r][1])
    return picked


def logit_gaps(ref_rows, chosen) -> np.ndarray:
    """How far each chosen token's reference logit lies below its row's
    best."""
    ref = np.asarray(ref_rows, np.float64)
    chosen = np.asarray(chosen)
    return ref.max(axis=-1) - ref[np.arange(len(chosen)), chosen]


def reference_rows(config: dict, params, prompt, generated,
                   quantize: str = "none") -> np.ndarray:
    """The reference's logits at every position where a token was
    served: after the prompt's last token and after each served token
    but the last."""
    from bench.reference import internlm2
    toks = list(prompt) + list(generated[:-1])
    pos = np.arange(len(prompt) - 1, len(toks))
    # padded to buckets so a few programs serve every length; the padding
    # comes after every position read, which causal attention never sees
    n, g = len(toks), len(pos)
    toks += [0] * (-n % REF_BUCKET)
    pos = np.concatenate([pos, np.full(-g % POS_BUCKET, pos[-1])])
    return np.asarray(internlm2.logits(params, config, toks, pos,
                                       quantize))[:g]


def check(config: dict, traffic: dict, params, served: dict, seed: int,
          log, control=None):
    """The compared numbers, each with its limit, and the errors.  With
    ``control`` the tokens compared at each position are those the
    reference at that lower precision puts first, over the same prompts
    and served tokens: the control of the comparison."""
    errors = []
    picked = sample(served, traffic["check"]["tokens"], seed)
    if not picked:
        errors.append("no request finished in the window")
    widest = 0.0
    n_tok = 0
    t0 = time.perf_counter()
    for rid in picked:
        prompt, gen, budget = served[rid]
        if len(gen) != budget:
            errors.append(f"request {rid} served {len(gen)} tokens of "
                          f"{budget}")
            continue
        rows = reference_rows(config, params, prompt, gen)
        chosen = gen if control is None else np.argmax(reference_rows(
            config, params, prompt, gen, control), axis=-1)
        widest = max(widest, float(logit_gaps(rows, chosen).max()))
        n_tok += len(gen)
    log(f"[check] {len(picked)} requests, {n_tok} served tokens"
        f"{' (control ' + control + ')' if control else ''} against "
        f"the float32 reference in {time.perf_counter() - t0:.1f} s; "
        f"widest logit gap {widest!r}")
    return {"max_logit_gap": {"value": widest,
                              "limit": traffic["check"]["max_logit_gap"]}
            }, errors
