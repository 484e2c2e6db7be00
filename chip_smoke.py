#!/usr/bin/env python3
"""Bring-up check: the sweep engine and internlm2-1.8b serving on one TPU.

    python chip_smoke.py [--seed N]

Everything runs in this one process, on one chip, and nowhere else:

* **device** — exits non-zero, printing no result, unless JAX's first
  device is a TPU.
* **sweep** — the ``tlb_demand`` section's cells at its quick-tier sizes
  (the 8 paper analogues, footprints capped at 2^19 pages, 150,000
  accesses, the full method suite), then the ``dyn-kv-churn`` dynamic
  world at ``tlb_dynamic``'s footprint cap (2^16 pages) and 120,000
  accesses, each through ``run_sweep`` with the result cache off.  A
  phase fails on a cache hit, a backend fallback, a bisection, an oracle
  fallback, a backend other than ``xla``, or on any counter or translated
  PPN that differs from the oracle (``run_method`` /
  ``run_method_dynamic`` on this process's CPU device) for the first and
  the last cell of every method kind.
* **serve** — ``ServingEngine`` with internlm2-1.8b at its published
  widths and seeded random weights: 8 requests of 512 prompt tokens and
  32 new tokens, 16-token pages, a 1,024-page pool and the compiled
  paged-attention kernel.  The logits the engine chose each token from
  (prefill and every decode step) of every request are compared with a
  float32 ``Model.prefill`` over the whole sequence at the highest matmul
  precision, and the compiled decode step must hold the kernel
  (``tpu_custom_call``).

The seconds printed are host wall time of this bring-up run, compiles
included; they are not performance numbers.  The last line of standard
output is ``{"ok": true, "device": {...}}``, printed only when every phase
passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from typing import Callable, Dict, List, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# the oracles run on the CPU device of this same process
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import tlb_suite  # noqa: E402
from repro.compile_cache import CompileLog, enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.simulator import run_method, run_method_dynamic  # noqa: E402
from repro.core.sweep import SweepResult, run_sweep  # noqa: E402
from repro.models import Model, RunConfig  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serve import EngineConfig, ServingEngine  # noqa: E402

WALL_NOTE = "host wall time of this bring-up run, not a performance number"

# every field of a SimResult the oracle must reproduce bit for bit
ORACLE_FIELDS = ("accesses", "l1_hits", "l2_regular_hits",
                 "l2_coalesced_hits", "walks", "aligned_probes",
                 "pred_correct", "cycles", "coverage_mean", "shootdowns")

# Logits tolerances, over the real vocabulary of each compared row.  The
# engine computes in bfloat16 (RunConfig's compute dtype: weights,
# activations and the residual stream rounded to 8 significant bits, unit
# roundoff 2^-8) and the reference in float32 at the highest precision.
# On the CPU, at 24 layers of width 512, that gap came to a relative L2
# error of 2e-2 and a largest error of 0.09 of the row's RMS.
# * REL_L2_TOL bounds ||got - ref|| / ||ref||.  0.06 leaves bfloat16 about
#   twice its expected error.  By its unit roundoff (2^-4 = 0.0625), float8
#   should exceed it with the rounding of the final matmul's inputs alone;
#   no run has measured that control.
# * MAX_ABS_TOL bounds the largest single error in units of the row's RMS.
#   Over a 92,544-logit row, bfloat16's spread reaches about five times its
#   L2 error (0.15); a logit moved by a corrupt page, a stale position or a
#   dropped class pass is off by O(1).
REL_L2_TOL = 0.06
MAX_ABS_TOL = 0.3


def oracle_mismatches(got, want) -> List[str]:
    """Every field (and the translated PPN array) in which ``got`` differs
    from the oracle result ``want``."""
    bad = [f for f in ORACLE_FIELDS if getattr(got, f) != getattr(want, f)]
    if not np.array_equal(np.asarray(got.ppn), np.asarray(want.ppn)):
        bad.append("ppn")
    return bad


def cells_to_check(cells) -> List[int]:
    """Indices of the first and the last cell of every method kind."""
    first: Dict[str, int] = {}
    last: Dict[str, int] = {}
    for i, c in enumerate(cells):
        first.setdefault(c.spec.kind, i)
        last[c.spec.kind] = i
    return sorted(set(first.values()) | set(last.values()))


def check_sweep(sweep: SweepResult, cells, oracle: Callable,
                oracle_device) -> List[str]:
    """The sweep's run statistics and its checked cells against the
    oracle; returns the failures."""
    st = sweep.stats
    failures = []
    if st["cache_hits"] != 0:
        failures.append(f"{st['cache_hits']} results came from the cache")
    for key in ("backend_fallbacks", "bisections", "oracle_fallbacks"):
        if st[key] != 0:
            failures.append(f"{key}={st[key]}")
    if st["backend"] != "xla":
        failures.append(f"backend {st['backend']!r} ran, want 'xla'")
    with jax.default_device(oracle_device):
        for i in cells_to_check(cells):
            c = cells[i]
            want = oracle(c.spec, c.mapping, c.trace)
            bad = oracle_mismatches(sweep.results[i], want)
            if bad:
                failures.append(f"cell {i} ({c.spec.name}) differs from the "
                                f"oracle in {', '.join(bad)}")
    return failures


def sweep_phase(label: str, plan, oracle: Callable, oracle_device
                ) -> List[str]:
    cells = plan.cells
    with CompileLog() as log:
        t0 = time.perf_counter()
        sweep = run_sweep(cells, cache=False)
        wall = time.perf_counter() - t0
    st = sweep.stats
    print(f"[sweep {label}] {st['n_cells']} cells in {st['n_batches']} "
          f"batches, backend={st['backend']} block={st['block']}, "
          f"cache_hits={st['cache_hits']}, fallbacks backend/bisect/oracle="
          f"{st['backend_fallbacks']}/{st['bisections']}/"
          f"{st['oracle_fallbacks']}; {wall:.1f} s, of which "
          f"{log.seconds:.1f} s in {log.count} compiles ({WALL_NOTE})",
          flush=True)
    t0 = time.perf_counter()
    failures = check_sweep(sweep, cells, oracle, oracle_device)
    checked = cells_to_check(cells)
    kinds = sorted({cells[i].spec.kind for i in checked})
    print(f"[sweep {label}] oracle on {oracle_device.platform}: "
          f"{len(checked)} cells over {len(kinds)} kinds ({', '.join(kinds)})"
          f" {'bit-exact' if not failures else 'FAILED'} "
          f"({time.perf_counter() - t0:.1f} s)", flush=True)
    return [f"sweep {label}: {f}" for f in failures]


def logits_errors(got: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    """Relative L2 error and the largest error in units of the reference
    row's RMS, for one logits row."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    diff = got - ref
    rms = np.sqrt(np.mean(ref * ref))
    return {"rel_l2": float(np.linalg.norm(diff) / np.linalg.norm(ref)),
            "max_abs_over_rms": float(np.max(np.abs(diff)) / rms)}


def check_logits(got_rows: Sequence[np.ndarray], ref_rows: np.ndarray
                 ) -> List[str]:
    """Each row the engine produced against the reference row at the same
    position; returns the failures."""
    if len(got_rows) != len(ref_rows):
        return [f"{len(got_rows)} logits rows, want {len(ref_rows)}"]
    failures = []
    for t, (g, r) in enumerate(zip(got_rows, ref_rows)):
        e = logits_errors(g, r)
        if not (e["rel_l2"] <= REL_L2_TOL
                and e["max_abs_over_rms"] <= MAX_ABS_TOL):
            failures.append(f"token {t}: rel_l2={e['rel_l2']:.3e} "
                            f"max_abs/rms={e['max_abs_over_rms']:.3e}")
    return failures


def serve(cfg: ModelConfig, *, seed: int, n_requests: int, prompt_len: int,
          new_tokens: int, page_size: int, num_pages: int, max_batch: int,
          max_seq: int, rc: RunConfig = RunConfig(), interpret=None
          ) -> Dict:
    """Serve seeded random requests through ``ServingEngine``; keep the
    logits rows it chose every request's tokens from, and the float32
    reference rows at the same positions: one ``Model.prefill`` over each
    whole sequence, no kernel, no cache."""
    t0 = time.perf_counter()
    model = Model(cfg, rc)
    with CompileLog() as init_log:
        params = model.init(seed)
        eng = ServingEngine(model, params, EngineConfig(
            page_size=page_size, num_pages=num_pages, max_batch=max_batch,
            max_seq=max_seq, interpret=interpret))
        jax.block_until_ready((params, eng.state))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, size=(n_requests, prompt_len))
    rids = [eng.add_request(p.tolist(), max_new_tokens=new_tokens)
            for p in prompts]
    rows: Dict[int, list] = {rid: [] for rid in rids}
    walls = {"init": time.perf_counter() - t0, "steps": []}
    with CompileLog() as log:
        more = True
        while more:
            t1 = time.perf_counter()
            more = eng.step()
            for rid in rids:
                rows[rid] += [np.asarray(r[: cfg.vocab])
                              for r in eng.last_logits(rid)]
            walls["steps"].append(time.perf_counter() - t1)
    with CompileLog() as hlo_log:
        hlo = eng.decode_hlo()
    t0 = time.perf_counter()
    ref_prefill = jax.jit(Model(cfg, rc.replace(compute_dtype="float32"))
                          .prefill)
    refs = {}
    with jax.default_matmul_precision("highest"):
        for rid in rids:
            req = eng.requests[rid]
            toks = jnp.asarray(req.prompt + req.generated[:-1], jnp.int32)
            logits, _ = ref_prefill(params, toks[None])
            refs[rid] = np.asarray(
                logits[0, len(req.prompt) - 1:, : cfg.vocab])
    walls["reference"] = time.perf_counter() - t0
    return dict(engine=eng, rows=rows, refs=refs, hlo=hlo, walls=walls,
                init_compiles=init_log, compiles=log, hlo_compiles=hlo_log,
                tokens=sum(len(r.generated) for r in eng.requests.values()),
                want_tokens=n_requests * new_tokens)


def check_serve(run: Dict) -> List[str]:
    """Tokens served, the kernel in the decode program, and every kept
    logits row against its reference row; returns the failures."""
    eng = run["engine"]
    failures = []
    if run["tokens"] != run["want_tokens"]:
        failures.append(f"served {run['tokens']} tokens, want "
                        f"{run['want_tokens']}")
    if not eng.interpret and "tpu_custom_call" not in run["hlo"]:
        failures.append("the compiled decode step holds no tpu_custom_call")
    for rid, got in run["rows"].items():
        failures += [f"request {rid} {f}"
                     for f in check_logits(got, run["refs"][rid])]
    return failures


def serve_phase(cfg: ModelConfig, **kw) -> List[str]:
    run = serve(cfg, **kw)
    eng, walls = run["engine"], run["walls"]
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}"
          f", {cfg.n_heads} heads over {cfg.n_kv_heads} KV heads of "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}; "
          f"{len(eng.requests)} requests, {run['tokens']} tokens served in "
          f"{eng.metrics['steps']} decode steps, K={eng.K}, "
          f"interpret={eng.interpret}", flush=True)
    print(f"[serve] init {walls['init']:.1f} s ({run['init_compiles'].count}"
          f" compiles took {run['init_compiles'].seconds:.1f} s); first step "
          f"{walls['steps'][0]:.1f} s; other steps "
          f"{sum(walls['steps'][1:]):.1f} s; {run['compiles'].count} compiles"
          f" took {run['compiles'].seconds:.1f} s; reference "
          f"{walls['reference']:.1f} s ({WALL_NOTE})", flush=True)
    print(f"[serve] decode HLO holds tpu_custom_call: "
          f"{'tpu_custom_call' in run['hlo']} ({run['hlo_compiles'].count} "
          f"compiles to get it)", flush=True)
    for rid, got in run["rows"].items():
        errs = [logits_errors(g, r) for g, r in zip(got, run["refs"][rid])]
        dec = errs[1:] or [dict(rel_l2=np.nan, max_abs_over_rms=np.nan)]
        print(f"[serve] request {rid} vs float32 reference: prefill rel_l2="
              f"{errs[0]['rel_l2']:.3e} max_abs/rms="
              f"{errs[0]['max_abs_over_rms']:.3e}; {len(dec)} decode steps "
              f"rel_l2 <= {max(e['rel_l2'] for e in dec):.3e} max_abs/rms <= "
              f"{max(e['max_abs_over_rms'] for e in dec):.3e} (tolerances "
              f"{REL_L2_TOL}, {MAX_ABS_TOL})", flush=True)
    return [f"serve: {f}" for f in check_serve(run)]


def _run_phase(name: str, fn: Callable[[], List[str]]) -> List[str]:
    try:
        return fn()
    except Exception:                 # report it, then run the next phase
        traceback.print_exc()
        return [f"{name}: raised (traceback above)"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f"; compile cache {cache_dir}", flush=True)
    cpu = jax.devices("cpu")[0]

    failures = _run_phase("sweep tlb_demand", lambda: sweep_phase(
        "tlb_demand", tlb_suite.demand_plan(trace_len=150_000, quick=True)[0],
        run_method, cpu))
    failures += _run_phase("sweep dyn-kv-churn", lambda: sweep_phase(
        "dyn-kv-churn", tlb_suite.dynamic_plan(("dyn-kv-churn",),
                                               trace_len=120_000),
        run_method_dynamic, cpu))
    failures += _run_phase("serve", lambda: serve_phase(
        get_config("internlm2-1.8b"), seed=args.seed, n_requests=8,
        prompt_len=512, new_tokens=32, page_size=16, num_pages=1024,
        max_batch=8, max_seq=1024))
    print(f"[device] peak device memory "
          f"{(dev.memory_stats() or {}).get('peak_bytes_in_use', 0) / 2**30:.2f}"
          " GiB",
          flush=True)
    for f in failures:
        print(f"FAIL {f}", flush=True)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
