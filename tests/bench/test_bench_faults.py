"""A run with its timed path broken underneath must come out not correct.

The harness's look for a chip is skipped; the rest of a run is driven as
the command drives it, with one fault planted in the program's path after
set-up: a step that returns its state unchanged, half of the batch left
out (the rest's results standing in for it), and an answer altered where
it is produced (in the sweep also in one lane alone).  The cells run on
one chip, so no exchange between chips exists to leave out."""
import dataclasses
import json
import os

import numpy as np
import pytest

from bench_helpers import CPU, PEAKS, ROOT, spec_of

from bench import run as harness
from bench.drivers import serve, sweep

with open(os.path.join(ROOT, "bench", "traffic", "short.json")) as f:
    SERVE_LIMIT = json.load(f)["check"]["max_logit_gap"]


# --------------------------------------------------------------- serve

def _state_unchanged(eng):
    import jax
    import jax.numpy as jnp
    real = eng._decode_fn

    def step(params, state, *a, **kw):
        logits, _ = real(params, jax.tree.map(jnp.copy, state), *a, **kw)
        return logits, state            # this step's KV is never written
    eng._decode_fn = step


def _half_batch(eng):
    real = eng._decode_fn

    def step(*a, **kw):
        logits, state = real(*a, **kw)
        half = logits.shape[0] // 2     # upper slots take the lower ones'
        return logits.at[half:].set(logits[:half]), state
    eng._decode_fn = step


def _token_altered(eng):
    real = eng._decode_fn
    vocab = eng.cfg.vocab

    def step(*a, **kw):
        import jax.numpy as jnp
        logits, state = real(*a, **kw)
        top = logits[:, 0, :vocab].argmax(-1)     # every slot's token moves
        rows = jnp.arange(logits.shape[0])
        return (logits.at[rows, 0, (top + vocab // 2) % vocab].set(1e9),
                state)
    eng._decode_fn = step


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_serve_fault_is_not_correct(tiny_serve, fault):
    config, traffic = tiny_serve
    traffic["check"]["max_logit_gap"] = SERVE_LIMIT
    spec = spec_of(config, traffic, ["tokens_per_s"])
    res = harness.execute(spec, serve, harness.Context(2**31 + 1, 2.0,
                                                       False, ""),
                          CPU, PEAKS, interpret=True, fault=fault)
    assert not res["correct"], res["checks"]


# --------------------------------------------------------------- sweep

def _zeroed(r):
    return dataclasses.replace(
        r, l1_hits=0, l2_regular_hits=0, l2_coalesced_hits=0, walks=0,
        aligned_probes=0, pred_correct=0, cycles=0, coverage_mean=0.0,
        ppn=np.full_like(r.ppn, -1))


def _sweep_fault(kind):
    from repro.core.sweep import run_sweep

    def fn(cells, **kw):
        res = run_sweep(cells, **kw)
        rs = res.results
        if kind == "state_unchanged":       # no lane ever advanced
            rs = [_zeroed(r) for r in rs]
        elif kind == "half_batch":          # the upper half left out
            half = len(rs) // 2
            rs = rs[:half] + rs[:len(rs) - half]
        elif kind == "answer_altered":
            rs = [dataclasses.replace(r, walks=r.walks + 1) for r in rs]
        elif kind == "one_lane_altered":    # the batch's last lane only
            rs = rs[:-1] + [dataclasses.replace(rs[-1], ppn=rs[-1].ppn + 1)]
        return dataclasses.replace(res, results=rs)
    return fn


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered", "one_lane_altered"])
def test_sweep_fault_is_not_correct(tiny_sweep, kind):
    config, traffic = tiny_sweep
    spec = spec_of(config, traffic, ["accesses_per_s"])
    res = harness.execute(spec, sweep, harness.Context(2**31 + 2, 0.5,
                                                       False, ""),
                          CPU, PEAKS, sweep_fn=_sweep_fault(kind))
    assert not res["correct"], res["checks"]
    assert res["checks"]["mismatched_fields"]["value"] > 0
