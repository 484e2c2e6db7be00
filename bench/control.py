#!/usr/bin/env python3
"""Readings of the program and of the control, seed by seed, on the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

The benchmark's own runs never run this.  It gives the two readings each
limit of ``correct`` is set between (see ``PERF.md``):

* serve cells: each seed runs the cell's window as the benchmark does and
  reads the widest logit gap of the served tokens (the program's reading);
  then, over the same sampled requests, the control puts the plain
  reference computed with float8 (e4m3) matmul inputs in the program's
  place and reads the gap of the token it puts first at every position
  (``serve.check`` with ``control="fp8"``).
* sweep cells: the control puts the plain reference with a 7-way L2 (the
  configuration states 8 ways) in the program's place, over every cell
  of the window's first batch, and counts the fields that differ from
  the reference.

One process runs every seed, so each program compiles once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def serve_readings(config, traffic, seed, seconds, ctx_factory, **kw):
    """(program's widest gap, control's widest gap, end-to-end metrics)
    for one seed: one window, both read over the same sampled
    requests."""
    from bench.drivers import serve
    seen = {}

    def observe(params, served):
        checks, _ = serve.check(config, traffic, params, served, seed,
                                lambda msg: None, control="fp8")
        seen["control"] = checks["max_logit_gap"]["value"]

    res = serve.run(config, traffic, ctx_factory(seed, seconds),
                    observe=observe, **kw)
    return (res["checks"]["max_logit_gap"]["value"], seen["control"],
            res["end_to_end"])


def sweep_readings(config, traffic, seed):
    """Fields in which the 7-way control differs from the reference over
    every cell of the window's first batch, as the check compares
    them."""
    import types
    from bench.drivers import sweep
    ws = sweep.worlds(traffic, config["roster"], seed)
    plain = sweep.plain_cells(config, traffic, ws)
    idx = list(sweep.batches(len(plain), traffic["batch"])[0])
    want = sweep.reference(ws, plain, idx)
    got = sweep.reference(ws, plain, idx, ways_less=1)
    return sum(len(sweep.mismatches(types.SimpleNamespace(**got[i]),
                                    want[i])) for i in idx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import run as harness
    spec = harness.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    platforms = os.environ.get("JAX_PLATFORMS")
    if spec["traffic"]["driver"] == "sweep" and platforms and \
            "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = []
    for seed in seeds:
        if spec["traffic"]["driver"] == "serve":
            prog, ctrl, e2e = serve_readings(
                spec["config"], spec["traffic"], seed, args.seconds,
                lambda s, sec: harness.Context(s, sec, False, ""))
            row = {"seed": seed, "program": prog, "control": ctrl,
                   "end_to_end": e2e}
        else:
            row = {"seed": seed, "control": sweep_readings(
                spec["config"], spec["traffic"], seed)}
        print(json.dumps(row), flush=True)
        out.append(row)
    print(json.dumps({"workload": args.workload, "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
