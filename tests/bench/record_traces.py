#!/usr/bin/env python3
"""Record the small device traces the trace-reduction tests read.

    python3 tests/bench/record_traces.py <out dir>

Run on a TPU.  Each driver runs at the tests' tiny sizes with tracing on,
as ``bench/run.py --trace 1`` runs a cell, and the newest ``.xplane.pb``
of each is copied to ``<out dir>/<driver>_tiny.xplane.pb``.  The numbers
the tests hold them to were read from these files by hand."""
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src"), ROOT]
if os.environ.get("JAX_PLATFORMS") and \
        "cpu" not in os.environ["JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"     # the sweep's reference
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import bench_helpers  # noqa: E402
from bench import run as harness  # noqa: E402
from bench import trace_reduce  # noqa: E402
from bench.drivers import serve, sweep  # noqa: E402


def record(name, driver, config, traffic, seconds, out):
    trace_dir = os.path.join(ROOT, ".bench_trace", f"{name}_tiny")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = harness.Context(2**31 + 99, seconds, True, trace_dir)
    res = driver.run(config, traffic, ctx)
    ctx.stop_trace()
    src = trace_reduce.newest_xplane(trace_dir)
    dst = os.path.join(out, f"{name}_tiny.xplane.pb")
    shutil.copy(src, dst)
    print(name, os.path.getsize(dst), "bytes;", res["checks"],
          {k: v for k, v in res["records"].items()
           if k in ("block", "window_s")}, flush=True)


def main():
    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    config, traffic = bench_helpers.tiny_sweep()
    traffic["trace_seconds"] = 0.02
    record("sweep", sweep, config, traffic, 0.3, out)
    config, traffic = bench_helpers.tiny_serve()
    config.update(hidden_size=256, num_attention_heads=2,
                  num_key_value_heads=1, head_dim=128)
    traffic["trace_seconds"] = 0.05
    record("serve", serve, config, traffic, 0.3, out)


if __name__ == "__main__":
    main()
