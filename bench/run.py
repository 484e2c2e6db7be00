#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the accelerator.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell's entry in
``BENCHMARK.json`` names a configuration (its ``file``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names its driver
(``bench/drivers/<driver>.py``); each per-layer metric is read by
``bench/layer_metrics/<metric>.py``.  A new cell, configuration or metric is
therefore new files and new ``BENCHMARK.json`` entries, never an edit here.

The run exits non-zero, printing no result, unless JAX's devices are
accelerators and there are at least as many as the cell asks for.  Set-up
(imports, weights or data, warming every shape the window uses) ends when
the window opens; the window lasts ``--seconds``; then the driver checks
what the window produced against the plain reference.  The last line of
standard output is the result object; the compared numbers, each with its
limit, are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")


def load_module(path: str, name: str):
    """Import a file of the benchmark by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """The cell's entries: workload, configuration, traffic and metrics."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(m):
        return workload in m.get("workloads", [workload])

    return dict(cell=cell, config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


class Context:
    """What a driver is handed: the run's knobs, and the hooks that mark
    the window and the traced interval."""

    def __init__(self, seed: int, seconds: float, trace: bool,
                 trace_dir: str):
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.trace_dir = trace_dir
        self.t_window = None          # wall clock at the window's start
        self.tracing = False
        self._lock = threading.Lock()
        self.lines = []               # earlier lines of standard output

    def log(self, msg: str) -> None:
        print(msg, flush=True)
        self.lines.append(msg)

    def window_opens(self) -> None:
        self.t_window = time.time()

    def start_trace(self) -> None:
        if self.trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans come from us
            with self._lock:
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self.tracing = True

    def stop_trace(self) -> None:
        """Stop the profiler if it runs; any thread may call it."""
        with self._lock:
            if self.tracing:
                import jax
                jax.profiler.stop_trace()
                self.tracing = False


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def load_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; have {sorted(table)}")
    return table[kind]


def format_checks(checks: dict) -> list:
    return [f"check {name}: {c['value']!r} (limit {c['limit']!r})"
            for name, c in checks.items()]


def checks_pass(checks: dict) -> bool:
    return bool(checks) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())


def layer_metrics(per_layer: list, trace, records: dict, peaks: dict
                  ) -> dict:
    """Each per-layer metric from its reader; a reader that finds nothing
    to read returns None and the metric is left out."""
    out = {}
    for m in per_layer:
        reader = load_module(os.path.join(BENCH, "layer_metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(trace, records, peaks)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(spec: dict, driver, ctx: Context, dev: dict, peaks: dict,
            **driver_kw) -> dict:
    """Run the driver, reduce its trace if any, and build the result
    object; its last key holds the compared numbers with their limits."""
    try:
        res = driver.run(spec["config"], spec["traffic"], ctx, **driver_kw)
    finally:
        ctx.stop_trace()
    setup_s = ctx.t_window - T_START
    memory_peak = res["memory_peak_bytes"]
    ctx.log(f"[run] setup {setup_s!r} s; compiles in window "
            f"{res['compiles_in_window']}; peak device memory "
            f"{memory_peak} B")
    checks = res["checks"]
    errors = res.get("check_errors", [])
    result = {"correct": checks_pass(checks) and not errors,
              "attempted": res["attempted"], "failed": res["failed"]}
    device = dict(dev, memory_peak_bytes=memory_peak)
    if ctx.trace:
        from bench import trace_reduce
        trace = trace_reduce.load(ctx.trace_dir)
        metrics = layer_metrics(spec["per_layer"], trace, res["records"],
                                peaks)
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
        result["breakdown"] = trace_reduce.breakdown(trace)
    else:
        metrics = {}
        for m in spec["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else \
                res["end_to_end"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device, checks=checks,
                  check_errors=errors)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    spec = load_cell(args.workload)
    chips = spec["cell"]["chips"]
    driver = load_module(os.path.join(
        BENCH, "drivers", spec["traffic"]["driver"] + ".py"),
        "bench_driver_" + spec["traffic"]["driver"])

    # the compile cache lives at a fixed path inside the checkout; the
    # program's own entry points take it from the environment.  The TPU
    # runtime's logs would go to a fixed path outside it.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    platforms = os.environ.get("JAX_PLATFORMS")
    if driver.NEEDS_HOST_CPU and platforms and \
            "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    t_import = time.time()

    dev = device_info(jax)
    if dev["platform"] not in ("tpu", "gpu") or dev["count"] < chips:
        print(f"bench: needs {chips} accelerator chip(s); JAX has "
              f"{dev['count']} {dev['platform']} device(s) "
              f"({dev['kind']})", file=sys.stderr)
        return 2
    peaks = load_peaks(dev["kind"])
    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(args.seed, args.seconds, bool(args.trace), trace_dir)
    ctx.log(f"[device] {dev['platform']} {dev['kind']} x{dev['count']}; "
            f"workload {args.workload} seed {args.seed} seconds "
            f"{args.seconds} trace {args.trace}; compile cache {CACHE_DIR}; "
            f"JAX imported {t_import - T_START:.3f} s and its devices found "
            f"{time.time() - T_START:.3f} s after start")
    try:
        result = execute(spec, driver, ctx, dev, peaks)
    except Exception:
        traceback.print_exc()
        return 1
    for err in result.pop("check_errors"):
        print(f"check error: {err}", file=sys.stderr)
    for line in format_checks(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
