"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load` reads the newest ``.xplane.pb`` under a trace directory with
JAX's own reader.  The traced window runs from the start of the first to
the end of the last host span the benchmark put around its calls (names
starting ``bench.``).  Within it:

* ``ops``: every device operation (the devices' ``XLA Ops`` lines) as
  (name, start s, duration s), the name cut to its HLO instruction
  (``%fusion.3 = ...`` -> ``fusion``; a Pallas kernel shows under the name
  of the jitted function that calls it);
* ``instrs``: the same events under the instruction's own name
  (``fusion.3``), which every run of one loop body repeats;
* ``modules``: every device program run (``XLA Modules``), named without
  the hash (``jit_decode_step_paged``);
* ``spans``: the benchmark's host spans;
* ``busy_s``: the union of the intervals in which some operation runs,
  averaged over the devices that ran any; ``window_s`` its window.

Times are seconds from the window's start.  :func:`breakdown` gives the
device operations that took most time (self time: an operation's time
less the operations nested in it) and the longest idle gaps, each named by
the host activity that covers it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start s, duration s)

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    window_s: float
    busy_s: float
    ops: List[Event]
    modules: List[Event]
    spans: List[Event]
    host: List[Event]                     # other host activity
    busy: List[Tuple[float, float]]       # merged busy intervals, device 0
    instrs: List[Event] = dataclasses.field(default_factory=list)


def op_name(full: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion``."""
    head = full.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def instr_name(full: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return full.split(" = ", 1)[0].strip().lstrip("%")


def module_name(full: str) -> str:
    return re.sub(r"\(\d+\)$", "", full)


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str, span=None, path: Optional[str] = None) -> Trace:
    """Reduce the newest trace under ``trace_dir`` (or the file ``path``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path or newest_xplane(trace_dir))
    spans_ns: List[Tuple[str, int, int]] = []
    host_ns: List[Tuple[str, int, int]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    rec = (e.name, e.start_ns, e.duration_ns)
                    (spans_ns if e.name.startswith(SPAN_PREFIX)
                     else host_ns).append(rec)
        elif re.match(r"/device:(TPU|GPU):\d+$", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = [(e.name, e.start_ns, e.duration_ns)
                   for e in lines["XLA Ops"].events] \
                if "XLA Ops" in lines else []
            mods = [(e.name, e.start_ns, e.duration_ns)
                    for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            if ops or mods:
                devices.append((ops, mods))
    if not spans_ns:
        raise ValueError("the trace holds no bench.* host span")
    t0 = min(s for _, s, _ in spans_ns)
    t1 = max(s + d for _, s, d in spans_ns)
    window = (t1 - t0) / 1e9

    def clip(events, name_fn) -> List[Event]:
        out = []
        for name, s, d in events:
            a, b = max(s, t0), min(s + d, t1)
            if b > a or (d == 0 and t0 <= s <= t1):
                out.append((name_fn(name), (a - t0) / 1e9, (b - a) / 1e9))
        return out

    busy_total = 0.0
    first_busy: List[Tuple[float, float]] = []
    ops0: List[Event] = []
    mods0: List[Event] = []
    instrs0: List[Event] = []
    for n, (ops, mods) in enumerate(devices):
        o = clip(ops, op_name)
        iv = merge([(s, s + d) for _, s, d in o])
        busy_total += sum(b - a for a, b in iv)
        if n == 0:
            first_busy, ops0, mods0 = iv, o, clip(mods, module_name)
            instrs0 = clip(ops, instr_name)
    return Trace(window_s=window,
                 busy_s=busy_total / max(len(devices), 1),
                 ops=ops0, modules=mods0,
                 spans=clip(spans_ns, str), host=clip(host_ns, str),
                 busy=first_busy, instrs=instrs0)


def self_times(ops: List[Event]) -> Dict[str, float]:
    """Total self time per operation name: its duration less that of the
    operations nested inside it."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    self_t = [d for _, _, d in order]
    stack: List[int] = []
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            self_t[p] -= min(d, order[p][1] + order[p][2] - s)
        stack.append(i)
    out: Dict[str, float] = {}
    for (name, _, _), t in zip(order, self_t):
        out[name] = out.get(name, 0.0) + max(t, 0.0)
    return out


def idle_gaps(trace: Trace) -> List[Tuple[float, float]]:
    """The window's intervals with no device operation running."""
    gaps, at = [], 0.0
    for a, b in trace.busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if trace.window_s > at:
        gaps.append((at, trace.window_s))
    return gaps


def gap_label(trace: Trace, a: float, b: float) -> str:
    """The host activity covering most of an idle gap: the innermost
    (shortest) host event that covers its middle, else the span."""
    mid = (a + b) / 2
    covering = [(d, name) for name, s, d in trace.host if s <= mid <= s + d]
    if covering:
        return min(covering)[1]
    spans = [name for name, s, d in trace.spans if s <= mid <= s + d]
    return spans[0] if spans else "between spans"


def breakdown(trace: Trace, top: int = 10) -> dict:
    st = self_times(trace.ops)
    ops = sorted(st.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[name, t] for name, t in ops],
            "idle_gaps": [[gap_label(trace, a, b), b - a] for a, b in gaps]}


def module_time(trace: Trace, name: str) -> Tuple[float, int]:
    """Total device time and number of runs of the programs ``name``."""
    hits = [d for n, _, d in trace.modules if n == name]
    return sum(hits), len(hits)


def busy_within(trace: Trace, a: float, b: float) -> float:
    return sum(max(0.0, min(y, b) - max(x, a)) for x, y in trace.busy)
