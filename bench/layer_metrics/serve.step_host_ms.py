"""Host time of one engine step, in ms: the part of the benchmark's span
around ``ServingEngine.step`` in which no device operation runs, the mean
over the traced steps."""
from bench.trace_reduce import busy_within

SPAN = "bench.serve.step"


def read(trace, records, peaks):
    steps = [(s, d) for name, s, d in trace.spans if name == SPAN]
    if not steps:
        return None
    idle = [d - busy_within(trace, s, s + d) for s, d in steps]
    return sum(idle) / len(idle) * 1e3
