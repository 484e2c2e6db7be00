"""Device time of the lane-scan program per trace step, in us.

The traced slice lies inside a batch's scan over blocks of ``block``
trace steps (the program's time blocking, from the run's stats).  Every
block runs the same device operations under the same instruction names,
whether the compiler keeps the block's inner loop or unrolls it, so the
instruction that recurs least often in the slice (three times or more)
marks one pass of the block body: the time from its first to its last
start, over the passes between them, is one block's device time, all of
it (the block's bulk gathers and its steps).  Divided by ``block``."""
from statistics import median


def read(trace, records, peaks):
    block = records.get("block")
    starts = {}
    for name, s, _ in trace.instrs:
        starts.setdefault(name, []).append(s)
    recurring = [sorted(v) for v in starts.values() if len(v) >= 3]
    if not block or not recurring:
        return None
    least = min(len(v) for v in recurring)
    passes = [(v[-1] - v[0]) / (len(v) - 1) for v in recurring
              if len(v) == least]
    return median(passes) / block * 1e6
