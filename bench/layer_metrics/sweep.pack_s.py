"""Host time packing one batch, in s: the median over the window's
batches of the program's ``repro.sweep.pack`` span (``pack_lanes`` and the
initial state; see ``bench/program_spans.py``)."""
from statistics import median

from bench.program_spans import children, seconds, window


def read(trace, records, peaks):
    w = window(records)
    if w is None:
        return None
    return median(sum(seconds(p) for p in children(w, b, "repro.sweep.pack"))
                  for b in w.batches)
