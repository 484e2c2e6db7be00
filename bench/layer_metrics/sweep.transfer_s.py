"""Time moving one batch to the device and its results back, in s: the
median over the window's batches of the program's ``repro.sweep.upload``
and ``repro.sweep.readback`` spans, summed per batch (see
``bench/program_spans.py``)."""
from statistics import median

from bench.program_spans import children, seconds, window


def read(trace, records, peaks):
    w = window(records)
    if w is None:
        return None
    return median(sum(seconds(r) for name in ("repro.sweep.upload",
                                              "repro.sweep.readback")
                      for r in children(w, b, name)) for b in w.batches)
