"""Share of the trace steps the lane-scan program runs that are padding:
one less the real steps over the steps scanned, summed over the window's
batches (the ``steps_real`` and ``steps_scanned`` of the program's
``repro.sweep.batch`` spans; see ``bench/program_spans.py``)."""
from bench.program_spans import window


def read(trace, records, peaks):
    w = window(records)
    if w is None:
        return None
    return 1 - (sum(b.attrs["steps_real"] for b in w.batches)
                / sum(b.attrs["steps_scanned"] for b in w.batches))
