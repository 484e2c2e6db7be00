"""Share of the window in which the lane-scan program does not run: one
less the time the program's ``repro.sweep.scan`` spans cover within the
window, over the window (see ``bench/program_spans.py``)."""
from bench.program_spans import window


def read(trace, records, peaks):
    w = window(records)
    if w is None:
        return None
    scanned = sum(max(0.0, min(r.end_ns, w.end_ns) - max(r.start_ns,
                                                         w.start_ns))
                  for r in w.spans if r.name == "repro.sweep.scan")
    return 1 - scanned / (w.end_ns - w.start_ns)
