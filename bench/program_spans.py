"""The program's own spans (``repro.spans``), as the sweep's per-layer
metrics read them.

The program keeps its spans in memory, so these readers see spans that
started before the profiler did, such as the packing the traced slice
misses.  The window's batches are the ``repro.sweep.batch`` spans, less
any that raised, that start no earlier than the last one's end less the
window's length (``records["window_s"]``): the set-up batches end before
the window opens.  A program without ``repro.spans`` has nothing to read,
and neither has a record with no batch in the window, nor one whose ring
dropped spans and whose oldest kept span starts inside the window:
:func:`window` returns None for each.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

BATCH = "repro.sweep.batch"


class Window(NamedTuple):
    batches: list          # the window's batch spans
    spans: list            # every span the program recorded
    start_ns: float
    end_ns: float


def window(records: dict) -> Optional[Window]:
    try:
        from repro import spans
    except ImportError:
        return None
    rec = spans.recorded()
    window_s = records.get("window_s")
    batches = [r for r in rec if r.name == BATCH and "error" not in r.attrs]
    if not batches or not window_s:
        return None
    end = max(r.end_ns for r in batches)
    start = end - window_s * 1e9
    if spans.dropped() and rec[0].start_ns > start:
        return None        # the ring dropped part of the window
    mine = [b for b in batches if b.start_ns >= start]
    return Window(mine, rec, start, end) if mine else None


def children(w: Window, parent, name: str) -> List:
    return [r for r in w.spans if r.parent_id == parent.id and r.name == name]


def seconds(r) -> float:
    return (r.end_ns - r.start_ns) / 1e9
