"""The program's in-memory span record (``repro.spans``) and the spans the
sweep path records: nesting across threads, attributes, the ring's bound,
raising bodies.  Their agreement with the profiler's host events is
tested beside the benchmark's trace reader, in
``tests/bench/test_bench_program_spans.py``."""
import os
import re
import threading

import jax
import pytest

from repro import spans
from repro.core import base_spec, colt_spec, demand_mapping, generate_trace
from repro.core.lane_program import pack_lanes
from repro.core.sweep import SweepCell, run_sweep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SWEEP = ("repro.sweep.batch", "repro.sweep.pack",
         "repro.sweep.pack.maps", "repro.sweep.pack.fills",
         "repro.sweep.pack.clusters", "repro.sweep.pack.stacks",
         "repro.sweep.upload", "repro.sweep.scan", "repro.sweep.readback")


def test_nesting_and_parents_per_thread():
    rec = spans.Recorder()
    ready = threading.Barrier(2)

    def work(tag):
        with rec.span(f"repro.t{tag}.outer"):
            ready.wait()        # both outers open at once
            with rec.span(f"repro.t{tag}.inner"):
                pass

    threads = [threading.Thread(target=work, args=(t,)) for t in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_name = {r.name: r for r in rec.recorded()}
    assert len(by_name) == 4
    for tag in (0, 1):
        outer = by_name[f"repro.t{tag}.outer"]
        inner = by_name[f"repro.t{tag}.inner"]
        assert outer.parent_id is None
        assert inner.parent_id == outer.id
        assert outer.start_ns <= inner.start_ns <= inner.end_ns \
            <= outer.end_ns
    assert len({r.id for r in rec.recorded()}) == 4


def test_attributes_set_while_open():
    rec = spans.Recorder()
    with rec.span("repro.a", lanes=4) as s:
        s.set(steps=10)
        s.set(lanes=8)
    (r,) = rec.recorded()
    assert r.attrs == {"lanes": 8, "steps": 10}
    assert r.end_ns >= r.start_ns


def test_ring_keeps_the_newest_and_counts_drops():
    rec = spans.Recorder(capacity=4)
    for i in range(10):
        with rec.span(f"repro.n{i}"):
            pass
    assert [r.name for r in rec.recorded()] == [f"repro.n{i}"
                                                for i in range(6, 10)]
    assert rec.dropped == 6


def test_a_raising_body_is_recorded():
    rec = spans.Recorder()
    with pytest.raises(ValueError):
        with rec.span("repro.outer"):
            with rec.span("repro.inner", k=1):
                raise ValueError("x")
    inner, outer = rec.recorded()
    assert (inner.name, outer.name) == ("repro.inner", "repro.outer")
    assert inner.attrs == {"k": 1, "error": "ValueError"}
    assert inner.parent_id == outer.id
    with rec.span("repro.after"):        # the stack was unwound
        pass
    assert rec.recorded()[-1].parent_id is None


def test_recorded_is_a_copy():
    rec = spans.Recorder()
    with rec.span("repro.a"):
        pass
    got = rec.recorded()
    got.clear()
    assert len(rec.recorded()) == 1


def test_program_span_names_start_with_repro():
    """A ``bench.`` name would widen the benchmark's traced window."""
    src = os.path.join(ROOT, "src", "repro")
    names = []
    for d, _, files in os.walk(src):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    names += re.findall(r"\bspan\(\s*[\"']([^\"']*)",
                                        fh.read())
    assert len(names) >= len(SWEEP)
    assert all(n.startswith("repro.") for n in names), names


@pytest.fixture(scope="module")
def sweep_spans():
    """The spans of a tiny two-world sweep."""
    m = demand_mapping(1 << 11, seed=3)
    m2 = demand_mapping(1 << 10, seed=4)
    tr = generate_trace("multiscale", 0, 700, seed=5, mapping=m)
    tr2 = generate_trace("zipf", 0, 500, seed=6, mapping=m2)
    cells = [SweepCell(s, w, t) for w, t in ((m, tr), (m2, tr2))
             for s in (base_spec(), colt_spec())]
    mark = max((r.id for r in spans.recorded()), default=0)
    run_sweep(cells, cache=False)
    return cells, [r for r in spans.recorded() if r.id > mark]


def test_sweep_records_its_layers(sweep_spans):
    cells, mine = sweep_spans
    assert sorted({r.name for r in mine}) == sorted(SWEEP)
    by_id = {r.id: r for r in mine}
    (batch,) = [r for r in mine if r.name == "repro.sweep.batch"]
    assert batch.parent_id is None
    for name in ("repro.sweep.pack", "repro.sweep.upload",
                 "repro.sweep.scan", "repro.sweep.readback"):
        (r,) = [r for r in mine if r.name == name]
        assert r.parent_id == batch.id, name
    for r in mine:
        if r.name.startswith("repro.sweep.pack."):
            assert by_id[r.parent_id].name == "repro.sweep.pack"
        parent = by_id.get(r.parent_id)
        if parent:
            assert parent.start_ns <= r.start_ns <= r.end_ns \
                <= parent.end_ns
    lanes, stacks, (L, _, _), _ = pack_lanes(
        cells, device_count=jax.local_device_count())
    T = stacks["trace"].shape[1]
    assert set(batch.attrs) == {"steps_real", "steps_scanned",
                                "bytes_uploaded"}
    assert batch.attrs["steps_real"] == int(lanes["t_real"].sum()) \
        == 2 * 700 + 2 * 500
    assert batch.attrs["steps_scanned"] == L * T == 4 * 1024
    assert batch.attrs["bytes_uploaded"] > stacks["trace"].nbytes
