"""The reduction from a profiler trace to the per-layer metrics' inputs:
its interval arithmetic on hand-made events, and the readers on traces of
the shapes the programs give."""
import os

import pytest

from bench import trace_reduce as T
from bench.run import BENCH, load_module

STEP_US = load_module(os.path.join(BENCH, "layer_metrics",
                                   "sweep.step_device_us.py"), "step_us")


def test_names():
    assert T.instr_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == \
        "fusion.12"
    assert T.op_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == "fusion"
    assert T.op_name("%_paged_attention_jit.20 = (f32[16,8]) custom-call()"
                     ) == "_paged_attention_jit"
    assert T.module_name("jit_decode_step_paged(9538280728791682622)") == \
        "jit_decode_step_paged"


def test_merge_and_gaps():
    assert T.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                              (3, 4)]
    tr = T.Trace(window_s=5.0, busy_s=3.5, ops=[], modules=[], spans=[],
                 host=[("Execute", 2.6, 0.3)], busy=[(0, 2.5), (3, 4)])
    assert T.idle_gaps(tr) == [(2.5, 3), (4, 5.0)]
    assert T.busy_within(tr, 2.0, 3.5) == pytest.approx(1.0)
    assert T.gap_label(tr, 2.5, 3) == "Execute"
    assert T.gap_label(tr, 4, 5) == "between spans"


def test_self_times_subtract_nested_ops():
    ops = [("while", 0.0, 10.0), ("fusion", 1.0, 2.0), ("copy", 4.0, 1.0),
           ("fusion", 12.0, 1.0)]
    st = T.self_times(ops)
    assert st == {"while": pytest.approx(7.0), "fusion": pytest.approx(3.0),
                  "copy": pytest.approx(1.0)}


def test_breakdown_lists_at_most_ten():
    ops = [(f"op{i}", float(i), 0.5) for i in range(20)]
    tr = T.Trace(window_s=20.0, busy_s=10.0, ops=ops, modules=[], spans=[],
                 host=[], busy=T.merge([(s, s + d) for _, s, d in ops]))
    b = T.breakdown(tr)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert all(g[1] == pytest.approx(0.5) for g in b["idle_gaps"])


def _scan_slice(unrolled, block=32, block_s=0.01, offset=0.0037, n=12):
    """A slice of a scan over blocks of ``block`` steps, cut mid-block:
    the body either unrolled (every step's operations under names of
    their own) or a loop kept (the step's operations recur each step)."""
    ops = []
    for b in range(-1, n + 1):
        t = b * block_s - offset
        ops.append(("gather.1", t, 1e-4))
        for k in range(block):
            ts = t + 2e-4 + k * (block_s - 2e-4) / block
            if unrolled:
                ops += [(f"fusion.{10 + 2 * k}", ts, 1e-5),
                        (f"copy.{11 + 2 * k}", ts + 1e-5, 1e-5)]
            else:
                ops += [("fusion.3", ts, 1e-5), ("copy.4", ts + 1e-5, 1e-5)]
    window = n * block_s
    ops = [(name, s, d) for name, s, d in ops if 0 <= s <= window]
    return T.Trace(window_s=window, busy_s=window, ops=[], modules=[],
                   spans=[], host=[], busy=[(0, window)], instrs=ops)


@pytest.mark.parametrize("unrolled", [True, False],
                         ids=["unrolled", "loop_kept"])
def test_sweep_step_time_is_one_block_over_its_steps(unrolled):
    tr = _scan_slice(unrolled)
    got = STEP_US.read(tr, {"block": 32}, {})
    assert got == pytest.approx(0.01 / 32 * 1e6)


def test_sweep_step_time_reads_nothing_without_a_scan():
    tr = _scan_slice(True)
    assert STEP_US.read(tr, {"block": None}, {}) is None
    tr.instrs = tr.instrs[:2]
    assert STEP_US.read(tr, {"block": 32}, {}) is None


def test_a_trace_recorded_on_the_chip(tmp_path):
    """A serve step at test size traced on one TPU v5e chip
    (``tests/bench/record_traces.py``); the numbers were read from the
    file with ``jax.profiler.ProfileData`` alone."""
    import gzip
    import shutil
    src = os.path.join(os.path.dirname(__file__), "data",
                       "serve_tiny.xplane.pb.gz")
    with gzip.open(src) as f, open(tmp_path / "t.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    tr = T.load(str(tmp_path))
    assert tr.window_s == pytest.approx(0.020957328, abs=1e-9)
    assert tr.busy_s == pytest.approx(70975e-9, abs=1e-9)
    assert len(tr.ops) == len(tr.instrs) == 316
    assert tr.ops[0][0] == "copy" and tr.instrs[0][0] == "copy.1"
    assert len(tr.modules) == 36
    assert T.module_time(tr, "jit_prefill")[1] == 1
    assert T.module_time(tr, "jit_decode_step_paged")[1] == 1
    assert T.module_time(tr, "jit_dynamic_slice")[1] == 13
    assert [name for name, _, _ in tr.spans] == ["bench.serve.step"]
    b = T.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10
    assert sum(g for _, g in b["idle_gaps"]) <= tr.window_s - tr.busy_s
