"""The benchmark's operation and byte counts, on hand-worked shapes."""
import json
import os

import pytest

from bench import counts, trace_reduce
from bench.run import ROOT, load_module

with open(os.path.join(ROOT, "bench", "configs", "internlm2-1.8b.json")) as f:
    FULL = json.load(f)
with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
    V5E = json.load(f)["devices"]["TPU v5 lite"]
TINY = dict(hidden_size=8, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=2, intermediate_size=16,
            vocab_size=10, arch="bench/arch/dense.py")


def test_matmul_params_of_internlm2():
    # per layer: wq 2048x2048, wk and wv 2048x1024, wo 2048x2048 and
    # three 2048x8192 feed-forward matrices; then the 2048x92544 head
    layer = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 8192
    assert layer == 62_914_560
    assert counts.matmul_params(FULL) == 24 * layer + 2048 * 92544
    assert counts.matmul_params(FULL) == 1_699_479_552


def test_token_and_prefill_flops():
    # TINY: q = 8, kv = 4 -> layer 8*8 + 2*8*4 + 8*8 + 3*8*16 = 576
    assert counts.matmul_params(TINY) == 2 * 576 + 8 * 10
    # attention: 2 layers x 4 (QK and PV, 2 FLOPs each) x 4 heads x 2 dims
    assert counts.attention_flops(TINY, 1) == 64
    assert counts.token_flops(TINY, 5) == 2 * 1232 + 64 * 5
    # three prompt tokens attend over 1, 2 and 3 positions
    assert counts.prefill_flops(TINY, 3) == 3 * 2 * 1232 + 64 * 6


def test_paged_attention_work_counts_live_tokens_only():
    flops, nbytes = counts.paged_attention_work(TINY, [17])
    # per layer: K and V of 17 tokens (2 KV heads x 2 dims x 2 bytes),
    # the query and the output (4 heads x 2 dims x 2 bytes each)
    assert nbytes == 2 * (2 * 17 * 2 * 2 * 2 + 2 * 4 * 2 * 2)
    assert flops == 64 * 17
    # a 17th token costs one token's K and V in each layer, not a second
    # 16-token page, however the pool pages or the kernel windows lie
    _, b16 = counts.paged_attention_work(TINY, [16])
    assert nbytes - b16 == 2 * 2 * 2 * 2 * 2
    # requests add up
    f2, b2 = counts.paged_attention_work(TINY, [17, 16])
    assert (f2, b2) == (flops + 64 * 16, nbytes + b16)


def test_least_seconds_takes_the_bound_that_binds():
    assert counts.least_seconds(197e12, 0, V5E) == pytest.approx(1.0)
    assert counts.least_seconds(0, 819e9, V5E) == pytest.approx(1.0)
    assert counts.least_seconds(197e12, 2 * 819e9, V5E) == \
        pytest.approx(2.0)


def _reader(name):
    return load_module(os.path.join(ROOT, "bench", "layer_metrics",
                                    name + ".py"), "t_" + name)


def test_roofline_reader_on_a_synthetic_trace():
    # one traced decode step: a 1,000-token and a 3,000-token request;
    # the kernel's two class passes took 2 ms in all
    step = {"traced": True, "decode_ctx": [1000, 3000], "prefill": []}
    trace = trace_reduce.Trace(
        window_s=0.01, busy_s=0.005, modules=[], spans=[], host=[],
        busy=[], ops=[("_paged_attention_jit", 0.0, 0.0015),
                      ("_paged_attention_jit", 0.0015, 0.0005),
                      ("fusion", 0.002, 0.003)])
    records = {"steps": [step], "config": FULL}
    flops, nbytes = counts.paged_attention_work(FULL, [1000, 3000])
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 0.002
    got = _reader("serve.paged_attn_roofline").read(trace, records, V5E)
    assert got == pytest.approx(want)
    # no kernel in the trace: nothing to read, not a zero
    trace.ops = [("fusion", 0.0, 0.001)]
    assert _reader("serve.paged_attn_roofline").read(trace, records,
                                                     V5E) is None


def test_mfu_reader_counts_prefill_and_decode_tokens():
    step = {"traced": True, "decode_ctx": [10, 20], "prefill": [3]}
    trace = trace_reduce.Trace(window_s=2.0, busy_s=1.0, ops=[],
                               modules=[], spans=[], host=[], busy=[])
    records = {"steps": [step], "config": TINY}
    want = (counts.prefill_flops(TINY, 3) + counts.token_flops(TINY, 10)
            + counts.token_flops(TINY, 20)) / (2.0 * 197e12) * 100
    assert _reader("serve.mfu").read(trace, records, V5E) == \
        pytest.approx(want)


def test_idle_share_reader_is_a_fraction():
    trace = trace_reduce.Trace(window_s=2.0, busy_s=1.5, ops=[],
                               modules=[], spans=[], host=[], busy=[])
    read = _reader("serve.device_idle_share").read
    assert read(trace, {"driver": "serve"}, V5E) == pytest.approx(0.25)
    assert read(trace, {"driver": "sweep"}, V5E) is None


def test_prefill_reader_leaves_out_every_step_s_own_programs():
    # two traced steps: the first admits two requests (two prefills and
    # their page writes), the second admits none; both decode, then slice
    # and argmax their tokens (3 ms a step)
    span = "bench.serve.step"
    mods = [("jit_prefill", 0.000, 0.010), ("jit_prefill", 0.011, 0.010),
            ("jit_scatter", 0.022, 0.004), ("jit_scatter", 0.027, 0.004),
            ("jit_decode_step_paged", 0.032, 0.040),
            ("jit_dynamic_slice", 0.073, 0.001), ("jit_argmax", 0.075, 0.002),
            ("jit_decode_step_paged", 0.100, 0.040),
            ("jit_dynamic_slice", 0.141, 0.001), ("jit_argmax", 0.143, 0.002)]
    trace = trace_reduce.Trace(
        window_s=0.15, busy_s=0.1, ops=[], modules=mods, host=[], busy=[],
        spans=[(span, 0.0, 0.08), (span, 0.09, 0.06)])
    read = _reader("serve.prefill_ms").read
    assert read(trace, {}, V5E) == pytest.approx((10 + 4) * 1.0)
    # no step without an admission: nothing to take the steps' own
    # programs from, so nothing is read
    trace.spans = trace.spans[:1]
    assert read(trace, {}, V5E) is None
