"""The sweep's span metrics (``bench/layer_metrics/sweep.*``) that read the
program's in-memory spans: hand arithmetic on a hand-built record, nothing
on an empty one, and all four in a traced run of the sweep driver."""
import os

import pytest
from bench_helpers import CPU, PEAKS, spec_of

from bench import run as harness
from bench.drivers import sweep
from bench.run import BENCH, load_module
from repro import spans

NAMES = ("sweep.pack_s", "sweep.transfer_s", "sweep.outside_scan_share",
         "sweep.padded_step_share")
READERS = {n: load_module(os.path.join(BENCH, "layer_metrics", n + ".py"),
                          "span_metric_" + n.replace(".", "_"))
           for n in NAMES}
T0 = 1_700_000_000 * 10**9          # a realtime clock's nanoseconds
S = 10**9


def _batch(first_id, start, pack, upload, scan, readback, real, scanned):
    """One batch's spans, laid end to end from ``start`` s: pack, upload,
    scan, readback (seconds each)."""
    b, p = first_id, first_id + 1
    t = [start]
    for d in (pack, upload, scan, readback):
        t.append(t[-1] + d)
    ns = [T0 + int(round(x * S)) for x in t]
    return [
        spans.Record(p + 1, p, "repro.sweep.pack.maps", ns[0], ns[1], {}),
        spans.Record(p, b, "repro.sweep.pack", ns[0], ns[1], {}),
        spans.Record(p + 2, b, "repro.sweep.upload", ns[1], ns[2], {}),
        spans.Record(p + 3, b, "repro.sweep.scan", ns[2], ns[3], {}),
        spans.Record(p + 4, b, "repro.sweep.readback", ns[3], ns[4], {}),
        spans.Record(b, None, "repro.sweep.batch", ns[0], ns[4],
                     {"steps_real": real, "steps_scanned": scanned}),
    ]


def _record():
    """A set-up batch (0-20 s), then two window batches (22-32 s and
    32-40 s) in a window of 18 s that ends with the last."""
    return (_batch(1, 0.0, 5.0, 1.0, 13.0, 1.0, 100, 400)
            + _batch(10, 22.0, 2.0, 0.5, 7.0, 0.5, 300, 400)
            + _batch(20, 32.0, 3.0, 0.25, 4.0, 0.75, 200, 400))


@pytest.fixture
def hand_built(monkeypatch):
    rec = _record()
    monkeypatch.setattr(spans, "recorded", lambda: list(rec))
    return {"driver": "sweep", "window_s": 18.0, "block": 32}


def _read(name, records):
    return READERS[name].read(None, records, PEAKS)


def test_pack_s_is_the_median_over_the_window(hand_built):
    # the set-up batch's 5 s is left out: median of 2 and 3
    assert _read("sweep.pack_s", hand_built) == pytest.approx(2.5)


def test_transfer_s_sums_upload_and_readback(hand_built):
    # per batch 0.5 + 0.5 and 0.25 + 0.75
    assert _read("sweep.transfer_s", hand_built) == pytest.approx(1.0)


def test_outside_scan_share_clips_to_the_window(hand_built):
    # the window is 22-40 s; scans 24.5-31.5 s and 35.25-39.25 s
    assert _read("sweep.outside_scan_share", hand_built) == \
        pytest.approx(1 - (7.0 + 4.0) / 18.0)


def test_padded_step_share_sums_over_the_window(hand_built):
    # (300 + 200) real of 800 scanned; the set-up batch's 100 left out
    assert _read("sweep.padded_step_share", hand_built) == \
        pytest.approx(1 - 500 / 800)


def test_a_batch_that_raised_is_left_out(monkeypatch):
    rec = _record()
    raised = rec[-1]._replace(attrs=dict(rec[-1].attrs, error="E"))
    rec = rec[:-1] + [raised]
    monkeypatch.setattr(spans, "recorded", lambda: list(rec))
    # the window now ends with the batch at 22-32 s
    got = _read("sweep.padded_step_share", {"window_s": 10.0})
    assert got == pytest.approx(1 - 300 / 400)


@pytest.mark.parametrize("name", NAMES)
def test_a_window_the_ring_cut_is_none(monkeypatch, name):
    rec = _record()
    monkeypatch.setattr(spans, "recorded", lambda: list(rec))
    monkeypatch.setattr(spans, "dropped", lambda: 6)
    # the oldest kept span (the set-up batch's, at 0 s) starts before the
    # window (22-40 s): nothing of the window was dropped
    assert _read(name, {"window_s": 18.0}) is not None
    # the ring dropped the set-up batch and the first window batch's
    # pack: the oldest kept span, its upload, starts at 24 s
    rec = rec[8:]
    assert rec[0].name == "repro.sweep.upload"
    assert _read(name, {"window_s": 18.0}) is None


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_is_none(monkeypatch, name):
    monkeypatch.setattr(spans, "recorded", lambda: [])
    assert _read(name, {"driver": "sweep", "window_s": 18.0}) is None
    monkeypatch.setattr(spans, "recorded", _record)
    assert _read(name, {"driver": "sweep", "window_s": None}) is None
    # a window shorter than the last batch holds no batch
    assert _read(name, {"driver": "sweep", "window_s": 1.0}) is None


def test_a_traced_sweep_reports_the_four(tiny_sweep, tmp_path):
    config, traffic = tiny_sweep
    per_layer = [{"name": n, "unit": "u"} for n in NAMES]
    spec = spec_of(config, traffic, ["accesses_per_s", "setup_s"],
                   per_layer)
    ctx = harness.Context(2**31 + 5, 1.0, True, str(tmp_path / "trace"))
    res = harness.execute(spec, sweep, ctx, CPU, PEAKS)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == set(NAMES)
    got = {n: m["value"] for n, m in res["metrics"].items()}
    # 8 cells of 1,500 accesses in 32 lanes (the lane floor) of the
    # 2,048-step bucket
    assert got["sweep.padded_step_share"] == \
        pytest.approx(1 - 8 * 1500 / (32 * 2048))
    assert got["sweep.pack_s"] > 0 and got["sweep.transfer_s"] > 0
    assert 0 <= got["sweep.outside_scan_share"] < 1
