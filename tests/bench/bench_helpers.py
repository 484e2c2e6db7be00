"""Helpers the benchmark's tests share."""
import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
    PEAKS = json.load(f)["devices"]["TPU v5 lite"]


def spec_of(config, traffic, end_to_end, per_layer=()):
    """A cell's entries as ``bench.run.load_cell`` gives them."""
    return {"config": copy.deepcopy(config),
            "traffic": copy.deepcopy(traffic),
            "end_to_end": [{"name": n, "unit": "u"} for n in end_to_end],
            "per_layer": list(per_layer)}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _cut_serve(config, traffic):
    """A dense decoder at a tiny width and a mix of short requests."""
    config.update(hidden_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                  intermediate_size=256, vocab_size=512)
    traffic.update(clients=4, trace_seconds=1,
                   prompts={"lengths": [16, 32], "counts": [1, 1]},
                   outputs={"min": 4, "max": 12, "median": 6})
    traffic["engine"].update(num_pages=128, max_batch=4, max_seq=256)
    traffic["check"]["tokens"] = 30


def _cut_sweep(config, traffic):
    """The roster over two small analogues."""
    traffic.update(benchmarks=[["mcf", "multiscale", 2048],
                               ["gups", "random", 1024]],
                   trace_len=1500, batch=8)


#: each driver's cut of a cell's own files to a size the CPU runs in
#: seconds, keyed by the driver's name
TINY = {"serve": _cut_serve, "sweep": _cut_sweep}


def tiny_serve():
    """internlm2's file at a tiny width, and the short mix cut to match."""
    config = _load("bench", "configs", "internlm2-1.8b.json")
    traffic = _load("bench", "traffic", "short.json")
    _cut_serve(config, traffic)
    return config, traffic


def tiny_sweep():
    """The paper suite's roster over two small analogues."""
    config = _load("bench", "configs", "tlb-paper-suite.json")
    traffic = _load("bench", "traffic", "paper.json")
    _cut_sweep(config, traffic)
    return config, traffic
