"""End-to-end serving driver: batched requests through the paged engine.

The paper's kind is memory-system efficiency at serving time, so this is the
flagship e2e driver: a small LM served with continuous batching, a buddy
paged KV cache, Algorithm-3-chosen coalescing classes and the coalesced
Pallas paged-attention kernel (compiled on a TPU, interpreted elsewhere).

Run:  PYTHONPATH=src python examples/serve_paged.py [--requests 8]
"""
import argparse
import time

import numpy as np

from repro.configs import get_config
from repro.models import Model, RunConfig
from repro.serve import EngineConfig, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--arch", default="internlm2-1.8b")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=True)
    rc = RunConfig(attn_q_chunk=32, attn_kv_chunk=32, scan_chunk=16)
    model = Model(cfg, rc)
    params = model.init(0)

    ec = EngineConfig(page_size=8, num_pages=256, max_batch=4, max_seq=128)
    engine = ServingEngine(model, params, ec)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        prompt = list(rng.integers(0, cfg.vocab,
                                   size=int(rng.integers(8, 48))))
        rid = engine.add_request(prompt, max_new_tokens=args.max_new)
        print(f"request {rid}: prompt len {len(prompt)}")

    metrics = engine.run_to_completion()
    dt = time.time() - t0

    print(f"\nserved {args.requests} requests in {metrics['steps']} engine "
          f"steps ({dt:.1f}s wall, interpret={engine.interpret})")
    print(f"kernel classes K = {metrics['K']} (Algorithm 3 on the live "
          f"contiguity histogram)")
    print(f"DMA descriptors: {metrics['dma_descriptors']:.0f} coalesced vs "
          f"{metrics['dma_descriptors_page_granular']:.0f} page-granular "
          f"→ {metrics['descriptor_reduction']:.1%} reduction")
    for rid, req in sorted(engine.requests.items()):
        print(f"  req {rid}: {req.state}, generated {len(req.generated)} "
              f"tokens: {req.generated[:6]}…")


if __name__ == "__main__":
    main()
