"""Model FLOPs of every token the engine processed in the traced window
(prompt tokens at prefill, decoded tokens at decode, each with attention
over its context), over the window's length times the chip's bf16 peak,
in %."""
from bench import counts


def read(trace, records, peaks):
    steps = [s for s in records.get("steps", []) if s["traced"]]
    if not steps or trace.window_s <= 0:
        return None
    cfg = records["config"]
    flops = sum(counts.prefill_flops(cfg, n) for s in steps
                for n in s["prefill"])
    flops += sum(counts.token_flops(cfg, n) for s in steps
                 for n in s["decode_ctx"])
    return 100.0 * flops / (trace.window_s * peaks["bf16_flops_per_s"])
