"""The paged-attention kernel's share of its roofline, in %.

The least time is the larger of the FLOPs at the chip's bf16 peak and the
bytes at its HBM bandwidth, for the work the algorithm needs in each traced
decode step: the query, the output, and the K and V of every running
request's live tokens, in every layer, at the pool's 2-byte dtype
(``bench/counts.py``).  It comes from the live lengths the driver logged,
not from the kernel's grid.  The kernel's time is the device time of its
class passes (the custom calls named after ``_paged_attention_jit``, which
calls the Pallas kernel); the merge of their partial softmax states is
not part of it.
"""
from bench import counts

KERNEL = "_paged_attention_jit"


def read(trace, records, peaks):
    kernel = sum(d for name, _, d in trace.ops if name == KERNEL)
    steps = [s for s in records.get("steps", [])
             if s["traced"] and s["decode_ctx"]]
    if not kernel or not steps:
        return None
    least = 0.0
    for s in steps:
        flops, nbytes = counts.paged_attention_work(records["config"],
                                                    s["decode_ctx"])
        least += counts.least_seconds(flops, nbytes, peaks)
    return 100.0 * least / kernel
