"""Device time the engine spends outside its decode step, per request
admitted in the traced window, in ms: the prefill program and the page
writes that copy the prompt's KV into the pool, with the small programs
around them (slicing, argmax)."""
from bench.trace_reduce import module_time

DECODE = "jit_decode_step_paged"


def read(trace, records, peaks):
    admitted = sum(len(s["prefill"]) for s in records.get("steps", [])
                   if s["traced"])
    if not admitted:
        return None
    other = sum(d for _, _, d in trace.modules) - module_time(trace,
                                                              DECODE)[0]
    return other / admitted * 1e3
