"""Device time of admitting one request in the traced window, in ms: the
prefill program and the page writes that copy the prompt's KV into the
pool, with the small programs around them.

Each step (the benchmark's span around ``ServingEngine.step``) runs the
decode step and, after it, the slice and argmax that pick its tokens; a
step that admits also runs a ``jit_prefill`` per request and its page
writes.  So the time of every program but the decode step, summed over
the admitting steps, less what the steps that admit nothing spend outside
the decode step, is divided by the number of prefills."""

DECODE = "jit_decode_step_paged"
PREFILL = "jit_prefill"
SPAN = "bench.serve.step"


def read(trace, records, peaks):
    admitting, plain = [], []
    for name, s, d in trace.spans:
        if name != SPAN:
            continue
        mods = [(n, md) for n, ms, md in trace.modules if s <= ms < s + d]
        other = sum(md for n, md in mods if n != DECODE)
        prefills = sum(n == PREFILL for n, _ in mods)
        (admitting if prefills else plain).append((prefills, other))
    if not admitting or not plain:
        return None
    per_step = sum(o for _, o in plain) / len(plain)
    extra = sum(o - per_step for _, o in admitting)
    return extra / sum(p for p, _ in admitting) * 1e3
