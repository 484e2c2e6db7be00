"""Device time of one decode step: the mean run of the engine's jitted
decode program (``jit_decode_step_paged``) in the traced window, in ms."""
from bench.trace_reduce import module_time

PROGRAM = "jit_decode_step_paged"


def read(trace, records, peaks):
    total, runs = module_time(trace, PROGRAM)
    return total / runs * 1e3 if runs else None
