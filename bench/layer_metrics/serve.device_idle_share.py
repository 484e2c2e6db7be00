"""Share of the traced window in which no operation runs on the device,
a fraction of 1."""


def read(trace, records, peaks):
    if records.get("driver") != "serve" or trace.window_s <= 0:
        return None
    return 1.0 - trace.busy_s / trace.window_s
