"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window, averaged over the chips."""
from bench import trace as tracing


def read(out, trace):
    busy = tracing.mean_busy_s(trace)
    if busy is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)
