"""Device time of one decode step: the traced window's device time in
programs named for decode, over their number."""
from bench import trace as tracing


def read(out, trace):
    secs, n = tracing.module_time(trace, "decode")
    return 1e3 * secs / n if n else None
