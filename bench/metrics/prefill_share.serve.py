"""Share of the device's busy time spent in prefill programs, in the
traced window."""
from bench import trace as tracing


def read(out, trace):
    busy = tracing.mean_busy_s(trace)
    secs, n = tracing.module_time(trace, "prefill")
    return 100.0 * secs / busy if busy and n else None
