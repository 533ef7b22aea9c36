"""Share of the traced window in which the device is idle while the
serving engine waits for work (``serve.wait``: no live slot, or the pause
gate). At a fixed offered rate it grows only when the work is done sooner."""
from bench import spans


def read(out, trace):
    shares = spans.idle_shares(trace)
    return None if shares is None else shares["no_work"]
