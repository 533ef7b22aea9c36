"""Share of the traced window in which the device is idle while the
serving engine's loop works on the host: in any ``serve.*`` phase but
``serve.wait`` (reading each step's tokens, bookkeeping, dispatching,
admitting, scheduling). A decode loop that never waits on the host would
remove it."""
from bench import spans


def read(out, trace):
    shares = spans.idle_shares(trace)
    return None if shares is None else shares["host"]
