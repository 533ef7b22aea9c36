"""Programs compiled, or loaded from the persistent compile cache, inside
the measured window (JAX monitoring events). Should read 0: every shape
is warmed in set-up, and one that is not stalls the requests behind it."""


def read(out, trace):
    return float(out.window_compiles)
