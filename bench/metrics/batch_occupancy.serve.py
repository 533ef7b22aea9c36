"""Share of decode-slot steps that carried a request over the window:
the engine's ``occupied_slot_steps`` over ``decode_steps`` times the
capacity, both as window deltas of its counters."""


def read(out, trace):
    c = out.counters
    if not c["decode_steps"]:
        return None
    return 100.0 * c["occupied_slot_steps"] / (c["decode_steps"] * c["capacity"])
