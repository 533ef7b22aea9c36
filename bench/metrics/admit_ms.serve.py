"""Mean wall time of the serving engine's ``serve.admit`` spans in the
traced window: how long the decode loop stops for each admitted prefill
batch (upload, prefill, first tokens read, splices). Every live slot's next
token and the new requests' first tokens wait for it."""
from bench import spans


def read(out, trace):
    return spans.mean_span_ms(trace, spans.ADMIT)
