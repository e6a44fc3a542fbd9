"""Serving engine: mean duration of the engine's ``serve.admit`` spans in
the traced window (queue pops, request noise written into the lanes, the
staleness rows of recycled lanes reset), in ms per admitting tick."""
from bench import spans


def read(rec):
    iv = spans.whole(rec, "serve.admit")
    if not iv:
        return None
    return 1e3 * sum(b - a for a, b in iv) / len(iv)
