"""Step program: time in the engine's ``serve.dispatch`` spans (the host's
launch of the jitted step: its weight tree and state leaves flattened
and enqueued) in the traced window, per traced tick."""
from bench import spans


def read(rec):
    return spans.per_tick_ms(rec, "serve.dispatch")
