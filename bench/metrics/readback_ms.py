"""Serving engine: time in the engine's ``serve.readback`` spans (the
per-tick read-back of the step's aux to the host and the registry
updates that consume it) in the traced window, per traced tick."""
from bench import spans


def read(rec):
    return spans.per_tick_ms(rec, "serve.readback")
