"""Device: share of the traced window in which no operation ran, averaged
over the chips, in percent."""
from bench import trace_reduce as tr


def read(rec):
    if rec.trace is None or rec.trace_window is None:
        return None
    t0, t1, _ = rec.trace_window
    busy = tr.busy_s(rec.trace, t0, t1)
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (t1 - t0))
