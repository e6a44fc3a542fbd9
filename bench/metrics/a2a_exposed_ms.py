"""MoE collectives: the part of the collectives' device time in which no
other operation ran on that chip, per traced tick, on the worst chip."""
from bench import trace_reduce as tr


def read(rec):
    if rec.trace is None or rec.trace_window is None:
        return None
    t0, t1, ticks = rec.trace_window
    per = tr.collective_s(rec.trace, t0, t1)
    if not any(c > 0 for c, _ in per.values()):
        return None
    return 1e3 * max(e for _, e in per.values()) / len(ticks)
