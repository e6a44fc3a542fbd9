"""MoE collectives: device time inside all-to-all and collective-permute
operations per traced tick, on the chip that spends the most."""
from bench import trace_reduce as tr


def read(rec):
    if rec.trace is None or rec.trace_window is None:
        return None
    t0, t1, ticks = rec.trace_window
    per = tr.collective_s(rec.trace, t0, t1)
    worst = max((c for c, _ in per.values()), default=0.0)
    return 1e3 * worst / len(ticks) if worst > 0 else None
