"""Step program: the FLOPs the served requests required in the traced
ticks (bench/flops.py), over the traced window's seconds times the chips'
bf16 peak, in percent."""
from bench import window


def read(rec):
    if rec.trace_window is None:
        return None
    t0, t1, ticks = rec.trace_window
    busy = window.lanes_busy(rec.admits, rec.num_steps)
    flops = sum(busy.get(t, 0) for t in ticks) * rec.flops_per_lane_step
    return 100.0 * flops / ((t1 - t0) * rec.chips * rec.peak_flops)
