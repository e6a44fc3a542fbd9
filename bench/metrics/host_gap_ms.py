"""Serving engine: window time outside the engine's tick spans, per tick
(admission, slot surgery, the per-tick read-back of the step's aux),
before the profiler started."""
from bench import window


def read(rec):
    win, end = rec.quiet_window()
    return window.host_gap_ms(win, rec.w0, end)
