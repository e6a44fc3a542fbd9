"""Admission: 90th percentile of (start of the tick that admitted a
request - its due time), over the requests due in the window and served
before the profiler started."""
from bench import window


def read(rec):
    start = {t.tick: t.start for t in rec.ticks}
    due = rec.quiet_due()
    waits = [start[a.tick] - due[a.rid] for a in rec.admits if a.rid in due]
    return window.percentile(waits, 90) if waits else None
