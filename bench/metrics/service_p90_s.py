"""Step program: 90th percentile of (sample on the host - start of the
tick that admitted it), over the requests due in the window and served
before the profiler started."""
from bench import window


def read(rec):
    start = {t.tick: t.start for t in rec.ticks}
    due = rec.quiet_due()
    serv = [rec.done[a.rid] - start[a.tick] for a in rec.admits
            if a.rid in due and a.rid in rec.done]
    return window.percentile(serv, 90) if serv else None
