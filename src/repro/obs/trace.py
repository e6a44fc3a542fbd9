"""Host-side step tracer emitting Chrome-trace-event JSON.

The output loads directly in Perfetto / chrome://tracing: a top-level
``{"traceEvents": [...], "displayTimeUnit": "ms"}`` object whose events
are complete spans (``ph == "X"`` with microsecond ``ts``/``dur``),
instants (``ph == "i"``), and counter samples (``ph == "C"``).

Host phases traced by the serving stack: plan build, per-variant jit
compile, the engine's per-tick phases (``serve.*`` spans, see
``repro.launch.serve.serve_continuous``), admission and completion
instants, paging ``io_callback`` fetches (emitted from the ExpertPool's
fetch thread — the tracer is lock-protected), and step execution.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name:
while ``jax.profiler`` records, it lands in the capture as a host event
on the device trace's clock, with no alignment step; with no profiler
running it costs about a microsecond.  The device side carries the
model's ``jax.named_scope`` names (``layer_NN/attn``, ``router``, ...)
in its ops' metadata, whatever the observability setting.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

import jax


class StepTracer:
    """Thread-safe collector of Chrome trace events.

    All timestamps are microseconds relative to tracer construction,
    taken from ``time.perf_counter()``; ``origin_unix_ns`` is that origin
    on the wall clock.  ``tid`` is the emitting thread, so paging fetches
    land on their own track.
    """

    def __init__(self, pid: int = 1):
        self.pid = pid
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self.origin_unix_ns = time.time_ns()
        self.events = []

    # -- time ------------------------------------------------------------
    def now(self) -> float:
        """Microseconds since tracer start (also usable as a span start
        handle for :meth:`complete`)."""
        return (time.perf_counter() - self._t0) * 1e6

    def _emit(self, ev: dict) -> None:
        with self._lock:
            self.events.append(ev)

    def _base(self, name: str, cat: str) -> dict:
        return {"name": name, "cat": cat, "pid": self.pid,
                "tid": threading.get_ident() & 0xFFFF}

    # -- emitters ----------------------------------------------------------
    @contextmanager
    def span(self, name: str, cat: str = "host",
             args: Optional[Dict] = None):
        """Complete-event span around a ``with`` block, entered as a
        ``jax.profiler.TraceAnnotation`` of the same name.  ``args`` is
        read as the block ends, so the block may fill it in."""
        t0 = self.now()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield self
        finally:
            ev = self._base(name, cat)
            ev.update(ph="X", ts=t0, dur=self.now() - t0,
                      args=dict(args or {}))
            self._emit(ev)

    def complete(self, name: str, start_us: float, cat: str = "host",
                 args: Optional[Dict] = None) -> None:
        """Span from a :meth:`now` handle to now (for call sites where a
        ``with`` block is awkward, e.g. inside locked sections)."""
        ev = self._base(name, cat)
        ev.update(ph="X", ts=start_us, dur=self.now() - start_us,
                  args=dict(args or {}))
        self._emit(ev)

    def instant(self, name: str, cat: str = "host",
                args: Optional[Dict] = None) -> None:
        ev = self._base(name, cat)
        ev.update(ph="i", ts=self.now(), s="t", args=dict(args or {}))
        self._emit(ev)

    def counter(self, name: str, value: float, cat: str = "host") -> None:
        ev = self._base(name, cat)
        ev.update(ph="C", ts=self.now(), args={name: float(value)})
        self._emit(ev)

    # -- export ------------------------------------------------------------
    def to_json(self) -> dict:
        with self._lock:
            events = list(self.events)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"origin_unix_ns": self.origin_unix_ns}}

    def write(self, path) -> None:
        with open(path, "w") as f:
            # args may carry arbitrary config objects; stringify rather
            # than fail the export
            json.dump(self.to_json(), f, indent=1, default=str)
            f.write("\n")
