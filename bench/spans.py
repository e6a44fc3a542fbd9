"""The engine's own spans in a traced run.

``repro.obs.trace.StepTracer`` enters every span as a
``jax.profiler.TraceAnnotation`` of the same name, so the profile's host
events hold them (``serve.admit``, ``serve.dispatch``, ...) on the clock
of the device trace.  A program without such spans leaves none, and the
readers built on this module then return None.
"""
from __future__ import annotations

from typing import List, Optional

from bench import trace_reduce as tr


def clipped(rec, name: str) -> Optional[List[tr.Interval]]:
    """The host events named ``name`` in the traced window, clipped to it
    (None without a traced window)."""
    if rec.trace is None or rec.trace_window is None:
        return None
    t0, t1, _ = rec.trace_window
    return [(max(a, t0), min(b, t1)) for a, b, n, _line in rec.trace.host
            if n == name and a < t1 and b > t0]


def whole(rec, name: str) -> Optional[List[tr.Interval]]:
    """The host events named ``name`` that lie wholly in the traced
    window (None without a traced window)."""
    if rec.trace is None or rec.trace_window is None:
        return None
    t0, t1, _ = rec.trace_window
    return [(a, b) for a, b, n, _line in rec.trace.host
            if n == name and t0 <= a and b <= t1]


def per_tick_ms(rec, name: str) -> Optional[float]:
    """Seconds inside ``name`` spans in the traced window over its ticks,
    in milliseconds; None where the trace holds no such span."""
    iv = clipped(rec, name)
    if not iv:
        return None
    return 1e3 * sum(b - a for a, b in iv) / len(rec.trace_window[2])
