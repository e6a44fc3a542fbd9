"""Window and latency arithmetic on the spans a run logged.

Times are seconds on one host clock.  A tick is one engine step over all
lanes; a request admitted at tick ``a`` runs on ticks ``a .. a + steps - 1``
(the engine runs a tick whenever a lane is busy) and its sample reaches
the host as that last tick ends.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


@dataclass(frozen=True)
class Tick:
    tick: int
    start: float
    end: float
    slotted: bool = False


@dataclass(frozen=True)
class Admit:
    rid: int
    lane: int
    tick: int
    time: float


def window_ticks(ticks: Sequence[Tick], w0: float, seconds: float
                 ) -> Tuple[List[Tick], float]:
    """Ticks that start in [w0, w0 + seconds), and the window's end: the
    end of the last of them (so the window holds whole ticks and lasts at
    least ``seconds`` while the engine is busy)."""
    inside = [t for t in ticks if w0 <= t.start < w0 + seconds]
    return inside, max((t.end for t in inside), default=w0 + seconds)


def lanes_busy(admits: Iterable[Admit], num_steps: int) -> Dict[int, int]:
    """tick -> number of requests that take a step in it."""
    busy: Dict[int, int] = {}
    for a in admits:
        for t in range(a.tick, a.tick + num_steps):
            busy[t] = busy.get(t, 0) + 1
    return busy


def images_per_s(win: Sequence[Tick], w0: float, w1: float,
                 admits: Iterable[Admit], num_steps: int) -> float:
    """Images' worth of steps served in the window over its seconds: every
    request step of every tick in it, divided by the steps per image."""
    busy = lanes_busy(admits, num_steps)
    steps = sum(busy.get(t.tick, 0) for t in win)
    return steps / num_steps / (w1 - w0)


def done_times(ticks: Sequence[Tick], admits: Iterable[Admit],
               num_steps: int) -> Dict[int, float]:
    """rid -> time its sample reached the host (its last tick's end)."""
    end = {t.tick: t.end for t in ticks}
    out = {}
    for a in admits:
        last = a.tick + num_steps - 1
        if last in end:
            out[a.rid] = end[last]
    return out


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, ``q`` in [0, 100]."""
    v = sorted(values)
    if not v:
        return math.nan
    x = (len(v) - 1) * q / 100.0
    lo = math.floor(x)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (x - lo)


def latencies(due: Dict[int, float], done: Dict[int, float]
              ) -> Tuple[List[float], List[int]]:
    """Latency (done - due) of every due request that finished, and the
    rids that never did."""
    lat, missing = [], []
    for rid, t in due.items():
        if rid in done:
            lat.append(done[rid] - t)
        else:
            missing.append(rid)
    return lat, missing


def host_gap_ms(win: Sequence[Tick], w0: float, w1: float) -> Optional[float]:
    """Window time not inside a tick span, per tick, in milliseconds."""
    if not win:
        return None
    return (w1 - w0 - sum(t.end - t.start for t in win)) / len(win) * 1e3
