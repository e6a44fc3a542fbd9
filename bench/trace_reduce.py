"""From a profiler trace to the device metrics: busy and idle time, time
in collectives and the part of it no other operation hides, the longest
operations and the longest idle gaps.

:func:`load_profile` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a plain :class:`Profile`: per device, the intervals of the "XLA Ops"
line (one operation each); and the host threads' events.  The reductions
below take a Profile, whatever made it, so a small recorded one can be
checked by hand (``bench/testdata``).  All times are seconds.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# the expert exchange: dispatch/combine all-to-alls and the ring's hops
COLLECTIVE = re.compile(r"all-to-all|collective-permute")
TICK_MARK = re.compile(r"^bench_tick (\d+)$")


HLO_TEXT = re.compile(r"^%?([^\s=]+) = .*?\b([a-z][a-z0-9-]*)\(")


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12 (fusion)``:
    the instruction and its opcode, without shapes and operands."""
    m = HLO_TEXT.match(text)
    return f"{m.group(1)} ({m.group(2)})" if m else text[:120]


@dataclass
class Op:
    start: float
    end: float
    name: str


@dataclass
class Profile:
    devices: Dict[int, List[Op]] = field(default_factory=dict)
    host: List[Tuple[float, float, str, str]] = field(default_factory=list)
    ticks: Dict[int, Interval] = field(default_factory=dict)

    def to_json(self) -> dict:
        return {"devices": {str(k): [[o.start, o.end, o.name] for o in v]
                            for k, v in self.devices.items()},
                "host": [list(h) for h in self.host],
                "ticks": {str(k): list(v) for k, v in self.ticks.items()}}

    @staticmethod
    def from_json(d: dict) -> "Profile":
        return Profile(
            devices={int(k): [Op(*o) for o in v]
                     for k, v in d["devices"].items()},
            host=[tuple(h) for h in d["host"]],
            ticks={int(k): tuple(v) for k, v in d["ticks"].items()})


def load_profile(log_dir: str) -> Profile:
    """Read the newest ``*.xplane.pb`` under ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    prof = Profile()
    for plane in pd.planes:
        dm = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dm and line.name == OPS_LINE:
                prof.devices.setdefault(int(dm.group(1)), []).extend(
                    Op(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                       op_name(e.name)) for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    t0 = e.start_ns * 1e-9
                    t1 = t0 + e.duration_ns * 1e-9
                    mk = TICK_MARK.match(e.name)
                    if mk:
                        prof.ticks[int(mk.group(1))] = (t0, t1)
                    elif e.duration_ns > 0:
                        prof.host.append((t0, t1, e.name, line.name))
    for ops in prof.devices.values():
        ops.sort(key=lambda o: o.start)
    return prof


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(iv: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    """Disjoint sorted union of intervals, clipped to [t0, t1]."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, t0), min(b, t1)) for a, b in iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(iv: Iterable[Interval]) -> float:
    return sum(b - a for a, b in iv)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def busy_s(prof: Profile, t0: float, t1: float) -> Dict[int, float]:
    """Seconds in which some operation ran, per device."""
    return {d: length(union(((o.start, o.end) for o in ops), t0, t1))
            for d, ops in prof.devices.items()}


def collective_s(prof: Profile, t0: float, t1: float
                 ) -> Dict[int, Tuple[float, float]]:
    """Per device: (seconds inside a collective, the part of them during
    which no other operation ran)."""
    out = {}
    for d, ops in prof.devices.items():
        coll = union(((o.start, o.end) for o in ops
                      if COLLECTIVE.search(o.name)), t0, t1)
        rest = union(((o.start, o.end) for o in ops
                      if not COLLECTIVE.search(o.name)), t0, t1)
        out[d] = (length(coll), length(subtract(coll, rest)))
    return out


def top_ops(prof: Profile, t0: float, t1: float, n: int = 10
            ) -> List[List]:
    """The operations that took the most device time, per device on
    average, as [[name, seconds], ...]."""
    tot: Dict[str, float] = {}
    for ops in prof.devices.values():
        for o in ops:
            dur = min(o.end, t1) - max(o.start, t0)
            if dur > 0:
                tot[o.name] = tot.get(o.name, 0.0) + dur
    k = max(len(prof.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in best]


def idle_gaps(prof: Profile, t0: float, t1: float, n: int = 10
              ) -> List[List]:
    """The longest stretches in which device 0 (the lowest index) ran
    nothing, each named by what the host was doing: the host event that
    overlaps the gap most (the shorter one on a tie), and whether the gap
    fell inside a tick or between ticks."""
    if not prof.devices:
        return []
    dev = min(prof.devices)
    busy = union(((o.start, o.end) for o in prof.devices[dev]), t0, t1)
    gaps = subtract([(t0, t1)], busy)
    gaps = sorted(gaps, key=lambda g: -(g[1] - g[0]))[:n]
    out = []
    for a, b in gaps:
        best, best_key = "no host event", None
        for h0, h1, name, _line in prof.host:
            ov = min(b, h1) - max(a, h0)
            if ov <= 0:
                continue
            key = (ov, -(h1 - h0))
            if best_key is None or key > best_key:
                best, best_key = name, key
        where = "between ticks"
        for tk, (s, e) in prof.ticks.items():
            if s <= (a + b) / 2 <= e:
                where = "in a tick"
                break
        out.append([f"{where}: {best}", b - a])
    return out


def tick_window(prof: Profile, ticks: Sequence[int]
                ) -> Optional[Tuple[float, float, List[int]]]:
    """The traced window: from the start of the first to the end of the
    last of ``ticks`` whose marks the trace holds whole."""
    have = [t for t in ticks if t in prof.ticks]
    if not have:
        return None
    return prof.ticks[have[0]][0], prof.ticks[have[-1]][1], have


def write_json(prof: Profile, path: str) -> None:
    with open(path, "w") as f:
        json.dump(prof.to_json(), f)
