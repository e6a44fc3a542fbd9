"""How far the engine's own spans cover a traced run, and what they cost.

  python3 bench/span_cover.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints that result
line first.  The second line, also written to
``chiprun_out/bench/span_cover.json``, reads the same run's profile:

- ``between_cover``: the share of the traced window's time outside the
  ``tick`` spans that the between-tick spans (``serve.admit``,
  ``serve.prepare``, ``serve.readback``, ``serve.complete``,
  ``serve.quarantine``) cover;
- ``in_tick_cover``: per tick, the share of its ``tick`` span that
  ``serve.dispatch`` and ``serve.wait`` cover (least and mean);
- ``per_tick``: for each traced tick, ``[tick ms, serve.dispatch ms,
  device idle ms inside the tick]``;
- ``phase``: per span name, its count in the window, mean ms per span
  and ms per traced tick; ``prepare_ms`` splits ``serve.prepare`` by
  the kind of tick it prepares (lockstep or slotted);
- ``host_gap_ms``: window time outside ticks per tick, for the ticks
  before the profiler started (``quiet``) and the profiled ones;
- ``span_us``: the host cost of one span entered and left in a tight
  loop, with no tracer (``off``), the benchmark's tracer with no
  profiler (``on``) and the profiler recording (``profiled``); with
  ``spans_per_tick``, each mode's cost per tick.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

BETWEEN = ("serve.admit", "serve.prepare", "serve.readback",
           "serve.complete", "serve.quarantine")
IN_TICK = ("serve.dispatch", "serve.wait")
LOOP = 20000


def cover(rec) -> dict:
    """Coverage and per-phase times from the run's profile."""
    from bench import trace_reduce as tr
    from bench import window as win_lib
    prof = rec.trace
    t0, t1, ticks = rec.trace_window
    n = len(ticks)
    tick_iv = [(a, b) for a, b, name, _ in prof.host if name == "tick"
               and t0 <= a and b <= t1]
    outside = tr.subtract([(t0, t1)], tr.union(tick_iv, t0, t1))
    between = tr.union([(a, b) for a, b, name, _ in prof.host
                        if name in BETWEEN], t0, t1)
    covered = tr.length(tr.subtract(outside, tr.subtract(outside, between)))
    busy = tr.union(((o.start, o.end)
                     for o in prof.devices[min(prof.devices)]), t0, t1)
    in_tick, per_tick = [], []
    for a, b in tick_iv:
        inner = tr.union([(s, e) for s, e, name, _ in prof.host
                          if name in IN_TICK], a, b)
        in_tick.append(tr.length(inner) / (b - a))
        launch = tr.union([(s, e) for s, e, name, _ in prof.host
                           if name == "serve.dispatch"], a, b)
        idle = tr.subtract([(a, b)], tr.union(busy, a, b))
        per_tick.append([1e3 * (b - a), 1e3 * tr.length(launch),
                         1e3 * tr.length(idle)])
    phase = {}
    for name in BETWEEN + IN_TICK + ("tick",):
        iv = [(a, b) for a, b, nm, _ in prof.host
              if nm == name and t0 <= a and b <= t1]
        if iv:
            tot = sum(b - a for a, b in iv)
            phase[name] = {"count": len(iv), "mean_ms": 1e3 * tot / len(iv),
                           "ms_per_tick": 1e3 * tot / n}
    # serve.prepare by the tick it prepares: the first tick after it
    starts = sorted((t.start, t.slotted) for t in rec.ticks)
    prep = {"lockstep": [], "slotted": []}
    for a, b, name, _ in prof.host:
        if name == "serve.prepare" and t0 <= a and b <= t1:
            nxt = next((s for s in starts if s[0] >= b), None)
            if nxt is not None:
                prep["slotted" if nxt[1] else "lockstep"].append(b - a)
    traced = [t for t in rec.window if t0 <= t.start and t.end <= t1]
    quiet, quiet_end = rec.quiet_window()
    spans = sum(1 for a, b, name, _ in prof.host
                if (name in BETWEEN + IN_TICK or name == "tick")
                and t0 <= a and b <= t1)
    return {
        "ticks": n,
        "outside_ticks_s": tr.length(outside),
        "between_cover": covered / max(tr.length(outside), 1e-12),
        "in_tick_cover": {"min": min(in_tick, default=None),
                          "mean": (sum(in_tick) / len(in_tick)
                                   if in_tick else None)},
        "per_tick": per_tick,
        "phase": phase,
        "prepare_ms": {k: {"count": len(v),
                           "mean": 1e3 * sum(v) / len(v) if v else None}
                       for k, v in prep.items()},
        "host_gap_ms": {
            "quiet": win_lib.host_gap_ms(quiet, rec.w0, quiet_end),
            "profiled": (win_lib.host_gap_ms(
                traced, traced[0].start, traced[-1].end)
                if traced else None)},
        "spans_per_tick": spans / n,
    }


def span_us(loops: int = LOOP) -> dict:
    """Host microseconds per span entered and left: off, on, profiled."""
    import jax
    from bench import harness
    from repro.launch.serve import _span

    def loop(tracer):
        t = time.perf_counter()
        for _ in range(loops):
            with _span(tracer, "serve.cost", {"tick": 0}):
                pass
        return (time.perf_counter() - t) / loops * 1e6

    out = {"off": loop(None), "on": loop(harness.make_tracer(_noop))}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["profiled"] = loop(harness.make_tracer(_noop))
        finally:
            jax.profiler.stop_trace()
    return out


def _noop(_tick):
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    seen = {}
    load_reader = harness.load_reader

    def keep_record(name):
        read = load_reader(name)

        def reader(rec):
            seen["rec"] = rec
            return read(rec)
        return reader

    harness.load_reader = keep_record
    cell = harness.load_cell(args.workload)
    devices, kind, peaks = harness.find_chips(cell)
    result = harness.run_cell(cell, args.seed, args.seconds, True,
                              t_process=T_PROCESS, peaks=peaks,
                              device_kind=kind, devices=devices)
    print(json.dumps(result), flush=True)
    rec = seen.get("rec")
    if rec is None or rec.trace is None or rec.trace_window is None:
        raise SystemExit("the run left no traced window")
    report = {"workload": args.workload, "seed": args.seed,
              "device": kind, **cover(rec), "span_us": span_us()}
    report["span_us_per_tick"] = {k: v * report["spans_per_tick"]
                                  for k, v in report["span_us"].items()}
    out = ROOT / "chiprun_out" / "bench"
    out.mkdir(parents=True, exist_ok=True)
    (out / "span_cover.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
