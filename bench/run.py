"""Run one cell of the on-chip benchmark.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine holding the chips the cell asks
for.  JAX is pinned to the TPU before it starts: with no TPU, fewer chips
than the cell needs, or a device kind missing from ``bench/peaks.json``
the run fails and prints no result.  The last line of stdout is the
result, one JSON object; the numbers compared for ``correct`` are the last
lines of stderr.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    devices, kind, peaks = harness.find_chips(cell)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS,
                              peaks=peaks, device_kind=kind, devices=devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
