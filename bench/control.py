"""Readings for the limit of ``correct``, at the cell's own size.

  python3 bench/control.py --workload <cell> --seeds <n,n,...> --seconds <s>

For each seed, one whole run of the cell with a short window, judged
twice by the harness's own checks and decision: the program's served
outputs (the lower reading), and the reference in fp8 put in the
program's place on the same served run (the control, which has to come
out not correct).  All seeds in one process, on the chips the cell asks
for; one JSON line per seed on stdout.  The benchmark's runs do not run
this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    devices, kind, peaks = harness.find_chips(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = harness.run_cell(cell, seed, args.seconds, False, t_process=t,
                             peaks=peaks, device_kind=kind, devices=devices,
                             control=True)
        print(json.dumps({
            "seed": seed, "seconds": round(time.perf_counter() - t, 1),
            "program": {"correct": r["correct"], "checks": r["checks"]},
            "control": r["control"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
