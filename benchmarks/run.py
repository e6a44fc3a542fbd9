"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Quality metrics are
FID-proxy / paired-MSE on synthetic latents (ordering is the validated
claim); latency/speedup numbers are modeled on the paper's 8-device setup
from roofline terms, never measured.  See EXPERIMENTS.md.

  PYTHONPATH=src python -m benchmarks.run [--only table1,table5] [--fast]
"""
import argparse
import glob
import json
import os
import sys
import time

TABLES = ["table1_quality", "table23_fewer_steps", "table4_ablation",
          "table5_comm_fraction", "fig9_scaling", "fig10_tradeoff",
          "fig_compress_tradeoff", "fig_overlap", "serve_throughput"]

# ---------------------------------------------------------------------------
# BENCH_*.json validation (benchmarks/run.py --check)
# ---------------------------------------------------------------------------
# every artifact must carry the _env provenance block written by
# benchmarks.common.write_bench_json
REQUIRED_ENV_KEYS = {"schema_version", "jax", "backend", "device_count",
                     "mesh"}
# per-benchmark payload schema: REQUIRED keys must be present, OPTIONAL
# keys may be (feature-gated result blocks), anything else is unknown —
# a renamed or newly added stat must be declared here or --check fails
BENCH_SCHEMAS = {
    "serve_throughput": {
        "required": {
            "schedule", "requests", "mesh", "fifo_padded_slot_steps",
            "cont_padded_slot_steps", "fifo_occupancy", "cont_occupancy",
            "fifo_makespan_steps", "cont_makespan_steps", "fifo_req_per_s",
            "cont_req_per_s", "cont_recycled_admissions",
            "num_plan_variants", "jit_cache_size",
            "fifo_dispatch_bytes_total", "fifo_a2a_bytes_per_layer",
            "fifo_buffer_bytes", "codec", "cont_wire_bytes_total",
            "cont_raw_bytes_total", "cont_compression_ratio",
            "fifo_wire_bytes_total", "fifo_raw_bytes_total", "overlap",
            "cont_ring_hops", "cont_hop_bytes_total",
            "modeled_overlap_efficiency", "modeled_step_blocking_s",
            "modeled_step_ring_s", "skew", "placement", "replicate_top",
            "max_routing_share", "paging",
        },
        "optional": {
            # expert paging (Sec. 15) — present when the run paged
            "peak_resident_expert_bytes", "paged_transfers",
            "paged_bytes_in", "expert_hbm_budget",
            "fully_resident_expert_bytes",
            # affinity placement two-pass flow (Sec. 13)
            "placement_hop_bytes_total", "identity_hop_bytes_total",
            "hop_bytes_reduction", "placement_parity_err",
            "placement_wire_scale", "placement_replicated",
            "placement_cap_scales", "placement_jit_cache_size",
            "placement_num_plan_variants", "modeled_step_ring_s_identity",
            "modeled_step_ring_s_placed",
        },
    },
    "serve_chaos": {
        "required": {
            "faults", "requests", "mesh", "schedules", "zero_crashes",
        },
        "optional": {"paging"},
    },
}


def check_bench_artifacts(root: str = None) -> int:
    """Validate every committed BENCH_*.json: parseable, carrying the
    ``_env`` provenance stamp, and — where a schema is declared —
    exactly the known payload keys.  Returns the number of failures
    (0 == everything valid)."""
    root = root or os.path.abspath(
        os.path.join(os.path.dirname(__file__), ".."))
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    if not paths:
        print("no BENCH_*.json artifacts found", file=sys.stderr)
        return 1
    failures = 0

    def fail(path, msg):
        nonlocal failures
        failures += 1
        print(f"FAIL {os.path.basename(path)}: {msg}", file=sys.stderr)

    for path in paths:
        name = os.path.basename(path)[len("BENCH_"):-len(".json")]
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            fail(path, f"unreadable: {e}")
            continue
        if not isinstance(data, dict):
            fail(path, f"payload must be a JSON object, got "
                       f"{type(data).__name__}")
            continue
        env = data.get("_env")
        if not isinstance(env, dict):
            fail(path, "missing _env provenance block (re-run the "
                       "benchmark to restamp)")
            continue
        missing_env = REQUIRED_ENV_KEYS - set(env)
        if missing_env:
            fail(path, f"_env missing keys: {sorted(missing_env)}")
        if env.get("schema_version") != 1:
            fail(path, f"unsupported schema_version "
                       f"{env.get('schema_version')!r} (expected 1)")
        schema = BENCH_SCHEMAS.get(name)
        if schema is None:
            print(f"ok   {os.path.basename(path)} (no payload schema "
                  f"declared; _env validated)")
            continue
        keys = set(data) - {"_env"}
        missing = schema["required"] - keys
        unknown = keys - schema["required"] - schema["optional"]
        if missing:
            fail(path, f"missing required keys: {sorted(missing)}")
        if unknown:
            fail(path, f"unknown keys (declare them in "
                       f"benchmarks.run.BENCH_SCHEMAS): {sorted(unknown)}")
        if not missing and not unknown:
            print(f"ok   {os.path.basename(path)}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of modules")
    ap.add_argument("--fast", action="store_true",
                    help="fewer train steps / samples (smoke)")
    ap.add_argument("--seed", type=int, default=None,
                    help="sampling-noise seed threaded into every benchmark "
                         "(BENCH_SEED) for reproducible CSV rows")
    ap.add_argument("--check", action="store_true",
                    help="validate the committed BENCH_*.json artifacts "
                         "(provenance stamp + known payload keys) and exit "
                         "non-zero on any failure")
    args = ap.parse_args()
    if args.check:
        sys.exit(1 if check_bench_artifacts() else 0)
    if args.seed is not None:
        os.environ["BENCH_SEED"] = str(args.seed)
    if args.fast:
        os.environ.setdefault("BENCH_TRAIN_STEPS", "60")
        os.environ.setdefault("BENCH_SAMPLES", "32")
        os.environ.setdefault("BENCH_SMOKE", "1")
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    mods = args.only.split(",") if args.only else TABLES
    print("name,us_per_call,derived")
    for name in mods:
        key = name if name in TABLES else next(
            (t for t in TABLES if t.startswith(name)), name)
        t0 = time.time()
        mod = __import__(f"benchmarks.{key}", fromlist=["run"])
        res = mod.run()
        if res is not None:
            # persist every table's structured result next to the CSV so
            # drivers diff numbers instead of scraping stdout
            from benchmarks.common import write_bench_json
            write_bench_json(key, res)
        print(f"# {key} done in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
