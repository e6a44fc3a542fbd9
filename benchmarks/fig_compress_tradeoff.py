"""Wire-codec trade-off: bytes-on-wire vs quality, codec x stride (ISSUE 4).

Sweeps the residual wire codecs (none / int8_residual / topk_residual,
DESIGN.md Sec. 11) across conditional-communication strides under DICE and
reports, per point: total bytes actually on the wire, the same payloads
uncompressed, the compression ratio, FID-proxy against the reference set,
and paired-MSE against the synchronous-EP sample — the compression analogue
of the paper's Fig. 10 latency/quality frontier.  Two DICE variants per
point: the paper's ``sync_policy="deep"`` (protected layers dilute the
step-level ratio — the honest serving number) and ``sync_policy="none"``
(all layers async — the per-payload codec ratio shows up undiluted, and is
asserted >= 3x for int8 on light steps).

  PYTHONPATH=src:. python benchmarks/fig_compress_tradeoff.py --smoke
"""
from __future__ import annotations

import argparse
import os
import time

CODECS = ["none", "int8_residual", "topk_residual"]
STRIDES = [2, 4]


def run(num_steps: int = None, label: str = "fig_compress"):
    import jax
    import jax.numpy as jnp
    from benchmarks import common
    from repro.compress.codecs import CompressConfig
    from repro.core.schedules import DiceConfig
    from repro.metrics.fid_proxy import fid_proxy, mse_vs_reference
    from repro.sampling.rectified_flow import rf_sample

    from repro.core import conditional

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    if num_steps is None:
        num_steps = 6 if smoke else 20
    cfg = common.tiny_cfg()
    strides = STRIDES
    if smoke:
        # CI-sized model (shared with serve_throughput --smoke); one
        # stride keeps the run to 7 samples
        cfg = common.smoke_cfg("dit-moe-compress-smoke")
        strides = [2]
    w = DiceConfig.dice().warmup_steps
    # the light-step assertions below need at least one post-warmup
    # refresh/light alternation in every swept stride
    num_steps = max(num_steps, w + max(strides) + 1)
    params = common.get_trained_params(cfg)
    ref_data = common.reference_set(cfg)
    n = common.num_samples()
    classes = jnp.arange(n) % cfg.num_classes
    key = jax.random.PRNGKey(common.bench_seed())
    sync_samples, _ = rf_sample(params, cfg, DiceConfig.sync_ep(),
                                num_steps=num_steps, classes=classes,
                                key=key, guidance=1.5)

    results = {}
    for sync_policy in ("deep", "none"):
        for codec in CODECS:
            for stride in strides:
                compress = (None if codec == "none"
                            else CompressConfig(codec=codec))
                dcfg = DiceConfig.dice(sync_policy=sync_policy,
                                       cond_stride=stride,
                                       compress=compress)
                t0 = time.time()
                samples, stats = rf_sample(params, cfg, dcfg,
                                           num_steps=num_steps,
                                           classes=classes, key=key,
                                           guidance=1.5)
                jax.block_until_ready(samples)
                us = (time.time() - t0) / num_steps * 1e6
                wire = sum(stats["dispatch_bytes"])
                raw = sum(stats["raw_bytes"])
                fid = fid_proxy(samples, ref_data)
                mse = mse_vs_reference(samples, sync_samples)
                common.csv_row(
                    f"{label}/{sync_policy}/{codec}/stride{stride}", us,
                    f"wire_bytes={wire:.0f};raw_bytes={raw:.0f};"
                    f"ratio={raw / wire:.3f};fid_proxy={fid:.4f};"
                    f"mse_vs_sync={mse:.6f};"
                    f"jit_cache={stats['jit_cache_size']};"
                    f"variants={stats['num_plan_variants']}")
                results[(sync_policy, codec, stride)] = {
                    "wire": wire, "raw": raw, "fid": fid, "mse": mse,
                    "per_step": stats["dispatch_bytes"],
                    "jit_cache": stats["jit_cache_size"],
                    "variants": stats["num_plan_variants"]}

    # ---- invariants the sweep must exhibit (ISSUE 4 acceptance) ----------
    for sync_policy in ("deep", "none"):
        for stride in strides:
            none_r = results[(sync_policy, "none", stride)]
            for codec in ("int8_residual", "topk_residual"):
                r = results[(sync_policy, codec, stride)]
                assert r["wire"] < none_r["wire"], (sync_policy, codec,
                                                    stride)
                assert r["raw"] == none_r["wire"], "raw must equal the " \
                    "lossless wire"
                assert r["jit_cache"] == r["variants"], (codec, stride)
    for stride in strides:
        # all-async DICE: int8 light steps put >= 3x fewer bytes on the
        # wire than uncompressed light steps (first post-warmup non-refresh
        # step — guaranteed in range by the num_steps clamp above)
        light_idx = next(s for s in range(w, num_steps)
                         if not conditional.is_refresh_step(s, stride))
        light_u = results[("none", "none", stride)]["per_step"][light_idx]
        light_c = results[("none", "int8_residual",
                           stride)]["per_step"][light_idx]
        assert light_c * 3 <= light_u, (stride, light_c, light_u)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized training/sampling")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--seed", type=int, default=None,
                    help="sampling-noise seed (BENCH_SEED)")
    args = ap.parse_args()
    if args.seed is not None:
        os.environ["BENCH_SEED"] = str(args.seed)
    if args.smoke:
        os.environ.setdefault("BENCH_TRAIN_STEPS", "40")
        os.environ.setdefault("BENCH_SAMPLES", "16")
        os.environ.setdefault("BENCH_SMOKE", "1")
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(num_steps=args.steps)
    print("OK: compressed wire < lossless wire, raw == lossless, "
          "int8 light steps >= 3x smaller, jit cache == variants")


if __name__ == "__main__":
    main()
