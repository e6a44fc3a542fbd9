"""Paper Table 1: 50-step quality across parallelism schemes.

Reproduces the comparison {Expert Parallelism, DistriFusion, Displaced EP,
Interweaved, DICE} at the paper's 50-step Rectified Flow setting, on the
CPU-sized DiT-MoE with synthetic latents.  Expected (paper): sync best;
DICE < interweaved < DistriFusion ~ displaced on FID; here: same ordering
on FID-proxy and paired-MSE vs the synchronous reference.
"""
from __future__ import annotations

from benchmarks import common


def run(num_steps: int = 50, label: str = "table1"):
    cfg = common.tiny_cfg()
    params = common.get_trained_params(cfg)
    ref_data = common.reference_set(cfg)
    from repro.metrics.fid_proxy import fid_proxy, mse_vs_reference

    sync_samples, _, _ = common.sample_method(
        params, cfg, "expert_parallelism", num_steps=num_steps)
    rows = []
    for method in common.SCHEDULES:
        samples, stats, us = common.sample_method(params, cfg, method,
                                                  num_steps=num_steps)
        fid = fid_proxy(samples, ref_data)
        mse = mse_vs_reference(samples, sync_samples)
        speed = common.modeled_speedup(cfg, method)
        common.csv_row(
            f"{label}/{method}", us,
            f"fid_proxy={fid:.4f};mse_vs_sync={mse:.6f};"
            f"modeled_speedup={speed:.3f};"
            f"buffer_bytes={stats['buffer_bytes'][-1]:.0f}")
        rows.append((method, fid, mse))
    return rows


def main():
    common.enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
