"""Paper Figure 10: latency-quality trade-off.

Joins the Table-1 quality axis (FID-proxy / MSE vs sync) with the modeled
step latency axis, one point per method — reproducing the paper's frontier:
interweaved strictly dominates displaced (better quality, same latency);
DICE trades a little latency (selective sync) for most of the sync quality.
"""
from __future__ import annotations

from benchmarks import common
from repro.core.schedules import DiceConfig
from repro.metrics.latency_model import modeled_step_latency
from repro.metrics.fid_proxy import fid_proxy, mse_vs_reference


def run(num_steps: int = 20):
    cfg = common.tiny_cfg()
    params = common.get_trained_params(cfg)
    ref_data = common.reference_set(cfg)
    sync_samples, _, _ = common.sample_method(
        params, cfg, "expert_parallelism", num_steps=num_steps)
    lat_cfg = common.tiny_cfg()

    for method, (dcfg, ndev) in common.SCHEDULES.items():
        samples, _, us = common.sample_method(params, cfg, method,
                                              num_steps=num_steps)
        fid = fid_proxy(samples, ref_data)
        mse = mse_vs_reference(samples, sync_samples)
        d = DiceConfig.displaced(overlap="ring") if ndev else dcfg
        t = modeled_step_latency(lat_cfg, d, local_batch=16)["t_step_s"]
        common.csv_row(f"fig10/{method}", t * 1e6,
                       f"fid_proxy={fid:.4f};mse_vs_sync={mse:.6f};"
                       f"modeled_step_us={t*1e6:.1f}")


def main():
    common.enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
