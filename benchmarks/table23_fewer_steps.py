"""Paper Tables 2 & 3: 10-step and 20-step settings.

Same protocol as Table 1 at fewer sampling steps (the paper's warmup rule:
2 synchronized steps at 10 steps, 4 at 20).  The paper's claim — DICE's
advantage GROWS at fewer steps (staleness is a larger fraction of the
trajectory) — is checked via the mse ratio displaced/DICE.
"""
from __future__ import annotations

import dataclasses

from benchmarks import common
from repro.core.schedules import DiceConfig


def run():
    cfg = common.tiny_cfg()
    params = common.get_trained_params(cfg)
    from repro.metrics.fid_proxy import fid_proxy, mse_vs_reference
    ref_data = common.reference_set(cfg)

    for steps, warmup, label in ((10, 2, "table2_10steps"),
                                 (20, 4, "table3_20steps")):
        # paper: N synchronized steps post cold start
        schedules = {
            name: (dataclasses.replace(d, warmup_steps=warmup)
                   if d.schedule.value != "sync" else d, nd)
            for name, (d, nd) in common.SCHEDULES.items()
        }
        saved = dict(common.SCHEDULES)
        common.SCHEDULES.clear()
        common.SCHEDULES.update(schedules)
        try:
            sync_samples, _, _ = common.sample_method(
                params, cfg, "expert_parallelism", num_steps=steps)
            for method in schedules:
                samples, stats, us = common.sample_method(
                    params, cfg, method, num_steps=steps)
                fid = fid_proxy(samples, ref_data)
                mse = mse_vs_reference(samples, sync_samples)
                speed = common.modeled_speedup(cfg, method)
                common.csv_row(
                    f"{label}/{method}", us,
                    f"fid_proxy={fid:.4f};mse_vs_sync={mse:.6f};"
                    f"modeled_speedup={speed:.3f}")
        finally:
            common.SCHEDULES.clear()
            common.SCHEDULES.update(saved)


def main():
    common.enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
