"""Paper Table 4: ablation of Selective Synchronization and Conditional
Communication strategies, all on top of interweaved parallelism.

Rows: sync policy in {none, deep, shallow, staggered} x cond-comm policy in
{off, low, high, random}.  Paper findings to reproduce (as orderings):
deep-sync best among sync policies; deprioritising LOW-score tokens beats
high/random for conditional communication.
"""
from __future__ import annotations

from benchmarks import common
from repro.core.schedules import DiceConfig, Schedule
from repro.metrics.fid_proxy import fid_proxy, mse_vs_reference

ABLATIONS = [
    ("interweaved_only", DiceConfig.interweaved()),
    ("sync_deep", DiceConfig(schedule=Schedule.DICE, sync_policy="deep",
                             cond_comm=False)),
    ("sync_shallow", DiceConfig(schedule=Schedule.DICE, sync_policy="shallow",
                                cond_comm=False)),
    ("sync_staggered", DiceConfig(schedule=Schedule.DICE,
                                  sync_policy="staggered", cond_comm=False)),
    ("cond_low_score", DiceConfig(schedule=Schedule.DICE, sync_policy="none",
                                  cond_comm=True, cond_policy="low")),
    ("cond_high_score", DiceConfig(schedule=Schedule.DICE, sync_policy="none",
                                   cond_comm=True, cond_policy="high")),
    ("cond_random", DiceConfig(schedule=Schedule.DICE, sync_policy="none",
                               cond_comm=True, cond_policy="random")),
]


def run(num_steps: int = 50):
    import jax
    import jax.numpy as jnp
    from repro.sampling.rectified_flow import rf_sample

    cfg = common.tiny_cfg()
    params = common.get_trained_params(cfg)
    ref_data = common.reference_set(cfg)
    classes = jnp.arange(common.num_samples()) % cfg.num_classes
    sync_samples, _, _ = common.sample_method(
        params, cfg, "expert_parallelism", num_steps=num_steps)

    results = {}
    for name, dcfg in ABLATIONS:
        import time
        t0 = time.time()
        samples, stats = rf_sample(params, cfg, dcfg, num_steps=num_steps,
                                   classes=classes,
                                   key=jax.random.PRNGKey(common.bench_seed()),
                                   guidance=1.5)
        jax.block_until_ready(samples)
        us = (time.time() - t0) / num_steps * 1e6
        fid = fid_proxy(samples, ref_data)
        mse = mse_vs_reference(samples, sync_samples)
        mean_disp = sum(stats["dispatch_bytes"]) / len(stats["dispatch_bytes"])
        common.csv_row(f"table4/{name}", us,
                       f"fid_proxy={fid:.4f};mse_vs_sync={mse:.6f};"
                       f"mean_dispatch_bytes={mean_disp:.0f}")
        results[name] = mse
    return results


def main():
    common.enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
