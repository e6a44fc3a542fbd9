"""Paper Figure 9: batch-size and image-size scaling of latency + memory.

Modeled step latency (8 devices, paper setup) and measured persistent
buffer bytes per method, across batch sizes {4, 8, 16, 32} and image sizes
{256, 512} (patch_tokens {256, 1024}).  Paper claims reproduced:
  * DICE/interweaved sustain speedup over sync EP at every point,
  * DistriFusion's replicated-model + full-sequence buffers blow up
    memory (OOM on XL b>=16 / G in the paper) — visible as buffer bytes
    orders of magnitude above DICE's.
"""
from __future__ import annotations

from benchmarks import common
from repro.configs.dit_moe_xl import config as xl_config
from repro.core import plan as plan_lib
from repro.core.schedules import DiceConfig
from repro.metrics.latency_model import modeled_step_latency


def buffer_bytes_per_method(cfg, method: str, *, local_batch: int,
                            n_dev: int = 8) -> float:
    """Persistent per-device activation buffers (analytic, full-size model)."""
    tokens = local_batch * cfg.patch_tokens
    d = cfg.d_model
    elem = 2  # bf16
    if method == "distrifusion":
        # full-sequence K+V per layer, model replicated across devices
        return 2 * cfg.num_layers * (tokens * n_dev) * d * elem
    dcfg, _ = common.SCHEDULES[method]
    # derive from the plan (works for enum and registered-string schedules)
    n_buf = plan_lib.steady_state_plan_for(
        dcfg, cfg.num_layers,
        experts_per_token=cfg.experts_per_token).num_buffers
    cache = tokens * cfg.experts_per_token * d * elem \
        if (plan_lib.schedule_name(dcfg.schedule) == "dice"
            and dcfg.cond_comm) else 0
    return cfg.num_layers * (n_buf * tokens * d * elem) + \
        cfg.num_layers * cache


def run():
    cfg0 = xl_config()
    for tokens, img in ((256, 256), (1024, 512)):
        cfg = cfg0.replace(patch_tokens=tokens)
        for b in (4, 8, 16, 32):
            base = modeled_step_latency(cfg, DiceConfig.sync_ep(),
                                        local_batch=b)["t_step_s"]
            for method, (dcfg, ndev) in common.SCHEDULES.items():
                if ndev:
                    dcfg = DiceConfig.displaced(overlap="ring")
                t = modeled_step_latency(cfg, dcfg, local_batch=b)["t_step_s"]
                buf = buffer_bytes_per_method(cfg, method, local_batch=b)
                common.csv_row(
                    f"fig9/img{img}/b{b}/{method}", t * 1e6,
                    f"modeled_speedup={base/t:.3f};buffer_bytes={buf:.0f}")


def main():
    common.enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
