"""End-to-end serving driver (the paper's workload): batched
class-conditional generation requests through the DICE engine, comparing
all schedules' quality/communication/memory and the modeled TPU latency.

Run:  PYTHONPATH=src python examples/serve_diffusion.py [--steps 10]
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs.dit_moe_xl import tiny
from repro.launch.serve import SCHEDULES, DiceServer, Request
from repro.metrics.fid_proxy import mse_vs_reference
from repro.metrics.latency_model import modeled_step_latency
from repro.models.dit_moe import init_dit


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args()

    cfg = tiny()
    params = init_dit(jax.random.PRNGKey(0), cfg)
    # adaLN-zero init yields exactly-zero velocity untrained (all schedules
    # would trivially agree); un-zero the gates so staleness is visible.
    # Real deployments pass a trained checkpoint to DiceServer instead.
    k1, k2 = jax.random.split(jax.random.PRNGKey(42))
    params["final_out"] = 0.1 * jax.random.normal(k1,
                                                  params["final_out"].shape)
    params["blocks"] = [
        dict(b, adaln=0.1 * jax.random.normal(
            jax.random.fold_in(k2, i), b["adaln"].shape))
        for i, b in enumerate(params["blocks"])]
    reqs = [Request(class_id=i % cfg.num_classes, rid=i)
            for i in range(args.requests)]

    ref = None
    print(f"{'schedule':28s} {'mse_vs_sync':>12s} {'modeled_step_ms':>16s} "
          f"{'buffer_bytes':>13s}")
    for name in SCHEDULES:
        dcfg = SCHEDULES[name]()
        server = DiceServer(cfg, dcfg, params=params)
        samples, stats = server.generate(reqs, num_steps=args.steps)
        # the paper's 8-device deployment, each device a share of the batch
        lat = modeled_step_latency(cfg, dcfg,
                                   local_batch=max(1, len(reqs) // 8))
        mse = 0.0 if ref is None else mse_vs_reference(samples, ref)
        if ref is None:
            ref = samples
        print(f"{name:28s} {mse:12.6f} "
              f"{lat['t_step_s']*1e3:16.3f} "
              f"{stats['buffer_bytes']:13,.0f}")


if __name__ == "__main__":
    main()
