"""True expert-parallel DICE serving across every local device — mesh-native.

Runs the paper's workload end-to-end DISTRIBUTED through the core stack
(DESIGN.md §10): ``rf_sample(mesh=...)`` lowers every StepPlan variant to
one shard_map-ped step function, with the DiT-MoE experts sharded across
an "ep" mesh axis as wide as the devices present
(``common.sharding.ep_param_specs``), requests batch-split, staleness state sharded over the same axis, and the real
dispatch/combine all-to-alls executing in every MoE layer — under the
synchronous, interweaved, selective and FULL DICE schedules (conditional
communication included: light steps put a genuinely smaller per-device
buffer on the wire).  Verifies that distributed sampling matches the
single-device reference.

Run:  PYTHONPATH=src python examples/ep_serving_multidevice.py
(on the CPU it asks for 8 XLA host devices before importing jax; on an
accelerator host it uses the chips there, e.g. ep=4 on a four-chip v5e)
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.configs.dit_moe_xl import tiny
from repro.core.schedules import DiceConfig, Schedule
from repro.launch.mesh import make_ep_mesh
from repro.metrics.fid_proxy import mse_vs_reference
from repro.models.dit_moe import init_dit
from repro.sampling.rectified_flow import rf_sample


def main():
    cfg = tiny().replace(num_layers=4, capacity_factor=8.0)
    ep = len(jax.devices())
    if ep < 2 or cfg.num_experts % ep:
        raise SystemExit(f"{ep} devices: expert parallelism needs 2 or more "
                         f"that divide the {cfg.num_experts} experts")
    mesh = make_ep_mesh(ep)
    params = init_dit(jax.random.PRNGKey(0), cfg)
    # adaLN-zero init gives exactly-zero velocity on an untrained model (all
    # schedules would trivially agree); un-zero the gates for a real signal
    k1, k2 = jax.random.split(jax.random.PRNGKey(42))
    params["final_out"] = 0.1 * jax.random.normal(
        k1, params["final_out"].shape)
    params["blocks"] = [
        dict(b, adaln=0.1 * jax.random.normal(jax.random.fold_in(k2, i),
                                              b["adaln"].shape))
        for i, b in enumerate(params["blocks"])]
    classes = jnp.arange(16) % cfg.num_classes
    key = jax.random.PRNGKey(7)

    ref_sync, _ = rf_sample(params, cfg, DiceConfig.sync_ep(), num_steps=8,
                            classes=classes, key=key, guidance=1.0)

    print(f"{'schedule':14s} {'max|Δ| vs 1-dev self':>21s} "
          f"{'mse vs 1-dev sync':>18s} {'cache':>6s}")
    for name, dcfg in [
            ("sync", DiceConfig.sync_ep()),
            ("interweaved", DiceConfig.interweaved()),
            ("selective", DiceConfig(schedule=Schedule.DICE,
                                     sync_policy="deep", cond_comm=False)),
            ("dice", DiceConfig.dice(sync_policy="deep")),
            # ring-overlap engine (DESIGN.md Sec. 12): same wire bytes,
            # 2*(ep-1) chunked ppermutes per layer instead of 2 blocking
            # all-to-alls; on one device it normalizes away, so the
            # single-device reference below is the plain DICE run
            ("dice+ring", DiceConfig.dice(sync_policy="deep",
                                          overlap="ring"))]:
        ref, _ = rf_sample(params, cfg, dcfg, num_steps=8, classes=classes,
                           key=key, guidance=1.0)
        out, stats = rf_sample(params, cfg, dcfg, num_steps=8,
                               classes=classes, key=key, guidance=1.0,
                               mesh=mesh)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 0.1, (name, err)   # full-DICE distributed parity too
        assert stats["jit_cache_size"] == stats["num_plan_variants"]
        mse = mse_vs_reference(out, ref_sync)
        print(f"{name:14s} {err:21.3e} {mse:18.6f} "
              f"{stats['jit_cache_size']:6d}")
        if name == "dice":
            per_step = stats["dispatch_bytes"]
            light, full = min(per_step), max(per_step)
            assert light < full, per_step
            print(f"{'':14s} conditional comm on the wire: per-device "
                  f"payload {full:.0f} B (refresh) -> {light:.0f} B (light)")
        if name == "dice+ring":
            assert max(stats["hops"]) == 2 * (ep - 1), stats["hops"]
            print(f"{'':14s} ring overlap: {max(stats['hops'])} "
                  f"collective-permutes per MoE layer, "
                  f"{stats['hop_bytes'][0]:.0f} B per step of in-flight "
                  f"chunks")
    print(f"distributed EP serving OK — experts sharded {ep}-way, all-to-all "
          "dispatch/combine in every MoE layer, full DICE included, ring "
          "overlap executed")


if __name__ == "__main__":
    main()
