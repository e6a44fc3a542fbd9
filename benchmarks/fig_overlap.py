"""Ring-overlap hop-count sweep: modeled vs measured, blocking vs ring
(ISSUE 5; DESIGN.md Sec. 12).

Modeled part (always runs): for each device count the upgraded latency
model's blocking bound (``t_comp + t_comm`` — the two monolithic
all-to-alls serialize) and the ring's per-hop pipeline bound
(``t_local + (n-1) * max(t_hop_comm, t_hop_comp)``) on the paper's
8x-4090 hardware point at DiT-MoE-XL scale, per schedule.  Asserts the
acceptance inequality: ring < blocking whenever t_comm > t_comp/(n-1).

Measured part (needs multiple XLA devices, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``): actually executes
both engines over an ep mesh on the CI-sized model and reports wall
us/step plus the executed hop stats — CPU wall time is not the claim
(there is no async wire on host devices), the point is that the ring path
RUNS end-to-end and its per-step byte accounting matches blocking.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src:. python benchmarks/fig_overlap.py --smoke
"""
from __future__ import annotations

import argparse
import os
import time

DEVICE_SWEEP = [2, 4, 8, 16]
SCHEDULES = ["sync", "interweaved", "dice"]


def run(label: str = "fig_overlap"):
    import jax
    import jax.numpy as jnp
    from benchmarks import common
    from repro.configs.dit_moe_xl import config as xl_config
    from repro.core.schedules import DiceConfig
    from repro.launch.serve import SCHEDULES as SERVE_SCHEDULES
    from repro.metrics.latency_model import modeled_step_latency
    from repro.sampling.rectified_flow import rf_sample

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    sweep = [2, 4, 8] if smoke else DEVICE_SWEEP

    # ---- modeled: hop-count sweep of the pipeline bound ------------------
    cfg_xl = xl_config()
    results = {}
    for n_dev in sweep:
        for sched in SCHEDULES:
            dcfg = SERVE_SCHEDULES[sched]()
            ring = modeled_step_latency(cfg_xl, dcfg, local_batch=4,
                                        n_dev=n_dev)
            t_block = ring["t_step_blocking_s"]
            t_ring = ring["t_step_ring_s"]
            common.csv_row(
                f"{label}/modeled/{sched}/n{n_dev}", t_ring * 1e6,
                f"t_blocking_us={t_block * 1e6:.1f};"
                f"t_ring_us={t_ring * 1e6:.1f};"
                f"speedup={t_block / t_ring:.3f};"
                f"hops={2 * (n_dev - 1)}")
            results[(sched, n_dev)] = ring
            # acceptance (ISSUE 5): ring beats blocking whenever one hop's
            # wire time exceeds one chunk's compute
            if ring["t_comm_layer"] > ring["t_comp_layer"] / (n_dev - 1):
                assert t_ring < t_block, (sched, n_dev, t_ring, t_block)

    # ---- modeled: two-tier topology — aware hop order beats oblivious ----
    # (DESIGN.md §14) The hardware point is derived from the model's own
    # roofline terms to sit in the pipeline-crossover regime: intra-host
    # links fast enough that a one-chunk hop hides behind one chunk's FFN
    # (0.1x), the inter-host trunk slow enough that a single-crossing hop
    # almost fills it (0.8x) — so multi-crossing hops spill past compute
    # and WHERE the schedule puts them moves the pipeline bound.  (In the
    # fully wire-bound limit every order costs sum-of-hops and ordering is
    # provably irrelevant; the assertion targets the regime where it
    # matters.)
    import dataclasses as _dc
    for n_dev in [n for n in sweep if n >= 4]:
        H = n_dev // 2
        dcfg = _dc.replace(SERVE_SCHEDULES["sync"](), overlap="ring")
        probe = modeled_step_latency(cfg_xl, dcfg, local_batch=4,
                                     n_dev=n_dev)
        chunk = probe["t_comp_layer"] / n_dev
        b_hop = probe["a2a_bytes_layer"] / (n_dev - 1)
        hw2 = {"flops": 37e12, "link_bw": b_hop / (0.1 * chunk)}
        inter_bw = b_hop / (0.8 * chunk)
        het = modeled_step_latency(cfg_xl, dcfg, local_batch=4,
                                   n_dev=n_dev, hw=hw2,
                                   devices_per_host=H,
                                   inter_host_bw=inter_bw)
        t_aware = het["t_step_ring_s"]
        t_obl = het["t_step_ring_oblivious_s"]
        common.csv_row(
            f"{label}/modeled/topology/n{n_dev}xH{H}", t_aware * 1e6,
            f"t_oblivious_us={t_obl * 1e6:.1f};"
            f"t_blocking_us={het['t_step_blocking_s'] * 1e6:.1f};"
            f"gain={t_obl / t_aware:.4f};"
            f"sched={'-'.join(map(str, het['hop_schedule']))}")
        # acceptance (ISSUE 7): the topology-aware schedule strictly beats
        # the oblivious natural order when inter-host hops outprice
        # intra-host ones and the ring is not purely wire-bound
        assert t_aware < t_obl, (n_dev, H, t_aware, t_obl)
        assert t_obl < het["t_step_blocking_s"], (n_dev, H)

    # ---- measured: execute both engines over a real ep mesh --------------
    n_avail = len(jax.devices())
    n_mesh = max(n for n in [1] + sweep if n <= n_avail)
    if n_mesh < 2:
        print("# fig_overlap: single XLA device -> measured sweep skipped "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              flush=True)
        return results
    from repro.launch.mesh import make_ep_mesh
    from repro.models.dit_moe import init_dit
    cfg = common.smoke_cfg("dit-moe-overlap-smoke") if smoke \
        else common.tiny_cfg()
    mesh = make_ep_mesh(n_mesh)
    params = init_dit(jax.random.PRNGKey(0), cfg)
    classes = jnp.arange(n_mesh) % cfg.num_classes
    num_steps = 4 if smoke else 8
    measured = {}
    for overlap in ("blocking", "ring"):
        dcfg = DiceConfig.dice(overlap=overlap)
        # warm the jit cache, then time a second full run
        rf_sample(params, cfg, dcfg, num_steps=num_steps, classes=classes,
                  key=jax.random.PRNGKey(1), guidance=1.0, mesh=mesh)
        t0 = time.time()
        samples, stats = rf_sample(params, cfg, dcfg, num_steps=num_steps,
                                   classes=classes,
                                   key=jax.random.PRNGKey(1), guidance=1.0,
                                   mesh=mesh)
        jax.block_until_ready(samples)
        us = (time.time() - t0) / num_steps * 1e6
        measured[overlap] = dict(stats, us=us, samples=samples)
        common.csv_row(
            f"{label}/measured/dice+{overlap}/n{n_mesh}", us,
            f"hops={max(stats['hops'])};"
            f"hop_bytes_step={stats['hop_bytes'][0]:.0f};"
            f"wire_bytes={sum(stats['dispatch_bytes']):.0f};"
            f"jit_cache={stats['jit_cache_size']}")
    err = float(jnp.max(jnp.abs(measured["ring"]["samples"]
                                - measured["blocking"]["samples"])))
    assert err < 1e-4, f"ring vs blocking mesh mismatch: {err}"
    # topology-aware hop order is a pure permutation of the ring's
    # collective-permutes (each hop writes its own combine slot), so the
    # samples must be BIT-identical to the oblivious ring (DESIGN.md §14)
    from repro.core.overlap import ring_hop_schedule
    sched = ring_hop_schedule(n_mesh, devices_per_host=max(1, n_mesh // 2))
    topo_samples, topo_stats = rf_sample(
        params, cfg, DiceConfig.dice(overlap="ring"), num_steps=num_steps,
        classes=classes, key=jax.random.PRNGKey(1), guidance=1.0,
        mesh=mesh, hop_schedule=sched)
    terr = float(jnp.max(jnp.abs(topo_samples - measured["ring"]["samples"])))
    assert terr == 0.0, f"topology-aware ring must be bit-identical: {terr}"
    assert max(topo_stats["hops"]) == 2 * (n_mesh - 1)
    print(f"# fig_overlap: topology-aware hop schedule "
          f"{'-'.join(map(str, sched))} bit-identical to oblivious ring",
          flush=True)
    assert measured["ring"]["dispatch_bytes"] == \
        measured["blocking"]["dispatch_bytes"], \
        "the ring must not change the wire-byte accounting"
    assert max(measured["ring"]["hops"]) == 2 * (n_mesh - 1)
    print(f"# fig_overlap: measured ring == blocking within {err:.2e} "
          f"on the {n_mesh}-way mesh", flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized model and reduced sweep")
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("BENCH_SMOKE", "1")
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run()
    print("OK: modeled ring < blocking in the comm-bound regime; "
          "measured ring matches blocking where a mesh exists")


if __name__ == "__main__":
    main()
