"""Continuous-batching throughput vs the FIFO baseline (DESIGN.md Sec. 9).

A Poisson request stream is drained twice through a ``max_batch``-slot
server:

  * **fifo** — :func:`repro.launch.serve.serve_queue` semantics: a batch
    launches with whatever requests have arrived (padded with the null
    class) and every slot is held for the full ``num_steps``;
  * **continuous** — :func:`repro.launch.serve.serve_continuous`: slots
    complete independently and freed slots are recycled at plan-variant-
    aligned boundaries, with slot-level staleness-state resets.

Reported per engine: padded-slot step-executions (the wasted null-class
compute the paper's heavy-traffic scenario cares about), modeled slot
occupancy, makespan in steps, and modeled requests/s on the paper's
8-device hardware point.  The continuous engine must execute strictly
fewer padded slots than FIFO and keep its jit cache at the plan-variant
count (acceptance criteria, ISSUE 2).

  PYTHONPATH=src python benchmarks/serve_throughput.py --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.compress.codecs import CODEC_KINDS, CompressConfig
from repro.configs.dit_moe_xl import tiny
from repro.core import placement as placement_lib
from repro.core.schedules import DiceConfig
from repro.launch.serve import (DiceServer, Request, SCHEDULES,
                                serve_continuous, serve_queue, write_metrics)
from repro.metrics.latency_model import modeled_step_latency
from repro.models.dit_moe import init_dit
from repro.obs import MetricsRegistry, ObsConfig
from repro.resilience import faults as fault_lib


def poisson_arrivals(n: int, rate_per_step: float, seed: int) -> List[float]:
    """Arrival tick of each request: cumulative Exp(1/rate) inter-arrivals."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(rate_per_step, 1e-9), size=n)
    return np.cumsum(gaps).tolist()


def fifo_schedule(arrivals: List[float], *, max_batch: int,
                  num_steps: int) -> Tuple[int, float, int]:
    """Model of serve_queue under an arrival process: a batch launches as
    soon as the server is free and at least one request has arrived,
    taking min(queued, max_batch) and padding the rest.  Returns
    (padded_slot_steps, makespan_steps, num_batches)."""
    arrivals = sorted(arrivals)
    t, i, padded, batches = 0.0, 0, 0, 0
    n = len(arrivals)
    while i < n:
        if arrivals[i] > t:
            t = arrivals[i]                 # idle until the next arrival
        take = sum(1 for a in arrivals[i:i + max_batch] if a <= t)
        i += take
        padded += (max_batch - take) * num_steps
        batches += 1
        t += num_steps
    return padded, t, batches


def skewed_params(cfg, skew: str, *, seed: int = 0, strength: float = 2.0):
    """Model params with the routing skew knob applied: ``zipf:a`` biases
    every MoE layer's router logits by ``-strength * a * ln(rank + 1)``
    (expert 0 hottest), emulating the skewed expert affinity the paper's
    traces show; ``uniform`` leaves the router untouched."""
    params = init_dit(jax.random.PRNGKey(seed), cfg)
    if skew == "uniform":
        return params
    if not skew.startswith("zipf:"):
        raise ValueError(f"skew must be 'uniform' or 'zipf:<a>', got "
                         f"{skew!r}")
    a = float(skew.split(":", 1)[1])
    bias = -strength * a * np.log(np.arange(cfg.num_experts) + 1.0)
    for blk in params["blocks"]:
        blk["moe"]["router_bias"] = jnp.asarray(bias, jnp.float32)
    return params


def run(*, schedule: str = "dice", requests: int = 32, max_batch: int = 8,
        num_steps: int = 8, rate: float = 0.5, seed: int = 0,
        smoke: bool = False, ep: int = 0, dp: int = 1, patch: int = 1,
        codec: str = "none",
        overlap: str = "blocking", skew: str = "uniform",
        placement: str = "identity", replicate_top: int = 0,
        paging: str = "off", expert_hbm_budget: int = 0,
        paging_depth: int = 1, obs: bool = False,
        trace_out: str = None, metrics_out: str = None) -> dict:
    if os.environ.get("BENCH_SMOKE") == "1" and not smoke:
        # benchmarks.run --fast sets BENCH_SMOKE: shrink like the other tables
        smoke = True
        requests, num_steps, max_batch = (min(requests, 12),
                                          min(num_steps, 4),
                                          min(max_batch, 4))
    cfg = tiny()
    if smoke:
        cfg = common.smoke_cfg("dit-moe-serve-smoke")
    if skew != "uniform" or placement != "identity":
        # the placement-parity gate (placed run == single-device baseline
        # to 1e-4) needs a drop-impossible capacity (C == T*K per shard),
        # so that the identity-headroom-preserving cap_scale can shrink
        # the wire without introducing capacity drops the baseline lacks;
        # >= 32 tokens per shard keeps the 8-aligned scaled capacity fine-
        # grained enough that cap_scale survives quantization
        cfg = cfg.replace(name=cfg.name + "-skew", capacity_factor=8.0,
                          patch_tokens=max(cfg.patch_tokens, 32))
    mesh = None
    if ep or dp > 1 or patch > 1:
        # mesh-native continuous engine (DESIGN.md §10/§14): slots shard
        # over the dp x ep batch axes, so the slot count must divide them
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(ep=max(1, ep), dp=dp, patch=patch)
        lanes = max(1, dp) * max(1, ep)
        max_batch = max(max_batch, lanes)
        max_batch -= max_batch % lanes
    params = skewed_params(cfg, skew, seed=0)
    pspec = None
    if paging == "on":
        from repro.core.paging import PagingSpec
        pspec = PagingSpec(
            budget_bytes=None if expert_hbm_budget < 0 else expert_hbm_budget,
            depth=paging_depth)
    dcfg = dataclasses.replace(SCHEDULES[schedule](),
                               compress=CompressConfig(codec=codec),
                               overlap=overlap, paging=pspec)
    obs_on = bool(obs or trace_out or metrics_out)
    server = DiceServer(cfg, dcfg, params=params, mesh=mesh,
                        obs=ObsConfig(enabled=obs_on))
    reqs = [Request(class_id=i % cfg.num_classes, rid=i)
            for i in range(requests)]
    arrivals = poisson_arrivals(requests, rate, seed)

    # ---- continuous engine (actually executed) ---------------------------
    out, cstats = serve_continuous(server, reqs, max_batch=max_batch,
                                   num_steps=num_steps,
                                   arrival_steps=arrivals,
                                   key=jax.random.PRNGKey(seed))
    assert sorted(out) == [r.rid for r in reqs], "requests lost"
    assert all(np.isfinite(v).all() for v in out.values())

    # ---- FIFO baseline: arrival-aware occupancy model + an executed
    # serve_queue pass for the aggregate byte/compile stats ----------------
    fifo_padded, fifo_makespan, fifo_batches = fifo_schedule(
        arrivals, max_batch=max_batch, num_steps=num_steps)
    _, fstats = serve_queue(server, reqs, max_batch=max_batch,
                            num_steps=num_steps,
                            key=jax.random.PRNGKey(seed))

    # ---- affinity-aware placement pass (DESIGN.md Sec. 13) ---------------
    # two-pass flow: the identity run above doubles as the histogram probe
    # (its stats carry the routing-share EMA); the optimizer turns that
    # into per-layer placements + a hot-expert replica set, and a second
    # placed run over the SAME request trace measures what the layout
    # actually saves on the wire.  Parity gates against a mesh-less
    # identity baseline of the same trace.
    # modeled latency of the REQUESTED deployment (dcfg as asked for, its
    # codec and a2a engine included, not server.dcfg as this mesh runs
    # it) on the mesh's ep size, or on the paper's 8 devices mesh-less
    n_model = 8
    if mesh is not None:
        n_model = mesh.shape["ep"] if "ep" in mesh.axis_names else 1

    def modeled(d):
        return modeled_step_latency(cfg, d, n_dev=n_model,
                                    local_batch=max(1, max_batch // n_model))

    lat = modeled(dcfg)
    place_res = {}
    if placement == "greedy" and ep > 1:
        shares = np.asarray(cstats["routing_shares"])
        pls = placement_lib.greedy_placements(shares, ep,
                                              replicate_top=replicate_top)
        dcfg_placed = dataclasses.replace(server.dcfg, placements=pls)
        server_pl = DiceServer(cfg, dcfg_placed, params=params, mesh=mesh)
        out_pl, pstats = serve_continuous(server_pl, reqs,
                                          max_batch=max_batch,
                                          num_steps=num_steps,
                                          arrival_steps=arrivals,
                                          key=jax.random.PRNGKey(seed))
        server_1d = DiceServer(cfg, server.dcfg, params=params)
        out_1d, _ = serve_continuous(server_1d, reqs, max_batch=max_batch,
                                     num_steps=num_steps,
                                     arrival_steps=arrivals,
                                     key=jax.random.PRNGKey(seed))
        parity = max(float(np.max(np.abs(out_pl[r] - out_1d[r])))
                     for r in out_1d)
        ident_hop = cstats["hop_bytes_total"]
        place_res = {
            "placement_hop_bytes_total": pstats["hop_bytes_total"],
            "identity_hop_bytes_total": ident_hop,
            "hop_bytes_reduction": 1.0 - pstats["hop_bytes_total"]
            / max(ident_hop, 1.0),
            "placement_parity_err": parity,
            "placement_wire_scale": pstats["placement_wire_scale"],
            "placement_replicated": [list(p.replicated) for p in pls],
            "placement_cap_scales": [p.cap_scale for p in pls],
            "placement_jit_cache_size": pstats["jit_cache_size"],
            "placement_num_plan_variants": pstats["num_plan_variants"],
            # modeled ring-step latency with vs without the placement
            "modeled_step_ring_s_identity": lat["t_step_ring_s"],
            "modeled_step_ring_s_placed": modeled(dataclasses.replace(
                dcfg, placements=pls))["t_step_ring_s"],
        }

    t_step = lat["t_step_s"]
    fifo_slot_steps = fifo_batches * max_batch * num_steps
    res = {
        "schedule": schedule,
        "requests": requests,
        # the hierarchical mesh shape of the run (DESIGN.md §14); all-1
        # with the sentinel ep size when mesh-less
        "mesh": {"ep": max(1, ep), "dp": max(1, dp), "patch": max(1, patch),
                 "native": mesh is not None},
        "fifo_padded_slot_steps": fifo_padded,
        "cont_padded_slot_steps": cstats["padded_slot_steps"],
        "fifo_occupancy": 1.0 - fifo_padded / max(1, fifo_slot_steps),
        "cont_occupancy": cstats["slot_occupancy"],
        "fifo_makespan_steps": fifo_makespan,
        "cont_makespan_steps": cstats["makespan_steps"],
        "fifo_req_per_s": requests / (fifo_makespan * t_step),
        "cont_req_per_s": requests / (cstats["makespan_steps"] * t_step),
        "cont_recycled_admissions": cstats["recycled_admissions"],
        "num_plan_variants": cstats["num_plan_variants"],
        "jit_cache_size": cstats["jit_cache_size"],
        "fifo_dispatch_bytes_total": fstats["dispatch_bytes_total"],
        "fifo_a2a_bytes_per_layer": lat["a2a_bytes_layer"],
        "fifo_buffer_bytes": fstats["buffer_bytes"],
        # wire vs raw payload (Sec. 11): ratio > 1 iff a codec is active
        "codec": codec,
        "cont_wire_bytes_total": cstats["wire_bytes_total"],
        "cont_raw_bytes_total": cstats["raw_bytes_total"],
        "cont_compression_ratio": cstats["raw_bytes_total"]
        / max(cstats["wire_bytes_total"], 1.0),
        "fifo_wire_bytes_total": fstats["wire_bytes_total"],
        "fifo_raw_bytes_total": fstats["raw_bytes_total"],
        # ring-overlap execution stats (DESIGN.md Sec. 12)
        "overlap": overlap,
        "cont_ring_hops": cstats["ring_hops"],
        "cont_hop_bytes_total": cstats["hop_bytes_total"],
        "modeled_overlap_efficiency": lat["overlap_efficiency"],
        "modeled_step_blocking_s": lat["t_step_blocking_s"],
        "modeled_step_ring_s": lat["t_step_ring_s"],
        # routing skew + placement (DESIGN.md Sec. 13)
        "skew": skew,
        "placement": placement,
        "replicate_top": replicate_top,
        "max_routing_share": float(
            np.asarray(cstats["routing_shares"]).max()),
        # expert paging (DESIGN.md Sec. 15): the residency ledger's
        # realized peak vs the budget, plus what full residency would
        # have cost per device — present when the run actually paged
        "paging": paging,
        **({"peak_resident_expert_bytes":
                cstats["peak_resident_expert_bytes"],
            "paged_transfers": cstats["paged_transfers"],
            "paged_bytes_in": cstats["paged_bytes_in"],
            "expert_hbm_budget": cstats["expert_hbm_budget"],
            "fully_resident_expert_bytes": server.expert_pool.window_bytes(
                server.expert_pool.layer_indices)}
           if "peak_resident_expert_bytes" in cstats else {}),
        **place_res,
    }
    # observability exports (DESIGN.md Sec. 16): the server registry has
    # folded in the continuous AND fifo passes; the tracer holds plan/
    # compile/step/admission host phases of both
    if trace_out and server.tracer is not None:
        server.tracer.write(trace_out)
        print(f"# wrote step trace to {trace_out} "
              f"({len(server.tracer.events)} events)", flush=True)
    if metrics_out:
        write_metrics(server.metrics, metrics_out)
        print(f"# wrote metrics to {metrics_out}", flush=True)

    tag = f"serve_throughput/{schedule}" \
          + (f"+{codec}" if codec != "none" else "") \
          + (f"+{overlap}" if overlap != "blocking" else "") \
          + (f"+{skew}" if skew != "uniform" else "") \
          + (f"+{placement}" if placement != "identity" else "") \
          + ("+paging" if paging == "on" else "") \
          + f"/b{max_batch}"
    common.csv_row(
        tag,
        res["cont_req_per_s"],
        f"fifo_req_per_s={res['fifo_req_per_s']:.4g} "
        f"cont_padded={res['cont_padded_slot_steps']} "
        f"fifo_padded={res['fifo_padded_slot_steps']} "
        f"occupancy={res['cont_occupancy']:.3f} "
        f"compression={res['cont_compression_ratio']:.2f} "
        f"overlap_eff={res['modeled_overlap_efficiency']:.2f}"
        + (f" hop_reduction={place_res['hop_bytes_reduction']:.2f}"
           if place_res else ""))
    return res


def run_chaos(*, faults: str, requests: int = 8, max_batch: int = 4,
              num_steps: int = 6, rate: float = 0.5, seed: int = 0,
              smoke: bool = False, ep: int = 0, dp: int = 1,
              paging: str = "off", trace_out: str = None,
              metrics_out: str = None) -> dict:
    """Chaos smoke (DESIGN.md Sec. 17): every schedule serves a seeded
    fault storm to completion.

    For each of the five schedules, the SAME request trace runs three
    times through the continuous engine: a fault-free reference, the
    seeded fault run, and a full-degradation envelope
    (``corrupt_combine_rate=1.0`` + guards — every step fully
    cache-degraded, the "one extra light step" quality bound).  Asserts
    per schedule: zero crashes, every request either served or
    explicitly shed (nothing silently lost), all served samples finite,
    degradation events visible in the stats, and the fault run's max
    per-request deviation from the reference bounded by the envelope's.
    """
    cfg = common.smoke_cfg("dit-moe-chaos") if smoke else tiny()
    res_cfg = fault_lib.parse_resilience(faults)
    assert res_cfg is not None, f"--faults {faults!r} parsed to no config"
    mesh = None
    if ep or dp > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(ep=max(1, ep), dp=dp, patch=1)
        lanes = max(1, dp) * max(1, ep)
        max_batch = max(max_batch, lanes)
        max_batch -= max_batch % lanes
    fcfg = res_cfg.faults
    if fcfg is not None and fcfg.burst_size > 0:
        arrivals = fault_lib.bursty_arrivals(requests, rate,
                                             fcfg.burst_size)
    else:
        arrivals = poisson_arrivals(requests, rate, seed)
    reqs = [Request(class_id=i % cfg.num_classes, rid=i)
            for i in range(requests)]
    # full-degradation envelope: every fresh combine pair corrupted every
    # step, absorbed by the guards' cache fallback
    env_res = fault_lib.ResilienceConfig(
        faults=fault_lib.FaultConfig(seed=seed, corrupt_combine_rate=1.0),
        guards=True)
    corrupting = fcfg is not None and (fcfg.corrupt_combine_rate > 0
                                       or fcfg.corrupt_dispatch_rate > 0)
    params = init_dit(jax.random.PRNGKey(0), cfg)
    reg = MetricsRegistry()
    tracer_holder = {}
    per = {}
    pspec = None
    if paging == "on":
        from repro.core.paging import PagingSpec
        pspec = PagingSpec(budget_bytes=None, depth=1)
    for name in SCHEDULES:
        def _run(resilience, obs=False):
            dcfg = dataclasses.replace(SCHEDULES[name](), paging=pspec,
                                       resilience=resilience)
            srv = DiceServer(cfg, dcfg, params=params, mesh=mesh,
                             obs=ObsConfig(enabled=obs))
            o, s = serve_continuous(srv, reqs, max_batch=max_batch,
                                    num_steps=num_steps,
                                    arrival_steps=arrivals,
                                    key=jax.random.PRNGKey(seed))
            return srv, o, s

        _, ref_out, _ = _run(None)
        obs_on = bool(trace_out or metrics_out)
        srv_f, f_out, f_stats = _run(res_cfg, obs=obs_on)
        reg.merge(srv_f.metrics)
        if srv_f.tracer is not None:
            tracer_holder[name] = srv_f.tracer
        shed = set(f_stats.get("shed_rids", []))
        served = set(f_out)
        assert not (served & shed), (name, served & shed)
        assert sorted(served | shed) == [r.rid for r in reqs], (
            f"{name}: requests silently lost — served {sorted(served)}, "
            f"shed {sorted(shed)}")
        assert all(np.isfinite(v).all() for v in f_out.values()), (
            f"{name}: non-finite sample escaped the guards")
        fe = f_stats.get("fault_events", {})
        if corrupting:
            assert sum(fe.values()) > 0, (
                f"{name}: corruption configured but no fault events "
                f"recorded: {fe}")
        max_delta = max((float(np.max(np.abs(f_out[r] - ref_out[r])))
                         for r in served), default=0.0)
        env_delta = None
        if corrupting:
            _, e_out, _ = _run(env_res)
            env_delta = max((float(np.max(np.abs(e_out[r] - ref_out[r])))
                             for r in e_out if r in ref_out), default=0.0)
            # partial corruption cannot hurt more than total degradation
            # (small slack: corruption flips WHICH pairs degrade, not the
            # magnitude scale)
            assert max_delta <= 4.0 * max(env_delta, 1e-6), (
                f"{name}: fault-run delta {max_delta} exceeds the "
                f"full-degradation envelope {env_delta}")
        per[name] = {
            "served": len(served),
            "shed": len(shed),
            "quarantined": int(f_stats.get("quarantined", 0)),
            "requeued": int(f_stats.get("requeued", 0)),
            "watchdog_breaches": int(f_stats.get("watchdog_breaches", 0)),
            "demotions": list(f_stats.get("demotions", [])),
            "fault_events": {k: float(v) for k, v in fe.items()},
            "paging_stale_fallbacks": int(
                f_stats.get("paging_stale_fallbacks", 0)),
            "max_delta": max_delta,
            "envelope_delta": env_delta,
        }
        common.csv_row(
            f"serve_chaos/{name}/b{max_batch}", per[name]["served"],
            f"shed={per[name]['shed']} "
            f"quarantined={per[name]['quarantined']} "
            f"events={sum(fe.values()):.0f} "
            f"delta={max_delta:.3g}"
            + (f" envelope={env_delta:.3g}" if env_delta is not None
               else ""))
    if trace_out and tracer_holder:
        last = list(tracer_holder.values())[-1]
        last.write(trace_out)
        print(f"# wrote step trace to {trace_out} "
              f"({len(last.events)} events)", flush=True)
    if metrics_out:
        write_metrics(reg, metrics_out)
        print(f"# wrote metrics to {metrics_out}", flush=True)
    return {
        "faults": faults,
        "requests": requests,
        "mesh": {"ep": max(1, ep), "dp": max(1, dp), "patch": 1,
                 "native": mesh is not None},
        "paging": paging,
        "schedules": per,
        "zero_crashes": True,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--schedule", choices=list(SCHEDULES), default="dice")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate, requests per diffusion step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized model and workload")
    ap.add_argument("--ep", type=int, default=0,
                    help="run mesh-native over an N-way 'ep' axis (needs N "
                         "devices, e.g. XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replica groups of the hierarchical "
                         "dp x ep x patch mesh (DESIGN.md §14); needs "
                         "dp*ep*patch devices")
    ap.add_argument("--patch", type=int, default=1,
                    help="patch-parallel split of the image-token dim "
                         "(§14); the continuous engine refuses it — use "
                         "repro.launch.serve rigid batches for patch "
                         "meshes")
    ap.add_argument("--codec", choices=list(CODEC_KINDS), default="none",
                    help="wire codec for staleness-era payloads "
                         "(DESIGN.md Sec. 11)")
    ap.add_argument("--overlap", choices=["blocking", "ring"],
                    default="blocking",
                    help="a2a execution engine (DESIGN.md Sec. 12): ring "
                         "pipelines chunked ppermute hops against the "
                         "expert FFN (executed when --ep > 1)")
    ap.add_argument("--skew", default="uniform",
                    help="routing skew knob: 'uniform' or 'zipf:<a>' "
                         "(biases router logits by -a*ln(rank+1), expert "
                         "0 hottest — DESIGN.md Sec. 13)")
    ap.add_argument("--placement", choices=["identity", "greedy"],
                    default="identity",
                    help="expert placement (Sec. 13): 'greedy' runs the "
                         "two-pass flow — identity probe, then the "
                         "affinity bin-pack layout — and compares "
                         "hop_bytes_total between the runs")
    ap.add_argument("--replicate-top", type=int, default=0,
                    help="hottest experts replicated on every device")
    ap.add_argument("--paging", choices=["off", "on"], default="off",
                    help="expert paging (DESIGN.md Sec. 15): host-RAM "
                         "expert pool, per-layer shards paged into device "
                         "memory one MoE layer ahead (needs --ep > 1)")
    ap.add_argument("--expert-hbm-budget", type=int, default=0,
                    help="per-device resident-expert byte budget under "
                         "--paging on (0 = auto-tightest, negative = "
                         "unbounded)")
    ap.add_argument("--paging-depth", type=int, default=1,
                    help="prefetch distance in MoE layers")
    ap.add_argument("--obs", action="store_true",
                    help="observability plane (DESIGN.md Sec. 16): in-graph "
                         "staleness telemetry, measured per-tick walltimes, "
                         "host-phase tracing; outputs stay bit-identical")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace-event JSON of host phases "
                         "(implies --obs)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics registry: Prometheus text, or "
                         "a JSON snapshot for *.json paths (implies --obs)")
    ap.add_argument("--faults", default=None,
                    help="chaos mode (DESIGN.md Sec. 17): a seeded "
                         "resilience spec, e.g. 'seed=7,corrupt=0.1,"
                         "paging_err=0.3,poison_tick=3'.  Runs EVERY "
                         "schedule through reference / fault / envelope "
                         "passes, asserting completion, zero crashes, and "
                         "a bounded quality delta; writes "
                         "BENCH_serve_chaos.json")
    args = ap.parse_args()
    common.enable_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 12)
        args.steps = min(args.steps, 4)
        args.max_batch = min(args.max_batch, 4)

    if args.faults:
        res = run_chaos(faults=args.faults, requests=min(args.requests, 8),
                        max_batch=args.max_batch, num_steps=args.steps,
                        rate=args.rate, seed=args.seed, smoke=args.smoke,
                        ep=args.ep, dp=args.dp, paging=args.paging,
                        trace_out=args.trace_out,
                        metrics_out=args.metrics_out)
        common.write_bench_json("serve_chaos", res)
        for name, r in res["schedules"].items():
            print(f"  {name:18s} served={r['served']} shed={r['shed']} "
                  f"quarantined={r['quarantined']} "
                  f"events={sum(r['fault_events'].values()):.0f} "
                  f"delta={r['max_delta']:.3g}")
        print("CHAOS-OK: all schedules completed every request under "
              "seeded faults (zero crashes, bounded quality delta)")
        return

    res = run(schedule=args.schedule, requests=args.requests,
              max_batch=args.max_batch, num_steps=args.steps,
              rate=args.rate, seed=args.seed, smoke=args.smoke, ep=args.ep,
              dp=args.dp, patch=args.patch,
              codec=args.codec, overlap=args.overlap, skew=args.skew,
              placement=args.placement, replicate_top=args.replicate_top,
              paging=args.paging, expert_hbm_budget=args.expert_hbm_budget,
              paging_depth=args.paging_depth, obs=args.obs,
              trace_out=args.trace_out, metrics_out=args.metrics_out)
    common.write_bench_json("serve_throughput", res)
    for k, v in res.items():
        print(f"  {k:28s} {v:.6g}" if isinstance(v, float)
              else f"  {k:28s} {v}")
    assert res["cont_padded_slot_steps"] < res["fifo_padded_slot_steps"], (
        "continuous batching must strictly reduce padded-slot executions")
    assert res["jit_cache_size"] == res["num_plan_variants"], (
        "slot recycling must not grow the jit cache beyond the plan "
        "variants")
    # the planner only attaches codecs to staleness schedules; sync /
    # staggered_batch legitimately ignore --codec (wire == raw)
    compresses = args.codec != "none" and args.schedule in (
        "dice", "interweaved", "displaced")
    if compresses:
        assert res["cont_compression_ratio"] > 1.0, (
            "an active wire codec must put fewer bytes on the wire than "
            "the lossless payloads")
        assert res["cont_wire_bytes_total"] < res["cont_raw_bytes_total"]
    elif args.codec != "none":
        assert res["cont_wire_bytes_total"] == res["cont_raw_bytes_total"], (
            f"schedule {args.schedule!r} plans no codec; wire must equal raw")
    if args.overlap == "ring":
        # modeled ring never loses to blocking; on a real mesh the engine
        # must actually have executed its 2*(n-1) permutes per layer
        assert res["modeled_step_ring_s"] <= res["modeled_step_blocking_s"]
        if args.ep > 1:
            # staggered steady steps run two independent half-batch rings
            rings = 4 if args.schedule == "staggered_batch" else 2
            assert res["cont_ring_hops"] == rings * (args.ep - 1), res
            assert res["cont_hop_bytes_total"] > 0
    if args.paging == "on" and args.ep > 1:
        # expert-paging acceptance (DESIGN.md Sec. 15): the realized
        # residency peak must respect the budget and stay strictly below
        # full residency whenever the model has more layers than the
        # prefetch window holds
        assert res["paged_transfers"] > 0, res
        budget = res["expert_hbm_budget"]
        if budget is not None:
            assert res["peak_resident_expert_bytes"] <= budget, res
        assert res["peak_resident_expert_bytes"] <= \
            res["fully_resident_expert_bytes"], res
        if budget is not None and budget < res["fully_resident_expert_bytes"]:
            # a budget below full residency must actually be honored by
            # paging, not by quietly keeping everything resident
            assert res["peak_resident_expert_bytes"] < \
                res["fully_resident_expert_bytes"], res
    if args.placement == "greedy" and args.ep > 1:
        # affinity-aware placement acceptance (DESIGN.md Sec. 13): the
        # placed run of the SAME request trace must put strictly fewer
        # bytes on the ring than the identity probe — ≥20% with a
        # replicated hot expert under zipf skew — while matching the
        # single-device identity baseline and holding the jit-cache
        # contract
        assert res["placement_hop_bytes_total"] < \
            res["identity_hop_bytes_total"], res
        assert res["placement_parity_err"] <= 1e-4, res
        assert res["placement_jit_cache_size"] == \
            res["placement_num_plan_variants"], res
        if args.replicate_top >= 1 and args.skew != "uniform":
            assert res["hop_bytes_reduction"] >= 0.20, res
    print("OK: continuous < fifo padded-slot steps, jit cache == variants"
          + (f", wire compression {res['cont_compression_ratio']:.2f}x"
             if compresses else "")
          + (f", ring hops {res['cont_ring_hops']}, overlap efficiency "
             f"{res['modeled_overlap_efficiency']:.2f}"
             if args.overlap == "ring" else "")
          + (f", placement hop-bytes -{res['hop_bytes_reduction']:.0%} "
             f"(parity {res['placement_parity_err']:.1e})"
             if args.placement == "greedy" and args.ep > 1 else "")
          + (f", paged peak {res['peak_resident_expert_bytes']} B/dev "
             f"(full residency {res['fully_resident_expert_bytes']} B)"
             if args.paging == "on" and args.ep > 1 else ""))


if __name__ == "__main__":
    main()
