"""Modeled step latency of a DICE schedule on a target fabric.

The paper-table yardstick (DESIGN.md Sec. 2): wall-clock speedups on the
paper's 8-GPU testbed cannot be measured here, so the tables model them
from the roofline terms of each MoE layer and the schedule's overlap
structure.  Nothing in it is a measurement; the serving engine does not
call it.  Its callers are the paper-table scripts under ``benchmarks/``
and ``examples/serve_diffusion.py``, which pass the *requested*
``DiceConfig`` (the deployment being modeled), not a server's resolved
one.
"""
from __future__ import annotations

from typing import List, Optional

from repro.common.config import HW, ModelConfig
from repro.core import overlap as overlap_lib
from repro.core import plan as plan_lib
from repro.core.conditional import comm_volume_fraction
from repro.core.schedules import DiceConfig

# The paper's setup: 8x RTX 4090 over PCIe.  Effective (not peak) constants,
# calibrated against the paper's own Table 5 measurement (all-to-all is
# 75.6-79.2% of sync-EP step time on DiT-MoE-XL at batch 4-32):
#   flops   = 82.6 TF dense bf16 x ~45% achieved utilisation,
#   link_bw = ~0.9 GB/s effective per-GPU all-to-all bandwidth (8 GPUs
#             contending through host PCIe root complexes).
PAPER_HW = {"flops": 37e12, "link_bw": 0.9e9}
TPU_HW = {"flops": HW.peak_flops_bf16, "link_bw": HW.ici_bw * 4}


def layer_compute_flops(cfg: ModelConfig, tokens: int) -> float:
    """Per-MoE-layer forward flops (attention + routed + shared experts).

    Attention: QKV + output projections are four d x d matmuls per token
    (8*T*d^2 multiply-adds-counted-as-2), and the QK^T + AV score terms
    are 4*T^2*d.  The routed/shared expert FFNs are three d x d_ff matmuls
    per dispatched token (gated SwiGLU).
    """
    d = cfg.d_model
    attn_flops = 8 * tokens * d * d + 4 * tokens ** 2 * d
    moe_flops = 6 * tokens * d * cfg.expert_d_ff * (
        cfg.experts_per_token + cfg.num_shared_experts)
    return attn_flops + moe_flops


def hop_wire_times(t_comm: float, n_dev: int, sched, *,
                   devices_per_host: int, link_bw: float,
                   inter_host_bw: float) -> List[float]:
    """Per-hop wire seconds of a chunked ring a2a on a two-tier fabric.

    ``t_comm / (n-1)`` is the homogeneous per-hop chunk time.  A shift-h
    hop pushes ``hop_crossings(h, n, H)`` of each host's H chunks through
    the single inter-host trunk (they contend; intra-host links run in
    parallel), so its time is the slower of the trunk serialisation and
    one intra-host chunk transfer (DESIGN.md §14).
    """
    base = t_comm / max(1, n_dev - 1)
    out = []
    for h in sched:
        c = overlap_lib.hop_crossings(h, n_dev, devices_per_host)
        out.append(max(base, c * base * (link_bw / inter_host_bw)))
    return out


def _ring_pipeline_bound(chunk_comp: float, wire_times) -> float:
    """Flow-shop recurrence of the ring engine over explicit per-hop wire
    times: the local chunk's FFN runs behind hop 1's wire, then each
    arriving chunk computes as soon as BOTH its data has landed and the
    previous chunk's FFN is done.  With equal wire times this reduces
    exactly to the closed form ``t_local + (n-1) * max(w, c)``."""
    done = chunk_comp                 # local chunk, free behind hop 1
    wire = 0.0
    for w in wire_times:
        wire += w
        done = max(done, wire) + chunk_comp
    return done


def modeled_step_latency(cfg: ModelConfig, dcfg: DiceConfig, *,
                         local_batch: int, n_dev: int = 8,
                         hw: Optional[dict] = None,
                         devices_per_host: int = 0,
                         inter_host_bw: Optional[float] = None) -> dict:
    """Seconds per diffusion step on n_dev devices.

    Defaults to the paper's hardware point (8x RTX 4090 over PCIe, where
    all-to-all is 60-80% of step time and DICE's overlap pays 1.2-1.26x);
    pass hw=TPU_HW for the v5e target, where ICI bandwidth shrinks the
    communication share and with it the achievable overlap gain.

    Execution-faithful since the ring engine (DESIGN.md Sec. 12): a
    ``dcfg.overlap == "blocking"`` layer is modeled SERIAL
    (``t_comp + t_comm`` — the two monolithic all-to-alls block, whatever
    the staleness schedule does about when results are consumed), while
    ``"ring"`` uses the per-hop pipeline bound

        t_local + Σ_h max(t_hop_comm(h), t_hop_comp)

    — the flow-shop recurrence over the hop schedule, which on a
    homogeneous fabric reduces exactly to the closed form
    ``t_local + (n-1) * max(t_hop_comm, t_hop_comp)``.  The returned dict
    always carries BOTH bounds (``t_step_blocking_s`` /
    ``t_step_ring_s``) plus ``overlap_efficiency`` — the fraction of the
    step's communication time the selected mode hides.

    ``devices_per_host`` H with ``inter_host_bw`` < link_bw models the
    two-tier fabric of DESIGN.md §14: hops whose shift crosses host
    boundaries serialise their crossing chunks through the inter-host
    trunk.  The ring bound then follows the TOPOLOGY-AWARE hop order
    (``repro.core.overlap.ring_hop_schedule`` — cheap intra-host hops
    first, the flow-shop optimum), and ``t_step_ring_oblivious_s``
    additionally reports the natural-order schedule for comparison.
    """
    hw = hw or PAPER_HW
    hetero = (0 < devices_per_host < n_dev
              and inter_host_bw is not None
              and inter_host_bw < hw["link_bw"]
              and n_dev % max(1, devices_per_host) == 0)
    # steady-state StepPlan: the single source of truth for which layers
    # block (replaces the per-schedule if/elif that used to live here)
    steady = plan_lib.steady_state_plan_for(dcfg, cfg.num_layers,
                                            experts_per_token=cfg.experts_per_token)
    tokens = local_batch * cfg.patch_tokens
    d = cfg.d_model
    t_comp = layer_compute_flops(cfg, tokens) / hw["flops"]
    # per-layer all-to-all: dispatch + combine of the capacity buffer
    cap_tokens = tokens * cfg.experts_per_token * cfg.capacity_factor
    a2a_full = 2 * cap_tokens * d * 2 * (n_dev - 1) / n_dev
    # affinity-aware placement (Sec. 13): hot replicated experts serve
    # their tokens locally and the dispatch capacity scales down with
    # them, shrinking every capacity-sized wire payload by the planned
    # mean per-layer capacity scale (1.0 without placements)
    a2a_full *= plan_lib.placement_wire_scale(dcfg)
    a2a_async = a2a_full
    # wire codec (Sec. 11): light-step payloads shrink by the codec's
    # ratio at the 2-byte (bf16/fp16) wire dtype the model counts in
    light_scale = 1.0
    cspec = plan_lib.codec_spec_of(dcfg)
    if cspec is not None and plan_lib.schedule_name(dcfg.schedule) in (
            "displaced", "interweaved", "dice"):
        light_scale = cspec.wire_ratio(d, itemsize=2)
    if dcfg.cond_comm:
        # conditional communication gates ASYNC layers only; synchronized
        # layers transmit everything fresh (that is their purpose)
        a2a_async = a2a_full * comm_volume_fraction(
            cfg.experts_per_token, dcfg.cond_stride, dcfg.cond_policy,
            light_scale=light_scale)
    elif light_scale < 1.0 and dcfg.cond_stride > 1:
        # codec without conditional communication: every rank still moves,
        # but non-refresh steps move it compressed
        a2a_async = a2a_full * (
            1 + (dcfg.cond_stride - 1) * light_scale) / dcfg.cond_stride
    t_comm_full = a2a_full / hw["link_bw"]
    t_comm_async = a2a_async / hw["link_bw"]

    if plan_lib.schedule_name(dcfg.schedule) == "staggered_batch":
        # supplement Sec. 8: two half-batches -> each expert GEMM runs at
        # lower utilization (saturating efficiency curve)
        def eff(b):
            return b / (b + 4)
        t_comp = t_comp * eff(local_batch) / eff(max(1, local_batch // 2))

    sync_frac = steady.num_sync_layers / max(1, steady.num_layers)

    aware_sched = oblivious_sched = tuple(range(1, n_dev))
    if hetero:
        aware_sched = overlap_lib.ring_hop_schedule(
            n_dev, devices_per_host=devices_per_host)

    def _wire(tm, sched):
        return hop_wire_times(tm, n_dev, sched,
                              devices_per_host=devices_per_host,
                              link_bw=hw["link_bw"],
                              inter_host_bw=inter_host_bw)

    def ring_bound(tc: float, tm: float, sched=None) -> float:
        """Per-hop pipeline bound of the ring engine: the local chunk's
        FFN hides behind hop 1's wire, then each of the n-1 hops costs
        the slower of one chunk transfer and one chunk compute.  On a
        homogeneous fabric the exact closed form; on a two-tier one the
        flow-shop recurrence over the given hop order."""
        if n_dev <= 1:
            return tc + tm
        t_local = tc / n_dev
        if not hetero:
            return t_local + (n_dev - 1) * max(tm / (n_dev - 1), tc / n_dev)
        return _ring_pipeline_bound(
            t_local, _wire(tm, aware_sched if sched is None else sched))

    def _comm(tm: float) -> float:
        """Wire time of one monolithic a2a: Σ per-hop times (the blocking
        collective moves every hop's payload with nothing to hide behind;
        order-invariant).  Homogeneous: exactly ``tm``."""
        return sum(_wire(tm, oblivious_sched)) if hetero else tm

    def step_of(t_sync: float, t_async: float) -> float:
        return cfg.num_layers * (sync_frac * t_sync
                                 + (1 - sync_frac) * t_async)

    # blocking: the monolithic all-to-alls serialize against compute —
    # synchronized AND staleness layers alike (staleness only moves when
    # results are consumed, never when the collectives block)
    t_blocking = step_of(t_comp + _comm(t_comm_full),
                         t_comp + _comm(t_comm_async))
    t_ring = step_of(ring_bound(t_comp, t_comm_full),
                     ring_bound(t_comp, t_comm_async))
    t_ring_obl = (step_of(ring_bound(t_comp, t_comm_full, oblivious_sched),
                          ring_bound(t_comp, t_comm_async, oblivious_sched))
                  if hetero else t_ring)
    t_step = t_ring if plan_lib.overlap_of(dcfg) else t_blocking
    t_comm_step = cfg.num_layers * (sync_frac * _comm(t_comm_full)
                                    + (1 - sync_frac) * _comm(t_comm_async))
    efficiency = ((t_blocking - t_step) / t_comm_step
                  if t_comm_step > 0 else 0.0)
    return {"t_step_s": t_step,
            "t_step_blocking_s": t_blocking,
            "t_step_ring_s": t_ring,
            "t_step_ring_oblivious_s": t_ring_obl,
            "hop_schedule": aware_sched if hetero else None,
            "overlap_efficiency": max(0.0, min(1.0, efficiency)),
            "t_comp_layer": t_comp,
            "t_comm_layer": t_comm_async, "sync_frac": sync_frac,
            "a2a_bytes_layer": sync_frac * a2a_full
            + (1 - sync_frac) * a2a_async}
