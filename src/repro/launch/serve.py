"""DICE serving engine — the paper-kind end-to-end driver.

Serves class-conditional DiT-MoE generation requests under a selectable
parallelism schedule (the paper's baselines and DICE itself), in two
modes: rigid FIFO batches (:func:`serve_queue`) and continuous batching
(:func:`serve_continuous`, DESIGN.md Sec. 9) where each batch slot steps
and completes independently and freed slots are recycled mid-flight with
slot-level staleness-state resets.  Besides the samples it reports the
quantities behind the paper's claims: per-step all-to-all payload,
persistent staleness-buffer bytes, measured wall seconds (taken after
``block_until_ready``, on whatever backend ran), and a step latency
MODELED from roofline terms for the paper's 8-GPU setup (the
``modeled_*`` keys; never a measurement).

  PYTHONPATH=src python -m repro.launch.serve --schedule dice \
      --requests 16 --steps 20
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import HW, ModelConfig
from repro.compress.codecs import CODEC_KINDS, CompressConfig
from repro.configs.dit_moe_xl import config as xl_config, tiny
from repro.core import conditional
from repro.core import overlap as overlap_lib
from repro.core import paging as paging_lib
from repro.core import placement as placement_lib
from repro.core import plan as plan_lib
from repro.core import staleness as stale_lib
from repro.core.schedules import DiceConfig
from repro.core.conditional import comm_volume_fraction
from repro.models.dit_moe import init_dit
from repro.obs import MetricsRegistry, ObsConfig, StepTracer
from repro.obs import telemetry as obs_fields
from repro.resilience import degrade as degrade_lib
from repro.resilience import faults as fault_lib
from repro.resilience import recovery as recovery_lib
from repro.sampling.rectified_flow import make_rf_step, rf_sample


@dataclass
class Request:
    class_id: int
    rid: int


SCHEDULES = {
    "sync": DiceConfig.sync_ep,
    "displaced": DiceConfig.displaced,
    "interweaved": DiceConfig.interweaved,
    "dice": DiceConfig.dice,
    "staggered_batch": DiceConfig.staggered_batch,   # supplement Sec. 8
}


# ---------------------------------------------------------------------------
# modeled step latency on the target hardware (per DESIGN.md Sec. 2:
# wall-clock speedups cannot be measured on CPU; the model uses the roofline
# terms per MoE layer and the schedule's overlap structure)
# ---------------------------------------------------------------------------
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


# ---------------------------------------------------------------------------
# metrics publication (DESIGN.md Sec. 16): the registry is the single
# source of truth; the summary dicts the serving loops return are VIEWS
# computed from it.  The metric TYPE encodes the aggregation rule that
# used to be hand-maintained in three per-loop accumulator dicts: flows
# are counters (sum), per-batch sizes are max-gauges (every batch runs
# the same compiled shapes, so max IS the per-batch value), per-batch
# means are histogram means.
# ---------------------------------------------------------------------------
def _publish_batch(reg: MetricsRegistry, stats: dict, lab: dict) -> None:
    """Publish one ``DiceServer.generate`` summary into a registry."""
    reg.counter("dice_batches_total", "generate() batches executed",
                lab).inc()
    reg.histogram("dice_modeled_step_seconds",
                  "modeled per-step latency on the target deployment",
                  lab).observe(stats["modeled_step_s_tpu8"])
    reg.counter("dice_modeled_seconds_total",
                "modeled run seconds on the target deployment",
                lab).inc(stats["modeled_total_s_tpu8"])
    reg.gauge("dice_a2a_bytes_per_layer",
              "modeled per-MoE-layer all-to-all payload",
              lab).set_max(float(stats["a2a_bytes_per_layer"]))
    reg.gauge("dice_buffer_bytes", "persistent staleness-buffer footprint",
              lab).set_max(int(stats["buffer_bytes"]))
    reg.counter("dice_dispatch_bytes_total", "dispatch payload moved",
                lab).inc(float(sum(stats["dispatch_bytes_per_step"])))
    reg.counter("dice_raw_bytes_total", "lossless-equivalent payload bytes",
                lab).inc(stats["raw_bytes_total"])
    reg.gauge("dice_ring_hops", "ring collective-permutes per MoE layer",
              lab).set_max(int(stats["ring_hops"]))
    reg.counter("dice_hop_bytes_total", "per-device one-hop ring wire",
                lab).inc(float(stats["hop_bytes_total"]))
    reg.gauge("dice_overlap_efficiency",
              "fraction of comm time the selected engine hides",
              lab).set_max(float(stats["modeled_overlap_efficiency"]))
    reg.gauge("dice_plan_variants", "compiled StepPlan variants",
              lab).set_max(stats["num_plan_variants"])
    reg.gauge("dice_jit_cache_size", "jit cache entries of the step fn",
              lab).set_max(stats["jit_cache_size"])
    if "paged_transfers" in stats:
        reg.counter("dice_paged_transfers_total",
                    "expert-pool host->device fetches",
                    lab).inc(stats["paged_transfers"])
        reg.counter("dice_paged_bytes_in_total",
                    "expert-pool host->device bytes",
                    lab).inc(stats["paged_bytes_in"])
    if stats.get("peak_resident_expert_bytes") is not None:
        reg.gauge("dice_peak_resident_expert_bytes",
                  "realized per-device expert-residency peak",
                  lab).set_max(stats["peak_resident_expert_bytes"])
    if stats.get("expert_hbm_budget") is not None:
        reg.gauge("dice_expert_hbm_budget_bytes",
                  "per-device resident-expert byte budget",
                  lab).set_max(stats["expert_hbm_budget"])


def _publish_telemetry_step(reg: MetricsRegistry, tel, lab: dict) -> None:
    """Append one step's (num_layers, NUM_FIELDS) in-graph telemetry block
    to the per-layer series the closed-loop controller reads (Sec. 16)."""
    tel = np.asarray(tel)
    per_layer = {"dice_staleness_age": obs_fields.AGE,
                 "dice_mask_rate": obs_fields.MASK_RATE,
                 "dice_dropped_frac": obs_fields.DROP_FRAC,
                 "dice_codec_error": obs_fields.CODEC_ERR}
    for layer in range(tel.shape[0]):
        ll = {**lab, "layer": f"{layer:02d}"}
        reg.series("dice_residual_energy",
                   "relative staleness-residual energy per layer/step",
                   {**ll, "path": "dispatch"}).append(
                       tel[layer, obs_fields.RES_DISPATCH])
        reg.series("dice_residual_energy", "",
                   {**ll, "path": "combine"}).append(
                       tel[layer, obs_fields.RES_COMBINE])
        for name, idx in per_layer.items():
            reg.series(name, "", ll).append(tel[layer, idx])


def _publish_obs(reg: MetricsRegistry, stats: dict, lab: dict) -> None:
    """Publish the MEASURED observability extras an obs-enabled
    ``rf_sample`` carries: per-step walltimes (block_until_ready-timed),
    per-variant trace+compile seconds, and the in-graph telemetry."""
    for w in stats.get("step_wall_s", ()):
        reg.histogram("dice_step_wall_seconds",
                      "measured wall seconds per diffusion step",
                      lab).observe(w)
    for v, sec in stats.get("compile_s", {}).items():
        reg.gauge("dice_compile_seconds",
                  "trace+compile seconds of one plan variant",
                  {**lab, "variant": str(v)}).set(sec)
    for tel in stats.get("telemetry", ()):
        _publish_telemetry_step(reg, tel, lab)


def _registry_view(reg: MetricsRegistry, lab: dict) -> dict:
    """The ``serve_queue`` summary dict, computed FROM the registry —
    same keys the hand-rolled ``stats_acc`` used to maintain."""
    view = {
        "batches": int(reg.value("dice_batches_total", lab)),
        "padded": int(reg.value("dice_padded_requests_total", lab)),
        "modeled_step_s_tpu8": reg.histogram("dice_modeled_step_seconds",
                                             labels=lab).mean,
        "modeled_total_s_tpu8": reg.value("dice_modeled_seconds_total", lab),
        "a2a_bytes_per_layer": reg.value("dice_a2a_bytes_per_layer", lab),
        "buffer_bytes": int(reg.value("dice_buffer_bytes", lab)),
        "dispatch_bytes_total": reg.value("dice_dispatch_bytes_total", lab),
        # the dispatch payload IS the wire payload (codec-compressed)
        "wire_bytes_total": reg.value("dice_dispatch_bytes_total", lab),
        "raw_bytes_total": reg.value("dice_raw_bytes_total", lab),
        "ring_hops": int(reg.value("dice_ring_hops", lab)),
        "hop_bytes_total": reg.value("dice_hop_bytes_total", lab),
        "modeled_overlap_efficiency": reg.value("dice_overlap_efficiency",
                                                lab),
        "num_plan_variants": int(reg.value("dice_plan_variants", lab)),
        "jit_cache_size": int(reg.value("dice_jit_cache_size", lab)),
    }
    if reg.get("dice_paged_transfers_total", lab) is not None:
        view["paged_transfers"] = int(
            reg.value("dice_paged_transfers_total", lab))
        view["paged_bytes_in"] = int(
            reg.value("dice_paged_bytes_in_total", lab))
    if reg.get("dice_peak_resident_expert_bytes", lab) is not None:
        view["peak_resident_expert_bytes"] = int(
            reg.value("dice_peak_resident_expert_bytes", lab))
    if reg.get("dice_expert_hbm_budget_bytes", lab) is not None:
        view["expert_hbm_budget"] = int(
            reg.value("dice_expert_hbm_budget_bytes", lab))
    return view


def write_metrics(registry: MetricsRegistry, path: str) -> None:
    """Write a registry to ``path``: JSON snapshot for ``*.json``,
    Prometheus text exposition otherwise."""
    if str(path).endswith(".json"):
        registry.write_snapshot(path)
    else:
        registry.write_prometheus(path)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
class DiceServer:
    """``n_dev`` is the ep fan-out of the serving mesh; it feeds both the
    per-device local batch and the all-to-all fan-out of the latency model.

    ``mesh`` (any hierarchical dp x ep x patch mesh from
    ``launch.mesh.make_mesh``, incl. the flat ``make_ep_mesh``) makes the
    server mesh-native: ``generate`` and :func:`serve_continuous` execute
    the real sharded dispatch/combine all-to-alls via the shard_map-
    lowered step functions (DESIGN.md §10/§14), and ``n_dev`` defaults to
    the mesh's ep size (1 on an ep-less mesh) so the latency model
    describes the mesh actually running.  ``devices_per_host`` declares
    the two-tier fabric: the ring engine then runs the topology-aware hop
    schedule and the latency model prices inter-host hops at
    ``inter_host_bw``."""

    def __init__(self, cfg: ModelConfig, dcfg: DiceConfig, *,
                 params=None, seed: int = 0, n_dev: Optional[int] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 ep_axis: str = "ep",
                 compress: Optional[CompressConfig] = None,
                 overlap: Optional[str] = None,
                 placement: Optional[placement_lib.PlacementConfig] = None,
                 paging: Optional[paging_lib.PagingSpec] = None,
                 expert_pool: Optional[paging_lib.ExpertPool] = None,
                 devices_per_host: int = 0,
                 inter_host_bw: Optional[float] = None,
                 obs: Optional[ObsConfig] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 resilience: Optional[fault_lib.ResilienceConfig] = None):
        # observability plane (DESIGN.md Sec. 16): the registry is the
        # single source of truth the serving loops publish into (their
        # summary dicts are views of it); the tracer records host phases
        # as Chrome trace events.  obs off keeps every traced graph —
        # and therefore every sample — bit-identical.
        self.obs = obs if obs is not None else ObsConfig()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = StepTracer() if self.obs.enabled else None
        if compress is not None:
            # thread the wire codec into the schedule config (Sec. 11);
            # codec="none" normalizes to no compression so plans — and
            # therefore outputs — stay bit-identical to an uncompressed
            # server
            dcfg = dataclasses.replace(
                dcfg, compress=None if compress.codec == "none" else compress)
        if overlap is not None:
            # thread the a2a execution engine (Sec. 12) into the schedule
            # config; the samplers normalize "ring" away when the server
            # has no n>1 ep mesh, but the latency model keeps describing
            # the REQUESTED engine on the target n_dev-device deployment
            dcfg = dataclasses.replace(dcfg, overlap=overlap)
        if paging is not None:
            # thread the expert-paging spec (Sec. 15) into the schedule
            # config; the samplers normalize it away on mesh-less / 1-dev
            # runs, exactly like overlap and placement
            dcfg = dataclasses.replace(dcfg, paging=paging)
        resilience = fault_lib.normalize_resilience(resilience)
        if resilience is not None:
            # degradation-ladder policy (DESIGN.md Sec. 17): rides inside
            # DiceConfig like compress/paging; the planner ignores it, so
            # plans, variants, and jit-cache counts are untouched, and
            # None keeps every traced graph byte-identical
            dcfg = dataclasses.replace(dcfg, resilience=resilience)
        n_ep = (mesh.shape[ep_axis]
                if mesh is not None and ep_axis in mesh.axis_names else 1)
        if n_dev is None:
            n_dev = n_ep if mesh is not None else 8
        if n_dev < 1:
            raise ValueError(f"n_dev must be >= 1, got {n_dev}")
        self.cfg = cfg
        self.dcfg = dcfg
        self.n_dev = n_dev
        self.mesh = mesh
        self.ep_axis = ep_axis
        self.devices_per_host = devices_per_host
        self.inter_host_bw = inter_host_bw
        # topology-aware ring hop order (DESIGN.md §14): cheap intra-host
        # shifts first.  A pure permutation of the oblivious 1..n-1 order,
        # so numerics are identical; None (== natural order) off topology
        # or off the ring engine keeps the historical lowering.
        self.hop_schedule = None
        if (plan_lib.overlap_of(dcfg) and n_ep > 1
                and 0 < devices_per_host < n_ep
                and n_ep % devices_per_host == 0):
            self.hop_schedule = plan_lib.normalize_hop_schedule(
                overlap_lib.ring_hop_schedule(
                    n_ep, devices_per_host=devices_per_host), n_ep)
        # online affinity-aware placement (Sec. 13): "greedy" mode makes
        # serve_continuous accumulate a routing histogram and re-layout
        # the experts when it drifts; None / "identity" leaves the layout
        # alone (any dcfg.placements the caller pre-planned still apply)
        self.placement = placement
        self.params = params if params is not None else init_dit(
            jax.random.PRNGKey(seed), cfg)
        # expert paging (DESIGN.md Sec. 15): on an n>1 ep mesh the routed-
        # expert stacks move out of the device tree into the host-RAM
        # pool BEFORE params are sharded, so the full expert set is never
        # device-placed; the step functions page per-layer shards back in
        # along the plan's prefetch schedule.  The budget "auto" sentinel
        # resolves here — plans stamp the resolved spec.
        self.expert_pool = expert_pool
        if paging_lib.paging_of(dcfg) is not None and n_ep > 1:
            if placement is not None and placement.mode == "greedy":
                raise ValueError(
                    "expert paging and online affinity placement are "
                    "mutually exclusive: the pool serves per-layer shards "
                    "in canonical expert order (DESIGN.md Sec. 15)")
            if (self.expert_pool is None
                    and paging_lib.has_expert_leaves(self.params)):
                self.expert_pool = paging_lib.pool_from_params(
                    self.params, n_dev=n_ep)
            if self.expert_pool is None:
                raise ValueError(
                    "paging is configured but params carry no expert "
                    "leaves and no expert_pool was provided")
            dcfg = paging_lib.resolve_budget(dcfg, self.expert_pool)
            self.dcfg = dcfg
            self.params = paging_lib.strip_expert_params(self.params)
        if self.expert_pool is not None:
            # the pool's retry/fallback policy + seeded fetch faults
            # (DESIGN.md Sec. 17 rung 1) follow the server's config
            self.expert_pool.set_resilience(
                fault_lib.resilience_of(self.dcfg))
        if mesh is not None:
            # place once at construction; the per-batch ep_shard_params
            # inside make_rf_step then sees an already-sharded tree and
            # device_put is a no-op (no host->device re-transfer per batch)
            from repro.common.sharding import ep_shard_params
            self.params = ep_shard_params(
                self.params, mesh,
                ep_axis=ep_axis if ep_axis in mesh.axis_names else None)

    def plan(self, num_steps: int) -> plan_lib.SchedulePlan:
        """The compile-once schedule plan a ``generate`` call will run."""
        return plan_lib.compile_step_plans(
            self.dcfg, self.cfg.num_layers, num_steps,
            experts_per_token=self.cfg.experts_per_token)

    def generate(self, requests: List[Request], *, num_steps: int = 20,
                 guidance: float = 1.5, key=None,
                 metrics: Optional[MetricsRegistry] = None,
                 metric_labels: Optional[dict] = None):
        classes = jnp.asarray([r.class_id for r in requests], jnp.int32)
        key = key if key is not None else jax.random.PRNGKey(0)
        t0 = time.perf_counter()
        samples, stats = rf_sample(self.params, self.cfg, self.dcfg,
                                   num_steps=num_steps, classes=classes,
                                   key=key, guidance=guidance,
                                   mesh=self.mesh,
                                   ep_axis=self.ep_axis if self.mesh
                                   is not None else None,
                                   hop_schedule=self.hop_schedule,
                                   expert_pool=self.expert_pool,
                                   obs=self.obs, tracer=self.tracer)
        jax.block_until_ready(samples)
        wall = time.perf_counter() - t0
        lat = modeled_step_latency(
            self.cfg, self.dcfg, n_dev=self.n_dev,
            local_batch=max(1, len(requests) // self.n_dev),
            devices_per_host=self.devices_per_host,
            inter_host_bw=self.inter_host_bw)
        result = {
            # measured on whatever backend ran the batch, compiles included
            "wall_s": wall,
            "modeled_step_s_tpu8": lat["t_step_s"],
            "modeled_total_s_tpu8": lat["t_step_s"] * num_steps,
            "modeled_step_blocking_s": lat["t_step_blocking_s"],
            "modeled_step_ring_s": lat["t_step_ring_s"],
            "modeled_overlap_efficiency": lat["overlap_efficiency"],
            # ring execution stats (Sec. 12): collective-permutes per MoE
            # layer actually lowered (0 on the blocking path) and the
            # per-device one-hop wire total
            "ring_hops": max(stats["hops"], default=0),
            "hop_bytes_total": float(sum(stats["hop_bytes"])),
            "a2a_bytes_per_layer": lat["a2a_bytes_layer"],
            "buffer_bytes": stats["buffer_bytes"][-1] if stats["buffer_bytes"]
            else 0,
            "dispatch_bytes_per_step": stats["dispatch_bytes"],
            # wire vs raw payload (Sec. 11): with a codec the wire sum is
            # smaller; without one they are equal and the ratio is 1
            "wire_bytes_total": float(sum(stats["dispatch_bytes"])),
            "raw_bytes_total": float(sum(stats["raw_bytes"])),
            "num_plan_variants": stats["num_plan_variants"],
            "jit_cache_size": stats["jit_cache_size"],
            # expert paging observability (Sec. 15), present when the run
            # paged: host->device transfer count/bytes and the realized
            # per-device residency peak the --expert-hbm-budget bounds
            **{k: stats[k] for k in ("paged_transfers", "paged_bytes_in",
                                     "peak_resident_expert_bytes",
                                     "expert_hbm_budget") if k in stats},
        }
        # publish into the registry (the server's own, or a caller-scoped
        # one — serve_queue derives its per-call summary as a view)
        reg = metrics if metrics is not None else self.metrics
        lab = metric_labels if metric_labels is not None else {
            "schedule": plan_lib.schedule_name(self.dcfg.schedule),
            "engine": "batch"}
        _publish_batch(reg, result, lab)
        _publish_obs(reg, stats, lab)
        return samples, result


def _span(tracer: Optional[StepTracer], name: str,
          args: Optional[dict] = None, cat: str = "serve"):
    """``tracer.span(name, cat, args)``, or a no-op context without a
    tracer.  The span reads ``args`` as it ends, so the block may fill
    it in."""
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, cat=cat, args=args)


def _read(a, rb: dict) -> np.ndarray:
    """One blocking device->host read of a tick's aux, counted in the
    ``reads`` arg of its ``serve.readback`` span."""
    rb["reads"] += 1
    return np.asarray(a)


# ---------------------------------------------------------------------------
# batched serving loop (FIFO queue -> fixed-size compiled batches)
# ---------------------------------------------------------------------------
def serve_queue(server: "DiceServer", requests: List[Request], *,
                max_batch: int = 8, num_steps: int = 10,
                guidance: float = 1.5, key=None):
    """Drain a request queue through fixed-size batches (a compiled batch
    size keeps one jit cache entry; short final batches are padded with the
    null class and trimmed).  Returns {rid: sample} plus aggregate stats.

    Every per-batch quantity is published into a call-scoped
    :class:`MetricsRegistry` (folded into ``server.metrics`` on return)
    and the returned summary is a *view* of it (DESIGN.md Sec. 16): the
    metric types encode the aggregation — flows are counters, per-batch
    sizes are max-gauges, the modeled step time is a histogram mean —
    so the sum/max/running-mean rules live in one place."""
    key = key if key is not None else jax.random.PRNGKey(0)
    out: dict = {}
    reg = MetricsRegistry()
    lab = {"schedule": plan_lib.schedule_name(server.dcfg.schedule),
           "engine": "queue"}
    tracer = server.tracer
    queue = list(requests)
    t_start = time.perf_counter()
    while queue:
        batch, queue = queue[:max_batch], queue[max_batch:]
        pad = max_batch - len(batch)
        reg.series("dice_queue_depth", "requests still waiting",
                   lab).append(len(queue))
        # cfg.num_classes IS the null/uncond class id (class_embed carries
        # num_classes + 1 rows)
        padded = batch + [Request(class_id=server.cfg.num_classes,
                                  rid=-1)] * pad
        key, k = jax.random.split(key)
        with _span(tracer, "serve_queue_batch",
                   {"batch": len(batch), "pad": pad}):
            samples, _ = server.generate(padded, num_steps=num_steps,
                                         guidance=guidance, key=k,
                                         metrics=reg, metric_labels=lab)
        for i, r in enumerate(batch):
            out[r.rid] = samples[i]
        # measured per-request end-to-end latency: queue wait + execution
        # (every request of a rigid batch completes with the batch)
        done = time.perf_counter() - t_start
        e2e = reg.histogram("dice_request_e2e_seconds",
                            "request end-to-end seconds (enqueue->sample)",
                            lab)
        for _ in batch:
            e2e.observe(done)
        reg.counter("dice_requests_total", "requests served", lab).inc(
            len(batch))
        reg.counter("dice_padded_requests_total", "null-class pad slots",
                    lab).inc(pad)
    server.metrics.merge(reg)
    return out, _registry_view(reg, lab)


# ---------------------------------------------------------------------------
# continuous batching (slot-level staleness-state recycling, DESIGN.md Sec. 9)
# ---------------------------------------------------------------------------
@dataclass
class _Slot:
    """One batch lane of the continuous engine."""
    rid: int = -1
    class_id: int = 0
    local_step: int = 0
    active: bool = False


@dataclass
class _Launched:
    """An engine tick from its launch until the host has read it back.

    What its read-back and completion need is taken at launch from the
    host's own counters: the free lanes, the queue depth, and the lanes
    that finish with it (``finished``: lane, rid and the sample sliced
    from the tick's latents as a device op).  ``wall`` is set when the
    host first sees the tick ready."""
    tick: int
    slotted: bool
    variant: str
    n_free: int
    queue_len: int
    t_launch: float
    aux: Optional[dict] = None
    wall: Optional[float] = None
    finished: list = field(default_factory=list)


def request_noise(key, rid: int, cfg: ModelConfig) -> jnp.ndarray:
    """Per-request initial latent noise: (patch_tokens, in_channels).

    Keyed by ``fold_in(key, rid)`` so a request's noise — and therefore its
    sample — is independent of which slot or batch it lands in.  The
    slot-recycling equivalence guarantee (a recycled-slot sample is
    bit-identical to the same request in a fresh batch) is defined w.r.t.
    this derivation, and holds for configurations whose per-step sampling
    path consumes no randomness — ``router_jitter == 0`` and
    ``cond_policy != "random"``, i.e. the paper's serving defaults.  A
    ``random`` conditional-communication mask is drawn over the whole
    (batch*tokens, K) shape, so its per-slot rows depend on batch
    composition under ANY batching scheme and no bit-level equivalence
    across batch placements exists to preserve.
    """
    return _lane_noise(key, rid, (cfg.patch_tokens, cfg.in_channels))


def _lane_noise(key, rid, shape) -> jnp.ndarray:
    return jax.random.normal(jax.random.fold_in(key, rid), shape)


def _admit_surgery(x, states, states_u, recycle, rids, noise_key):
    """A cohort's admission surgery as one program: every recycled lane
    of ``x`` (B, T, C) takes its request's :func:`request_noise`, and its
    rows of both staleness state sets are zeroed by
    :func:`repro.core.staleness.reset_slots`.

    ``recycle`` (B,) bool marks the admitted lanes; ``rids`` (B,) uint32
    holds their request ids (``fold_in``'s own data type; the other
    lanes' entries are ignored).  Both are traced, so one compile serves
    every cohort, and ``x``, ``states`` and ``states_u`` are donated:
    the caller holds only what this returns.
    """
    T, C = x.shape[1:]
    noise = jax.vmap(lambda r: _lane_noise(noise_key, r, (T, C)))(rids)
    x = jnp.where(recycle[:, None, None], noise, x)
    return (x, stale_lib.reset_slots(states, recycle, tokens_per_slot=T),
            stale_lib.reset_slots(states_u, recycle, tokens_per_slot=T))


_admit_lanes = jax.jit(_admit_surgery, donate_argnums=(0, 1, 2))


@lru_cache(maxsize=8)
def _admit_lanes_on(batch_sharding):
    """:func:`_admit_lanes` for a mesh: every output pinned to
    ``batch_sharding`` (lanes, and the state rows that follow them, over
    the batch axes), the layout the mesh step takes, so nothing is
    re-placed after it."""
    return jax.jit(_admit_surgery, donate_argnums=(0, 1, 2),
                   out_shardings=batch_sharding)


def serve_continuous(server: "DiceServer", requests: List[Request], *,
                     max_batch: int = 8, num_steps: int = 10,
                     guidance: float = 1.5, key=None,
                     arrival_steps: Optional[List[float]] = None,
                     mesh: Optional[jax.sharding.Mesh] = None):
    """Continuous-batching serving loop: slot-level admission + recycling.

    Unlike :func:`serve_queue` (rigid FIFO batches: a finished request
    holds its slot until every peer finishes), each of the ``max_batch``
    slots carries its own step counter and completes independently; queued
    requests are admitted into freed slots at plan-variant-aligned step
    boundaries (``tick % steady_period == 0``) so every established slot
    shares the tick's StepPlan.  A recycled slot replays the schedule's
    warmup prefix via the traced per-slot selectors of
    :func:`repro.sampling.rectified_flow.make_rf_step`, its staleness rows
    zeroed by :func:`repro.core.staleness.reset_slots` — so no activation
    of the previous occupant leaks into the successor, and the jit cache
    still holds exactly one entry per plan variant.  A tick's admissions
    are one compiled program (:func:`_admit_lanes`: the lanes' noise and
    the reset of both state sets) that donates the latents and both
    state sets; those buffers never leave this function.

    Bit-identity of recycled-slot samples to fresh-batch samples holds
    for key-free sampling configurations (``router_jitter == 0`` and
    ``cond_policy != "random"`` — the serving defaults); see
    :func:`request_noise`.

    ``arrival_steps[i]`` is the tick at which ``requests[i]`` becomes
    available (default: all at tick 0).  Returns ({rid: sample}, stats)
    where stats reports the occupancy quantities behind the throughput
    benchmark: executed ticks, padded-slot step-executions, mean slot
    occupancy, and the aggregate byte/compile stats.

    ``mesh`` (default: the server's mesh) runs every tick mesh-native:
    slots shard over the ``"ep"`` axis, the admission program's outputs
    are pinned to that layout (:func:`_admit_lanes_on`) so the jitted
    step always sees one stable input layout, and the compile-count
    guarantee (jit cache == plan-variant count) carries over to the
    sharded path.

    One tick is kept in flight: tick t+1 is launched before the host
    reads back and completes tick t, so the host's per-tick work runs
    while the device computes.  Tick t+1's inputs come from the host's
    step counters and tick t's unready outputs; a finishing lane's
    sample is sliced on the device as its last tick is launched, before
    the next admission donates the latents.  The engine drops to no
    tick in flight when a policy acts on a tick's outputs before the
    next tick is decided: resilience (the watchdog reads the tick's
    wall time, quarantine scans ``x``), online placement (it re-shards
    from the routing histogram) and paging (its fetches ride inside the
    step).  A variant's first tick, whose launch compiles, also runs at
    depth 0.  ``stats["pipelined_ticks"]`` counts the ticks launched while
    the previous tick was still unread; ``stats["tick_s"]`` holds
    seconds between successive ready times.  The step donates both
    state sets (:func:`~repro.sampling.rectified_flow.make_rf_step`), so
    a tick in flight holds no third generation of the staleness
    buffers.

    With ``server.tracer`` set, every tick's host work is spanned, in
    order: ``serve.admit`` (a tick that admits; args ``tick``,
    ``admitted``, ``reset_lanes``: the lanes the admission program
    seeded and zeroed), ``serve.prepare`` (plan, slot masks, step
    inputs), ``serve.dispatch`` (the step's launch; arg ``tick``),
    ``serve.wait`` (``block_until_ready``), ``serve.readback`` (the aux
    read-back of the tick just seen ready and what consumes it; ``tick``
    names it,
    ``reads`` counts its blocking device->host reads,
    ``wire_bytes``/``raw_bytes`` the tick's one-way per-device dispatch
    payload over all its MoE calls, as sent and uncompressed),
    ``serve.quarantine`` (resilience on) and ``serve.complete`` (a tick
    where a request finishes).  A ``tick`` span (args ``tick``,
    ``slotted``, ``variant``: the plan's
    :attr:`~repro.core.plan.StepPlan.kind`, or "slotted";
    ``pipelined``: the next tick's launch is in it) ends as the host
    sees the tick it names ready and holds that ``serve.wait``.  With no
    tick in flight it also holds the tick's own launch; with one in
    flight it holds the next tick's launch instead, the launch that
    starts a run of pipelined ticks lies outside every ``tick`` span,
    and a tick drained with no launch after it (the last one, one
    before the engine idles or before a depth-0 tick) holds only its
    wait.  A request's ``admit`` and ``done`` instants share its
    ``rid``.
    """
    cfg, dcfg = server.cfg, server.dcfg
    mesh = mesh if mesh is not None else server.mesh
    ep_axis = server.ep_axis if mesh is not None else None
    if mesh is not None and "patch" in mesh.axis_names:
        raise ValueError(
            "continuous batching does not compose with a 'patch' mesh axis "
            "(slot surgery assumes batch-only sharding); use rigid batches "
            "via DiceServer.generate on patch meshes")
    n_ep = (mesh.shape[ep_axis]
            if mesh is not None and ep_axis in mesh.axis_names else 1)
    # ring overlap needs an n>1 ep axis; normalize BEFORE planning so the
    # compiled plans (and the jit-cache accounting below) match what the
    # steps execute (DESIGN.md Sec. 12).  The latency model below keeps
    # the un-normalized server.dcfg: it describes the target deployment.
    dcfg = plan_lib.normalize_overlap(dcfg, n_ep)
    # placement likewise is an n>1-mesh layout property (Sec. 13): the
    # single-device server's params are unpermuted, so placements strip
    dcfg = plan_lib.normalize_placement(dcfg, n_ep)
    # and paging (Sec. 15): one device holds every expert locally
    dcfg = paging_lib.normalize_paging(dcfg, n_ep)
    # resilience (DESIGN.md Sec. 17): the in-graph half (guards, seeded
    # corruption) rides in dcfg as a closure constant; the host-side
    # ladder — paging retry/fallback, watchdog demotion, quarantine,
    # bounded admission — lives in this loop.  res None keeps every code
    # path below byte-identical to the pre-resilience engine.
    res = fault_lib.resilience_of(dcfg)
    fplan = (fault_lib.FaultPlan(res.faults)
             if res is not None and res.faults is not None else None)
    ctrl = degrade_lib.DegradationController(res) if res is not None else None
    pool = (server.expert_pool
            if paging_lib.paging_of(dcfg) is not None else None)
    if paging_lib.paging_of(dcfg) is not None:
        if pool is None:
            raise ValueError("paging is planned but the server holds no "
                             "expert pool (construct DiceServer with "
                             "paging= on an n>1 ep mesh)")
        if pool.n_dev != n_ep:
            raise ValueError(
                f"expert pool is sharded for {pool.n_dev} devices but the "
                f"serving mesh has a {n_ep}-way ep axis")
        pool.reset_stats()
    # observability (DESIGN.md Sec. 16): a call-scoped registry (folded
    # into server.metrics on return) replaces the local accumulator
    # variables; the summary below is a view of it
    reg = MetricsRegistry()
    lab = {"schedule": plan_lib.schedule_name(dcfg.schedule),
           "engine": "continuous"}
    obs_on = server.obs.enabled
    tracer = server.tracer
    if pool is not None and tracer is not None:
        pool.tracer = tracer
    admit_time: dict = {}      # rid -> admission walltime (e2e latency)
    key = key if key is not None else jax.random.PRNGKey(0)
    noise_key, step_key = jax.random.split(key)
    B, Tp = max_batch, cfg.patch_tokens
    dt = 1.0 / num_steps
    n_pass = 2 if guidance != 1.0 else 1     # MoE forwards per step
    k_exp = cfg.experts_per_token
    b_dim = None
    if mesh is not None:
        from repro.common import sharding as shard_lib
        bax = shard_lib.batch_shard_axes(mesh)
        n_batch = 1
        for a in bax:
            n_batch *= mesh.shape[a]
        if n_batch and B % n_batch:
            raise ValueError(f"max_batch={B} must divide over the "
                             f"{n_batch}-way {bax} batch axes")
        bsp = shard_lib.hier_batch_spec(mesh)
        b_dim = bsp[0] if len(bsp) else None
    # on a mesh the admission program's outputs are pinned to the layout
    # the step takes (lanes and state rows over the batch axes): a drifted
    # layout would key extra jit-cache entries of the step
    admit_lanes = (_admit_lanes if mesh is None else _admit_lanes_on(
        jax.sharding.NamedSharding(mesh, bsp)))

    def _place(a):
        """Pin the batch to its dp x ep sharding after host-side slot
        surgery."""
        if mesh is None:
            return a
        from repro.common.sharding import hier_place_batch
        return hier_place_batch(a, mesh)

    def _build(dcfg):
        """Compile plans + step function for one placement epoch.  A
        drift-triggered re-shard swaps ``dcfg.placements`` and rebuilds —
        always from ``server.params`` (the ORIGINAL, identity-layout
        tree), which ``_make_mesh_rf_step`` re-lays-out per placement."""
        with _span(tracer, "plan_build", {"schedule": lab["schedule"],
                                          "num_steps": num_steps},
                   cat="plan"):
            splan = plan_lib.compile_step_plans(
                dcfg, cfg.num_layers, num_steps, experts_per_token=k_exp)
            merge_plan = plan_lib.slotted_merge_plan(
                dcfg, cfg.num_layers, experts_per_token=k_exp)
            if pool is not None:
                # budget was resolved at server construction; every planned
                # residency window must fit before anything compiles
                pool.validate_plan(splan)
            rf_step = make_rf_step(server.params, cfg, dcfg, dt=dt,
                                   guidance=guidance, mesh=mesh,
                                   ep_axis=ep_axis,
                                   hop_schedule=server.hop_schedule,
                                   expert_pool=pool, obs=server.obs)
        launched_variants.clear()
        return splan, merge_plan, rf_step

    launched_variants: set = set()    # (plan, slotted) run by this step fn
    splan, merge_plan, rf_step = _build(dcfg)
    period = plan_lib.steady_period(dcfg, cfg.num_layers,
                                    experts_per_token=k_exp)
    merge_wants_cache = any(a.want_cache for a in merge_plan.actions)

    # ---- online affinity-aware placement (DESIGN.md Sec. 13) -------------
    # the histogram always accumulates (it is the probe the two-pass
    # benchmark reads back); re-sharding only triggers in "greedy" mode on
    # an n>1 ep mesh, at admission-aligned boundaries, after warmup
    pcfg = server.placement
    n_place = n_ep
    place_online = (pcfg is not None and pcfg.mode == "greedy"
                    and n_place > 1)
    # one tick in flight, unless a policy acts on a tick's outputs before
    # the next tick is decided: the watchdog and quarantine (resilience),
    # the online re-shard (the routing histogram), paging (its fetches)
    pipelined = res is None and not place_online and pool is None
    hist = placement_lib.RoutingHistogram(
        cfg.num_layers, cfg.num_experts,
        decay=pcfg.ema_decay if pcfg is not None else 0.9)
    placed_shares = None      # shares snapshot behind the live placements
    planned_init = partial(stale_lib.init_planned_states, splan,
                           num_tokens=B * Tp, d_model=cfg.d_model,
                           k=k_exp, dtype=jnp.float32, mesh=mesh,
                           ep_axis=(b_dim if mesh is not None else "ep"))
    states, states_u = planned_init(), planned_init()
    x = _place(jnp.zeros((B, Tp, cfg.in_channels), jnp.float32))
    classes = np.full((B,), cfg.num_classes, np.int32)   # null = free slot
    slots = [_Slot() for _ in range(B)]
    ever_used = [False] * B

    # bounded admission (Sec. 17 rungs 4-5): with no ResilienceConfig the
    # queue is unbounded and reproduces the legacy sorted-pending-list
    # semantics exactly (FIFO by arrival then index; nothing is ever shed)
    queue = recovery_lib.AdmissionQueue(
        max_queue_depth=res.max_queue_depth if res is not None else 0,
        admission_deadline_steps=(res.admission_deadline_steps
                                  if res is not None else 0))
    for i, r in enumerate(requests):
        queue.push(0.0 if arrival_steps is None else float(arrival_steps[i]),
                   r)
    out: dict = {}
    compile_s: dict = {}     # variant label -> seconds of its compiling tick
    tick_s: list = []        # seconds of every tick that compiled nothing
    compiling: list = []     # variants compiled since a tick was last seen
    inflight: Optional[_Launched] = None   # launched, not yet read back
    t_ready: Optional[float] = None        # the last tick seen ready
    tick = 0
    t0 = time.perf_counter()

    def _next_aligned(g: float) -> int:
        g = int(np.ceil(g))
        return g + (-g) % period

    def _seen(done: _Launched) -> None:
        """The host sees ``done``'s tick ready: its seconds run from the
        previous tick's ready time (one in flight), else from its launch;
        a variant compiled meanwhile claims them."""
        nonlocal t_ready
        now = time.perf_counter()
        done.wall = now - (done.t_launch if t_ready is None else t_ready)
        for label in compiling:
            compile_s[label] = done.wall
        if not compiling:
            tick_s.append(done.wall)
        compiling.clear()
        t_ready = now if pipelined else None

    def _finish_lanes(launched: _Launched, x) -> None:
        """Step every busy lane's counter past ``launched``'s tick; a lane
        that finishes frees its slot, its sample sliced from ``x`` on
        the device (read to the host by :func:`_complete`)."""
        for i, slot in enumerate(slots):
            if slot.active:
                slot.local_step += 1
                if slot.local_step >= num_steps:
                    launched.finished.append((i, slot.rid, x[i]))
                    slots[i] = _Slot()
                    classes[i] = cfg.num_classes

    def _readback(done: _Launched) -> None:
        """The aux read-back of a tick seen ready, and the registry
        updates that consume it."""
        aux = done.aux
        rb = {"tick": done.tick, "reads": 0}
        with _span(tracer, "serve.readback", rb):
            tel = None
            if "telemetry" in aux and (obs_on or ctrl is not None):
                tel = _read(aux["telemetry"], rb)
            if obs_on:
                reg.histogram("dice_step_wall_seconds",
                              "measured wall seconds per engine tick",
                              lab).observe(done.wall)
                if tel is not None:
                    _publish_telemetry_step(reg, tel, lab)
            if ctrl is not None:
                codec_err = (None if tel is None
                             else float(tel[:, obs_fields.CODEC_ERR].mean()))
                if ctrl.observe_step(done.wall, codec_err):
                    reg.counter("dice_watchdog_breaches_total",
                                "engine-tick step-deadline breaches",
                                lab).inc()

            reg.counter("dice_ticks_total", "engine ticks executed",
                        lab).inc()
            if done.slotted:
                reg.counter("dice_slotted_ticks_total",
                            "ticks on the slotted merge plan", lab).inc()
            reg.counter("dice_padded_slot_steps_total",
                        "free-slot step executions", lab).inc(done.n_free)
            reg.series("dice_slot_occupancy", "active-slot fraction per tick",
                       lab).append(1.0 - done.n_free / B)
            reg.series("dice_queue_depth", "requests still waiting",
                       lab).append(done.queue_len)
            if "fault_events" in aux:
                fe = _read(aux["fault_events"], rb)
                for idx, nm in enumerate(("corrupt_combine",
                                          "guarded_combine",
                                          "corrupt_dispatch",
                                          "guarded_dispatch")):
                    if fe[idx]:
                        reg.counter("dice_fault_events_total",
                                    "in-graph wire corruption / guard events",
                                    {**lab, "event": nm}).inc(float(fe[idx]))
            hist.update(_read(aux["expert_counts"], rb))
            wire = float(_read(aux["dispatch_bytes"], rb))
            raw = float(_read(aux["raw_dispatch_bytes"], rb))
            reg.counter("dice_dispatch_bytes_total",
                        "dispatch payload moved", lab).inc(wire)
            reg.counter("dice_raw_bytes_total",
                        "lossless-equivalent payload bytes", lab).inc(raw)
            # aux holds the conditional pass's payload; a guided step's
            # unconditional pass runs the same plan and moves as much
            rb["wire_bytes"] = wire * n_pass
            rb["raw_bytes"] = raw * n_pass
            reg.counter("dice_hop_bytes_total",
                        "per-device one-hop ring wire",
                        lab).inc(float(_read(aux["hop_bytes"], rb)))
            reg.gauge("dice_ring_hops",
                      "ring collective-permutes per MoE layer",
                      lab).set_max(int(_read(aux["hops"], rb)))
            reg.gauge("dice_buffer_bytes",
                      "persistent staleness-buffer footprint",
                      lab).set(int(_read(aux["buffer_bytes"], rb)))

    def _complete(done: _Launched) -> None:
        """The finished lanes' samples to the host, one read for all."""
        if not done.finished:
            return
        with _span(tracer, "serve.complete",
                   {"tick": done.tick, "finished": len(done.finished)}):
            samples = jax.device_get([s for _, _, s in done.finished])
            for (i, rid, _), sample in zip(done.finished, samples):
                out[rid] = sample
                reg.counter("dice_requests_total", "requests served",
                            lab).inc()
                if rid in admit_time:
                    reg.histogram(
                        "dice_request_service_seconds",
                        "request service seconds (admission->sample; "
                        "the queue wait is not in it)",
                        lab).observe(time.perf_counter()
                                     - admit_time.pop(rid))
                if tracer is not None:
                    tracer.instant("done", args={
                        "rid": rid, "slot": i, "tick": done.tick})

    def _tick_span(done: Optional[_Launched], holds_next: bool):
        """The ``tick`` span of ``done``: it ends as the host sees that
        tick ready.  ``pipelined``: the next tick's launch is in it."""
        if done is None:
            return contextlib.nullcontext()
        return _span(tracer, "tick", {"tick": done.tick,
                                      "slotted": bool(done.slotted),
                                      "variant": done.variant,
                                      "pipelined": holds_next}, cat="step")

    def _drain() -> None:
        """Wait for the tick in flight, then read it back and complete
        it: after the last launch, and before the engine idles."""
        nonlocal inflight, t_ready
        if inflight is None:
            return
        with _tick_span(inflight, holds_next=False):
            with _span(tracer, "serve.wait"):
                jax.block_until_ready(inflight.aux)
            _seen(inflight)
        _readback(inflight)
        _complete(inflight)
        inflight, t_ready = None, None

    while len(queue) or any(s.active for s in slots):
        # ---- watchdog variant demotion at aligned boundaries (Sec. 17) ---
        # repeated step-deadline breaches while the ring engine is live
        # demote overlap ring->blocking; repeated codec-error blowups
        # demote codec->none.  Same controlled plan-swap machinery as the
        # placement re-shard below: peak jit-cache folds in via the
        # max-gauge, the rebuild swaps dcfg, nothing ever crashes.
        if ctrl is not None and tick % period == 0:
            kind = ctrl.should_demote(
                ring_live=bool(plan_lib.overlap_of(dcfg)),
                codec_live=plan_lib.codec_spec_of(dcfg) is not None)
            if kind is not None:
                reg.gauge("dice_jit_cache_size",
                          "jit cache entries of the step fn",
                          lab).set_max(int(rf_step._cache_size()))
                if tracer is not None:
                    tracer.instant("demote", args={"kind": kind,
                                                   "tick": tick})
                if kind == degrade_lib.DEMOTE_OVERLAP:
                    dcfg = dataclasses.replace(dcfg, overlap="blocking")
                else:
                    dcfg = dataclasses.replace(dcfg, compress=None)
                splan, merge_plan, rf_step = _build(dcfg)
                period = plan_lib.steady_period(dcfg, cfg.num_layers,
                                                experts_per_token=k_exp)
                merge_wants_cache = any(a.want_cache
                                        for a in merge_plan.actions)
                ctrl.record_demotion(kind)
                reg.counter("dice_demotions_total",
                            "watchdog variant demotions",
                            {**lab, "kind": kind}).inc()

        # ---- drift-triggered re-shard at aligned boundaries --------------
        # (same cadence as admission: every established slot is at a plan-
        # cycle boundary, so swapping the placement epoch never splits a
        # step sequence mid-cycle; staleness caches carry over untouched —
        # their rows follow tokens, not experts)
        if (place_online and tick % period == 0
                and hist.updates >= pcfg.warmup_ticks):
            base = (placed_shares if placed_shares is not None
                    else np.full((cfg.num_layers, cfg.num_experts),
                                 1.0 / cfg.num_experts))
            if placement_lib.drift(base, hist.shares) > pcfg.drift_threshold:
                new_pl = placement_lib.greedy_placements(
                    hist.shares, n_place,
                    replicate_top=pcfg.replicate_top)
                if all(p.is_identity for p in new_pl):
                    new_pl = None
                if new_pl != plan_lib.placements_of(dcfg):
                    # the peak across placement epochs is the jit-cache
                    # contract the benchmark asserts (== variants when no
                    # re-shard), so it folds in via the max-gauge
                    reg.gauge("dice_jit_cache_size",
                              "jit cache entries of the step fn",
                              lab).set_max(int(rf_step._cache_size()))
                    if tracer is not None:
                        tracer.instant("placement_reshard",
                                       args={"tick": tick})
                    dcfg = dataclasses.replace(dcfg, placements=new_pl)
                    splan, merge_plan, rf_step = _build(dcfg)
                    reg.counter("dice_placement_reshards_total",
                                "drift-triggered expert re-layouts",
                                lab).inc()
                placed_shares = hist.shares

        # ---- admission at plan-variant-aligned boundaries ----------------
        if tick % period == 0:
            recycle = np.zeros(B, bool)
            rids = np.zeros(B, np.uint32)
            # the span marks a tick that admits: a lane is free and a
            # request has arrived (what pop_ready below tests)
            nxt = queue.next_arrival()
            admitting = (nxt is not None and nxt <= tick
                         and not all(s.active for s in slots))
            adm = {"tick": tick, "admitted": 0, "reset_lanes": 0}
            with _span(tracer if admitting else None, "serve.admit", adm):
                for i, slot in enumerate(slots):
                    if slot.active:
                        continue
                    req = queue.pop_ready(tick)
                    if req is None:
                        break
                    slots[i] = _Slot(rid=req.rid, class_id=req.class_id,
                                     local_step=0, active=True)
                    recycle[i] = True
                    rids[i] = req.rid
                    classes[i] = req.class_id
                    reg.counter("dice_admissions_total", "slot admissions",
                                lab).inc()
                    if ever_used[i]:
                        reg.counter("dice_recycled_admissions_total",
                                    "admissions into a recycled slot",
                                    lab).inc()
                    if tracer is not None:
                        tracer.instant("admit", args={
                            "rid": req.rid, "slot": i, "tick": tick,
                            "recycled": bool(ever_used[i])})
                    admit_time[req.rid] = time.perf_counter()
                    ever_used[i] = True
                    adm["admitted"] += 1
                # load shedding (Sec. 17 rung 5): only when a depth bound or
                # admission deadline is configured — a no-op ([], peak-depth
                # bookkeeping only) on the unbounded default
                for rid in queue.shed_overdue(tick,
                                              retry_after=float(period)):
                    reg.counter("dice_shed_requests_total",
                                "requests shed by admission bounds",
                                lab).inc()
                    if tracer is not None:
                        tracer.instant("shed", args={"rid": rid,
                                                     "tick": tick})
                if recycle.any():
                    x, states, states_u = admit_lanes(
                        x, states, states_u, recycle, rids, noise_key)
                    adm["reset_lanes"] = int(recycle.sum())
                    reg.counter("dice_fused_admissions_total",
                                "admission programs launched", lab).inc()
        if not any(s.active for s in slots):
            _drain()
            nxt = queue.next_arrival()
            if nxt is None:
                break          # everything remaining was shed
            # fully idle: jump to the next aligned tick with an arrival
            tick = _next_aligned(max(nxt, tick + 1))
            continue

        # ---- one engine tick --------------------------------------------
        with _span(tracer, "serve.prepare", {"tick": tick}):
            warming = [s.active and s.local_step < dcfg.warmup_steps
                       for s in slots]
            slotted = any(warming)
            tick_key = jax.random.fold_in(step_key, tick)
            if slotted:
                plan = merge_plan
                # free slots replay warmup too: their (discarded) lanes
                # then consume only fresh values, never the zeroed buffers
                fresh_b = np.array([w or not s.active
                                    for w, s in zip(warming, slots)])
                slot_fresh = jnp.repeat(jnp.asarray(fresh_b), Tp)
                consume = None
                if merge_wants_cache:
                    light = dcfg.cond_comm and not conditional.is_refresh_step(
                        tick, dcfg.cond_stride)
                    if light:
                        steady_mask = conditional.policy_mask(
                            dcfg.cond_policy, B * Tp, k_exp, key=tick_key)
                    else:
                        steady_mask = jnp.ones((B * Tp, k_exp), bool)
                    consume = jnp.where(slot_fresh[:, None], True,
                                        steady_mask)
            else:
                ref = min(s.local_step for s in slots if s.active)
                plan_idx = min(ref, num_steps - 1)
                plan = splan.steps[plan_idx]
                slot_fresh = consume = None
            t = jnp.asarray([s.local_step * dt if s.active else 0.0
                             for s in slots], jnp.float32)
            # a copy: the host edits ``classes`` in place while this tick
            # is in flight, and on the CPU ``asarray`` may alias it
            cls = jnp.array(classes)
            n_compiled = rf_step._cache_size()

        # a variant's first launch compiles, which outlasts a tick in
        # flight: that tick runs at depth 0, so its span holds its compile
        # and its first run, as a compiling tick's span did before
        first_run = (plan, slotted) not in launched_variants
        launched_variants.add((plan, slotted))
        depth0 = not pipelined or first_run
        if depth0:
            _drain()
        launched = _Launched(tick=tick, slotted=slotted,
                             variant="slotted" if slotted else plan.kind,
                             n_free=sum(not s.active for s in slots),
                             queue_len=len(queue),
                             t_launch=time.perf_counter())
        if inflight is not None:
            reg.counter("dice_pipelined_ticks_total",
                        "ticks launched while the previous tick was "
                        "still unread", lab).inc()
        # the tick this launch's span waits for and ends on: this one at
        # depth 0, else the one in flight; none for the launch that
        # starts a run of pipelined ticks
        ready = launched if depth0 else inflight
        with _tick_span(ready, holds_next=inflight is not None):
            # the host's launch of the step: argument flattening and
            # enqueue (a compile, on a variant's first tick)
            with _span(tracer, "serve.dispatch", {"tick": tick}):
                x, states, states_u, _, _, launched.aux = rf_step(
                    x, cls, states, states_u, {}, {}, t, tick_key,
                    plan=plan, slotted=slotted, slot_fresh=slot_fresh,
                    consume_mask=consume)
            if rf_step._cache_size() > n_compiled:
                # first tick of a (plan, slotted) variant: trace + compile,
                # kept out of the steady per-tick times
                compiling.append("slotted" if slotted else
                                 f"v{splan.variant_of_step[plan_idx]}")
            if (fplan is not None and plan_lib.overlap_of(dcfg)
                    and fplan.hop_delay(tick)):
                # injected slow ring hop (Sec. 17): host-visible, so the
                # watchdog sees the walltime breach.  Gated on the LIVE
                # engine — demoting ring->blocking stops the injection,
                # the closed loop the chaos test asserts.
                reg.counter("dice_injected_hop_delays_total",
                            "injected slow ring hops", lab).inc()
                time.sleep(fplan.cfg.hop_delay_s)
            # measured (not modeled) per-tick walltime, read through the
            # tick's aux (this tick's admission donated the latents of the
            # tick in flight)
            if ready is not None:
                with _span(tracer, "serve.wait"):
                    jax.block_until_ready(ready.aux)
                _seen(ready)

        if not depth0:
            # this tick's finishing lanes are sliced before the next
            # admission donates x; the previous tick is read back while
            # this one runs
            _finish_lanes(launched, x)
            if inflight is not None:
                _readback(inflight)
                _complete(inflight)
            inflight = launched
            tick += 1
            continue
        _readback(launched)

        # ---- slot quarantine (Sec. 17 rung 4) ----------------------------
        # a non-finite lane — corruption that escaped the wire guards, or
        # the deterministic poison_tick injection — is quarantined BEFORE
        # the completion scan: its staleness rows reset, its lane zeroed
        # (so no NaN pollutes ring peers on the next tick), its request
        # requeued for a deterministic replay (request_noise is rid-keyed)
        # up to max_requeues, then shed.
        if res is not None and res.quarantine:
            qa = {"tick": tick, "quarantined": 0}
            with _span(tracer, "serve.quarantine", qa):
                if fplan is not None and fplan.poison(tick):
                    victim = next((i for i, s in enumerate(slots)
                                   if s.active), None)
                    if victim is not None:
                        x = x.at[victim].set(jnp.nan)
                        if tracer is not None:
                            tracer.instant("poison", args={"slot": victim,
                                                           "tick": tick})
                bad = ~np.isfinite(np.asarray(x).reshape(B, -1)).all(axis=1)
                hit = [i for i in range(B) if bad[i] and slots[i].active]
                qa["quarantined"] = len(hit)
                if hit:
                    qm = np.zeros(B, bool)
                    for i in hit:
                        slot = slots[i]
                        reg.counter("dice_quarantined_slots_total",
                                    "poisoned slots quarantined", lab).inc()
                        if tracer is not None:
                            tracer.instant("quarantine", args={
                                "rid": slot.rid, "slot": i, "tick": tick})
                        if queue.requeue(tick,
                                         Request(class_id=slot.class_id,
                                                 rid=slot.rid),
                                         res.max_requeues):
                            reg.counter("dice_requeued_requests_total",
                                        "quarantined requests requeued",
                                        lab).inc()
                        else:
                            reg.counter("dice_shed_requests_total",
                                        "requests shed by admission bounds",
                                        lab).inc()
                        admit_time.pop(slot.rid, None)
                        qm[i] = True
                        slots[i] = _Slot()
                        classes[i] = cfg.num_classes
                    m = jnp.asarray(qm)
                    x = jnp.where(m[:, None, None], 0.0, x)
                    states = stale_lib.reset_slots(states, m,
                                                   tokens_per_slot=Tp)
                    states_u = stale_lib.reset_slots(states_u, m,
                                                     tokens_per_slot=Tp)
                    if mesh is not None:
                        states = stale_lib.shard_states(states, mesh,
                                                        ep_axis=b_dim)
                        states_u = stale_lib.shard_states(states_u, mesh,
                                                          ep_axis=b_dim)
                        x = _place(x)

        # ---- completion: finished lanes' samples to the host --------------
        _finish_lanes(launched, x)
        _complete(launched)
        tick += 1
    _drain()

    # the latency model describes the REQUESTED deployment (server.dcfg,
    # un-normalized) but with whatever placements the run ended on — an
    # online re-shard changes the modeled wire volume going forward
    lat_dcfg = server.dcfg
    live_placements = plan_lib.placements_of(dcfg)
    if live_placements is not None:
        lat_dcfg = dataclasses.replace(lat_dcfg, placements=live_placements)
    lat = modeled_step_latency(cfg, lat_dcfg, n_dev=server.n_dev,
                               local_batch=max(1, B // server.n_dev),
                               devices_per_host=server.devices_per_host,
                               inter_host_bw=server.inter_host_bw)
    # max over placement epochs: each epoch's fresh step function holds
    # at most one entry per plan variant, and the peak is the contract
    # the benchmark asserts (== variants when no re-shard)
    reg.gauge("dice_jit_cache_size", "jit cache entries of the step fn",
              lab).set_max(int(rf_step._cache_size()))
    reg.gauge("dice_plan_variants", "compiled StepPlan variants",
              lab).set_max(splan.num_variants)
    ticks = int(reg.value("dice_ticks_total", lab))
    padded_slot_steps = int(reg.value("dice_padded_slot_steps_total", lab))
    # the summary is a VIEW of the registry (DESIGN.md Sec. 16); the
    # modeled-latency and placement quantities are single computed
    # values, not accumulations, so they read straight from their source
    stats = {
        "ticks": ticks,
        "makespan_steps": tick,
        "padded_slot_steps": padded_slot_steps,
        "slot_occupancy": 1.0 - padded_slot_steps / max(1, ticks * B),
        "slotted_ticks": int(reg.value("dice_slotted_ticks_total", lab)),
        "admissions": int(reg.value("dice_admissions_total", lab)),
        "recycled_admissions": int(
            reg.value("dice_recycled_admissions_total", lab)),
        "steady_period": period,
        # measured on whatever backend ran the ticks, each tick timed
        # after block_until_ready
        "wall_s": time.perf_counter() - t0,
        "compile_s": compile_s,
        "tick_s": tick_s,
        "modeled_step_s_tpu8": lat["t_step_s"],
        "modeled_total_s_tpu8": lat["t_step_s"] * ticks,
        "modeled_step_blocking_s": lat["t_step_blocking_s"],
        "modeled_step_ring_s": lat["t_step_ring_s"],
        "modeled_overlap_efficiency": lat["overlap_efficiency"],
        "ring_hops": int(reg.value("dice_ring_hops", lab)),
        "hop_bytes_total": reg.value("dice_hop_bytes_total", lab),
        "a2a_bytes_per_layer": lat["a2a_bytes_layer"],
        "buffer_bytes": int(reg.value("dice_buffer_bytes", lab)),
        "dispatch_bytes_total": reg.value("dice_dispatch_bytes_total", lab),
        # wire vs raw payload flows (Sec. 11): wire == dispatch_bytes_total
        "wire_bytes_total": reg.value("dice_dispatch_bytes_total", lab),
        "raw_bytes_total": reg.value("dice_raw_bytes_total", lab),
        "num_plan_variants": splan.num_variants,
        "jit_cache_size": int(reg.value("dice_jit_cache_size", lab)),
        # online placement observability (Sec. 13): the EMA the optimizer
        # would consume — the two-pass benchmark's identity run reads
        # this back as its histogram probe — plus the re-shard count and
        # the planned wire scale the run ended on
        "routing_shares": hist.shares.tolist(),
        "hist_updates": hist.updates,
        "placement_reshards": int(
            reg.value("dice_placement_reshards_total", lab)),
        "placement_wire_scale": plan_lib.placement_wire_scale(dcfg),
    }

    def _cnt(name, labels=lab):
        return reg.value(name, labels) if reg.get(name, labels) is not None \
            else 0.0

    stats["pipelined_ticks"] = int(_cnt("dice_pipelined_ticks_total"))
    if res is not None:
        # resilience observability (Sec. 17): every rung's event counts,
        # all views of the same registry the tracer/metrics exports carry
        stats.update({
            "quarantined": int(_cnt("dice_quarantined_slots_total")),
            "requeued": int(_cnt("dice_requeued_requests_total")),
            "shed": len(queue.shed),
            "shed_rids": sorted(rid for rid, _ in queue.shed),
            "queue_peak_depth": queue.peak_depth,
            "watchdog_breaches": int(_cnt("dice_watchdog_breaches_total")),
            "injected_hop_delays": int(
                _cnt("dice_injected_hop_delays_total")),
            "demotions": list(ctrl.demotions),
            "fault_events": {
                nm: float(_cnt("dice_fault_events_total",
                               {**lab, "event": nm}))
                for nm in ("corrupt_combine", "guarded_combine",
                           "corrupt_dispatch", "guarded_dispatch")},
        })
    if pool is not None:
        # drain in-flight fetches before reading the ledger (Sec. 15)
        jax.block_until_ready(x)
        reg.counter("dice_paged_transfers_total",
                    "expert-pool host->device fetches",
                    lab).inc(pool.transfers)
        reg.counter("dice_paged_bytes_in_total",
                    "expert-pool host->device bytes",
                    lab).inc(pool.bytes_transferred)
        reg.gauge("dice_peak_resident_expert_bytes",
                  "realized per-device expert-residency peak",
                  lab).set_max(pool.peak_resident_bytes)
        stats["paged_transfers"] = pool.transfers
        stats["paged_bytes_in"] = pool.bytes_transferred
        stats["peak_resident_expert_bytes"] = pool.peak_resident_bytes
        stats["expert_hbm_budget"] = paging_lib.paging_of(dcfg).budget_bytes
        if res is not None:
            reg.counter("dice_paging_fetch_errors_total",
                        "failed expert-shard fetch attempts",
                        lab).inc(pool.fetch_errors)
            reg.counter("dice_paging_fetch_retries_total",
                        "expert-shard fetch re-attempts",
                        lab).inc(pool.fetch_retries)
            reg.counter("dice_paging_stale_fallbacks_total",
                        "fetches served from the stale resident shard",
                        lab).inc(pool.stale_fallbacks)
            stats["paging_fetch_errors"] = pool.fetch_errors
            stats["paging_fetch_retries"] = pool.fetch_retries
            stats["paging_stale_fallbacks"] = pool.stale_fallbacks
    server.metrics.merge(reg)
    return out, stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--schedule", choices=list(SCHEDULES), default="dice")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--tiny", action="store_true", default=True,
                    help="CPU-sized model (default); --no-tiny for XL shapes")
    ap.add_argument("--no-tiny", dest="tiny", action="store_false")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--guidance", type=float, default=1.5)
    ap.add_argument("--n-dev", type=int, default=None,
                    help="serving mesh size for the latency model "
                         "(default: the ep mesh size, else 8)")
    ap.add_argument("--ep", type=int, default=0,
                    help="run mesh-native over an N-way 'ep' axis "
                         "(DESIGN.md §10; needs N devices, e.g. XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replica groups of the hierarchical "
                         "dp x ep x patch mesh (DESIGN.md §14): experts "
                         "replicate per group, the batch shards over "
                         "dp x ep")
    ap.add_argument("--patch", type=int, default=1,
                    help="patch-parallel split of the image-token dim "
                         "(DESIGN.md §14): displaced patch attention runs "
                         "sharded, KV freshness follows the warmup "
                         "schedule")
    ap.add_argument("--devices-per-host", type=int, default=0,
                    help="two-tier fabric: devices per host H (0 = flat). "
                         "The ring engine then orders hops intra-host "
                         "first (topology-aware schedule, §14) and the "
                         "latency model prices host-crossing hops at "
                         "--inter-host-bw")
    ap.add_argument("--inter-host-bw", type=float, default=0.2e9,
                    help="effective inter-host trunk bandwidth B/s for "
                         "the two-tier latency model (default 0.2 GB/s)")
    ap.add_argument("--codec", choices=list(CODEC_KINDS), default="none",
                    help="wire codec for staleness-era payloads (Sec. 11): "
                         "light/stale steps transmit quantized residuals "
                         "against the staleness cache; refresh steps stay "
                         "lossless")
    ap.add_argument("--topk-frac", type=float, default=0.125,
                    help="fraction of residual entries the topk_residual "
                         "codec keeps per token")
    ap.add_argument("--overlap", choices=["blocking", "ring"],
                    default="blocking",
                    help="a2a execution engine (DESIGN.md Sec. 12): "
                         "'ring' pipelines (n-1) chunked ppermute hops "
                         "against the expert FFN instead of two blocking "
                         "all-to-alls (executed when --ep > 1; always "
                         "reflected in the modeled latency)")
    ap.add_argument("--placement", choices=["identity", "greedy"],
                    default="identity",
                    help="expert placement policy (DESIGN.md Sec. 13): "
                         "'greedy' makes the continuous engine accumulate "
                         "a routing histogram and re-layout the experts "
                         "(affinity bin-pack + hot-expert replication) "
                         "when it drifts past the threshold")
    ap.add_argument("--replicate-top", type=int, default=0,
                    help="hottest experts replicated on every device "
                         "(served locally, off the wire); 0 disables")
    ap.add_argument("--paging", choices=["off", "on"], default="off",
                    help="expert paging (DESIGN.md Sec. 15): hold the full "
                         "expert set in host RAM and page per-layer shards "
                         "into device memory one MoE layer ahead of use "
                         "(needs --ep > 1; also lifts the E %% n_dev == 0 "
                         "restriction via phantom-expert padding)")
    ap.add_argument("--expert-hbm-budget", type=int, default=0,
                    help="per-device byte budget for resident routed-expert "
                         "shards under --paging on: 0 (default) auto-"
                         "resolves to the tightest feasible window, "
                         "negative means unbounded")
    ap.add_argument("--paging-depth", type=int, default=1,
                    help="prefetch distance in MoE layers: layer i issues "
                         "the fetch of layer i+depth so the transfer hides "
                         "behind the intervening compute/collectives")
    ap.add_argument("--continuous", action="store_true",
                    help="drain the requests through the continuous-"
                         "batching engine (--max-batch slots) instead of "
                         "one fixed batch")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--obs", action="store_true",
                    help="observability plane (DESIGN.md Sec. 16): in-"
                         "graph staleness telemetry, measured step "
                         "walltimes, and host-phase tracing (outputs stay "
                         "bit-identical to an obs-off run)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace-event JSON (Perfetto-"
                         "loadable) of host phases to this path "
                         "(implies --obs)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics registry here after the run: "
                         "Prometheus text, or a JSON snapshot when the "
                         "path ends in .json (implies --obs)")
    ap.add_argument("--faults", default=None,
                    help="resilience / chaos spec (DESIGN.md Sec. 17): "
                         "comma-separated key=value, e.g. 'seed=7,"
                         "corrupt=0.05,paging_err=0.3,hop_delay=0.5:0.01,"
                         "queue=16'.  Fault keys inject seeded failures; "
                         "policy keys (guards, retries, quarantine, "
                         "demote_after, queue, admit_deadline, requeues) "
                         "tune the degradation ladder.  'off' disables")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = tiny() if args.tiny else xl_config()
    dcfg = SCHEDULES[args.schedule]()
    paging = None
    if args.paging == "on":
        paging = paging_lib.PagingSpec(
            budget_bytes=(None if args.expert_hbm_budget < 0
                          else args.expert_hbm_budget),
            depth=args.paging_depth)
    params = None
    expert_pool = None
    if args.ckpt:
        # shapes and dtypes only: the restore fills the real buffers
        like = jax.eval_shape(lambda k: init_dit(k, cfg),
                              jax.random.PRNGKey(0))
        if paging is not None and args.ep > 1:
            # streamed restore straight into the paging split (Sec. 15):
            # expert stacks land in the host pool, the rest in the device
            # tree — the full param tree is never materialized at once
            params, expert_pool = paging_lib.load_pooled_checkpoint(
                args.ckpt, like, n_dev=args.ep)
        else:
            params = load_checkpoint(args.ckpt, like)
    mesh = None
    if args.ep or args.dp > 1 or args.patch > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(ep=max(1, args.ep), dp=args.dp, patch=args.patch)
    obs_on = bool(args.obs or args.trace_out or args.metrics_out)
    resilience = fault_lib.parse_resilience(args.faults)
    server = DiceServer(cfg, dcfg, params=params, n_dev=args.n_dev,
                        mesh=mesh,
                        compress=CompressConfig(codec=args.codec,
                                                topk_frac=args.topk_frac),
                        overlap=args.overlap,
                        placement=placement_lib.PlacementConfig(
                            mode=args.placement,
                            replicate_top=args.replicate_top),
                        paging=paging,
                        expert_pool=expert_pool,
                        devices_per_host=args.devices_per_host,
                        inter_host_bw=args.inter_host_bw,
                        obs=ObsConfig(enabled=obs_on),
                        resilience=resilience)
    reqs = [Request(class_id=i % cfg.num_classes, rid=i)
            for i in range(args.requests)]
    splan = server.plan(args.steps)
    mesh_tag = ""
    if mesh is not None:
        mesh_tag = ", mesh-native " + " x ".join(
            f"{mesh.shape[a]}-way {a}" for a in mesh.axis_names)
    print(f"serving {len(reqs)} requests, schedule={args.schedule}, "
          f"{args.steps} steps, model={cfg.name}, n_dev={server.n_dev}"
          + mesh_tag
          + (f", wire codec {args.codec}" if args.codec != "none" else "")
          + (", ring overlap" if args.overlap == "ring" else "")
          + (f", paging on (pool {server.expert_pool.num_experts}->"
             f"{server.expert_pool.num_wire_experts} experts, budget "
             f"{paging_lib.paging_of(server.dcfg).budget_bytes} B/dev)"
             if server.expert_pool is not None else "")
          + (", resilience on"
             + (f" (fault seed {resilience.faults.seed})"
                if resilience.faults is not None else "")
             if fault_lib.resilience_of(server.dcfg) is not None else ""))
    print(f"step plan: {splan.num_variants} compiled variants for "
          f"{splan.num_steps} steps "
          f"({[len(splan.steps_of_variant(v)) for v in range(splan.num_variants)]} "
          f"steps each)")
    def _write_obs_outputs():
        if args.trace_out and server.tracer is not None:
            server.tracer.write(args.trace_out)
            print(f"wrote step trace to {args.trace_out} "
                  f"({len(server.tracer.events)} events)")
        if args.metrics_out:
            write_metrics(server.metrics, args.metrics_out)
            print(f"wrote metrics to {args.metrics_out}")

    if args.continuous:
        arrivals = None
        if (resilience is not None and resilience.faults is not None
                and resilience.faults.burst_size > 0):
            arrivals = fault_lib.bursty_arrivals(
                len(reqs), rate=1.0,
                burst_size=resilience.faults.burst_size)
        out, stats = serve_continuous(server, reqs,
                                      max_batch=args.max_batch,
                                      num_steps=args.steps,
                                      guidance=args.guidance,
                                      arrival_steps=arrivals)
        finite = all(bool(np.isfinite(s).all()) for s in out.values())
        print(f"served {len(out)} requests continuously, finite={finite}")
        for k, v in stats.items():
            if k == "routing_shares":
                flat = np.asarray(v)
                v = (f"(L={flat.shape[0]}, E={flat.shape[1]}) "
                     f"max_share={flat.max():.3f}")
            print(f"  {k:26s} {v:.6g}" if isinstance(v, float)
                  else f"  {k:26s} {v}")
        _write_obs_outputs()
        return
    samples, stats = server.generate(reqs, num_steps=args.steps,
                                     guidance=args.guidance)
    print(f"samples: {samples.shape}, "
          f"finite={bool(jnp.isfinite(samples).all())}")
    for k, v in stats.items():
        if isinstance(v, list):
            v = f"[{v[0]:.3g} ... {v[-1]:.3g}] ({len(v)} steps)"
        elif isinstance(v, float):
            v = f"{v:.6g}"
        print(f"  {k:26s} {v}")
    _write_obs_outputs()


if __name__ == "__main__":
    main()
