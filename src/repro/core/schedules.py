"""Step-level parallelism schedules (paper Fig. 2) and the DICE config.

Staleness (paper Sec. 1): the step-distance between when a MoE layer's
input activations were produced and the step whose output consumes them.

  SYNC         staleness 0   blocking dispatch+combine     (baseline EP)
  DISPLACED    staleness 2   both collectives deferred     (DistriFusion-style)
  INTERWEAVED  staleness 1   dispatch in-step, combine deferred (ours, free)
  DICE         staleness 1   + selective sync + conditional communication

These quantities are no longer hand-maintained tables: both enum
properties are *derived* from the schedule's steady-state StepPlan
(repro.core.plan), so a registered planner is the single source of truth.
``DiceConfig.schedule`` also accepts a plain registered-schedule name
(string) so new schedules plug in without touching this enum.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Optional, Tuple, Union

from repro.compress.codecs import CompressConfig
from repro.core.paging import PagingSpec
from repro.core.placement import Placement
from repro.resilience.faults import ResilienceConfig


class Schedule(enum.Enum):
    SYNC = "sync"
    DISPLACED = "displaced"
    INTERWEAVED = "interweaved"
    DICE = "dice"
    # supplement Sec. 8: the staggered-batch alternative the paper REJECTED —
    # 1-step staleness like interweaved, but persistent dispatch AND combine
    # buffers (2x memory) and halved effective GEMM batch (utilization loss)
    STAGGERED_BATCH = "staggered_batch"

    @property
    def step_staleness(self) -> int:
        """Worst-case staleness at steady state (derived from the plan)."""
        from repro.core.plan import steady_state_plan
        return steady_state_plan(self).step_staleness

    @property
    def num_buffers(self) -> int:
        """Persistent per-layer buffers (paper: interweaved halves memory);
        derived from the steady-state plan's buffer write ops."""
        from repro.core.plan import steady_state_plan
        return steady_state_plan(self).num_buffers


@dataclass(frozen=True)
class DiceConfig:
    # a Schedule member, or the registered name of a third-party planner
    schedule: Union[Schedule, str] = Schedule.DICE
    # -- layer level: selective synchronization ------------------------------
    sync_policy: str = "deep"        # none | deep | shallow | staggered
    sync_fraction: float = 0.5       # fraction of layers protected
    # -- token level: conditional communication ------------------------------
    cond_comm: bool = True
    cond_stride: int = 2             # non-top-1 pairs refresh every n steps
    cond_policy: str = "low"         # low | high | random (ablation Table 4)
    # -- cold start -----------------------------------------------------------
    warmup_steps: int = 2            # synchronized steps post cold start
    # -- wire level: residual compression of staleness-era payloads -----------
    # (DESIGN.md Sec. 11) None == lossless wire; the planner also treats a
    # CompressConfig(codec="none") as lossless, so plans stay bit-identical
    compress: Optional[CompressConfig] = None
    # -- execution level: how the dispatch/combine collectives lower ----------
    # (DESIGN.md Sec. 12) "blocking" = two monolithic all-to-alls around the
    # expert FFN; "ring" = (n-1)-hop chunked ppermute pipeline that hides
    # each hop's wire time behind the expert GEMMs.  Resolved back to
    # "blocking" when no n>1 ep mesh backs the run (plan.resolve).
    overlap: str = "blocking"
    # -- expert level: affinity-aware placement + hot-expert replication ------
    # (DESIGN.md Sec. 13) one Placement per MoE layer, stamped onto every
    # LayerAction by the plan compiler; the caller re-lays-out the expert
    # params to match (repro.core.placement.placed_params).  None — or a
    # tuple of identity placements, which normalize away — is the
    # pre-placement layout.  Resolved to None when no n>1 ep mesh backs
    # the run (plan.resolve).
    placements: Optional[Tuple[Optional[Placement], ...]] = None
    # -- memory level: expert paging + async prefetch --------------------------
    # (DESIGN.md Sec. 15) with a PagingSpec the routed-expert stacks live in
    # a host-RAM ExpertPool and each device keeps only a (depth+1)-layer
    # window of shards resident, fetched one layer ahead inside the traced
    # step.  Stamped onto every LayerAction by the plan compiler (prefetch /
    # resident fields); mutually exclusive with ``placements``.  Resolved
    # to None when no n>1 ep mesh backs the run (plan.resolve), so
    # mesh-less runs stay bit-identical to fully-resident configs.
    paging: Optional[PagingSpec] = None
    # -- resilience level: fault injection + degraded modes --------------------
    # (DESIGN.md Sec. 17) seeded deterministic fault injection plus the
    # degradation ladder (wire guards, paging retry/stale-fallback, variant
    # demotion, quarantine, bounded admission).  Rides inside the config
    # like compress/paging — the planner ignores it, so plans, jit
    # signatures, and the plan-variant count are untouched; None (the
    # default) keeps graphs byte-identical to the pre-resilience stack.
    # Inert configs resolve to None (plan.resolve, through
    # repro.resilience.faults.normalize_resilience).
    resilience: Optional[ResilienceConfig] = None

    def __post_init__(self):
        if self.overlap not in ("blocking", "ring"):
            raise ValueError(f"overlap must be 'blocking' or 'ring', got "
                             f"{self.overlap!r}")

    @staticmethod
    def sync_ep(*, overlap="blocking") -> "DiceConfig":
        return DiceConfig(schedule=Schedule.SYNC, sync_policy="none",
                          cond_comm=False, warmup_steps=0, overlap=overlap)

    @staticmethod
    def displaced(*, compress=None, overlap="blocking") -> "DiceConfig":
        return DiceConfig(schedule=Schedule.DISPLACED, sync_policy="none",
                          cond_comm=False, compress=compress, overlap=overlap)

    @staticmethod
    def interweaved(*, compress=None, overlap="blocking") -> "DiceConfig":
        return DiceConfig(schedule=Schedule.INTERWEAVED, sync_policy="none",
                          cond_comm=False, compress=compress, overlap=overlap)

    @staticmethod
    def dice(*, sync_policy="deep", cond_stride=2, cond_policy="low",
             compress=None, overlap="blocking") -> "DiceConfig":
        return DiceConfig(schedule=Schedule.DICE, sync_policy=sync_policy,
                          cond_comm=True, cond_stride=cond_stride,
                          cond_policy=cond_policy, compress=compress,
                          overlap=overlap)

    @staticmethod
    def staggered_batch(*, overlap="blocking") -> "DiceConfig":
        return DiceConfig(schedule=Schedule.STAGGERED_BATCH,
                          sync_policy="none", cond_comm=False,
                          overlap=overlap)
