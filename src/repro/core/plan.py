"""StepPlan engine: compile-once schedule planning (DESIGN.md Sec. 2).

The paper's contribution is *when* each MoE layer communicates.  Rather
than re-deciding that inside the traced step function (and re-jitting per
``step_idx``), the decision is made **once, ahead of time**: a registered
planner maps ``(DiceConfig, num_moe_layers, step_idx)`` to a ``StepPlan``
— a hashable tuple of per-layer :class:`LayerAction`\\ s.  Because only a
handful of distinct plans exist for a whole sampling run (warmup-sync,
refresh, light, ...), the sampler jits **one step function per plan
variant** instead of one per step, and the plan itself is the static
argument that keys the jit cache.

Adding a schedule is a single registered function::

    @register_schedule("scmoe_shortcut")
    def _plan_scmoe(dcfg, num_moe_layers, step_idx, k):
        ...
        return StepPlan(schedule="scmoe_shortcut", is_warmup=..., actions=...)

then ``DiceConfig(schedule="scmoe_shortcut")`` works everywhere — the
sampler, the serving engine, and the benchmarks all go through the
registry; nothing else needs to change.

The ``Schedule`` enum's ``step_staleness`` / ``num_buffers`` lookup tables
are gone: both are *derived properties of the plan* (see
:meth:`StepPlan.step_staleness` / :meth:`StepPlan.num_buffers`), computed
from the buffer read/write ops each action declares.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from repro.compress.codecs import CodecSpec
from repro.core import conditional
from repro.core.paging import PagingSpec, paging_of
from repro.core.placement import Placement
from repro.core.selective import sync_layer_mask
from repro.resilience.faults import normalize_resilience


# ---------------------------------------------------------------------------
# the plan IR
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class LayerAction:
    """What one MoE layer does this step.  Hashable; fully static.

    mode
        "sync"         run MoE(x(s)), consume it immediately
        "displaced"    run MoE(x_prev buffer), consume y_buf  (staleness 2)
        "interweaved"  run MoE(x(s)), consume y_buf           (staleness 1)
        "staggered"    two half-batch MoE calls, consume y_buf (staleness 1)
    store_y / store_x
        buffer *write* ops: persist the combined output / the dispatched
        tokens into the layer state for a later step.
    mask_policy
        Conditional-Communication mask for this step: ``None`` transmits
        every (token, rank) pair fresh; otherwise one of
        "low" | "high" | "random" (paper Table 4).
    effective_k
        ranks actually dispatched (sizes the capacity buffer); ``None``
        means the model's full experts_per_token.
    want_cache
        maintain the per-(token, rank) expert-output cache h_cache.
    codec
        wire codec for this step's payloads (DESIGN.md Sec. 11): the
        dispatch/combine all-to-alls move quantized residuals against the
        staleness cache instead of raw activations.  ``None`` is the
        lossless wire.  Planned per step like ``dispatch_capacity``:
        refresh steps stay lossless while light/stale steps compress, and
        the (hashable) spec keys the jit cache exactly like every other
        field.
    store_base
        refresh the residual-base buffer ``c_base`` from this step's
        losslessly transmitted payload, so the next compressed step has a
        fresh predictor.  Codec'd steps write the base implicitly (the
        decoded reconstruction); see ``writes_c_base``.
    overlap
        execute this step's dispatch/combine as the ring-overlap engine
        (DESIGN.md Sec. 12): 2*(n-1) chunked ``ppermute`` hops pipelined
        against the expert FFN instead of two monolithic blocking
        all-to-alls.  Same wire volume and identical per-row math — a
        pure execution-shape property, planned per step so the (hashable)
        flag keys the jit cache like every other field.  :func:`resolve`
        strips it when no ep mesh (or a 1-device axis) backs the run, so
        single-device plan variants and outputs stay bit-identical to
        blocking.
    placement
        this layer's expert layout (DESIGN.md Sec. 13): the dispatch
        buffer's expert order, the replica set served locally off the
        wire, and the histogram-informed capacity scale.  Hashable and
        planned like ``codec``; the caller contract is that the expert
        params were re-laid-out with
        :func:`repro.core.placement.placed_params` to match.  Identity
        placements normalize to ``None`` so plans — and outputs — stay
        bit-identical to pre-placement configs.
    paging / prefetch / resident
        expert paging (DESIGN.md Sec. 15): with a
        :class:`repro.core.paging.PagingSpec` stamped, this layer's
        routed-expert shards come from the host-RAM ExpertPool instead
        of the params tree.  ``prefetch`` is the MoE layer index whose
        shards this layer's body fetches AHEAD (``i + depth``, ``None``
        at the tail) — the fetch has no data dependency on this layer,
        so it hides behind the ring hops already in flight — and
        ``resident`` is the planned residency window (the layer indices
        device-resident while this layer runs), the set the HBM budget
        is validated against.  All three are hashable plan fields like
        ``codec``; ``prefetch``/``resident`` normalize to ``None``
        without a spec so paging-off plans stay equal to historical
        plans.
    """
    mode: str = "sync"
    store_y: bool = False
    store_x: bool = False
    mask_policy: Optional[str] = None
    effective_k: Optional[int] = None
    want_cache: bool = False
    codec: Optional[CodecSpec] = None
    store_base: bool = False
    overlap: bool = False
    placement: Optional[Placement] = None
    paging: Optional[PagingSpec] = None
    prefetch: Optional[int] = None
    resident: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.mode not in ("sync", "displaced", "interweaved", "staggered"):
            raise ValueError(f"unknown LayerAction mode: {self.mode}")
        if self.codec is not None and self.codec.kind == "none":
            # normalize: a "none" codec IS the lossless wire, and must be
            # indistinguishable from no codec (bit-identity + plan equality)
            object.__setattr__(self, "codec", None)
        if self.codec is not None and self.mode == "staggered":
            raise ValueError("staggered mode does not support a wire codec "
                             "(half-batch payloads have no per-batch "
                             "residual base)")
        if self.placement is not None and self.placement.is_identity:
            # normalize: the identity placement IS the pre-placement layout
            # and must be indistinguishable from no placement (bit-identity
            # + plan equality, like codec="none" / overlap on one device)
            object.__setattr__(self, "placement", None)
        if self.paging is None:
            # normalize: without a spec there is no pool to prefetch from;
            # stray prefetch/resident stamps must not break plan equality
            object.__setattr__(self, "prefetch", None)
            object.__setattr__(self, "resident", None)
        else:
            if self.placement is not None:
                raise ValueError(
                    "expert paging and affinity placement are mutually "
                    "exclusive on one layer: the pool serves shards in the "
                    "canonical expert order, a placement permutes them "
                    "(page OR place, not both)")
            if self.resident is not None:
                object.__setattr__(self, "resident",
                                   tuple(int(i) for i in self.resident))

    # -- buffer read/write accounting (drives the derived properties) -------
    @property
    def reads_y_buf(self) -> bool:
        return self.mode in ("displaced", "interweaved", "staggered")

    @property
    def reads_x_prev(self) -> bool:
        return self.mode == "displaced"

    @property
    def writes_y_buf(self) -> bool:
        return self.store_y or self.mode != "sync"

    @property
    def writes_x_prev(self) -> bool:
        return self.store_x or self.mode in ("displaced", "staggered")

    @property
    def writes_c_base(self) -> bool:
        """Keeps the residual-base buffer for the wire codec: implicitly
        when a codec is attached (the decoded reconstruction becomes the
        next base), explicitly via ``store_base`` on lossless refresh
        steps."""
        return self.codec is not None or self.store_base

    @property
    def num_buffers(self) -> int:
        """Persistent (T, d)-sized buffers this action keeps alive (the
        codec's residual base counts: compression buys bandwidth with
        memory)."""
        return (int(self.writes_y_buf) + int(self.writes_x_prev)
                + int(self.writes_c_base))

    @property
    def staleness(self) -> int:
        """Step-distance between the consumed output's input and now."""
        return {"sync": 0, "interweaved": 1, "staggered": 1,
                "displaced": 2}[self.mode]

    # -- ep-aware buffer sizing (DESIGN.md §10) -----------------------------
    def dispatch_capacity(self, num_local_tokens: int, cfg) -> int:
        """Per-device dispatch-buffer capacity this action's all-to-all
        moves.  ``num_local_tokens`` is the per-device token count — under
        a mesh the plan sizes the buffer from the LOCAL shard, so a
        Conditional-Communication light step (``effective_k < K``) shrinks
        the payload actually on the wire, not just a mask over it.

        A placement with replicas additionally scales the buffer by its
        histogram-informed ``cap_scale`` (DESIGN.md Sec. 13): with the
        hottest experts served off-wire by local replicas, the static
        per-expert capacity only needs the hottest *remaining* expert's
        headroom — this is where replication turns into genuinely fewer
        wire bytes rather than a mask over the same buffer.
        """
        from repro.core.moe import default_capacity
        cap = default_capacity(num_local_tokens, cfg, k=self.effective_k)
        if self.placement is not None:
            cap = self.placement.scaled_capacity(cap)
        return cap

    def dispatch_bytes(self, num_local_tokens: int, cfg, *,
                       itemsize: int = 4) -> int:
        """One-way per-device all-to-all payload under this action, *as it
        goes on the wire*: with a codec attached each (expert, slot) row
        costs ``CodecSpec.wire_bytes_per_row`` instead of ``d *
        itemsize``.  ``itemsize`` is the activation dtype's byte width and
        must match it for the planned == measured ``aux.dispatch_bytes``
        contract: 4 for the f32 serving/test path, 2 to count a bf16
        wire."""
        cap = self.dispatch_capacity(num_local_tokens, cfg)
        per_row = (self.codec.wire_bytes_per_row(cfg.d_model, itemsize)
                   if self.codec is not None else cfg.d_model * itemsize)
        return cfg.num_experts * cap * per_row

    def raw_dispatch_bytes(self, num_local_tokens: int, cfg, *,
                           itemsize: int = 4) -> int:
        """The same payload uncompressed — the codec-off wire size the
        serving stats report alongside ``dispatch_bytes`` so compression
        ratios are visible in aggregates."""
        return (cfg.num_experts
                * self.dispatch_capacity(num_local_tokens, cfg)
                * cfg.d_model * itemsize)


@dataclass(frozen=True)
class StepPlan:
    """Per-layer actions for one diffusion step.  Hashable -> usable as a
    ``jax.jit`` static argument; equal plans share one compiled executable."""
    schedule: str
    is_warmup: bool
    actions: Tuple[LayerAction, ...]

    @property
    def num_layers(self) -> int:
        return len(self.actions)

    @property
    def step_staleness(self) -> int:
        """Worst-case staleness any layer consumes this step (paper Sec. 1)."""
        return max((a.staleness for a in self.actions), default=0)

    @property
    def staleness_ages(self) -> Tuple[int, ...]:
        """Per-layer consumption staleness in steps — the plan-static
        ground truth the in-graph telemetry's ``staleness_age`` field
        (DESIGN.md Sec. 16) must reproduce exactly, and the per-layer
        vector a staleness-aware controller indexes when deciding where
        to spend sync steps."""
        return tuple(a.staleness for a in self.actions)

    @property
    def num_buffers(self) -> int:
        """Max persistent per-layer buffers (the paper's memory claim)."""
        return max((a.num_buffers for a in self.actions), default=0)

    @property
    def num_sync_layers(self) -> int:
        return sum(a.mode == "sync" for a in self.actions)

    @property
    def kind(self) -> str:
        """The variant by what it sends: "warmup", "sync" (every layer
        synchronous after warm-up), "light" (some layer sends fewer pairs
        than it routes: Conditional Communication) or "refresh"."""
        if self.is_warmup:
            return "warmup"
        if any(a.mask_policy is not None for a in self.actions):
            return "light"
        if all(a.mode == "sync" for a in self.actions):
            return "sync"
        return "refresh"


@dataclass(frozen=True)
class SchedulePlan:
    """All steps of a sampling run, pre-bucketed into plan variants."""
    steps: Tuple[StepPlan, ...]
    variants: Tuple[StepPlan, ...]          # unique plans, first-seen order
    variant_of_step: Tuple[int, ...]        # step -> index into variants

    @property
    def num_steps(self) -> int:
        return len(self.steps)

    @property
    def num_variants(self) -> int:
        return len(self.variants)

    def steps_of_variant(self, v: int) -> List[int]:
        return [s for s, i in enumerate(self.variant_of_step) if i == v]


# ---------------------------------------------------------------------------
# schedule registry
# ---------------------------------------------------------------------------
# planner(dcfg, num_moe_layers, step_idx, experts_per_token) -> StepPlan
Planner = Callable[..., StepPlan]

_REGISTRY: Dict[str, Planner] = {}


def schedule_name(schedule) -> str:
    """Accept a Schedule enum member or a plain registered name."""
    return getattr(schedule, "value", str(schedule))


def register_schedule(name: str, planner_fn: Optional[Planner] = None):
    """Register ``planner_fn`` under ``name``.  Usable as a decorator::

        @register_schedule("my_sched")
        def _plan(dcfg, num_moe_layers, step_idx, k): ...
    """
    def _register(fn: Planner) -> Planner:
        _REGISTRY[name] = fn
        return fn
    if planner_fn is not None:
        return _register(planner_fn)
    return _register


def get_planner(schedule) -> Planner:
    name = schedule_name(schedule)
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no planner registered for schedule {name!r}; known: "
            f"{sorted(_REGISTRY)}") from None


def registered_schedules() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# the compiler
# ---------------------------------------------------------------------------
def plan_for_step(dcfg, num_moe_layers: int, step_idx: int, *,
                  experts_per_token: int) -> StepPlan:
    """One step's plan via the registered planner for ``dcfg.schedule``.

    A ``dcfg.overlap == "ring"`` config stamps ``LayerAction.overlap`` on
    every action here, after the planner ran — one point of truth, so
    third-party registered schedules ride the ring engine for free.  A
    ``dcfg.placements`` tuple stamps each layer's expert placement the
    same way (identity entries normalize back to ``None`` inside
    ``LayerAction``), so every registered schedule gets affinity-aware
    placement without planner changes.
    """
    planner = get_planner(dcfg.schedule)
    plan = planner(dcfg, num_moe_layers, step_idx, experts_per_token)
    if overlap_of(dcfg) and not all(a.overlap for a in plan.actions):
        plan = dataclasses.replace(plan, actions=tuple(
            dataclasses.replace(a, overlap=True) for a in plan.actions))
    placements = placements_of(dcfg)
    if placements is not None:
        if len(placements) != len(plan.actions):
            raise ValueError(
                f"dcfg.placements has {len(placements)} entries for "
                f"{len(plan.actions)} MoE layers")
        plan = dataclasses.replace(plan, actions=tuple(
            dataclasses.replace(a, placement=pl)
            for a, pl in zip(plan.actions, placements)))
    pspec = paging_of(dcfg)
    if pspec is not None:
        if placements is not None:
            raise ValueError(
                "dcfg.paging and dcfg.placements are mutually exclusive: "
                "the pool serves shards in the canonical expert order, a "
                "placement permutes them")
        L = len(plan.actions)
        plan = dataclasses.replace(plan, actions=tuple(
            dataclasses.replace(
                a, paging=pspec,
                prefetch=(i + pspec.depth) if i + pspec.depth < L else None,
                resident=tuple(range(i, min(i + pspec.depth + 1, L))))
            for i, a in enumerate(plan.actions)))
    return plan


def compile_step_plans(dcfg, num_moe_layers: int, num_steps: int, *,
                       experts_per_token: int) -> SchedulePlan:
    """Precompute every step's plan and bucket steps by static shape.

    ``SchedulePlan.num_variants`` is the number of distinct compiled step
    functions a sampler needs — e.g. DICE (stride=2, warmup=2) yields 3:
    warmup-sync, refresh, light.
    """
    steps = tuple(plan_for_step(dcfg, num_moe_layers, s,
                                experts_per_token=experts_per_token)
                  for s in range(num_steps))
    variants: List[StepPlan] = []
    index: Dict[StepPlan, int] = {}
    variant_of_step = []
    for p in steps:
        if p not in index:
            index[p] = len(variants)
            variants.append(p)
        variant_of_step.append(index[p])
    return SchedulePlan(steps=steps, variants=tuple(variants),
                        variant_of_step=tuple(variant_of_step))


# ---------------------------------------------------------------------------
# built-in planners (the paper's schedules, Fig. 2 + supplement Sec. 8)
# ---------------------------------------------------------------------------
def _uniform(action: LayerAction, n: int) -> Tuple[LayerAction, ...]:
    return (action,) * n


def overlap_of(dcfg) -> bool:
    """Whether ``dcfg`` asks for the ring-overlap execution engine
    (DESIGN.md Sec. 12).  ``getattr`` so pre-overlap config objects (and
    test doubles) keep planning unchanged."""
    return getattr(dcfg, "overlap", "blocking") == "ring"


def placements_of(dcfg) -> Optional[Tuple[Placement, ...]]:
    """The per-layer expert placements of ``dcfg``, or None.  ``getattr``
    so pre-placement config objects (and test doubles) keep planning
    unchanged."""
    return getattr(dcfg, "placements", None)


def resolve(dcfg, n_ep: int):
    """``dcfg`` as it runs on an ``n_ep``-way ep axis (1 when mesh-less).

    The one place a requested config meets the mesh that serves it;
    ``DiceServer`` calls it once at construction and ``rf_sample`` once
    per call (it also takes raw configs), and every plan and step is then
    built from the result:

    * a ``CompressConfig(codec="none")`` IS the lossless wire: no codec;
    * an inert ``ResilienceConfig`` is no resilience
      (:func:`repro.resilience.faults.normalize_resilience`), so "off"
      keeps every traced graph byte-identical;
    * without an n>1 ep axis, ``overlap="ring"``, ``placements`` and
      ``paging`` strip.  Each is a property of a multi-device axis: on
      one device the ring has no wire to hide (hop 0 is the whole
      layer), every expert is already local in the caller's unpermuted
      params, and nothing needs paging in.  A plan that still carried
      them would key extra jit entries for the same computation (and a
      placement would mis-index the experts), so single-device plans and
      samples stay bit-identical to the plain config.

    Idempotent, and returns ``dcfg`` itself when nothing changes.  Reads
    each field by ``getattr``, so configs without one plan unchanged.
    """
    changes = {}
    compress = getattr(dcfg, "compress", None)
    if compress is not None and compress.codec == "none":
        changes["compress"] = None
    res = getattr(dcfg, "resilience", None)
    if normalize_resilience(res) is not res:
        changes["resilience"] = normalize_resilience(res)
    if n_ep <= 1:
        if overlap_of(dcfg):
            changes["overlap"] = "blocking"
        if placements_of(dcfg) is not None:
            changes["placements"] = None
        if paging_of(dcfg) is not None:
            changes["paging"] = None
    return dataclasses.replace(dcfg, **changes) if changes else dcfg


def normalize_hop_schedule(hop_schedule, n_dev: int):
    """Canonicalize a ring hop order against the live ep axis size.

    A hop schedule is a pure permutation of the ring shifts ``1..n-1`` —
    it reorders the all-to-all's collective-permutes without changing
    what any device receives, so numerics are identical by construction.
    Normalizing the natural order (and anything on a <=1-device axis)
    to ``None`` keeps mesh-less and oblivious-ring runs on the exact
    historical code path, as :func:`resolve` does for the config.
    """
    if hop_schedule is None or n_dev <= 1:
        return None
    sched = tuple(int(h) for h in hop_schedule)
    if sorted(sched) != list(range(1, n_dev)):
        raise ValueError(
            f"hop_schedule {sched} is not a permutation of 1..{n_dev - 1}")
    if sched == tuple(range(1, n_dev)):
        return None
    return sched


def placement_wire_scale(dcfg) -> float:
    """Mean planned capacity scale over layers (1.0 without placements) —
    the factor by which placement shrinks every capacity-sized wire
    payload; the latency model scales its a2a volume by it."""
    placements = placements_of(dcfg)
    if not placements:
        return 1.0
    return sum(p.cap_scale if p is not None else 1.0
               for p in placements) / len(placements)


def codec_spec_of(dcfg) -> Optional[CodecSpec]:
    """The planned wire codec of ``dcfg``, or None.  The lossless-refresh
    cadence is ``dcfg.cond_stride`` (shared with Conditional Communication
    by design: light steps both shrink AND compress the payload, refresh
    steps stay bit-lossless)."""
    compress = getattr(dcfg, "compress", None)
    return compress.spec() if compress is not None else None


def _plan_sync(dcfg, num_moe_layers, step_idx, k) -> StepPlan:
    """Baseline EP: blocking dispatch+combine, no persistent buffers.
    ``is_warmup`` still tracks the config (the patch-parallel attention
    path warms up independently of the MoE schedule)."""
    return StepPlan(schedule="sync", is_warmup=step_idx < dcfg.warmup_steps,
                    actions=_uniform(LayerAction(mode="sync"), num_moe_layers))


def _plan_displaced(dcfg, num_moe_layers, step_idx, k) -> StepPlan:
    """DistriFusion-style: both collectives deferred, 2-step staleness."""
    cspec = codec_spec_of(dcfg)
    if step_idx < dcfg.warmup_steps:
        a = LayerAction(mode="sync", store_y=True, store_x=True,
                        store_base=cspec is not None)
        return StepPlan(schedule="displaced", is_warmup=True,
                        actions=_uniform(a, num_moe_layers))
    refresh = conditional.is_refresh_step(step_idx, dcfg.cond_stride)
    a = LayerAction(mode="displaced",
                    codec=None if refresh else cspec,
                    store_base=cspec is not None and refresh)
    return StepPlan(schedule="displaced", is_warmup=False,
                    actions=_uniform(a, num_moe_layers))


def _plan_interweaved(dcfg, num_moe_layers, step_idx, k) -> StepPlan:
    """Dispatch in-step, combine deferred: 1-step staleness, 1 buffer."""
    cspec = codec_spec_of(dcfg)
    if step_idx < dcfg.warmup_steps:
        a = LayerAction(mode="sync", store_y=True,
                        store_base=cspec is not None)
        return StepPlan(schedule="interweaved", is_warmup=True,
                        actions=_uniform(a, num_moe_layers))
    refresh = conditional.is_refresh_step(step_idx, dcfg.cond_stride)
    a = LayerAction(mode="interweaved",
                    codec=None if refresh else cspec,
                    store_base=cspec is not None and refresh)
    return StepPlan(schedule="interweaved", is_warmup=False,
                    actions=_uniform(a, num_moe_layers))


def _plan_staggered_batch(dcfg, num_moe_layers, step_idx, k) -> StepPlan:
    """Supplement Sec. 8: the rejected alternative — 1-step staleness but
    2 persistent buffers and halved effective GEMM batch."""
    if step_idx < dcfg.warmup_steps:
        # store_x already during warmup: steady-state staggered writes the
        # dispatch buffer every step (it is write-only bookkeeping, never
        # read), and keeping the state pytree structure constant lets all
        # warmup + steady steps share the planned state layout.
        a = LayerAction(mode="sync", store_y=True, store_x=True)
        return StepPlan(schedule="staggered_batch", is_warmup=True,
                        actions=_uniform(a, num_moe_layers))
    return StepPlan(schedule="staggered_batch", is_warmup=False,
                    actions=_uniform(LayerAction(mode="staggered"),
                                     num_moe_layers))


def _plan_dice(dcfg, num_moe_layers, step_idx, k) -> StepPlan:
    """Interweaved + selective sync (deep layers) + conditional comm.

    With a :class:`repro.compress.codecs.CompressConfig` attached the
    async layers' light steps additionally compress their wire payloads
    (quantized residuals vs the staleness cache); refresh steps and the
    protected sync layers stay bit-lossless, and lossless steps of async
    layers refresh the residual base (``store_base``) so the next light
    step has a fresh predictor.
    """
    warmup = step_idx < dcfg.warmup_steps
    sync_mask = sync_layer_mask(dcfg.sync_policy, num_moe_layers,
                                fraction=dcfg.sync_fraction)
    want_cache = bool(dcfg.cond_comm)
    refresh = conditional.is_refresh_step(step_idx, dcfg.cond_stride)
    cspec = codec_spec_of(dcfg)
    actions = []
    for i in range(num_moe_layers):
        # async-in-steady-state layers keep a residual base; protected
        # sync layers never compress and never need one (keeping the
        # per-layer state pytree constant across all plan variants)
        wants_codec = cspec is not None and not bool(sync_mask[i])
        if warmup or bool(sync_mask[i]):
            actions.append(LayerAction(mode="sync", store_y=True,
                                       want_cache=want_cache,
                                       store_base=wants_codec))
        elif dcfg.cond_comm:
            actions.append(LayerAction(
                mode="interweaved",
                mask_policy=None if refresh else dcfg.cond_policy,
                effective_k=k if refresh
                else conditional.policy_effective_k(dcfg.cond_policy, k),
                want_cache=True,
                codec=None if refresh else cspec,
                store_base=wants_codec and refresh))
        else:
            actions.append(LayerAction(
                mode="interweaved",
                codec=None if refresh else cspec,
                store_base=wants_codec and refresh))
    return StepPlan(schedule="dice", is_warmup=warmup,
                    actions=tuple(actions))


register_schedule("sync", _plan_sync)
register_schedule("displaced", _plan_displaced)
register_schedule("interweaved", _plan_interweaved)
register_schedule("staggered_batch", _plan_staggered_batch)
register_schedule("dice", _plan_dice)


# ---------------------------------------------------------------------------
# steady-state probe (backs the Schedule enum's derived properties)
# ---------------------------------------------------------------------------
def steady_state_plan(schedule, *, num_moe_layers: int = 2,
                      experts_per_token: int = 2) -> StepPlan:
    """A representative post-warmup refresh-step plan for ``schedule`` with
    its default DiceConfig — the source of truth for the schedule-level
    ``step_staleness`` / ``num_buffers`` quantities the paper tabulates."""
    from repro.core.schedules import DiceConfig, Schedule
    name = schedule_name(schedule)
    factories = {
        "sync": DiceConfig.sync_ep,
        "displaced": DiceConfig.displaced,
        "interweaved": DiceConfig.interweaved,
        "dice": DiceConfig.dice,
        "staggered_batch": DiceConfig.staggered_batch,
    }
    if name in factories:
        dcfg = factories[name]()
    else:                       # registered third-party schedule
        dcfg = DiceConfig(schedule=name)  # type: ignore[arg-type]
    return steady_state_plan_for(dcfg, num_moe_layers,
                                 experts_per_token=experts_per_token)


def steady_state_plan_for(dcfg, num_moe_layers: int, *,
                          experts_per_token: int) -> StepPlan:
    """The plan of the first post-warmup refresh step under ``dcfg`` — what
    the latency model treats as the schedule's characteristic step."""
    step = dcfg.warmup_steps
    while not conditional.is_refresh_step(step, dcfg.cond_stride):
        step += 1
    return plan_for_step(dcfg, num_moe_layers, step,
                         experts_per_token=experts_per_token)


# ---------------------------------------------------------------------------
# continuous batching: per-slot warmup support (DESIGN.md Sec. 9)
# ---------------------------------------------------------------------------
def steady_period(dcfg, num_moe_layers: int, *, experts_per_token: int,
                  max_period: int = 8) -> int:
    """Period of the post-warmup plan sequence (1 for sync, and for
    displaced / interweaved without a wire codec; ``cond_stride`` for
    DICE's refresh/light alternation AND for any codec'd schedule, whose
    steady state alternates lossless-refresh and compressed-light steps
    on the same cadence — Sec. 11).

    The continuous-batching engine admits requests only at global ticks
    ``g % steady_period == 0`` ("plan-variant-aligned step boundaries"), so
    every established slot — whatever tick it was admitted at — is at the
    same point of the steady-state plan cycle and the whole batch shares
    one StepPlan per tick.
    """
    w = dcfg.warmup_steps
    probe = [plan_for_step(dcfg, num_moe_layers, w + i,
                           experts_per_token=experts_per_token)
             for i in range(2 * max_period)]
    for p in range(1, max_period + 1):
        if all(probe[i] == probe[i + p] for i in range(len(probe) - p)):
            return p
    raise ValueError(
        f"schedule {schedule_name(dcfg.schedule)!r} has no steady-state "
        f"period <= {max_period}; continuous batching cannot align "
        f"admissions")


def slotted_merge_plan(dcfg, num_moe_layers: int, *,
                       experts_per_token: int) -> StepPlan:
    """The plan a mixed warmup/steady tick executes under per-slot select.

    A recycled slot must replay the schedule's warmup prefix (sync-mode
    steps with full dispatch) while established slots continue in steady
    state.  Rather than compiling a new hybrid variant per mixture, the
    engine runs the schedule's *steady-state full-dispatch plan* — the
    refresh variant, which already exists in the SchedulePlan — and
    resolves the per-slot difference with TRACED masks:

      * ``slot_fresh`` (tokens,): warmup-slot tokens consume the freshly
        combined output (sync semantics) instead of ``y_buf``;
      * ``consume_mask`` (tokens, K): warmup-slot rows are all-fresh while
        established rows follow their local step's conditional-
        communication mask (all-fresh on refresh phase, policy mask on
        light phase).

    Because the masks are traced, every warmup mixture shares ONE compiled
    entry keyed by (this plan, slotted=True) — the jit cache still holds
    exactly ``SchedulePlan.num_variants`` entries.
    """
    return steady_state_plan_for(dcfg, num_moe_layers,
                                 experts_per_token=experts_per_token)
