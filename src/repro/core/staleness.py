"""Staleness buffers: the functional JAX encoding of asynchronous execution.

On GPUs the paper's schedules are built from async NCCL handles; the
numerical effect is deterministic — *which step's activations each MoE
layer consumes*.  We encode exactly that as per-layer state threaded
through the sampling loop (DESIGN.md Sec. 2):

  SYNC         y(s) = MoE(x(s))                      state: {}
  DISPLACED    y(s) = MoE(x(s-2))                    state: {x_prev, y_buf}   (2 buffers)
  INTERWEAVED  y(s) = MoE(x(s-1))                    state: {y_buf}           (1 buffer)
  DICE         interweaved + deep layers sync + conditional-communication
               cache of per-(token, rank) expert outputs

The *decision* of which mode each layer runs in lives in the StepPlan
engine (repro.core.plan): a registered planner emits a per-step, per-layer
:class:`~repro.core.plan.LayerAction`, and :func:`apply_layer_action` here
is the sole executor.  ``moe_step`` remains as the step-indexed
convenience wrapper (it plans one step on the fly).

The buffer counts reproduce the paper's memory claim (interweaved halves
displaced's persistent buffers); ``state_bytes`` makes it measurable.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.core import conditional
from repro.core.moe import MoEAux, moe_forward
from repro.core.plan import LayerAction, plan_for_step
from repro.core.schedules import DiceConfig, Schedule
from repro.obs import telemetry as obs_telemetry
from repro.obs.telemetry import ObsConfig


@dataclass
class MoELayerState:
    """Per-MoE-layer staleness buffers (pytree)."""
    y_buf: Optional[jnp.ndarray] = None     # (T,d) combined output of step s-1
    x_prev: Optional[jnp.ndarray] = None    # (T,d) displaced-only: step s-1 tokens
    h_cache: Optional[jnp.ndarray] = None   # (T,K,d) conditional-comm cache
    c_base: Optional[jnp.ndarray] = None    # (T,d) wire-codec residual base:
    #                                         the DECODED dispatch payload of
    #                                         the last transmission (Sec. 11)

    def bytes(self) -> int:
        tot = 0
        for a in (self.y_buf, self.x_prev, self.h_cache, self.c_base):
            if a is not None:
                tot += a.size * a.dtype.itemsize
        return tot


jax.tree_util.register_dataclass(
    MoELayerState, data_fields=["y_buf", "x_prev", "h_cache", "c_base"],
    meta_fields=[])


def init_layer_states(num_moe_layers: int) -> Dict[int, MoELayerState]:
    return {i: MoELayerState() for i in range(num_moe_layers)}


def init_planned_states(splan, *, num_tokens: int, d_model: int, k: int,
                        dtype=jnp.float32, mesh=None,
                        ep_axis: str = "ep",
                        patch_axis: Optional[str] = None,
                        token_shape=None) -> Dict[int, MoELayerState]:
    """Pre-allocate exactly the buffers a SchedulePlan will ever write.

    Zero-filled buffers are never *read* before a warmup step overwrites
    them; allocating them up front keeps the state pytree structure
    constant across the whole run, so the jitted step function compiles
    exactly once per plan variant (no extra cache entry when the first
    warmup step would otherwise change the pytree signature).

    With ``mesh`` the buffers are placed sharded over the ``ep_axis`` mesh
    axis (token dim 0 — the sharding of the activations they cache,
    DESIGN.md §10), so the mesh-native step function starts from the
    layout its shard_map expects instead of paying a reshard on first use.

    ``token_shape`` selects the buffer layout: ``None`` keeps the
    historical flat ``(num_tokens, ...)`` rows (correct whenever only
    batch-sharding axes are in play — flat rows are batch-major, so
    contiguous chunks ARE batch shards), while a ``(B, T)`` tuple
    allocates batch-and-token-factored ``(B, T, ...)`` buffers, the only
    layout whose shards line up with a mesh that ALSO splits the image-
    token dim over ``"patch"`` (DESIGN.md §14).  With ``mesh``, specs
    follow the layout via :func:`state_specs`.
    """
    states = {}
    lead = tuple(token_shape) if token_shape is not None else (num_tokens,)
    num_layers = splan.steps[0].num_layers if splan.steps else 0
    for i in range(num_layers):
        acts = [p.actions[i] for p in splan.variants]
        states[i] = MoELayerState(
            y_buf=jnp.zeros(lead + (d_model,), dtype)
            if any(a.writes_y_buf for a in acts) else None,
            x_prev=jnp.zeros(lead + (d_model,), dtype)
            if any(a.writes_x_prev for a in acts) else None,
            h_cache=jnp.zeros(lead + (k, d_model), dtype)
            if any(a.want_cache for a in acts) else None,
            c_base=jnp.zeros(lead + (d_model,), dtype)
            if any(a.writes_c_base for a in acts) else None)
    if mesh is not None:
        states = shard_states(states, mesh, ep_axis=ep_axis,
                              patch_axis=patch_axis)
    return states


def state_specs(states, *, ep_axis="ep", patch_axis: Optional[str] = None):
    """PartitionSpec pytree matching ``states`` — the in/out specs of the
    mesh-native step function's shard_map.

    Flat layout (``patch_axis=None``): every buffer (``y_buf`` (T, d),
    ``h_cache`` (T, K, d), ...) shards its leading token dim over
    ``ep_axis`` — a single axis name or, on a hierarchical mesh, the
    tuple of batch-sharding axes — and replicates the rest.  Factored
    layout (``patch_axis`` given): buffers are (B, T, ...) with batch
    over ``ep_axis`` and the token dim over ``patch_axis``.
    """
    from jax.sharding import PartitionSpec as P
    if patch_axis is None:
        return jax.tree.map(lambda _: P(ep_axis), states)
    return jax.tree.map(lambda _: P(ep_axis, patch_axis), states)


def shard_states(states, mesh, *, ep_axis="ep",
                 patch_axis: Optional[str] = None):
    """Place staleness state on ``mesh`` under :func:`state_specs`.

    Used at init and after any eager surgery (e.g. the continuous
    engine's :func:`reset_slots` at quarantine) so the jitted step always
    sees one stable input sharding — a changed layout would otherwise key
    a fresh jit-cache entry and break the compile-count guarantee.
    """
    from jax.sharding import NamedSharding
    return jax.tree.map(
        lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)),
        states, state_specs(states, ep_axis=ep_axis, patch_axis=patch_axis))


def flatten_state(s: MoELayerState) -> MoELayerState:
    """(B, T, ...) factored buffers -> flat (B*T, ...) local rows, the
    shape :func:`apply_layer_action` computes in.  Batch-major, matching
    ``hn.reshape(B*T, d)`` in the model forward."""
    def _f(a):
        return None if a is None else a.reshape((-1,) + a.shape[2:])
    return MoELayerState(y_buf=_f(s.y_buf), x_prev=_f(s.x_prev),
                         h_cache=_f(s.h_cache), c_base=_f(s.c_base))


def unflatten_state(s: MoELayerState, b: int, t: int) -> MoELayerState:
    """Inverse of :func:`flatten_state`."""
    def _u(a):
        return None if a is None else a.reshape((b, t) + a.shape[1:])
    return MoELayerState(y_buf=_u(s.y_buf), x_prev=_u(s.x_prev),
                         h_cache=_u(s.h_cache), c_base=_u(s.c_base))


def state_bytes(states: Dict[int, MoELayerState]) -> int:
    return sum(s.bytes() for s in states.values())


def reset_slots(states: Dict[int, MoELayerState], slot_mask, *,
                tokens_per_slot: int) -> Dict[int, MoELayerState]:
    """Zero the staleness rows of recycled batch slots.

    ``slot_mask`` is a (B,) bool array marking slots being handed to a new
    request; each slot owns ``tokens_per_slot`` consecutive token rows of
    every buffer.  Zeroing y_buf / x_prev / h_cache rows guarantees no
    activation from a completed request leaks into its successor's sample
    — a recycled slot starts from exactly the all-zeros planned-init state
    a fresh batch would have (DESIGN.md Sec. 9).

    Handles both buffer layouts: flat ``(B * tokens_per_slot, ...)`` rows
    (slot tokens are consecutive) and the factored ``(B, T, ...)`` layout
    of patch-sharded runs, where the leading dim IS the slot dim.
    """
    slot = jnp.asarray(slot_mask, bool)
    tok = jnp.repeat(slot, tokens_per_slot)

    def _zero(buf):
        if buf is None:
            return None
        m = slot if buf.shape[0] == slot.shape[0] else tok
        m = m.reshape((-1,) + (1,) * (buf.ndim - 1))
        return jnp.where(m, jnp.zeros_like(buf), buf)

    return {i: MoELayerState(y_buf=_zero(s.y_buf), x_prev=_zero(s.x_prev),
                             h_cache=_zero(s.h_cache), c_base=_zero(s.c_base))
            for i, s in states.items()}


def _cache_update_mask(mask, pair_keep):
    """Pairs whose cache entry may take the freshly combined value: they
    must have been transmitted fresh (mask) AND survived capacity (keep) —
    a capacity-overflowed pair gathers zeros, and storing those would
    poison h_cache for every later light step."""
    if pair_keep is None:
        return mask
    if mask is None:
        return pair_keep
    return mask & pair_keep


def apply_layer_action(p, x, cfg: ModelConfig, action: LayerAction,
                       state: MoELayerState, *,
                       key=None, ep_axis: Optional[str] = None,
                       use_pallas: bool = False,
                       slot_fresh=None, consume_mask=None,
                       reduce_axes=None, hop_schedule=None,
                       num_wire_experts: Optional[int] = None,
                       obs: Optional[ObsConfig] = None,
                       resilience=None, layer_idx: int = 0):
    """Execute one MoE layer under a planned :class:`LayerAction`.

    x: (T, d) flat tokens.  All schedule decisions (mode, mask, capacity,
    buffer writes) are already baked into ``action`` — this function is
    pure dataflow and traces identically for equal actions, which is what
    lets the sampler share one compiled executable per plan variant.

    ``slot_fresh`` / ``consume_mask`` implement the continuous-batching
    engine's per-slot warmup replay (DESIGN.md Sec. 9).  Both are TRACED:
    ``slot_fresh`` (T,) marks tokens of slots replaying the warmup prefix
    — their non-sync actions consume the freshly combined output (sync
    semantics) instead of the staleness buffer — and ``consume_mask``
    (T, K) carries the per-slot conditional-communication mask (all-fresh
    rows for warmup slots, the local step's policy mask for established
    slots).  ``None`` for both (the default) is the ordinary uniform-batch
    path.  ``resilience`` / ``layer_idx`` thread the fault-injection and
    wire-guard config (DESIGN.md Sec. 17) down to :func:`moe_forward`,
    with the layer index as the per-layer injection salt; ``None`` keeps
    the traced graph byte-identical.  Returns (y, new_state, aux).
    """
    mask = None
    if action.mask_policy is not None:
        k = cfg.experts_per_token
        mkey = key
        # a "random" policy mask must differ per token shard: the global
        # mask is the concatenation of independent per-device draws, not
        # one draw repeated across the mesh — fold in the device index of
        # every axis the tokens shard over (just ep on the flat mesh)
        fold_axes = reduce_axes if reduce_axes is not None else (
            (ep_axis,) if ep_axis is not None else ())
        if mkey is not None:
            for ax in fold_axes:
                mkey = jax.random.fold_in(mkey, jax.lax.axis_index(ax))
        mask = conditional.policy_mask(action.mask_policy, x.shape[0], k,
                                       key=mkey)
    if slot_fresh is not None and consume_mask is not None \
            and action.want_cache and action.mode != "sync":
        # slotted execution: the per-slot composed mask replaces the
        # uniform policy mask (the merged plan dispatches at full capacity)
        mask = consume_mask

    want_cache = action.want_cache

    def run(inp, m=None, cache=None):
        # per-device capacity carried by the plan, sized from THIS call's
        # token count: inside shard_map inp is the local shard (so light
        # steps genuinely shrink the wire payload), and staggered mode's
        # half-batch calls get half-batch buffers
        capacity = action.dispatch_capacity(inp.shape[0], cfg)
        return moe_forward(p, inp, cfg, capacity=capacity, fresh_mask=m,
                           h_cache=cache, ep_axis=ep_axis, key=key,
                           use_pallas=use_pallas, want_pair_vals=want_cache,
                           codec=action.codec, dispatch_base=state.c_base,
                           overlap=action.overlap,
                           placement=action.placement,
                           reduce_axes=reduce_axes,
                           hop_schedule=hop_schedule,
                           num_wire_experts=num_wire_experts,
                           obs=obs, resilience=resilience,
                           fault_salt=layer_idx)

    def next_base(payload, aux):
        """Residual base for the next wire transmission (Sec. 11): the
        DECODED reconstruction on codec'd steps (both endpoints advance
        from what was actually received), the lossless payload on
        ``store_base`` refresh steps, else carried through unchanged so
        the state pytree structure never varies across plan variants."""
        if action.codec is not None:
            return aux.wire_payload
        if action.store_base:
            return payload
        return state.c_base

    @jax.named_scope("stale_select")
    def select_out(y_new, y_buf):
        """Consumed output: warmup-slot tokens take the fresh combine."""
        if slot_fresh is None:
            return y_buf
        return jnp.where(slot_fresh[:, None], y_new, y_buf)

    if action.mode == "sync":
        y, aux = run(x)
        new = MoELayerState(
            y_buf=y if action.store_y else None,
            x_prev=x if action.store_x else None,
            h_cache=conditional.update_cache(state.h_cache, aux.pair_vals,
                                             _cache_update_mask(None, aux.pair_keep))
            if want_cache else None,
            c_base=next_base(x, aux))
        return y, new, obs_telemetry.stamp_age(aux, action, obs)

    if action.mode == "displaced":
        # experts process tokens dispatched at s-1; their combine lands at s+1,
        # so the output consumed *now* is the buffered result of x(s-2).
        # Warmup slots run sync: their experts see x(s), and they consume it.
        with jax.named_scope("stale_select"):
            inp = state.x_prev if slot_fresh is None else \
                jnp.where(slot_fresh[:, None], x, state.x_prev)
        y_new, aux = run(inp)
        out = select_out(y_new, state.y_buf)
        new = MoELayerState(y_buf=y_new, x_prev=x, h_cache=None,
                            c_base=next_base(inp, aux))
        return out, new, obs_telemetry.stamp_age(aux, action, obs)

    if action.mode == "staggered":
        # supplement Sec. 8: sub-batches interleave so each half overlaps the
        # other's communication — 1-step staleness like interweaved, but BOTH
        # the dispatched tokens and the combined results persist (2 buffers,
        # the memory cost the paper rejected it for), and each expert GEMM
        # runs at half the effective batch (utilization cost).
        half = x.shape[0] // 2
        y0, aux0 = run(x[:half])
        y1, aux1 = run(x[half:])
        y_new = jnp.concatenate([y0, y1], axis=0)
        out = select_out(y_new, state.y_buf)
        new = MoELayerState(y_buf=y_new, x_prev=x, h_cache=None,
                            c_base=state.c_base)
        aux = MoEAux(lb_loss=(aux0.lb_loss + aux1.lb_loss) / 2,
                     dropped_frac=(aux0.dropped_frac + aux1.dropped_frac) / 2,
                     dispatch_bytes=aux0.dispatch_bytes + aux1.dispatch_bytes,
                     pair_vals=None, scores=None, pair_keep=None,
                     raw_dispatch_bytes=aux0.raw_dispatch_bytes
                     + aux1.raw_dispatch_bytes,
                     # two INDEPENDENT half-batch ring exchanges: the
                     # layer lowers both rings' permutes (4*(n-1) total),
                     # each hop moving one half-batch chunk
                     hops=aux0.hops + aux1.hops,
                     hop_bytes=aux0.hop_bytes,
                     counts=aux0.counts + aux1.counts,
                     served_counts=aux0.served_counts + aux1.served_counts,
                     telemetry=obs_telemetry.merge_staggered(
                         aux0.telemetry, aux1.telemetry),
                     fault_events=None if aux0.fault_events is None
                     else aux0.fault_events + aux1.fault_events)
        return out, new, obs_telemetry.stamp_age(aux, action, obs)

    # "interweaved": dispatch of x(s) completes within step s (overlapped
    # with the previous layer's expert compute); only the combine is deferred,
    # so the output consumed now is the buffered result of x(s-1).
    y_new, aux = run(x, mask, state.h_cache if want_cache else None)
    out = select_out(y_new, state.y_buf)
    new = MoELayerState(
        y_buf=y_new, x_prev=None,
        h_cache=conditional.update_cache(state.h_cache, aux.pair_vals,
                                         _cache_update_mask(mask, aux.pair_keep))
        if want_cache else None,
        c_base=next_base(x, aux))
    return out, new, obs_telemetry.stamp_age(aux, action, obs)


def moe_step(p, x, cfg: ModelConfig, dcfg: DiceConfig,
             state: MoELayerState, *,
             moe_layer_idx: int, num_moe_layers: int, step_idx: int,
             key=None, ep_axis: Optional[str] = None,
             use_pallas: bool = False):
    """One MoE layer under a staleness schedule (step-indexed wrapper).

    Plans ``step_idx`` through the schedule registry and executes this
    layer's action.  The sampler avoids the per-step planning by compiling
    a SchedulePlan once (repro.core.plan.compile_step_plans) and calling
    :func:`apply_layer_action` via the plan-parameterised model forward.
    Returns (y, new_state, aux).
    """
    plan = plan_for_step(dcfg, num_moe_layers, step_idx,
                         experts_per_token=cfg.experts_per_token)
    return apply_layer_action(p, x, cfg, plan.actions[moe_layer_idx], state,
                              key=key, ep_axis=ep_axis, use_pallas=use_pallas)


def staleness_of(schedule: Schedule) -> int:
    return schedule.step_staleness
