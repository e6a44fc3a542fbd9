"""DiT-MoE: Diffusion Transformer with Mixture-of-Experts FFNs.

Faithful to DiT-MoE (Fei et al., arXiv:2407.11633) as used by the paper:
adaLN-zero DiT blocks, MoE FFN with top-k routed experts + shared experts,
class-conditional with a null class for CFG.  The paper's configurations:
XL = 28 layers / 8 experts (+2 shared), G = 40 layers / 16 experts (+2
shared), top-2 routing.

The forward pass takes per-MoE-layer staleness state (repro.core.staleness)
so one implementation serves every schedule: synchronous EP, displaced EP,
interweaved, and full DICE.  DistriFusion (displaced patch parallelism) is
selected via ``patch_parallel_ndev`` and threads attention-KV states
instead.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import compat
from repro.common.config import ModelConfig
from repro.core import plan as plan_lib
from repro.core import staleness as stale_lib
from repro.core.patch_parallel import (PatchParallelState,
                                       displaced_patch_attention,
                                       sharded_patch_attention)
from repro.core.schedules import DiceConfig, Schedule
from repro.core import moe as moe_lib
from repro.models import layers as L
from repro.obs.telemetry import ObsConfig
from repro.resilience import faults as fault_lib


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------
def timestep_embedding(t, dim: int = 256):
    """Sinusoidal embedding of continuous t in [0, 1]. t: (B,)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = t[:, None] * 1000.0 * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_dit(key, cfg: ModelConfig) -> Dict[str, Any]:
    """Weights in ``cfg.dtype`` (bf16 for the published configs); norm
    scales and the router stay f32."""
    dtype = jnp.dtype(cfg.dtype)
    d, c_in = cfg.d_model, cfg.in_channels
    keys = jax.random.split(key, cfg.num_layers + 6)
    params: Dict[str, Any] = {
        "patch_embed": L.dense_init(keys[0], (c_in, d), dtype=dtype),
        "pos_embed": 0.02 * jax.random.normal(keys[1], (cfg.patch_tokens, d)).astype(dtype),
        "t_mlp1": L.dense_init(keys[2], (256, d), dtype=dtype),
        "t_mlp2": L.dense_init(keys[3], (d, d), dtype=dtype),
        # +1 null class for classifier-free guidance
        "class_embed": 0.02 * jax.random.normal(
            keys[4], (cfg.num_classes + 1, d)).astype(dtype),
        "final_mod": jnp.zeros((d, 2 * d), dtype),
        "final_out": jnp.zeros((d, c_in), dtype),   # zero-init output layer
        "final_norm": L.rmsnorm_init(d),
    }
    blocks = []
    for i in range(cfg.num_layers):
        kb = jax.random.split(keys[5 + i], 3)
        blocks.append({
            "ln1": L.rmsnorm_init(d),
            "ln2": L.rmsnorm_init(d),
            "attn": L.attn_init(kb[0], d, cfg.num_heads, cfg.num_kv_heads,
                                cfg.head_dim, dtype=dtype),
            "moe": moe_lib.moe_init(kb[1], cfg, dtype=dtype),
            "adaln": jnp.zeros((d, 6 * d), dtype),  # adaLN-zero: zero-init
        })
    params["blocks"] = blocks                       # python list: per-layer state
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def dit_forward(params, x, t, y, cfg: ModelConfig, dcfg: DiceConfig,
                states: Dict[int, stale_lib.MoELayerState], *,
                step_idx: Optional[int] = None,
                plan: Optional[plan_lib.StepPlan] = None,
                patch_states: Optional[Dict[int, PatchParallelState]] = None,
                patch_parallel_ndev: int = 0,
                ep_axis: Optional[str] = None,
                key=None,
                use_pallas: bool = False,
                slot_fresh=None,
                consume_mask=None,
                patch_axis: Optional[str] = None,
                patch_fresh=None,
                patch_compose: bool = False,
                reduce_axes=None,
                hop_schedule=None,
                expert_pool=None,
                obs: Optional[ObsConfig] = None):
    """Velocity prediction.

    x: (B, T, C_in) latents; t: (B,) times; y: (B,) class ids
    (cfg.num_classes = null/uncond).  The schedule enters via ``plan`` (a
    precompiled :class:`repro.core.plan.StepPlan`, hashable, jit-static);
    callers that still think in step indices may pass ``step_idx`` instead
    and the plan is derived on the fly through the schedule registry.
    ``slot_fresh`` (B*T,) / ``consume_mask`` (B*T, K) are the continuous-
    batching engine's traced per-slot warmup-replay selectors (DESIGN.md
    Sec. 9), forwarded to every MoE layer.

    Patch parallelism comes in three flavours (DESIGN.md §14):

      * ``patch_parallel_ndev`` alone — the replicated DistriFusion
        simulation: displaced patch attention, MoE locally fresh (the
        whole model replicated; the historical baseline);
      * ``patch_parallel_ndev`` + ``patch_compose`` — the same replicated
        attention simulation COMPOSED with the staleness schedule's MoE
        path: the single-device numerics reference for the sharded axis;
      * ``patch_axis`` (inside shard_map) — the genuinely sharded axis:
        x is this device's (B_loc, T_loc, C) patch shard,
        :func:`sharded_patch_attention` exchanges KV on the mesh, and
        the MoE runs the schedule over ``ep_axis`` within the patch
        group.  ``patch_fresh`` is the traced per-row freshness selector
        (warmup / step 0); staleness buffers arrive factored (B, T, ...)
        and are flattened locally around each layer action.

    ``reduce_axes`` / ``hop_schedule`` thread through to the MoE layers
    (see :func:`repro.core.moe.moe_forward`).

    ``expert_pool`` (DESIGN.md Sec. 15): the host-RAM
    :class:`repro.core.paging.ExpertPool` backing a plan whose actions
    carry a PagingSpec.  Each layer's routed-expert shards are fetched
    from the pool INSIDE the trace — layer ``i`` issues layer
    ``i + depth``'s fetch before its own compute (the plan's
    ``prefetch`` field), and the fetch has no data dependency on the
    surrounding layers, so XLA overlaps the transfer with the ring hops
    already in flight.  The params tree must be stripped of its
    ``experts_*`` stacks (:func:`repro.core.paging.strip_expert_params`)
    and the MoE runs with the pool's padded wire-expert count, lifting
    the ``E % n_dev`` restriction.

    ``obs`` (DESIGN.md Sec. 16): an enabled :class:`ObsConfig` adds the
    fixed-shape ``"telemetry"`` (L, NUM_FIELDS) block to the aux dict
    (per-layer staleness age / residual energies / mask rate / drop
    fraction / codec error).  It is a closure constant, never a traced
    or static *argument*, and with ``obs=None`` (default) the traced
    graph is byte-identical to a build without the subsystem.  Every op
    carries its layer part's ``jax.named_scope`` (``layer_NN/attn``,
    ``layer_NN/router``, ...) either way: metadata only.
    Returns (v, new_states, new_patch_states, aux dict).
    """
    if plan is None:
        if step_idx is None:
            raise TypeError("dit_forward needs either plan= or step_idx=")
        plan = plan_lib.plan_for_step(dcfg, cfg.num_layers, step_idx,
                                      experts_per_token=cfg.experts_per_token)
    # resilience rides inside dcfg (a closure constant, like obs): the
    # planner ignores it, so plans/variants are untouched and None keeps
    # the traced graph byte-identical (DESIGN.md Sec. 17)
    res = fault_lib.resilience_of(dcfg)
    paged = any(a.paging is not None for a in plan.actions)
    if paged and expert_pool is None:
        raise ValueError("the plan carries expert paging but no expert_pool "
                         "was provided (pass repro.core.paging.ExpertPool, "
                         "or normalize the config with normalize_paging)")
    if paged and ep_axis is None:
        raise ValueError("expert paging needs a live ep mesh axis")
    fetched: Dict[int, Dict[str, Any]] = {}

    def _ensure_fetched(j: int):
        if j not in fetched:
            fetched[j] = expert_pool.device_fetch(j, ep_axis=ep_axis)
    B, T, _ = x.shape
    d = cfg.d_model
    pos_embed = params["pos_embed"]
    if patch_axis is not None:
        # this device's patch shard covers tokens [idx*T_loc, (idx+1)*T_loc)
        pos_embed = jax.lax.dynamic_slice_in_dim(
            pos_embed, jax.lax.axis_index(patch_axis) * T, T, axis=0)
    h = x @ params["patch_embed"] + pos_embed[None]
    temb = timestep_embedding(t) @ params["t_mlp1"]
    temb = jax.nn.silu(temb) @ params["t_mlp2"]
    c = temb + params["class_embed"][y]             # (B, d)
    positions = jnp.arange(T)[None, :].repeat(B, 0)

    new_states: Dict[int, stale_lib.MoELayerState] = {}
    new_patch: Dict[int, PatchParallelState] = {}
    total_lb = 0.0
    total_dispatch_bytes = 0.0
    total_raw_bytes = 0.0
    total_hop_bytes = 0.0
    ring_hops = jnp.asarray(0)
    dropped = 0.0
    served_counts = []
    telems = []
    fault_events = jnp.zeros((fault_lib.NUM_FAULT_EVENTS,), jnp.float32) \
        if res is not None else None

    for i, blk in enumerate(params["blocks"]):
        # every op of the layer under layer_NN: attn here, the MoE
        # parts (router, dispatch, expert_ffn, combine, shared_ffn,
        # stale_select) inside core/moe.py and core/staleness.py
        with jax.named_scope(f"layer_{i:02d}"):
            if paged and plan.actions[i].paging is not None:
                # issue this layer's fetch (a no-op past layer 0: the previous
                # layer already prefetched it) and the depth-ahead prefetch —
                # BEFORE this layer's compute, so the transfer rides behind
                # the attention + ring hops about to be traced
                _ensure_fetched(i)
                if plan.actions[i].prefetch is not None:
                    _ensure_fetched(plan.actions[i].prefetch)
            mod = jax.nn.silu(c) @ blk["adaln"]         # (B, 6d)
            s1, sc1, g1, s2, sc2, g2 = jnp.split(mod, 6, axis=-1)

            with jax.named_scope("attn"):
                hn = _modulate(L.rmsnorm(blk["ln1"], h, eps=cfg.norm_eps), s1, sc1)
                if patch_parallel_ndev or patch_axis is not None:
                    q = (hn @ blk["attn"]["wq"]).reshape(B, T, cfg.num_heads, cfg.head_dim)
                    k = (hn @ blk["attn"]["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
                    v = (hn @ blk["attn"]["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
                    pstate = patch_states.get(i, PatchParallelState()) if patch_states else PatchParallelState()
                    if patch_axis is not None:
                        attn, pnew = sharded_patch_attention(
                            q, k, v, pstate, patch_axis=patch_axis,
                            fresh=patch_fresh)
                    else:
                        attn, pnew = displaced_patch_attention(
                            q, k, v, pstate, n_dev=patch_parallel_ndev,
                            warmup=plan.is_warmup)
                    attn = attn.reshape(B, T, -1) @ blk["attn"]["wo"]
                    new_patch[i] = pnew
                else:
                    attn, _ = L.attn_apply(blk["attn"], hn, positions, cfg,
                                           causal=False)
                h = h + g1[:, None, :] * attn

            hn = _modulate(L.rmsnorm(blk["ln2"], h, eps=cfg.norm_eps), s2, sc2)
            if patch_parallel_ndev and not patch_compose:
                # DistriFusion replicates the model: MoE runs locally + fresh.
                flat = hn.reshape(B * T, d)
                moe_out, aux = moe_lib.moe_forward(blk["moe"], flat, cfg,
                                                   use_pallas=use_pallas,
                                                   obs=obs, resilience=res,
                                                   fault_salt=i)
                new_st = stale_lib.MoELayerState()
            else:
                flat = hn.reshape(B * T, d)
                st = states[i]
                if patch_axis is not None:
                    # factored (B, T, ...) buffers (the only layout that
                    # shards over patch) -> local flat rows, batch-major like
                    # ``flat`` above
                    st = stale_lib.flatten_state(st)
                moe_p = blk["moe"]
                wire_E = None
                if paged and plan.actions[i].paging is not None:
                    shards = fetched.pop(i)
                    moe_p = dict(moe_p, **shards)
                    wire_E = (shards["experts_gate"].shape[0]
                              * compat.axis_size(ep_axis))
                moe_out, new_st, aux = stale_lib.apply_layer_action(
                    moe_p, flat, cfg, plan.actions[i], st,
                    key=key, ep_axis=ep_axis, use_pallas=use_pallas,
                    slot_fresh=slot_fresh, consume_mask=consume_mask,
                    reduce_axes=reduce_axes, hop_schedule=hop_schedule,
                    num_wire_experts=wire_E, obs=obs,
                    resilience=res, layer_idx=i)
                if patch_axis is not None:
                    new_st = stale_lib.unflatten_state(new_st, B, T)
            new_states[i] = new_st
            total_lb += aux.lb_loss
            total_dispatch_bytes += aux.dispatch_bytes
            total_raw_bytes += aux.raw_dispatch_bytes
            if aux.hops is not None:
                ring_hops = jnp.maximum(ring_hops, aux.hops)
                total_hop_bytes += aux.hop_bytes
            dropped += aux.dropped_frac
            served_counts.append(aux.served_counts)
            telems.append(aux.telemetry)
            if fault_events is not None and aux.fault_events is not None:
                fault_events = fault_events + aux.fault_events
            h = h + g2[:, None, :] * moe_out.reshape(B, T, d).astype(h.dtype)

    fmod = jax.nn.silu(c) @ params["final_mod"]
    fs, fsc = jnp.split(fmod, 2, axis=-1)
    h = _modulate(L.rmsnorm(params["final_norm"], h, eps=cfg.norm_eps), fs, fsc)
    v = h @ params["final_out"]
    aux_out = {
        "lb_loss": total_lb / cfg.num_layers,
        "dispatch_bytes": total_dispatch_bytes,
        # the same payloads uncompressed — with a wire codec (Sec. 11) the
        # wire/raw pair makes the compression ratio visible in aggregates
        "raw_dispatch_bytes": total_raw_bytes,
        # ring-overlap execution stats (DESIGN.md Sec. 12): collective-
        # permutes per MoE layer (0 on the blocking path) and the summed
        # per-device one-hop wire payload across layers
        "hops": ring_hops,
        "hop_bytes": jnp.asarray(total_hop_bytes),
        "dropped_frac": dropped / cfg.num_layers,
        "buffer_bytes": stale_lib.state_bytes(new_states)
        + sum(p.bytes() for p in new_patch.values()),
        # (L, E) per-layer post-drop served-pair histogram — the routing
        # signal the placement optimizer accumulates (DESIGN.md Sec. 13)
        "expert_counts": jnp.stack(served_counts).astype(jnp.float32),
    }
    if obs is not None and obs.enabled:
        # (L, NUM_FIELDS) fixed-shape staleness telemetry (Sec. 16) —
        # keyed into aux only when obs is on so the off graph (and its
        # pytree structure) is exactly the historical one
        aux_out["telemetry"] = jnp.stack(telems)
    if fault_events is not None:
        # (NUM_FAULT_EVENTS,) in-graph fault accounting summed over layers
        # (Sec. 17) — keyed in only when resilience is on, same discipline
        # as telemetry
        aux_out["fault_events"] = fault_events
    mean_axes = reduce_axes if reduce_axes is not None else ep_axis
    if mean_axes is not None:
        # mesh-native execution (inside shard_map): token-mean quantities
        # average over every token-sharding axis (just ep on the flat
        # mesh; dp/ep/patch subsets on the hierarchical one, DESIGN.md
        # §14) so the reported aux is replicated; buffer_bytes scales to
        # the GLOBAL persistent footprint while dispatch_bytes stays the
        # PER-DEVICE wire payload — the quantity the paper's all-to-all
        # claim is about (DESIGN.md §10)
        aux_out["lb_loss"] = jax.lax.pmean(aux_out["lb_loss"], mean_axes)
        aux_out["dropped_frac"] = jax.lax.pmean(aux_out["dropped_frac"],
                                                mean_axes)
        # pmean, not psum: the placement histogram normalizes each layer
        # to shares, so the mean over equal-sized token shards carries the
        # identical signal while staying replicated like the other aux
        aux_out["expert_counts"] = jax.lax.pmean(aux_out["expert_counts"],
                                                 mean_axes)
        if "telemetry" in aux_out:
            # shard-local energy/rate ratios -> shard mean, replicated
            # like the rest of the aux block (staleness_age is identical
            # on every shard, so the mean is exact for it)
            aux_out["telemetry"] = jax.lax.pmean(aux_out["telemetry"],
                                                 mean_axes)
        if "fault_events" in aux_out:
            # psum, not pmean: fault events are shard-local COUNTS, and
            # the registry wants the global total
            aux_out["fault_events"] = jax.lax.psum(aux_out["fault_events"],
                                                   mean_axes)
        scale = 1
        for ax in ((mean_axes,) if isinstance(mean_axes, str)
                   else tuple(mean_axes)):
            scale *= compat.axis_size(ax)
        aux_out["buffer_bytes"] = aux_out["buffer_bytes"] * scale
    return v, new_states, new_patch, aux_out


# ---------------------------------------------------------------------------
# training-mode forward (synchronous, differentiable)
# ---------------------------------------------------------------------------
def dit_train_forward(params, x, t, y, cfg: ModelConfig, *, key=None):
    dcfg = DiceConfig.sync_ep()
    states = stale_lib.init_layer_states(cfg.num_layers)
    v, _, _, aux = dit_forward(params, x, t, y, cfg, dcfg, states,
                               step_idx=0, key=key)
    return v, aux
