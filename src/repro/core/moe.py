"""Capacity-based Mixture-of-Experts with expert parallelism.

This is the substrate the paper's staleness schedules operate on.  The
dispatch path is sort-based (MaxText-style), never materialising the
GShard (T, E, C) one-hot tensors:

  1. top-k routing -> (token, rank) -> expert assignments,
  2. stable-sort pairs by expert, position-in-expert via group offsets,
  3. scatter into a static (E, capacity, d) buffer (overflow pairs drop),
  4. expert-parallel all-to-all over the "model" mesh axis (dispatch),
  5. grouped expert FFN on local experts,
  6. all-to-all back (combine) + score-weighted un-permute.

Steps 4/6 are the two collectives the paper identifies as the bottleneck
(60-80% of inference time); every staleness optimisation in repro.core
re-schedules *when* their results are consumed.

``fresh_mask`` / ``h_cache`` implement the paper's Conditional
Communication (Sec 4.3 / Alg 4): pairs whose mask is False are NOT
dispatched (they do not occupy buffer capacity -> smaller all-to-all) and
their contribution to the weighted sum comes from the cached expert output
of an earlier step.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.common import compat
from repro.common.config import ModelConfig
from repro.compress import codecs as codec_lib
from repro.core import overlap as overlap_lib
from repro.core.placement import Placement
from repro.models.layers import dense_init
from repro.obs import telemetry as obs_telemetry
from repro.obs.telemetry import ObsConfig
from repro.resilience import faults as fault_lib
from repro.resilience.faults import ResilienceConfig


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig, *, dtype=jnp.bfloat16):
    d, f, E = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d, E), dtype=jnp.float32),
        "experts_gate": dense_init(ks[1], (E, d, f), dtype=dtype),
        "experts_up": dense_init(ks[2], (E, d, f), dtype=dtype),
        "experts_down": dense_init(ks[3], (E, f, d), dtype=dtype),
    }
    if cfg.num_shared_experts:
        fs = cfg.shared_width
        p["shared_gate"] = dense_init(ks[4], (d, fs), dtype=dtype)
        p["shared_up"] = dense_init(ks[5], (d, fs), dtype=dtype)
        p["shared_down"] = dense_init(ks[6], (fs, d), dtype=dtype)
    return p


def default_capacity(num_tokens: int, cfg: ModelConfig, *,
                     k: Optional[int] = None, ep_degree: int = 1,
                     floor: int = 8) -> int:
    """Static per-expert capacity (rounded up to ``floor`` — 8 keeps TPU
    lane alignment; decode paths may lower it since the padded slots turn
    directly into wasted expert GEMM flops)."""
    k = cfg.experts_per_token if k is None else k
    c = math.ceil(num_tokens * k * cfg.capacity_factor / cfg.num_experts)
    return max(floor, -(-c // floor) * floor)


# ---------------------------------------------------------------------------
# routing + dispatch plan
# ---------------------------------------------------------------------------
class DispatchPlan(NamedTuple):
    slot: jnp.ndarray        # (T*K,) destination slot e*C+pos, == E*C if dropped
    t_sorted: jnp.ndarray    # (T*K,) source token per sorted pair
    inv_order: jnp.ndarray   # (T*K,) unsort permutation
    keep: jnp.ndarray        # (T*K,) bool, sorted order
    capacity: jnp.ndarray    # () static int
    counts: jnp.ndarray      # (E,) tokens routed per expert (pre-drop)


@jax.named_scope("router")
def route(p, x, cfg: ModelConfig, *, key=None):
    """Router probabilities + top-k selection.  x: (T, d).

    An optional ``p["router_bias"]`` (E,) adds to the logits — the
    routing-skew knob synthetic workloads use to shape the per-expert
    traffic histogram (benchmarks' ``--skew zipf:a``); absent in real
    checkpoints, where the trained router carries its own skew.
    """
    logits = x.astype(jnp.float32) @ p["router"]
    if "router_bias" in p:
        logits = logits + p["router_bias"].astype(jnp.float32)[None, :]
    if key is not None and cfg.router_jitter > 0:
        logits += cfg.router_jitter * jax.random.normal(key, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)
    scores, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    return probs, scores, idx


@jax.named_scope("dispatch")
def make_plan(idx, E: int, capacity: int,
              fresh_mask: Optional[jnp.ndarray] = None,
              num_slots: Optional[int] = None) -> DispatchPlan:
    """Sort-based dispatch plan.  idx: (T, K) expert ids.

    ``num_slots`` is the dispatch buffer's expert dimension when it is
    WIDER than the routable id space — expert paging pads the wire to
    ``E_pad = ceil(E / n_dev) * n_dev`` with phantom experts the router
    never emits (DESIGN.md Sec. 15); the drop slot moves past the padded
    buffer so dropped pairs stay out of phantom rows.  Default: ``E``."""
    S = E if num_slots is None else num_slots
    T, K = idx.shape
    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    if fresh_mask is not None:
        # Stale pairs never enter the buffer: route them to a virtual expert
        # (any id >= E sorts after all real pairs) and drop them from
        # dispatch entirely.
        flat_e = jnp.where(fresh_mask.reshape(-1), flat_e, S)
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    t_sorted = flat_t[order]
    counts = jnp.bincount(jnp.clip(flat_e, 0, E), length=E + 1)[:E]
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K) - starts[jnp.clip(e_sorted, 0, E - 1)]
    keep = (pos < capacity) & (e_sorted < E)
    slot = jnp.where(keep, e_sorted * capacity + pos, S * capacity)
    inv_order = jnp.argsort(order, stable=True)
    return DispatchPlan(slot=slot, t_sorted=t_sorted, inv_order=inv_order,
                        keep=keep, capacity=jnp.asarray(capacity),
                        counts=counts)


@jax.named_scope("dispatch")
def dispatch(x, plan: DispatchPlan, E: int, capacity: int):
    """Scatter tokens into the (E, C, d) dispatch buffer."""
    d = x.shape[-1]
    vals = x[plan.t_sorted] * plan.keep[:, None].astype(x.dtype)
    buf = jnp.zeros((E * capacity, d), x.dtype)
    buf = buf.at[plan.slot].set(vals, mode="drop")
    return buf.reshape(E, capacity, d)


@jax.named_scope("combine")
def combine(buf_out, plan: DispatchPlan, scores, T: int, *,
            h_cache: Optional[jnp.ndarray] = None,
            fresh_mask: Optional[jnp.ndarray] = None):
    """Score-weighted un-permute.  buf_out: (E, C, d).

    Returns (y, pair_vals, pair_keep) where pair_vals (T, K, d) are the
    per-pair expert outputs actually used (fresh or cached) — the
    Conditional Communication cache for the next step — and pair_keep
    (T, K) marks the pairs that actually made it through dispatch
    (unsorted order).  A pair that was transmitted fresh but overflowed
    capacity gathers zeros; pair_keep lets callers avoid treating those
    zeros as valid expert output (e.g. storing them into h_cache).
    """
    E, C, d = buf_out.shape
    flat = buf_out.reshape(E * C, d)
    gathered = flat.at[plan.slot].get(mode="fill", fill_value=0.0)
    gathered = gathered * plan.keep[:, None].astype(flat.dtype)
    K = scores.shape[-1]
    pair_vals = gathered[plan.inv_order].reshape(T, K, d)
    pair_keep = plan.keep[plan.inv_order].reshape(T, K)
    if h_cache is not None and fresh_mask is not None:
        pair_vals = jnp.where(fresh_mask[..., None], pair_vals,
                              h_cache.astype(pair_vals.dtype))
    y = jnp.einsum("tk,tkd->td", scores.astype(jnp.float32),
                   pair_vals.astype(jnp.float32))
    return y, pair_vals, pair_keep


# ---------------------------------------------------------------------------
# expert FFN (grouped, gated) — jnp reference; Pallas kernel in repro.kernels
# ---------------------------------------------------------------------------
@jax.named_scope("expert_ffn")
def expert_ffn(p, buf, *, act: str = "silu", use_pallas: bool = False):
    """buf: (E_local, C, d) -> (E_local, C, d)."""
    if use_pallas:
        from repro.kernels.ops import expert_ffn_pallas
        return expert_ffn_pallas(buf, p["experts_gate"], p["experts_up"],
                                 p["experts_down"], act=act)
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    h = fn(jnp.einsum("ecd,edf->ecf", buf, p["experts_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["experts_up"])
    return jnp.einsum("ecf,efd->ecd", h, p["experts_down"])


@jax.named_scope("shared_ffn")
def shared_expert(p, x, *, act: str = "silu"):
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    return (fn(x @ p["shared_gate"]) * (x @ p["shared_up"])) @ p["shared_down"]


# ---------------------------------------------------------------------------
# load-balance aux loss (switch-style)
# ---------------------------------------------------------------------------
@jax.named_scope("router")
def load_balance_loss(probs, idx, E: int, ep_axis=None):
    """Switch-style aux loss.  ``ep_axis`` is the axis (or tuple of axes
    — the hierarchical dp x ep x patch mesh shards tokens over several)
    the token batch is sharded over; ``None`` means unsharded.

    Reducing over a tuple is dp-invariant by construction: pmean over
    identical dp replicas is exact in floating point ((x + x) / 2 == x),
    so the dp=2 loss on per-replica-identical batches equals the dp=1
    loss bit-for-bit (the property test in test_mesh_hierarchy.py).
    """
    T, K = idx.shape
    frac_routed = jnp.mean(
        jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1), axis=0)  # (E,)
    mean_prob = jnp.mean(probs, axis=0)
    if ep_axis is not None:
        # global-batch loss under expert parallelism: the loss is bilinear
        # in these two batch means, so average each across the equal-sized
        # token shards BEFORE the product — a pmean of per-shard losses
        # would be a mean of products, not the global switch loss
        frac_routed = jax.lax.pmean(frac_routed, ep_axis)
        mean_prob = jax.lax.pmean(mean_prob, ep_axis)
    return E * jnp.sum(frac_routed / K * mean_prob)


# ---------------------------------------------------------------------------
# full forward — single device or expert-parallel
# ---------------------------------------------------------------------------
class MoEAux(NamedTuple):
    lb_loss: jnp.ndarray
    dropped_frac: jnp.ndarray      # capacity drops over DISPATCHED pairs only
    dispatch_bytes: jnp.ndarray    # per-device all-to-all payload (one way,
    #                                AS TRANSMITTED: codec-compressed)
    pair_vals: Optional[jnp.ndarray]
    scores: Optional[jnp.ndarray]
    pair_keep: Optional[jnp.ndarray] = None   # (T, K) survived dispatch
    raw_dispatch_bytes: Optional[jnp.ndarray] = None  # same payload, lossless
    wire_payload: Optional[jnp.ndarray] = None  # (T, d) decoded dispatch
    #                                payload — the codec's next residual base
    hops: Optional[jnp.ndarray] = None       # collective-permutes this layer
    #                                ran (2*(n-1) on the ring, 0 blocking)
    hop_bytes: Optional[jnp.ndarray] = None  # per-device wire bytes of ONE
    #                                ring hop (e_loc * C * wire row bytes) —
    #                                like dispatch_bytes, counted under the
    #                                Sec.-11 wire model where BOTH directions
    #                                carry codec'd residuals
    counts: Optional[jnp.ndarray] = None  # (E,) fresh pairs ROUTED per
    #                                expert (expert-id space, post-mask,
    #                                PRE-capacity-drop — demand)
    served_counts: Optional[jnp.ndarray] = None  # (E,) fresh pairs actually
    #                                SERVED per expert (post-capacity-drop,
    #                                wire-kept + replica-served) — what the
    #                                placement histogram accumulates, so
    #                                dropped tokens never inflate a hot
    #                                expert's score (Sec. 13)
    telemetry: Optional[jnp.ndarray] = None  # (obs.NUM_FIELDS,) f32 in-graph
    #                                staleness telemetry (DESIGN.md Sec. 16):
    #                                [age, residual energy dispatch/combine,
    #                                mask rate, dropped frac, codec error].
    #                                None unless an enabled ObsConfig is
    #                                passed, so obs=off graphs are
    #                                byte-identical to pre-obs builds
    fault_events: Optional[jnp.ndarray] = None  # (faults.NUM_FAULT_EVENTS,)
    #                                f32 in-graph fault accounting
    #                                (DESIGN.md Sec. 17): [combine rows
    #                                corrupted, combine rows guarded,
    #                                dispatch rows corrupted, dispatch rows
    #                                guarded].  None unless a
    #                                ResilienceConfig is passed, so
    #                                resilience=off graphs stay
    #                                byte-identical


def moe_forward(p, x, cfg: ModelConfig, *,
                capacity: Optional[int] = None,
                fresh_mask: Optional[jnp.ndarray] = None,
                h_cache: Optional[jnp.ndarray] = None,
                ep_axis: Optional[str] = None,
                key=None,
                use_pallas: bool = False,
                want_pair_vals: bool = False,
                codec: Optional[codec_lib.CodecSpec] = None,
                dispatch_base: Optional[jnp.ndarray] = None,
                overlap: bool = False,
                placement: Optional[Placement] = None,
                reduce_axes=None,
                hop_schedule=None,
                num_wire_experts: Optional[int] = None,
                obs: Optional[ObsConfig] = None,
                resilience: Optional[ResilienceConfig] = None,
                fault_salt: int = 0):
    """MoE layer forward.  x: (T, d) flat tokens (per-device shard if EP).

    ``ep_axis``: mesh axis name for expert parallelism — call inside
    shard_map with experts sharded over that axis; the two lax.all_to_all
    calls are the paper's dispatch/combine collectives.  ``capacity`` (and
    the default derived from T) is the PER-DEVICE capacity: inside
    shard_map T is the local token shard, so a Conditional-Communication
    light step's smaller ``effective_k`` shrinks the (E, C, d) buffer each
    device puts on the wire — ``aux.dispatch_bytes`` reports exactly that
    one-way per-device payload.

    ``codec`` / ``dispatch_base`` (DESIGN.md Sec. 11): with a codec, both
    collectives carry quantized residuals instead of raw activations.  The
    dispatch payload is encoded against ``dispatch_base`` (the decoded
    payload of the previous step; zeros if None), decoded on arrival —
    routing still sees the full-precision ``x`` (routing is local, only
    the wire is lossy) — and the reconstruction is returned as
    ``aux.wire_payload`` for the caller to store as the next base.  The
    combine payload is encoded against ``h_cache`` (the per-(token, rank)
    expert-output cache — both endpoints hold it), so its reconstruction
    feeds the weighted sum AND becomes the next cache entry via
    ``aux.pair_vals``.  ``aux.dispatch_bytes`` reports the wire
    (compressed) payload, ``aux.raw_dispatch_bytes`` the lossless size.

    ``overlap`` (DESIGN.md Sec. 12): replace each monolithic all-to-all +
    grouped FFN with the (n-1)-hop ``ppermute`` ring of
    :mod:`repro.core.overlap`, whose chunk transfers hide behind the
    expert GEMMs.  A no-op when ``ep_axis is None`` or the axis has one
    device (the StepPlan engine normalizes the flag away there so plans
    and outputs stay bit-identical); the total wire volume and
    ``aux.dispatch_bytes`` are unchanged — only the collective shape is
    (``aux.hops`` / ``aux.hop_bytes`` report the decomposition).

    ``placement`` (DESIGN.md Sec. 13): the dispatch buffer indexes
    experts in the placement's device-major wire order (the expert
    stacks in ``p`` must already be permuted to match —
    :func:`repro.core.placement.placed_params`), and pairs routed to a
    replicated expert never enter it: they dispatch into a small LOCAL
    buffer served by the ``experts_*_rep`` replica stacks (the identical
    per-row math, so outputs match the identity layout bit-for-bit),
    riding the ring's hop-1 wire time as its prelude.  The caller passes
    the placement-scaled ``capacity`` (``LayerAction.dispatch_capacity``)
    — that scaling, not the masking, is what shrinks the statically
    shaped wire payload.  Identity placements must be passed as ``None``
    (the StepPlan engine normalizes them away).

    ``reduce_axes`` (DESIGN.md §14): on a hierarchical dp x ep x patch
    mesh the token batch shards over MORE axes than the all-to-alls run
    on; the tuple names every token-sharding axis so the lb loss averages
    the true global batch.  ``None`` keeps the historical flat-ep
    behaviour (reduce over ``ep_axis`` alone).  ``hop_schedule`` is the
    topology-aware hop order :func:`repro.core.overlap.ring_hop_schedule`
    derives; ``None`` is the natural ring order.

    ``num_wire_experts`` (DESIGN.md Sec. 15): the expert dimension of the
    wire/dispatch buffers when the expert stacks in ``p`` are PADDED
    past ``cfg.num_experts`` — expert paging pads to the next multiple
    of the ep-axis size with zero-weight phantom experts so ANY expert
    count serves on any mesh.  The router only ever emits real ids, so
    phantom rows carry zero tokens and contribute nothing; with
    ``num_wire_experts == E`` (or ``None``) every code path below is
    exactly the historical one.  Requires ``ep_axis``; incompatible with
    ``placement`` (the pool serves canonical expert order).

    ``resilience`` (DESIGN.md Sec. 17): deterministic NaN corruption of
    the wire payloads (seeded from the traced step ``key``, rates are
    closure constants so traces stay static) plus NaN/Inf guards that
    absorb corrupted rows into the staleness fallbacks — a guarded
    combine pair falls back to ``h_cache`` exactly like a cond-comm
    masked pair, a guarded dispatch row to the codec base ``c_base`` (or
    a zero contribution without one).  ``fault_salt`` (the layer index)
    decorrelates injection across layers.  ``None`` keeps the graph
    byte-identical; guards-on with clean payloads keeps outputs
    bit-identical (the guard selects are all-true passthroughs).
    """
    faults = resilience.faults if resilience is not None else None
    guard = resilience.guards if resilience is not None else False
    fe = None
    if resilience is not None:
        fe = jnp.zeros((fault_lib.NUM_FAULT_EVENTS,), jnp.float32)
    T, d = x.shape
    E = cfg.num_experts
    probs, scores, idx = route(p, x, cfg, key=key)
    K = idx.shape[1]
    pl = placement if (placement is not None
                      and not placement.is_identity) else None
    S = E                       # wire/dispatch-buffer expert dimension
    if num_wire_experts is not None and ep_axis is not None:
        if num_wire_experts < E:
            raise ValueError(
                f"num_wire_experts={num_wire_experts} < num_experts={E}")
        if pl is not None and num_wire_experts != E:
            raise ValueError("a padded wire (expert paging) cannot compose "
                             "with an expert placement")
        S = num_wire_experts
    if capacity is None:
        capacity = default_capacity(T, cfg)
        if pl is not None:
            capacity = pl.scaled_capacity(capacity)

    # ---- placement: replicated pairs leave the wire entirely; the rest
    # scatter at the placement's wire positions so each device's buffer
    # chunk addresses the experts it (post-permutation) owns
    rep_mask = None
    wire_fresh = fresh_mask
    wire_idx = idx
    if pl is not None:
        if pl.replicated:
            rep_ids = jnp.asarray(pl.replicated)
            rep_mask = (idx[..., None] == rep_ids[None, None, :]).any(-1)
            wire_fresh = ~rep_mask if fresh_mask is None \
                else (fresh_mask & ~rep_mask)
        wire_idx = jnp.asarray(pl.inv_perm())[idx]
    plan = make_plan(wire_idx, E, capacity, fresh_mask=wire_fresh,
                     num_slots=S)
    # ---- wire codec, dispatch direction: the (E, C, d) buffer scattered
    # below holds rows of x_wire, so encoding per token before the scatter
    # is exactly encoding the buffer the all-to-all moves
    x_wire = x
    if codec is not None:
        base = dispatch_base if dispatch_base is not None \
            else jnp.zeros_like(x)
        x_wire = codec_lib.apply(codec, x, base, use_pallas=use_pallas)
    # ---- resilience, dispatch direction (DESIGN.md Sec. 17): corrupt
    # token rows of the wire payload, then guard the buffer boundary —
    # non-finite rows fall back to the codec base c_base (the previous
    # step's decoded payload, already shared by both endpoints) or, with
    # a lossless wire, to a zero row (the gated FFN maps zero rows to
    # zero, so the token contributes nothing this layer, exactly like a
    # capacity-dropped pair)
    if faults is not None and faults.corrupt_dispatch_rate > 0:
        cm = fault_lib.corruption_mask(key, faults.seed, fault_salt,
                                       fault_lib.FE_CORRUPT_DISPATCH,
                                       faults.corrupt_dispatch_rate, (T,))
        x_wire = fault_lib.corrupt_rows(x_wire, cm)
        fe = fe.at[fault_lib.FE_CORRUPT_DISPATCH].add(
            cm.sum().astype(jnp.float32))
    if guard:
        row_ok = jnp.isfinite(x_wire).all(-1)
        fe = fe.at[fault_lib.FE_GUARDED_DISPATCH].add(
            jnp.sum(~row_ok).astype(jnp.float32))
        fb = base if codec is not None else jnp.zeros_like(x_wire)
        x_wire = jnp.where(row_ok[:, None], x_wire, fb)
    buf = dispatch(x_wire, plan, S, capacity)                   # (S, C, d)

    # ---- replica-served pairs: dispatch the SAME wire payload (x_wire —
    # codec'd rows stay codec'd, keeping parity with the identity layout,
    # which quantizes every fresh pair) into a local (R, C_loc, d) buffer.
    # C_loc covers all T*K pairs: replicas hold the HOT experts, whose
    # identity-capacity headroom the scaled wire buffer gave away.
    loc_plan = loc_buf = loc_ffn = None
    if rep_mask is not None:
        R = len(pl.replicated)
        pos_of = [R] * E
        for j, e in enumerate(pl.replicated):
            pos_of[e] = j
        loc_idx = jnp.asarray(pos_of)[idx]
        loc_fresh = rep_mask if fresh_mask is None \
            else (fresh_mask & rep_mask)
        loc_cap = -(-(T * K) // 8) * 8
        loc_plan = make_plan(loc_idx, R, loc_cap, fresh_mask=loc_fresh)
        loc_buf = dispatch(x_wire, loc_plan, R, loc_cap)
        rep_p = {"experts_gate": p["experts_gate_rep"],
                 "experts_up": p["experts_up_rep"],
                 "experts_down": p["experts_down_rep"]}

        def loc_ffn():
            return expert_ffn(rep_p, loc_buf, act=cfg.act,
                              use_pallas=use_pallas)

    n_dev = 1
    loc_out = None
    if ep_axis is None:
        buf_out = expert_ffn(p, buf, act=cfg.act, use_pallas=use_pallas)
        if loc_ffn is not None:
            loc_out = loc_ffn()
    else:
        n = compat.axis_size(ep_axis)
        if S % n:
            raise ValueError(
                f"num_experts={E} must divide over the {n}-way "
                f"{ep_axis!r} mesh axis for expert parallelism — or enable "
                f"expert paging (DESIGN.md Sec. 15), whose pool pads the "
                f"wire to the next multiple so any expert count serves on "
                f"any mesh")
        n_dev = n
        e_loc = S // n
        local = {k: v for k, v in p.items()
                 if k.startswith("experts_") and not k.endswith("_rep")}
        if overlap and n > 1:
            # ---- ring engine (DESIGN.md Sec. 12): 2*(n-1) ppermutes whose
            # chunk transfers overlap the per-chunk expert FFN; same wire
            # volume as the all-to-alls, decomposed so XLA can hide it.
            # The replica FFN rides as the ring's prelude: issued behind
            # hop 1's wire transfer, so serving hot experts locally costs
            # no additional exposed time (Sec. 13).
            b = overlap_lib.ring_expert_exchange(
                buf.reshape(n, e_loc, capacity, d),
                lambda c: expert_ffn(local, c, act=cfg.act,
                                     use_pallas=use_pallas),
                ep_axis=ep_axis, n=n, wire_dtype=x.dtype,
                prelude_fn=loc_ffn, hop_schedule=hop_schedule)
            if loc_ffn is not None:
                b, loc_out = b
            buf_out = b.reshape(S, capacity, d)
        else:
            # ---- dispatch all-to-all (collective #1) ---------------------
            # NOTE: the CPU backend's float-normalization pass upcasts bf16
            # collectives to f32 in the lowered HLO; on TPU the wire dtype is
            # bf16 (repro.launch.hlo_cost applies the bf16-wire correction).
            b = buf.reshape(n, e_loc, capacity, d)
            with jax.named_scope("dispatch"):
                b = jax.lax.all_to_all(b, ep_axis, split_axis=0,
                                       concat_axis=0,
                                       tiled=True)      # (n, e_loc, C, d)
            # named so remat policies can keep the received buffer and avoid
            # re-running the dispatch all-to-all during the backward pass
            b = jax.ad_checkpoint.checkpoint_name(b, "ep_recv")
            b = jnp.moveaxis(b, 0, 1).reshape(e_loc, n * capacity, d)
            b = expert_ffn(local, b, act=cfg.act, use_pallas=use_pallas)
            # ---- combine all-to-all (collective #2) ----------------------
            b = jnp.moveaxis(b.reshape(e_loc, n, capacity, d), 1, 0)
            with jax.named_scope("combine"):
                b = jax.lax.all_to_all(b.astype(x.dtype), ep_axis,
                                       split_axis=0, concat_axis=0,
                                       tiled=True)
            buf_out = b.reshape(S, capacity, d)
            if loc_ffn is not None:
                loc_out = loc_ffn()

    if rep_mask is not None:
        # merge wire and replica outputs per (token, rank) pair, then apply
        # the conditional-communication cache exactly as ``combine`` would:
        # same select order, same dtypes — bit-identical to the identity
        # layout's path for every pair
        _, wire_vals, wire_keep = combine(buf_out, plan, scores, T)
        _, loc_vals, loc_keep = combine(loc_out, loc_plan, scores, T)
        pair_vals = jnp.where(rep_mask[..., None], loc_vals, wire_vals)
        pair_keep = jnp.where(rep_mask, loc_keep, wire_keep)
        if h_cache is not None and fresh_mask is not None:
            pair_vals = jnp.where(fresh_mask[..., None], pair_vals,
                                  h_cache.astype(pair_vals.dtype))
        y = jnp.einsum("tk,tkd->td", scores.astype(jnp.float32),
                       pair_vals.astype(jnp.float32))
    else:
        y, pair_vals, pair_keep = combine(buf_out, plan, scores, T,
                                          h_cache=h_cache,
                                          fresh_mask=fresh_mask)
    # fresh-kept pairs still hold the raw (pre-reconstruction) wire value
    # here — the telemetry block below measures residual energy against
    # the cache on exactly these values, before the codec overwrites them
    # (and before fault injection, so chaos runs keep clean residual
    # telemetry)
    pair_vals_fresh = pair_vals
    # ---- resilience, combine direction (DESIGN.md Sec. 17): corrupt the
    # expert outputs of transmitted pairs, as a wire fault would
    y_dirty = False
    if faults is not None and faults.corrupt_combine_rate > 0:
        hit = pair_keep if fresh_mask is None else (pair_keep & fresh_mask)
        cm = fault_lib.corruption_mask(key, faults.seed, fault_salt,
                                       fault_lib.FE_CORRUPT_COMBINE,
                                       faults.corrupt_combine_rate,
                                       pair_keep.shape) & hit
        pair_vals = fault_lib.corrupt_rows(pair_vals, cm)
        fe = fe.at[fault_lib.FE_CORRUPT_COMBINE].add(
            cm.sum().astype(jnp.float32))
        y_dirty = True
    recon = None
    if codec is not None and h_cache is not None:
        # ---- wire codec, combine direction: freshly transmitted pairs
        # arrive as residuals against the shared (token, rank) cache; the
        # reconstruction feeds the weighted sum and (via aux.pair_vals)
        # becomes the next cache entry, keeping both endpoints' bases in
        # lockstep.  Masked pairs already read h_cache; dropped pairs
        # stay zero (nothing arrived for them).
        wire_ok = pair_keep if fresh_mask is None \
            else (pair_keep & fresh_mask)
        recon = codec_lib.apply(codec, pair_vals.astype(jnp.float32),
                                h_cache.astype(jnp.float32),
                                use_pallas=use_pallas, guard=guard)
        pair_vals = jnp.where(wire_ok[..., None],
                              recon.astype(pair_vals.dtype), pair_vals)
        y = jnp.einsum("tk,tkd->td", scores.astype(jnp.float32),
                       pair_vals.astype(jnp.float32))
    # ---- resilience guard: a non-finite pair row falls back to its
    # h_cache entry — the exact value a cond-comm masked pair would have
    # used, so quality degrades like one extra light step for that pair —
    # and is cleared from pair_keep so it can never be written back into
    # the cache or counted as served.  Without a cache (sync schedule)
    # the pair's contribution drops to zero, like a capacity drop.  With
    # clean payloads every select is an all-true passthrough and the
    # recomputed y is the same einsum on the same values: bit-identical.
    if guard:
        pair_ok = jnp.isfinite(pair_vals).all(-1)
        fe = fe.at[fault_lib.FE_GUARDED_COMBINE].add(
            jnp.sum(~pair_ok).astype(jnp.float32))
        fb = h_cache.astype(pair_vals.dtype) if h_cache is not None \
            else jnp.zeros_like(pair_vals)
        pair_vals = jnp.where(pair_ok[..., None], pair_vals, fb)
        pair_keep = pair_keep & pair_ok
        y_dirty = True
    if y_dirty:
        y = jnp.einsum("tk,tkd->td", scores.astype(jnp.float32),
                       pair_vals.astype(jnp.float32))
    if cfg.num_shared_experts:
        y = y + shared_expert(p, x, act=cfg.act).astype(y.dtype)

    # ---- per-expert accounting (expert-ID space, whatever the wire
    # layout): ``counts`` is routed demand (post-mask, PRE-drop);
    # ``served_counts`` the pairs actually computed fresh this step —
    # wire-kept plus replica-served — the post-drop histogram the
    # placement optimizer consumes (Sec. 13)
    if pl is None:
        counts = plan.counts                    # wire space == expert space
    else:
        flat_e = idx.reshape(-1)
        if fresh_mask is not None:
            flat_e = jnp.where(fresh_mask.reshape(-1), flat_e, E)
        counts = jnp.bincount(jnp.clip(flat_e, 0, E), length=E + 1)[:E]
    served_counts = jnp.bincount(
        idx.reshape(-1), weights=pair_keep.reshape(-1).astype(jnp.float32),
        length=E)

    # capacity-drop rate over pairs that were actually dispatched: pairs a
    # conditional-communication mask routed to the virtual expert E are not
    # drops, they are deliberately-cached pairs (Sec. 4.3); replica-served
    # pairs count as dispatched-and-kept (their local buffer cannot drop)
    dispatched = counts.sum().astype(jnp.float32)
    kept = pair_keep.sum().astype(jnp.float32)
    dropped_frac = jnp.where(dispatched > 0,
                             1.0 - kept / jnp.maximum(dispatched, 1.0), 0.0)
    itemsize = jnp.dtype(x.dtype).itemsize
    per_row = (codec.wire_bytes_per_row(d, itemsize)
               if codec is not None else d * itemsize)
    # ---- in-graph staleness telemetry (DESIGN.md Sec. 16): fixed-shape,
    # plan-variant-invariant, and None (not zeros) when obs is off so the
    # traced graph is byte-identical to a build without the subsystem
    telemetry = None
    if obs is not None and obs.enabled:
        telemetry = obs_telemetry.layer_telemetry(
            x=x, x_wire=x_wire, dispatch_base=dispatch_base, codec=codec,
            pair_vals=pair_vals_fresh, recon=recon, pair_keep=pair_keep,
            fresh_mask=fresh_mask, h_cache=h_cache,
            dropped_frac=dropped_frac)
    # ring accounting: same total wire volume as the all-to-alls, split
    # across 2*(n-1) collective-permutes of one (e_loc, C, d) chunk each
    ring = bool(overlap and n_dev > 1)
    aux = MoEAux(
        lb_loss=load_balance_loss(
            probs, idx, E,
            ep_axis=reduce_axes if reduce_axes is not None else ep_axis),
        dropped_frac=dropped_frac,
        dispatch_bytes=jnp.asarray(S * capacity * per_row),
        pair_vals=pair_vals if (want_pair_vals or fresh_mask is not None) else None,
        scores=scores if (want_pair_vals or fresh_mask is not None) else None,
        pair_keep=pair_keep if (want_pair_vals or fresh_mask is not None) else None,
        raw_dispatch_bytes=jnp.asarray(S * capacity * d * itemsize),
        wire_payload=x_wire if codec is not None else None,
        hops=jnp.asarray(2 * (n_dev - 1) if ring else 0),
        hop_bytes=jnp.asarray((S // n_dev) * capacity * per_row
                              if ring else 0),
        counts=counts,
        served_counts=served_counts,
        telemetry=telemetry,
        fault_events=fe,
    )
    return y.astype(x.dtype), aux
