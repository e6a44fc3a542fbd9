"""Rectified Flow training and sampling for DiT-MoE (paper Sec. 5.1).

x_t = t * x1 + (1 - t) * x0 with x0 ~ N(0, I); the model predicts the
velocity v = x1 - x0.  Sampling = Euler integration from t=0 to t=1 —
the paper evaluates 10/20/50 steps with a few synchronized warmup steps.

The sampler drives the DICE staleness machinery through the StepPlan
engine (DESIGN.md Sec. 2): ``compile_step_plans`` buckets the run's steps
into a small set of plan variants (warmup-sync / refresh / light for
DICE), and the python loop calls ONE jitted step function whose static
argument is the hashable StepPlan — so the jit cache holds one executable
per *variant*, not per step index, while Conditional Communication's
light steps still get a genuinely smaller dispatch buffer.
"""
from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.common import compat
from repro.common import sharding as shard_lib
from repro.common.config import ModelConfig
from repro.core import paging as paging_lib
from repro.core import plan as plan_lib
from repro.core import staleness as stale_lib
from repro.core.patch_parallel import PatchParallelState
from repro.core.schedules import DiceConfig
from repro.models.dit_moe import dit_forward, dit_train_forward
from repro.obs.telemetry import ObsConfig
from repro.optim.adamw import adamw_update, clip_by_global_norm, cosine_schedule
from repro.resilience import faults as fault_lib


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def rf_loss(params, batch, cfg: ModelConfig, key, *, lb_weight: float = 0.01):
    x1, y = batch["latents"], batch["classes"]
    k_t, k_n, k_drop = jax.random.split(key, 3)
    B = x1.shape[0]
    t = jax.random.uniform(k_t, (B,))
    x0 = jax.random.normal(k_n, x1.shape)
    xt = t[:, None, None] * x1 + (1 - t)[:, None, None] * x0
    # class dropout for CFG training
    drop = jax.random.bernoulli(k_drop, 0.1, (B,))
    y_in = jnp.where(drop, cfg.num_classes, y)
    v, aux = dit_train_forward(params, xt, t, y_in, cfg)
    mse = jnp.mean(jnp.square(v - (x1 - x0)))
    return mse + lb_weight * aux["lb_loss"], {"mse": mse, "lb": aux["lb_loss"]}


@partial(jax.jit, static_argnames=("cfg",))
def rf_train_step(params, opt_state, batch, key, cfg: ModelConfig):
    (loss, metrics), grads = jax.value_and_grad(rf_loss, has_aux=True)(
        params, batch, cfg, key)
    grads, gnorm = clip_by_global_norm(grads, 1.0)
    lr = cosine_schedule(opt_state.step, base_lr=1e-3, warmup=20, total=2000)
    params, opt_state = adamw_update(grads, opt_state, params, lr=lr)
    metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
    return params, opt_state, metrics


# ---------------------------------------------------------------------------
# sampling under a parallelism schedule
# ---------------------------------------------------------------------------
def _euler_step(params, cfg: ModelConfig, dcfg: DiceConfig,
                x, classes, states, states_u, patch_states, patch_states_u,
                t, key, *, plan, dt, guidance, patch_parallel_ndev=0,
                ep_axis=None, slot_fresh=None, consume_mask=None,
                patch_axis=None, patch_fresh=None, patch_compose=False,
                reduce_axes=None, hop_schedule=None, expert_pool=None,
                obs=None):
    """One CFG-guided Euler step — the schedule-agnostic core both the
    single-device and the mesh-native (shard_map-ped) step functions trace.
    Inside shard_map every operand is the per-device shard, ``ep_axis``
    names the live mesh axis the MoE all-to-alls run over and
    ``patch_axis`` the axis the image-token dim shards over (DESIGN.md
    §14); ``patch_compose`` selects the replicated patch simulation
    COMPOSED with the staleness MoE path — the single-device reference of
    the sharded patch axis."""
    null = jnp.full_like(classes, cfg.num_classes)
    v_c, ns, nps, aux = dit_forward(
        params, x, t, classes, cfg, dcfg, states, plan=plan,
        patch_states=patch_states or None,
        patch_parallel_ndev=patch_parallel_ndev, ep_axis=ep_axis, key=key,
        slot_fresh=slot_fresh, consume_mask=consume_mask,
        patch_axis=patch_axis, patch_fresh=patch_fresh,
        patch_compose=patch_compose, reduce_axes=reduce_axes,
        hop_schedule=hop_schedule, expert_pool=expert_pool, obs=obs)
    if guidance != 1.0:
        # the unconditional pass's aux is discarded, so it never computes
        # telemetry — obs instruments the conditional pass only
        v_u, nsu, npsu, _ = dit_forward(
            params, x, t, null, cfg, dcfg, states_u, plan=plan,
            patch_states=patch_states_u or None,
            patch_parallel_ndev=patch_parallel_ndev, ep_axis=ep_axis,
            key=key, slot_fresh=slot_fresh, consume_mask=consume_mask,
            patch_axis=patch_axis, patch_fresh=patch_fresh,
            patch_compose=patch_compose, reduce_axes=reduce_axes,
            hop_schedule=hop_schedule, expert_pool=expert_pool)
        v = v_u + guidance * (v_c - v_u)
    else:
        v, nsu, npsu = v_c, states_u, patch_states_u
    return x + dt * v, ns, nsu, nps, npsu, aux


def make_rf_step(params, cfg: ModelConfig, dcfg: DiceConfig, *,
                 dt: float, guidance: float = 1.5,
                 patch_parallel_ndev: int = 0,
                 ep_axis: Optional[str] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 patch_compose: bool = False,
                 hop_schedule=None,
                 expert_pool=None,
                 obs: Optional[ObsConfig] = None):
    """The reusable single-Euler-step callable behind both :func:`rf_sample`
    and the continuous-batching serving engine (DESIGN.md Sec. 9).

    The returned jitted function's cache is keyed by the (hashable) static
    ``plan`` plus the static ``slotted`` flag; ``t`` and ``classes`` are
    traced, so neither the step index nor the admitted request mix enters
    the trace.  Signature::

        rf_step(x, classes, states, states_u, patch_states, patch_states_u,
                t, key, *, plan, slotted=False,
                slot_fresh=None, consume_mask=None)

    ``slotted=True`` is the continuous-batching mixed tick: ``slot_fresh``
    (B*T tokens,) marks warmup-replaying slots (their layers consume the
    fresh combine — sync semantics) and ``consume_mask`` (B*T, K) carries
    each slot's conditional-communication mask.  Both are traced arrays,
    so every warmup/steady mixture shares one compiled entry per
    (plan, slotted) pair.

    With ``mesh`` each plan variant lowers to ONE shard_map-ped step over
    the hierarchical dp x ep x patch axes (any subset may be present —
    ``launch.mesh.make_mesh``): the batch shards over dp x ep, the image-
    token dim over patch, staleness state and per-slot selectors follow
    the batch layout, expert params shard under
    ``common.sharding.ep_param_specs`` (replicated per dp/patch group),
    and the dispatch/combine all-to-alls of every MoE layer run over the
    ep axis within each (dp, patch) slice (DESIGN.md §10/§14).  The
    jit-cache contract is unchanged — one entry per (plan, slotted) pair,
    mesh-independent.  ``hop_schedule`` orders the ring engine's hops
    (``repro.core.overlap.ring_hop_schedule``); ``patch_compose`` runs the
    replicated patch simulation composed with the staleness MoE path (the
    mesh-less numerics reference of the sharded patch axis).

    ``obs`` is a CLOSURE constant of the step function — an enabled
    :class:`ObsConfig` adds the ``"telemetry"`` aux output (DESIGN.md
    Sec. 16) without becoming a jit argument, so the cache contract (one
    entry per (plan, slotted) pair) is unchanged either way.

    ``params`` are never a closure constant: the jitted step takes them
    as its first argument and the returned :class:`BoundStep` passes
    them in on every call (see there).

    ``states`` and ``states_u`` are donated: the step writes its new
    staleness state over them, so a caller keeps one generation of the
    buffers, also with a step in flight, and must not read them after
    the call (it holds what the step returns).  The latents (a few
    hundred KB) are not donated.
    """
    if mesh is not None:
        return _make_mesh_rf_step(
            params, cfg, dcfg, dt=dt, guidance=guidance,
            patch_parallel_ndev=patch_parallel_ndev, mesh=mesh,
            ep_axis=ep_axis or "ep", hop_schedule=hop_schedule,
            expert_pool=expert_pool, obs=obs)

    @partial(jax.jit, static_argnames=("plan", "slotted"),
             donate_argnames=("states", "states_u"))
    def rf_step(params, x, classes, states, states_u, patch_states,
                patch_states_u, t, key, *, plan, slotted=False,
                slot_fresh=None, consume_mask=None, patch_fresh=None):
        del patch_fresh                # mesh-only selector; inert here
        return _euler_step(
            params, cfg, dcfg, x, classes, states, states_u,
            patch_states, patch_states_u, t, key, plan=plan, dt=dt,
            guidance=guidance, patch_parallel_ndev=patch_parallel_ndev,
            ep_axis=ep_axis, patch_compose=patch_compose,
            slot_fresh=slot_fresh if slotted else None,
            consume_mask=consume_mask if slotted else None,
            obs=obs)

    return BoundStep(rf_step, params)


class BoundStep:
    """A jitted step whose first argument is the weight tree, with that
    tree bound: ``step(x, ...)`` calls ``jitted(params, x, ...)``.

    jax embeds the arrays a jitted function closes over as literal
    constants of its compiled program — at DiT-MoE-XL size ~10 GB per
    plan variant, compiled and held on the device again beside the
    weights themselves.  Passed as an argument, the weights stay one
    set of device buffers that every variant reads.  ``lower`` and
    ``_cache_size`` forward to the jitted function.
    """

    def __init__(self, jitted, params):
        self.jitted = jitted
        self.params = params

    def __call__(self, *args, **kwargs):
        return self.jitted(self.params, *args, **kwargs)

    def lower(self, *args, **kwargs):
        return self.jitted.lower(self.params, *args, **kwargs)

    def _cache_size(self) -> int:
        return self.jitted._cache_size()


def _make_mesh_rf_step(params, cfg: ModelConfig, dcfg: DiceConfig, *,
                       dt: float, guidance: float, patch_parallel_ndev: int,
                       mesh: jax.sharding.Mesh, ep_axis: str,
                       hop_schedule=None, expert_pool=None, obs=None):
    """Mesh-native lowering of :func:`make_rf_step` (DESIGN.md §10/§14).

    One ``shard_map`` per plan variant over the hierarchical
    dp x ep x patch mesh (any axis subset): the batch shards over
    dp x ep, the image-token dim over patch, staleness state and per-slot
    selectors follow the batch layout, experts shard under
    ``ep_param_specs`` (implicitly replicated per dp/patch group), and
    aux is reduced to replicated values inside the mapped body
    (``dispatch_bytes`` stays the per-device wire payload).  Params are
    placed on the mesh once, here.  On a flat ``("ep",)`` mesh every
    spec below degenerates to the historical single-axis form.
    """
    if patch_parallel_ndev:
        raise ValueError("the replicated patch-parallel simulation does "
                         "not compose with the mesh-native path; build "
                         "the mesh with a 'patch' axis instead")
    patch_axis = "patch" if "patch" in mesh.axis_names else None
    bax = shard_lib.batch_shard_axes(mesh)
    dax = shard_lib.data_shard_axes(mesh)
    if not dax:
        raise ValueError(f"mesh axes {mesh.axis_names} carry none of the "
                         f"hierarchical dp/ep/patch axes")
    live_ep = ep_axis if ep_axis in mesh.axis_names else None
    n_ep = mesh.shape[ep_axis] if live_ep else 1
    paged = paging_lib.paging_of(dcfg) is not None and n_ep > 1
    if live_ep and not paged and cfg.num_experts % n_ep:
        raise ValueError(f"num_experts={cfg.num_experts} must divide the "
                         f"{n_ep}-way {ep_axis!r} axis — or enable expert "
                         f"paging (DiceConfig.paging), whose pool pads the "
                         f"wire so any expert count serves on any mesh")
    n_patch = mesh.shape[patch_axis] if patch_axis else 1
    if patch_axis and cfg.patch_tokens % n_patch:
        raise ValueError(f"patch_tokens={cfg.patch_tokens} must divide "
                         f"over the {n_patch}-way patch axis")
    n_batch = 1
    for a in bax:
        n_batch *= mesh.shape[a]
    # flat-ep meshes keep the legacy single-axis reductions (bit-safe);
    # hierarchical meshes reduce token means over every data axis
    reduce_axes = None if dax == (ep_axis,) else dax
    hop_schedule = plan_lib.normalize_hop_schedule(hop_schedule, n_ep)
    b_spec = shard_lib.hier_batch_spec(mesh)
    x_spec = shard_lib.hier_token_spec(mesh) if patch_axis else b_spec
    b_dim = b_spec[0] if len(b_spec) else None
    placements = plan_lib.placements_of(dcfg)
    if placements is not None and live_ep:
        # affinity-aware layout (DESIGN.md Sec. 13): permute each layer's
        # expert stacks to the placement order and append the hot-expert
        # replica leaves BEFORE sharding — the ep shards then hold the
        # placed experts and every device carries the replica stack
        from repro.core import placement as placement_lib
        params = placement_lib.placed_params(params, placements)
    if paged:
        # the pool owns the routed-expert stacks (host RAM); the device
        # tree keeps only the always-resident remainder — router, shared
        # experts, attention, embeddings (DESIGN.md Sec. 15)
        params, expert_pool = paging_lib.split_params(params, expert_pool,
                                                      n_ep)
    pool = expert_pool if paged else None
    params = shard_lib.ep_shard_params(params, mesh, ep_axis=live_ep)
    pspecs = shard_lib.ep_param_specs(params, ep_axis=live_ep)

    @partial(jax.jit, static_argnames=("plan", "slotted"),
             donate_argnames=("states", "states_u"))
    def rf_step(params, x, classes, states, states_u, patch_states,
                patch_states_u, t, key, *, plan, slotted=False,
                slot_fresh=None, consume_mask=None, patch_fresh=None):
        if x.shape[0] % max(n_batch, 1):
            raise ValueError(f"batch {x.shape[0]} must divide over the "
                             f"{n_batch}-way {bax} batch axes")
        if patch_axis and patch_fresh is None:
            raise ValueError("a patch-axis mesh step needs the traced "
                             "patch_fresh selector (warmup/step-0 rows)")
        st_spec = stale_lib.state_specs(states, ep_axis=b_dim,
                                        patch_axis=patch_axis)
        stu_spec = stale_lib.state_specs(states_u, ep_axis=b_dim,
                                         patch_axis=patch_axis)
        # patch KV buffers: batch-sharded, full sequence per device (the
        # DistriFusion memory cost), identical across the patch group
        pst_spec = jax.tree.map(lambda _: P(b_dim), patch_states)
        pstu_spec = jax.tree.map(lambda _: P(b_dim), patch_states_u)
        aux_spec = {"lb_loss": P(), "dispatch_bytes": P(),
                    "raw_dispatch_bytes": P(), "dropped_frac": P(),
                    "hops": P(), "hop_bytes": P(),
                    "buffer_bytes": P(), "expert_counts": P()}
        if obs is not None and obs.enabled:
            # telemetry is pmean'd inside the mapped body like the other
            # aux reductions -> replicated (DESIGN.md Sec. 16)
            aux_spec["telemetry"] = P()
        if fault_lib.resilience_of(dcfg) is not None:
            # in-graph fault accounting, psum'd inside the mapped body ->
            # replicated global counts (DESIGN.md Sec. 17)
            aux_spec["fault_events"] = P()
        ops = (params, x, classes, states, states_u, patch_states,
               patch_states_u, t, key, patch_fresh)
        in_specs = (pspecs, x_spec, b_spec, st_spec, stu_spec, pst_spec,
                    pstu_spec, b_spec, P(), b_spec)
        if slotted:
            if patch_axis:
                # per-token selectors must follow the factored (B, T)
                # layout to shard over patch; re-flattened inside
                slot_fresh = slot_fresh.reshape(x.shape[0], -1)
                consume_mask = consume_mask.reshape(
                    x.shape[0], -1, consume_mask.shape[-1])
                sl_spec = shard_lib.hier_token_spec(mesh)
            else:
                sl_spec = b_spec
            ops += (slot_fresh, consume_mask)
            in_specs += (sl_spec, sl_spec)

        def inner(p_l, x_l, cls_l, st_l, stu_l, pst_l, pstu_l, t_l, key_l,
                  pf_l, *slot_ops):
            sf, cm = slot_ops if slotted else (None, None)
            if slotted and patch_axis:
                sf = sf.reshape(-1)
                cm = cm.reshape(-1, cm.shape[-1])
            x_new, ns, nsu, nps, npsu, aux = _euler_step(
                p_l, cfg, dcfg, x_l, cls_l, st_l, stu_l, pst_l, pstu_l,
                t_l, key_l, plan=plan, dt=dt, guidance=guidance,
                ep_axis=live_ep, slot_fresh=sf, consume_mask=cm,
                patch_axis=patch_axis, patch_fresh=pf_l,
                reduce_axes=reduce_axes, hop_schedule=hop_schedule,
                expert_pool=pool, obs=obs)
            aux = dict(aux, buffer_bytes=jnp.asarray(aux["buffer_bytes"]))
            return x_new, ns, nsu, nps, npsu, aux

        # The patch KV buffers leave ``sharded_patch_attention`` as the
        # tiled ``all_gather`` over the patch axis, so they are identical
        # on every device of a patch group by construction.  jax types an
        # all_gather result as varying over the gathered axis, so the
        # replication check cannot infer the ``P(b_dim)`` out_spec; it
        # is turned off only on meshes that carry a patch axis.
        x_new, ns, nsu, nps, npsu, aux = compat.shard_map(
            inner, mesh=mesh, in_specs=in_specs,
            out_specs=(x_spec, st_spec, stu_spec, pst_spec, pstu_spec,
                       aux_spec),
            check_vma=patch_axis is None)(*ops)
        return x_new, ns, nsu, nps, npsu, aux

    return BoundStep(rf_step, params)


def make_sample_step(params, cfg: ModelConfig, dcfg: DiceConfig, classes, *,
                     dt: float, guidance: float = 1.5,
                     patch_parallel_ndev: int = 0,
                     ep_axis: Optional[str] = None,
                     mesh: Optional[jax.sharding.Mesh] = None,
                     patch_compose: bool = False,
                     hop_schedule=None,
                     expert_pool=None,
                     obs: Optional[ObsConfig] = None):
    """One jitted Euler step with ``classes`` bound — the whole-loop
    sampler's view of :func:`make_rf_step`.

    The underlying jit cache is keyed by the (hashable) plan: equal plans —
    however many step indices map to them — share a single compiled
    executable.  ``t`` is a traced argument, so the step index never
    enters the trace.
    """
    classes = jnp.asarray(classes, jnp.int32)
    if mesh is not None:
        classes = shard_lib.hier_place_batch(classes, mesh)
    rf_step = make_rf_step(params, cfg, dcfg, dt=dt, guidance=guidance,
                           patch_parallel_ndev=patch_parallel_ndev,
                           ep_axis=ep_axis, mesh=mesh,
                           patch_compose=patch_compose,
                           hop_schedule=hop_schedule,
                           expert_pool=expert_pool,
                           obs=obs)

    def one_step(x, states, states_u, patch_states, patch_states_u, t, key,
                 *, plan, patch_fresh=None):
        return rf_step(x, classes, states, states_u, patch_states,
                       patch_states_u, t, key, plan=plan,
                       patch_fresh=patch_fresh)

    one_step._cache_size = rf_step._cache_size
    return one_step


def rf_sample(params, cfg: ModelConfig, dcfg: DiceConfig, *,
              num_steps: int, classes, key,
              guidance: float = 1.5,
              patch_parallel_ndev: int = 0,
              ep_axis: Optional[str] = None,
              mesh: Optional[jax.sharding.Mesh] = None,
              patch_compose: bool = False,
              hop_schedule=None,
              expert_pool=None,
              collect_stats: bool = True,
              obs: Optional[ObsConfig] = None,
              tracer=None):
    """Generate latents (B, T, C) for ``classes`` under a schedule.

    Returns (samples, stats) where stats records per-step all-to-all
    payload bytes and persistent buffer bytes — the quantities behind the
    paper's speedup/memory claims — plus the compile accounting of the
    StepPlan engine: ``num_plan_variants`` (distinct static step shapes)
    and ``jit_cache_size`` (actual compiled entries of the step function
    — equal to the variant count thanks to plan-aware state init, and
    O(1) in ``num_steps`` vs. the seed's one-compile-per-step).

    ``mesh`` runs the whole loop mesh-native (DESIGN.md §10): batch and
    staleness state shard over the mesh's ``"ep"`` axis (``ep_axis``
    overrides the name), experts shard under ``ep_param_specs``, and the
    per-step ``dispatch_bytes`` stat becomes the PER-DEVICE all-to-all
    payload — on Conditional-Communication light steps a genuinely
    smaller number, straight off the sharded dispatch buffer.

    ``obs`` / ``tracer`` (DESIGN.md Sec. 16): with an enabled
    :class:`ObsConfig` the loop additionally records per-step MEASURED
    walltime (``stats["step_wall_s"]``, each step ``block_until_ready``-
    timed — the first call of a variant includes its compile, reported
    separately in ``stats["compile_s"]`` keyed by variant index) and the
    in-graph telemetry block (``stats["telemetry"]``, one (L, NUM_FIELDS)
    array per step).  The samples themselves are bit-identical to an
    obs-off run — telemetry only ADDS aux outputs.  ``tracer`` (a
    :class:`repro.obs.trace.StepTracer`) receives plan-build / compile /
    step-execute spans, and paging pools emit their ``io_callback``
    fetches onto it.
    """
    obs_on = obs is not None and obs.enabled
    B = classes.shape[0]
    ep = ep_axis or ("ep" if mesh is not None else None)
    n_ep = (mesh.shape[ep] if mesh is not None and ep in mesh.axis_names
            else 1)
    patch_axis = ("patch" if mesh is not None
                  and "patch" in mesh.axis_names else None)
    # the config as this mesh runs it: a mesh-less (or 1-device-axis) run
    # drops ring overlap, placements and paging, and so plans — and
    # samples — bit-identically to the plain config (plan.resolve)
    dcfg = plan_lib.resolve(dcfg, n_ep)
    if paging_lib.paging_of(dcfg) is not None:
        if expert_pool is None:
            expert_pool = paging_lib.pool_from_params(params, n_dev=n_ep)
        dcfg = paging_lib.resolve_budget(dcfg, expert_pool)
        expert_pool.reset_stats()
    x = jax.random.normal(key, (B, cfg.patch_tokens, cfg.in_channels))
    if mesh is not None:
        x = jax.device_put(x, jax.sharding.NamedSharding(
            mesh, shard_lib.hier_token_spec(mesh) if patch_axis
            else shard_lib.hier_batch_spec(mesh)))
    dt = 1.0 / num_steps
    if tracer is not None:
        with tracer.span("plan_build", cat="plan",
                         args={"schedule": plan_lib.schedule_name(
                                   dcfg.schedule),
                               "num_steps": num_steps}):
            splan = plan_lib.compile_step_plans(
                dcfg, cfg.num_layers, num_steps,
                experts_per_token=cfg.experts_per_token)
    else:
        splan = plan_lib.compile_step_plans(
            dcfg, cfg.num_layers, num_steps,
            experts_per_token=cfg.experts_per_token)
    if tracer is not None and expert_pool is not None:
        # paging io_callback fetches run on runtime threads; the tracer is
        # lock-protected, so they land on their own track
        expert_pool.tracer = tracer
    if expert_pool is not None and paging_lib.paging_of(dcfg) is not None:
        # every planned residency window must fit the HBM budget — fail
        # here, before compile, not by overflowing device memory mid-run
        expert_pool.validate_plan(splan)
    # plan-aware init: allocate exactly the buffers the run will write, so
    # the state pytree signature is constant and the jit cache holds
    # exactly one entry per plan variant (sharded over ep under a mesh).
    # On a patch-axis mesh the buffers factor to (B, T, ...) — the only
    # layout whose shards line up with the token split (DESIGN.md §14).
    b_dim = None
    if mesh is not None:
        bsp = shard_lib.hier_batch_spec(mesh)
        b_dim = bsp[0] if len(bsp) else None
    planned_init = partial(stale_lib.init_planned_states, splan,
                           num_tokens=B * cfg.patch_tokens,
                           d_model=cfg.d_model, k=cfg.experts_per_token,
                           dtype=x.dtype, mesh=mesh,
                           ep_axis=(b_dim if mesh is not None else "ep"),
                           patch_axis=patch_axis,
                           token_shape=((B, cfg.patch_tokens)
                                        if patch_axis else None))
    states = planned_init()
    states_u = planned_init()
    patch_states: Dict = {}
    patch_states_u: Dict = {}
    if patch_axis:
        # constant-structure patch KV buffers: full-sequence per device
        # (DistriFusion's memory cost), batch-sharded, zero-filled — never
        # read before the traced patch_fresh selector stops masking them
        def _patch_init():
            z = jnp.zeros((B, cfg.patch_tokens, cfg.num_kv_heads,
                           cfg.head_dim), x.dtype)
            st = {i: PatchParallelState(
                k_prev=shard_lib.hier_place_batch(z, mesh),
                v_prev=shard_lib.hier_place_batch(z, mesh))
                for i in range(cfg.num_layers)}
            return st
        patch_states = _patch_init()
        patch_states_u = _patch_init()
    stats = {"dispatch_bytes": [], "raw_bytes": [], "buffer_bytes": [],
             "hops": [], "hop_bytes": []}
    if obs_on:
        stats["telemetry"] = []       # per step: (L, NUM_FIELDS) arrays
        stats["step_wall_s"] = []     # per step: measured, block-timed
        stats["compile_s"] = {}       # variant index -> first-call seconds

    one_step = make_sample_step(params, cfg, dcfg, classes, dt=dt,
                                guidance=guidance,
                                patch_parallel_ndev=patch_parallel_ndev,
                                ep_axis=ep, mesh=mesh,
                                patch_compose=patch_compose,
                                hop_schedule=hop_schedule,
                                expert_pool=expert_pool,
                                obs=obs)

    for s in range(num_steps):
        key, k = jax.random.split(key)
        t = jnp.full((B,), s * dt)
        pf = None
        if patch_axis:
            # fresh remote KV exactly where the replicated baseline is
            # fresh: warmup steps, and step 0 (its stale buffer is unborn)
            pf = jnp.full((B,), bool(s == 0 or splan.steps[s].is_warmup))
            pf = shard_lib.hier_place_batch(pf, mesh)
        v_idx = splan.variant_of_step[s]
        t0 = time.perf_counter() if obs_on else 0.0
        cache_before = one_step._cache_size() if obs_on else 0
        # the step's span (a profiler annotation too) names its plan
        # variant; "compiled" is filled in once the call has returned
        step_args = {"step": s, "variant": v_idx}
        with (tracer.span("rf_step", cat="step", args=step_args)
              if obs_on and tracer is not None else contextlib.nullcontext()):
            step_out = one_step(
                x, states, states_u, patch_states, patch_states_u, t, k,
                plan=splan.steps[s], patch_fresh=pf)
            x, states, states_u, patch_states, patch_states_u, aux = step_out
            if obs_on:
                t_dispatched = time.perf_counter()
                compiled = one_step._cache_size() > cache_before
                step_args["compiled"] = bool(compiled)
                if compiled and v_idx not in stats["compile_s"]:
                    # jit compiles synchronously inside the call, so the
                    # call-to-return time of a cache-growing step IS the
                    # trace+compile cost of its variant
                    stats["compile_s"][v_idx] = t_dispatched - t0
                    if tracer is not None:
                        tracer.complete(
                            f"compile_variant_{v_idx}",
                            tracer.now() - (t_dispatched - t0) * 1e6,
                            cat="compile", args={"variant": v_idx, "step": s})
                jax.block_until_ready(x)
        if obs_on:
            stats["step_wall_s"].append(time.perf_counter() - t0)
            stats["telemetry"].append(jax.device_get(aux["telemetry"]))
        if collect_stats:
            stats["dispatch_bytes"].append(float(aux["dispatch_bytes"]))
            stats["raw_bytes"].append(float(aux["raw_dispatch_bytes"]))
            stats["buffer_bytes"].append(float(aux["buffer_bytes"]))
            stats["hops"].append(int(aux["hops"]))
            stats["hop_bytes"].append(float(aux["hop_bytes"]))
    stats["num_plan_variants"] = splan.num_variants
    stats["jit_cache_size"] = int(one_step._cache_size())
    pag = paging_lib.paging_of(dcfg)
    if pag is not None and expert_pool is not None:
        # block until every enqueued step has executed so the ledger has
        # seen the full fetch sequence before we read it
        jax.block_until_ready(x)
        stats["paged_transfers"] = expert_pool.transfers
        stats["paged_bytes_in"] = expert_pool.bytes_transferred
        stats["peak_resident_expert_bytes"] = expert_pool.peak_resident_bytes
        stats["expert_hbm_budget"] = pag.budget_bytes
    return x, stats
