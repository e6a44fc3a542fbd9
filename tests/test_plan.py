"""StepPlan engine tests (DESIGN.md Sec. 2).

Three claims:
  1. the plan compiler buckets steps into exactly the number of distinct
     static shapes (DICE stride=2, warmup=2 -> 3 variants);
  2. registry-planned execution is numerically identical to the
     pre-refactor ``moe_step`` if/elif chain (inlined below as the
     reference) for all five schedules;
  3. a 20-step ``rf_sample`` compiles the step function once per variant
     (<= 4; at seed it was 20) with bit-identical samples to a
     one-jit-per-step execution of the same plans.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import ModelConfig
from repro.configs.dit_moe_xl import tiny
from repro.core import conditional, plan as plan_lib
from repro.core.moe import MoEAux, default_capacity, moe_forward, moe_init
from repro.core.schedules import DiceConfig, Schedule
from repro.core.selective import sync_layer_mask
from repro.core.staleness import (MoELayerState, apply_layer_action,
                                  init_planned_states, moe_step)
from repro.models.dit_moe import init_dit
from repro.sampling.rectified_flow import make_sample_step, rf_sample

CFG = ModelConfig(name="t", family="moe", num_layers=4, d_model=32, d_ff=64,
                  vocab_size=64, num_heads=4, num_kv_heads=4, num_experts=4,
                  experts_per_token=2, moe_d_ff=48, capacity_factor=4.0)

FIVE = {
    "sync": DiceConfig.sync_ep(),
    "displaced": DiceConfig.displaced(),
    "interweaved": DiceConfig.interweaved(),
    "dice": DiceConfig.dice(),
    "staggered_batch": DiceConfig.staggered_batch(),
}


# ---------------------------------------------------------------------------
# 1. plan compiler: variant bucketing
# ---------------------------------------------------------------------------
def _compile(dcfg, num_steps=20, L=4):
    return plan_lib.compile_step_plans(dcfg, L, num_steps,
                                       experts_per_token=CFG.experts_per_token)


def test_dice_has_three_variants():
    """warmup-sync, refresh, light."""
    sp = _compile(DiceConfig.dice())
    assert sp.num_variants == 3
    assert sp.num_steps == 20
    # warmup variant covers steps 0-1; refresh the even steps; light the odd
    assert sp.steps_of_variant(sp.variant_of_step[0]) == [0, 1]
    assert sp.steps_of_variant(sp.variant_of_step[2]) == list(range(2, 20, 2))
    assert sp.steps_of_variant(sp.variant_of_step[3]) == list(range(3, 20, 2))


def test_variant_count_is_number_of_distinct_shapes():
    expected = {"sync": 1, "displaced": 2, "interweaved": 2,
                "dice": 3, "staggered_batch": 2}
    for name, dcfg in FIVE.items():
        sp = _compile(dcfg)
        assert sp.num_variants == expected[name], name
        assert sp.num_variants == len(set(sp.steps)), name
        # bucketing is consistent
        assert all(sp.steps[s] == sp.variants[v]
                   for s, v in enumerate(sp.variant_of_step)), name


def test_dice_stride4_still_three_variants():
    sp = _compile(DiceConfig.dice(cond_stride=4))
    assert sp.num_variants == 3      # warmup, refresh (s%4==0), light


def test_dice_without_cond_comm_two_variants():
    dcfg = DiceConfig(schedule=Schedule.DICE, cond_comm=False)
    assert _compile(dcfg).num_variants == 2


def test_light_step_shrinks_effective_k():
    sp = _compile(DiceConfig.dice())
    light = sp.steps[3]
    refresh = sp.steps[2]
    shallow = 0                      # layer 0 is async under sync_policy=deep
    assert refresh.actions[shallow].mask_policy is None
    assert refresh.actions[shallow].effective_k == CFG.experts_per_token
    assert light.actions[shallow].mask_policy == "low"
    assert light.actions[shallow].effective_k == 1
    # deep layers are protected: synchronous in both variants
    assert refresh.actions[3].mode == "sync"
    assert light.actions[3].mode == "sync"


def test_plan_derived_properties_match_paper_table():
    """step_staleness / num_buffers are derived from the plan now; the
    values must still reproduce the paper's table (and enum properties)."""
    expect = {"sync": (0, 0), "displaced": (2, 2), "interweaved": (1, 1),
              "dice": (1, 1), "staggered_batch": (1, 2)}
    for name, (stale, bufs) in expect.items():
        plan = plan_lib.steady_state_plan(name)
        assert plan.step_staleness == stale, name
        assert plan.num_buffers == bufs, name
        member = Schedule(name)
        assert member.step_staleness == stale
        assert member.num_buffers == bufs


# ---------------------------------------------------------------------------
# registry pluggability
# ---------------------------------------------------------------------------
def test_register_schedule_plugs_into_everything():
    name = "test_always_interweaved"

    @plan_lib.register_schedule(name)
    def _plan(dcfg, L, s, k):
        return plan_lib.StepPlan(
            schedule=name, is_warmup=False,
            actions=(plan_lib.LayerAction(mode="interweaved"),) * L)

    try:
        assert name in plan_lib.registered_schedules()
        dcfg = DiceConfig(schedule=name, warmup_steps=0)
        sp = plan_lib.compile_step_plans(dcfg, 4, 10, experts_per_token=2)
        assert sp.num_variants == 1
        assert plan_lib.steady_state_plan(name).step_staleness == 1
        # moe_step resolves string schedules through the registry
        p = moe_init(jax.random.PRNGKey(0), CFG)
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
        state = MoELayerState(y_buf=jnp.zeros((16, 32)))
        y, state, _ = moe_step(p, x, CFG, dcfg, state, moe_layer_idx=0,
                               num_moe_layers=4, step_idx=0)
        np.testing.assert_array_equal(np.asarray(y), np.zeros((16, 32)))
        y, _, _ = moe_step(p, x, CFG, dcfg, state, moe_layer_idx=0,
                           num_moe_layers=4, step_idx=1)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(moe_forward(p, x, CFG)[0]),
                                   rtol=1e-5)
    finally:
        plan_lib._REGISTRY.pop(name, None)


def test_unknown_schedule_raises():
    with pytest.raises(KeyError, match="no planner registered"):
        plan_lib.plan_for_step(DiceConfig(schedule="nope"), 4, 0,
                               experts_per_token=2)


# ---------------------------------------------------------------------------
# 2. numerical identity with the pre-refactor moe_step (inlined reference)
# ---------------------------------------------------------------------------
_NUM_BUFFERS = {"sync": 0, "displaced": 2, "interweaved": 1, "dice": 1,
                "staggered_batch": 2}


def _legacy_moe_step(p, x, cfg, dcfg, state, *, moe_layer_idx,
                     num_moe_layers, step_idx, key=None):
    """The seed's if/elif chain, verbatim (modulo the enum lookup dicts)."""
    sched = dcfg.schedule
    warmup = step_idx < dcfg.warmup_steps
    sync_mask = sync_layer_mask(dcfg.sync_policy, num_moe_layers,
                                fraction=dcfg.sync_fraction)
    layer_sync = bool(sync_mask[moe_layer_idx]) and sched == Schedule.DICE
    run_sync = (sched == Schedule.SYNC) or warmup or layer_sync

    mask = None
    capacity = None
    if (sched == Schedule.DICE and dcfg.cond_comm and not run_sync):
        k = cfg.experts_per_token
        mask = conditional.fresh_mask(step_idx, x.shape[0], k,
                                      stride=dcfg.cond_stride,
                                      policy=dcfg.cond_policy, key=key)
        k_eff = conditional.effective_k(step_idx, k, stride=dcfg.cond_stride,
                                        policy=dcfg.cond_policy)
        capacity = default_capacity(x.shape[0], cfg, k=k_eff)

    want_cache = sched == Schedule.DICE and dcfg.cond_comm

    def run(inp, m=None, cache=None):
        return moe_forward(p, inp, cfg, capacity=capacity, fresh_mask=m,
                           h_cache=cache, key=key, want_pair_vals=want_cache)

    if run_sync:
        y, aux = run(x)
        new = MoELayerState(
            y_buf=y if _NUM_BUFFERS[sched.value] >= 1 else None,
            x_prev=x if sched == Schedule.DISPLACED else None,
            h_cache=aux.pair_vals if want_cache else None)
        return y, new, aux

    if sched == Schedule.DISPLACED:
        y_new, aux = run(state.x_prev)
        out = state.y_buf
        new = MoELayerState(y_buf=y_new, x_prev=x, h_cache=None)
        return out, new, aux

    if sched == Schedule.STAGGERED_BATCH:
        half = x.shape[0] // 2
        y0, aux0 = run(x[:half])
        y1, aux1 = run(x[half:])
        y_new = jnp.concatenate([y0, y1], axis=0)
        out = state.y_buf
        new = MoELayerState(y_buf=y_new, x_prev=x, h_cache=None)
        aux = MoEAux(lb_loss=(aux0.lb_loss + aux1.lb_loss) / 2,
                     dropped_frac=(aux0.dropped_frac + aux1.dropped_frac) / 2,
                     dispatch_bytes=aux0.dispatch_bytes + aux1.dispatch_bytes,
                     pair_vals=None, scores=None)
        return out, new, aux

    y_new, aux = run(x, mask, state.h_cache if want_cache else None)
    out = state.y_buf
    new = MoELayerState(
        y_buf=y_new, x_prev=None,
        h_cache=conditional.update_cache(state.h_cache, aux.pair_vals, mask)
        if want_cache else None)
    return out, new, aux


@pytest.mark.parametrize("name", list(FIVE))
@pytest.mark.parametrize("layer", [0, 3])
def test_registry_matches_legacy_moe_step(name, layer):
    """Outputs of the planned path are BITWISE equal to the seed's chain,
    per layer, for every step of an 8-step run (covers warmup, refresh,
    light, and both deep/shallow layers under DICE)."""
    dcfg = FIVE[name]
    p = moe_init(jax.random.PRNGKey(0), CFG)
    xs = [jax.random.normal(jax.random.PRNGKey(10 + s), (16, 32), jnp.float32)
          for s in range(8)]
    st_new, st_old = MoELayerState(), MoELayerState()
    for s, x in enumerate(xs):
        y_new, st_new, aux_new = moe_step(
            p, x, CFG, dcfg, st_new, moe_layer_idx=layer, num_moe_layers=4,
            step_idx=s)
        y_old, st_old, aux_old = _legacy_moe_step(
            p, x, CFG, dcfg, st_old, moe_layer_idx=layer, num_moe_layers=4,
            step_idx=s)
        assert (y_new is None) == (y_old is None), (name, s)
        if y_new is not None:
            np.testing.assert_array_equal(np.asarray(y_new),
                                          np.asarray(y_old),
                                          err_msg=f"{name} step {s}")
        assert int(aux_new.dispatch_bytes) == int(aux_old.dispatch_bytes)
        # persistent numerical state agrees (x_prev bookkeeping may be
        # pre-allocated earlier under the planned path; y_buf/h_cache are
        # the values later steps actually consume)
        for f in ("y_buf", "h_cache"):
            a, b = getattr(st_new, f), getattr(st_old, f)
            assert (a is None) == (b is None), (name, s, f)
            if a is not None:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("policy", ["low", "high"])
def test_cond_policy_ablation_matches_legacy(policy):
    dcfg = DiceConfig.dice(sync_policy="none", cond_policy=policy)
    p = moe_init(jax.random.PRNGKey(0), CFG)
    xs = [jax.random.normal(jax.random.PRNGKey(30 + s), (16, 32), jnp.float32)
          for s in range(6)]
    st_new, st_old = MoELayerState(), MoELayerState()
    for s, x in enumerate(xs):
        y_new, st_new, _ = moe_step(p, x, CFG, dcfg, st_new, moe_layer_idx=1,
                                    num_moe_layers=4, step_idx=s)
        y_old, st_old, _ = _legacy_moe_step(p, x, CFG, dcfg, st_old,
                                            moe_layer_idx=1,
                                            num_moe_layers=4, step_idx=s)
        np.testing.assert_array_equal(np.asarray(y_new), np.asarray(y_old))


# ---------------------------------------------------------------------------
# 3. compile count + bit-identical sampling
# ---------------------------------------------------------------------------
def test_dice_20_step_sample_compiles_once_per_variant():
    """Acceptance: 20-step rf_sample under DiceConfig.dice() triggers <= 4
    jit compilations of the step function (seed: 20), and the bucketed
    execution is bit-identical to jitting every step separately."""
    cfg = tiny()
    dcfg = DiceConfig.dice()
    params = init_dit(jax.random.PRNGKey(0), cfg)
    classes = jnp.arange(2) % cfg.num_classes
    key = jax.random.PRNGKey(7)
    num_steps = 20

    x, stats = rf_sample(params, cfg, dcfg, num_steps=num_steps,
                         classes=classes, key=key)
    assert stats["num_plan_variants"] == 3
    assert stats["jit_cache_size"] <= 4
    assert stats["jit_cache_size"] == stats["num_plan_variants"]

    # reference: identical plans, but a FRESH jit cache per step -> one
    # compile per step (the seed behavior); outputs must be bitwise equal
    B = classes.shape[0]
    dt = 1.0 / num_steps
    splan = plan_lib.compile_step_plans(
        dcfg, cfg.num_layers, num_steps,
        experts_per_token=cfg.experts_per_token)
    k0 = jax.random.PRNGKey(7)
    x_ref = jax.random.normal(k0, (B, cfg.patch_tokens, cfg.in_channels))
    states = init_planned_states(splan, num_tokens=B * cfg.patch_tokens,
                                 d_model=cfg.d_model,
                                 k=cfg.experts_per_token, dtype=x_ref.dtype)
    states_u = init_planned_states(splan, num_tokens=B * cfg.patch_tokens,
                                   d_model=cfg.d_model,
                                   k=cfg.experts_per_token, dtype=x_ref.dtype)
    ps, psu = {}, {}
    for s in range(num_steps):
        step = make_sample_step(params, cfg, dcfg, classes, dt=dt)  # fresh jit
        k0, k = jax.random.split(k0)
        t = jnp.full((B,), s * dt)
        x_ref, states, states_u, ps, psu, _ = step(
            x_ref, states, states_u, ps, psu, t, k, plan=splan.steps[s])
        assert step._cache_size() == 1      # per-step recompile, by design
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_ref))
    assert bool(jnp.isfinite(x).all())


def test_all_schedules_cache_equals_variants():
    cfg = tiny()
    params = init_dit(jax.random.PRNGKey(0), cfg)
    classes = jnp.arange(2) % cfg.num_classes
    for name, dcfg in FIVE.items():
        _, stats = rf_sample(params, cfg, dcfg, num_steps=8, classes=classes,
                             key=jax.random.PRNGKey(3))
        assert stats["jit_cache_size"] == stats["num_plan_variants"], name


def test_step_plan_kind_names_each_variant():
    def kinds(dcfg):
        return [p.kind for p in plan_lib.compile_step_plans(
            dcfg, 4, 6, experts_per_token=2).steps]

    assert kinds(DiceConfig.dice()) == ["warmup", "warmup", "refresh",
                                        "light", "refresh", "light"]
    selective = DiceConfig(schedule=Schedule.DICE, sync_policy="deep",
                           cond_comm=False)
    assert kinds(selective)[2:] == ["refresh"] * 4
    assert kinds(DiceConfig.sync_ep()) == ["sync"] * 6
