"""Expert paging conformance (ISSUE 8 tentpole): paged == resident.

In-process: pool geometry (phantom padding to E_pad, window/budget
arithmetic), the params <-> pool split, the streamed
``load_pooled_checkpoint`` restore, plan stamping (prefetch/resident
fields, paging x placement exclusion, mesh-less normalization keeping
plans bit-identical to unpaged ones), and budget validation.

Subprocess (8 host devices, like test_ep_dice): for ALL FIVE schedules a
paged run under the tightest auto budget — holding strictly fewer
expert-shard bytes per device than full residency — is bit-identical to
the fully-resident mesh run; E=12 on an 8-way mesh (impossible before
paging: ``E % n_dev != 0``) matches the single-device reference; the
jit cache stays at the plan-variant count; an infeasible budget raises
before anything compiles.
"""
import dataclasses
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import save_checkpoint
from repro.core import paging as paging_lib
from repro.core import plan as plan_lib
from repro.core.paging import EXPERT_LEAF_NAMES, ExpertPool, PagingSpec
from repro.core.schedules import DiceConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _layers(num_layers=3, e=8, d=4, f=6):
    rng = np.random.default_rng(0)
    return {i: {"experts_gate": rng.normal(size=(e, d, f)).astype(np.float32),
                "experts_up": rng.normal(size=(e, d, f)).astype(np.float32),
                "experts_down": rng.normal(size=(e, f, d)).astype(np.float32)}
            for i in range(num_layers)}


# ---------------------------------------------------------------------------
# pool geometry
# ---------------------------------------------------------------------------
def test_pool_pads_to_multiple_of_n_dev():
    pool = ExpertPool(_layers(e=12), n_dev=8)
    assert pool.num_experts == 12
    assert pool.num_wire_experts == 16       # next multiple of 8
    assert pool.e_loc == 2
    # phantom rows are zero weight: they can never contribute even if a
    # stray token landed on them
    (shape, dt), *_ = pool.shard_shape_dtypes(0)
    last = pool._layers[0]["experts_gate"][12:]
    assert last.shape[0] == 4 and not last.any()


def test_pool_no_padding_when_divisible():
    pool = ExpertPool(_layers(e=8), n_dev=8)
    assert pool.num_wire_experts == 8 and pool.e_loc == 1


def test_pool_budget_arithmetic():
    pool = ExpertPool(_layers(num_layers=4, e=8), n_dev=4)
    per_layer = pool.layer_shard_bytes(0)
    assert pool.window_bytes([0, 1]) == 2 * per_layer
    # depth 1 -> largest 2-layer window; uniform layers: 2x one shard
    assert pool.min_budget_bytes(1) == 2 * per_layer
    assert pool.min_budget_bytes(3) == 4 * per_layer
    assert pool.total_host_bytes() == 4 * 4 * per_layer   # n_dev * layers


def test_pool_fetch_ledger_tracks_peak():
    pool = ExpertPool(_layers(num_layers=4, e=8), n_dev=4)
    pool._resident_window = 2
    per_layer = pool.layer_shard_bytes(0)
    for layer in range(4):
        pool._fetch_host(layer, np.int32(0))
    assert pool.transfers == 4
    assert pool.peak_resident_bytes == 2 * per_layer      # window caps it
    # a re-fetch refreshes residency instead of double-counting
    pool._fetch_host(3, np.int32(0))
    assert pool.peak_resident_bytes == 2 * per_layer
    pool.reset_stats()
    assert pool.transfers == 0 and pool.peak_resident_bytes == 0


def test_pool_rejects_nonuniform_expert_counts():
    layers = _layers(num_layers=2, e=8)
    layers[1] = {k: v[:6] for k, v in layers[1].items()}
    with pytest.raises(ValueError, match="uniform expert count"):
        ExpertPool(layers, n_dev=4)


def test_paging_spec_validation():
    with pytest.raises(ValueError, match="depth"):
        PagingSpec(depth=0)
    with pytest.raises(ValueError, match="budget"):
        PagingSpec(budget_bytes=-1)


# ---------------------------------------------------------------------------
# params <-> pool split + streamed pooled restore
# ---------------------------------------------------------------------------
def _tiny_params():
    from repro.configs.dit_moe_xl import tiny
    from repro.models.dit_moe import init_dit
    cfg = tiny().replace(num_layers=2, d_model=32, moe_d_ff=32, d_ff=64,
                         num_heads=2, num_kv_heads=2, head_dim=16,
                         patch_tokens=8)
    return cfg, init_dit(jax.random.PRNGKey(0), cfg)


def test_strip_and_pool_partition_params():
    cfg, params = _tiny_params()
    assert paging_lib.has_expert_leaves(params)
    pool = paging_lib.pool_from_params(params, n_dev=4)
    stripped = paging_lib.strip_expert_params(params)
    assert not paging_lib.has_expert_leaves(stripped)
    assert pool.num_layers == cfg.num_layers
    assert pool.num_experts == cfg.num_experts
    # the split is lossless: pool rows == original expert stacks
    for i, blk in enumerate(params["blocks"]):
        for k in EXPERT_LEAF_NAMES:
            np.testing.assert_array_equal(
                pool._layers[i][k][:cfg.num_experts], np.asarray(blk["moe"][k]))
        # non-expert leaves survive the strip untouched
        assert "router" in stripped["blocks"][i]["moe"]


def test_load_pooled_checkpoint_streams_the_split():
    cfg, params = _tiny_params()
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.msgpack")
        save_checkpoint(path, params)
        stripped, pool = paging_lib.load_pooled_checkpoint(path, params,
                                                           n_dev=4)
    assert not paging_lib.has_expert_leaves(stripped)
    assert pool.num_layers == cfg.num_layers
    for i, blk in enumerate(params["blocks"]):
        for k in EXPERT_LEAF_NAMES:
            np.testing.assert_array_equal(
                pool._layers[i][k][:cfg.num_experts], np.asarray(blk["moe"][k]))
        np.testing.assert_array_equal(
            np.asarray(stripped["blocks"][i]["moe"]["router"]),
            np.asarray(blk["moe"]["router"]))


# ---------------------------------------------------------------------------
# plan stamping + normalization
# ---------------------------------------------------------------------------
def test_plan_stamps_prefetch_and_resident():
    dcfg = dataclasses.replace(DiceConfig.sync_ep(),
                               paging=PagingSpec(budget_bytes=None, depth=1))
    plan = plan_lib.plan_for_step(dcfg, 4, 5,
                                  experts_per_token=2)
    for i, a in enumerate(plan.actions):
        assert a.paging is not None
        assert a.resident == tuple(range(i, min(i + 2, 4)))
        assert a.prefetch == (i + 1 if i + 1 < 4 else None)
    # depth 2: two layers ahead, three resident
    dcfg2 = dataclasses.replace(dcfg, paging=PagingSpec(depth=2))
    plan2 = plan_lib.plan_for_step(dcfg2, 4, 5,
                                   experts_per_token=2)
    assert plan2.actions[0].prefetch == 2
    assert plan2.actions[0].resident == (0, 1, 2)
    assert plan2.actions[3].prefetch is None


def test_normalize_paging_strips_meshless_plans_bit_identical():
    base = DiceConfig.dice()
    paged = dataclasses.replace(base, paging=PagingSpec(budget_bytes=None))
    norm = plan_lib.resolve(paged, 1)
    for step in range(4):
        ref = plan_lib.plan_for_step(base, 4, step,
                                     experts_per_token=2)
        got = plan_lib.plan_for_step(norm, 4, step,
                                     experts_per_token=2)
        assert got == ref                     # same hash -> same jit entry
    # n > 1 keeps the spec
    assert paging_lib.paging_of(plan_lib.resolve(paged, 8))


def test_paging_excludes_placement():
    from repro.core.placement import Placement
    pl = Placement(perm=tuple(range(8)), replicated=(), cap_scale=1.0)
    dcfg = dataclasses.replace(DiceConfig.sync_ep(),
                               paging=PagingSpec(),
                               placements=(pl,) * 2)
    with pytest.raises(ValueError):
        plan_lib.plan_for_step(dcfg, 2, 0,
                               experts_per_token=2)


def test_validate_plan_rejects_infeasible_budget():
    pool = ExpertPool(_layers(num_layers=4, e=8), n_dev=4)
    dcfg = dataclasses.replace(
        DiceConfig.sync_ep(), paging=PagingSpec(budget_bytes=1))
    splan = plan_lib.compile_step_plans(dcfg, 4, 4, experts_per_token=2)
    with pytest.raises(ValueError, match="budget"):
        pool.validate_plan(splan)
    # the tightest feasible budget passes
    ok = dataclasses.replace(
        DiceConfig.sync_ep(),
        paging=PagingSpec(budget_bytes=pool.min_budget_bytes(1)))
    pool.validate_plan(plan_lib.compile_step_plans(ok, 4, 4,
                                                   experts_per_token=2))


def test_resolve_budget_auto_sentinel():
    pool = ExpertPool(_layers(num_layers=4, e=8), n_dev=4)
    dcfg = dataclasses.replace(DiceConfig.sync_ep(),
                               paging=PagingSpec(budget_bytes=0))
    got = paging_lib.paging_of(paging_lib.resolve_budget(dcfg, pool))
    assert got.budget_bytes == pool.min_budget_bytes(1)
    # explicit and unbounded budgets pass through untouched
    for b in (None, 12345):
        dcfg_b = dataclasses.replace(dcfg, paging=PagingSpec(budget_bytes=b))
        assert paging_lib.paging_of(
            paging_lib.resolve_budget(dcfg_b, pool)).budget_bytes == b


# ---------------------------------------------------------------------------
# fetch resilience (ISSUE 10): retry/backoff, stale fallback, and the
# no-budget-leak reservation ledger
# ---------------------------------------------------------------------------
def _res(**kw):
    from repro.resilience import FaultConfig, ResilienceConfig
    fault_kw = {k: kw.pop(k) for k in list(kw)
                if k in ("seed", "paging_error_rate")}
    return ResilienceConfig(faults=FaultConfig(**fault_kw) if fault_kw
                            else None, **kw)


def test_fetch_error_releases_reservation_no_budget_leak():
    # a fetch that never delivered bytes must not occupy a window slot,
    # count a transfer, or move the residency peak — across ALL retries
    pool = ExpertPool(_layers(num_layers=4, e=8), n_dev=4)
    pool._resident_window = 2
    pool.set_resilience(_res(seed=0, paging_error_rate=1.0,
                             paging_retries=2, paging_backoff_s=0.0,
                             stale_fallback=False))
    from repro.core.paging import PagingFetchError
    for layer in range(4):
        with pytest.raises(PagingFetchError, match="injected"):
            pool._fetch_host(layer, np.int32(0))
    assert pool.transfers == 0
    assert pool.bytes_transferred == 0
    assert pool.peak_resident_bytes == 0
    assert pool._resident.get(0, []) == []           # nothing leaked
    assert pool.fetch_errors == 4 * 3                # every attempt failed
    assert pool.fetch_retries == 4 * 2
    # the pool still works once the fault clears
    pool.set_resilience(_res(stale_fallback=True))
    shards = pool._fetch_host(0, np.int32(0))
    np.testing.assert_array_equal(shards[0], pool._slice_shards(0, 0)[0])
    assert pool.transfers == 1 and pool._resident[0] == [0]


def test_fetch_retry_then_success():
    # find a seed where attempt 0 rolls a fault and attempt 1 does not:
    # the retry path must deliver the shard and count exactly one error
    from repro.resilience.faults import FaultPlan, FaultConfig
    rate = 0.5
    seed = next(s for s in range(1000)
                if FaultPlan(FaultConfig(s, paging_error_rate=rate)
                             ).paging_error(0, 0, 1, 0)
                and not FaultPlan(FaultConfig(s, paging_error_rate=rate)
                                  ).paging_error(0, 0, 1, 1))
    pool = ExpertPool(_layers(num_layers=2, e=8), n_dev=4)
    pool.set_resilience(_res(seed=seed, paging_error_rate=rate,
                             paging_retries=2, paging_backoff_s=0.0))
    shards = pool._fetch_host(0, np.int32(0))
    np.testing.assert_array_equal(shards[0], pool._slice_shards(0, 0)[0])
    assert pool.fetch_errors == 1
    assert pool.fetch_retries == 1
    assert pool.transfers == 1                        # delivered exactly once


def test_stale_fallback_serves_resident_shard():
    # all retries exhausted with the fallback on: the still-resident shard
    # is served (bit-identical — weights are static), no transfer counted
    pool = ExpertPool(_layers(num_layers=2, e=8), n_dev=4)
    pool.set_resilience(_res())
    ok = pool._fetch_host(0, np.int32(0))             # clean fetch first
    assert pool.transfers == 1
    pool.set_resilience(_res(seed=0, paging_error_rate=1.0,
                             paging_retries=0, stale_fallback=True))
    again = pool._fetch_host(0, np.int32(0))
    for a, b in zip(ok, again):
        np.testing.assert_array_equal(a, b)
    assert pool.stale_fallbacks == 1
    assert pool.transfers == 1                        # nothing new moved
    assert pool._resident[0] == [0]                   # residency kept


def test_fetch_deadline_cuts_retries_short():
    # a backoff that would bust the deadline stops retrying immediately
    pool = ExpertPool(_layers(num_layers=2, e=8), n_dev=4)
    pool.set_resilience(_res(seed=0, paging_error_rate=1.0,
                             paging_retries=5, paging_backoff_s=10.0,
                             paging_deadline_s=1e-3, stale_fallback=True))
    pool._fetch_host(0, np.int32(0))
    assert pool.fetch_errors == 1                     # first attempt only
    assert pool.fetch_retries == 0
    assert pool.stale_fallbacks == 1


# ---------------------------------------------------------------------------
# 8-device conformance (subprocess, like test_ep_dice)
# ---------------------------------------------------------------------------
PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs.dit_moe_xl import tiny
    from repro.core import plan as plan_lib
    from repro.core.paging import PagingSpec
    from repro.core.schedules import DiceConfig, Schedule
    from repro.launch.mesh import make_ep_mesh
    from repro.models.dit_moe import init_dit
    from repro.sampling.rectified_flow import rf_sample

    # 4 MoE layers so the depth-1 residency window (2 layers) is a strict
    # subset of full residency; drop-free capacity as in test_ep_dice
    cfg = tiny().replace(num_layers=4, d_model=64, moe_d_ff=64, d_ff=256,
                         num_heads=4, num_kv_heads=4, head_dim=16,
                         patch_tokens=16, capacity_factor=8.0)
    params = init_dit(jax.random.PRNGKey(0), cfg)
    classes = jnp.arange(8) % cfg.num_classes
    key = jax.random.PRNGKey(7)
    mesh = make_ep_mesh(8)
    NUM_STEPS = 6

    SCHEDULES = [
        ("sync", DiceConfig.sync_ep()),
        ("displaced", DiceConfig.displaced()),
        ("interweaved", DiceConfig.interweaved()),
        ("selective", DiceConfig(schedule=Schedule.DICE, sync_policy="deep",
                                 cond_comm=False)),
        ("dice", DiceConfig.dice(sync_policy="deep")),
    ]
    for name, dcfg in SCHEDULES:
        ref, _ = rf_sample(params, cfg, dcfg, num_steps=NUM_STEPS,
                           classes=classes, key=key, guidance=1.0,
                           mesh=mesh)
        pcfg = dataclasses.replace(dcfg, paging=PagingSpec(budget_bytes=0))
        out, stats = rf_sample(params, cfg, pcfg, num_steps=NUM_STEPS,
                               classes=classes, key=key, guidance=1.0,
                               mesh=mesh)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err <= 1e-7, (name, err)
        budget = stats["expert_hbm_budget"]
        peak = stats["peak_resident_expert_bytes"]
        assert stats["paged_transfers"] > 0, name
        assert peak <= budget, (name, peak, budget)
        # the budget holds < E experts worth of layers per device: peak
        # stays strictly below what full residency would occupy
        full = 2 * peak          # window = 2 of 4 uniform layers
        assert budget < full, (name, budget, full)
        splan = plan_lib.compile_step_plans(
            pcfg if stats["expert_hbm_budget"] is None else
            dataclasses.replace(pcfg, paging=PagingSpec(budget_bytes=budget)),
            cfg.num_layers, NUM_STEPS,
            experts_per_token=cfg.experts_per_token)
        assert stats["num_plan_variants"] == splan.num_variants, name
        assert stats["jit_cache_size"] == splan.num_variants, (
            name, stats["jit_cache_size"], splan.num_variants)
        print("PAGED-PARITY", name, err, peak, budget)

    # ---- E % n_dev decoupling: 12 experts on an 8-way mesh -------------
    cfg12 = cfg.replace(num_experts=12)
    params12 = init_dit(jax.random.PRNGKey(0), cfg12)
    dcfg = DiceConfig.dice(sync_policy="deep")
    ref12, _ = rf_sample(params12, cfg12, dcfg, num_steps=NUM_STEPS,
                         classes=classes, key=key, guidance=1.0)
    pcfg12 = dataclasses.replace(dcfg, paging=PagingSpec(budget_bytes=0))
    out12, stats12 = rf_sample(params12, cfg12, pcfg12, num_steps=NUM_STEPS,
                               classes=classes, key=key, guidance=1.0,
                               mesh=mesh)
    err12 = float(jnp.max(jnp.abs(out12 - ref12)))
    assert err12 <= 1e-7, err12
    assert stats12["jit_cache_size"] == stats12["num_plan_variants"]
    print("DECOUPLED", err12)

    # the unpaged mesh path still refuses indivisible expert counts, with
    # a pointer at paging
    try:
        rf_sample(params12, cfg12, dcfg, num_steps=2, classes=classes,
                  key=key, guidance=1.0, mesh=mesh)
        raise SystemExit("indivisible E without paging should have raised")
    except ValueError as e:
        assert "paging" in str(e), e
    print("UNPAGED-RAISES ok")

    # ---- an infeasible budget fails before compiling -------------------
    bad = dataclasses.replace(dcfg, paging=PagingSpec(budget_bytes=1))
    try:
        rf_sample(params, cfg, bad, num_steps=2, classes=classes, key=key,
                  guidance=1.0, mesh=mesh)
        raise SystemExit("1-byte budget should have raised")
    except ValueError as e:
        assert "budget" in str(e), e
    print("BUDGET-RAISES ok")
    print("PAGING-OK")
""")


def test_paged_distributed_parity_all_schedules():
    r = subprocess.run([sys.executable, "-c", PROG], capture_output=True,
                       text=True,
                       env=dict(os.environ, PYTHONPATH="src"),
                       cwd=REPO, timeout=1200)
    assert "PAGING-OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
    for name in ("sync", "displaced", "interweaved", "selective", "dice"):
        assert f"PAGED-PARITY {name}" in r.stdout, (name, r.stdout[-2000:])
    assert "DECOUPLED" in r.stdout, r.stdout[-2000:]
    assert "UNPAGED-RAISES ok" in r.stdout, r.stdout[-2000:]
    assert "BUDGET-RAISES ok" in r.stdout, r.stdout[-2000:]
