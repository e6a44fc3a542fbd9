"""Affinity-aware expert placement (DESIGN.md Sec. 13): optimizer,
histogram, parameter layout, and plan normalization — the single-device
half of the placement contract.  The mesh half (distributed == unplaced
single-device for all five schedules, cap_scale shrinking real wire
payloads) lives in test_ep_dice.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import ModelConfig
from repro.core import plan as plan_lib
from repro.core.moe import moe_forward, moe_init
from repro.core.placement import (CAP_QUANTUM, Placement, PlacementConfig,
                                  RoutingHistogram, drift,
                                  expected_cross_device_traffic,
                                  greedy_placement, greedy_placements,
                                  place_moe_params, placed_params)
from repro.core.schedules import DiceConfig


def _cfg(**kw):
    base = dict(name="t", family="moe", num_layers=2, d_model=64, d_ff=128,
                vocab_size=64, num_heads=4, num_kv_heads=2, num_experts=8,
                experts_per_token=2, moe_d_ff=96)
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------------------
# Placement dataclass
# ---------------------------------------------------------------------------
def test_placement_validation():
    with pytest.raises(ValueError):
        Placement(perm=(0, 0, 1, 2))                  # not a permutation
    with pytest.raises(ValueError):
        Placement(perm=(0, 1, 2, 3), replicated=(2, 1))   # unsorted
    with pytest.raises(ValueError):
        Placement(perm=(0, 1, 2, 3), replicated=(1, 1))   # duplicate
    with pytest.raises(ValueError):
        Placement(perm=(0, 1, 2, 3), replicated=(4,))     # out of range
    with pytest.raises(ValueError):
        Placement(perm=(0, 1, 2, 3), cap_scale=0.0)
    with pytest.raises(ValueError):
        Placement(perm=(0, 1, 2, 3), cap_scale=1.5)


def test_identity_properties():
    pl = Placement.identity(8)
    assert pl.is_identity and pl.num_experts == 8
    assert not Placement(perm=(1, 0, 2, 3)).is_identity
    assert not Placement(perm=(0, 1, 2, 3), replicated=(0,)).is_identity
    assert not Placement(perm=(0, 1, 2, 3), cap_scale=0.5).is_identity
    pl = Placement(perm=(3, 1, 0, 2))
    inv = pl.inv_perm()
    assert all(pl.perm[inv[e]] == e for e in range(4))


def test_scaled_capacity_alignment():
    pl = Placement(perm=tuple(range(8)), replicated=(0,), cap_scale=0.51)
    # rounds UP to the 8-alignment of default_capacity, never exceeds
    assert pl.scaled_capacity(64) == 40           # ceil(32.64) -> 33 -> 40
    assert pl.scaled_capacity(8) == 8             # floor keeps it runnable
    assert Placement.identity(8).scaled_capacity(64) == 64
    for cap in (8, 16, 64, 104):
        c = pl.scaled_capacity(cap)
        assert c % 8 == 0 and 8 <= c <= cap


# ---------------------------------------------------------------------------
# greedy optimizer
# ---------------------------------------------------------------------------
def test_greedy_uniform_is_identity():
    """A flat histogram must reproduce the pre-placement layout exactly,
    for every device count — that is what lets the stamped placement
    normalize away and keep plans bit-identical."""
    s = np.full(8, 1 / 8)
    for n in (1, 2, 4, 8):
        pl = greedy_placement(s, n)
        assert pl.is_identity, (n, pl)


def test_greedy_skewed_lowers_bottleneck():
    """On a skewed histogram with several experts per device the pack must
    strictly beat identity on the bottleneck-traffic objective."""
    s = np.array([0.4, 0.3, 0.1, 0.05, 0.05, 0.04, 0.03, 0.03])
    for n in (2, 4):
        pl = greedy_placement(s, n)
        ident = Placement.identity(8)
        t_pl = expected_cross_device_traffic(s, pl, n)
        t_id = expected_cross_device_traffic(s, ident, n)
        assert t_pl < t_id, (n, t_pl, t_id)
        # LPT can never beat the per-device mean; sanity-bound it
        assert t_pl >= (1.0 / n) * (n - 1) / n - 1e-12


def test_replication_lowers_traffic_further():
    s = np.array([0.5, 0.2, 0.1, 0.05, 0.05, 0.04, 0.03, 0.03])
    base = greedy_placement(s, 4)
    rep = greedy_placement(s, 4, replicate_top=1)
    assert rep.replicated == (0,)            # the hottest expert
    assert (expected_cross_device_traffic(s, rep, 4)
            < expected_cross_device_traffic(s, base, 4))
    # serving the 0.5-share expert locally means the planned capacity only
    # needs to cover the 0.2-share runner-up
    assert rep.cap_scale < 1.0
    assert abs(rep.cap_scale * CAP_QUANTUM
               - round(rep.cap_scale * CAP_QUANTUM)) < 1e-9
    assert rep.cap_scale >= 0.2 / 0.5        # quantized UP, never down


def test_greedy_deterministic_ties():
    """Equal shares break ties toward lower ids — byte-identical reruns."""
    s = np.array([0.3, 0.3, 0.1, 0.1, 0.05, 0.05, 0.05, 0.05])
    a = greedy_placement(s, 4, replicate_top=2)
    b = greedy_placement(s, 4, replicate_top=2)
    assert a == b
    assert a.replicated == (0, 1)            # ties -> lower id


def test_greedy_errors():
    with pytest.raises(ValueError):
        greedy_placement(np.full(6, 1 / 6), 4)       # 6 experts on 4 devices
    with pytest.raises(ValueError):
        greedy_placement(np.full(4, 0.25), 2, replicate_top=4)


def test_greedy_placements_per_layer():
    sh = np.stack([np.full(8, 1 / 8),
                   np.array([0.4, 0.3, 0.1, 0.05, 0.05, 0.04, 0.03, 0.03])])
    pls = greedy_placements(sh, 4)
    assert len(pls) == 2
    assert pls[0].is_identity and not pls[1].is_identity


# ---------------------------------------------------------------------------
# routing histogram
# ---------------------------------------------------------------------------
def test_histogram_scale_invariant():
    """pmean-reduced, psum-reduced, and raw single-device count feeds all
    produce the identical EMA — distributed histogram == single-device."""
    rng = np.random.default_rng(0)
    feeds = [rng.uniform(0, 100, (3, 8)) for _ in range(5)]
    h_raw = RoutingHistogram(3, 8)
    h_pmean = RoutingHistogram(3, 8)
    h_psum = RoutingHistogram(3, 8)
    for c in feeds:
        h_raw.update(c)
        h_pmean.update(c / 8.0)
        h_psum.update(c * 8.0)
    np.testing.assert_allclose(h_raw.shares, h_pmean.shares, rtol=1e-12)
    np.testing.assert_allclose(h_raw.shares, h_psum.shares, rtol=1e-12)
    assert h_raw.updates == 5


def test_histogram_first_update_direct():
    """The first observation replaces the uniform prior outright — no
    uniform bias bleeding into early drift decisions."""
    h = RoutingHistogram(1, 4, decay=0.9)
    np.testing.assert_allclose(h.shares, 0.25)       # prior before any data
    h.update(np.array([[8.0, 0.0, 0.0, 0.0]]))
    np.testing.assert_allclose(h.shares, [[1.0, 0.0, 0.0, 0.0]])
    h.update(np.array([[0.0, 8.0, 0.0, 0.0]]))
    np.testing.assert_allclose(h.shares, [[0.9, 0.1, 0.0, 0.0]])


def test_histogram_zero_step_and_shape():
    h = RoutingHistogram(1, 4)
    h.update(np.zeros((1, 4)))                       # an all-stale step
    np.testing.assert_allclose(h.shares, 0.25)       # falls back to uniform
    with pytest.raises(ValueError):
        h.update(np.zeros((2, 4)))


def test_drift_metric():
    a = np.full((2, 4), 0.25)
    assert drift(a, a) == 0.0
    b = a.copy()
    b[1] = [1.0, 0.0, 0.0, 0.0]                      # one layer fully moved
    assert abs(drift(a, b) - 0.75) < 1e-12
    assert drift(a, b) == drift(b, a)


def test_placement_config_validation():
    with pytest.raises(ValueError):
        PlacementConfig(mode="magic")
    with pytest.raises(ValueError):
        PlacementConfig(replicate_top=-1)
    with pytest.raises(ValueError):
        PlacementConfig(ema_decay=1.0)


# ---------------------------------------------------------------------------
# parameter layout
# ---------------------------------------------------------------------------
def test_place_moe_params_roundtrip():
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(0), cfg)
    pl = Placement(perm=(3, 1, 0, 2, 5, 4, 7, 6), replicated=(2,))
    pp = place_moe_params(p, pl)
    for name in ("experts_gate", "experts_up", "experts_down"):
        # wire slot s holds original expert perm[s]
        for s, e in enumerate(pl.perm):
            np.testing.assert_array_equal(np.asarray(pp[name][s]),
                                          np.asarray(p[name][e]))
        np.testing.assert_array_equal(np.asarray(pp[name + "_rep"][0]),
                                      np.asarray(p[name][2]))
    # router stays in expert-id space, untouched
    np.testing.assert_array_equal(np.asarray(pp["router"]),
                                  np.asarray(p["router"]))
    # double application is a layout bug, not a silent re-shuffle
    with pytest.raises(ValueError):
        place_moe_params(pp, pl)
    # identity / None are no-ops returning the original dict
    assert place_moe_params(p, None) is p
    assert place_moe_params(p, Placement.identity(8)) is p


def test_placed_params_count_mismatch():
    cfg = _cfg()
    p = {"blocks": [{"moe": moe_init(jax.random.PRNGKey(0), cfg)}
                    for _ in range(2)]}
    pls = (Placement.identity(8),)                   # 1 placement, 2 layers
    with pytest.raises(ValueError):
        placed_params(p, pls)
    out = placed_params(p, (None, Placement.identity(8)))
    np.testing.assert_array_equal(
        np.asarray(out["blocks"][0]["moe"]["experts_gate"]),
        np.asarray(p["blocks"][0]["moe"]["experts_gate"]))


# ---------------------------------------------------------------------------
# single-device execution parity + served counts
# ---------------------------------------------------------------------------
def test_moe_forward_placement_parity_single_device():
    """Placed params + placement == plain forward, exactly: the layout is
    an execution detail, the math (combine order included) is unchanged."""
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64), jnp.float32)
    y_ref, aux_ref = moe_forward(p, x, cfg, capacity=32)
    pl = Placement(perm=(3, 1, 0, 2, 5, 4, 7, 6), replicated=(2,))
    y_pl, aux_pl = moe_forward(place_moe_params(p, pl), x, cfg,
                               capacity=32, placement=pl)
    err = float(jnp.max(jnp.abs(y_pl - y_ref)))
    assert err < 1e-6, err
    # routing accounting stays in expert-id space under any layout
    np.testing.assert_array_equal(np.asarray(aux_pl.counts),
                                  np.asarray(aux_ref.counts))
    np.testing.assert_array_equal(np.asarray(aux_pl.served_counts),
                                  np.asarray(aux_ref.served_counts))


def test_served_counts_post_drop():
    """MoEAux.counts is routed demand (pre-drop); served_counts is what
    the capacity actually admitted — the histogram feed must use the
    latter so dropped tokens never inflate a hot expert's score."""
    cfg = _cfg(num_experts=4)
    p = moe_init(jax.random.PRNGKey(0), cfg)
    # ample capacity: every routed pair is served
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64), jnp.float32)
    _, aux = moe_forward(p, x, cfg, capacity=64)
    np.testing.assert_array_equal(np.asarray(aux.counts),
                                  np.asarray(aux.served_counts))
    # identical tokens slam one expert past capacity: served < routed
    _, aux = moe_forward(p, jnp.ones((64, 64), jnp.float32), cfg, capacity=8)
    counts = np.asarray(aux.counts)
    served = np.asarray(aux.served_counts)
    assert served.sum() < counts.sum()
    assert (served <= counts).all() and (served <= 8).all()


# ---------------------------------------------------------------------------
# plan stamping + normalization
# ---------------------------------------------------------------------------
def test_identity_placements_normalize_away():
    """dcfg with all-identity placements plans bit-identically to a dcfg
    with none — same StepPlans, same variant count, same jit keys."""
    dcfg = DiceConfig.dice(sync_policy="deep")
    dcfg_i = dataclasses.replace(
        dcfg, placements=(Placement.identity(8), Placement.identity(8)))
    for s in range(6):
        assert (plan_lib.plan_for_step(dcfg_i, 2, s, experts_per_token=2)
                == plan_lib.plan_for_step(dcfg, 2, s, experts_per_token=2))
    sp = plan_lib.compile_step_plans(dcfg, 2, 6, experts_per_token=2)
    sp_i = plan_lib.compile_step_plans(dcfg_i, 2, 6, experts_per_token=2)
    assert sp_i.num_variants == sp.num_variants


def test_placement_stamped_on_every_layer():
    pl = Placement(perm=(1, 0, 2, 3, 4, 5, 6, 7))
    dcfg = dataclasses.replace(DiceConfig.sync_ep(), placements=(pl, pl))
    plan = plan_lib.plan_for_step(dcfg, 2, 0, experts_per_token=2)
    assert all(a.placement == pl for a in plan.actions)
    # a real placement is a new static shape -> a distinct plan variant
    assert plan != plan_lib.plan_for_step(DiceConfig.sync_ep(), 2, 0,
                                          experts_per_token=2)
    with pytest.raises(ValueError):
        plan_lib.plan_for_step(dataclasses.replace(
            DiceConfig.sync_ep(), placements=(pl,)), 2, 0,
            experts_per_token=2)


def test_normalize_placement_strips_single_device():
    pl = Placement(perm=(1, 0, 2, 3, 4, 5, 6, 7), replicated=(0,),
                   cap_scale=0.5)
    dcfg = dataclasses.replace(DiceConfig.sync_ep(), placements=(pl, pl))
    assert plan_lib.placements_of(
        plan_lib.resolve(dcfg, 1)) is None
    assert plan_lib.resolve(dcfg, 8) is dcfg
    # wire-scale model: mean cap_scale over layers
    assert plan_lib.placement_wire_scale(dcfg) == 0.5
    assert plan_lib.placement_wire_scale(DiceConfig.sync_ep()) == 1.0
