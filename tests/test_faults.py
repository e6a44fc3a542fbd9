"""Fault injection, degradation ladder, and request-level recovery
(DESIGN.md Sec. 17).

The load-bearing guarantees tested here:

* **Guard == cond-comm equivalence**: a combine pair NaN-corrupted on the
  wire and absorbed by the guard produces a step BIT-IDENTICAL to a
  conditional-communication step whose ``fresh_mask`` excludes exactly
  those pairs — the guard fallback IS the staleness fallback, so a wire
  fault costs one extra light step of quality for the hit pairs, nothing
  more.
* **Off == absent**: guards-on with clean payloads is bit-identical to
  resilience-off end-to-end, and the jit cache stays at the plan-variant
  count (faults are closure constants, never new trace shapes).
* **Deterministic chaos**: seeded fault storms replay exactly — a
  quarantined request's requeue resamples its rid-keyed noise, so the
  whole degraded run is reproducible bit for bit.
* **Nothing silently lost**: every request is either served or explicitly
  shed with a retry-after hint, under arrival floods and on an 8-device
  mesh under a multi-fault storm (subprocess chaos case below).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import ModelConfig
from repro.configs.dit_moe_xl import tiny
from repro.core.moe import moe_forward, moe_init
from repro.core.schedules import DiceConfig
from repro.launch.serve import DiceServer, Request, serve_continuous
from repro.models.dit_moe import init_dit
from repro.obs import ObsConfig
from repro.resilience import (DegradationController, AdmissionQueue,
                              FE_CORRUPT_COMBINE, FaultConfig, FaultPlan,
                              ResilienceConfig, bursty_arrivals,
                              corruption_mask, normalize_resilience,
                              parse_resilience)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # property tests need the dev extra
    HAVE_HYPOTHESIS = False

CFG = ModelConfig(name="t", family="moe", num_layers=4, d_model=32, d_ff=64,
                  vocab_size=64, num_heads=4, num_kv_heads=4, num_experts=4,
                  experts_per_token=2, moe_d_ff=48, capacity_factor=4.0)


# ---------------------------------------------------------------------------
# FaultPlan: seeded, deterministic, decorrelated
# ---------------------------------------------------------------------------
def test_fault_plan_deterministic_by_seed():
    a = FaultPlan(FaultConfig(seed=3, paging_error_rate=0.5))
    b = FaultPlan(FaultConfig(seed=3, paging_error_rate=0.5))
    c = FaultPlan(FaultConfig(seed=4, paging_error_rate=0.5))
    rolls_a = [a.paging_error(l, d, s, 0) for l in range(4)
               for d in range(4) for s in range(4)]
    assert rolls_a == [b.paging_error(l, d, s, 0) for l in range(4)
                       for d in range(4) for s in range(4)]
    assert rolls_a != [c.paging_error(l, d, s, 0) for l in range(4)
                       for d in range(4) for s in range(4)]


def test_retry_attempts_decorrelated():
    """The roll at attempt 1 must be independent of attempt 0 — otherwise
    a failed fetch implies a failed retry and the retry rung is dead code
    (this is exactly what a linear crc-based roll gets wrong)."""
    fail_then_pass = sum(
        1 for s in range(400)
        if FaultPlan(FaultConfig(seed=s, paging_error_rate=0.5)
                     ).paging_error(0, 0, 1, 0)
        and not FaultPlan(FaultConfig(seed=s, paging_error_rate=0.5)
                          ).paging_error(0, 0, 1, 1))
    # independent Bernoulli(0.5) pair -> ~25%; correlated rolls -> ~0%
    assert 50 <= fail_then_pass <= 150


def test_bursty_arrivals_groups():
    arr = bursty_arrivals(7, rate=2.0, burst_size=3)
    assert len(arr) == 7
    assert arr[0] == arr[1] == arr[2]
    assert arr[3] == arr[4] == arr[5]
    assert arr[3] > arr[0] and arr[6] > arr[3]


def test_parse_resilience_spec():
    res = parse_resilience("seed=9,corrupt=0.25,paging_err=0.5,queue=4,"
                           "admit_deadline=6,requeues=1,demote_after=2")
    assert res.faults.seed == 9
    assert res.faults.corrupt_combine_rate == 0.25
    assert res.faults.paging_error_rate == 0.5
    assert res.max_queue_depth == 4
    assert res.admission_deadline_steps == 6
    assert res.max_requeues == 1
    assert res.demote_after == 2
    assert parse_resilience(None) is None
    assert parse_resilience("") is None
    with pytest.raises(ValueError):
        parse_resilience("not_a_key=1")


def test_normalize_strips_inert_configs():
    assert normalize_resilience(None) is None
    # all-zero fault rates are structurally OFF -> None (byte-identical
    # graphs, the discipline plan.resolve applies to every feature)
    off = ResilienceConfig(faults=FaultConfig(), guards=False,
                           quarantine=False)
    assert normalize_resilience(off) is None
    on = normalize_resilience(ResilienceConfig(
        faults=FaultConfig(seed=1, corrupt_combine_rate=0.1)))
    assert on is not None and on.faults.corrupt_combine_rate == 0.1


# ---------------------------------------------------------------------------
# guard fallback == conditional-communication masked step (bit-identical)
# ---------------------------------------------------------------------------
def _guard_equivalence(seed: int, rate: float):
    """Run A: all pairs fresh, combine payload corrupted at ``rate`` and
    absorbed by the guard.  Run B: no faults, but a cond-comm
    ``fresh_mask`` excluding exactly the pairs A corrupted.  The two
    steps must be bit-identical (capacity is ample, so dispatch cannot
    drop and row independence holds)."""
    p = moe_init(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32), jnp.float32)
    T, K = 16, CFG.experts_per_token
    h_cache = jax.random.normal(jax.random.PRNGKey(2), (T, K, 32),
                                jnp.float32)
    key = jax.random.PRNGKey(100 + seed)
    cap = T * K                                  # no drops possible
    all_fresh = jnp.ones((T, K), bool)

    res = ResilienceConfig(
        faults=FaultConfig(seed=seed, corrupt_combine_rate=rate),
        guards=True)
    y_a, aux_a = moe_forward(p, x, CFG, capacity=cap, fresh_mask=all_fresh,
                             h_cache=h_cache, key=key, resilience=res)

    # the exact mask moe_forward drew (fault_salt defaults to 0; with
    # ample capacity every pair is kept, so the mask applies unclipped)
    cm = corruption_mask(key, seed, 0, FE_CORRUPT_COMBINE, rate, (T, K))
    y_b, aux_b = moe_forward(p, x, CFG, capacity=cap,
                             fresh_mask=all_fresh & ~cm,
                             h_cache=h_cache, key=key)
    return y_a, y_b, cm


@pytest.mark.parametrize("seed,rate", [(0, 0.3), (1, 0.5), (7, 0.9),
                                       (3, 1.0)])
def test_guarded_combine_equals_cond_comm_masked_step(seed, rate):
    y_a, y_b, cm = _guard_equivalence(seed, rate)
    assert bool(np.asarray(cm).any()), "draw corrupted nothing; dead test"
    np.testing.assert_array_equal(np.asarray(y_a), np.asarray(y_b))


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10_000),
           rate=st.floats(0.05, 1.0, allow_nan=False))
    def test_guard_equivalence_property(seed, rate):
        y_a, y_b, _ = _guard_equivalence(seed, rate)
        np.testing.assert_array_equal(np.asarray(y_a), np.asarray(y_b))


def test_guards_off_faults_off_graph_unchanged():
    """resilience=None and a normalized-away config take the identical
    code path: bit-identical outputs."""
    p = moe_init(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32), jnp.float32)
    key = jax.random.PRNGKey(5)
    y0, _ = moe_forward(p, x, CFG, key=key)
    y1, _ = moe_forward(p, x, CFG, key=key,
                        resilience=normalize_resilience(
                            ResilienceConfig(faults=FaultConfig(),
                                             guards=False,
                                             quarantine=False)))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


# ---------------------------------------------------------------------------
# controller / queue units (ladder rungs 3-5)
# ---------------------------------------------------------------------------
def _res_watchdog(**kw):
    return ResilienceConfig(**{"demote_after": 2, "step_deadline_factor": 4.0,
                               **kw})


def test_degradation_controller_demotes_overlap_after_breaches():
    ctrl = DegradationController(_res_watchdog(), baseline_window=3)
    for _ in range(3):
        assert not ctrl.observe_step(0.01)       # calibration only
    assert ctrl.baseline_s == pytest.approx(0.01)
    assert not ctrl.observe_step(0.02)           # jitter, not a breach
    assert ctrl.observe_step(0.1)                # 10x baseline
    assert ctrl.should_demote(True, False) is None   # 1 < demote_after
    assert ctrl.observe_step(0.1)
    assert ctrl.should_demote(True, False) == "overlap"
    assert ctrl.should_demote(False, False) is None  # ring not live
    ctrl.record_demotion("overlap")
    assert ctrl.demotions == ["overlap"]
    assert ctrl.baseline_s == 0.0                # fresh baseline next variant
    assert ctrl.consecutive_breaches == 0


def test_degradation_controller_demotes_codec_on_error_blowup():
    ctrl = DegradationController(_res_watchdog(codec_error_limit=1e-3),
                                 baseline_window=2)
    ctrl.observe_step(0.01, codec_err=1e-6)
    ctrl.observe_step(0.01, codec_err=5e-3)
    assert ctrl.should_demote(False, True) is None   # 1 blowup < demote_after
    ctrl.observe_step(0.01, codec_err=1e-6)          # recovers -> reset
    assert ctrl.consecutive_codec_blowups == 0
    ctrl.observe_step(0.01, codec_err=5e-3)
    ctrl.observe_step(0.01, codec_err=5e-3)
    assert ctrl.should_demote(False, True) == "codec"
    assert ctrl.should_demote(False, False) is None  # codec not live


def test_admission_queue_unbounded_is_legacy_fifo():
    q = AdmissionQueue()
    class R:                                        # noqa: E306
        def __init__(self, rid):
            self.rid = rid
    q.push(2.0, R(2)); q.push(0.0, R(0)); q.push(0.0, R(1))
    assert q.next_arrival() == 0.0
    assert q.shed_overdue(100, retry_after=3.0) == []   # no bounds -> never
    assert [q.pop_ready(100).rid for _ in range(3)] == [0, 1, 2]
    assert q.pop_ready(100) is None


def test_admission_queue_depth_bound_sheds_newest_first():
    q = AdmissionQueue(max_queue_depth=2)
    class R:                                        # noqa: E306
        def __init__(self, rid):
            self.rid = rid
    for i in range(5):
        q.push(0.0, R(i))
    shed = q.shed_overdue(0, retry_after=2.0)
    assert shed == [2, 3, 4]                        # oldest 2 kept (FIFO)
    assert q.peak_depth == 5
    assert q.shed == [(2, 2.0), (3, 2.0), (4, 2.0)]
    assert [q.pop_ready(0).rid for _ in range(2)] == [0, 1]


def test_admission_queue_deadline_and_requeue_cap():
    q = AdmissionQueue(admission_deadline_steps=2)
    class R:                                        # noqa: E306
        def __init__(self, rid):
            self.rid = rid
    r = R(7)
    q.push(0.0, r)
    assert q.shed_overdue(2) == []                  # waited exactly 2: keep
    assert q.shed_overdue(3) == [7]                 # waited 3 > 2: shed
    assert len(q) == 0
    # requeue budget: the third requeue of a persistently poisoned request
    # degrades to a shed, never a livelock
    assert q.requeue(4, r, max_requeues=2)
    q.pop_ready(4)
    assert q.requeue(5, r, max_requeues=2)
    q.pop_ready(5)
    assert not q.requeue(6, r, max_requeues=2)
    assert (7, 0.0) in q.shed


# ---------------------------------------------------------------------------
# end-to-end: serving engine under faults (1 device)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    cfg = tiny().replace(num_layers=4, d_model=64, moe_d_ff=64, d_ff=256,
                         patch_tokens=16, capacity_factor=8.0)
    params = init_dit(jax.random.PRNGKey(0), cfg)
    # de-degenerate the adaLN-zero init so samples actually move (see
    # test_serve_continuous): a zero velocity field would make every
    # bit-identity and quality-delta assertion below vacuous
    k = jax.random.PRNGKey(99)
    for i, blk in enumerate(params["blocks"]):
        blk["adaln"] = 0.05 * jax.random.normal(jax.random.fold_in(k, i),
                                                blk["adaln"].shape)
    params["final_out"] = 0.05 * jax.random.normal(
        jax.random.fold_in(k, 10_000), params["final_out"].shape)
    return cfg, params


def _dice_int8():
    from repro.compress.codecs import CompressConfig
    return DiceConfig.dice(compress=CompressConfig(codec="int8_residual"))


def _serve(cfg, params, dcfg, *, resilience=None, obs=False, nreq=3,
           max_batch=2, num_steps=4, arrivals=None):
    server = DiceServer(cfg, dataclasses.replace(dcfg, resilience=resilience),
                        params=params, obs=ObsConfig(enabled=obs))
    reqs = [Request(class_id=i % cfg.num_classes, rid=i)
            for i in range(nreq)]
    return serve_continuous(
        server, reqs, max_batch=max_batch, num_steps=num_steps,
        key=jax.random.PRNGKey(42),
        arrival_steps=arrivals if arrivals is not None else [0.0] * nreq)


@pytest.mark.parametrize("mk", [DiceConfig.sync_ep, DiceConfig.dice,
                                _dice_int8],
                         ids=["sync", "dice", "dice_int8"])
def test_guards_on_faults_off_bit_identical_end_to_end(mk, served):
    """The acceptance gate: a ResilienceConfig with guards armed but no
    faults configured must not move a single bit, and must not add jit
    entries beyond the plan-variant count."""
    cfg, params = served
    ref, ref_stats = _serve(cfg, params, mk())
    out, stats = _serve(cfg, params, mk(),
                        resilience=ResilienceConfig(guards=True))
    assert sorted(out) == sorted(ref)
    for rid in ref:
        np.testing.assert_array_equal(out[rid], ref[rid])
    assert stats["jit_cache_size"] == stats["num_plan_variants"]
    assert stats["jit_cache_size"] == ref_stats["jit_cache_size"]
    assert sum(stats["fault_events"].values()) == 0


def test_quarantine_requeues_and_replays_deterministically(served):
    """poison_tick corrupts a live slot past the wire guards; the engine
    must quarantine it (reset + requeue) rather than emit NaNs, the
    requeued request must still complete, and — because requeue noise is
    rid-keyed — the whole degraded run must replay bit for bit."""
    cfg, params = served
    res = ResilienceConfig(faults=FaultConfig(seed=11, poison_tick=2))
    out1, s1 = _serve(cfg, params, DiceConfig.dice(), resilience=res)
    assert s1["quarantined"] == 1
    assert s1["requeued"] == 1
    assert s1["shed"] == 0
    assert sorted(out1) == [0, 1, 2]                # nothing lost
    assert all(np.isfinite(v).all() for v in out1.values())
    out2, s2 = _serve(cfg, params, DiceConfig.dice(), resilience=res)
    for rid in out1:
        np.testing.assert_array_equal(out1[rid], out2[rid])
    assert s1["quarantined"] == s2["quarantined"]
    assert s1["requeued"] == s2["requeued"]


def test_overload_burst_sheds_bounded(served):
    """Satellite 6: an arrival flood against a bounded queue + admission
    deadline sheds explicitly (retry-after, newest-first) instead of
    queueing unboundedly; every request is served XOR shed."""
    cfg, params = served
    res = ResilienceConfig(max_queue_depth=2, admission_deadline_steps=2)
    arrivals = bursty_arrivals(8, rate=1.0, burst_size=8)   # all at t=0
    out, stats = _serve(cfg, params, DiceConfig.dice(), resilience=res,
                        nreq=8, max_batch=2, num_steps=4, arrivals=arrivals)
    served_rids, shed_rids = set(out), set(stats["shed_rids"])
    assert stats["shed"] > 0                        # flood actually shed
    assert not (served_rids & shed_rids)
    assert sorted(served_rids | shed_rids) == list(range(8))
    assert stats["queue_peak_depth"] >= 2
    # unbounded control: same flood, no resilience -> everything served
    out_all, _ = _serve(cfg, params, DiceConfig.dice(), nreq=8,
                        max_batch=2, num_steps=4, arrivals=arrivals)
    assert sorted(out_all) == list(range(8))


def test_codec_error_blowup_demotes_codec_at_variant_boundary(served):
    """Rung 3 end-to-end, walltime-free: with a near-zero codec error
    limit every quantized (light) step is a blowup, so the controller
    demotes codec -> none at a plan boundary; the run completes on the
    lossless wire with the demotion recorded.  demote_after=1 because
    DICE's heavy steps re-anchor the base losslessly (codec_err 0), so
    blowups alternate and can never be consecutive — the consecutive
    counting itself is covered by the controller unit test above."""
    cfg, params = served
    res = ResilienceConfig(codec_error_limit=1e-12, demote_after=1)
    out, stats = _serve(cfg, params, _dice_int8(), resilience=res, obs=True,
                        nreq=3, num_steps=6)
    assert "codec" in stats["demotions"]
    assert sorted(out) == [0, 1, 2]
    assert all(np.isfinite(v).all() for v in out.values())
    assert stats["jit_cache_size"] == stats["num_plan_variants"]


# ---------------------------------------------------------------------------
# 8-device chaos case: every schedule under a multi-fault storm
# ---------------------------------------------------------------------------
def test_chaos_eight_device_all_schedules():
    """Subprocess (the parent must keep the single real CPU device): all
    five schedules on an 8-way ep mesh under seeded corruption + slot
    poisoning complete every request with zero crashes, finite samples,
    visible degradation events, and hop-delay injection active while the
    ring engine is live."""
    prog = textwrap.dedent("""
        import dataclasses
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.configs.dit_moe_xl import tiny
        from repro.core.schedules import DiceConfig
        from repro.launch.mesh import make_ep_mesh
        from repro.launch.serve import DiceServer, Request, serve_continuous
        from repro.models.dit_moe import init_dit
        from repro.resilience import parse_resilience

        cfg = tiny().replace(num_layers=4, d_model=64, moe_d_ff=64,
                             d_ff=256, patch_tokens=16, capacity_factor=8.0)
        params = init_dit(jax.random.PRNGKey(0), cfg)
        mesh = make_ep_mesh(8)
        res = parse_resilience(
            "seed=7,corrupt=0.08,corrupt_dispatch=0.05,poison_tick=3,"
            "queue=16,admit_deadline=64,requeues=2")
        scheds = {
            "sync": DiceConfig.sync_ep(),
            "interweaved": DiceConfig.interweaved(),
            "displaced": DiceConfig.displaced(),
            "dice": DiceConfig.dice(),
            "staggered_batch": DiceConfig.staggered_batch(),
        }
        reqs = [Request(class_id=i % cfg.num_classes, rid=i)
                for i in range(10)]
        for name, dcfg in scheds.items():
            server = DiceServer(cfg,
                                dataclasses.replace(dcfg, resilience=res),
                                params=params, mesh=mesh)
            out, stats = serve_continuous(
                server, reqs, max_batch=8, num_steps=4,
                key=jax.random.PRNGKey(7),
                arrival_steps=[0.0] * 8 + [1.0, 1.0])
            served, shed = set(out), set(stats["shed_rids"])
            assert not (served & shed), (name, served & shed)
            assert sorted(served | shed) == list(range(10)), (
                name, sorted(served), sorted(shed))
            assert all(np.isfinite(v).all() for v in out.values()), name
            assert sum(stats["fault_events"].values()) > 0, (
                name, stats["fault_events"])
            assert stats["quarantined"] >= 1, (name, stats)
            assert stats["jit_cache_size"] == stats["num_plan_variants"], (
                name, stats)
            print(f"SCHED-OK {name} served={len(served)} "
                  f"shed={len(shed)} "
                  f"events={sum(stats['fault_events'].values()):.0f}")

        # ring engine + seeded slow hops: injection is live only while the
        # ring is (demotion would stop it) and the run still completes
        ring = parse_resilience("seed=3,corrupt=0.05,hop_delay=0.5:0.001")
        server = DiceServer(cfg, dataclasses.replace(
            DiceConfig.dice(overlap="ring"), resilience=ring),
            params=params, mesh=mesh)
        out, stats = serve_continuous(
            server, reqs[:8], max_batch=8, num_steps=4,
            key=jax.random.PRNGKey(7), arrival_steps=[0.0] * 8)
        assert sorted(out) == list(range(8)), sorted(out)
        assert stats["injected_hop_delays"] > 0, stats
        print("CHAOS-TEST-OK")
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=dict(os.environ, PYTHONPATH="src"),
                       cwd=repo, timeout=1200)
    assert "CHAOS-TEST-OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
    assert r.stdout.count("SCHED-OK") == 5, r.stdout[-2000:]
