"""Continuous-batching engine: slot recycling, warmup replay, state reset.

The load-bearing guarantee (ISSUE 2 acceptance): a request admitted into a
RECYCLED slot mid-flight produces a bit-identical sample to the same
request run in a fresh batch under the same keys — i.e. zero cross-request
state leakage — for the sync, interweaved and dice schedules; and the slot
machinery keeps the jit cache at exactly the plan-variant count.

Bit-identity holds because every MoE/attention path is batch-row
independent once nothing overflows capacity; the test config pins
``capacity_factor = num_experts`` so overflow is impossible by
construction.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dit_moe_xl import tiny
from repro.core import plan as plan_lib
from repro.core.schedules import DiceConfig
from repro.core.staleness import (MoELayerState, init_planned_states,
                                  reset_slots)
from repro.launch.serve import (DiceServer, Request, _admit_lanes,
                                request_noise, serve_continuous)
from repro.models.dit_moe import init_dit
from repro.sampling.rectified_flow import make_rf_step


@pytest.fixture(scope="module")
def setup():
    # capacity_factor == num_experts -> a dispatch drop is impossible even
    # if every pair routes to one expert, so per-slot rows are exactly
    # independent of their co-residents (see module docstring)
    cfg = tiny().replace(num_layers=4, d_model=64, moe_d_ff=64, d_ff=256,
                         patch_tokens=16, capacity_factor=8.0)
    params = init_dit(jax.random.PRNGKey(0), cfg)
    # de-degenerate the adaLN-zero init: with zero gates and a zero output
    # head the predicted velocity is identically 0 and every "sample"
    # equals its initial noise, which would make the bit-identity
    # assertions below vacuous
    k = jax.random.PRNGKey(99)
    for i, blk in enumerate(params["blocks"]):
        blk["adaln"] = 0.05 * jax.random.normal(jax.random.fold_in(k, i),
                                                blk["adaln"].shape)
    params["final_out"] = 0.05 * jax.random.normal(
        jax.random.fold_in(k, 10_000), params["final_out"].shape)
    return cfg, params


def _dice_int8():
    """DICE + int8 residual wire codec (DESIGN.md Sec. 11): the recycled-
    slot guarantees must also hold for the codec's per-slot residual base
    (c_base zeroed at admission, re-anchored lossless by the merge plan's
    store_base refresh) — a leaked base from a previous occupant would
    break bit-identity on the successor's compressed light steps."""
    from repro.compress.codecs import CompressConfig
    return DiceConfig.dice(compress=CompressConfig(codec="int8_residual"))


SCHEDS = {
    "sync": DiceConfig.sync_ep,
    "interweaved": DiceConfig.interweaved,
    "dice": DiceConfig.dice,
    "dice_int8": _dice_int8,
}


def _fresh_batch(params, cfg, dcfg, requests, *, num_steps, key,
                 guidance=1.5):
    """Reference: the whole-loop fixed-batch sampler (never slotted), with
    the engine's per-request noise derivation."""
    noise_key, step_key = jax.random.split(key)
    B = len(requests)
    x = jnp.stack([request_noise(noise_key, r.rid, cfg) for r in requests])
    classes = jnp.asarray([r.class_id for r in requests], jnp.int32)
    dt = 1.0 / num_steps
    splan = plan_lib.compile_step_plans(dcfg, cfg.num_layers, num_steps,
                                        experts_per_token=cfg.experts_per_token)
    init = lambda: init_planned_states(
        splan, num_tokens=B * cfg.patch_tokens, d_model=cfg.d_model,
        k=cfg.experts_per_token, dtype=jnp.float32)
    states, states_u = init(), init()
    step = make_rf_step(params, cfg, dcfg, dt=dt, guidance=guidance)
    for s in range(num_steps):
        t = jnp.full((B,), s * dt)
        x, states, states_u, _, _, _ = step(
            x, classes, states, states_u, {}, {}, t,
            jax.random.fold_in(step_key, s), plan=splan.steps[s])
    return {r.rid: np.asarray(x[i]) for i, r in enumerate(requests)}


@pytest.mark.parametrize("name", list(SCHEDS))
def test_recycled_slot_bit_identical(name, setup):
    """rid=2 arrives late, is admitted into the slot rid=0 or rid=1 just
    vacated, and must match its fresh-batch sample bit for bit."""
    cfg, params = setup
    dcfg = SCHEDS[name]()
    server = DiceServer(cfg, dcfg, params=params)
    reqs = [Request(class_id=1, rid=0), Request(class_id=2, rid=1),
            Request(class_id=3, rid=2)]
    key = jax.random.PRNGKey(42)
    out, stats = serve_continuous(server, reqs, max_batch=2, num_steps=4,
                                  key=key, arrival_steps=[0.0, 0.0, 1.0])
    assert sorted(out) == [0, 1, 2]
    assert stats["recycled_admissions"] >= 1
    # guard against a degenerate model: the sampler must actually have
    # moved the latents away from the initial noise
    noise_key, _ = jax.random.split(key)
    assert not np.array_equal(
        out[2], np.asarray(request_noise(noise_key, 2, cfg)))

    # the recycled request, re-run in a fresh batch (co-resident differs —
    # leakage from the previous occupant would break bit-identity)
    ref = _fresh_batch(params, cfg, dcfg,
                       [reqs[2], Request(class_id=5, rid=7)],
                       num_steps=4, key=key)
    np.testing.assert_array_equal(out[2], ref[2])

    # first-wave requests replayed warmup under the slotted path; they too
    # must match the plain fixed-batch sampler
    ref01 = _fresh_batch(params, cfg, dcfg, reqs[:2], num_steps=4, key=key)
    np.testing.assert_array_equal(out[0], ref01[0])
    np.testing.assert_array_equal(out[1], ref01[1])


def test_mid_flight_admission_fills_free_slot(setup):
    """A request arriving mid-flight joins a FREE slot at the next aligned
    boundary instead of waiting for the whole batch to drain."""
    cfg, params = setup
    dcfg = DiceConfig.dice()
    server = DiceServer(cfg, dcfg, params=params)
    reqs = [Request(class_id=1, rid=10), Request(class_id=2, rid=11)]
    out, stats = serve_continuous(server, reqs, max_batch=2, num_steps=4,
                                  key=jax.random.PRNGKey(1),
                                  arrival_steps=[0.0, 1.0])
    assert sorted(out) == [10, 11]
    # rid=11 admitted at tick 2 (aligned), overlapping rid=10's flight:
    # the whole run takes 6 ticks, not 2 x 4
    assert stats["makespan_steps"] == 6
    assert stats["padded_slot_steps"] == 4      # ticks 0-1 + ticks 4-5
    ref = _fresh_batch(params, cfg, dcfg,
                       [reqs[1], Request(class_id=6, rid=9)],
                       num_steps=4, key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(out[11], ref[11])


def test_jit_cache_stays_at_plan_variant_count(setup):
    """Slot recycling must not add compiled step variants: warmup mixtures
    ride the traced per-slot masks, not new static shapes."""
    cfg, params = setup
    for name, mk in SCHEDS.items():
        dcfg = mk()
        server = DiceServer(cfg, dcfg, params=params)
        reqs = [Request(class_id=i % cfg.num_classes, rid=i)
                for i in range(5)]
        out, stats = serve_continuous(
            server, reqs, max_batch=2, num_steps=4,
            key=jax.random.PRNGKey(3),
            arrival_steps=[0.0, 0.0, 1.0, 3.0, 5.0])
        assert sorted(out) == list(range(5)), name
        assert stats["jit_cache_size"] == stats["num_plan_variants"], name
        assert stats["recycled_admissions"] >= 1, name


def test_reset_slots_zeroes_only_recycled_rows():
    st = {0: MoELayerState(y_buf=jnp.ones((8, 3)),
                           x_prev=jnp.full((8, 3), 2.0),
                           h_cache=jnp.full((8, 2, 3), 3.0)),
          1: MoELayerState(y_buf=jnp.ones((8, 3)))}
    new = reset_slots(st, jnp.asarray([True, False]), tokens_per_slot=4)
    for i in (0, 1):
        np.testing.assert_array_equal(np.asarray(new[i].y_buf[:4]), 0.0)
        np.testing.assert_array_equal(np.asarray(new[i].y_buf[4:]), 1.0)
    np.testing.assert_array_equal(np.asarray(new[0].x_prev[:4]), 0.0)
    np.testing.assert_array_equal(np.asarray(new[0].x_prev[4:]), 2.0)
    np.testing.assert_array_equal(np.asarray(new[0].h_cache[:4]), 0.0)
    np.testing.assert_array_equal(np.asarray(new[0].h_cache[4:]), 3.0)
    assert new[1].x_prev is None and new[1].h_cache is None


@pytest.mark.parametrize("mask", [[True, False, False, True],
                                  [True] * 4, [False] * 4],
                         ids=["partial", "full", "empty"])
def test_admit_lanes_matches_the_eager_admission(mask):
    """The admission program gives, bit for bit, what the eager surgery
    gives: each recycled lane's request_noise written into x, and
    reset_slots on both state sets.  The rids of other lanes are
    ignored, and the inputs are donated (so copies go in)."""
    cfg = tiny().replace(patch_tokens=8)
    B, T, C = len(mask), cfg.patch_tokens, cfg.in_channels
    ks = iter(jax.random.split(jax.random.PRNGKey(7), 16))

    def leaf(*shape):
        return jax.random.normal(next(ks), shape)

    def state_set():
        return {0: MoELayerState(y_buf=leaf(B * T, 6), x_prev=leaf(B * T, 6),
                                 h_cache=leaf(B * T, 2, 6),
                                 c_base=leaf(B * T, 6)),
                1: MoELayerState(y_buf=leaf(B * T, 6),
                                 h_cache=leaf(B * T, 2, 6))}

    noise_key = next(ks)
    x, states, states_u = leaf(B, T, C), state_set(), state_set()
    recycle = np.asarray(mask)
    rids = np.asarray([11, 3, 2 ** 31 + 5, 7], np.uint32)
    want_x = x
    for i in np.flatnonzero(recycle):
        want_x = want_x.at[i].set(request_noise(noise_key, int(rids[i]),
                                                cfg))
    want = [reset_slots(s, jnp.asarray(recycle), tokens_per_slot=T)
            for s in (states, states_u)]
    args = jax.tree.map(jnp.copy, (x, states, states_u))
    got_x, *got = _admit_lanes(*args, recycle, rids, noise_key)
    assert all(a.is_deleted() for a in jax.tree.leaves(args))
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(want_x))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_paper_comm_fraction_band():
    """After the attention-flops fix (QKV+O = 8*T*d^2, QK^T+AV = 4*T^2*d)
    the Table-5-calibrated all-to-all share must still land in the paper's
    60-80% band on the 4090-PCIe hardware point."""
    from repro.configs.dit_moe_xl import config as xl_config
    from repro.metrics.latency_model import PAPER_HW, layer_compute_flops
    cfg = xl_config()
    for n_dev in (4, 8):
        for b in (4, 8, 16, 32):
            tokens = b * cfg.patch_tokens
            t_comp = layer_compute_flops(cfg, tokens) / PAPER_HW["flops"]
            cap = tokens * cfg.experts_per_token * cfg.capacity_factor
            a2a = 2 * cap * cfg.d_model * 2 * (n_dev - 1) / n_dev
            t_comm = a2a / PAPER_HW["link_bw"]
            frac = t_comm / (t_comm + t_comp)
            assert 0.6 <= frac <= 0.8, (n_dev, b, frac)


def test_sharded_recycled_slots_match_fresh_sharded_batch():
    """Mesh-native continuous batching (DESIGN.md §10): requests admitted
    into RECYCLED slots of an 8-way-ep mesh server's ``serve_continuous``
    run
    must be bit-identical to the same requests in a fresh sharded batch —
    no cross-request leakage through the sharded ``h_cache``/``y_buf``
    rows — and the jit cache must stay at the plan-variant count.
    Subprocess-based: the parent process must keep the single real CPU
    device."""
    import os
    import subprocess
    import sys
    import textwrap
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        from functools import partial
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.dit_moe_xl import tiny
        from repro.core import plan as plan_lib
        from repro.core.schedules import DiceConfig
        from repro.core.staleness import init_planned_states
        from repro.launch.mesh import make_ep_mesh
        from repro.launch.serve import (DiceServer, Request, request_noise,
                                        serve_continuous)
        from repro.models.dit_moe import init_dit
        from repro.sampling.rectified_flow import make_rf_step

        cfg = tiny().replace(num_layers=4, d_model=64, moe_d_ff=64,
                             d_ff=256, patch_tokens=16, capacity_factor=8.0)
        params = init_dit(jax.random.PRNGKey(0), cfg)
        k = jax.random.PRNGKey(99)
        for i, blk in enumerate(params["blocks"]):
            blk["adaln"] = 0.05 * jax.random.normal(
                jax.random.fold_in(k, i), blk["adaln"].shape)
        params["final_out"] = 0.05 * jax.random.normal(
            jax.random.fold_in(k, 10_000), params["final_out"].shape)
        mesh = make_ep_mesh(8)
        dcfg = DiceConfig.dice()
        NUM_STEPS = 4

        def fresh_mesh_batch(requests, key):
            noise_key, step_key = jax.random.split(key)
            B = len(requests)
            sh = NamedSharding(mesh, P("ep"))
            x = jax.device_put(jnp.stack(
                [request_noise(noise_key, r.rid, cfg) for r in requests]), sh)
            classes = jax.device_put(jnp.asarray(
                [r.class_id for r in requests], jnp.int32), sh)
            splan = plan_lib.compile_step_plans(
                dcfg, cfg.num_layers, NUM_STEPS,
                experts_per_token=cfg.experts_per_token)
            init = partial(init_planned_states, splan,
                           num_tokens=B * cfg.patch_tokens,
                           d_model=cfg.d_model, k=cfg.experts_per_token,
                           dtype=jnp.float32, mesh=mesh)
            states, states_u = init(), init()
            step = make_rf_step(params, cfg, dcfg, dt=1.0 / NUM_STEPS,
                                guidance=1.5, mesh=mesh)
            for s in range(NUM_STEPS):
                t = jnp.full((B,), s / NUM_STEPS)
                x, states, states_u, _, _, _ = step(
                    x, classes, states, states_u, {}, {}, t,
                    jax.random.fold_in(step_key, s), plan=splan.steps[s])
            return {r.rid: np.asarray(x[i]) for i, r in enumerate(requests)}

        server = DiceServer(cfg, dcfg, params=params, mesh=mesh)
        reqs = [Request(class_id=i % cfg.num_classes, rid=i)
                for i in range(10)]
        key = jax.random.PRNGKey(42)
        out, stats = serve_continuous(
            server, reqs, max_batch=8, num_steps=NUM_STEPS, key=key,
            arrival_steps=[0.0] * 8 + [1.0, 1.0])
        assert sorted(out) == list(range(10))
        assert stats["recycled_admissions"] >= 2, stats
        assert stats["jit_cache_size"] == stats["num_plan_variants"], stats

        # recycled requests (rid 8, 9) vs a fresh sharded batch with
        # DIFFERENT co-residents: leakage from the previous occupants of
        # their slots would break bit-identity
        ref = fresh_mesh_batch(
            [reqs[8], reqs[9]] + [Request(class_id=(i * 3) % cfg.num_classes,
                                          rid=100 + i) for i in range(6)],
            key)
        np.testing.assert_array_equal(out[8], ref[8])
        np.testing.assert_array_equal(out[9], ref[9])
        # first-wave requests went through slotted warmup ticks; they too
        # must match the plain mesh-sharded fixed-batch sampler
        ref0 = fresh_mesh_batch(reqs[:8], key)
        np.testing.assert_array_equal(out[0], ref0[0])
        print("EPSERVE-OK")
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=dict(os.environ, PYTHONPATH="src"),
                       cwd=repo, timeout=1200)
    assert "EPSERVE-OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])


def test_steady_period_and_merge_plan():
    assert plan_lib.steady_period(DiceConfig.dice(), 4,
                                  experts_per_token=2) == 2
    assert plan_lib.steady_period(DiceConfig.dice(cond_stride=4), 4,
                                  experts_per_token=2) == 4
    for mk in (DiceConfig.sync_ep, DiceConfig.interweaved,
               DiceConfig.displaced, DiceConfig.staggered_batch):
        assert plan_lib.steady_period(mk(), 4, experts_per_token=2) == 1
    # the merge plan IS the refresh variant: full dispatch, no mask
    merge = plan_lib.slotted_merge_plan(DiceConfig.dice(), 4,
                                        experts_per_token=2)
    splan = plan_lib.compile_step_plans(DiceConfig.dice(), 4, 8,
                                        experts_per_token=2)
    assert merge in splan.variants
    assert all(a.mask_policy is None for a in merge.actions)


def _three_cohorts(cfg):
    return [Request(class_id=(3 * i + 1) % cfg.num_classes, rid=i)
            for i in range(6)]


def test_one_tick_in_flight_over_three_cohorts(setup):
    """The default server keeps one tick in flight: a tick is launched
    while the previous one is still unread, except a variant's first
    (compiling) tick, which runs at depth 0, and the tick after it; a
    pipelined launch comes before the previous tick's read-back, and
    every sample is bit-identical to the same cohort in a fresh fixed
    batch."""
    from repro.obs import StepTracer
    cfg, params = setup
    dcfg = DiceConfig.dice()
    server = DiceServer(cfg, dcfg, params=params)
    server.tracer = StepTracer()
    reqs = _three_cohorts(cfg)
    key = jax.random.PRNGKey(11)
    out, stats = serve_continuous(server, reqs, max_batch=2, num_steps=4,
                                  key=key)
    assert stats["ticks"] == 12                     # 3 cohorts x 4 steps
    # ticks 0, 2, 3 first run the slotted, refresh and light variants,
    # ticks 0, 1, 2, 3, 4 find no tick in flight
    assert stats["pipelined_ticks"] == stats["ticks"] - 5
    lab = {"schedule": "dice", "engine": "continuous"}
    assert server.metrics.value("dice_pipelined_ticks_total",
                                lab) == stats["ticks"] - 5
    assert len(stats["tick_s"]) + len(stats["compile_s"]) <= stats["ticks"]
    assert len(stats["compile_s"]) == 3

    ev = server.tracer.events
    ticks = {e["args"]["tick"]: e for e in ev if e["name"] == "tick"}
    assert sorted(ticks) == list(range(stats["ticks"]))
    launch = {e["args"]["tick"]: e["ts"] for e in ev
              if e["name"] == "serve.dispatch"}
    readback = {e["args"]["tick"]: e["ts"] for e in ev
                if e["name"] == "serve.readback"}
    assert sorted(launch) == sorted(readback) == sorted(ticks)
    pipelined = [t for t in sorted(ticks) if ticks[t]["args"]["pipelined"]]
    assert pipelined == list(range(4, stats["ticks"] - 1))
    for t in sorted(ticks)[:-1]:
        # tick t's span ends as tick t is seen ready, before its read-back
        assert ticks[t]["ts"] + ticks[t]["dur"] <= readback[t], t
        if t in pipelined:
            assert launch[t + 1] < readback[t], t
        else:
            assert launch[t + 1] > readback[t], t

    for c in range(3):
        cohort = reqs[2 * c:2 * c + 2]
        ref = _fresh_batch(params, cfg, dcfg, cohort, num_steps=4, key=key)
        for r in cohort:
            np.testing.assert_array_equal(out[r.rid], ref[r.rid])


def test_resilience_keeps_no_tick_in_flight(setup):
    """A policy that reads each tick before the next is decided (here the
    watchdog and quarantine) runs with no tick in flight, and serves the
    same samples."""
    from repro.resilience.faults import ResilienceConfig
    cfg, params = setup
    dcfg = DiceConfig.dice()
    reqs = _three_cohorts(cfg)
    key = jax.random.PRNGKey(11)
    server = DiceServer(cfg, dataclasses.replace(
        dcfg, resilience=ResilienceConfig(guards=True)), params=params)
    out, stats = serve_continuous(server, reqs, max_batch=2, num_steps=4,
                                  key=key)
    assert stats["pipelined_ticks"] == 0
    lab = {"schedule": "dice", "engine": "continuous"}
    assert server.metrics.get("dice_pipelined_ticks_total", lab) is None
    ref, _ = serve_continuous(DiceServer(cfg, dcfg, params=params), reqs,
                              max_batch=2, num_steps=4, key=key)
    assert sorted(out) == sorted(ref)
    for rid in ref:
        np.testing.assert_array_equal(out[rid], ref[rid])


def _ring():
    return DiceConfig.dice(overlap="ring")


def _placed():
    from repro.core.placement import Placement
    pl = Placement(perm=(1, 0, 2, 3, 4, 5, 6, 7), replicated=(0,),
                   cap_scale=0.5)
    return dataclasses.replace(DiceConfig.dice(), placements=(pl,) * 4)


def _paged():
    from repro.core.paging import PagingSpec
    return dataclasses.replace(DiceConfig.dice(),
                               paging=PagingSpec(budget_bytes=None))


def _codec_none():
    from repro.compress.codecs import CompressConfig
    return DiceConfig.dice(compress=CompressConfig(codec="none"))


def _resilience_off():
    from repro.resilience.faults import ResilienceConfig
    return dataclasses.replace(DiceConfig.dice(), resilience=ResilienceConfig(
        guards=False, quarantine=False))


@pytest.fixture(scope="module")
def plain_dice_run(setup):
    cfg, params = setup
    return serve_continuous(DiceServer(cfg, DiceConfig.dice(), params=params),
                            _three_cohorts(cfg), max_batch=2, num_steps=4,
                            key=jax.random.PRNGKey(5))


@pytest.mark.parametrize("make", [_ring, _placed, _paged, _codec_none,
                                  _resilience_off])
def test_meshless_server_resolves_its_config_once(make, setup,
                                                  plain_dice_run):
    """A mesh-less server stores its config resolved against a 1-way ep
    axis (``plan.resolve``), and ``serve_continuous`` runs it as stored:
    ring overlap, placements, paging, codec "none" and inert resilience
    all serve bit-identically to a plain ``DiceConfig.dice()`` server,
    with one jit entry per plan variant."""
    cfg, params = setup
    raw = make()
    server = DiceServer(cfg, raw, params=params)
    assert server.dcfg == plan_lib.resolve(raw, 1) == DiceConfig.dice()
    out, stats = serve_continuous(server, _three_cohorts(cfg), max_batch=2,
                                  num_steps=4, key=jax.random.PRNGKey(5))
    ref, ref_stats = plain_dice_run
    assert stats["jit_cache_size"] == stats["num_plan_variants"] \
        == ref_stats["num_plan_variants"]
    assert sorted(out) == sorted(ref)
    for rid in ref:
        np.testing.assert_array_equal(out[rid], ref[rid])


def test_placement_and_paging_keep_no_tick_in_flight():
    """On an 8-way ep mesh the default server keeps one tick in flight;
    online placement (re-shards from each tick's routing histogram) and
    paging (fetches ride inside the step) keep none, and online placement
    serves the same samples.  Subprocess-based: the parent process must
    keep the single real CPU device."""
    import os
    import subprocess
    import sys
    import textwrap
    prog = textwrap.dedent("""
        import dataclasses
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import numpy as np
        from repro.configs.dit_moe_xl import tiny
        from repro.core.paging import PagingSpec
        from repro.core.placement import PlacementConfig
        from repro.core.schedules import DiceConfig
        from repro.launch.mesh import make_ep_mesh
        from repro.launch.serve import DiceServer, Request, serve_continuous
        from repro.models.dit_moe import init_dit

        cfg = tiny().replace(num_layers=4, d_model=64, moe_d_ff=64,
                             d_ff=256, patch_tokens=16, capacity_factor=8.0)
        params = init_dit(jax.random.PRNGKey(0), cfg)
        mesh = make_ep_mesh(8)
        reqs = [Request(class_id=(3 * i + 1) % cfg.num_classes, rid=i)
                for i in range(24)]
        key = jax.random.PRNGKey(11)

        def run(dcfg=DiceConfig.dice(), **kw):
            server = DiceServer(cfg, dcfg, params=params, mesh=mesh, **kw)
            return serve_continuous(server, reqs, max_batch=8, num_steps=4,
                                    key=key)

        out, stats = run()
        # each variant's first tick runs at depth 0 (see the tiny test)
        assert stats["pipelined_ticks"] == stats["ticks"] - 5 > 0, stats
        # greedy placement that never finds drift enough to re-shard
        placed, pstats = run(placement=PlacementConfig(
            mode="greedy", drift_threshold=1.0, warmup_ticks=1))
        assert pstats["pipelined_ticks"] == 0, pstats
        assert pstats["placement_reshards"] == 0, pstats
        assert sorted(placed) == sorted(out) == list(range(24))
        for rid in out:
            np.testing.assert_array_equal(placed[rid], out[rid])
        paged, gstats = run(dataclasses.replace(
            DiceConfig.dice(), paging=PagingSpec(budget_bytes=None, depth=1)))
        assert gstats["pipelined_ticks"] == 0, gstats
        assert gstats["paged_transfers"] > 0, gstats
        assert sorted(paged) == list(range(24))
        assert all(np.isfinite(x).all() for x in paged.values())
        print("DEPTH-OK")
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=dict(os.environ, PYTHONPATH="src"),
                       cwd=repo, timeout=1200)
    assert "DEPTH-OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
