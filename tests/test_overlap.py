"""Ring-overlap engine conformance (ISSUE 5): executed, not modeled.

Two layers of evidence that the chunked-ppermute ring of
``repro.core.overlap`` is a pure execution-shape change:

* **Subprocess 8-host-device mesh** — the ring engine matches the blocking
  all-to-all path within 1e-4 for ALL FIVE schedules, composed with the
  wire codecs (int8 residual on every staleness schedule); the jit cache
  stays == plan-variant count with overlap enabled; the wire-byte
  accounting (``aux.dispatch_bytes``) is unchanged; a hypothesis property
  drives random buffers through ring-vs-blocking exchange at the moe
  level; and ``launch.hlo_cost.check_ring_lowering`` proves the ring step
  lowers to exactly 2*(n-1) collective-permutes per MoE layer with NO
  residual all-to-all.

* **In-process single device** — ``overlap="ring"`` normalizes away
  (plans AND samples bit-identical to blocking), and the upgraded latency
  model obeys the per-hop pipeline bound: modeled ring-step < modeled
  blocking-step whenever t_comm > t_comp/(n-1).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.common import compat
    from repro.compress.codecs import CompressConfig
    from repro.configs.dit_moe_xl import tiny
    from repro.core import overlap as overlap_lib
    from repro.core import plan as plan_lib
    from repro.core import staleness as stale_lib
    from repro.core.schedules import DiceConfig, Schedule
    from repro.launch.hlo_cost import check_ring_lowering
    from repro.launch.mesh import make_ep_mesh
    from repro.models.dit_moe import init_dit
    from repro.sampling.rectified_flow import make_rf_step, rf_sample

    # capacity_factor == num_experts: drops impossible on the per-device
    # shard too, so ring and blocking runs drop exactly the same (zero)
    # pairs (same reasoning as the mesh-native conformance suite)
    cfg = tiny().replace(num_layers=2, d_model=64, moe_d_ff=64, d_ff=256,
                         num_heads=4, num_kv_heads=4, head_dim=16,
                         patch_tokens=16, capacity_factor=8.0)
    params = init_dit(jax.random.PRNGKey(0), cfg)
    k = jax.random.PRNGKey(99)
    for i, blk in enumerate(params["blocks"]):
        blk["adaln"] = 0.05 * jax.random.normal(
            jax.random.fold_in(k, i), blk["adaln"].shape)
    params["final_out"] = 0.05 * jax.random.normal(
        jax.random.fold_in(k, 10_000), params["final_out"].shape)
    classes = jnp.arange(8) % cfg.num_classes
    key = jax.random.PRNGKey(7)
    mesh = make_ep_mesh(8)
    N = 8
    NUM_STEPS = 5

    int8 = CompressConfig(codec="int8_residual")
    CASES = [
        ("sync", DiceConfig.sync_ep(), None),
        ("displaced", DiceConfig.displaced(), None),
        ("interweaved", DiceConfig.interweaved(), None),
        ("staggered_batch", DiceConfig.staggered_batch(), None),
        ("dice", DiceConfig.dice(sync_policy="deep"), None),
        # the codecs the planner attaches to staleness schedules: ring
        # must compose with residual-compressed wires (Sec. 11 x Sec. 12)
        ("displaced+int8", DiceConfig.displaced(compress=int8), None),
        ("interweaved+int8", DiceConfig.interweaved(compress=int8), None),
        ("dice+int8", DiceConfig.dice(sync_policy="deep",
                                      compress=int8), None),
    ]
    for name, dcfg, _ in CASES:
        ref, bstats = rf_sample(params, cfg, dcfg, num_steps=NUM_STEPS,
                                classes=classes, key=key, guidance=1.0,
                                mesh=mesh)
        ring_dcfg = dataclasses.replace(dcfg, overlap="ring")
        out, rstats = rf_sample(params, cfg, ring_dcfg,
                                num_steps=NUM_STEPS, classes=classes,
                                key=key, guidance=1.0, mesh=mesh)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < 1e-4, (name, err)
        # the ring must not change what goes on the wire, only how
        assert rstats["dispatch_bytes"] == bstats["dispatch_bytes"], name
        assert rstats["raw_bytes"] == bstats["raw_bytes"], name
        # one compiled entry per plan variant, overlap enabled
        splan = plan_lib.compile_step_plans(
            ring_dcfg, cfg.num_layers, NUM_STEPS,
            experts_per_token=cfg.experts_per_token)
        assert rstats["num_plan_variants"] == splan.num_variants, name
        assert rstats["jit_cache_size"] == splan.num_variants, (
            name, rstats["jit_cache_size"], splan.num_variants)
        # 2*(n-1) collective-permutes per layer, measured through aux —
        # staggered steady steps run TWO independent half-batch rings
        if name == "staggered_batch":
            w = dcfg.warmup_steps
            assert all(h == 2 * (N - 1) for h in rstats["hops"][:w])
            assert all(h == 4 * (N - 1) for h in rstats["hops"][w:]), \\
                rstats["hops"]
        else:
            assert all(h == 2 * (N - 1) for h in rstats["hops"]), \\
                rstats["hops"]
        assert all(h == 0 for h in bstats["hops"]), bstats["hops"]
        assert all(b > 0 for b in rstats["hop_bytes"])
        print("RINGPARITY", name, err, rstats["jit_cache_size"])

    # ---- hop chunks shrink with the payload: DICE light steps ----------
    rs = rstats  # dice+int8 ring stats from the loop above
    w = CASES[-1][1].warmup_steps
    assert rs["hop_bytes"][w + 1] < rs["hop_bytes"][w], rs["hop_bytes"]

    # ---- hypothesis property: ring == blocking exchange, random bufs ---
    # (falls back to a fixed seed sweep where the dev extra is absent;
    # CI installs hypothesis and runs the property proper)
    try:
        from hypothesis import given, settings, strategies as st
        HAVE_HYPOTHESIS = True
    except ImportError:
        HAVE_HYPOTHESIS = False

    def blocking_exchange(b, w_l):
        r = jax.lax.all_to_all(b, "ep", split_axis=0, concat_axis=0,
                               tiled=True)
        o = jnp.einsum("necd,edf->necf", r, w_l)
        return jax.lax.all_to_all(o, "ep", split_axis=0, concat_axis=0,
                                  tiled=True)

    def ring_exchange(b, w_l):
        return overlap_lib.ring_expert_exchange(
            b, lambda c: jnp.einsum("ecd,edf->ecf", c, w_l),
            ep_axis="ep", n=N)

    def sharded(f):
        def g(b, w_l):
            return f(b[0], w_l[0])[None]
        return jax.jit(compat.shard_map(g, mesh=mesh,
                                        in_specs=(P("ep"), P("ep")),
                                        out_specs=P("ep")))

    e_loc, C, d = 2, 8, 16
    run_block = sharded(blocking_exchange)
    run_ring = sharded(ring_exchange)

    def check_seed(seed):
        kk = jax.random.PRNGKey(seed)
        b = jax.random.normal(kk, (N, N, e_loc, C, d))
        w_l = jax.random.normal(jax.random.fold_in(kk, 1),
                                (N, e_loc, d, d))
        got = run_ring(b, w_l)
        want = run_block(b, w_l)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5, seed

    if HAVE_HYPOTHESIS:
        @settings(max_examples=8, deadline=None)
        @given(seed=st.integers(0, 2**31 - 1))
        def ring_matches_blocking(seed):
            check_seed(seed)
        ring_matches_blocking()
        print("PROPERTY-OK hypothesis")
    else:
        for seed in range(8):
            check_seed(seed)
        print("PROPERTY-OK seeds")

    # ---- HLO contract: 2*(n-1) collective-permutes, zero all-to-alls ---
    ring_dcfg = DiceConfig.dice(sync_policy="deep", overlap="ring")
    splan = plan_lib.compile_step_plans(
        ring_dcfg, cfg.num_layers, NUM_STEPS,
        experts_per_token=cfg.experts_per_token)
    rf_step = make_rf_step(params, cfg, ring_dcfg, dt=1.0 / NUM_STEPS,
                           guidance=1.0, mesh=mesh)
    states = stale_lib.init_planned_states(
        splan, num_tokens=8 * cfg.patch_tokens, d_model=cfg.d_model,
        k=cfg.experts_per_token, dtype=jnp.float32, mesh=mesh)
    x0 = jnp.zeros((8, cfg.patch_tokens, cfg.in_channels))
    t0 = jnp.zeros((8,))
    for v, plan in enumerate(splan.variants):
        txt = rf_step.lower(x0, classes, states, states, {}, {}, t0, key,
                            plan=plan,
                            slotted=False).compile().as_text()
        # guidance=1.0: one dit_forward -> num_layers MoE calls per step
        counts = check_ring_lowering(txt, n_dev=N,
                                     moe_layer_calls=cfg.num_layers)
        print("HLO", v, counts)
    print("OVERLAP-OK")
""")


def test_ring_overlap_distributed_conformance():
    r = subprocess.run([sys.executable, "-c", PROG], capture_output=True,
                       text=True,
                       env=dict(os.environ, PYTHONPATH="src"),
                       cwd=REPO, timeout=1800)
    assert "OVERLAP-OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
    for name in ("sync", "displaced", "interweaved", "staggered_batch",
                 "dice", "displaced+int8", "interweaved+int8", "dice+int8"):
        assert f"RINGPARITY {name}" in r.stdout, (name, r.stdout[-2000:])
    assert "PROPERTY-OK" in r.stdout, r.stdout[-2000:]
    assert "HLO 0" in r.stdout, r.stdout[-2000:]


# ---------------------------------------------------------------------------
# in-process: single-device normalization + the upgraded latency model
# ---------------------------------------------------------------------------
def test_ring_normalizes_away_on_single_device():
    """Mesh-less runs of a ring config are bit-identical to blocking and
    report zero hops — overlap is an n>1-mesh execution property."""
    import jax
    import jax.numpy as jnp
    from repro.configs.dit_moe_xl import tiny
    from repro.core.schedules import DiceConfig
    from repro.models.dit_moe import init_dit
    from repro.sampling.rectified_flow import rf_sample

    cfg = tiny().replace(num_layers=2, d_model=32, moe_d_ff=32, d_ff=64,
                         num_heads=2, num_kv_heads=2, head_dim=16,
                         patch_tokens=8)
    params = init_dit(jax.random.PRNGKey(0), cfg)
    classes = jnp.arange(4) % cfg.num_classes
    key = jax.random.PRNGKey(3)
    ring, rs = rf_sample(params, cfg, DiceConfig.dice(overlap="ring"),
                         num_steps=4, classes=classes, key=key,
                         guidance=1.0)
    block, bs = rf_sample(params, cfg, DiceConfig.dice(),
                          num_steps=4, classes=classes, key=key,
                          guidance=1.0)
    assert np.array_equal(np.asarray(ring), np.asarray(block))
    assert rs["hops"] == [0] * 4 and rs["hop_bytes"] == [0.0] * 4
    assert rs["jit_cache_size"] == rs["num_plan_variants"]


def test_normalize_overlap_and_plan_stamping():
    from repro.core import plan as plan_lib
    from repro.core.schedules import DiceConfig

    ring = DiceConfig.dice(overlap="ring")
    # stripped on one device, kept on many
    assert plan_lib.resolve(ring, 1) == DiceConfig.dice()
    assert plan_lib.resolve(ring, 8) is ring
    assert plan_lib.resolve(DiceConfig.dice(), 1) \
        == DiceConfig.dice()
    # the planner stamps every action; variant structure is unchanged
    for dcfg in (ring, DiceConfig.sync_ep(overlap="ring"),
                 DiceConfig.staggered_batch(overlap="ring")):
        splan = plan_lib.compile_step_plans(dcfg, 4, 6, experts_per_token=2)
        base = plan_lib.compile_step_plans(
            plan_lib.resolve(dcfg, 1), 4, 6, experts_per_token=2)
        assert splan.num_variants == base.num_variants
        assert all(a.overlap for p in splan.steps for a in p.actions)
        assert not any(a.overlap for p in base.steps for a in p.actions)
        assert splan.variant_of_step == base.variant_of_step


def test_dice_config_rejects_unknown_overlap():
    from repro.core.schedules import DiceConfig
    with pytest.raises(ValueError):
        DiceConfig(overlap="chunked")


def test_modeled_ring_latency_pipeline_bound():
    """The latency model's acceptance inequality (ISSUE 5): modeled ring
    step < modeled blocking step whenever t_comm > t_comp/(n-1), and the
    ring bound equals t_local + (n-1)*max(t_hop_comm, t_hop_comp)."""
    from repro.configs.dit_moe_xl import config as xl_config
    from repro.core.schedules import DiceConfig
    from repro.metrics.latency_model import modeled_step_latency

    cfg = xl_config()
    for n_dev in (2, 4, 8):
        for dcfg_ring, dcfg_block in (
                (DiceConfig.sync_ep(overlap="ring"), DiceConfig.sync_ep()),
                (DiceConfig.interweaved(overlap="ring"),
                 DiceConfig.interweaved()),
                (DiceConfig.dice(overlap="ring"), DiceConfig.dice())):
            ring = modeled_step_latency(cfg, dcfg_ring, local_batch=4,
                                        n_dev=n_dev)
            block = modeled_step_latency(cfg, dcfg_block, local_batch=4,
                                         n_dev=n_dev)
            # both bounds are reported regardless of the selected mode
            assert ring["t_step_s"] == ring["t_step_ring_s"]
            assert block["t_step_s"] == block["t_step_blocking_s"]
            assert ring["t_step_blocking_s"] == block["t_step_blocking_s"]
            t_comp, t_comm = ring["t_comp_layer"], ring["t_comm_layer"]
            if t_comm > t_comp / (n_dev - 1):
                assert ring["t_step_s"] < block["t_step_s"], (
                    n_dev, ring["t_step_s"], block["t_step_s"])
            assert 0.0 <= ring["overlap_efficiency"] <= 1.0
            assert block["overlap_efficiency"] == 0.0
    # closed form on one synthetic layer mix: sync EP, all layers sync
    n_dev = 8
    lat = modeled_step_latency(cfg, DiceConfig.sync_ep(overlap="ring"),
                               local_batch=4, n_dev=n_dev)
    t_comp, L = lat["t_comp_layer"], cfg.num_layers
    t_comm_full = lat["t_comm_layer"]  # sync: async volume == full volume
    want = L * (t_comp / n_dev
                + (n_dev - 1) * max(t_comm_full / (n_dev - 1),
                                    t_comp / n_dev))
    assert lat["t_step_ring_s"] == pytest.approx(want, rel=1e-12)
