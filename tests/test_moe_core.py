"""MoE substrate correctness: routing, dispatch/combine, capacity, EP."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st

from repro.common.config import ModelConfig
from repro.core.moe import (combine, default_capacity, dispatch, expert_ffn,
                            make_plan, moe_forward, moe_init, route)


def _cfg(**kw):
    base = dict(name="t", family="moe", num_layers=2, d_model=64, d_ff=128,
                vocab_size=64, num_heads=4, num_kv_heads=2, num_experts=4,
                experts_per_token=2, moe_d_ff=96)
    base.update(kw)
    return ModelConfig(**base)


def test_dispatch_combine_roundtrip_identity():
    """With an identity 'expert' and ample capacity, every (t, r) pair's
    value equals the token itself."""
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64), jnp.float32)
    _, scores, idx = route(p, x, cfg)
    plan = make_plan(idx, cfg.num_experts, 64)
    buf = dispatch(x, plan, cfg.num_experts, 64)
    _, pair_vals, pair_keep = combine(buf, plan, jnp.ones_like(scores), 32)
    assert bool(pair_keep.all())                          # ample capacity
    np.testing.assert_allclose(np.asarray(pair_vals),
                               np.asarray(x)[:, None, :].repeat(2, 1),
                               rtol=1e-6)


def test_moe_forward_matches_dense_oracle():
    """Capacity large enough -> output == explicit per-token loop."""
    cfg = _cfg(num_shared_experts=1)
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64), jnp.float32)
    y, aux = moe_forward(p, x, cfg, capacity=32)
    assert float(aux.dropped_frac) == 0.0
    _, scores, idx = route(p, x, cfg)

    def one_expert(e, xi):
        g = jax.nn.silu(xi @ p["experts_gate"][e].astype(jnp.float32))
        u = xi @ p["experts_up"][e].astype(jnp.float32)
        return (g * u) @ p["experts_down"][e].astype(jnp.float32)

    from repro.core.moe import shared_expert
    want = []
    for t in range(16):
        acc = sum(float(scores[t, r]) * one_expert(int(idx[t, r]), x[t])
                  for r in range(2))
        want.append(acc)
    want = jnp.stack(want) + shared_expert(p, x, act=cfg.act)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_capacity_drops_overflow():
    """Tokens beyond per-expert capacity are dropped, not mis-routed."""
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((64, 64), jnp.float32)     # identical tokens -> same expert
    y, aux = moe_forward(p, x, cfg, capacity=8)
    # 64 tokens x 2 ranks to <= 4 experts at capacity 8 -> most pairs dropped
    assert float(aux.dropped_frac) > 0.5
    assert jnp.isfinite(y).all()


@settings(max_examples=25, deadline=None)
@given(t=st.integers(4, 64), e=st.integers(2, 8), k=st.integers(1, 3),
       seed=st.integers(0, 2**31 - 1))
def test_plan_invariants(t, e, k, seed):
    """Property: every kept pair lands in the slot region of its expert and
    no slot is used twice."""
    k = min(k, e)
    idx = jax.random.randint(jax.random.PRNGKey(seed), (t, k), 0, e)
    cap = default_capacity(t, _cfg(num_experts=e, experts_per_token=k))
    plan = make_plan(idx, e, cap)
    slots = np.asarray(plan.slot)
    keep = np.asarray(plan.keep)
    used = slots[keep]
    assert len(set(used.tolist())) == len(used), "slot collision"
    # every kept pair's slot lies inside its expert's [e*cap, (e+1)*cap) region
    flat_e = np.asarray(idx).reshape(-1)
    order = np.asarray(jnp.argsort(jnp.asarray(flat_e), stable=True))
    e_sorted = flat_e[order][keep]
    assert (used // cap == e_sorted).all(), "pair landed in wrong expert"


def test_fresh_mask_reduces_dispatch():
    """Conditional communication: stale pairs never enter the buffer."""
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64), jnp.float32)
    _, scores, idx = route(p, x, cfg)
    mask = jnp.zeros((32, 2), bool).at[:, 0].set(True)   # top-1 only
    plan = make_plan(idx, cfg.num_experts, 64, fresh_mask=mask)
    assert int(plan.keep.sum()) == 32                    # one pair per token
    # cached values substitute for stale pairs
    cache = jnp.full((32, 2, 64), 7.0)
    buf = dispatch(x, plan, cfg.num_experts, 64)
    y, pair_vals, pair_keep = combine(buf, plan, scores, 32, h_cache=cache,
                                      fresh_mask=mask)
    np.testing.assert_allclose(np.asarray(pair_vals[:, 1]), 7.0)
    # masked-out pairs never entered dispatch, so they are not "kept"
    assert not bool(pair_keep[:, 1].any()) and bool(pair_keep[:, 0].all())


def test_expert_parallel_matches_single_device():
    """EP over 4 host devices == single-device MoE (ample capacity).
    Runs in a subprocess so XLA_FLAGS doesn't leak into this process."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.common.config import ModelConfig
        from repro.core.moe import moe_init, moe_forward
        cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=64,
                          d_ff=128, vocab_size=64, num_heads=4, num_kv_heads=2,
                          num_experts=4, experts_per_token=2,
                          num_shared_experts=1, moe_d_ff=96)
        p = moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)
        y_ref, _ = moe_forward(p, x, cfg, capacity=128)
        from repro.common.compat import make_mesh
        mesh = make_mesh((4,), ("model",))
        ps = jax.tree.map(lambda a: P(), p)
        for n in ("experts_gate", "experts_up", "experts_down"):
            ps[n] = P("model")
        f = lambda pl_, xl: moe_forward(pl_, xl, cfg, capacity=32,
                                        ep_axis="model")[0]
        from repro.common.compat import shard_map
        y = jax.jit(shard_map(f, mesh=mesh, in_specs=(ps, P("model")),
                              out_specs=P("model")))(p, x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 1e-3, err
        print("EP-OK", err)
    """)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                       "HOME": "/root"}, cwd="/root/repo")
    assert "EP-OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.parametrize("shared_d_ff", [None, 256])
def test_shared_d_ff_sets_the_shared_ffn_width(shared_d_ff):
    """``shared_d_ff`` is the shared experts' fused width in the weights
    and in both parameter counts; unset, the width stays
    ``num_shared_experts x expert_d_ff``."""
    cfg = _cfg(num_shared_experts=2, shared_d_ff=shared_d_ff)
    fs = 2 * 96 if shared_d_ff is None else shared_d_ff
    assert cfg.shared_width == fs
    p = jax.eval_shape(lambda k: moe_init(k, cfg), jax.random.PRNGKey(0))
    assert p["shared_gate"].shape == (64, fs)
    assert p["shared_up"].shape == (64, fs)
    assert p["shared_down"].shape == (fs, 64)
    base = _cfg(num_shared_experts=0)
    d, L = cfg.d_model, cfg.num_layers
    assert cfg.param_count() - base.param_count() == L * 3 * d * fs
    assert (cfg.active_param_count() - base.active_param_count()
            == L * 3 * d * fs)


@pytest.mark.parametrize("name,shared,total", [
    ("dit_moe_g", 2816, 16.5e9), ("dit_moe_xl", 2304, 4.17e9)])
def test_published_dit_moe_sizes(name, shared, total):
    """The published configs build the published models: the shared
    experts one gated FFN of width 2 x d_model (DiT-MoE's
    ``SparseMoeBlock``), DiT-MoE-G at 16.5 B parameters, XL at 4.17 B,
    counted over the weights ``init_dit`` makes (shapes only)."""
    import importlib
    from repro.models.dit_moe import init_dit
    cfg = importlib.import_module(f"repro.configs.{name}").config()
    assert cfg.shared_d_ff == shared == 2 * cfg.d_model
    tree = jax.eval_shape(lambda k: init_dit(k, cfg), jax.random.PRNGKey(0))
    assert tree["blocks"][0]["moe"]["shared_down"].shape == (shared,
                                                              cfg.d_model)
    n = sum(a.size for a in jax.tree.leaves(tree))
    assert abs(n - total) <= 0.01 * total, n


def test_smoke_configs_keep_the_default_shared_width():
    from repro.configs import dit_moe_g, dit_moe_xl
    for cfg in (dit_moe_g.smoke(), dit_moe_xl.smoke(), dit_moe_xl.tiny()):
        assert cfg.shared_d_ff is None
        assert cfg.shared_width == cfg.num_shared_experts * cfg.moe_d_ff
