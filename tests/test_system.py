"""End-to-end behaviour tests for the paper's system.

Integration: train a tiny DiT-MoE for a handful of steps (loss must drop),
then sample under every parallelism schedule and check the structural
claims that do not need a converged model:
  * all schedules produce finite samples,
  * displaced carries 2x interweaved's persistent buffers,
  * DICE's light steps move fewer all-to-all bytes,
  * staleness causes output divergence from the synchronous reference,
    and 1-step staleness diverges LESS than 2-step (the paper's core
    quality ordering), measured on the same seed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dit_moe_xl import tiny
from repro.core.schedules import DiceConfig
from repro.data.synthetic import latent_batches
from repro.metrics.fid_proxy import mse_vs_reference
from repro.optim.adamw import adamw_init
from repro.sampling.rectified_flow import rf_sample, rf_train_step


@pytest.fixture(scope="module")
def trained():
    cfg = tiny().replace(num_layers=4, d_model=64, moe_d_ff=64, d_ff=256,
                         patch_tokens=16)
    params = jax.tree.map(
        lambda a: a, __import__("repro.models.dit_moe",
                                fromlist=["init_dit"]).init_dit(
            jax.random.PRNGKey(0), cfg))
    opt = adamw_init(params)
    it = latent_batches(batch=16, tokens=cfg.patch_tokens,
                        channels=cfg.in_channels,
                        num_classes=cfg.num_classes, seed=0)
    losses = []
    key = jax.random.PRNGKey(1)
    for i in range(30):
        key, k = jax.random.split(key)
        params, opt, m = rf_train_step(params, opt, next(it), k, cfg)
        losses.append(float(m["loss"]))
    return cfg, params, losses


def test_training_reduces_loss(trained):
    _, _, losses = trained
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.9, losses


def _sample(cfg, params, dcfg, *, steps=8, ndev=0):
    classes = jnp.arange(8) % cfg.num_classes
    return rf_sample(params, cfg, dcfg, num_steps=steps, classes=classes,
                     key=jax.random.PRNGKey(7), guidance=1.5,
                     patch_parallel_ndev=ndev)


def test_all_schedules_produce_finite_samples(trained):
    cfg, params, _ = trained
    for dcfg, ndev in [(DiceConfig.sync_ep(), 0),
                       (DiceConfig.displaced(), 0),
                       (DiceConfig.interweaved(), 0),
                       (DiceConfig.dice(), 0),
                       (DiceConfig.sync_ep(), 4)]:       # DistriFusion
        s, _ = _sample(cfg, params, dcfg, ndev=ndev)
        assert np.isfinite(np.asarray(s)).all()


def test_staleness_quality_ordering(trained):
    """MSE vs sync: interweaved (1-step) < displaced (2-step)."""
    cfg, params, _ = trained
    ref, _ = _sample(cfg, params, DiceConfig.sync_ep())
    inter, _ = _sample(cfg, params, DiceConfig.interweaved())
    disp, _ = _sample(cfg, params, DiceConfig.displaced())
    m_i = mse_vs_reference(inter, ref)
    m_d = mse_vs_reference(disp, ref)
    assert m_i > 0 and m_d > 0          # staleness does perturb outputs
    assert m_i < m_d, f"1-step staleness ({m_i}) must beat 2-step ({m_d})"


def test_dice_buffers_and_volume(trained):
    cfg, params, _ = trained
    _, stats_i = _sample(cfg, params, DiceConfig.interweaved())
    _, stats_d = _sample(cfg, params, DiceConfig.displaced())
    assert stats_d["buffer_bytes"][-1] == 2 * stats_i["buffer_bytes"][-1]
    _, stats_dice = _sample(cfg, params, DiceConfig.dice())
    # light steps (odd) move fewer bytes than refresh steps (even)
    disp = stats_dice["dispatch_bytes"]
    w = DiceConfig.dice().warmup_steps
    assert disp[w + 1] < disp[w]


def test_selective_sync_improves_quality(trained):
    """Deep-sync DICE must be at least as close to sync as plain
    interweaved (it synchronizes half the layers)."""
    cfg, params, _ = trained
    ref, _ = _sample(cfg, params, DiceConfig.sync_ep())
    inter, _ = _sample(cfg, params, DiceConfig.interweaved())
    deep, _ = _sample(cfg, params,
                      DiceConfig(schedule=DiceConfig.dice().schedule,
                                 sync_policy="deep", cond_comm=False))
    assert mse_vs_reference(deep, ref) <= mse_vs_reference(inter, ref) * 1.05


def test_serve_queue_drains_requests(trained):
    """Continuous-batching loop: every request served once, padding trimmed,
    fixed compiled batch size."""
    cfg, params, _ = trained
    from repro.launch.serve import DiceServer, Request, serve_queue
    from repro.core.schedules import DiceConfig
    server = DiceServer(cfg, DiceConfig.dice(), params=params)
    reqs = [Request(class_id=i % cfg.num_classes, rid=100 + i)
            for i in range(11)]                     # not a multiple of 4
    out, stats = serve_queue(server, reqs, max_batch=4, num_steps=4)
    assert sorted(out) == [100 + i for i in range(11)]
    assert stats["batches"] == 3 and stats["padded"] == 1
    for s in out.values():
        assert np.isfinite(np.asarray(s)).all()
    # byte/compile stats survive aggregation (consumed by the throughput
    # benchmark): sums for byte flows, max for sizes and the jit cache
    assert stats["buffer_bytes"] > 0
    assert stats["dispatch_bytes_total"] > 0
    assert stats["raw_bytes_total"] == stats["dispatch_bytes_total"]
    assert stats["jit_cache_size"] == stats["num_plan_variants"] > 0
