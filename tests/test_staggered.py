"""Staggered-batch alternative (paper supplement Sec. 8).

The paper analysed and REJECTED this scheme; we reproduce its three
rejection reasons quantitatively:
  1. same 1-step staleness as interweaved (no quality advantage),
  2. persistent dispatch AND combine buffers (2x interweaved's memory),
  3. halved effective GEMM batch (lower utilization -> slower modeled step).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig
from repro.core.moe import moe_forward, moe_init
from repro.core.schedules import DiceConfig, Schedule
from repro.core.staleness import MoELayerState, moe_step

CFG = ModelConfig(name="t", family="moe", num_layers=4, d_model=32, d_ff=64,
                  vocab_size=64, num_heads=4, num_kv_heads=4, num_experts=4,
                  experts_per_token=2, moe_d_ff=48, capacity_factor=8.0)


def _run(p, xs, dcfg):
    state = MoELayerState()
    outs = []
    for s, x in enumerate(xs):
        y, state, _ = moe_step(p, x, CFG, dcfg, state, moe_layer_idx=0,
                               num_moe_layers=4, step_idx=s)
        outs.append(y)
    return outs, state


def test_staggered_one_step_staleness():
    p = moe_init(jax.random.PRNGKey(0), CFG)
    xs = [jax.random.normal(jax.random.PRNGKey(10 + s), (16, 32), jnp.float32)
          for s in range(6)]
    dcfg = DiceConfig.staggered_batch()
    outs, _ = _run(p, xs, dcfg)
    for s in range(dcfg.warmup_steps, len(xs)):
        want = moe_forward(p, xs[s - 1], CFG)[0]       # 1-step stale
        np.testing.assert_allclose(np.asarray(outs[s]), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def test_staggered_double_buffers():
    p = moe_init(jax.random.PRNGKey(0), CFG)
    xs = [jax.random.normal(jax.random.PRNGKey(20 + s), (16, 32), jnp.float32)
          for s in range(4)]
    _, st_stag = _run(p, xs, DiceConfig.staggered_batch())
    _, st_int = _run(p, xs, DiceConfig.interweaved())
    assert st_stag.bytes() == 2 * st_int.bytes()
    assert Schedule.STAGGERED_BATCH.num_buffers == 2
    assert Schedule.STAGGERED_BATCH.step_staleness == 1


def test_staggered_modeled_latency_slower_than_interweaved():
    from repro.configs.dit_moe_xl import config
    from repro.metrics.latency_model import modeled_step_latency
    cfg = config()
    t_int = modeled_step_latency(cfg, DiceConfig.interweaved(),
                                 local_batch=8)["t_step_s"]
    t_stag = modeled_step_latency(cfg, DiceConfig.staggered_batch(),
                                  local_batch=8)["t_step_s"]
    assert t_stag >= t_int, (t_stag, t_int)
