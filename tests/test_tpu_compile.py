"""Compile the served path for a described TPU v5e chip, with no chip.

The TPU compiler is installed beside the CPU backend, so the Pallas
kernels and one DiT-MoE-XL step can be compiled for a ``v5e:2x2``
topology that is described, not attached: whatever the chip's compiler
refuses (VMEM overflow, misaligned tiles, a program larger than HBM)
fails here.  Nothing runs, so nothing here is a time or a result.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist workers import
every test file.  The last test needs no topology: it guards against the
weights coming back into the step programs as constants.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.dit_moe_g import config as g_config
from repro.configs.dit_moe_xl import config as xl_config, tiny
from repro.core import plan as plan_lib
from repro.core import staleness as stale_lib
from repro.core.moe import default_capacity
from repro.core.schedules import DiceConfig
from repro.kernels import ops
from repro.models.dit_moe import init_dit
from repro.sampling.rectified_flow import make_rf_step

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("cfg_fn", [xl_config, g_config], ids=["xl", "g"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_expert_ffn_compiles_at_published_widths(one_chip, cfg_fn, dtype):
    cfg = cfg_fn()
    E, d, f = cfg.num_experts, cfg.d_model, cfg.expert_d_ff
    C = default_capacity(8 * cfg.patch_tokens, cfg)     # B=8 requests
    buf, wg, wu, wd = _abstract(
        [jax.ShapeDtypeStruct(s, dtype)
         for s in ((E, C, d), (E, d, f), (E, d, f), (E, f, d))], one_chip)
    compiled = ops.expert_ffn_pallas.lower(buf, wg, wu, wd,
                                           interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_residual_codec_compiles(one_chip):
    value, base = _abstract([jax.ShapeDtypeStruct((4096, 1152),
                                                  jnp.float32)] * 2, one_chip)
    compiled = ops.residual_int8_pallas.lower(value, base,
                                              interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xl_step_compiles_with_weights_as_arguments(one_chip):
    """A 2-layer slice of DiT-MoE-XL at published widths in bf16, one
    guided dice step at B=8: the weights are arguments of the program
    and the whole of it fits one chip."""
    cfg = xl_config().replace(num_layers=2)
    assert cfg.dtype == "bfloat16"
    B, num_steps = 8, 10
    dcfg = DiceConfig.dice()
    splan = plan_lib.compile_step_plans(
        dcfg, cfg.num_layers, num_steps,
        experts_per_token=cfg.experts_per_token)
    params = _abstract(jax.eval_shape(lambda k: init_dit(k, cfg),
                                      jax.random.PRNGKey(0)), one_chip)
    states = _abstract(jax.eval_shape(
        lambda: stale_lib.init_planned_states(
            splan, num_tokens=B * cfg.patch_tokens, d_model=cfg.d_model,
            k=cfg.experts_per_token)), one_chip)
    x, classes, t, key = _abstract(
        [jax.ShapeDtypeStruct((B, cfg.patch_tokens, cfg.in_channels),
                              jnp.float32),
         jax.ShapeDtypeStruct((B,), jnp.int32),
         jax.ShapeDtypeStruct((B,), jnp.float32),
         jax.ShapeDtypeStruct((2,), jnp.uint32)], one_chip)
    step = make_rf_step(params, cfg, dcfg, dt=1.0 / num_steps,
                        guidance=1.5)
    compiled = step.lower(x, classes, states, states, {}, {}, t, key,
                          plan=splan.steps[dcfg.warmup_steps]).compile()
    mem = compiled.memory_analysis()
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weight_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem


def test_step_program_carries_no_weights():
    """The tiny step's lowered module is far smaller than its weights: a
    step that closed over them would embed every byte as a constant."""
    cfg = tiny()
    params = init_dit(jax.random.PRNGKey(0), cfg)
    B, num_steps = 2, 4
    dcfg = DiceConfig.sync_ep()
    splan = plan_lib.compile_step_plans(
        dcfg, cfg.num_layers, num_steps,
        experts_per_token=cfg.experts_per_token)
    states = stale_lib.init_planned_states(
        splan, num_tokens=B * cfg.patch_tokens, d_model=cfg.d_model,
        k=cfg.experts_per_token)
    step = make_rf_step(params, cfg, dcfg, dt=1.0 / num_steps, guidance=1.5)
    text = step.lower(jnp.zeros((B, cfg.patch_tokens, cfg.in_channels)),
                      jnp.zeros((B,), jnp.int32), states, states, {}, {},
                      jnp.zeros((B,)), jax.random.PRNGKey(0),
                      plan=splan.steps[0]).as_text()
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    assert len(text) < weight_bytes / 4, (len(text), weight_bytes)
