"""Shared benchmark infrastructure.

Trains (once, disk-cached keyed by config hash) the CPU-sized DiT-MoE used
by all quality benchmarks, and provides timed sampling under each
parallelism schedule.  Quality numbers are FID-proxy / paired-MSE on
synthetic latents — the validated claim is the paper's ORDERING (DESIGN.md
Sec. 8); latency/speedup numbers are modeled on the paper's 8-device setup
from the roofline terms.

Environment knobs (all read lazily, so ``benchmarks.run`` and standalone
``--smoke`` mains can set them before the first use):
  BENCH_TRAIN_STEPS   tiny-model training steps (default 300)
  BENCH_SAMPLES       samples per quality measurement (default 64)
  BENCH_SEED          sampling-noise PRNG seed (default 7) — threaded into
                      every ``sample_method`` call for reproducible CSV rows
"""
from __future__ import annotations

import hashlib
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.common.compile_cache import enable_compile_cache  # noqa: F401
from repro.configs.dit_moe_xl import config as xl_config, tiny
from repro.configs.dit_moe_g import config as g_config
from repro.core.schedules import DiceConfig
from repro.data.synthetic import gaussian_mixture_latents, latent_batches
from repro.metrics.latency_model import modeled_step_latency
from repro.metrics.fid_proxy import fid_proxy, mse_vs_reference
from repro.models.dit_moe import init_dit
from repro.optim.adamw import adamw_init
from repro.sampling.rectified_flow import rf_sample, rf_train_step

CACHE_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "cache")


def train_steps() -> int:
    return int(os.environ.get("BENCH_TRAIN_STEPS", "300"))


def num_samples() -> int:
    return int(os.environ.get("BENCH_SAMPLES", "64"))


def bench_seed() -> int:
    return int(os.environ.get("BENCH_SEED", "7"))


def _cfg_hash(cfg, *extra) -> str:
    """Key for the on-disk caches: the full (frozen-dataclass) config repr
    plus any run parameters that change the artifact — a stale cache under
    a different cfg can never be loaded by accident."""
    payload = repr(cfg) + "|" + "|".join(map(str, extra))
    return hashlib.sha1(payload.encode()).hexdigest()[:12]

# async (staleness) schedules carry the ring-overlap engine (DESIGN.md
# Sec. 12): since ISSUE 5 the overlap the paper claims is an EXECUTED
# property, so the modeled latencies may legitimately assume it.  The
# sync-EP baseline stays blocking — that is the baseline the paper beats.
# (On mesh-less sampling runs the flag normalizes away; outputs are
# bit-identical to blocking configs.)
SCHEDULES = {
    "expert_parallelism": (DiceConfig.sync_ep(), 0),
    "distrifusion": (DiceConfig.sync_ep(), 8),          # displaced patch par.
    "displaced_expert_parallelism": (DiceConfig.displaced(overlap="ring"), 0),
    "interweaved_parallelism": (DiceConfig.interweaved(overlap="ring"), 0),
    "dice": (DiceConfig.dice(overlap="ring"), 0),
}


def tiny_cfg():
    return tiny()


def smoke_cfg(name: str):
    """The CI-sized model the serving/compression smokes share — one
    definition so the shrink pattern (and the disk-cache key) cannot
    silently diverge between benchmarks."""
    return tiny().replace(name=name, num_layers=4, d_model=48, d_ff=192,
                          num_heads=4, num_kv_heads=4, head_dim=12,
                          moe_d_ff=48, patch_tokens=16, capacity_factor=4.0)


def get_trained_params(cfg=None, *, steps: Optional[int] = None):
    """Train once per (cfg, steps) and cache the checkpoint on disk, keyed
    by the config hash — table1/table23/fig10/fig_compress all reuse it
    instead of retraining the tiny model per benchmark invocation, and a
    changed config can never pick up a stale checkpoint."""
    cfg = cfg or tiny_cfg()
    steps = train_steps() if steps is None else steps
    params0 = init_dit(jax.random.PRNGKey(0), cfg)
    path = os.path.join(CACHE_DIR, f"dit_{_cfg_hash(cfg, steps)}.ckpt")
    if os.path.exists(path):
        try:
            return load_checkpoint(path, params0)
        except Exception:
            pass
    params, opt = params0, adamw_init(params0)
    it = latent_batches(batch=32, tokens=cfg.patch_tokens,
                        channels=cfg.in_channels,
                        num_classes=cfg.num_classes, seed=0)
    key = jax.random.PRNGKey(1)
    for i in range(steps):
        key, k = jax.random.split(key)
        params, opt, m = rf_train_step(params, opt, next(it), k, cfg)
        if i % 50 == 0:
            print(f"# train step {i}, loss {float(m['loss']):.4f}",
                  flush=True)
    os.makedirs(CACHE_DIR, exist_ok=True)
    save_checkpoint(path, params, step=steps)
    return params


def reference_set(cfg, n: Optional[int] = None):
    """'Real' data for the FID proxy (disk-cached keyed by cfg hash)."""
    n = num_samples() if n is None else n
    path = os.path.join(CACHE_DIR, f"ref_{_cfg_hash(cfg, n)}.npy")
    if os.path.exists(path):
        try:
            return jnp.asarray(np.load(path))
        except Exception:
            pass
    x, _ = gaussian_mixture_latents(jax.random.PRNGKey(99), batch=n,
                                    tokens=cfg.patch_tokens,
                                    channels=cfg.in_channels,
                                    num_classes=cfg.num_classes)
    os.makedirs(CACHE_DIR, exist_ok=True)
    np.save(path, np.asarray(x))
    return x


def sample_method(params, cfg, method: str, *, num_steps: int,
                  n: Optional[int] = None,
                  guidance=1.5) -> Tuple[jnp.ndarray, Dict, float]:
    """Returns (samples, stats, us_per_step) for a schedule by name.
    stats includes the StepPlan engine's compile accounting
    (num_plan_variants / jit_cache_size).  Sampling noise is keyed by
    BENCH_SEED (``--seed`` on benchmarks/run.py) for reproducible rows."""
    n = num_samples() if n is None else n
    dcfg, ndev = SCHEDULES[method]
    classes = jnp.arange(n) % cfg.num_classes
    t0 = time.time()
    samples, stats = rf_sample(params, cfg, dcfg, num_steps=num_steps,
                               classes=classes,
                               key=jax.random.PRNGKey(bench_seed()),
                               guidance=guidance, patch_parallel_ndev=ndev)
    jax.block_until_ready(samples)
    us_per_step = (time.time() - t0) / num_steps * 1e6
    return samples, stats, us_per_step


def modeled_speedup(cfg, method: str, *, local_batch=4, n_dev=8) -> float:
    """Step-latency speedup over synchronous expert parallelism, modeled on
    the paper's hardware AND the paper's model scale (DiT-MoE-XL): the
    quality benches run a CPU-sized model whose compute is negligible, so
    the latency model always uses the published XL configuration."""
    dcfg, ndev = SCHEDULES[method]
    if ndev:            # DistriFusion: no EP all-to-all, model replicated;
        # patch-parallel overlaps its gather -> model as async EP variant
        dcfg = DiceConfig.displaced(overlap="ring")
    cfg_lat = xl_config()
    base = modeled_step_latency(cfg_lat, DiceConfig.sync_ep(),
                                local_batch=local_batch, n_dev=n_dev)
    t = modeled_step_latency(cfg_lat, dcfg, local_batch=local_batch,
                             n_dev=n_dev)
    return base["t_step_s"] / t["t_step_s"]


def csv_row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}", flush=True)


BENCH_SCHEMA_VERSION = 1


def bench_env(mesh=None) -> dict:
    """The provenance stamp every BENCH_*.json carries under ``_env``:
    schema version, jax version, backend, device count, and the mesh
    shape of the run — so a committed artifact can be validated
    (``benchmarks/run.py --check``) and its numbers attributed to the
    environment that produced them."""
    return {
        "schema_version": BENCH_SCHEMA_VERSION,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "mesh": mesh,
    }


def write_bench_json(name: str, payload) -> str:
    """Persist a benchmark's result dict as ``BENCH_<name>.json`` at the
    repo root — the machine-readable artifact next to the CSV rows, so
    drivers (CI, the paper-claims checker) diff structured numbers
    instead of scraping stdout.  Dict payloads are stamped with the
    ``_env`` provenance block.  Returns the path written."""
    import json

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    path = os.path.join(root, f"BENCH_{name}.json")
    if isinstance(payload, dict) and "_env" not in payload:
        payload = {**payload, "_env": bench_env(payload.get("mesh"))}

    def default(o):
        if isinstance(o, (np.integer,)):
            return int(o)
        if isinstance(o, (np.floating,)):
            return float(o)
        if isinstance(o, np.ndarray):
            return o.tolist()
        return repr(o)

    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=default)
        f.write("\n")
    return path
