"""Smoke run of the DICE serving path on a TPU.

DiT-MoE-XL at its published widths, bf16 weights made from a seed, served
through ``DiceServer`` + ``serve_continuous`` (8 requests, 8 slots, 10
steps, guidance 1.5):

  python chip_smoke.py             # one chip: sync and dice, plus a
                                   # one-layer f32 agreement check
  python chip_smoke.py --chips 4   # ep=4 mesh only: sync/blocking and
                                   # dice/ring against the mesh-less run

Everything runs in this one process, which owns the chips.  The platform
is pinned to ``tpu`` before JAX starts, so a TPU that fails to start
raises instead of falling back to the CPU, and nothing is printed as a
result.  The last line of stdout is one JSON object, ``{"ok": ...,
"device": {"platform", "kind", "count"}}``; the exit code is 0 only when
every check held.  Times and rates printed here are measured on the chip
(each tick after ``block_until_ready``); nothing modeled is printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

NUM_REQUESTS = 8
MAX_BATCH = 8
NUM_STEPS = 10
GUIDANCE = 1.5
SEED = 0
# Mesh phase depth: XL widths with 4 of its 28 layers (2 sync + 2 stale
# under dice's deep sync policy).  Full depth would hold 9.7 GB for the
# mesh-less reference beside a 4.4 GB shard on the same chip, and compile
# eight 28-layer programs.  Its capacity factor is E/k = 4, where no token
# can be dropped: the mesh sizes expert capacity per source chip, so at
# the published 1.25 the two runs drop different tokens by design.
MESH_LAYERS = 4
# Served bf16 forward vs f32 weights at "highest" matmul precision, as
# relative L2 error.  The served path runs its matmuls at DEFAULT
# precision, which on a TPU rounds f32 activations to bf16 (unit
# roundoff 2^-9) at every matmul input of a ~10-matmul chain, and a
# near-tied top-2 routing choice can flip and move one token's expert
# output.  Wrong weights, a dropped layer or a dtype slip give O(1).
AGREEMENT_TOL = 5e-2
# ep=4 mesh vs mesh-less run of the same weights and schedule, relative
# L2 over the final latents.  Without drops both do the same math (bit-
# identical on the CPU backend), but on the chip the two programs may tile
# and fuse their matmuls differently, moving f32 accumulation order and
# the bf16 rounding points, and over 10 Euler steps a near-tied routing
# choice can flip; a sharding or collective fault gives O(1).
MESH_TOL = 5e-2


def random_weights(key, cfg):
    """``init_dit`` weights with the adaLN-zero and output projections
    drawn like every other dense layer: zero-initialised, the model's
    velocity is exactly 0 and every check would pass on an identity."""
    import jax
    from repro.models import layers as L
    from repro.models.dit_moe import init_dit

    k_init, k_out, k_mod, k_ada = jax.random.split(key, 4)
    params = init_dit(k_init, cfg)
    dtype = params["final_out"].dtype
    params["final_out"] = L.dense_init(k_out, params["final_out"].shape,
                                       dtype=dtype)
    params["final_mod"] = L.dense_init(k_mod, params["final_mod"].shape,
                                       dtype=dtype)
    params["blocks"] = [
        dict(b, adaln=L.dense_init(jax.random.fold_in(k_ada, i),
                                   b["adaln"].shape, dtype=dtype))
        for i, b in enumerate(params["blocks"])]
    return params


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B ({peak / 1e9:.3f} GB)"


def agreement(cfg, failures) -> None:
    """One layer at ``cfg``'s widths: served weights vs the same weights
    upcast to f32 under "highest" matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.models.dit_moe import dit_train_forward

    cfg1 = cfg.replace(num_layers=1)
    params = random_weights(jax.random.PRNGKey(SEED), cfg1)
    kx, kt, ky = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    x = jax.random.normal(kx, (MAX_BATCH, cfg1.patch_tokens,
                               cfg1.in_channels))
    t = jax.random.uniform(kt, (MAX_BATCH,))
    y = jax.random.randint(ky, (MAX_BATCH,), 0, cfg1.num_classes)

    def fwd(p):
        return dit_train_forward(p, x, t, y, cfg1)[0]

    served = jax.jit(fwd)(params)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(fwd)(p32)
    err = rel_l2(served, ref)
    max_abs = float(jnp.max(jnp.abs(served.astype(jnp.float32) - ref)))
    print(f"agreement (1 layer, {cfg1.dtype} weights vs f32 at highest "
          f"precision): rel_l2 {err:.6g} (tol {AGREEMENT_TOL}), max_abs "
          f"{max_abs:.6g}, |ref|_max {float(jnp.max(jnp.abs(ref))):.6g}")
    if not err <= AGREEMENT_TOL:
        failures.append(f"agreement rel_l2 {err} > {AGREEMENT_TOL}")


def serve(cfg, params, schedule, failures, *, mesh=None, overlap=None,
          tag=""):
    """Serve NUM_REQUESTS through serve_continuous; check and report.
    Returns {rid: latent}."""
    import jax
    import numpy as np
    from repro.launch.serve import (SCHEDULES, DiceServer, Request,
                                    request_noise, serve_continuous)

    dcfg = SCHEDULES[schedule]()
    if overlap is not None:
        dcfg = dataclasses.replace(dcfg, overlap=overlap)
    server = DiceServer(cfg, dcfg, params=params, mesh=mesh)
    reqs = [Request(class_id=(125 * i) % cfg.num_classes, rid=i)
            for i in range(NUM_REQUESTS)]
    key = jax.random.PRNGKey(SEED)
    t0 = time.perf_counter()
    out, stats = serve_continuous(server, reqs, max_batch=MAX_BATCH,
                                  num_steps=NUM_STEPS, guidance=GUIDANCE,
                                  key=key)
    wall = time.perf_counter() - t0
    name = f"{schedule}{tag}"
    for label, sec in stats["compile_s"].items():
        print(f"{name}: compile variant {label}: {sec:.3f} s "
              f"(trace + compile + first tick)")
    ticks = stats["tick_s"]
    if ticks:
        per_tick = sum(ticks) / len(ticks)
        print(f"{name}: steady {per_tick:.6f} s/tick over {len(ticks)} ticks "
              f"(min {min(ticks):.6f}, max {max(ticks):.6f}); "
              f"{MAX_BATCH / (NUM_STEPS * per_tick):.4f} images/s "
              f"({MAX_BATCH} slots / ({NUM_STEPS} steps x s/tick))")
    else:
        failures.append(f"{name}: no steady tick to time")
    print(f"{name}: wall {wall:.3f} s for {len(out)} requests, "
          f"{stats['ticks']} ticks; peak {peak_bytes(jax.devices()[0])}")
    print(f"{name}: jit cache {stats['jit_cache_size']} vs "
          f"{stats['num_plan_variants']} plan variants")
    if stats["jit_cache_size"] != stats["num_plan_variants"]:
        failures.append(f"{name}: jit cache {stats['jit_cache_size']} != "
                        f"{stats['num_plan_variants']} plan variants")
    noise_key = jax.random.split(key)[0]
    shape = (cfg.patch_tokens, cfg.in_channels)
    for r in reqs:
        s = out.get(r.rid)
        if s is None or s.shape != shape or not np.isfinite(s).all():
            failures.append(f"{name}: request {r.rid} missing, misshapen "
                            f"or not finite")
        elif np.array_equal(s, np.asarray(request_noise(noise_key, r.rid,
                                                        cfg))):
            failures.append(f"{name}: request {r.rid} left at its noise")
    return out


def one_chip(failures) -> None:
    import jax
    from repro.configs.dit_moe_xl import config as xl_config

    cfg = xl_config()
    agreement(cfg, failures)
    params = random_weights(jax.random.PRNGKey(SEED), cfg)
    n = sum(a.size for a in jax.tree.leaves(params))
    print(f"{cfg.name}: {n} parameters, {cfg.dtype}, {cfg.num_layers} layers, "
          f"d={cfg.d_model}, {cfg.num_experts} experts top-"
          f"{cfg.experts_per_token}; {NUM_REQUESTS} requests, "
          f"{MAX_BATCH} slots, {NUM_STEPS} steps, guidance {GUIDANCE}")
    for schedule in ("sync", "dice"):
        serve(cfg, params, schedule, failures)


def four_chips(failures) -> None:
    import jax
    import numpy as np
    from repro.configs.dit_moe_xl import config as xl_config
    from repro.launch.mesh import make_mesh

    xl = xl_config()
    cfg = xl.replace(num_layers=MESH_LAYERS,
                     capacity_factor=xl.num_experts / xl.experts_per_token)
    params = random_weights(jax.random.PRNGKey(SEED), cfg)
    mesh = make_mesh(ep=4)
    print(f"{cfg.name} cut to {cfg.num_layers} layers at published widths, "
          f"{cfg.dtype}, capacity factor {cfg.capacity_factor}; ep=4 mesh, "
          f"{cfg.num_experts // 4} experts per chip")
    for schedule, overlap in (("sync", "blocking"), ("dice", "ring")):
        ref = serve(cfg, params, schedule, failures, tag=" (1 chip)")
        got = serve(cfg, params, schedule, failures, mesh=mesh,
                    overlap=overlap, tag=f" (ep=4, {overlap})")
        common = sorted(set(ref) & set(got))
        if len(common) != NUM_REQUESTS:
            failures.append(f"{schedule}: mesh and mesh-less runs served "
                            f"different requests")
            continue
        err = rel_l2(np.stack([got[r] for r in common]),
                     np.stack([ref[r] for r in common]))
        print(f"{schedule}: ep=4 {overlap} vs 1 chip: rel_l2 {err:.6g} "
              f"(tol {MESH_TOL})")
        if not err <= MESH_TOL:
            failures.append(f"{schedule}: ep=4 vs 1 chip rel_l2 {err} > "
                            f"{MESH_TOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the one-chip phase; 4: only the ep=4 "
                         "mesh phase")
    args = ap.parse_args()

    import jax
    from repro.common.compile_cache import enable_compile_cache

    # pinned before first use: a TPU that fails to start raises here
    jax.config.update("jax_platforms", "tpu")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"needs a TPU, found {devices[0].platform}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} chips, "
                         f"found {len(devices)}")
    print(f"device: {devices[0].device_kind} x {len(devices)} "
          f"({devices[0].platform}); using {args.chips}")
    print(f"compile cache: {enable_compile_cache()}")

    failures: list = []
    try:
        (four_chips if args.chips == 4 else one_chip)(failures)
    except Exception:
        traceback.print_exc()
        failures.append("a phase raised (traceback on stderr)")
    for f in failures:
        print(f"FAILED: {f}")
    print(json.dumps({"ok": not failures,
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": args.chips}}))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
