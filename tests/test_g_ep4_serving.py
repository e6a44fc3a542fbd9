"""DiT-MoE-G's shape served on a four-way ``ep`` mesh, against the plain
reference.

In a subprocess with 4 forced host devices (the parent keeps the single
real CPU device), a small model of G's shape — 16 experts top-2, so 4 on
each device, and two shared experts fused at ``shared_d_ff = 2 x
d_model`` — is served through ``DiceServer`` + ``serve_continuous`` on
``make_mesh(ep=4)`` under the DICE schedule (warm-up, the deep half of
the layers synchronous, light steps every other step) at capacity factor
1.25, 2 lanes per device, and a second cohort in recycled lanes.  The
served latents are replayed by ``bench/reference_dit_moe.py`` (plain
jnp, f32 at "highest", per-device capacity groups) from the engine's
logged ticks and admissions, on the same seeded weights.

Tolerance: both sides compute in float32 on the CPU and differ only in
the order of their sums (the exchange, the grouped expert matmuls), so
they agree to ~1e-6; 1e-4 leaves that room and is still far below what
one capacity or routing decision taken differently moves a latent
(~1e-2).  The same run with the all-to-all made the identity (every
device keeps its own tokens and feeds them to its own experts) must
fail by more than the benchmark's limit for this configuration.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4

PROG = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{repo!r}, os.path.join({repo!r}, "src")]
import jax
import numpy as np
from jax.sharding import NamedSharding
from bench import reference_dit_moe as R
from repro.common.config import ModelConfig
from repro.common.sharding import ep_param_specs
from repro.core import plan as plan_lib
from repro.core.schedules import DiceConfig
from repro.launch.mesh import make_mesh
from repro.launch.serve import DiceServer, Request, serve_continuous
from repro.obs import StepTracer

M = dict(num_layers=4, d_model=64, num_heads=4, head_dim=16,
         num_experts=16, experts_per_token=2, num_shared_experts=2,
         moe_d_ff=64, shared_d_ff=128, patch_tokens=256, in_channels=4,
         num_classes=8, capacity_factor=1.25, norm_eps=1e-6,
         rope_theta=10000.0, dtype="float32")
B, STEPS, GUIDANCE, N = 8, 6, 1.5, 16
cfg = ModelConfig(
    name="g-shape", family="dit_moe", num_layers=M["num_layers"],
    d_model=M["d_model"], d_ff=M["moe_d_ff"], vocab_size=0,
    num_heads=M["num_heads"], num_kv_heads=M["num_heads"],
    head_dim=M["head_dim"], num_experts=M["num_experts"],
    experts_per_token=M["experts_per_token"],
    num_shared_experts=M["num_shared_experts"], moe_d_ff=M["moe_d_ff"],
    shared_d_ff=M["shared_d_ff"], patch_tokens=M["patch_tokens"],
    num_classes=M["num_classes"], in_channels=M["in_channels"],
    capacity_factor=M["capacity_factor"], dtype=M["dtype"])
dcfg = DiceConfig.dice(cond_stride=2)
mesh = make_mesh(ep=4)
specs = ep_param_specs(R.abstract_weights(M), ep_axis="ep")
weights = R.make_weights(M, 3, out_shardings=jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs))
classes = [int(c) for c in np.random.default_rng(3).integers(0, 8, N)]
key = jax.random.PRNGKey(11)
sched = R.Schedule(name="dice", warmup_steps=dcfg.warmup_steps,
                   sync_fraction=dcfg.sync_fraction,
                   cond_stride=dcfg.cond_stride, capacity_groups=4)
noise_key = jax.random.split(key)[0]


def serve():
    server = DiceServer(cfg, dcfg, params=weights, mesh=mesh)
    server.tracer = StepTracer()
    reqs = [Request(class_id=c, rid=r) for r, c in enumerate(classes)]
    out, stats = serve_continuous(server, reqs, max_batch=B,
                                  num_steps=STEPS, guidance=GUIDANCE,
                                  key=key)
    ev = server.tracer.events
    served = R.Served(ticks=[(e["args"]["tick"], e["args"]["slotted"])
                             for e in ev if e["name"] == "tick"],
                      admissions={{}})
    for e in ev:
        if e["name"] == "admit":
            a = e["args"]
            served.admissions.setdefault(a["tick"], []).append(
                (a["slot"], a["rid"], classes[a["rid"]]))
    return out, stats, served, ev


def gaps(out, model, served):
    ref = R.replay(weights, model, sched, served, key=key, num_steps=STEPS,
                   guidance=GUIDANCE, max_batch=B, want=list(range(N)),
                   sharding=NamedSharding(mesh, jax.sharding.PartitionSpec()))
    g = []
    for r in range(N):
        x0 = np.asarray(R.request_noise(noise_key, r, model))
        a, b = out[r] - x0, ref[r] - x0
        g.append(float(np.linalg.norm(a - b) / np.linalg.norm(b)))
    return max(g)


out, stats, served, ev = serve()
res = {{"recycled": stats["recycled_admissions"],
       "gap": gaps(out, M, served),
       # the same run replayed with room for every pair: a different
       # answer shows that capacity 1.25 dropped pairs, as served
       "gap_no_drops": gaps(out, dict(M, capacity_factor=16.0), served)}}

# the readback span's wire bytes against the plan: two MoE forwards a
# guided step, each layer's one-way per-device (E, C, d) f32 buffer
splan = plan_lib.compile_step_plans(dcfg, cfg.num_layers, STEPS,
                                    experts_per_token=2)
local = B // 4 * cfg.patch_tokens
kinds = {{}}
ticks = {{e["args"]["tick"]: e for e in ev if e["name"] == "tick"}}
for e in ev:
    if e["name"] == "serve.readback":
        tk = ticks[e["args"]["tick"]]["args"]
        kinds.setdefault(tk["variant"], set()).add(e["args"]["wire_bytes"])
        assert e["args"]["raw_bytes"] == e["args"]["wire_bytes"]
res["wire"] = {{k: sorted(v) for k, v in kinds.items()}}
res["planned"] = {{p.kind: 2 * sum(a.dispatch_bytes(local, cfg)
                                  for a in p.actions)
                  for p in splan.variants}}

# the exchange made the identity: each device keeps its own buffer
jax.lax.all_to_all = lambda x, *a, **k: x
out_id, _, served_id, _ = serve()
res["gap_identity"] = gaps(out_id, M, served_id)
print("RESULT " + json.dumps(res))
"""


def _run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", PROG.format(repo=REPO)],
                       env=env, capture_output=True, text=True,
                       timeout=1200, cwd=REPO)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, (r.stdout[-2000:], r.stderr[-4000:])
    return json.loads(line[-1][len("RESULT "):])


def test_g_shape_on_an_ep4_mesh_matches_the_reference():
    res = _run()
    assert res["recycled"] >= 8, res             # the second cohort
    assert res["gap"] < TOL, res
    assert res["gap_no_drops"] > 100 * TOL, res  # pairs really dropped
    # the exchange is part of the answer: without it the served
    # latents miss the limit the benchmark holds G to (0.045)
    assert res["gap_identity"] > 0.045, res
    # light steps send each token's first pair only: the async half of
    # the layers moves half its refresh payload, the sync half all of it
    wire, planned = res["wire"], res["planned"]
    # (at 512 tokens a device, light capacity 40 slots an expert, full 80)
    assert set(wire) == {"refresh", "light", "slotted"}, wire
    for kind in ("refresh", "light"):
        assert wire[kind] == [planned[kind]], (kind, wire, planned)
    # warm-up ticks run the slotted merge plan: full dispatch everywhere
    assert wire["slotted"] == [planned["warmup"]] == [planned["refresh"]]
    assert planned["light"] == 0.75 * planned["refresh"], planned
