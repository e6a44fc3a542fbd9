"""The plain reference against the served path, at a size the CPU holds.

On the CPU both compute in float32, so the reference replays what the
engine served to rounding; the control (the reference in fp8, the
precision below the configuration's bf16) must fail the configuration's
limit.  The chip readings the limits were set from (``bench/control.py``
at the cell's own size) are in PERF.md."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import reference_dit_moe as R  # noqa: E402
from bench.tests.small import run_small, small_cell  # noqa: E402


def _served(cell, model, seed, n, arrivals):
    import jax
    from repro.launch.serve import DiceServer, Request, serve_continuous
    from repro.obs.trace import StepTracer
    weights = R.make_weights(model, seed)
    server = DiceServer(harness.model_config(model),
                        harness.schedule_config(cell.config["serving"]),
                        params=weights)
    server.tracer = StepTracer()
    rng = np.random.default_rng(seed)
    classes = rng.integers(0, model["num_classes"], n).tolist()
    reqs = [Request(class_id=c, rid=i) for i, c in enumerate(classes)]
    key = jax.random.PRNGKey(seed)
    out, _ = serve_continuous(
        server, reqs, max_batch=cell.config["serving"]["max_batch"],
        num_steps=cell.mix["num_steps"], guidance=cell.mix["guidance"],
        key=key, arrival_steps=arrivals)
    ev = server.tracer.events
    served = R.Served(
        ticks=[(e["args"]["tick"], e["args"]["slotted"]) for e in ev
               if e["name"] == "tick"], admissions={})
    for e in ev:
        if e["name"] == "admit":
            a = e["args"]
            served.admissions.setdefault(a["tick"], []).append(
                (a["slot"], a["rid"], classes[a["rid"]]))
    return weights, served, out, key


def _gaps(cell, model, weights, served, out, key, want, fp8):
    import jax
    sched = R.Schedule(warmup_steps=cell.config["serving"]["warmup_steps"],
                       sync_fraction=cell.config["serving"]["sync_fraction"],
                       cond_stride=cell.config["serving"]["cond_stride"])
    ref = R.replay(weights, model, sched, served, key=key,
                   num_steps=cell.mix["num_steps"],
                   guidance=cell.mix["guidance"],
                   max_batch=cell.config["serving"]["max_batch"], want=want,
                   fp8=fp8)
    nk = jax.random.split(key)[0]
    gaps = []
    for r in want:
        x0 = np.asarray(R.request_noise(nk, r, model))
        gaps.append(harness.rel_l2(out[r] - x0, ref[r] - x0))
    return gaps


@pytest.mark.parametrize("cap", [1.25, 0.5])
def test_replay_matches_the_served_run(cap):
    """Open-loop arrivals: lanes recycled mid-run, free lanes, warm-up
    replay in recycled lanes; at capacity 0.5 many pairs drop."""
    cell, model = small_cell("xl_1chip.poisson")
    model = dict(model, capacity_factor=cap)
    arrivals = np.cumsum(np.random.default_rng(0).exponential(1.5, 20))
    weights, served, out, key = _served(cell, model, 5, 20,
                                        arrivals.tolist())
    assert any(len(a) < 8 for a in served.admissions.values())
    gaps = _gaps(cell, model, weights, served, out, key, list(range(20)),
                 fp8=False)
    assert max(gaps) < 1e-5


def test_control_fails_the_limit():
    """A whole run, with the reference in fp8 put in the program's place
    and judged by the harness's own checks: the program comes out
    correct, the control not."""
    r = run_small("xl_1chip.backlog", 6, control=True)
    assert r["correct"] is True, r["checks"]
    assert r["control"]["correct"] is False
    c = r["control"]["checks"]["latent_gap"]
    assert c["value"] > c["limit"], c
    assert c["limit"] == harness.limits_of(small_cell(
        "xl_1chip.backlog")[0])["latent_gap"]


def test_replay_refuses_a_run_that_breaks_the_rules():
    cell, model = small_cell("xl_1chip.backlog")
    weights, served, out, key = _served(cell, model, 7, 8, None)
    served.ticks[0] = (served.ticks[0][0], False)
    with pytest.raises(R.ReplayMismatch):
        _gaps(cell, model, weights, served, out, key, [0], fp8=False)
