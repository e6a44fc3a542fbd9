"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a cell at a size the CPU holds (the
harness's look for a chip is skipped): set-up, warm-up, the measured
call, the metrics and the comparison with the reference.  The faults are
planted in the serving step the engine builds, as a wrong step would
produce them: a step that leaves the latents unchanged, half the batch
given the other half's mean update, one token's update lost, and (on a
four-device mesh) the expert exchange between chips left out."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.tests.small import run_small  # noqa: E402


def faulty_step(make_rf_step, kind):
    """``make_rf_step`` whose steps are broken in one way."""
    def make(*a, **k):
        step = make_rf_step(*a, **k)

        class Broken:
            def __call__(self, x, *rest, **kw):
                out = step(x, *rest, **kw)
                new = out[0]
                if kind == "unchanged":
                    new = x
                elif kind == "half_batch":
                    h = x.shape[0] // 2
                    d = new - x
                    new = x + d.at[h:].set(d[:h].mean(0))
                elif kind == "token":
                    new = new.at[:, 0, :].set(x[:, 0, :])
                return (new,) + tuple(out[1:])

            def _cache_size(self):
                return step._cache_size()

        return Broken()
    return make


def test_clean_run_is_correct():
    # stale_rel_err is computed for a cell that reports it; it has no
    # entry in BENCHMARK.json yet, so the test asks for it
    r = run_small("xl_1chip.backlog", 11,
                  also=({"name": "stale_rel_err", "unit": "ratio"},))
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"]["images_per_s"]["value"] > 0
    assert r["metrics"]["stale_rel_err"]["value"] > 0
    assert r["checks"]["latent_gap"]["value"] < 1e-4


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "token"])
@pytest.mark.parametrize("cell", ["xl_1chip.backlog", "xl_1chip.poisson"])
def test_broken_step_is_not_correct(monkeypatch, kind, cell):
    import repro.launch.serve as serve_mod
    monkeypatch.setattr(serve_mod, "make_rf_step",
                        faulty_step(serve_mod.make_rf_step, kind))
    r = run_small(cell, 12)
    assert r["correct"] is False
    c = r["checks"]["latent_gap"]
    assert c["value"] > c["limit"], (kind, c)


MESH_PROG = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{root!r}, {src!r}]
import jax
kind = sys.argv[1]
if kind == "no_exchange":
    jax.lax.all_to_all = lambda x, *a, **k: x
from bench.tests.small import run_small
r = run_small("g_ep4.backlog", 13)
print("RESULT " + json.dumps(r))
"""


def _mesh_run(kind):
    prog = MESH_PROG.format(root=str(ROOT), src=str(ROOT / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", prog, kind], env=env,
                       capture_output=True, text=True, timeout=900)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, r.stderr[-3000:]
    import json
    return json.loads(line[-1][len("RESULT "):])


def test_mesh_clean_run_is_correct():
    r = _mesh_run("clean")
    assert r["device"]["count"] == 4
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["latent_gap"]["value"] < 1e-4


def test_mesh_without_exchange_is_not_correct():
    r = _mesh_run("no_exchange")
    assert r["correct"] is False
    c = r["checks"]["latent_gap"]
    assert c["value"] > c["limit"]
