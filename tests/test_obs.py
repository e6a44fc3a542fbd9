"""Observability plane conformance (ISSUE 9, DESIGN.md Sec. 16).

Covers all three parts of the plane:

  * in-graph telemetry: an ``ObsConfig``-enabled run is BIT-IDENTICAL to
    an obs-off run (telemetry only adds aux outputs), the jit cache
    stays at the plan-variant count, the per-layer ``staleness_age`` row
    reproduces the plan's static ground truth exactly, residual energy
    is exactly 0 on lossless actions and strictly positive where the
    wire codec quantizes — single-device AND on an 8-device ep mesh
    (subprocess, like test_ep_dice);
  * metrics registry: counter/gauge/histogram/series semantics, merge
    (counters add, gauges max, histograms/series concatenate),
    Prometheus text exposition parses with correct TYPE lines and
    cumulative buckets, JSON snapshot schema, serving summaries as
    registry views (legacy key sets preserved), measured step-walltime
    histograms + per-layer residual-energy series for all five serving
    schedules;
  * step tracing: structurally valid Chrome trace-event JSON with the
    plan-build / per-variant-compile / step-execute phases;

plus the benchmark-artifact provenance stamp and the
``benchmarks/run.py --check`` validator (satellite b).
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress.codecs import CompressConfig
from repro.configs.dit_moe_xl import tiny
from repro.core import plan as plan_lib
from repro.core.schedules import DiceConfig
from repro.models.dit_moe import init_dit
from repro.obs import (AGE, CODEC_ERR, DROP_FRAC, MASK_RATE, NUM_FIELDS,
                       RES_COMBINE, RES_DISPATCH, TELEMETRY_FIELDS,
                       MetricsRegistry, ObsConfig, StepTracer,
                       parse_prometheus)
from repro.sampling.rectified_flow import rf_sample

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # benchmarks.* (repo-root package-less modules)

from repro.launch.serve import (DiceServer, Request, SCHEDULES,  # noqa: E402
                                serve_queue, write_metrics)


def small_cfg(name="obs-test"):
    return tiny().replace(name=name, num_layers=4, d_model=48, d_ff=192,
                          num_heads=4, num_kv_heads=4, head_dim=12,
                          moe_d_ff=48, patch_tokens=16, capacity_factor=4.0)


def nondegenerate_params(cfg, seed=0):
    """adaLN-zero init makes every hidden state timestep-independent on
    an untrained model (residual energy would be legitimately 0);
    perturbing the modulation tables makes the MoE inputs drift across
    diffusion steps so staleness residuals are exercised — the same
    fixture test_ep_dice uses."""
    params = init_dit(jax.random.PRNGKey(seed), cfg)
    k = jax.random.PRNGKey(99)
    for i, blk in enumerate(params["blocks"]):
        blk["adaln"] = 0.05 * jax.random.normal(
            jax.random.fold_in(k, i), blk["adaln"].shape)
    params["final_out"] = 0.05 * jax.random.normal(
        jax.random.fold_in(k, 10_000), params["final_out"].shape)
    return params


NUM_STEPS = 6


@pytest.fixture(scope="module")
def dice_runs():
    """One obs-off + one obs-on compressed-DICE run shared by the
    telemetry tests (rf_sample compiles are the expensive part)."""
    cfg = small_cfg()
    params = nondegenerate_params(cfg)
    dcfg = DiceConfig.dice(compress=CompressConfig(codec="int8_residual"))
    classes = jnp.arange(4) % cfg.num_classes
    key = jax.random.PRNGKey(3)
    off, s_off = rf_sample(params, cfg, dcfg, num_steps=NUM_STEPS,
                           classes=classes, key=key, guidance=1.5)
    tracer = StepTracer()
    on, s_on = rf_sample(params, cfg, dcfg, num_steps=NUM_STEPS,
                         classes=classes, key=key, guidance=1.5,
                         obs=ObsConfig(enabled=True), tracer=tracer)
    splan = plan_lib.compile_step_plans(dcfg, cfg.num_layers, NUM_STEPS,
                                        experts_per_token=cfg.experts_per_token)
    return dict(cfg=cfg, dcfg=dcfg, off=off, on=on, s_off=s_off, s_on=s_on,
                splan=splan, tracer=tracer)


# ---------------------------------------------------------------------------
# in-graph telemetry (tentpole part 1)
# ---------------------------------------------------------------------------
def test_obs_on_is_bit_identical(dice_runs):
    a = np.asarray(dice_runs["off"])
    b = np.asarray(dice_runs["on"])
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


def test_obs_keeps_jit_cache_at_variant_count(dice_runs):
    splan = dice_runs["splan"]
    assert dice_runs["s_off"]["jit_cache_size"] == splan.num_variants
    assert dice_runs["s_on"]["jit_cache_size"] == splan.num_variants
    assert dice_runs["s_on"]["num_plan_variants"] == splan.num_variants


def test_telemetry_shape_and_static_fields(dice_runs):
    cfg, splan = dice_runs["cfg"], dice_runs["splan"]
    tel = dice_runs["s_on"]["telemetry"]
    assert "telemetry" not in dice_runs["s_off"]
    assert len(tel) == NUM_STEPS
    for s, t in enumerate(tel):
        t = np.asarray(t)
        assert t.shape == (cfg.num_layers, NUM_FIELDS)
        # staleness_age reproduces the plan's static per-layer ground truth
        np.testing.assert_array_equal(
            t[:, AGE], np.asarray(splan.steps[s].staleness_ages, np.float32))
        for layer, action in enumerate(splan.steps[s].actions):
            # mask rate: fraction of (token, rank) pairs sent fresh — 1.0
            # on full-dispatch steps, < 1 under Conditional Communication
            if action.mask_policy is None:
                assert t[layer, MASK_RATE] == 1.0
            else:
                assert 0.0 < t[layer, MASK_RATE] < 1.0
        assert (t[:, DROP_FRAC] >= 0).all()
        assert (t[:, DROP_FRAC] <= 1).all()


def test_residual_energy_gated_on_codec(dice_runs):
    """Dispatch residual energy and codec error are EXACTLY 0 on lossless
    actions (warmup sync / refresh) and strictly positive where the int8
    residual codec quantizes the payload of a drifting activation."""
    splan = dice_runs["splan"]
    tel = [np.asarray(t) for t in dice_runs["s_on"]["telemetry"]]
    saw_codec = 0
    for s, t in enumerate(tel):
        for layer, action in enumerate(splan.steps[s].actions):
            if action.codec is None:
                assert t[layer, RES_DISPATCH] == 0.0, (s, layer)
                assert t[layer, CODEC_ERR] == 0.0, (s, layer)
            else:
                saw_codec += 1
                assert t[layer, RES_DISPATCH] > 0.0, (s, layer)
                assert t[layer, CODEC_ERR] > 0.0, (s, layer)
    assert saw_codec > 0  # the schedule must actually exercise the codec
    # combine residual (drift between cached expert outputs and fresh
    # recompute) shows up somewhere on the stale steps of a drifting model
    assert max(t[:, RES_COMBINE].max() for t in tel) > 0.0


def test_measured_step_walltime_and_compile(dice_runs):
    s_on, splan = dice_runs["s_on"], dice_runs["splan"]
    assert "step_wall_s" not in dice_runs["s_off"]
    wall = s_on["step_wall_s"]
    assert len(wall) == NUM_STEPS and all(w > 0 for w in wall)
    # first call of each variant is compile-timed, keyed by variant index
    assert set(s_on["compile_s"]) == set(range(splan.num_variants))
    assert all(v > 0 for v in s_on["compile_s"].values())


def test_tracer_emits_chrome_trace(dice_runs, tmp_path):
    tracer, splan = dice_runs["tracer"], dice_runs["splan"]
    doc = tracer.to_json()
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    cats = {e["cat"] for e in doc["traceEvents"]}
    assert {"plan", "compile", "step"} <= cats
    compiles = [e for e in doc["traceEvents"] if e["cat"] == "compile"]
    assert len(compiles) == splan.num_variants
    for e in doc["traceEvents"]:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    path = tmp_path / "trace.json"
    tracer.write(path)
    with open(path) as f:
        assert json.load(f)["traceEvents"]  # valid JSON on disk


def test_mesh_obs_bit_identity_subprocess():
    """8-host-device ep mesh (subprocess, like test_ep_dice): obs-on is
    bit-identical to obs-off on the sharded path too, the jit cache stays
    at the variant count, and the pmean'd telemetry block has the same
    layout and static fields as the single-device one."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.compress.codecs import CompressConfig
        from repro.configs.dit_moe_xl import tiny
        from repro.core import plan as plan_lib
        from repro.core.schedules import DiceConfig
        from repro.launch.mesh import make_ep_mesh
        from repro.models.dit_moe import init_dit
        from repro.obs import AGE, NUM_FIELDS, ObsConfig
        from repro.sampling.rectified_flow import rf_sample

        cfg = tiny().replace(num_layers=2, d_model=64, moe_d_ff=64,
                             d_ff=256, num_heads=4, num_kv_heads=4,
                             head_dim=16, patch_tokens=16,
                             capacity_factor=8.0)
        params = init_dit(jax.random.PRNGKey(0), cfg)
        k = jax.random.PRNGKey(99)
        for i, blk in enumerate(params["blocks"]):
            blk["adaln"] = 0.05 * jax.random.normal(
                jax.random.fold_in(k, i), blk["adaln"].shape)
        classes = jnp.arange(8) % cfg.num_classes
        key = jax.random.PRNGKey(7)
        mesh = make_ep_mesh(8)
        NUM = 6
        dcfg = DiceConfig.dice(
            compress=CompressConfig(codec="int8_residual"))
        off, s_off = rf_sample(params, cfg, dcfg, num_steps=NUM,
                               classes=classes, key=key, guidance=1.0,
                               mesh=mesh)
        on, s_on = rf_sample(params, cfg, dcfg, num_steps=NUM,
                             classes=classes, key=key, guidance=1.0,
                             mesh=mesh, obs=ObsConfig(enabled=True))
        a, b = np.asarray(off), np.asarray(on)
        assert a.tobytes() == b.tobytes(), "mesh obs changed the samples"
        splan = plan_lib.compile_step_plans(
            dcfg, cfg.num_layers, NUM,
            experts_per_token=cfg.experts_per_token)
        assert s_off["jit_cache_size"] == splan.num_variants
        assert s_on["jit_cache_size"] == splan.num_variants
        tel = [np.asarray(t) for t in s_on["telemetry"]]
        assert len(tel) == NUM
        for s, t in enumerate(tel):
            assert t.shape == (cfg.num_layers, NUM_FIELDS)
            np.testing.assert_array_equal(
                t[:, AGE],
                np.asarray(splan.steps[s].staleness_ages, np.float32))
        print("MESH-OBS-OK")
    """)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=dict(os.environ, PYTHONPATH="src"),
                       cwd=REPO, timeout=1200)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "MESH-OBS-OK" in r.stdout


# ---------------------------------------------------------------------------
# metrics registry (tentpole part 2)
# ---------------------------------------------------------------------------
def test_registry_primitives_and_exposition():
    reg = MetricsRegistry()
    c = reg.counter("dice_requests_total", "requests", {"engine": "queue"})
    c.inc()
    c.inc(2)
    assert reg.counter("dice_requests_total",
                       labels={"engine": "queue"}) is c  # get-or-create
    g = reg.gauge("dice_jit_cache_size")
    g.set_max(3)
    g.set_max(1)          # max semantics: stays 3
    h = reg.histogram("dice_step_wall_seconds")
    for v in (0.004, 0.2, 0.2, 3.0):
        h.observe(v)
    assert h.count == 4
    assert h.mean == pytest.approx((0.004 + 0.2 + 0.2 + 3.0) / 4)
    assert h.quantile(0.5) == 0.2
    s = reg.series("dice_residual_energy", labels={"layer": "00",
                                                   "path": "dispatch"})
    s.extend([0.0, 1.5])
    assert s.last == 1.5

    parsed = parse_prometheus(reg.to_prometheus())
    samples, types = parsed["samples"], parsed["__types__"]
    assert types["dice_requests_total"] == "counter"
    assert types["dice_jit_cache_size"] == "gauge"
    assert types["dice_step_wall_seconds"] == "histogram"
    assert types["dice_residual_energy"] == "gauge"  # series -> last value
    assert samples['dice_requests_total{engine="queue"}'] == 3
    assert samples["dice_jit_cache_size"] == 3
    assert samples['dice_residual_energy{layer="00",path="dispatch"}'] == 1.5
    # cumulative buckets, +Inf == count, sum matches
    assert samples['dice_step_wall_seconds_bucket{le="+Inf"}'] == 4
    assert samples['dice_step_wall_seconds_bucket{le="0.005"}'] == 1
    assert samples['dice_step_wall_seconds_bucket{le="0.25"}'] == 3
    assert samples["dice_step_wall_seconds_count"] == 4
    assert samples["dice_step_wall_seconds_sum"] == pytest.approx(3.404)
    # bucket counts are cumulative: non-decreasing in upper-bound order
    def ub_of(k):
        le = k.split('le="')[1].rstrip('"}')
        return math.inf if le == "+Inf" else float(le)
    buckets = sorted((ub_of(k), v) for k, v in samples.items()
                     if k.startswith("dice_step_wall_seconds_bucket"))
    counts = [v for _, v in buckets]
    assert counts == sorted(counts)


def test_registry_merge_semantics():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("dice_ticks_total").inc(2)
    b.counter("dice_ticks_total").inc(5)
    a.gauge("dice_plan_variants").set_max(3)
    b.gauge("dice_plan_variants").set_max(2)
    a.histogram("dice_step_wall_seconds").observe(0.1)
    b.histogram("dice_step_wall_seconds").observe(0.3)
    a.series("dice_queue_depth").extend([4, 3])
    b.series("dice_queue_depth").extend([2])
    a.merge(b)
    assert a.value("dice_ticks_total") == 7
    assert a.value("dice_plan_variants") == 3
    h = a.get("dice_step_wall_seconds")
    assert h.count == 2 and h.sum == pytest.approx(0.4)
    assert a.get("dice_queue_depth").values == [4, 3, 2]


def test_snapshot_schema_and_write(tmp_path):
    reg = MetricsRegistry()
    reg.counter("dice_batches_total", labels={"schedule": "dice"}).inc()
    reg.histogram("dice_request_e2e_seconds").observe(0.5)
    reg.series("dice_slot_occupancy").extend([1.0, 0.5])
    snap = reg.snapshot()
    assert snap["schema"] == "dice-metrics-snapshot/1"
    by_name = {m["name"]: m for m in snap["metrics"]}
    assert by_name["dice_batches_total"]["labels"] == {"schedule": "dice"}
    assert by_name["dice_request_e2e_seconds"]["p50"] == 0.5
    assert by_name["dice_slot_occupancy"]["values"] == [1.0, 0.5]
    json.dumps(snap)  # JSON-able
    # write_metrics dispatches on extension: .json -> snapshot, else text
    jpath, ppath = tmp_path / "m.json", tmp_path / "m.prom"
    write_metrics(reg, str(jpath))
    write_metrics(reg, str(ppath))
    with open(jpath) as f:
        assert json.load(f)["schema"] == "dice-metrics-snapshot/1"
    with open(ppath) as f:
        assert parse_prometheus(f.read())["samples"]


def test_serve_queue_summary_is_registry_view():
    cfg = small_cfg("obs-queue")
    server = DiceServer(cfg, DiceConfig.sync_ep(), seed=0,
                        obs=ObsConfig(enabled=True))
    reqs = [Request(rid=i, class_id=i % cfg.num_classes) for i in range(3)]
    out, stats = serve_queue(server, reqs, max_batch=2, num_steps=2)
    assert len(out) == 3
    assert stats["batches"] == 2
    assert stats["padded"] == 1          # 3 requests -> batches of 2
    assert stats["jit_cache_size"] == stats["num_plan_variants"]
    # legacy summary keys survive the registry-view rewrite
    for k in ("buffer_bytes", "dispatch_bytes_total", "wire_bytes_total",
              "raw_bytes_total", "ring_hops", "hop_bytes_total"):
        assert k in stats, k
    # the per-call registry folded into the server's source of truth
    lab = {"schedule": "sync", "engine": "queue"}
    assert server.metrics.value("dice_requests_total", lab) == 3
    assert server.metrics.value("dice_batches_total", lab) == 2
    e2e = server.metrics.get("dice_request_e2e_seconds", lab)
    assert e2e is not None and e2e.count == 3
    wall = server.metrics.get("dice_step_wall_seconds", lab)
    assert wall is not None and wall.count == 2 * 2  # 2 batches x 2 steps


def test_all_schedules_publish_measured_series():
    """Every serving schedule publishes a measured step-walltime histogram
    and per-layer residual-energy series, with cache == variants — the
    acceptance snapshot the issue asks for."""
    cfg = small_cfg("obs-sched")
    steps = 4
    merged = MetricsRegistry()
    for name, mk in SCHEDULES.items():
        server = DiceServer(cfg, mk(), seed=0, obs=ObsConfig(enabled=True))
        reqs = [Request(rid=i, class_id=i) for i in range(2)]
        _, stats = server.generate(reqs, num_steps=steps)
        assert stats["jit_cache_size"] == stats["num_plan_variants"], name
        merged.merge(server.metrics)
    for name in SCHEDULES:
        lab = {"schedule": plan_lib.schedule_name(SCHEDULES[name]().schedule),
               "engine": "batch"}
        h = merged.get("dice_step_wall_seconds", lab)
        assert h is not None and h.count == steps, name
        assert all(v > 0 for v in h.raw), name
        for layer in range(cfg.num_layers):
            for path in ("dispatch", "combine"):
                s = merged.get("dice_residual_energy",
                               {**lab, "layer": f"{layer:02d}", "path": path})
                assert s is not None, (name, layer, path)
                assert len(s.values) == steps, (name, layer, path)
    # the merged registry exposes cleanly in both formats
    assert parse_prometheus(merged.to_prometheus())["samples"]
    json.dumps(merged.snapshot())


# ---------------------------------------------------------------------------
# step tracer (tentpole part 3) — pure-host structural tests
# ---------------------------------------------------------------------------
def test_tracer_span_instant_counter():
    tr = StepTracer()
    with tr.span("plan_build", cat="plan", args={"steps": 4}):
        tr.instant("admit", cat="serve", args={"rid": 0})
    tr.counter("queue_depth", 3)
    doc = tr.to_json()
    phs = {e["name"]: e["ph"] for e in doc["traceEvents"]}
    assert phs["plan_build"] == "X"
    assert phs["admit"] == "i"
    assert phs["queue_depth"] == "C"
    span = next(e for e in doc["traceEvents"] if e["name"] == "plan_build")
    assert span["args"] == {"steps": 4}
    json.dumps(doc)


def test_tracer_write_stringifies_exotic_args(tmp_path):
    tr = StepTracer()
    tr.instant("odd", args={"cfg": DiceConfig.dice()})  # not JSON-able
    path = tmp_path / "t.json"
    tr.write(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["traceEvents"][0]["name"] == "odd"


# ---------------------------------------------------------------------------
# bench artifact provenance + --check validator (satellite b)
# ---------------------------------------------------------------------------
def test_bench_env_stamp_fields():
    from benchmarks.common import bench_env
    env = bench_env(mesh={"ep": 8})
    assert env["schema_version"] == 1
    assert env["jax"] == jax.__version__
    assert env["backend"] == jax.default_backend()
    assert env["device_count"] == jax.device_count()
    assert env["mesh"] == {"ep": 8}


def test_check_bench_artifacts(tmp_path):
    from benchmarks.common import bench_env
    from benchmarks.run import check_bench_artifacts

    def write(name, payload):
        with open(tmp_path / f"BENCH_{name}.json", "w") as f:
            json.dump(payload, f)

    # no artifacts at all is a failure (the validator must not vacuously
    # pass an empty tree)
    assert check_bench_artifacts(str(tmp_path)) == 1
    # unstamped artifact -> fail
    write("foo", {"x": 1})
    assert check_bench_artifacts(str(tmp_path)) == 1
    # stamped, no declared schema -> _env-only validation passes
    write("foo", {"x": 1, "_env": bench_env()})
    assert check_bench_artifacts(str(tmp_path)) == 0
    # wrong schema version -> fail
    write("foo", {"x": 1, "_env": {**bench_env(), "schema_version": 99}})
    assert check_bench_artifacts(str(tmp_path)) == 1
    os.remove(tmp_path / "BENCH_foo.json")
    # a schema'd artifact with an unknown key -> fail
    from benchmarks.run import BENCH_SCHEMAS
    required = BENCH_SCHEMAS["serve_throughput"]["required"]
    payload = {k: 0 for k in required}
    payload["_env"] = bench_env()
    write("serve_throughput", payload)
    assert check_bench_artifacts(str(tmp_path)) == 0
    write("serve_throughput", {**payload, "mystery_stat": 1})
    assert check_bench_artifacts(str(tmp_path)) == 1
    # a required key missing -> fail
    short = dict(payload)
    del short["jit_cache_size"]
    write("serve_throughput", short)
    assert check_bench_artifacts(str(tmp_path)) == 1


def test_committed_bench_artifacts_pass_check():
    from benchmarks.run import check_bench_artifacts
    assert check_bench_artifacts(REPO) == 0
