"""The continuous engine's own spans (``serve.*``), their place on the
profiler's clock, and the always-on device scopes of the model.

A ``StepTracer`` on ``server.tracer`` must see, per tick: ``serve.admit``
(a tick that admits), ``serve.prepare``, ``serve.dispatch``, the ``tick``
span ending as the host sees the tick ready (it holds the tick's
``serve.wait`` and at most one launch: its own at depth 0, the next
tick's with one in flight), ``serve.readback`` and, where a request
finishes, ``serve.complete`` with one ``done`` instant per request.
The benchmark reads the ``tick`` span and ``admit`` instant by name and
args, so those are pinned here too.  Every span is also a
``jax.profiler.TraceAnnotation``: a profiler capture holds the names as
host events.  The model's layer parts carry ``jax.named_scope`` names
in their HLO metadata whatever the observability setting.
"""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dit_moe_xl import tiny
from repro.core import plan as plan_lib
from repro.core import staleness as stale_lib
from repro.core.schedules import DiceConfig
from repro.launch.serve import (DiceServer, Request, _admit_lanes,
                                serve_continuous)
from repro.obs import StepTracer
from repro.resilience.faults import ResilienceConfig
from repro.sampling.rectified_flow import make_rf_step

NUM_STEPS = 4
B = 4
# two cohorts: the second waits for the first lanes to free up
ARRIVALS = [0.0] * B + [1.0] * B
BETWEEN_TICKS = ("serve.admit", "serve.prepare", "serve.readback",
                 "serve.complete")
IN_TICK = ("serve.dispatch", "serve.wait")


def small_cfg():
    return tiny().replace(name="spans-test", num_layers=2, d_model=32,
                          d_ff=64, num_heads=2, num_kv_heads=2, head_dim=16,
                          moe_d_ff=32, patch_tokens=8, capacity_factor=2.0)


def _serve(tracer=None, resilience=None, n=len(ARRIVALS)):
    cfg = small_cfg()
    server = DiceServer(cfg, dataclasses.replace(
        DiceConfig.dice(), resilience=resilience), seed=0)
    server.tracer = tracer
    reqs = [Request(class_id=i % cfg.num_classes, rid=i) for i in range(n)]
    return serve_continuous(server, reqs, max_batch=B, num_steps=NUM_STEPS,
                            arrival_steps=ARRIVALS[:n]), server


@pytest.fixture(scope="module")
def traced():
    tracer = StepTracer()
    (out, stats), server = _serve(tracer)
    return dict(out=out, stats=stats, events=list(tracer.events),
                server=server)


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _inside(e, outer):
    return (outer["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"])


def _overlaps(e, outer):
    return (e["ts"] < outer["ts"] + outer["dur"]
            and outer["ts"] < e["ts"] + e["dur"])


def test_every_engine_span_is_emitted(traced):
    names = {e["name"] for e in traced["events"]}
    assert set(BETWEEN_TICKS + IN_TICK) <= names
    ticks = _named(traced["events"], "tick")
    assert len(ticks) == traced["stats"]["ticks"]
    # one prepare and one readback per tick; admit on the two admitting
    # ticks only, complete on the two ticks where a cohort finishes
    assert len(_named(traced["events"], "serve.prepare")) == len(ticks)
    assert len(_named(traced["events"], "serve.readback")) == len(ticks)
    admits = _named(traced["events"], "serve.admit")
    assert [e["args"] for e in admits] == [
        {"tick": 0, "admitted": B, "reset_lanes": B},
        {"tick": NUM_STEPS, "admitted": B, "reset_lanes": B}]
    assert len(_named(traced["events"], "serve.complete")) == 2


def test_admission_is_one_program_compiled_once():
    """Each admitting tick launches the admission program once, with
    every admitted lane seeded and zeroed in it; a second call on the
    same server compiles nothing new."""
    _admit_lanes.clear_cache()
    tracer = StepTracer()
    _, server = _serve(tracer)
    cfg = server.cfg
    reqs = [Request(class_id=(i + 1) % cfg.num_classes, rid=100 + i)
            for i in range(len(ARRIVALS))]
    serve_continuous(server, reqs, max_batch=B, num_steps=NUM_STEPS,
                     arrival_steps=ARRIVALS, key=jax.random.PRNGKey(5))
    assert _admit_lanes._cache_size() == 1
    admits = _named(tracer.events, "serve.admit")
    assert len(admits) == 4                     # two cohorts per call
    assert all(e["args"]["reset_lanes"] == e["args"]["admitted"] == B
               for e in admits)
    lab = {"schedule": "dice", "engine": "continuous"}
    assert server.metrics.value("dice_fused_admissions_total",
                                lab) == len(admits)


@pytest.fixture(scope="module")
def traced_depth0():
    """A server whose policies read each tick before the next is
    launched (resilience): no tick in flight."""
    tracer = StepTracer()
    (out, stats), server = _serve(tracer,
                                  resilience=ResilienceConfig(guards=True))
    return dict(out=out, stats=stats, events=list(tracer.events),
                server=server)


@pytest.mark.parametrize("run", ["traced", "traced_depth0"])
def test_dispatch_and_wait_nest_in_each_tick(run, request):
    """Every tick span holds one ``serve.wait`` and at most one launch
    before it: its own tick's at depth 0 (always with resilience on; a
    variant's first, compiling tick otherwise), the next tick's with one
    in flight (``pipelined``).  A launch outside every tick span finds
    no tick in flight.  A tick's span ends as the host sees it ready:
    its read-back follows the span and comes before the next span."""
    traced = request.getfixturevalue(run)
    ev = traced["events"]
    depth0 = run == "traced_depth0"
    ticks = _named(ev, "tick")
    launches = _named(ev, "serve.dispatch")
    assert sorted(e["args"]["tick"] for e in launches) == [
        tk["args"]["tick"] for tk in ticks]
    own = []
    for tk in ticks:
        n, pipelined = tk["args"]["tick"], tk["args"]["pipelined"]
        (w,) = [e for e in _named(ev, "serve.wait") if _overlaps(e, tk)]
        assert _inside(w, tk), tk["args"]
        inner = [e for e in launches if _overlaps(e, tk)]
        assert len(inner) <= 1 and len(inner) >= pipelined, tk["args"]
        for d in inner:
            assert _inside(d, tk), tk["args"]
            assert d["ts"] + d["dur"] <= w["ts"]      # launch, then the wait
            assert d["args"]["tick"] == n + pipelined, tk["args"]
            if not pipelined:
                own.append(n)
    readback = {e["args"]["tick"]: e for e in _named(ev, "serve.readback")}
    for tk, nxt in zip(ticks, ticks[1:] + [None]):
        rb = readback[tk["args"]["tick"]]
        assert tk["ts"] + tk["dur"] <= rb["ts"], tk["args"]
        if nxt is not None:
            assert rb["ts"] + rb["dur"] <= nxt["ts"], tk["args"]
    for d in launches:
        if not any(_overlaps(d, tk) for tk in ticks):
            n = d["args"]["tick"]
            assert n == 0 or readback[n - 1]["ts"] < d["ts"], n
    pipelined = sum(tk["args"]["pipelined"] for tk in ticks)
    assert pipelined == traced["stats"]["pipelined_ticks"]
    if depth0:
        assert pipelined == 0 and own == list(range(len(ticks)))
    else:
        # lockstep cohorts: ticks 0, 2 and 3 first run the slotted,
        # refresh and light variants
        assert own == [0, 2, 3] and pipelined == len(ticks) - 5


def test_between_tick_spans_lie_outside_every_tick(traced):
    ev = traced["events"]
    ticks = _named(ev, "tick")
    for name in BETWEEN_TICKS:
        for e in _named(ev, name):
            assert not any(_overlaps(e, tk) for tk in ticks), (name, e)


def test_one_done_per_request_with_its_admit_rid(traced):
    ev = traced["events"]
    admits = {e["args"]["rid"]: e["args"] for e in _named(ev, "admit")}
    dones = {e["args"]["rid"]: e["args"] for e in _named(ev, "done")}
    assert len(_named(ev, "done")) == len(traced["out"]) == len(ARRIVALS)
    assert set(dones) == set(admits) == set(traced["out"])
    for rid, d in dones.items():
        assert d["slot"] == admits[rid]["slot"]
        assert d["tick"] == admits[rid]["tick"] + NUM_STEPS - 1
        done_ev = next(e for e in _named(ev, "done")
                       if e["args"]["rid"] == rid)
        complete = [c for c in _named(ev, "serve.complete")
                    if c["ts"] <= done_ev["ts"] <= c["ts"] + c["dur"]]
        assert len(complete) == 1


def test_tick_and_admit_args_as_the_benchmark_reads_them(traced):
    ev = traced["events"]
    for e in _named(ev, "tick"):
        assert e["ph"] == "X" and e["cat"] == "step"
        assert set(e["args"]) == {"tick", "slotted", "variant", "pipelined"}
        assert isinstance(e["args"]["tick"], int)
        assert isinstance(e["args"]["slotted"], bool)
        assert isinstance(e["args"]["pipelined"], bool)
    assert [e["args"]["tick"] for e in _named(ev, "tick")] == list(
        range(len(_named(ev, "tick"))))
    for e in _named(ev, "admit"):
        assert e["ph"] == "i"
        assert set(e["args"]) == {"rid", "slot", "tick", "recycled"}
        assert all(isinstance(e["args"][k], int)
                   for k in ("rid", "slot", "tick"))


def test_tick_names_its_variant_and_readback_its_wire_bytes(traced):
    """The ``tick`` span names the plan variant that ran; the
    ``serve.readback`` span carries the tick's one-way dispatch payload
    over both guided forwards, which is the plan's, and a light step
    (each token's first pair only) sends less than a refresh step."""
    cfg = traced["server"].cfg
    dcfg = DiceConfig.dice()
    k = cfg.experts_per_token
    splan = plan_lib.compile_step_plans(dcfg, cfg.num_layers, NUM_STEPS,
                                        experts_per_token=k)
    merge = plan_lib.slotted_merge_plan(dcfg, cfg.num_layers,
                                        experts_per_token=k)
    tokens = B * cfg.patch_tokens
    planned = {p.kind: 2 * sum(a.dispatch_bytes(tokens, cfg)
                               for a in p.actions) for p in splan.variants}
    planned["slotted"] = 2 * sum(a.dispatch_bytes(tokens, cfg)
                                 for a in merge.actions)
    ev = traced["events"]
    variant = {e["args"]["tick"]: e["args"]["variant"]
               for e in _named(ev, "tick")}
    # lockstep cohorts of NUM_STEPS: two warm-up ticks (slotted), then
    # a refresh and a light step
    assert [variant[t] for t in sorted(variant)] == [
        "slotted", "slotted", "refresh", "light"] * 2
    for e in _named(ev, "serve.readback"):
        v = variant[e["args"]["tick"]]
        assert e["args"]["wire_bytes"] == planned[v], (v, e["args"])
        assert e["args"]["raw_bytes"] == e["args"]["wire_bytes"]
    assert planned["light"] < planned["refresh"] == planned["slotted"]


def test_readback_counts_its_device_reads(traced):
    # expert_counts, dispatch/raw/hop bytes, hops, buffer_bytes: six
    # blocking reads a tick with observability and resilience off
    for e in _named(traced["events"], "serve.readback"):
        assert e["args"]["reads"] == 6


def test_service_seconds_replace_e2e_on_the_continuous_engine(traced):
    lab = {"schedule": "dice", "engine": "continuous"}
    h = traced["server"].metrics.get("dice_request_service_seconds", lab)
    assert h is not None and h.count == len(ARRIVALS)
    assert traced["server"].metrics.get("dice_request_e2e_seconds",
                                        lab) is None
    assert traced["server"].metrics.get("dice_wire_bytes_total",
                                        lab) is None
    assert (traced["stats"]["wire_bytes_total"]
            == traced["stats"]["dispatch_bytes_total"] > 0)


def test_no_events_without_a_tracer(traced, monkeypatch):
    emitted = []
    monkeypatch.setattr(StepTracer, "_emit",
                        lambda self, ev: emitted.append(ev))
    (out, _), _ = _serve(tracer=None)
    assert emitted == []
    # the spans change nothing served
    for rid, x in out.items():
        assert np.asarray(x).tobytes() == np.asarray(
            traced["out"][rid]).tobytes()


def test_quarantine_scan_has_its_span():
    tracer = StepTracer()
    _serve(tracer, resilience=ResilienceConfig(guards=True), n=B)
    q = _named(tracer.events, "serve.quarantine")
    assert len(q) == len(_named(tracer.events, "tick"))
    assert all(e["args"]["quarantined"] == 0 for e in q)


def test_span_args_are_read_as_the_span_ends():
    tr = StepTracer()
    args = {"reads": 0}
    with tr.span("serve.readback", args=args):
        args["reads"] += 2
    assert tr.events[-1]["args"] == {"reads": 2}
    doc = tr.to_json()
    assert doc["otherData"]["origin_unix_ns"] == tr.origin_unix_ns > 0


def test_engine_spans_reach_a_profiler_capture(tmp_path):
    tracer = StepTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(tracer, n=B)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert paths
    pd = jax.profiler.ProfileData.from_file(paths[0])
    host = {e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    want = {"tick", "serve.admit", "serve.prepare", "serve.dispatch",
            "serve.wait", "serve.readback", "serve.complete", "plan_build"}
    assert want <= host, want - host


# ---------------------------------------------------------------------------
# device scopes: always on, in the compiled step's op metadata
# ---------------------------------------------------------------------------
SCOPES = ("layer_00/attn", "layer_01/attn", "layer_00/router",
          "layer_00/dispatch", "layer_00/expert_ffn", "layer_00/shared_ffn",
          "layer_00/combine", "layer_00/stale_select")


def test_compiled_step_carries_the_layer_part_scopes():
    cfg = small_cfg()
    server = DiceServer(cfg, DiceConfig.dice(), seed=0)
    assert not server.obs.enabled
    dcfg = server.dcfg
    k = cfg.experts_per_token
    merge = plan_lib.slotted_merge_plan(dcfg, cfg.num_layers,
                                        experts_per_token=k)
    splan = plan_lib.compile_step_plans(dcfg, cfg.num_layers, NUM_STEPS,
                                        experts_per_token=k)
    step = make_rf_step(server.params, cfg, dcfg, dt=1.0 / NUM_STEPS,
                        obs=server.obs)
    T = B * cfg.patch_tokens
    states = stale_lib.init_planned_states(splan, num_tokens=T,
                                           d_model=cfg.d_model, k=k,
                                           dtype=jnp.float32)
    x = jnp.zeros((B, cfg.patch_tokens, cfg.in_channels), jnp.float32)
    hlo = step.lower(
        x, jnp.zeros((B,), jnp.int32), states, states, {}, {},
        jnp.zeros((B,), jnp.float32), jax.random.PRNGKey(0), plan=merge,
        slotted=True, slot_fresh=jnp.ones((T,), bool),
        consume_mask=jnp.ones((T, k), bool)).compile().as_text()
    meta = [ln for ln in hlo.splitlines() if "op_name=" in ln]
    for scope in SCOPES:
        assert any(f"/{scope}/" in ln for ln in meta), scope
