"""One run of one benchmark cell.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``bench/configs/<config>.json``: the model's sizes, how it
is served, its reference and the limits of its comparison) and a traffic
mix (``bench/traffic/<traffic>.json``).  A per-layer metric is a reader
``bench/metrics/<name>.py`` with ``read(rec) -> float | None``.  Nothing
here names a cell, a configuration or a metric.

A run, all in this one process, which owns the chips:

1. set-up: weights from the seed, a ``DiceServer`` with the configured
   schedule (observability off), and a ``StepTracer`` attached to it, so
   the engine's own tick spans and admission instants are logged;
2. a warm-up ``serve_continuous`` call that compiles every plan variant
   and measures the steady wall time per tick, host gaps included;
3. the measured call, sized from that: a lead-in that runs each plan
   variant once (each call re-traces its step programs), then the
   window's traffic.  The window opens when the lead-in's last tick
   ends and lasts ``seconds``; compilations inside it are counted;
4. after the window: the device's peak memory, the served outputs
   against the plain reference (``correct``), and, where the cell
   reports ``stale_rel_err``, every backlog cohort the window finished
   served again synchronously.

``setup_s`` runs from process start to the window's opening.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import flops as flops_lib
from bench import trace_reduce as tr
from bench import traffic as traffic_lib
from bench import window as win_lib

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 3.0          # profiled stretch at the end of the window


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, from its files
# ---------------------------------------------------------------------------
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: str
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def model(self) -> dict:
        return self.config["model"]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _config_path(bench: dict, name: str) -> Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_path(name: str) -> Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def metric_path(name: str) -> Path:
    return BENCH_DIR / "metrics" / f"{name}.py"


def load_cell(workload: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    w = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    with open(_config_path(bench, w["config"])) as f:
        config = json.load(f)
    with open(traffic_path(w["traffic"])) as f:
        mix = json.load(f)
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=w["traffic"], mix=mix,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def load_reader(name: str) -> Callable:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", metric_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(config: dict):
    return importlib.import_module(f"bench.{config['reference']}")


# ---------------------------------------------------------------------------
# what the run logged, in one place for the metric readers
# ---------------------------------------------------------------------------
@dataclass
class Record:
    """Everything a per-layer metric reader may read.  Times are seconds
    on the tracer's clock; ``trace`` is None when no profile was taken."""
    ticks: List[win_lib.Tick]
    admits: List[win_lib.Admit]
    w0: float
    w1: float
    window: List[win_lib.Tick]
    due: Dict[int, float]                  # rid -> due time (open loop)
    done: Dict[int, float]
    num_steps: int
    chips: int
    flops_per_lane_step: int
    peak_flops: float
    trace: Optional[tr.Profile] = None
    trace_window: Optional[Tuple[float, float, List[int]]] = None
    # the profiler's start stalls the engine: readers of the engine's own
    # spans look only at what happened before it
    quiet_until: float = math.inf

    def quiet_window(self) -> Tuple[List[win_lib.Tick], float]:
        """Window ticks that ended before the profiler started, and the
        end of the last of them."""
        win = [t for t in self.window if t.end <= self.quiet_until]
        return win, max((t.end for t in win), default=self.w0)

    def quiet_due(self) -> Dict[int, float]:
        """Requests due early enough in the window to have been served
        before the profiler started."""
        cut = self.quiet_until - TRACE_SECONDS
        return {r: t for r, t in self.due.items() if t <= cut}


def find_chips(cell: Cell):
    """The cell's chips, their kind and its peaks; JAX pinned to the TPU
    first.  Raises ``SystemExit`` (no result) with no TPU, too few chips
    or a device kind that ``peaks.json`` lacks."""
    import jax
    with open(BENCH_DIR / "peaks.json") as f:
        peaks = json.load(f)
    jax.config.update("jax_platforms", "tpu")     # no fallback to the CPU
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"needs a TPU, found {devices[0].platform}")
    if len(devices) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips, found "
                         f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    from repro.common.compile_cache import enable_compile_cache
    log(f"device: {kind} x {len(devices)}; compile cache: "
        f"{enable_compile_cache()}")
    return devices[:cell.chips], kind, peaks[kind]


# ---------------------------------------------------------------------------
# the tracer hook: tick marks for the profiler, profiler start/stop
# ---------------------------------------------------------------------------
def make_tracer(on_tick: Callable[[int], None]):
    from repro.obs.trace import StepTracer
    import jax

    class BenchTracer(StepTracer):
        """The engine's own tracer; around each tick span it also calls
        ``on_tick`` and, while the profiler runs, marks the tick in the
        profile so device operations can be put on the tick clock."""

        profiling = False

        @contextmanager
        def span(self, name, cat="host", args=None):
            if name != "tick":
                with super().span(name, cat, args) as s:
                    yield s
                return
            tick = int((args or {}).get("tick", -1))
            on_tick(tick)
            if self.profiling:
                with jax.profiler.TraceAnnotation(f"bench_tick {tick}"):
                    with super().span(name, cat, args) as s:
                        yield s
            else:
                with super().span(name, cat, args) as s:
                    yield s

    return BenchTracer()


class Compiles:
    """Backend compilations (persistent-cache hits included), with the
    tracer-clock time each finished."""

    def __init__(self, clock):
        import jax
        self.clock = clock
        self.events: List[Tuple[float, float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._cb)

    def _cb(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((self.clock(), float(duration),
                                str(kw.get("fun_name", ""))))

    def between(self, a: float, b: float):
        return [e for e in self.events if a <= e[0] < b]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def model_config(m: dict):
    from repro.common.config import ModelConfig
    return ModelConfig(
        name="bench", family="dit_moe", num_layers=m["num_layers"],
        d_model=m["d_model"], d_ff=m["moe_d_ff"], vocab_size=0,
        num_heads=m["num_heads"], num_kv_heads=m["num_heads"],
        head_dim=m["head_dim"], num_experts=m["num_experts"],
        experts_per_token=m["experts_per_token"],
        num_shared_experts=m["num_shared_experts"], moe_d_ff=m["moe_d_ff"],
        patch_tokens=m["patch_tokens"], num_classes=m["num_classes"],
        in_channels=m["in_channels"], capacity_factor=m["capacity_factor"],
        norm_eps=m["norm_eps"], rope_theta=m["rope_theta"],
        dtype=m["dtype"])


def schedule_config(serving: dict, name: Optional[str] = None):
    """The program's schedule config for ``serving`` (or the named
    baseline), checked against every knob the configuration states."""
    from repro.core.schedules import DiceConfig
    name = name or serving["schedule"]
    if name == "sync":
        return DiceConfig.sync_ep()
    if name != "dice":
        raise ValueError(f"schedule {name!r} is not one this bench serves")
    dcfg = DiceConfig.dice(cond_stride=serving["cond_stride"])
    dcfg = dataclasses.replace(dcfg, warmup_steps=serving["warmup_steps"],
                               sync_fraction=serving["sync_fraction"])
    return dcfg


def base_keys(seed: int):
    import jax
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return {"warm": jax.random.fold_in(k, 1),
            "serve": jax.random.fold_in(k, 2)}


@dataclass
class Setup:
    cell: Cell
    seed: int
    model: dict
    cfg: object
    mesh: object
    weights: object
    server: object
    tracer: object
    compiles: Compiles
    s_tick: float = 0.0
    lead_ticks: int = 0
    hooks: Dict[str, Callable] = field(default_factory=dict)


def build(cell: Cell, seed: int, model: Optional[dict] = None) -> Setup:
    import jax
    from jax.sharding import NamedSharding
    from repro.common.sharding import ep_param_specs
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import DiceServer

    m = dict(model if model is not None else cell.model)
    ref = load_reference(cell.config)
    serving = cell.config["serving"]
    ep = int(serving.get("ep", 1))
    mesh = make_mesh(ep=ep) if ep > 1 else None
    shardings = None
    if mesh is not None:
        specs = ep_param_specs(ref.abstract_weights(m), ep_axis="ep")
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    weights = ref.make_weights(m, seed, out_shardings=shardings)
    jax.block_until_ready(weights)
    hooks: Dict[str, Callable] = {}
    tracer = make_tracer(lambda tick: hooks.get("tick", _noop)(tick))
    server = DiceServer(model_config(m), schedule_config(serving),
                        params=weights, mesh=mesh)
    if server.obs.enabled:
        raise RuntimeError("the benchmark serves with observability off")
    server.tracer = tracer
    compiles = Compiles(lambda: tracer.now() * 1e-6)
    return Setup(cell=cell, seed=seed, model=m, cfg=server.cfg, mesh=mesh,
                 weights=weights, server=server, tracer=tracer,
                 compiles=compiles, hooks=hooks)


def _noop(_tick):
    return None


# ---------------------------------------------------------------------------
# one serve_continuous call, and what its spans say
# ---------------------------------------------------------------------------
@dataclass
class Call:
    ticks: List[win_lib.Tick]
    admits: List[win_lib.Admit]
    out: Dict[int, np.ndarray]
    classes: Dict[int, int]
    t_start: float
    t_end: float


def serve(st: Setup, classes: List[int], arrivals: Optional[List[float]],
          key, *, schedule: Optional[str] = None,
          rids: Optional[List[int]] = None) -> Call:
    from repro.launch.serve import Request, serve_continuous, DiceServer
    server = st.server
    if schedule is not None:
        server = DiceServer(st.cfg, schedule_config(
            st.cell.config["serving"], schedule), params=st.weights,
            mesh=st.mesh)
        server.tracer = st.tracer
    rids = list(range(len(classes))) if rids is None else rids
    reqs = [Request(class_id=int(c), rid=r) for r, c in zip(rids, classes)]
    n0 = len(st.tracer.events)
    t0 = st.tracer.now() * 1e-6
    out, _ = serve_continuous(server, reqs,
                              max_batch=st.cell.config["serving"]["max_batch"],
                              num_steps=st.cell.mix["num_steps"],
                              guidance=st.cell.mix["guidance"], key=key,
                              arrival_steps=arrivals)
    t1 = st.tracer.now() * 1e-6
    ev = st.tracer.events[n0:]
    ticks = [win_lib.Tick(e["args"]["tick"], e["ts"] * 1e-6,
                          (e["ts"] + e["dur"]) * 1e-6,
                          bool(e["args"]["slotted"]))
             for e in ev if e["name"] == "tick"]
    admits = [win_lib.Admit(e["args"]["rid"], e["args"]["slot"],
                            e["args"]["tick"], e["ts"] * 1e-6)
              for e in ev if e["name"] == "admit"]
    return Call(ticks=ticks, admits=admits, out=out,
                classes={r: int(c) for r, c in zip(rids, classes)},
                t_start=t0, t_end=t1)


def compile_ticks(st: Setup, call: Call) -> List[win_lib.Tick]:
    """Ticks during which something compiled."""
    return [t for t in call.ticks if st.compiles.between(t.start, t.end)]


def warm_up(st: Setup, keys) -> None:
    """Compile every plan variant and measure the steady seconds per
    tick, host gaps included.

    One call: a cohort of ``max_batch`` requests at tick 0 (every lane in
    lockstep, as a backlog runs).  An open loop adds ``2 * max_batch``
    more, arriving from the tick the cohort finishes at 0.8 of the lanes'
    capacity (lanes recycled every few ticks, most ticks mixing warm-up
    lanes with established ones).  A backlog's seconds per tick are read
    from the cohort's ticks after the last compiling one, an open loop's
    from the ticks while its arrivals run."""
    serving = st.cell.config["serving"]
    B, steps = serving["max_batch"], st.cell.mix["num_steps"]
    backlog = st.cell.mix["arrivals"] == "backlog"
    rate = 0.8 * B / steps                      # requests per tick
    start = steps
    arrivals = [0.0] * B
    if not backlog:
        arrivals += [start + j / rate for j in range(2 * B)]
    call = serve(st, traffic_lib.classes(st.cell.mix, st.seed,
                                         len(arrivals), 1),
                 arrivals, keys["warm"])
    comp = compile_ticks(st, call)
    last = max((t.tick for t in comp), default=-1)

    def period(ticks):
        if len(ticks) < 4:
            raise RuntimeError(f"warm-up left {len(ticks)} steady ticks")
        return (ticks[-1].end - ticks[0].start) / len(ticks)

    if backlog:
        st.s_tick = period([t for t in call.ticks if last < t.tick < start])
    else:
        stop = start + 2 * B / rate
        st.s_tick = period([t for t in call.ticks
                            if max(last, start + steps) < t.tick <= stop])
    st.lead_ticks = serving["warmup_steps"] + serving["cond_stride"]
    log(f"warm-up: {len(call.ticks)} ticks, {len(comp)} compiled; steady "
        f"{st.s_tick:.6f} s/tick")


def plan_traffic(st: Setup, seconds: float
                 ) -> Tuple[List[int], Optional[List[float]],
                            Optional[List[float]]]:
    """Classes and arrival ticks of the measured call, and each window
    request's due time in seconds after the window opens (None for a
    backlog).  Request 0.. of a lead-in come first."""
    mix = st.cell.mix
    B = st.cell.config["serving"]["max_batch"]
    steps = mix["num_steps"]
    L = st.lead_ticks
    offsets = traffic_lib.arrival_offsets(mix, st.seed, seconds)
    if offsets is None:
        # the lead-in is the first cohort; the backlog lasts the window
        # with a quarter to spare, plus a cohort
        ticks = L + 1.25 * seconds / st.s_tick
        n = B * (int(math.ceil(ticks / steps)) + 1)
        return traffic_lib.classes(mix, st.seed, n), None, None
    lead = 1
    n = lead + len(offsets)
    arrivals = [0.0] * lead + [L + a / st.s_tick for a in offsets]
    return traffic_lib.classes(mix, st.seed, n), arrivals, offsets


# ---------------------------------------------------------------------------
# the measured call's tick hook: arrival clock and profiler
# ---------------------------------------------------------------------------
class TickHook:
    """Called as each tick of the measured call begins.

    The window opens as the lead-in ends (tick ``lead_ticks``).  With
    ``pace``, a tick that would start before its planned wall time
    (window opening + ticks since then x the warm-up's seconds per tick)
    waits for it: arrivals are mapped to ticks on that plan, so no
    request is served before it is due, also when the engine idles and
    jumps ahead to the next arrival's tick.  With ``trace``, the profiler
    runs over the last ``TRACE_SECONDS`` of the window."""

    def __init__(self, st: Setup, seconds: float, *, trace: bool,
                 pace: bool, log_dir: Path):
        self.st, self.seconds, self.dir = st, seconds, log_dir
        self.trace, self.pace = trace, pace
        self.w0: Optional[float] = None
        self.on = False
        self.traced = False
        self.started: Optional[float] = None
        self.paced_s = 0.0

    def _last_tick_end(self) -> float:
        for e in reversed(self.st.tracer.events):
            if e["name"] == "tick":
                return (e["ts"] + e["dur"]) * 1e-6
        return self.st.tracer.now() * 1e-6

    def __call__(self, tick: int) -> None:
        import jax
        L = self.st.lead_ticks
        if tick < L:
            return
        if self.w0 is None:
            self.w0 = self._last_tick_end()
        now = self.st.tracer.now() * 1e-6
        if self.pace:
            wait = self.w0 + (tick - L) * self.st.s_tick - now
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench_pacing"):
                    time.sleep(wait)
                self.paced_s += wait
                now += wait
        if not self.trace or self.traced:
            return
        span = min(TRACE_SECONDS, self.seconds)
        if not self.on and now >= self.w0 + self.seconds - span:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.started = now
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # host C++ events only
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.on = self.st.tracer.profiling = True
        elif self.on and now >= self.w0 + self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.on:
            self.st.tracer.profiling = False
            jax.profiler.stop_trace()
            self.on = False
            self.traced = True


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, peaks: dict, device_kind: str,
             devices, model: Optional[dict] = None,
             scratch: Path = ROOT / "chiprun_out" / "bench",
             control: bool = False) -> dict:
    """Set up, measure, check; return the result line (a dict) and print
    the lines that lead to it on stderr.  ``control``: also judge the
    reference in fp8 in the program's place (``result["control"]``)."""
    import jax
    t_built = time.perf_counter()
    st = build(cell, seed, model)
    tracer_offset = time.perf_counter() - st.tracer.now() * 1e-6  # -> perf
    t_warm = time.perf_counter()
    keys = base_keys(seed)
    warm_up(st, keys)
    t_lead = time.perf_counter()
    log(f"set-up: process start to weights {t_built - t_process:.3f} s, "
        f"weights and server {t_warm - t_built:.3f} s, warm-up call "
        f"{t_lead - t_warm:.3f} s")
    classes, arrivals, offsets = plan_traffic(st, seconds)
    prof = TickHook(st, seconds, trace=trace, pace=offsets is not None,
                    log_dir=scratch / f"profile_{os.getpid()}")
    st.hooks["tick"] = prof
    call = serve(st, classes, arrivals, keys["serve"])
    prof.stop()
    st.hooks["tick"] = _noop
    mix, serving = cell.mix, cell.config["serving"]
    steps = mix["num_steps"]

    # ---- the window ---------------------------------------------------
    comp = compile_ticks(st, call)
    lead = [t for t in call.ticks if t.tick < st.lead_ticks] + comp
    w0 = max(t.end for t in lead)
    win, w1 = win_lib.window_ticks(call.ticks, w0, seconds)
    in_window = st.compiles.between(w0, w1)
    log(f"window: {w0 - call.t_start:.3f} s after the call began, "
        f"{w1 - w0:.3f} s, {len(win)} ticks; compiles inside it: "
        f"{len(in_window)}")
    for t, dur, fn in in_window:
        log(f"  compile in window at +{t - w0:.3f} s: {fn} ({dur:.3f} s)")
    setup_s = (w0 + tracer_offset) - t_process
    done = win_lib.done_times(call.ticks, call.admits, steps)
    finite = {rid for rid, x in call.out.items() if np.isfinite(x).all()}
    if offsets is not None:
        due = {i + 1: w0 + a for i, a in enumerate(offsets)}
        attempted = sorted(due)
    else:
        due = {}
        by_tick = {t.tick: t for t in call.ticks}
        attempted = sorted(
            a.rid for a in call.admits
            if by_tick[a.tick].start < w1 and done.get(a.rid, w1) > w0)
    failed = [r for r in attempted if r not in finite or r not in done]

    metrics: Dict[str, dict] = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}

    def put(name, value):
        if value is not None and name in units:
            metrics[name] = {"value": float(value), "unit": units[name]}

    if not trace:
        put("setup_s", setup_s)
        if offsets is None:
            put("images_per_s", win_lib.images_per_s(win, w0, w1,
                                                     call.admits, steps))
        else:
            lat, _ = win_lib.latencies(due, done)
            put("latency_p90_s", win_lib.percentile(lat, 90))
            put("latency_p50_s", win_lib.percentile(lat, 50))
            start = {t.tick: t.start for t in call.ticks}
            early = max((due[a.rid] - start[a.tick] for a in call.admits
                         if a.rid in due), default=0.0)
            log(f"open loop: {len(due)} due in the window, {len(lat)} "
                f"finished; ticks held back to the arrival clock "
                f"{prof.paced_s:.3f} s in all; latest start of service "
                f"before its due time {early:.6f} s")
    mem = peak_bytes(devices)

    # ---- the trace ----------------------------------------------------
    record = Record(ticks=call.ticks, admits=call.admits, w0=w0, w1=w1,
                    window=win, due=due, done=done, num_steps=steps,
                    chips=cell.chips,
                    flops_per_lane_step=flops_lib.per_lane_step(
                        st.model, mix["guidance"] != 1.0),
                    peak_flops=peaks["bf16_flops_per_s"],
                    quiet_until=(prof.started if prof.started is not None
                                 else math.inf))
    device = {"platform": devices[0].platform, "kind": device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    breakdown = None
    if trace and prof.dir.exists():
        try:
            p = tr.load_profile(str(prof.dir))
        except FileNotFoundError:
            p = None
        shutil.rmtree(prof.dir, ignore_errors=True)
        if p is not None and p.devices:
            off = _trace_offset(p, call.ticks)
            p = _shift(p, off)
            tw = tr.tick_window(p, [t.tick for t in call.ticks])
            record.trace, record.trace_window = p, tw
            if tw is not None:
                t0, t1, _ = tw
                busy = tr.busy_s(p, t0, t1)
                device["busy_s"] = sum(busy.values()) / len(busy)
                device["window_s"] = t1 - t0
                breakdown = {"device_ops": tr.top_ops(p, t0, t1),
                             "idle_gaps": tr.idle_gaps(p, t0, t1)}
            scratch.mkdir(parents=True, exist_ok=True)
            tr.write_json(_trim(p, tw), str(scratch / "profile_last.json"))
    if trace:
        for m in cell.per_layer:
            put(m["name"], load_reader(m["name"])(record))

    # ---- after the window: state freed, then the checks ---------------
    st.server = None
    ref = load_reference(cell.config)
    checks, stale, control_checks = check_outputs(
        st, call, ref, keys["serve"], w0, w1, attempted, failed, done,
        staleness=("stale_rel_err" in units and not trace and not control),
        control=control)
    put("stale_rel_err", stale)
    correct = decide(checks)
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control_checks is not None:
        result["control"] = {"correct": decide(control_checks),
                             "checks": control_checks}
        for name, c in control_checks.items():
            log(f"control {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    for name, c in checks.items():
        log(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    result["checks"] = checks
    return result


def _trace_offset(p: tr.Profile, ticks: List[win_lib.Tick]) -> float:
    """tracer clock - profile clock, from the ticks both logged."""
    d = [t.start - p.ticks[t.tick][0] for t in ticks if t.tick in p.ticks]
    return float(np.median(d)) if d else 0.0


def _shift(p: tr.Profile, off: float) -> tr.Profile:
    return tr.Profile(
        devices={k: [tr.Op(o.start + off, o.end + off, o.name) for o in v]
                 for k, v in p.devices.items()},
        host=[(a + off, b + off, n, ln) for a, b, n, ln in p.host],
        ticks={k: (a + off, b + off) for k, (a, b) in p.ticks.items()})


def _trim(p: tr.Profile, tw, ticks: int = 2) -> tr.Profile:
    """The first ``ticks`` traced ticks of a profile (a small record)."""
    if tw is None:
        return tr.Profile()
    keep = tw[2][:ticks]
    a, b = p.ticks[keep[0]][0], p.ticks[keep[-1]][1]
    return tr.Profile(
        devices={k: [o for o in v if a <= o.start < b]
                 for k, v in p.devices.items()},
        host=[h for h in p.host if a <= h[0] < b],
        ticks={k: p.ticks[k] for k in keep})


# ---------------------------------------------------------------------------
# correctness and the staleness cost
# ---------------------------------------------------------------------------
def window_cohorts(st: Setup, call: Call, w0: float, w1: float,
                   done: Dict[int, float]) -> List[Tuple[int, List[int]]]:
    """(admitting tick, rids in lane order) of every whole cohort (every
    lane admitted at one tick) that finished inside the window."""
    B = st.cell.config["serving"]["max_batch"]
    by_tick: Dict[int, List[win_lib.Admit]] = {}
    for a in call.admits:
        by_tick.setdefault(a.tick, []).append(a)
    return [(tk, [a.rid for a in sorted(adm, key=lambda a: a.lane)])
            for tk, adm in sorted(by_tick.items()) if len(adm) == B
            and all(w0 <= done.get(a.rid, -1) <= w1 for a in adm)]


def check_sample(st: Setup, call: Call, w0: float, w1: float,
                 done: Dict[int, float], attempted: List[int]
                 ) -> Tuple[List[int], Optional[int]]:
    """The requests the check compares, and the tick the replay starts at
    (None: the call's first tick).

    A backlog serves whole cohorts: the seed draws one cohort that
    finished inside the window, replayed from its admission.  Otherwise
    ``check_requests`` of the requests due in the window, the earliest
    done, one per lane where it can, replayed from the call's start
    (lanes share expert capacity, so every lane's history counts)."""
    if st.cell.mix["arrivals"] == "backlog":
        cohorts = window_cohorts(st, call, w0, w1, done)
        if not cohorts:
            raise RuntimeError("no whole cohort finished inside the window")
        tk, rids = cohorts[int(traffic_lib.rng(st.seed, 7).integers(
            len(cohorts)))]
        return sorted(rids), tk
    # one request per lane first, the earliest done, so the sample spans
    # as many lanes as the window filled; then the earliest of the rest
    k = st.cell.mix["check_requests"]
    lane = {a.rid: a.lane for a in call.admits}
    early = sorted((r for r in attempted if r in done), key=done.get)
    pick, seen = [], set()
    for r in early:
        if lane[r] not in seen and len(pick) < k:
            pick.append(r)
            seen.add(lane[r])
    pick += [r for r in early if r not in pick][:k - len(pick)]
    return sorted(pick), None


def limits_of(cell: Cell) -> Dict[str, float]:
    """The configuration's limit of each compared reading: one number, or
    one per traffic mix (the mixes serve different lane histories)."""
    return {k: v if isinstance(v, (int, float)) else v[cell.traffic]
            for k, v in cell.config["correct"].items()}


def decide(checks: Dict[str, dict]) -> bool:
    """``correct``: every compared reading within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def check_outputs(st: Setup, call: Call, ref, key, w0, w1, attempted,
                  failed, done, *, staleness: bool = True,
                  control: bool = False):
    """The checks of ``correct``, and (``staleness``) the staleness cost
    of a backlog.

    The compared reading, ``latent_gap``: the worst checked request's
    rel. L2 of its served latent against the reference's, both taken as
    displacements from the request's noise.  With ``control``, the
    checks are also made with the reference in fp8 put in the program's
    place (the same served run replayed at the precision below bf16),
    and returned as a third value; the control's checks must fail."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mix, serving, m = st.cell.mix, st.cell.config["serving"], st.model
    want, start = check_sample(st, call, w0, w1, done, attempted)
    served = ref.Served(
        ticks=[(t.tick, t.slotted) for t in call.ticks],
        admissions={})
    for a in call.admits:
        served.admissions.setdefault(a.tick, []).append(
            (a.lane, a.rid, call.classes[a.rid]))
    sched = ref.Schedule(name=serving["schedule"],
                         warmup_steps=serving["warmup_steps"],
                         sync_fraction=serving["sync_fraction"],
                         cond_stride=serving["cond_stride"],
                         capacity_groups=serving.get("ep", 1))
    sharding = (NamedSharding(st.mesh, P()) if st.mesh is not None
                else None)
    limits = limits_of(st.cell)
    noise_key = jax.random.split(key)[0]

    def replay(fp8):
        t0 = time.perf_counter()
        try:
            got = ref.replay(st.weights, m, sched, served, key=key,
                             num_steps=mix["num_steps"],
                             guidance=mix["guidance"],
                             max_batch=serving["max_batch"], want=want,
                             start_tick=start, sharding=sharding, fp8=fp8)
        except ref.ReplayMismatch as e:
            log(f"replay refused the logged run: {e}")
            return None
        log(f"reference{' in fp8' if fp8 else ''}: {len(got)} requests "
            f"replayed in {time.perf_counter() - t0:.3f} s")
        return got

    def judge(out, got):
        gap = 0.0
        for rid in want:
            if got is None or rid not in got or rid not in out:
                gap = math.inf
                continue
            x0 = np.asarray(ref.request_noise(noise_key, rid, m))
            gap = max(gap, rel_l2(out[rid] - x0, got[rid] - x0))
        return {"latent_gap": {"value": gap,
                               "limit": limits["latent_gap"]},
                "unfinished": {"value": float(len(failed)), "limit": 0.0},
                "replay_mismatch": {"value": float(got is None),
                                    "limit": 0.0}}

    got = replay(False)
    checks = judge(call.out, got)
    control_checks = None
    if control:
        control_checks = judge(replay(True) or {}, got)
    stale = None
    if staleness and mix["arrivals"] == "backlog":
        stale = stale_rel_err(st, call, key, window_cohorts(
            st, call, w0, w1, done))
    return checks, stale, control_checks


def stale_rel_err(st: Setup, call: Call, key,
                  cohorts: List[Tuple[int, List[int]]]) -> Optional[float]:
    """Rel. L2 of the final latents of every cohort that finished inside
    the window against the same requests served with the synchronous
    schedule on the same weights and noise, cohort by cohort in the same
    lanes (so the same tokens share expert capacity)."""
    order = [r for _, rids in cohorts for r in rids]
    if not order or not all(r in call.out for r in order):
        return None
    t0 = time.perf_counter()
    sync = serve(st, [call.classes[r] for r in order], None, key,
                 schedule="sync", rids=order)
    err = rel_l2(np.stack([call.out[r] for r in order]),
                 np.stack([sync.out[r] for r in order]))
    log(f"sync baseline: {len(cohorts)} cohorts, {len(order)} requests in "
        f"{time.perf_counter() - t0:.3f} s")
    return err
