"""The readers of the engine's own spans: ``admit_ms``, ``readback_ms`` and
``dispatch_ms`` on a hand-made profile (values computed by hand), None
where the profile holds no such span, and values on a real profiler
capture of the engine on the CPU."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

READERS = ("admit_ms", "readback_ms", "dispatch_ms")


def record(prof, window):
    return harness.Record(ticks=[], admits=[], w0=0.0, w1=3.0, window=[],
                          due={}, done={}, num_steps=10, chips=1,
                          flops_per_lane_step=1, peak_flops=1.0,
                          trace=prof, trace_window=window)


def hand_profile():
    """Four traced ticks in [1, 2] s; spans before, inside and across the
    window's edges, and a runtime event that no reader may count."""
    host = [
        (0.90, 0.95, "serve.admit", "python"),      # before the window
        (1.20, 1.35, "serve.admit", "python"),      # 150 ms
        (1.60, 1.70, "serve.admit", "python"),      # 100 ms
        (1.98, 2.05, "serve.admit", "python"),      # across the end
        (0.95, 1.02, "serve.readback", "python"),   # 20 ms inside
        (1.30, 1.33, "serve.readback", "python"),   # 30 ms
        (1.99, 2.10, "serve.readback", "python"),   # 10 ms inside
        (1.000, 1.007, "serve.dispatch", "python"),
        (1.250, 1.256, "serve.dispatch", "python"),
        (1.500, 1.508, "serve.dispatch", "python"),
        (1.750, 1.759, "serve.dispatch", "python"),
        (1.001, 1.006, "PjitFunction(rf_step)", "python"),
    ]
    return tr.Profile(devices={0: [tr.Op(1.0, 2.0, "fusion.1 (fusion)")]},
                      host=host)


def test_readers_on_a_hand_made_profile():
    rec = record(hand_profile(), (1.0, 2.0, [10, 11, 12, 13]))
    got = {n: harness.load_reader(n)(rec) for n in READERS}
    # admit: mean of the two whole spans; the others: clipped total over
    # the four ticks
    assert got["admit_ms"] == pytest.approx(125.0)
    assert got["readback_ms"] == pytest.approx((20 + 30 + 10) / 4)
    assert got["dispatch_ms"] == pytest.approx((7 + 6 + 8 + 9) / 4)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_none_without_spans(name):
    read = harness.load_reader(name)
    bare = tr.Profile(devices={0: [tr.Op(1.0, 2.0, "fusion.1 (fusion)")]},
                      host=[(1.0, 1.5, "PjitFunction(rf_step)", "python")])
    assert read(record(bare, (1.0, 2.0, [1, 2]))) is None
    assert read(record(None, None)) is None
    assert read(record(hand_profile(), None)) is None


def test_readers_on_a_cpu_capture_of_the_engine(tmp_path):
    """The engine's spans, captured by ``jax.profiler`` on the CPU and
    loaded by ``load_profile``, give every reader a value; the tick
    marks come from the harness's own tracer."""
    import jax
    from repro.configs.dit_moe_xl import tiny
    from repro.core.schedules import DiceConfig
    from repro.launch.serve import DiceServer, Request, serve_continuous

    cfg = tiny().replace(name="bench-spans", num_layers=2, d_model=32,
                         d_ff=64, num_heads=2, num_kv_heads=2, head_dim=16,
                         moe_d_ff=32, patch_tokens=8)
    server = DiceServer(cfg, DiceConfig.dice(), seed=0)
    tracer = harness.make_tracer(lambda tick: None)
    tracer.profiling = True
    server.tracer = tracer
    reqs = [Request(class_id=i % cfg.num_classes, rid=i) for i in range(8)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve_continuous(server, reqs, max_batch=4, num_steps=4)
    finally:
        jax.profiler.stop_trace()
    prof = tr.load_profile(str(tmp_path))
    window = tr.tick_window(prof, sorted(prof.ticks))
    assert window is not None and len(window[2]) == 8
    rec = record(prof, window)
    got = {n: harness.load_reader(n)(rec) for n in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the launch lies inside a tick: less than a tick's mean length
    t0, t1, ticks = window
    assert got["dispatch_ms"] < 1e3 * (t1 - t0) / len(ticks)
