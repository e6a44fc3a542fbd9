"""The yardstick: trace reduction, required FLOPs, traffic generation and
the window and latency arithmetic, each against a hand count."""
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from bench import traffic  # noqa: E402
from bench import window as W  # noqa: E402


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def _hand_profile():
    """Two chips over [0, 10] s.  Chip 0: compute 0-4, all-to-all 3-6
    (1 s hidden under compute, 2 s exposed), compute 7-9.  Chip 1:
    all-to-all 1-2 alone, compute 2-5.  Host: a tick 0-5, a read-back
    6-7 between ticks, the next tick 7-10."""
    return tr.Profile(
        devices={0: [tr.Op(0, 4, "fusion.1"), tr.Op(3, 6, "all-to-all.2"),
                     tr.Op(7, 9, "fusion.1")],
                 1: [tr.Op(1, 2, "all-to-all.2"), tr.Op(2, 5, "fusion.3")]},
        host=[(6.0, 7.0, "readback", "python"),
              (5.0, 9.5, "PjitFunction(step)", "python")],
        ticks={0: (0.0, 5.0), 1: (7.0, 10.0)})


def test_busy_and_idle_by_hand():
    p = _hand_profile()
    assert tr.busy_s(p, 0, 10) == {0: 8.0, 1: 4.0}
    assert tr.busy_s(p, 2, 8) == {0: 5.0, 1: 3.0}


def test_collective_and_exposed_by_hand():
    p = _hand_profile()
    assert tr.collective_s(p, 0, 10) == {0: (3.0, 2.0), 1: (1.0, 1.0)}


def test_breakdown_by_hand():
    p = _hand_profile()
    ops = dict(tr.top_ops(p, 0, 10))
    assert ops["fusion.1"] == pytest.approx(6.0 / 2)
    assert ops["all-to-all.2"] == pytest.approx(4.0 / 2)
    gaps = tr.idle_gaps(p, 0, 10)
    assert gaps[0] == ["between ticks: readback", 1.0]
    assert gaps[1] == ["in a tick: PjitFunction(step)", 1.0]
    assert len(gaps) == 2


def test_interval_helpers():
    assert tr.union([(3, 5), (0, 1), (4, 6)], 0, 10) == [(0, 1), (3, 6)]
    assert tr.union([(0, 4)], 1, 3) == [(1, 3)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                       (6, 10)]
    assert tr.subtract([(0, 1), (4, 6)], [(0, 5)]) == [(5, 6)]


def test_recorded_trace():
    """A recorded stretch of a real chip trace (two ticks of
    ``xl_1chip.backlog`` on one TPU v5e): the reductions agree with a
    direct count over its events."""
    path = ROOT / "bench" / "testdata" / "trace_xl_two_ticks.json"
    p = tr.Profile.from_json(json.loads(path.read_text()))
    t0 = min(a for a, _ in p.ticks.values())
    t1 = max(b for _, b in p.ticks.values())
    busy = tr.busy_s(p, t0, t1)
    assert set(busy) == {0}
    ops = sorted((o.start, o.end) for o in p.devices[0])
    merged, cur = 0.0, None
    for a, b in ops:
        a, b = max(a, t0), min(b, t1)
        if b <= a:
            continue
        if cur is None or a > cur[1]:
            merged += 0 if cur is None else cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    merged += cur[1] - cur[0]
    assert busy[0] == pytest.approx(merged)
    assert 0 < busy[0] <= t1 - t0
    gaps = tr.idle_gaps(p, t0, t1)
    assert sum(g for _, g in gaps) <= (t1 - t0) - busy[0] + 1e-9
    top = tr.top_ops(p, t0, t1)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    assert tr.collective_s(p, t0, t1) == {0: (0.0, 0.0)}


# ---------------------------------------------------------------------------
# required FLOPs
# ---------------------------------------------------------------------------
def _model(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["model"]


def test_flops_xl_by_hand():
    m = _model("xl_1chip")
    d, f, T = 1152, 4608, 256
    attn = 4 * 2 * d * d + 2 * 2 * T * d           # projections + scores
    ffn = 2 * 3 * 2 * d * f                        # top-2 routed
    shared = 3 * 2 * d * (2 * d)                   # one FFN, 2 x d wide
    router = 2 * d * 8
    assert flops.per_token_layer(m) == attn + ffn + shared + router
    assert flops.per_token_layer(m) == pytest.approx(91.44e6, rel=1e-3)
    tick = 8 * flops.per_lane_step(m)              # 8 lanes, guided
    assert tick == pytest.approx(10.49e12, rel=0.01)


def test_flops_g_by_hand():
    m = _model("g_ep4")
    d, f, T = 1408, 5632, 256
    per = (4 * 2 * d * d + 2 * 2 * T * d + 2 * 3 * 2 * d * f
           + 3 * 2 * d * (2 * d) + 2 * d * 16)
    assert flops.per_token_layer(m) == per
    img = (T * 10 * per + 10 * 2 * d * 6 * d + T * 2 * 16 * d
           + 2 * 256 * d + 2 * d * d + 2 * d * 2 * d + T * 2 * d * 16)
    assert flops.per_image_forward(m) == img
    assert flops.per_lane_step(m, guided=False) == img


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
MIX = {"arrivals": "poisson", "rate_per_s": 4.2, "gap_seed": 11,
       "num_classes": 1000}


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 3 * 10 ** 9])
def test_traffic_is_deterministic_per_seed(seed):
    a = traffic.arrival_offsets(MIX, seed, 30)
    assert a == traffic.arrival_offsets(MIX, seed, 30)
    assert traffic.classes(MIX, seed, 50) == traffic.classes(MIX, seed, 50)
    assert traffic.arrival_offsets(MIX, seed + 1, 30) != a


def test_traffic_rate_and_gaps_are_the_stated_ones():
    a = traffic.arrival_offsets(MIX, 1, 30)
    b = traffic.arrival_offsets(MIX, 2, 30)
    assert len(a) == len(b) == round(4.2 * 30)
    ga, gb = np.diff(a), np.diff(b)
    # the same gaps in another order: the mean rate is exactly the stated
    gaps = sorted(np.append(ga, 30 - a[-1]))
    assert np.allclose(gaps, sorted(np.append(gb, 30 - b[-1])))
    assert (len(a)) / 30 == pytest.approx(4.2, rel=1e-2)
    assert 0 == a[0] and a[-1] < 30
    c = traffic.classes(MIX, 7, 20000)
    assert min(c) >= 0 and max(c) < 1000
    assert abs(np.mean(c) - 499.5) < 10
    assert traffic.arrival_offsets({"arrivals": "backlog"}, 1, 30) is None


# ---------------------------------------------------------------------------
# window and latency arithmetic
# ---------------------------------------------------------------------------
def _steady(n, dt=0.125, gap=0.0, stall_at=None, stall=0.0):
    ticks, t = [], 0.0
    for k in range(n):
        if k == stall_at:
            t += stall
        ticks.append(W.Tick(k, t, t + dt))
        t += dt + gap
    return ticks


def _cohorts(lanes, steps, n):
    return [W.Admit(rid=c * lanes + i, lane=i, tick=c * steps, time=0.0)
            for c in range(n) for i in range(lanes)]


def test_images_per_s_by_hand():
    ticks = _steady(100)
    admits = _cohorts(8, 10, 10)
    win, w1 = W.window_ticks(ticks, 0.0, 5.0)
    assert len(win) == 40 and w1 == pytest.approx(5.0)
    # 8 lanes x 40 ticks / 10 steps = 32 images in 5 s
    assert W.images_per_s(win, 0.0, w1, admits, 10) == pytest.approx(6.4)
    assert W.host_gap_ms(win, 0.0, w1) == pytest.approx(0.0, abs=1e-9)


def test_a_stall_lowers_images_per_s_and_shows_as_host_gap():
    admits = _cohorts(8, 10, 10)
    base = _steady(100)
    slow = _steady(100, stall_at=20, stall=1.0)
    w_b, e_b = W.window_ticks(base, 0.0, 5.0)
    w_s, e_s = W.window_ticks(slow, 0.0, 5.0)
    assert W.images_per_s(w_s, 0.0, e_s, admits, 10) < \
        W.images_per_s(w_b, 0.0, e_b, admits, 10)
    # 20 ticks, the stall, 12 more ticks by 5 s
    assert W.images_per_s(w_s, 0.0, e_s, admits, 10) == pytest.approx(
        8 * 32 / 10 / 5.0)
    assert W.host_gap_ms(w_s, 0.0, e_s) == pytest.approx(1000.0 / 32)


def test_a_stall_raises_latency_p90():
    steps = 10
    admits = [W.Admit(rid=k, lane=k % 8, tick=2 * k, time=0.0)
              for k in range(40)]
    due = {k: 0.25 * k for k in range(40)}
    base = _steady(200)
    slow = _steady(200, stall_at=30, stall=2.0)
    lat_b, miss = W.latencies(due, W.done_times(base, admits, steps))
    lat_s, _ = W.latencies(due, W.done_times(slow, admits, steps))
    assert not miss
    # unstalled: each request runs ticks 2k..2k+9 and ends at 0.25k + 1.25
    assert lat_b == pytest.approx([1.25] * 40)
    assert W.percentile(lat_s, 90) == pytest.approx(3.25)
    assert W.percentile(lat_s, 90) > W.percentile(lat_b, 90)
    assert W.percentile(lat_s, 50) > W.percentile(lat_b, 50)


def test_percentile_and_missing():
    assert W.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert W.percentile([5], 90) == 5
    assert math.isnan(W.percentile([], 90))
    lat, miss = W.latencies({1: 0.0, 2: 0.0}, {1: 1.5})
    assert lat == [1.5] and miss == [2]
