"""The four-chip cell ``g_ep4.backlog``: what it reports, and its
collective readers on a hand-made four-chip profile (values by hand)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

PER_LAYER = {"host_gap_ms", "step_mfu", "device_idle_pct", "admit_ms",
             "readback_ms", "dispatch_ms", "a2a_ms", "a2a_exposed_ms"}


def test_the_cell_reports_its_metrics():
    cell = harness.load_cell("g_ep4.backlog")
    assert cell.chips == 4 == cell.config["serving"]["ep"]
    assert {m["name"] for m in cell.end_to_end} == {"images_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == PER_LAYER
    for m in cell.per_layer:
        assert m["moves"] == "images_per_s"
        assert callable(harness.load_reader(m["name"]))
    # one stage of the published model: only the depth is cut
    assert cell.config["reduced"] == ["num_layers"]
    assert cell.model["num_layers"] < cell.config["published"]["num_layers"]
    # the XL cell runs no collective and reports none
    xl = harness.load_cell("xl_1chip.backlog")
    assert not {"a2a_ms", "a2a_exposed_ms"} & {m["name"]
                                               for m in xl.per_layer}


def four_chip_profile():
    """Two traced ticks in [0, 10] ms on four chips.  Chips 0-2: compute
    0-4, an all-to-all 3-5 (1 ms under compute, 1 ms alone), compute
    5-9.  Chip 3, the slowest: compute 0-3, the all-to-all 3-7 alone,
    compute 7-9, a collective-permute 8-10 (1 ms under compute)."""
    ms = 1e-3
    devices = {c: [tr.Op(0, 4 * ms, "fusion.1 (fusion)"),
                   tr.Op(3 * ms, 5 * ms, "all-to-all.2 (all-to-all)"),
                   tr.Op(5 * ms, 9 * ms, "fusion.3 (fusion)")]
               for c in range(3)}
    devices[3] = [tr.Op(0, 3 * ms, "fusion.1 (fusion)"),
                  tr.Op(3 * ms, 7 * ms, "all-to-all.2 (all-to-all)"),
                  tr.Op(7 * ms, 9 * ms, "fusion.3 (fusion)"),
                  tr.Op(8 * ms, 10 * ms,
                        "collective-permute.4 (collective-permute)")]
    return tr.Profile(devices=devices,
                      ticks={0: (0.0, 5 * ms), 1: (5 * ms, 10 * ms)})


def _record(prof, window):
    return harness.Record(ticks=[], admits=[], w0=0.0, w1=0.01, window=[],
                          due={}, done={}, num_steps=10, chips=4,
                          flops_per_lane_step=1, peak_flops=1.0,
                          trace=prof, trace_window=window)


def test_collective_readers_on_a_four_chip_profile():
    rec = _record(four_chip_profile(), (0.0, 0.01, [0, 1]))
    a2a = harness.load_reader("a2a_ms")(rec)
    exposed = harness.load_reader("a2a_exposed_ms")(rec)
    # the worst chip (3): 4 + 2 ms in collectives over 2 ticks, of which
    # 4 + 1 ms with nothing else running
    assert a2a == pytest.approx(3.0)
    assert exposed == pytest.approx(2.5)


@pytest.mark.parametrize("name", ["a2a_ms", "a2a_exposed_ms"])
def test_collective_readers_give_none_without_collectives(name):
    ms = 1e-3
    bare = tr.Profile(devices={c: [tr.Op(0, 9 * ms, "fusion.1 (fusion)")]
                               for c in range(4)})
    read = harness.load_reader(name)
    assert read(_record(bare, (0.0, 0.01, [0, 1]))) is None
    assert read(_record(None, None)) is None
