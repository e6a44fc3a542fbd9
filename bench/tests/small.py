"""Runs of the harness at a size the CPU holds, for the tests.

The cell's own files are read; only the model's sizes are shrunk (the
same family, schedule and traffic), and the chip and its peak table are
stood in for: the tests drive everything a run does after that look."""
import json
import tempfile
import time
from pathlib import Path

from bench import harness

SMALL = dict(num_layers=2, d_model=128, num_heads=4, head_dim=32,
             moe_d_ff=128, shared_d_ff=256, patch_tokens=16, in_channels=4,
             num_classes=8,
             dtype="float32")


def cell_from_files(name: str) -> harness.Cell:
    """``<config>.<traffic>`` from its files alone, for a configuration
    that no entry of BENCHMARK.json runs yet (no metrics are reported)."""
    config, traffic = name.split(".")
    config = json.loads((harness.BENCH_DIR / "configs" / f"{config}.json")
                        .read_text())
    return harness.Cell(
        name=name, chips=int(config["serving"].get("ep", 1)), config=config,
        traffic=traffic,
        mix=json.loads(harness.traffic_path(traffic).read_text()),
        end_to_end=[], per_layer=[])


def small_cell(name: str):
    try:
        cell = harness.load_cell(name)
    except KeyError:
        cell = cell_from_files(name)
    ep = int(cell.config["serving"].get("ep", 1))
    model = dict(cell.model, **SMALL, num_experts=4 * ep)
    cell.mix = dict(cell.mix, num_classes=8)
    if "rate_per_s" in cell.mix:
        cell.mix["rate_per_s"] = 80.0
    return cell, model


def run_small(name: str, seed: int, *, seconds: float = 1.0,
              trace: bool = False, scratch: Path = None,
              control: bool = False, also: tuple = ()) -> dict:
    """One run of ``name`` shrunk; ``also``: end-to-end metrics the run
    reports besides the cell's own (entries ``{"name", "unit"}``)."""
    import jax
    t = time.perf_counter()
    cell, model = small_cell(name)
    cell.end_to_end = cell.end_to_end + list(also)
    return harness.run_cell(cell, seed, seconds, trace, t_process=t,
                            peaks={"bf16_flops_per_s": 1e12},
                            device_kind="cpu",
                            devices=jax.devices()[:cell.chips], model=model,
                            scratch=scratch or Path(tempfile.mkdtemp()),
                            control=control)
