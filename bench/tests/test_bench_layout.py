"""BENCHMARK.json is data: every name in it resolves to a file of its own,
and the file keeps to the benchmark's contract."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    assert (ROOT / bench["command"][1]).is_file()
    for p in bench["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= bench["run_seconds"] <= 51


def test_every_name_resolves(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]), c["name"]
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith("bench/")
        data = json.loads(path.read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
        assert (harness.BENCH_DIR / f"{data['reference']}.py").is_file()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert harness.traffic_path(w["traffic"]).is_file(), w["traffic"]
        assert w["chips"] in (1, 4)
        cell = harness.load_cell(w["name"], bench)
        assert cell.mix["num_steps"] > 0
    for m in bench["per_layer"]:
        assert harness.metric_path(m["name"]).is_file(), m["name"]
        assert callable(harness.load_reader(m["name"]))


def test_a_dangling_name_fails(bench, tmp_path):
    bad = json.loads(json.dumps(bench))
    bad["workloads"][0]["config"] = "no_such_config"
    with pytest.raises(KeyError):
        harness.load_cell(bad["workloads"][0]["name"], bad)
    assert not harness.metric_path("no_such_metric").exists()
    assert not harness.traffic_path("no_such_mix").exists()


def test_metrics_keep_to_the_contract(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        moved = next(x for x in bench["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer


def test_check_budget_fits(bench):
    """A full check with 24 cells fits the driver's allowance."""
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
