"""The chip benchmark's harness, on the CPU: lookup by name, refusal off
the chip, the result line, the route check and the roofline bytes."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from cpu_as_chip import HERE, ROOT, cpu_as_chip, small

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "checks"}


@pytest.mark.parametrize("config", sorted(
    p.stem for p in (HERE / "configs").glob("*.json")))
def test_config_round_trips_through_the_program(config):
    from repro.core import ScenarioSpec

    d = json.loads((HERE / "configs" / f"{config}.json").read_text())
    assert d["name"] == config == d["spec"]["name"]
    assert len(d["source"]) <= 200
    spec = ScenarioSpec.from_dict(d["spec"])
    assert json.loads(json.dumps(spec.to_dict())) == d["spec"]


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    import run

    for sub in ("configs", "traffic", "limits", "metrics"):
        (tmp_path / sub).mkdir()
    cfg = json.loads((HERE / "configs" / "paper_table1.json").read_text())
    cfg["name"] = cfg["spec"]["name"] = "paper_copy"
    (tmp_path / "configs" / "paper_copy.json").write_text(json.dumps(cfg))
    traffic = {"name": "trickle", "spec": {"interarrival_s": 90.0,
                                           "arrival_burst": 2},
               "n_jobs": 8, "warm_slot_capacities": []}
    (tmp_path / "traffic" / "trickle.json").write_text(json.dumps(traffic))
    (tmp_path / "limits" / "copy_trickle.json").write_text(json.dumps(
        json.loads((HERE / "limits" / "paper_bulk50.json").read_text())))
    (tmp_path / "metrics" / "jobs_seen.py").write_text(
        "def read(w):\n    return float(w['jobs'])\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "copy_trickle", "config": "paper_copy",
                               "traffic": "trickle", "chips": 1, "why": "x"})
    bench["per_layer"].append({
        "name": "jobs_seen", "unit": "jobs", "better": "higher",
        "source": "program_counter", "layer": "entry",
        "moves": "sim_jobs_per_s", "workloads": ["copy_trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.load_cell("copy_trickle", tmp_path / "BENCHMARK.json",
                         tmp_path)
    assert cell.spec_dict()["interarrival_s"] == 90.0
    assert cell.spec_dict()["n_jobs"] == 8
    assert [m["name"] for m in cell.per_layer] == ["jobs_seen"]
    assert run.load_reader("jobs_seen", tmp_path)({"jobs": 8}) == 8.0


def test_off_chip_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "paper_bulk50", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "paper_bulk50", "--seed", "3", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_has_the_contract_keys(monkeypatch, traced):
    with cpu_as_chip(monkeypatch) as run:
        line = run.measure(small(run.load_cell("paper_bulk50")),
                           2 ** 31 + 7, 0.1, traced)
    keys = CONTRACT_KEYS | ({"breakdown"} if traced else set())
    assert set(line) == keys and list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] == 100 and line["failed"] == 0
    names = {m["name"] for m in (run.load_cell("paper_bulk50").per_layer
                                 if traced else
                                 run.load_cell("paper_bulk50").end_to_end)}
    device_only = {"device_idle_share", "event_engine_device_us",
                   "event_engine_roofline"}
    # the CPU trace holds no TPU plane: the device readers find nothing
    assert set(line["metrics"]) == (names - device_only if traced else names)
    assert json.loads(json.dumps(line)) == line


def test_flush_fallen_back_to_the_host_is_not_correct(monkeypatch):
    with cpu_as_chip(monkeypatch, kernel_route=False) as run:
        line = run.measure(small(run.load_cell("paper_bulk50")),
                           11, 0.1, False)
    assert line["checks"]["flush_on_host"]["value"] > 0
    assert line["correct"] is False


@pytest.mark.parametrize("slots,flushes,depth,links,want", [
    (1, 1, 3, 555, 36 + 4448),
    (196, 1, 3, 555, 196 * 36 + 4448),
    (469_260, 2_393, 3, 555, 469_260 * 36 + 2_393 * 4448),
    (50, 2, 2, 56, 50 * 32 + 2 * 456),
])
def test_event_engine_bytes(slots, flushes, depth, links, want):
    from roofline import event_engine_bytes

    assert event_engine_bytes(slots, flushes, depth=depth,
                              links=links) == want


def test_world_shape_counts_links_of_the_tree():
    import run

    paper = json.loads((HERE / "configs" / "paper_table1.json").read_text())
    assert run.world_shape(paper["spec"]) == {
        "sites": 52, "links": 56, "depth": 2, "files": 100}
    grid = {"tier_fanouts": [5, 10, 10], "catalog_gb": 500.0,
            "file_size_mb": 500.0}
    assert run.world_shape(grid) == {
        "sites": 500, "links": 555, "depth": 3, "files": 1000}


def test_peaks_table_refuses_an_unknown_device():
    import run

    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")


def _worlds(run, capsys, seed):
    run.measure(small(run.load_cell("paper_bulk50"), n_jobs=10), seed, 0.1,
                False)
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"run"')]
    return [r["run"]["seed"] for r in rows]


def test_worlds_are_drawn_from_the_seed(monkeypatch, capsys):
    with cpu_as_chip(monkeypatch) as run:
        first = _worlds(run, capsys, 2 ** 33 + 1)
        again = _worlds(run, capsys, 2 ** 33 + 1)
        other = _worlds(run, capsys, 2 ** 33 + 2)
    n = min(len(first), len(again), len(other))
    assert n >= 1 and first[:n] == again[:n]
    assert other[:n] != first[:n]
    pool = json.loads((HERE / "traffic" / "diana_bulk50.json").read_text())
    assert set(first + other) <= set(pool["worlds"])
