"""The bulk broker's cell, ``paper_diana_bulk``, on the CPU: what decides
its ``correct`` (the reference, the control, the pool and the faults it
must catch) and the readers of the batch broker's metrics."""

from __future__ import annotations

import json

import numpy as np
import pytest

import test_chipbench_correct as correct
from cpu_as_chip import HERE, cpu_as_chip, small
from test_chipbench_host_stages import PARTS

CELL = "paper_diana_bulk"


@pytest.mark.parametrize("seed", [0, 7, 123_456_789, 2 ** 31 + 5])
def test_bulk_reference_matches_the_host_engine_bit_for_bit(seed):
    correct.test_reference_matches_the_host_engine_bit_for_bit(CELL, seed)


def test_bulk_control_in_the_programs_place_is_not_correct():
    correct.test_control_in_the_programs_place_is_not_correct(CELL)


def test_bulk_scan_holds_pool_worlds_off_the_chip(monkeypatch):
    correct.test_scan_holds_pool_worlds_off_the_chip(monkeypatch, CELL)


def test_bulk_pool_is_the_scans_whole_draw():
    """No world of the draw is left out: the flush and the broker keep
    every float64 tie, so every world runs as the reference does."""
    import scan

    traffic = correct._config(CELL).traffic
    assert traffic["worlds"] == scan.drawn(12345, 48)


def _tie_to_highest(select):
    """The batch broker with the site axis reversed: a tie goes to the
    highest site id."""
    def reversed_sites(presence, sizes, masks, load_rank, capacity, online):
        picks = select(presence[::-1], sizes, masks, load_rank[::-1],
                       capacity[::-1], online[::-1])
        return len(online) - 1 - np.asarray(picks)
    return reversed_sites


def _fault(monkeypatch, fault):
    """Break the bulk broker's timed path underneath the harness; the
    flush's faults are those of ``test_chipbench_correct``."""
    import repro.core.jaxsched as jaxsched
    from repro.core.simulator import GridSimulator

    if fault == "snapshot_refreshed":
        # each job of a burst scored after the previous one was placed
        monkeypatch.setattr(GridSimulator, "_dispatch_batch",
                            lambda self, batch: [self._schedule(job)
                                                 for job in batch])
    elif fault == "tie_to_highest":
        monkeypatch.setattr(jaxsched, "select_sites_batch",
                            _tie_to_highest(jaxsched.select_sites_batch))
    elif fault == "job_left_out":
        dispatch = GridSimulator._dispatch_batch
        monkeypatch.setattr(GridSimulator, "_dispatch_batch",
                            lambda self, batch: dispatch(self, batch[:-1]))
    else:
        correct._fault(monkeypatch, fault)


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_left_out",
                                   "snapshot_refreshed", "tie_to_highest",
                                   "job_left_out"])
def test_broken_bulk_broker_is_not_correct(monkeypatch, fault):
    """Two groups of 50 on the bulk broker's cell: the sound run is
    correct; a flush that returns its state, a flush that leaves half its
    slots unchanged, a group scored against a snapshot refreshed after
    each placement, ties sent to the highest site id and a job of each
    group left out are not."""
    with cpu_as_chip(monkeypatch) as run:
        _fault(monkeypatch, fault)
        line = run.measure(small(run.load_cell(CELL)), 2 ** 31 + 11, 0.1,
                           False)
    assert line["correct"] is (fault is None), json.dumps(line["checks"])


@pytest.mark.parametrize("calls,jobs,sites,files,want", [
    (1, 1, 1, 1, 1 + 4 + 9 + 1 + 4),
    (1, 5, 500, 1_000, 508_500 + 5 * 1_004),
    (10, 500, 52, 100, 10 * 6_068 + 500 * 104),
])
def test_broker_batch_bytes(calls, jobs, sites, files, want):
    from roofline_broker import broker_batch_bytes, broker_batch_flops

    assert broker_batch_bytes(calls, jobs, sites=sites, files=files) == want
    assert broker_batch_flops(jobs, sites=sites, files=files) == (
        2 * jobs * sites * files)


def test_select_batch_reader():
    import run

    read = run.load_reader("select_batch_us")
    parts = {"broker.batch.stage_ns": 600_000, "broker.batch.launch_ns":
             300_000, "broker.batch.fetch_ns": 100_000}
    w = {"counters": {**parts, "broker.batch_calls": 4}}
    assert read(w) == pytest.approx(250.0)      # us per batch call
    assert read({"counters": parts}) is None
    assert read({"counters": {"broker.batch_calls": 4}}) is None


def test_traced_bulk_run_reports_the_broker(monkeypatch):
    with cpu_as_chip(monkeypatch) as run:
        line = run.measure(small(run.load_cell(CELL)), 2 ** 32 + 13, 0.1,
                           True)
    metrics = line["metrics"]
    assert line["correct"] is True
    assert metrics["select_batch_us"]["value"] > 0
    for name in PARTS:
        assert metrics[name]["value"] > 0
    # the CPU trace holds no TPU plane: the device readers find nothing
    assert "broker_batch_device_us" not in metrics
    assert "broker_batch_roofline" not in metrics


def test_broker_readers_on_the_recorded_trace():
    """The batch broker's device time and roofline share, read from the
    recorded trace's one ``jit_select_sites_batch`` (a burst of 5 jobs
    over 500 sites and 1 000 files)."""
    import run
    from trace_reduce import reduce_trace

    data = HERE / "testdata"
    expect = json.loads((data / "g500thin_5jobs.json").read_text())
    got = reduce_trace(str(data / "g500thin_5jobs.xplane.pb"))
    w = {"trace": got, "counters": {"broker.batch_calls": 1,
                                    "broker.batch_jobs": 5},
         "world": {"sites": 500, "files": 1000},
         "peaks": run.peaks_for("TPU v5 lite")}
    device_us = run.load_reader("broker_batch_device_us")(w)
    share = run.load_reader("broker_batch_roofline")(w)
    ns, n = expect["programs"]["jit_select_sites_batch"]
    assert device_us == pytest.approx(ns / n / 1e3)
    # bandwidth-bound: 513 520 bytes at 819 GB/s over the program's time
    assert share == pytest.approx(100 * 513_520 / 819e9 / (ns * 1e-9))
    assert 0 < share < 100
    for missing in (dict(w, trace=None), dict(w, counters={})):
        assert run.load_reader("broker_batch_roofline")(missing) is None
    assert run.load_reader("broker_batch_device_us")(
        dict(w, trace=None)) is None
