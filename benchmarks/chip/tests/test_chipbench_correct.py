"""What decides ``correct``, on the CPU: the reference agrees with the
program's float64 host engine, the lower-precision control is judged not
correct, and a run with its timed path broken underneath is judged not
correct, once for each fault a cell can have."""

from __future__ import annotations

import json

import numpy as np
import pytest

from cpu_as_chip import HERE, cpu_as_chip, small

CELLS = ("paper_bulk50",)


def _config(cell):
    import run

    return run.load_cell(cell)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [0, 7, 123_456_789, 2 ** 31 + 5])
def test_reference_matches_the_host_engine_bit_for_bit(cell, seed):
    import dataclasses
    import run
    from reference import reference_run
    from repro.core import ScenarioSpec
    from repro.launch.experiments import run_spec

    spec = dict(small(_config(cell), n_jobs=150).spec_dict(), net="numpy")
    kept: list = []
    with run.kept_results(kept):
        run_spec(ScenarioSpec.from_dict(spec), seed=seed, n_jobs=150)
    got = kept[0]
    want = run.reference_side(reference_run(spec, seed, 150))
    assert got == want


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(cell):
    import compare
    from reference import reference_run
    import run

    c = _config(cell)
    spec, n = c.spec_dict(), c.traffic["n_jobs"]
    want = run.reference_side(reference_run(spec, 5, n))
    got = run.reference_side(reference_run(
        spec, 5, n, precision=c.config["control_precision"]))
    values = compare.worst([compare.readings(got, want)])
    assert not run.is_correct(run.beside_limits(c, values))


def _unchanged_flush(path, rem, rate, eta, link_bw, link_act, now,
                     backend="auto"):
    eta = np.asarray(eta, float)
    return (np.asarray(rem, float), np.asarray(rate, float), eta,
            float(eta.min(initial=np.inf)))


def _half_flush(flush):
    def half(path, rem, rate, eta, link_bw, link_act, now, backend="auto"):
        r, k, e, _ = flush(path, rem, rate, eta, link_bw, link_act, now,
                           backend="numpy")
        cut = len(r) // 2
        r[cut:], k[cut:], e[cut:] = rem[cut:], rate[cut:], eta[cut:]
        return r, k, e, float(e.min(initial=np.inf))
    return half


def _fault(monkeypatch, fault):
    """Break the timed path underneath the harness."""
    import repro.kernels.event_engine as ee
    from repro.core.scheduler import DataAwareScheduler

    if fault == "state_unchanged":
        monkeypatch.setattr(ee, "event_engine", _unchanged_flush)
    elif fault == "half_left_out":
        monkeypatch.setattr(ee, "event_engine", _half_flush(ee.event_engine))
    elif fault == "answer_altered":
        select = DataAwareScheduler.select_site
        placed = []

        def moved(self, job):
            site = select(self, job)
            placed.append(job)
            if len(placed) == 1:        # the first placement of the run
                site = (site + 1) % self.topology.n_sites
            return site

        monkeypatch.setattr(DataAwareScheduler, "select_site", moved)


def test_worlds_pool_is_the_scans_draw_in_order():
    import scan

    traffic = _config("paper_bulk50").traffic
    drawn = scan.drawn(12345, 245)
    left_out = [w for w in drawn if w not in traffic["worlds"]]
    assert [w for w in drawn if w not in left_out] == traffic["worlds"]
    assert len(left_out) == 4


@pytest.mark.parametrize("cell", CELLS)
def test_scan_holds_pool_worlds_off_the_chip(monkeypatch, cell):
    with cpu_as_chip(monkeypatch) as run:
        import scan

        c = small(run.load_cell(cell), n_jobs=20)
        worlds = c.traffic["worlds"][:2]
        found = scan.pool_of(c, worlds)
    assert found["departed"] == [] and found["pool"] == worlds
    assert {v[1] for v in found["pool_worst"].values()} <= set(worlds)
    assert found["pool_worst"]["jobs_lost"][0] == 0.0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    with cpu_as_chip(monkeypatch) as run:
        _fault(monkeypatch, fault)
        line = run.measure(small(run.load_cell(cell)), 2 ** 31 + 3, 0.1,
                           False)
    assert line["correct"] is (fault is None), json.dumps(line["checks"])
