"""Tolerance-golden harness for the batched ``device`` engine.

Two-tier golden contract (docs/ARCHITECTURE.md):

* tier 1 — the incremental numpy backend is pinned *bit-exactly*
  by tests/test_golden_metrics.py against ``golden_metrics.json``;
* tier 2 — the batched ``device`` engine reconstructs remaining bytes
  from cached completion times (``rate * (eta - now)``) instead of
  integrating them stepwise. That is a deliberate, ulp-level fidelity
  break: this suite pins it inside per-metric *relative-error* bounds
  (``golden_tolerance.json``) measured against the tier-1 goldens over
  the full fig4/fig5 paper grid plus the deep_contended tree cell.

The bounds are asserted tight from both sides: each is ``headroom``x the
maximum drift observed at pinning time, and the slow full-grid sweep
also fails when the observed drift *improves* past 10x under its bound —
a vacuously loose tolerance is a stale contract, re-pin it instead.
``completed_jobs`` carries a zero bound: the engines must finish exactly
the same jobs everywhere.
"""

import dataclasses
import json
import os

import pytest

from repro.core import GridConfig, SCENARIOS, run_experiment
from repro.launch.experiments import run_spec

_HERE = os.path.dirname(__file__)
TOL = json.load(open(os.path.join(_HERE, "golden_tolerance.json")))
GOLDEN = json.load(open(os.path.join(_HERE, "golden_metrics.json")))["metrics"]
GOLDEN_DEEP = json.load(open(os.path.join(_HERE, "golden_deep.json")))

BOUNDS = TOL["bounds"]
METRICS = ("avg_job_time", "avg_inter_comms", "makespan")


def _rel(got: float, want: float) -> float:
    if got == want:
        return 0.0
    return abs(got - want) / max(abs(got), abs(want))


def _drift(key: str, net: str = "device") -> dict:
    """Run one golden cell under the batched engine; relative error per
    metric against the bit-exact numpy pin. completed_jobs is checked
    here (bound 0 = integer-exact on every cell)."""
    if key == "deep_contended":
        g = GOLDEN_DEEP["metrics"]
        spec = dataclasses.replace(SCENARIOS[GOLDEN_DEEP["scenario"]], net=net)
        r = run_spec(spec, n_jobs=GOLDEN_DEEP["n_jobs"])
        assert r.completed_jobs == g["completed_jobs"], key
    else:
        _, strategy, n = key.split("/")
        n = int(n)
        cfg = GridConfig(n_jobs=n) if key.startswith("fig5") else GridConfig()
        r = run_experiment(cfg, strategy=strategy, n_jobs=n, net=net)
        g = GOLDEN[key]
        assert r.completed_jobs == n, key
    return {m: _rel(getattr(r, m), g[m]) for m in METRICS}


def test_tolerance_file_shape():
    assert set(TOL["cells"]) >= set(TOL["fast_cells"])
    assert set(TOL["cells"]) == set(GOLDEN) | {"deep_contended"}
    assert BOUNDS["completed_jobs"] == 0.0
    for m in METRICS:
        assert 0.0 <= BOUNDS[m] < 1e-6, (m, "bound is not tight")


@pytest.mark.parametrize("key", TOL["fast_cells"])
def test_device_tolerance_fast_cells(key):
    for metric, err in _drift(key).items():
        assert err <= BOUNDS[metric], (key, metric, err)


@pytest.mark.slow
def test_device_tolerance_full_grid_and_bounds_stay_tight():
    """Every cell of the full grid inside its bound — and the pinned
    bounds still tight: observed max drift per metric at least bound/10
    (nonzero bounds only; a zero bound already demands exact equality)."""
    worst = {m: 0.0 for m in METRICS}
    for key in TOL["cells"]:
        for metric, err in _drift(key).items():
            assert err <= BOUNDS[metric], (key, metric, err)
            worst[metric] = max(worst[metric], err)
    for metric, w in worst.items():
        if BOUNDS[metric] > 0.0:
            assert w >= BOUNDS[metric] / 10.0, (
                metric, w, "drift improved past 10x headroom — re-pin "
                "golden_tolerance.json")


@pytest.mark.slow
def test_device_interpret_tolerance_cell():
    """One cell through the actual Pallas interpreter (x64): the fused
    flush *kernel* — not just its numpy oracle — keeps the run inside the
    tolerance contract. (Unlike the incremental backends this is not
    bit-identical to ``device``: the kernel route re-rates every slot on
    every flush, the host route only the dirty neighborhood, so their
    rounding histories differ — both must land inside the same bounds.)"""
    for metric, err in _drift("fig4/hrs/100", net="device-interpret").items():
        assert err <= BOUNDS[metric], (metric, err)
