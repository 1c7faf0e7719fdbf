"""Golden-metrics regression for the event engine.

``golden_metrics.json`` pins every fig4/fig5 cell (paper Table-1 grid) as
produced by the pre-refactor engine. The rebuilt hot paths (NetworkEngine
slot arrays with per-link path contention, incremental re-rating,
deque/tombstone queues, bisect LRU) are required to be *bit-identical* —
any drift here means the refactor changed simulation semantics, not just
speed. ``golden_deep.json`` pins one deep-tree cell so the mid-tier
path-contention semantics are regression-locked too. (The batched
``net="device"`` routes are held to these pins within tolerance bounds by
``tests/test_golden_tolerance.py``.)

Tier-1 checks a 6-cell subset; the full 18-cell grid runs under ``-m slow``.
"""

import json
import os

import pytest

from repro.core import GridConfig, run_experiment
from repro.launch.experiments import run_spec

_HERE = os.path.dirname(__file__)
GOLDEN = json.load(open(os.path.join(_HERE, "golden_metrics.json")))["metrics"]
GOLDEN_DEEP = json.load(open(os.path.join(_HERE, "golden_deep.json")))

FAST_CELLS = ["fig4/hrs/100", "fig4/bhr/100", "fig4/lru/100",
              "fig4/hrs/300", "fig4/bhr/300", "fig4/lru/300"]


def _check(key: str, net: str = "numpy") -> None:
    _, strategy, n = key.split("/")
    n = int(n)
    cfg = GridConfig(n_jobs=n) if key.startswith("fig5") else GridConfig()
    r = run_experiment(cfg, strategy=strategy, n_jobs=n, net=net)
    g = GOLDEN[key]
    assert r.avg_job_time == g["avg_job_time"], key
    assert r.avg_inter_comms == g["avg_inter_comms"], key
    assert r.total_wan_gb == g["total_wan_gb"], key
    assert r.makespan == g["makespan"], key
    assert r.completed_jobs == n, key


@pytest.mark.parametrize("key", FAST_CELLS)
def test_golden_fig4_subset(key):
    _check(key)


def test_golden_deep_tree_cell():
    """The deep-tree pin: deep_contended at 300 jobs under the per-link
    path model. Drift here means mid-tier contention semantics moved."""
    from repro.core import SCENARIOS
    g = GOLDEN_DEEP["metrics"]
    r = run_spec(SCENARIOS[GOLDEN_DEEP["scenario"]],
                 n_jobs=GOLDEN_DEEP["n_jobs"])
    assert r.avg_job_time == g["avg_job_time"]
    assert r.avg_inter_comms == g["avg_inter_comms"]
    assert r.total_wan_gb == g["total_wan_gb"]
    assert r.makespan == g["makespan"]
    assert r.completed_jobs == g["completed_jobs"]


@pytest.mark.slow
@pytest.mark.parametrize("key", sorted(set(GOLDEN) - set(FAST_CELLS)))
def test_golden_full_grid(key):
    _check(key)

