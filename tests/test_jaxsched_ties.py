"""The jax brokers break relative-load ties as the sequential policies do.

In the paper's Table 1 world queued work is a whole number of 60e9-op
jobs and CPUs run at 1e9..4e9 op/s, so two sites often tie exactly in
float64 (one job on a 1e9 CPU, three on a 3e9 one). Queued work and
capacity rounded to float32 and divided there split such ties; the
brokers must not, and must still send a tie to the lowest site id.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import GridConfig, build_catalog, build_topology, generate_jobs
from repro.core.jaxsched import JaxLeastLoadedBroker, JaxScheduler
from repro.core.scheduler import make_scheduler

JOB_OPS = 60e9
LOW, HIGH = 2, 6        # the tied sites; the sequential key picks LOW

#: (jobs queued at LOW, its CPU in 1e9 op/s, jobs at HIGH, its CPU):
#: equal relative loads whose float32 quotients put LOW above HIGH
TIES = [(1, 1, 3, 3), (2, 2, 3, 3), (4, 4, 3, 3), (2, 1, 6, 3)]


def _float32_site_state(self):
    """The broker's site vectors as they were: queued work and capacity
    rounded to float32 and divided in float32, for the rank."""
    sites = self.topology.sites
    load = np.array([s.queued_work for s in sites], np.float32)
    cap = np.array([s.compute_capacity for s in sites], np.float32)
    return load / cap, np.array([s.online for s in sites], bool)


def _tied_world(tie):
    """8 sites; LOW and HIGH hold every file the jobs need and tie on
    relative load, every other site is busier."""
    n_low, c_low, n_high, c_high = tie
    cfg = GridConfig(n_regions=2, sites_per_region=4)
    topo = build_topology(cfg)
    cat = build_catalog(cfg, topo)
    for s in topo.sites:
        s.compute_capacity, s.queued_work = 1e9, 100 * JOB_OPS
    for site, n, c in ((LOW, n_low, c_low), (HIGH, n_high, c_high)):
        topo.sites[site].compute_capacity = c * 1e9
        topo.sites[site].queued_work = n * JOB_OPS
    assert (topo.sites[LOW].relative_load()
            == topo.sites[HIGH].relative_load())
    jobs = generate_jobs(cfg, 8)
    for job in jobs:
        for lfn in job.required:
            for site in (LOW, HIGH):
                if not cat.has_replica(lfn, site):
                    cat.add_replica(lfn, site)
    return cat, topo, jobs


def _sequential_and_batch(policy, tie):
    cat, topo, jobs = _tied_world(tie)
    broker = {"dataaware": JaxScheduler,
              "leastloaded": JaxLeastLoadedBroker}[policy](cat, topo)
    want = [make_scheduler(policy, cat, topo).select_site(j) for j in jobs]
    return want, broker.select_batch([j.required for j in jobs])


@pytest.mark.parametrize("policy", ["dataaware", "leastloaded"])
@pytest.mark.parametrize("tie", TIES)
def test_batch_broker_keeps_float64_load_ties(policy, tie):
    want, got = _sequential_and_batch(policy, tie)
    assert want == [LOW] * len(want)
    assert got == want


@pytest.mark.parametrize("policy", ["dataaware", "leastloaded"])
@pytest.mark.parametrize("tie", TIES)
def test_float32_loads_split_the_ties(monkeypatch, policy, tie):
    """The cases above catch the float32 path: under it the batch
    brokers send the tied jobs to HIGH."""
    monkeypatch.setattr(JaxScheduler, "site_state_np", _float32_site_state)
    want, got = _sequential_and_batch(policy, tie)
    assert got != want and set(got) == {HIGH}


def test_site_state_is_the_dense_rank_of_the_float64_load():
    cat, topo, _ = _tied_world(TIES[0])
    topo.sites[0].queued_work = 0.0
    topo.sites[7].online = False
    load, online = JaxScheduler(cat, topo).site_state_np()
    rel = [s.relative_load() for s in topo.sites]
    assert load.dtype == np.float32
    assert list(load) == [sorted(set(rel)).index(r) for r in rel]
    assert load[LOW] == load[HIGH] == 1.0
    assert list(online) == [True] * 7 + [False]
