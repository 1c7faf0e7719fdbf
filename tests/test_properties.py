"""Property-based tests (hypothesis) on the system's invariants."""

import pytest

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import (GridTopology, Job, ReplicaCatalog, StorageState,
                        make_scheduler, make_strategy)

GB = 1e9


def build_world(n_regions, sites_per_region, n_files, seed):
    topo = GridTopology(n_regions, sites_per_region,
                        lan_bandwidth=125e6, wan_bandwidth=1.25e6,
                        storage_capacity=4 * GB, seed=seed)
    cat = ReplicaCatalog()
    stor = StorageState(cat, topo)
    for i in range(n_files):
        # round-robin master placement: a stride that shares a factor with
        # n_sites would pile >4 masters (3.6 GB+) onto one 4 GB SE and make
        # the initial state itself violate the capacity invariant
        m = i % topo.n_sites
        cat.register_file(f"f{i}", 0.9 * GB, m)
        stor.bootstrap(m, f"f{i}")
    return topo, cat, stor


@settings(max_examples=40, deadline=None)
@given(
    n_regions=st.integers(2, 4),
    spr=st.integers(2, 5),
    n_files=st.integers(4, 12),
    strategy=st.sampled_from(["hrs", "bhr", "lru"]),
    ops=st.lists(st.tuples(st.integers(0, 11), st.integers(0, 19)),
                 min_size=1, max_size=60),
)
def test_storage_invariants_under_random_fetches(n_regions, spr, n_files,
                                                 strategy, ops):
    """Whatever sequence of fetches runs: SEs never overflow, masters are
    never destroyed, the catalog matches storage, pinned files survive."""
    topo, cat, stor = build_world(n_regions, spr, n_files, seed=1)
    strat = make_strategy(strategy, cat, topo, stor)
    now = 0.0
    for fi, si in ops:
        now += 1.0
        lfn = f"f{fi % n_files}"
        dst = si % topo.n_sites
        if stor.holds(dst, lfn):
            stor.touch(dst, lfn, now)
            continue
        plan = strat.plan_fetch(lfn, dst)
        # source must actually hold the file
        assert cat.has_replica(plan.lfn, plan.src)
        for victim in plan.evictions:
            assert stor.evictable(dst, victim)
            stor.remove(dst, victim)
        if plan.store:
            stor.add(dst, lfn, now)
        # invariants
        for s in topo.sites:
            assert s.used_storage <= s.storage_capacity + 1e-6
        for f in cat.files.values():
            assert cat.has_replica(f.lfn, f.master_site), "master destroyed"
            for h in cat.holders(f.lfn):
                assert stor.holds(h, f.lfn)


@settings(max_examples=30, deadline=None)
@given(
    replica_spread=st.lists(st.tuples(st.integers(0, 9), st.integers(0, 11)),
                            min_size=0, max_size=30),
    loads=st.lists(st.floats(0, 1e11), min_size=12, max_size=12),
    req=st.sets(st.integers(0, 9), min_size=1, max_size=6),
)
def test_scheduler_is_argmax_bytes_then_min_load(replica_spread, loads, req):
    """The paper's policy, checked against a brute-force oracle."""
    topo, cat, stor = build_world(3, 4, 10, seed=2)
    for fi, si in replica_spread:
        lfn = f"f{fi}"
        site = si % topo.n_sites
        if not cat.has_replica(lfn, site):
            cat.add_replica(lfn, site)
    for s, load in zip(topo.sites, loads):
        s.queued_work = load
    required = [f"f{i}" for i in sorted(req)]
    sched = make_scheduler("dataaware", cat, topo)
    pick = sched.select_site(Job(0, 0, required, 1.0))
    best = max(cat.bytes_at_site(required, s.site_id) for s in topo.sites)
    ties = [s.site_id for s in topo.sites
            if cat.bytes_at_site(required, s.site_id) == best]
    oracle = min(ties, key=lambda s: (topo.sites[s].relative_load(), s))
    assert pick == oracle
    assert cat.bytes_at_site(required, pick) == best


@settings(max_examples=20, deadline=None)
@given(
    data=st.data(),
)
def test_hrs_region_priority_property(data):
    """Whenever ANY replica exists in the destination's region, HRS never
    crosses the WAN (paper §3.3 top priority)."""
    topo, cat, stor = build_world(3, 3, 8, seed=3)
    # scatter extra replicas
    n_extra = data.draw(st.integers(0, 15))
    for _ in range(n_extra):
        fi = data.draw(st.integers(0, 7))
        si = data.draw(st.integers(0, topo.n_sites - 1))
        if not cat.has_replica(f"f{fi}", si):
            cat.add_replica(f"f{fi}", si)
            stor.bootstrap(si, f"f{fi}", now=0.0)
    strat = make_strategy("hrs", cat, topo, stor)
    fi = data.draw(st.integers(0, 7))
    dst = data.draw(st.integers(0, topo.n_sites - 1))
    lfn = f"f{fi}"
    if stor.holds(dst, lfn):
        return
    plan = strat.plan_fetch(lfn, dst)
    region = topo.region_of(dst)
    in_region = [h for h in cat.holders(lfn)
                 if topo.region_of(h) == region and h != dst]
    if in_region:
        assert not plan.inter_region
        assert plan.src in in_region


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_simulator_determinism(seed):
    from repro.core import GridConfig, run_experiment
    cfg = GridConfig(seed=seed % 7)
    a = run_experiment(cfg, strategy="hrs", n_jobs=30)
    b = run_experiment(cfg, strategy="hrs", n_jobs=30)
    assert a.avg_job_time == b.avg_job_time
    assert a.avg_inter_comms == b.avg_inter_comms


@settings(max_examples=8, deadline=None)
@given(
    n_regions=st.integers(2, 3),
    spr=st.integers(2, 4),
    n_jobs=st.integers(5, 30),
    strategy=st.sampled_from(["hrs", "bhr", "lru"]),
    seed=st.integers(0, 4),
)
def test_device_engine_matches_numpy(n_regions, spr, n_jobs, strategy, seed):
    """The batched ``device`` engine vs the bit-exact numpy oracle on
    random small worlds: integer results agree *exactly* (same jobs
    complete), continuous metrics agree within the eta-reconstruction
    tolerance (the engine rebuilds remaining bytes as rate * (eta - now),
    which drifts by ulps from stepwise integration — the honest fidelity
    break golden_tolerance.json pins on the paper grid)."""
    from repro.core import GridConfig, run_experiment
    cfg = GridConfig(n_regions=n_regions, sites_per_region=spr, seed=seed)
    a = run_experiment(cfg, strategy=strategy, n_jobs=n_jobs, net="numpy")
    b = run_experiment(cfg, strategy=strategy, n_jobs=n_jobs, net="device")
    assert b.completed_jobs == a.completed_jobs == n_jobs
    # same jobs completed, so equal averages mean equal inter-region totals
    assert b.avg_inter_comms == a.avg_inter_comms
    for metric in ("avg_job_time", "makespan", "total_wan_gb"):
        assert getattr(b, metric) == pytest.approx(getattr(a, metric),
                                                   rel=1e-9), metric


@settings(max_examples=6, deadline=None)
@given(
    n_jobs=st.integers(4, 24),
    strategy=st.sampled_from(["hrs", "lru"]),
    seed=st.integers(0, 3),
)
def test_device_engine_event_invariants(n_jobs, strategy, seed):
    """Engine invariants observed at every handled event of a batched
    run: the event clock never goes backwards, and no in-flight transfer
    is ever overdue by more than the done-epsilon (its cached completion
    time is honored — equivalently, no reconstructed backlog goes
    negative past the epsilon)."""
    from repro.core import GridConfig
    from repro.core.network import _DONE_EPS
    from repro.core.simulator import GridSimulator
    from repro.core.workload import build_catalog, build_topology, generate_jobs

    cfg = GridConfig(n_regions=2, sites_per_region=3, seed=seed)
    topo = build_topology(cfg)
    cat = build_catalog(cfg, topo)
    sim = GridSimulator(topo, cat, strategy=strategy, seed=seed, net="device")
    for info in cat.files.values():
        sim.storage.bootstrap(info.master_site, info.lfn)
    for j, job in enumerate(generate_jobs(cfg, n_jobs)):
        sim.submit_job(job, at=j * cfg.interarrival)

    import numpy as np
    clock = []
    orig_handle = sim._handle

    def spy(kind, payload):
        clock.append(sim.now)
        net = sim.network
        live = net.active & (net.rate > 0.0)
        overdue = net.rate[live] * (sim.now - net.eta[live])
        assert (overdue <= _DONE_EPS * (1 + 1e-12)).all()
        orig_handle(kind, payload)

    sim._handle = spy
    res = sim.run()
    assert clock == sorted(clock)
    assert len(res.records) == n_jobs
