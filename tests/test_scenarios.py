"""Scenario engine: spec round-trip, n-tier topology invariants, registry
completeness (scenarios, sweeps, and the full strategy registry), and a
2-scenario smoke through the runner."""

import json
import math

import pytest

from repro.core import (GridConfig, GridTopology, SCENARIOS, STRATEGIES,
                        SWEEPS, ScenarioSpec, SweepSpec, arrival_schedule,
                        get_scenario, get_sweep, to_grid_config, with_axis)
from repro.core.scenarios import ChurnSpec
from repro.fault.failures import churn_schedule


# -- registry ---------------------------------------------------------------
def test_registry_completeness():
    assert len(SCENARIOS) >= 8
    # the regimes the scenario engine exists to cover
    for name in ("paper_baseline", "deep_4tier", "deep_5tier", "fat_region",
                 "flash_crowd", "diurnal", "bulk_diana", "site_churn",
                 "cache_starved", "grid_500"):
        assert name in SCENARIOS, name
    for name, spec in SCENARIOS.items():
        assert spec.name == name
        assert spec.description and spec.probes, f"{name} is undocumented"
        # every registered spec must build a world without errors
        topo = __import__("repro.core", fromlist=["build_topology"]) \
            .build_topology(to_grid_config(spec))
        assert topo.n_sites == spec.n_sites


def test_get_scenario_unknown_name():
    with pytest.raises(KeyError, match="registered"):
        get_scenario("nope")


@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_every_strategy_runs_paper_baseline(strategy):
    """Registry-completeness smoke: every STRATEGIES entry — including the
    access-aware pair — runs the paper baseline at 50 jobs without error
    and completes every job."""
    import dataclasses

    from repro.launch.experiments import run_spec
    spec = dataclasses.replace(SCENARIOS["paper_baseline"],
                               strategy=strategy)
    r = run_spec(spec, n_jobs=50)
    assert r.completed_jobs == r.n_jobs == 50
    assert r.avg_job_time > 0 and r.makespan > 0


def test_grid_500_smoke():
    """The 500-site scale scenario runs end to end at a reduced job count
    through the jitted batch broker (incremental presence bitmap + shared
    network snapshot hot paths)."""
    from repro.launch.experiments import run_spec
    spec = SCENARIOS["grid_500"]
    assert spec.n_sites == 500 and spec.n_jobs == 100_000
    r = run_spec(spec, n_jobs=200)
    assert r.completed_jobs == 200
    assert r.avg_job_time > 0 and r.makespan > 0


# -- sweeps ------------------------------------------------------------------
def test_sweep_registry_completeness():
    assert {"starved_strategies", "drift_strategies",
            "contended_nets", "baseline_wan"} <= set(SWEEPS)
    for name, sw in SWEEPS.items():
        assert sw.name == name and sw.description
        assert sw.base in SCENARIOS
        cells = sw.expand()
        assert len(cells) == len(sw.values)
        for v, cell in cells:
            assert cell.name == f"{sw.base}@{sw.axis}={v}"


def test_sweep_round_trip_and_validation():
    sw = SWEEPS["drift_strategies"]
    wire = json.loads(json.dumps(sw.to_dict()))
    assert SweepSpec.from_dict(wire) == sw
    with pytest.raises(ValueError, match="axis"):
        SweepSpec(name="bad", base="paper_baseline", axis="warp",
                  values=(1,))
    with pytest.raises(ValueError, match="value"):
        SweepSpec(name="bad", base="paper_baseline", axis="n_jobs",
                  values=())
    with pytest.raises(KeyError):
        get_sweep("nope")
    # a sweep cell inherits full spec validation
    bad = SweepSpec(name="bad", base="paper_baseline", axis="strategy",
                    values=("magic",))
    with pytest.raises(ValueError, match="strategy"):
        bad.expand()


def test_with_axis_vocabulary():
    base = SCENARIOS["paper_baseline"]
    assert with_axis(base, "n_jobs", 42).n_jobs == 42
    assert with_axis(base, "strategy", "economic").strategy == "economic"
    assert with_axis(base, "net", "device").net == "device"
    assert with_axis(base, "wan_mbps", 100.0).uplink_mbps[0] == 100.0
    with pytest.raises(ValueError, match="axis"):
        with_axis(base, "name", "x")


def test_sweep_runner_writes_grid(tmp_path):
    from repro.launch.experiments import run_scenarios
    out = tmp_path / "bench.json"
    payload = run_scenarios(["baseline_wan"], n_jobs=20, out_path=str(out),
                            quiet=True)
    entry = payload["sweeps"]["baseline_wan"]
    rows = entry["rows"]
    assert len(rows) == len(SWEEPS["baseline_wan"].values)
    assert {r["wan_mbps"] for r in rows} == set(
        SWEEPS["baseline_wan"].values)
    for r in rows:
        assert r["completed_jobs"] == 20
    assert json.loads(out.read_text())["sweeps"]["baseline_wan"][
        "sweep"]["axis"] == "wan_mbps"


def test_spec_validation():
    with pytest.raises(ValueError, match="uplink"):
        ScenarioSpec(name="bad", tier_fanouts=(2, 3, 4))  # missing uplink bw
    with pytest.raises(ValueError, match="arrival"):
        ScenarioSpec(name="bad", arrival="bursty")
    with pytest.raises(ValueError, match="strategy"):
        ScenarioSpec(name="bad", strategy="magic")
    with pytest.raises(ValueError, match="econ"):
        ScenarioSpec(name="bad", econ="cuda")
    # drift needs a Zipf workload: fixed filesets cannot shift
    with pytest.raises(ValueError, match="Zipf"):
        ScenarioSpec(name="bad", zipf_alpha=None, hotset_shifts=2)


# -- serialization ----------------------------------------------------------
@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_spec_round_trip(name):
    spec = SCENARIOS[name]
    wire = json.loads(json.dumps(spec.to_dict()))   # through real JSON
    assert ScenarioSpec.from_dict(wire) == spec


def test_baseline_lowers_to_golden_grid_config():
    """The paper-baseline scenario must hit the exact GridConfig the
    golden-metrics suite pins — same floats, same defaults."""
    assert to_grid_config(SCENARIOS["paper_baseline"]) == GridConfig()


# -- n-tier topology invariants --------------------------------------------
@pytest.mark.parametrize("fanouts,uplinks", [
    ((2, 3), (1.25e6,)),
    ((2, 3, 4), (1.25e6, 12.5e6)),
    ((2, 2, 2, 3), (1.25e6, 6.25e6, 12.5e6)),
])
def test_ntier_invariants(fanouts, uplinks):
    topo = GridTopology(0, 0, lan_bandwidth=125e6, wan_bandwidth=uplinks[0],
                        storage_capacity=1e10, tier_fanouts=fanouts,
                        uplink_bandwidths=uplinks)
    n = math.prod(fanouts)
    assert topo.n_sites == n
    assert topo.n_regions * topo.sites_per_region == n
    # region partition: disjoint cover
    seen = set()
    for region in topo.regions:
        assert not seen & set(region.site_ids)
        seen.update(region.site_ids)
    assert seen == set(range(n))
    # uplink count: one per internal node
    expected_links = 0
    nodes = 1
    for f in fanouts[:-1]:
        nodes *= f
        expected_links += nodes
    assert len(topo.wan_links) == expected_links
    for a in range(n):
        for b in range(n):
            if a == b:
                continue
            # reachability: every pair has a positive-bandwidth path
            links = topo.links_for(a, b)
            assert links and all(l.bandwidth > 0 for l in links)
            assert topo.point_bandwidth(a, b) > 0
            # link symmetry: crossing the hierarchy is direction-independent
            ua, ub = topo.uplink_index(a, b), topo.uplink_index(b, a)
            assert (ua >= 0) == (ub >= 0)
            assert topo.is_inter_region(a, b) == topo.is_inter_region(b, a)
            if ua >= 0:
                # a source-side uplink belongs to the source's ancestry
                off = ua - [o for o in topo._uplink_offset if o <= ua][-1]
                assert off in topo.ancestors(a)


def test_two_level_fanouts_match_classic_form():
    classic = GridTopology(3, 4, lan_bandwidth=125e6, wan_bandwidth=1.25e6,
                           storage_capacity=1e10)
    tiered = GridTopology(0, 0, lan_bandwidth=125e6, wan_bandwidth=1.25e6,
                          storage_capacity=1e10, tier_fanouts=(3, 4))
    assert classic.n_sites == tiered.n_sites == 12
    assert len(classic.wan_links) == len(tiered.wan_links) == 3
    for a in range(12):
        assert classic.region_of(a) == tiered.region_of(a)
        for b in range(12):
            assert classic.uplink_index(a, b) == tiered.uplink_index(a, b)
            if a != b and not classic.same_region(a, b):
                # two-level invariant the simulator's slot arrays rely on
                assert classic.uplink_index(a, b) == classic.region_of(a)


def test_heterogeneity_knobs_reject_bad_targets():
    common = dict(lan_bandwidth=125e6, wan_bandwidth=1.25e6,
                  storage_capacity=1e10)
    with pytest.raises(ValueError, match="uplink_scale level"):
        GridTopology(2, 2, uplink_scale=((0, 0, 10.0),), **common)
    with pytest.raises(ValueError, match="uplink_scale node"):
        GridTopology(2, 2, uplink_scale=((1, 2, 10.0),), **common)
    with pytest.raises(ValueError, match="storage_scale region"):
        GridTopology(2, 2, storage_scale=((7, 0.1),), **common)


def test_heterogeneity_knobs():
    topo = GridTopology(2, 2, lan_bandwidth=125e6, wan_bandwidth=1.25e6,
                        storage_capacity=1e10,
                        uplink_scale=((1, 0, 10.0),),
                        storage_scale=((1, 0.25),))
    assert topo.wan_links[0].bandwidth == 12.5e6     # fat region 0
    assert topo.wan_links[1].bandwidth == 1.25e6
    assert topo.sites[0].storage_capacity == 1e10    # region 0 untouched
    assert topo.sites[2].storage_capacity == 2.5e9   # region 1 starved


# -- arrival processes ------------------------------------------------------
def _spec(**kw):
    return ScenarioSpec(name="t", description="d", probes="p", **kw)


def test_uniform_arrivals_use_default_path():
    assert arrival_schedule(_spec(), 100) is None


@pytest.mark.parametrize("arrival", ["poisson", "flash_crowd", "diurnal"])
def test_arrival_processes(arrival):
    spec = _spec(arrival=arrival)
    n = 300
    times = arrival_schedule(spec, n, seed=3)
    assert len(times) == n
    assert times[0] == 0.0
    assert all(b >= a for a, b in zip(times, times[1:]))   # nondecreasing
    assert times == arrival_schedule(spec, n, seed=3)      # deterministic
    # same mean rate (within process-specific tolerance): the whole stream
    # spans roughly n * interarrival seconds
    uniform_span = n * spec.interarrival_s
    assert 0.4 * uniform_span < times[-1] <= 1.9 * uniform_span


def test_flash_crowd_compresses_the_burst():
    spec = _spec(arrival="flash_crowd", crowd_at=0.5, crowd_frac=0.2,
                 crowd_factor=10.0)
    times = arrival_schedule(spec, 100, seed=0)
    gaps = [b - a for a, b in zip(times, times[1:])]
    assert min(gaps) == spec.interarrival_s / 10.0
    assert max(gaps) == spec.interarrival_s


# -- injections -------------------------------------------------------------
def test_churn_schedule_deterministic_and_bounded():
    spec = ChurnSpec(n_failures=5, window=(1000.0, 9000.0),
                     mean_downtime_s=2000.0)
    events = churn_schedule(spec, n_sites=8, seed=7)
    assert events == churn_schedule(spec, n_sites=8, seed=7)
    assert len(events) == 5
    sites = [s for s, _, _ in events]
    assert len(set(sites)) == 5                      # no site hit twice
    for site, at, duration in events:
        assert 0 <= site < 8
        assert 1000.0 <= at <= 9000.0
        assert duration >= 1.0
    assert churn_schedule(ChurnSpec(), n_sites=8) == []


# -- the runner -------------------------------------------------------------
def test_runner_bare_filename_out(tmp_path, monkeypatch):
    from repro.launch.experiments import run_scenarios
    monkeypatch.chdir(tmp_path)
    run_scenarios(["paper_baseline"], n_jobs=5, out_path="out.json",
                  quiet=True)
    assert json.loads((tmp_path / "out.json").read_text())["scenarios"]


def test_runner_two_scenario_smoke(tmp_path):
    from repro.launch.experiments import ROW_KEYS, run_scenarios
    out = tmp_path / "BENCH_scenarios.json"
    payload = run_scenarios(["paper_baseline", "deep_4tier"], n_jobs=40,
                            out_path=str(out), quiet=True)
    on_disk = json.loads(out.read_text())
    assert set(on_disk["scenarios"]) == {"paper_baseline", "deep_4tier"}
    for name, entry in payload["scenarios"].items():
        assert ScenarioSpec.from_dict(entry["spec"]) == SCENARIOS[name]
        for row in entry["rows"]:
            for key in ROW_KEYS:
                assert key in row, (name, key)
            assert row["completed_jobs"] == row["n_jobs"] == 40
            assert row["avg_job_time_s"] > 0
            assert row["makespan_s"] > 0
