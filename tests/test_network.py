"""Path-contention NetworkEngine: uplink-path topology queries on deep
trees, the hand-computed min-over-path contention fixture on every route,
rate agreement of the batched routes with the incremental one, and the
legacy topmost-model divergence."""

import dataclasses
import types

import pytest

from repro.core import GridConfig, GridTopology, NetworkEngine, run_experiment
from repro.core.network import BACKENDS

GB = 1e9


def _topo(fanouts, uplinks, lan=100.0, path_model="full"):
    return GridTopology(0, 0, lan_bandwidth=lan, wan_bandwidth=uplinks[0],
                        storage_capacity=10 * GB, tier_fanouts=fanouts,
                        uplink_bandwidths=uplinks, path_model=path_model)


# -- uplink_index / uplink_path / links_for on deep trees -------------------
class TestUplinkPath4Tier:
    """(2, 4, 7): 2 clusters x 4 groups x 7 sites. wan_links layout:
    level-1 cluster uplinks are ids 0-1, level-2 group uplinks ids 2-9."""

    def setup_method(self):
        self.topo = _topo((2, 4, 7), (10.0, 100.0))

    def test_same_group(self):
        assert self.topo.uplink_path(0, 3) == ()
        assert self.topo.uplink_index(0, 3) == -1
        assert [l.name for l in self.topo.links_for(0, 3)] == ["nic0"]
        assert self.topo.link_ids_for(0, 3) == (0,)

    def test_sibling_subtree(self):
        # site 0 (group 0) -> site 7 (group 1), same cluster: one crossed
        # uplink, the source group's — full and topmost models agree
        assert self.topo.uplink_path(0, 7) == (2,)
        assert self.topo.uplink_index(0, 7) == 2
        assert self.topo.link_ids_for(0, 7) == (0, 56 + 2)

    def test_cross_region(self):
        # site 0 -> site 28 (cluster 1): crosses the cluster-0 uplink AND
        # the group-0 uplink below it, topmost first
        assert self.topo.uplink_path(0, 28) == (0, 2)
        assert self.topo.uplink_index(0, 28) == 0          # topmost only
        assert self.topo.link_ids_for(0, 28) == (0, 56 + 0, 56 + 2)
        # reverse direction uses the *source-side* (cluster 1) links
        assert self.topo.uplink_path(28, 0) == (1, 2 + 4)

    def test_topmost_model_truncates(self):
        legacy = _topo((2, 4, 7), (10.0, 100.0), path_model="topmost")
        assert legacy.uplink_path(0, 28) == (0,)
        assert legacy.uplink_path(0, 7) == (2,)            # unchanged
        assert legacy.link_ids_for(0, 28) == (0, 56 + 0)


class TestUplinkPath5Tier:
    """(2, 3, 3, 3): 54 sites; wan_links: level-1 ids 0-1, level-2 ids 2-7,
    level-3 ids 8-25."""

    def setup_method(self):
        self.topo = _topo((2, 3, 3, 3), (10.0, 50.0, 200.0))

    def test_same_site_group(self):
        assert self.topo.uplink_path(0, 2) == ()
        assert self.topo.link_ids_for(0, 2) == (0,)

    def test_sibling_subtree_mid(self):
        # site 0 -> site 4: same level-2 node, different leaf groups
        assert self.topo.ancestors(0) == (0, 0, 0)
        assert self.topo.ancestors(4) == (0, 0, 1)
        assert self.topo.uplink_path(0, 4) == (8,)
        assert self.topo.uplink_index(0, 4) == 8

    def test_cross_region_full_depth(self):
        # site 0 -> site 53: diverges at the root, crosses all three
        # source-side uplinks top-down
        assert self.topo.ancestors(53) == (1, 5, 17)
        assert self.topo.uplink_path(0, 53) == (0, 2, 8)
        assert self.topo.uplink_index(0, 53) == 0
        assert self.topo.link_ids_for(0, 53) == (0, 54, 54 + 2, 54 + 8)

    def test_point_bandwidth_sees_thin_mid_tier(self):
        # make the lower tier the bottleneck: 100 over 1 top-down
        topo = _topo((2, 2, 2), (100.0, 1.0))
        # site 0 -> site 7 crosses level-1 (100) and a thin level-2 (1)
        assert topo.point_bandwidth(0, 7) == 1.0
        legacy = _topo((2, 2, 2), (100.0, 1.0), path_model="topmost")
        assert legacy.point_bandwidth(0, 7) == pytest.approx(100.0)


def test_path_model_validation():
    with pytest.raises(ValueError, match="path_model"):
        _topo((2, 2), (10.0,), path_model="bogus")


def _settle(net, changed, now):
    """Re-rate after ``changed`` links moved and, on the batched routes,
    run the instant's flush; return the next completion time."""
    eta = net.rerate(changed, now)
    return net.flush(now) if net.batched else eta


# -- the 3-transfer mid-tier contention fixture -----------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_three_transfer_min_over_path(backend):
    """Hand-computed fair shares on a (2,2,2) tree: NIC 100 B/s, cluster
    uplinks 50, group uplinks 10 (ids: cluster c -> 8+c, group g -> 8+2+g).

      t1: 0 -> 6  crosses nic0, cluster-0 (50), group-0 (10)
      t2: 1 -> 2  crosses nic1, group-0 (10)
      t3: 0 -> 1  crosses nic0 only

    Occupancy: nic0={t1,t3}, nic1={t2}, cluster0={t1}, group0={t1,t2}, so
      t1 = min(100/2, 50/1, 10/2) = 5      (mid-tier bound through-traffic)
      t2 = min(100/1, 10/2)       = 5
      t3 = 100/2                  = 50
    The legacy topmost model would rate t1 = min(100/2, 50/1) = 50.
    The batched routes flush after every event, one instant each."""
    topo = _topo((2, 2, 2), (50.0, 10.0))
    net = NetworkEngine(topo, backend=backend)
    slots = {}
    for name, (src, dst) in {"t1": (0, 6), "t2": (1, 2), "t3": (0, 1)}.items():
        tr = types.SimpleNamespace(slot=-1)
        net.alloc(tr, 1e6, topo.link_ids_for(src, dst))
        _settle(net, topo.link_ids_for(src, dst), 0.0)
        slots[name] = tr.slot
    assert net.rate[slots["t1"]] == pytest.approx(5.0)
    assert net.rate[slots["t2"]] == pytest.approx(5.0)
    assert net.rate[slots["t3"]] == pytest.approx(50.0)
    # eta scan: smallest rem/rate wins
    assert _settle(net, (), 0.0) == pytest.approx(1e6 / 50.0)

    legacy = _topo((2, 2, 2), (50.0, 10.0), path_model="topmost")
    lnet = NetworkEngine(legacy, backend=backend)
    tr = types.SimpleNamespace(slot=-1)
    lnet.alloc(tr, 1e6, legacy.link_ids_for(0, 6))
    _settle(lnet, legacy.link_ids_for(0, 6), 0.0)
    assert lnet.rate[tr.slot] == pytest.approx(50.0)


def test_three_transfer_batched_flush_matches_numpy():
    """The batched ``device`` engine defers re-rates (rerate() only marks
    dirty links) and resolves the whole instant in one fused flush; the
    flushed rates must equal the hand-computed incremental fixture above
    and the returned wake-up must be the global earliest completion."""
    topo = _topo((2, 2, 2), (50.0, 10.0))
    net = NetworkEngine(topo, backend="device")
    assert net.batched
    slots = {}
    for name, (src, dst) in {"t1": (0, 6), "t2": (1, 2), "t3": (0, 1)}.items():
        tr = types.SimpleNamespace(slot=-1)
        net.alloc(tr, 1e6, topo.link_ids_for(src, dst))
        assert net.rerate(topo.link_ids_for(src, dst), 0.0) is None
        slots[name] = tr.slot
    assert net.dirty
    eta = net.flush(0.0)
    assert not net.dirty
    assert net.rate[slots["t1"]] == pytest.approx(5.0)
    assert net.rate[slots["t2"]] == pytest.approx(5.0)
    assert net.rate[slots["t3"]] == pytest.approx(50.0)
    # the flush returns the next completion: t3 at 1e6 / 50 B/s
    assert eta == pytest.approx(1e6 / 50.0)
    assert net.rem_now(0.0)[slots["t1"]] == pytest.approx(1e6)


def _burst_stats(backend: str, n_backlog: int) -> tuple[dict, "object"]:
    """Load one uplink path with ``n_backlog`` in-flight transfers, then
    replay an identical 16-event same-instant burst on that path and
    return the engine's work counters for the burst alone."""
    topo = _topo((2, 2, 2), (50.0, 10.0))
    net = NetworkEngine(topo, backend=backend)
    links = topo.link_ids_for(0, 6)
    for _ in range(n_backlog):
        tr = types.SimpleNamespace(slot=-1)
        net.alloc(tr, 1e9, links)
        net.rerate(links, 0.0)
    if net.batched:
        net.flush(0.0)
    net.stats = {k: 0 for k in net.stats}
    for _ in range(16):
        tr = types.SimpleNamespace(slot=-1)
        net.alloc(tr, 1e6, links)
        net.rerate(links, 1.0)
    if net.batched:
        net.flush(1.0)
    return net.stats, net


def test_device_per_event_work_independent_of_backlog():
    """Saturated-backlog regression (counter-based, no timing): the numpy
    engine re-rates the changed-link union on *every* event, so its
    per-event work grows with the in-flight count; the batched device
    engine does zero per-event re-rate work (rerate only marks dirty)
    and pays one fused pass over the dirty neighborhood per instant,
    however many events the instant carries."""
    small_np, _ = _burst_stats("numpy", 8)
    big_np, _ = _burst_stats("numpy", 512)
    small_dev, _ = _burst_stats("device", 8)
    big_dev, net_dev = _burst_stats("device", 512)

    # numpy: 16 union re-rates, each touching the whole shared backlog
    assert big_np["rerate_slots"] >= 16 * 512
    assert big_np["rerate_slots"] > 4 * small_np["rerate_slots"]

    # device: no per-event slot work at all — backlog size is invisible
    # until the instant's single flush
    assert small_dev["rerate_slots"] == big_dev["rerate_slots"] == 0
    assert big_dev["flush_passes"] == 1
    assert big_dev["flush_slots"] <= 512 + 16      # one pass, not 16

    # and the fused pass lands on the same floats the incremental
    # engine integrates to (both are f64 min-over-path fair shares)
    _, net_np = _burst_stats("numpy", 512)
    import numpy as np
    assert np.array_equal(net_dev.rate[:528], net_np.rate[:528])


def test_engine_counters_surface_through_results():
    """The per-engine work counters asserted above must also be readable
    from a finished run — SimResult/ExperimentResult carry
    ``NetworkEngine.stats`` so the saturated-backlog regression can be
    re-checked on real workloads without reaching into the engine."""
    cfg = GridConfig(n_regions=2, sites_per_region=3)
    inc = run_experiment(cfg, n_jobs=80)                 # incremental numpy
    dev = run_experiment(cfg, n_jobs=80, net="device")   # batched device
    assert set(inc.net_stats) == {"rerate_calls", "rerate_slots",
                                  "flush_passes", "flush_slots",
                                  "flush_kernel", "flush_host",
                                  "flush_skipped"}
    # incremental engine: per-event union re-rates, never a fused flush
    assert inc.net_stats["rerate_slots"] > 0
    assert inc.net_stats["flush_passes"] == 0
    # batched engine: zero per-event slot work, all work in flush passes
    assert dev.net_stats["rerate_slots"] == 0
    assert dev.net_stats["flush_passes"] > 0
    assert dev.net_stats["flush_slots"] > 0
    # off the chip every pass that re-rated slots ran on the host
    assert dev.net_stats["flush_kernel"] == 0
    assert 0 < dev.net_stats["flush_host"] <= dev.net_stats["flush_passes"]
    # both engines saw the same event stream
    assert dev.net_stats["rerate_calls"] == inc.net_stats["rerate_calls"]


def _counting_crossings(monkeypatch) -> dict:
    """Patch the flush program to count its calls (the chip's crossings)."""
    from repro.kernels.event_engine import kernel
    real_call = kernel._flush_call
    calls = {"n": 0}

    def call(*operands, **kwargs):
        calls["n"] += 1
        return real_call(*operands, **kwargs)

    monkeypatch.setattr(kernel, "_flush_call", call)
    return calls


def _two_apart(backend):
    """Two live transfers on disjoint links of a (2,2,2) tree, flushed:
    ``a`` (0 -> 1, nic0 only, 1e6 B) and ``b`` (4 -> 5, nic4 only, 1e5 B,
    the earlier completion)."""
    topo = _topo((2, 2, 2), (50.0, 10.0))
    net = NetworkEngine(topo, backend=backend)
    trs = {}
    for name, (src, dst, size) in {"a": (0, 1, 1e6),
                                   "b": (4, 5, 1e5)}.items():
        trs[name] = types.SimpleNamespace(slot=-1)
        net.alloc(trs[name], size, topo.link_ids_for(src, dst))
        net.rerate(topo.link_ids_for(src, dst), 0.0)
    return net, trs, net.flush(0.0)


@pytest.mark.parametrize("backend", ["device", "device-interpret"])
@pytest.mark.parametrize("change", ["release_last_on_links", "net_wakeup"])
def test_flush_of_an_empty_neighborhood_makes_no_crossing(
        monkeypatch, change, backend):
    """A pass whose dirty links hold no live slot re-rates nothing on any
    route: no flush program runs, the slot state stays bit for bit, the
    next completion is the one the pass before returned, and only
    ``flush_skipped`` counts it."""
    import numpy as np

    net, trs, eta_before = _two_apart(backend)
    assert eta_before == pytest.approx(1e5 / 100.0)
    if change == "release_last_on_links":
        changed = net.release(trs["a"])       # nic0 held only ``a``
        assert not any(net.members[li] for li in changed)
    else:
        changed = ()                          # a NET wake-up, no completion
    calls = _counting_crossings(monkeypatch)
    state = {k: getattr(net, k).copy() for k in ("rem", "rate", "eta", "due")}
    stats = dict(net.stats)
    net.rerate(changed, 400.0)
    eta = net.flush(400.0)
    assert calls["n"] == 0
    assert eta == eta_before
    for k, v in state.items():
        assert np.array_equal(getattr(net, k), v)
    assert net.stats["flush_skipped"] == stats["flush_skipped"] + 1
    assert net.stats["flush_passes"] == stats["flush_passes"] + 1
    for k in ("flush_kernel", "flush_host", "flush_slots"):
        assert net.stats[k] == stats[k]
    assert not net.dirty and not net._dirty_links


def test_flush_of_a_neighborhood_with_a_live_slot_crosses_once(monkeypatch):
    """A release that leaves a live slot on a changed link still crosses,
    once, over the whole slot array, and re-rates that slot."""
    net, trs, _ = _two_apart("device-interpret")
    c = types.SimpleNamespace(slot=-1)
    links = net.topology.link_ids_for(0, 1)
    net.alloc(c, 1e6, links)                  # shares nic0 with ``a``
    net.rerate(links, 0.0)
    net.flush(0.0)
    assert net.rate[c.slot] == pytest.approx(50.0)
    calls = _counting_crossings(monkeypatch)
    stats = dict(net.stats)
    net.rerate(net.release(trs["a"]), 400.0)
    net.flush(400.0)
    assert calls["n"] == 1
    assert net.rate[c.slot] == pytest.approx(100.0)
    assert net.stats["flush_host"] == stats["flush_host"] + 1
    assert net.stats["flush_slots"] == stats["flush_slots"] + net.n_active
    assert net.stats["flush_skipped"] == stats["flush_skipped"]


def test_batched_routes_skip_the_same_passes_of_a_run():
    """A Table 1-shaped world (4 x 13 sites, a 20-job burst into the
    empty grid): the CPU route and the chip's route, rehearsed under the
    interpreter, skip the same passes and re-rate on the same others."""
    from repro.core import SCENARIOS
    from repro.launch.experiments import run_spec
    spec = dataclasses.replace(SCENARIOS["paper_baseline"], arrival_burst=20)
    runs = {net: run_spec(dataclasses.replace(spec, net=net), seed=3,
                          n_jobs=20) for net in ("device", "device-interpret")}
    cpu, chip = (runs[n].net_stats for n in ("device", "device-interpret"))
    assert cpu["flush_skipped"] > 0
    for k in ("flush_passes", "flush_skipped", "flush_host", "rerate_calls"):
        assert cpu[k] == chip[k]
    assert cpu["flush_passes"] == cpu["flush_skipped"] + cpu["flush_host"]


def test_engine_release_and_regrow():
    topo = _topo((2, 2), (10.0,))
    net = NetworkEngine(topo)
    trs = []
    for i in range(100):           # force a capacity doubling past 64
        tr = types.SimpleNamespace(slot=-1)
        net.alloc(tr, 1e6, topo.link_ids_for(0, 3))
        trs.append(tr)
    assert net.cap >= 128 and net.n_active == 100
    assert net.link_act[0] == 100.0
    changed = net.release(trs[0])
    assert changed == topo.link_ids_for(0, 3)
    assert net.n_active == 99 and net.link_act[0] == 99.0
    assert trs[0].slot == -1


def test_unknown_backend_rejected():
    topo = _topo((2, 2), (10.0,))
    with pytest.raises(ValueError, match="backend"):
        NetworkEngine(topo, backend="fortran")
    with pytest.raises(ValueError, match="net engine"):
        run_experiment(GridConfig(n_regions=2, sites_per_region=2), n_jobs=1,
                       net="fortran")
    assert "numpy" in BACKENDS and "pallas" not in BACKENDS
    with pytest.raises(ValueError, match="net engine"):
        run_experiment(GridConfig(n_regions=2, sites_per_region=2), n_jobs=1,
                       net="pallas")


def test_topmost_refuses_full_path_topology():
    """net='topmost' must not silently mutate a topology built with the
    full path model — a direct GridSimulator gets a loud error instead."""
    from repro.core import GridSimulator, build_catalog, build_topology
    cfg = GridConfig(n_regions=2, sites_per_region=2)
    topo = build_topology(cfg)                      # path_model="full"
    cat = build_catalog(cfg, topo)
    with pytest.raises(ValueError, match="path_model='topmost'"):
        GridSimulator(topo, cat, net="topmost")
    assert topo.path_model == "full"                # untouched
    legacy = build_topology(cfg, path_model="topmost")
    GridSimulator(legacy, build_catalog(cfg, legacy), net="topmost")


# -- backend equivalence and fidelity divergence ----------------------------
def test_two_level_backends_bit_identical():
    """On two-level grids the incremental path model and the topmost
    model must produce the same floats — the path is {NIC, region uplink}
    under both."""
    cfg = GridConfig(n_regions=2, sites_per_region=4)
    base = run_experiment(cfg, strategy="hrs", n_jobs=60, net="numpy")
    r = run_experiment(cfg, strategy="hrs", n_jobs=60, net="topmost")
    assert r.avg_job_time == base.avg_job_time
    assert r.avg_inter_comms == base.avg_inter_comms
    assert r.total_wan_gb == base.total_wan_gb
    assert r.makespan == base.makespan


@pytest.mark.parametrize("backend", ["device", "device-interpret"])
@pytest.mark.parametrize("scenario", ["paper_baseline", "deep_4tier",
                                      "deep_5tier", "deep_contended"])
def test_batched_routes_rate_like_the_incremental_route(scenario, backend):
    """A seeded history of 120 allocs and releases, one instant per step,
    fed to a batched engine and to the incremental ``numpy`` engine on
    the scenario's topology: after every step each live slot carries the
    same rate on both, bit for bit. Rates are a pure function of link
    occupancy on every route (remaining bytes and etas are not compared:
    the batched routes rebuild them by design, under the tolerance
    goldens)."""
    import numpy as np

    from repro.core import SCENARIOS, build_topology, to_grid_config
    cfg = to_grid_config(SCENARIOS[scenario])
    ref = NetworkEngine(build_topology(cfg), backend="numpy")
    net = NetworkEngine(build_topology(cfg), backend=backend)
    rng = np.random.default_rng(17)
    n_sites, now = ref.topology.n_sites, 0.0
    live: list[tuple] = []
    for _ in range(120):
        now += float(rng.random()) * 50.0
        ref.advance(now)
        net.advance(now)
        if not live or rng.random() < 0.65:
            src, dst = (int(x) for x in rng.integers(0, n_sites, 2))
            links = ref.topology.link_ids_for(src, dst)
            size = float(rng.random()) * 1e9 + 1e6
            pair = (types.SimpleNamespace(slot=-1),
                    types.SimpleNamespace(slot=-1))
            ref.alloc(pair[0], size, links)
            net.alloc(pair[1], size, links)
            assert pair[0].slot == pair[1].slot
            live.append(pair)
            changed = links
        else:
            pair = live.pop(int(rng.integers(0, len(live))))
            changed = ref.release(pair[0])
            assert net.release(pair[1]) == changed
        _settle(ref, changed, now)
        _settle(net, changed, now)
        slots = np.array([p[0].slot for p in live], np.intp)
        assert np.array_equal(net.rate[slots], ref.rate[slots])
        assert (ref.rate[slots] > 0.0).all()
    assert net.stats["flush_passes"] == 120


def test_deep_tree_full_path_diverges_from_topmost():
    """The fidelity change is real: on a fat-top/thin-mid tree the
    per-link path model must not reproduce the legacy topmost numbers."""
    mbps = 1e6 / 8
    cfg = GridConfig(tier_fanouts=(3, 3, 6),
                     uplink_bandwidths=(100 * mbps, 10 * mbps))
    full = run_experiment(cfg, strategy="hrs", n_jobs=60, net="numpy")
    legacy = run_experiment(cfg, strategy="hrs", n_jobs=60, net="topmost")
    assert full.avg_job_time != legacy.avg_job_time


@pytest.mark.parametrize("fanouts,uplinks,path_model", [
    ((4, 13), (10.0,), "full"),
    ((2, 4, 7), (10.0, 100.0), "full"),
    ((2, 3, 3, 3), (10.0, 50.0, 200.0), "full"),
    ((2, 3, 3, 3), (10.0, 50.0, 200.0), "topmost"),
])
def test_pair_link_matrix_matches_link_ids_for(fanouts, uplinks, path_model):
    """The vectorized (sites, sites, depth) tensor equals the per-pair
    link_ids_for rows: NIC first, same crossed-uplink id set (hole
    positions within a row carry no meaning — consumers mask on >= 0)."""
    topo = _topo(fanouts, uplinks, path_model=path_model)
    mat = topo.pair_link_matrix()
    assert mat.shape == (topo.n_sites, topo.n_sites, topo.depth)
    for h in range(topo.n_sites):
        for s in range(topo.n_sites):
            row = mat[h, s]
            assert row[0] == h                           # source NIC
            assert sorted(int(x) for x in row if x >= 0) == \
                sorted(topo.link_ids_for(h, s))


def test_point_bandwidth_matrix_is_the_shared_snapshot():
    """One cached path tensor serves both consumers: the jitted
    shortest-transfer broker and the replication economy read the same
    NetworkEngine.point_bandwidth_matrix, and its cell values equal the
    scalar point_bandwidth query."""
    import numpy as np

    from repro.core import GridSimulator, build_catalog, build_topology
    cfg = GridConfig(n_regions=2, sites_per_region=3)
    topo = build_topology(cfg)
    cat = build_catalog(cfg, topo)
    sim = GridSimulator(topo, cat, scheduler="shortesttransfer",
                        strategy="hrs", broker="jax")
    for info in cat.files.values():
        sim.storage.bootstrap(info.master_site, info.lfn)
    assert sim.network._pair_paths is None          # lazy until first use
    sim._jax_broker.select_batch([["lfn0000"], ["lfn0001"]])
    cached = sim.network._pair_paths
    assert cached is not None                       # broker went through it
    B = sim.network.point_bandwidth_matrix()
    assert sim.network._pair_paths is cached        # built exactly once
    for h, s in ((0, 0), (0, 5), (4, 1), (5, 2)):
        assert B[h, s] == sim.network.point_bandwidth(h, s)
    assert np.array_equal(cached, topo.pair_link_matrix())


# -- the vectorized shortest-transfer broker --------------------------------
def test_jax_shortest_transfer_matches_python():
    """Batch decisions over a frozen snapshot must equal the sequential
    python policy site-for-site (durable masters + zero-bw guard incl.)."""
    from repro.core import (GridSimulator, build_catalog, build_topology,
                            generate_jobs)
    from repro.core.scheduler import make_scheduler
    cfg = GridConfig(n_regions=3, sites_per_region=5)
    topo = build_topology(cfg)
    cat = build_catalog(cfg, topo)
    sim = GridSimulator(topo, cat, scheduler="shortesttransfer",
                        strategy="hrs", broker="jax")
    for info in cat.files.values():
        sim.storage.bootstrap(info.master_site, info.lfn)
    jobs = generate_jobs(cfg, 48)
    want = [make_scheduler("shortesttransfer", cat, topo).select_site(j)
            for j in jobs]
    got = sim._jax_broker.select_batch([j.required for j in jobs])
    assert got == want


def test_jax_shortest_transfer_broker_end_to_end():
    cfg = GridConfig(n_regions=2, sites_per_region=4)
    a = run_experiment(cfg, scheduler="shortesttransfer", strategy="hrs",
                       n_jobs=60, broker="jax", arrival_burst=10)
    b = run_experiment(cfg, scheduler="shortesttransfer", strategy="hrs",
                       n_jobs=60, broker="jax", arrival_burst=10)
    assert a.completed_jobs == a.n_jobs == 60
    assert a.avg_job_time == b.avg_job_time       # deterministic


def test_jax_broker_covers_every_registered_policy():
    """The broker gap is closed: every SCHEDULERS entry dispatches under
    broker='jax' (dataaware/shortesttransfer since PR 3, leastloaded and
    random via the argmin/PRNG-gather brokers)."""
    from repro.core import SCHEDULERS
    for scheduler in sorted(SCHEDULERS):
        r = run_experiment(GridConfig(n_regions=2, sites_per_region=2),
                           scheduler=scheduler, n_jobs=8, broker="jax",
                           arrival_burst=4)
        assert r.completed_jobs == 8, scheduler


def test_bulk_shortest_scenario_smoke():
    from repro.core import SCENARIOS
    from repro.launch.experiments import run_spec
    spec = dataclasses.replace(SCENARIOS["bulk_shortest"])
    r = run_spec(spec, n_jobs=50)
    assert r.completed_jobs == 50
