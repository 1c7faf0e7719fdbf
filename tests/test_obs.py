"""Telemetry layer (repro.obs): observation-only contract + plumbing.

The load-bearing guarantee is *observation-only*: running any ``obs=``
mode produces bit-identical simulation metrics to ``obs="off"`` (the
golden contract extends through telemetry), checked here per engine
backend and via the ``REPRO_OBS`` env override that CI uses to replay
the golden suites with tracing forced on. The rest pins the probe's
accounting invariants (exclusive span times partition wall, counters
mirror the DES event stream), the ring-buffer series, the Chrome trace
round-trip, and the result-surface plumbing (phases/counters rows,
net_stats, the prefetch ledger, ScenarioSpec round-trips).
"""

import json

import numpy as np
import pytest

from repro.core import (GridConfig, OBS_MODES, ScenarioSpec, get_scenario,
                        run_experiment)
from repro.core.simulator import GridSimulator
from repro.launch.experiments import run_scenario, run_spec
from repro.obs import (CHANNELS, DEFAULT_OBS_INTERVAL_S, GridSampler, Probe,
                       RingBuffer, TraceWriter, make_probe)

METRICS = ("avg_job_time", "avg_inter_comms", "total_wan_gb", "total_lan_gb",
           "makespan", "completed_jobs")


def _metrics(r) -> tuple:
    return tuple(getattr(r, m) for m in METRICS)


# -- probe unit behaviour ---------------------------------------------------

def test_span_exclusive_accounting_partitions_wall():
    """Nested spans: the child's inclusive time is subtracted from the
    parent's self time, so self times are disjoint and sum <= wall."""
    p = Probe("report")
    with p.span("outer"):
        for _ in range(3):
            with p.span("inner"):
                sum(range(2000))
    assert p.phase_calls == {"outer": 1, "inner": 3}
    # outer's inclusive time covers the inners entirely
    assert p.phase_total_s["outer"] >= p.phase_total_s["inner"]
    # exclusive times: outer self excludes the inner inclusive time
    assert p.phase_self_s["outer"] == pytest.approx(
        p.phase_total_s["outer"] - p.phase_total_s["inner"])
    report = p.finalize()
    assert sum(report.phase_self_s.values()) <= report.wall_s


def test_probe_counters_and_merge():
    p = Probe("report")
    p.count("a")
    p.count("a", 2)
    p.event("SUBMIT", 1.0)
    p.merge_counters("net", {"x": 2, "y": 3.0})
    assert p.counters == {"a": 3, "event.SUBMIT": 1, "net.x": 2, "net.y": 3}
    assert isinstance(p.counters["net.y"], int)


def test_make_probe_modes():
    assert make_probe("off") is None
    assert make_probe("report").sampler is None
    assert make_probe("series").sampler is not None
    assert make_probe("series").trace is None
    tr = make_probe("trace")
    assert tr.sampler is not None and tr.trace is not None
    with pytest.raises(ValueError, match="unknown obs mode"):
        make_probe("verbose")


def test_deepcopy_drops_probe():
    """Sanitizer twins must not double-count into the primary's probe."""
    import copy
    assert copy.deepcopy(Probe("report")) is None


def test_phase_breakdown_partitions_wall():
    p = Probe("report")
    with p.span("broker.dispatch"):
        pass
    bd = p.finalize().phase_breakdown(wall_s=2.0)
    assert set(bd) == {"dispatch_s", "strategy_plan_s", "flush_s", "other_s"}
    assert sum(bd.values()) == pytest.approx(2.0, abs=1e-5)


# -- ring-buffer series -----------------------------------------------------

def test_ring_buffer_wraps_chronologically():
    rb = RingBuffer(4, ("t", "v"))
    for i in range(7):
        rb.append((float(i), float(10 * i)))
    assert rb.n_total == 7 and len(rb) == 4
    rows = rb.rows()
    assert rows[:, 0].tolist() == [3.0, 4.0, 5.0, 6.0]   # oldest survivor first
    assert rb.arrays()["v"].tolist() == [30.0, 40.0, 50.0, 60.0]


def test_series_channels_from_live_run():
    r = run_experiment(GridConfig(), n_jobs=60, obs="series")
    series = r.telemetry.series
    assert set(series) == set(CHANNELS)
    t = series["t"]
    assert r.telemetry.n_samples == len(t) > 1
    assert np.all(np.diff(t) > 0)                        # sim clock advances
    for ch in ("wan_bytes", "accesses", "completed_jobs"):
        assert np.all(np.diff(series[ch]) >= 0), ch      # cumulative channels
    assert series["completed_jobs"][-1] <= r.completed_jobs
    assert np.all(series["se_used_frac"] >= 0.0)
    assert np.all(series["se_used_frac"] <= 1.0)


# -- trace export -----------------------------------------------------------

def test_trace_round_trip_and_nesting(tmp_path):
    """Exported trace is valid Chrome-trace JSON and the host-phase
    complete events nest monotonically (no partial overlap)."""
    r = run_experiment(GridConfig(), n_jobs=60, obs="trace")
    tel = r.telemetry
    path = tmp_path / "run.trace.json"
    tel.save_trace(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    spans = sorted((e for e in events if e.get("ph") == "X"),
                   key=lambda e: (e["ts"], -e["dur"]))
    assert spans, "no host-phase spans exported"
    stack = []
    for e in spans:
        while stack and e["ts"] >= stack[-1]:
            stack.pop()
        if stack:                      # strictly nested, never straddling
            assert e["ts"] + e["dur"] <= stack[-1]
        stack.append(e["ts"] + e["dur"])
    instants = [e for e in events if e.get("ph") == "i"]
    # one sim-track instant per handled DES event (within the cap)
    n_events = sum(v for k, v in tel.counters.items()
                   if k.startswith("event."))
    assert len(instants) == n_events
    # JSONL event log round-trips line by line, metadata excluded
    jl = tmp_path / "run.events.jsonl"
    tel.save_events_jsonl(str(jl))
    lines = [json.loads(l) for l in jl.read_text().splitlines()]
    assert len(lines) == len(tel.trace)
    assert all(e["ph"] != "M" for e in lines)


def test_trace_writer_caps_events():
    tw = TraceWriter(max_events=3)
    for i in range(5):
        tw.add_instant("E", float(i))
    assert len(tw) == 3 and tw.dropped == 2
    assert tw.to_dict()["otherData"]["dropped_events"] == 2


# -- observation-only: goldens unchanged under every obs mode ---------------

@pytest.mark.parametrize("mode", ["report", "series", "trace"])
def test_obs_modes_bit_identical_numpy(mode):
    base = _metrics(run_experiment(GridConfig(), n_jobs=100))
    assert _metrics(run_experiment(GridConfig(), n_jobs=100, obs=mode)) == base


def test_obs_bit_identical_device_backend():
    base = _metrics(run_experiment(GridConfig(), n_jobs=100, net="device"))
    got = _metrics(run_experiment(GridConfig(), n_jobs=100, net="device",
                                  obs="trace"))
    assert got == base


def test_repro_obs_env_override(monkeypatch):
    """CI replays the golden suites with REPRO_OBS=trace; the override
    must attach telemetry without touching a single metric."""
    base = run_experiment(GridConfig(), n_jobs=100)
    assert base.telemetry is None
    monkeypatch.setenv("REPRO_OBS", "trace")
    forced = run_experiment(GridConfig(), n_jobs=100)
    assert forced.telemetry is not None and forced.telemetry.mode == "trace"
    assert _metrics(forced) == _metrics(base)
    monkeypatch.setenv("REPRO_OBS", "loud")
    with pytest.raises(ValueError, match="obs mode"):
        run_experiment(GridConfig(), n_jobs=10)


def test_obs_events_do_not_change_sim_clock_semantics():
    """Trailing OBS samples must not stretch the reported makespan."""
    base = run_experiment(GridConfig(), n_jobs=100)
    fine = run_experiment(GridConfig(), n_jobs=100, obs="series",
                          obs_interval=50.0)
    assert fine.makespan == base.makespan
    assert fine.telemetry.n_samples > 100


# -- counter/event-stream consistency ---------------------------------------

def _check_counter_invariants(seed: int) -> None:
    cfg = GridConfig(seed=seed, n_regions=2, sites_per_region=3)
    r = run_experiment(cfg, n_jobs=80, obs="series")
    tel = r.telemetry
    c, calls = tel.counters, tel.phase_calls
    # every handled event of a phase-mapped kind passed through its span
    assert c["event.SUBMIT"] + c.get("event.FLUSH", 0) == \
        calls["broker.dispatch"]
    assert c["event.CPU_DONE"] == calls["cpu.done"] == r.completed_jobs == 80
    assert c.get("event.NET", 0) == calls.get("net.events", 0)
    # one sample per OBS event plus the baseline sample taken at arming
    assert tel.n_samples == c.get("event.OBS", 0) + 1
    # exclusive phase times partition measured wall
    assert sum(tel.phase_self_s.values()) <= tel.wall_s + 1e-9
    for name, total in tel.phase_total_s.items():
        assert tel.phase_self_s[name] <= total + 1e-12, name


@pytest.mark.parametrize("seed", [0, 3, 11])
def test_counter_invariants_seeded(seed):
    """Fixed-seed slice of the property probe — runs without hypothesis."""
    _check_counter_invariants(seed)


def test_counter_invariants_property():
    """Hypothesis-driven probe over arbitrary world seeds."""
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.settings(max_examples=10, deadline=None)
    @hypothesis.given(seed=st.integers(0, 2**32 - 1))
    def probe(seed):
        _check_counter_invariants(seed)

    probe()


# -- result-surface plumbing ------------------------------------------------

def test_prefetch_ledger_surfaces():
    spec = get_scenario("paper_baseline")
    r = run_spec(spec, n_jobs=50)
    assert r.prefetches == 0 and r.prefetch_gb == 0.0
    econ = run_experiment(GridConfig(), strategy="economic", n_jobs=200,
                          econ_interval=500.0)
    assert econ.prefetches > 0 and econ.prefetch_gb > 0.0


def test_run_scenario_rows_carry_phases(tmp_path):
    spec = ScenarioSpec(name="obs_smoke", description="x",
                        tier_fanouts=(2, 3), n_jobs=60, seeds=(0,),
                        obs="trace")
    rows = run_scenario(spec, obs_dir=str(tmp_path))
    row = rows[0]
    assert set(row["phases"]) == {"dispatch_s", "strategy_plan_s",
                                  "flush_s", "other_s"}
    assert sum(row["phases"].values()) == pytest.approx(
        row["wall_s"], abs=0.1 * max(row["wall_s"], 0.01))
    assert row["counters"]["event.SUBMIT"] == 60
    assert (tmp_path / "obs_smoke_s0.telemetry.json").exists()
    assert (tmp_path / "obs_smoke_s0.trace.json").exists()
    assert (tmp_path / "obs_smoke_s0.events.jsonl").exists()


def test_scenario_spec_obs_round_trip():
    spec = ScenarioSpec(name="x", description="x", obs="series",
                        obs_interval_s=120.0)
    clone = ScenarioSpec.from_dict(spec.to_dict())
    assert clone.obs == "series" and clone.obs_interval_s == 120.0
    with pytest.raises(ValueError, match="obs"):
        ScenarioSpec(name="x", description="x", obs="loud")


def test_simulator_rejects_bad_obs_args():
    from repro.core.workload import build_catalog, build_topology, generate_jobs
    cfg = GridConfig()
    topo = build_topology(cfg)
    with pytest.raises(ValueError, match="obs mode"):
        GridSimulator(topo, build_catalog(cfg, topo), obs="loud")


def test_default_interval_exported():
    assert DEFAULT_OBS_INTERVAL_S == 300.0
    assert OBS_MODES == ("off", "report", "series", "trace")
    assert GridSampler().ring.capacity == 8192


# -- parts, the GC hook and profiler annotations ----------------------------

FLUSH_PARTS = ("net.flush.stage", "net.flush.launch", "net.flush.fetch",
               "net.flush.apply")
SMALL = GridConfig(n_regions=2, sites_per_region=3)


def test_part_counts_ns_and_leaves_phases_alone():
    """A part adds its nanoseconds to ``<name>_ns`` and carves nothing out
    of the enclosing span: self time stays the inclusive time, and the
    self times still partition wall."""
    p = Probe("trace", trace=TraceWriter())
    with p.span("net.flush"):
        for _ in range(2):
            with p.part("net.flush.stage"):
                sum(range(2000))
    ns = p.counters["net.flush.stage_ns"]
    assert isinstance(ns, int) and 0 < ns <= p.phase_total_s["net.flush"] * 1e9
    assert p.phase_calls == {"net.flush": 1}
    assert p.phase_self_s["net.flush"] == p.phase_total_s["net.flush"]
    report = p.finalize()
    assert sum(report.phase_self_s.values()) <= report.wall_s
    assert report.phase_breakdown()["flush_s"] == round(
        p.phase_self_s["net.flush"], 6)
    # trace mode: one nested Chrome event per part activation
    spans = [e for e in p.trace.events if e.get("ph") == "X"]
    outer = next(e for e in spans if e["name"] == "net.flush")
    inner = [e for e in spans if e["name"] == "net.flush.stage"]
    assert len(inner) == 2
    for e in inner:
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3


@pytest.mark.parametrize("net", ["numpy", "device", "device-interpret"])
def test_flush_parts_only_on_the_kernel_routes(net):
    """The four flush parts are timed on the kernel flush routes alone
    (here ``device-interpret``; ``device`` off the chip takes the host
    oracle), and together stay within the ``net.flush`` phase."""
    r = run_experiment(SMALL, n_jobs=10, net=net, obs="report")
    tel = r.telemetry
    parts = {n: tel.counters.get(n + "_ns") for n in FLUSH_PARTS}
    if net != "device-interpret":
        assert set(parts.values()) == {None}
        return
    assert all(v > 0 for v in parts.values()), parts
    assert sum(parts.values()) <= tel.phase_total_s["net.flush"] * 1e9


def test_obs_bit_identical_device_interpret():
    base = _metrics(run_experiment(SMALL, n_jobs=10, net="device-interpret"))
    got = _metrics(run_experiment(SMALL, n_jobs=10, net="device-interpret",
                                  obs="report"))
    assert got == base


def test_gc_hook_counts_a_forced_collection():
    import gc

    p = Probe("report")
    before = len(gc.callbacks)
    with p.running():
        assert len(gc.callbacks) == before + 1
        gc.collect()
    assert len(gc.callbacks) == before
    assert p.counters["host.gc.collections"] >= 1
    assert p.counters["host.gc.pause_ns"] > 0
    # the hook is gone: a later collection is not counted
    n = p.counters["host.gc.collections"]
    gc.collect()
    assert p.counters["host.gc.collections"] == n


def test_gc_hook_removed_after_a_run_and_a_failed_one(monkeypatch):
    import gc

    before = len(gc.callbacks)
    r = run_experiment(SMALL, n_jobs=10, obs="report")
    assert len(gc.callbacks) == before
    assert {"host.gc.collections", "host.gc.pause_ns"} <= set(
        r.telemetry.counters)

    def broken(self, kind, payload):
        raise RuntimeError("handler fault")

    monkeypatch.setattr(GridSimulator, "_handle_event", broken)
    with pytest.raises(RuntimeError, match="handler fault"):
        run_experiment(SMALL, n_jobs=10, obs="report")
    assert len(gc.callbacks) == before


def test_flush_parts_nest_in_net_flush_on_the_profiler_trace(tmp_path):
    """Under ``jax.profiler.trace`` the program's spans and parts are
    annotations on the host thread: each part of a flush lies inside its
    ``net.flush``, in order (apply twice: the add-back of the flush
    instant in the op wrapper, then the engine's write-back)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        run_experiment(SMALL, n_jobs=5, net="device-interpret", obs="report")
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
              for line in host.lines for ev in line.events]
    flushes = [(s, e) for s, e, n in events if n == "net.flush"]
    parts = [(s, e, n) for s, e, n in events if n in FLUSH_PARTS]
    assert flushes and parts
    nested = 0
    for s0, e0 in flushes:
        inside = sorted((s, e, n) for s, e, n in parts if s0 <= s and e <= e0)
        if inside:
            names = [n for _, _, n in inside]
            assert names == [*FLUSH_PARTS, "net.flush.apply"], names
            nested += 1
    assert nested == len(parts) // 5 > 0


BATCH_PARTS = ("broker.batch.stage", "broker.batch.launch",
               "broker.batch.fetch")


def test_batch_parts_nest_in_select_batch_on_the_profiler_trace(tmp_path):
    """The bulk broker's three parts lie inside each ``broker.select_batch``
    on a ``jax.profiler`` trace, in order; the counters count its calls
    and the jobs they placed."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        r = run_experiment(SMALL, n_jobs=12, broker="jax", arrival_burst=4,
                           obs="report")
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
              for line in host.lines for ev in line.events]
    calls = [(s, e) for s, e, n in events if n == "broker.select_batch"]
    parts = [(s, e, n) for s, e, n in events if n in BATCH_PARTS]
    assert len(calls) == 3 and len(parts) == 9
    for s0, e0 in calls:
        inside = sorted((s, e, n) for s, e, n in parts if s0 <= s and e <= e0)
        assert [n for _, _, n in inside] == list(BATCH_PARTS)
    tel = r.telemetry
    assert tel.counters["broker.batch_calls"] == 3
    assert tel.counters["broker.batch_jobs"] == 12
    assert tel.phase_calls["broker.select_batch"] == 3
    ns = sum(tel.counters[n + "_ns"] for n in BATCH_PARTS)
    assert 0 < ns <= tel.phase_total_s["broker.select_batch"] * 1e9


def test_batch_broker_picks_bit_identical_with_obs_off():
    """The parts time the broker and change none of its picks."""
    from repro.core import build_catalog, build_topology, generate_jobs
    from repro.core.jaxsched import JaxScheduler

    picks = []
    for probe in (None, Probe("report")):
        topo = build_topology(SMALL)
        cat = build_catalog(SMALL, topo)
        for i, s in enumerate(topo.sites):
            s.queued_work = (i % 3) * 60e9
        broker = JaxScheduler(cat, topo)
        broker.probe = probe
        picks.append(broker.select_batch(
            [j.required for j in generate_jobs(SMALL, 20)]))
    assert picks[0] == picks[1]
    assert probe.counters["broker.batch.launch_ns"] > 0
    off = run_experiment(SMALL, n_jobs=12, broker="jax", arrival_burst=4)
    on = run_experiment(SMALL, n_jobs=12, broker="jax", arrival_burst=4,
                        obs="report")
    assert _metrics(on) == _metrics(off)
