"""Benchmark harness — one function per paper table/figure + substrate
µbenches. Prints ``name,us_per_call,derived`` CSV rows and writes
``results/bench_*.csv`` detail files.

Every simulation cell is config-driven: a figure is a ``sweep`` of the
``paper_baseline`` scenario (``repro.core.scenarios``) along one axis
through ``repro.launch.experiments``; the scale sweep reuses the
``bulk_diana`` scenario. The full beyond-paper scenario registry runs via
``python -m repro.launch.experiments --all`` (see docs/SCENARIOS.md).

Paper figures (all on the Table-1 grid: 4 regions x 13 sites, 10 GB SEs,
1000/10 Mbps LAN/WAN, 5 job types x 12 x 500 MB files):

  fig4  average job time vs number of jobs   (HRS / BHR / LRU)
  fig5  average job time at 1000 jobs
  fig6  average inter-region communications per job
  fig7  average job time vs WAN bandwidth (500 jobs)

Beyond-paper: scheduler ablation (the paper's scheduler vs random /
least-loaded / shortest-transfer), jit'd dispatch throughput, fault-
tolerance run, a scale sweep through the batch-dispatch broker — 2k/5k/
10k jobs on the paper grid, the 500-site rungs (incl. the saturated
numpy-vs-device engine pair) and the 5000-site/1M-job batched-engine
rung (writes ``results/BENCH_scale.json``), a network-engine sweep
quantifying the per-link path-contention fidelity change (writes
``results/BENCH_net.json``), kernel
µbenches (interpret mode on CPU).

Run ``python benchmarks/run.py --help`` for the bench list; name benches
as positional args to run a subset (default: all).
"""

from __future__ import annotations

import argparse
import csv
import dataclasses
import json
import os

from repro.compile_cache import enable_compile_cache
from repro.core.quantities import US_PER_S

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")

STRATS = ("hrs", "bhr", "lru")


def _probe():
    """Shared bench timer: a report-mode :class:`repro.obs.Probe`. Benches
    time work with ``with p.span(name): ...`` + ``p.elapsed_us(name)``
    instead of hand-rolled ``perf_counter`` deltas — same clock, one
    implementation, and nested spans compose (a bench can reuse the
    simulator's own phase names when it wants a breakdown)."""
    from repro.obs import Probe
    return Probe("report")


def _cfg(**kw):
    from repro.core import GridConfig
    return GridConfig(**kw)


def _baseline():
    from repro.core import SCENARIOS
    return SCENARIOS["paper_baseline"]


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


def _write_csv(name: str, header: list[str], rows: list[list]) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, name), "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)


def fig4_avg_job_time_vs_njobs() -> None:
    from repro.launch.experiments import sweep
    ns = (100, 200, 300, 400, 500)
    p = _probe()
    with p.span("fig4"):
        res = sweep(_baseline(), axis="n_jobs", values=ns, strategies=STRATS)
    us = p.elapsed_us("fig4") / len(ns)
    rows = [[n] + [round(res[(n, s)].avg_job_time, 1) for s in STRATS]
            for n in ns]
    _write_csv("bench_fig4.csv", ["n_jobs", *STRATS], rows)
    last = rows[-1]
    gain = 100.0 * (last[2] - last[1]) / last[2]
    _row("fig4_avg_job_time", us, f"hrs_over_bhr_at_500={gain:.1f}%")


def fig5_avg_job_time_1000() -> None:
    from repro.launch.experiments import sweep
    p = _probe()
    with p.span("fig5"):
        res = sweep(_baseline(), axis="n_jobs", values=(1000,),
                    strategies=STRATS)
    us = p.elapsed_us("fig5")
    vals = {s: res[(1000, s)].avg_job_time for s in STRATS}
    _write_csv("bench_fig5.csv", ["strategy", "avg_job_time_s"],
               [[s, round(vals[s], 1)] for s in STRATS])
    gain = 100.0 * (vals["bhr"] - vals["hrs"]) / vals["bhr"]
    _row("fig5_1000_jobs", us, f"hrs={vals['hrs']:.0f}s,"
         f"bhr={vals['bhr']:.0f}s,lru={vals['lru']:.0f}s,gain={gain:.1f}%")


def fig6_inter_communications() -> None:
    from repro.launch.experiments import sweep
    p = _probe()
    with p.span("fig6"):
        res = sweep(_baseline(), axis="n_jobs", values=(500,),
                    strategies=STRATS)
    us = p.elapsed_us("fig6")
    vals = {s: res[(500, s)].avg_inter_comms for s in STRATS}
    _write_csv("bench_fig6.csv", ["strategy", "avg_inter_comms"],
               [[s, round(vals[s], 3)] for s in STRATS])
    _row("fig6_inter_comms", us,
         ";".join(f"{s}={vals[s]:.2f}" for s in STRATS))


def fig7_wan_bandwidth_sweep() -> None:
    from repro.launch.experiments import sweep
    mbpss = (10, 50, 100, 500, 1000)
    p = _probe()
    with p.span("fig7"):
        res = sweep(_baseline(), axis="wan_mbps", values=mbpss,
                    strategies=STRATS)
    us = p.elapsed_us("fig7") / len(mbpss)
    rows = [[m] + [round(res[(m, s)].avg_job_time, 1) for s in STRATS]
            for m in mbpss]
    _write_csv("bench_fig7.csv", ["wan_mbps", *STRATS], rows)
    lo, hi = rows[0], rows[-1]
    _row("fig7_wan_sweep", us,
         f"gap@10Mbps={100*(lo[2]-lo[1])/lo[2]:.1f}%,"
         f"gap@1000Mbps={100*(hi[2]-hi[1])/max(hi[2],1e-9):.1f}%")


def scheduler_ablation() -> None:
    """Beyond-paper: hold replication = HRS, vary the scheduler."""
    from repro.launch.experiments import sweep
    scheds = ("dataaware", "random", "leastloaded", "shortesttransfer")
    base = dataclasses.replace(_baseline(), n_jobs=300)
    p = _probe()
    with p.span("sched_ablation"):
        res = sweep(base, axis="scheduler", values=scheds, strategies=("hrs",))
    us = p.elapsed_us("sched_ablation")
    vals = {s: res[(s, "hrs")].avg_job_time for s in scheds}
    _write_csv("bench_sched_ablation.csv", ["scheduler", "avg_job_time_s"],
               [[s, round(vals[s], 1)] for s in scheds])
    _row("scheduler_ablation", us,
         ";".join(f"{s}={vals[s]:.0f}" for s in scheds))


def eviction_phase_ablation() -> None:
    """Isolate the paper's novel two-phase eviction: HRS vs HRS with plain
    LRU eviction (everything else identical)."""
    from repro.launch.experiments import sweep
    p = _probe()
    with p.span("eviction_ablation"):
        res = sweep(_baseline(), axis="n_jobs", values=(500,),
                    strategies=("hrs", "hrs_singlephase"))
    full, single = res[(500, "hrs")], res[(500, "hrs_singlephase")]
    us = p.elapsed_us("eviction_ablation")
    gain = 100 * (single.avg_job_time - full.avg_job_time) / single.avg_job_time
    _write_csv("bench_eviction_ablation.csv",
               ["strategy", "avg_job_time_s", "avg_inter_comms"],
               [["hrs_twophase", round(full.avg_job_time, 1),
                 round(full.avg_inter_comms, 3)],
                ["hrs_singlephase", round(single.avg_job_time, 1),
                 round(single.avg_inter_comms, 3)]])
    _row("eviction_phase_ablation", us,
         f"two_phase={full.avg_job_time:.0f}s;single_phase="
         f"{single.avg_job_time:.0f}s;two_phase_gain={gain:.1f}%;"
         f"ic={full.avg_inter_comms:.2f}vs{single.avg_inter_comms:.2f}")


def sched_throughput() -> None:
    """jit'd dispatch decision latency (vectorized paper §3.2)."""
    from repro.core import build_catalog, build_topology, generate_jobs
    from repro.core.jaxsched import JaxScheduler
    cfg = _cfg()
    topo = build_topology(cfg)
    cat = build_catalog(cfg, topo)
    js = JaxScheduler(cat, topo)
    jobs = generate_jobs(cfg, 64)
    js.select(jobs[0].required)          # warm up
    p = _probe()
    reps = 20
    with p.span("dispatch"):
        for _ in range(reps):
            js.select_batch([j.required for j in jobs])
    us = p.elapsed_us("dispatch") / (reps * len(jobs))
    _row("jit_dispatch", us, f"us_per_decision={us:.1f}")


def failover_recovery() -> None:
    """Fault-tolerance: DES with failures + speculative backups."""
    from repro.core import run_experiment
    p = _probe()
    with p.span("failover"):
        base = run_experiment(_cfg(), strategy="hrs", n_jobs=200)
        failures = [(5, 2000.0, 4000.0), (20, 6000.0, 5000.0)]
        failed = run_experiment(_cfg(), strategy="hrs", n_jobs=200,
                                failures=failures)
        slow = run_experiment(_cfg(), strategy="hrs", n_jobs=200,
                              slowdowns=[(7, 1000.0, 8000.0, 0.05)],
                              speculative_backups=True)
    us = p.elapsed_us("failover")
    # n_jobs is the *submitted* count and is 200 by construction; only
    # completed_jobs (len(records)) can tell whether recovery really drained
    # the queue.
    assert failed.completed_jobs == failed.n_jobs, (
        f"failover lost jobs: {failed.completed_jobs}/{failed.n_jobs}")
    _row("failover_recovery", us,
         f"base={base.avg_job_time:.0f}s;with_failures={failed.avg_job_time:.0f}s;"
         f"stragglers+spec={slow.avg_job_time:.0f}s;"
         f"all_jobs_completed={failed.completed_jobs == failed.n_jobs}")


def scale_sweep(scale_jobs: int = 100_000) -> None:
    """Beyond-paper: engine scalability sweep with burst arrivals
    dispatched through the jitted batch broker — the ``bulk_diana``
    scenario at 2k/5k/10k jobs on the 52-site paper grid (multi-seed),
    the 500-site / 100k-job ``grid_500`` scale point (incremental
    presence bitmap + blocked st-cost snapshot hot paths), the
    ``grid_500_saturated`` backlog pathology run under *both* network
    engines (numpy incremental vs batched ``device`` — the engine-pair
    wall-clock evidence), the eviction-scan-bound ``grid_500_evict``
    planner-pathology point, and the 5000-site / 1M-job ``grid_5000``
    rung on the batched engine. The 500-site rungs additionally re-run
    with ``strategy_mode="batch"`` (one ``strategy_plan`` pass per burst
    plus cached continuation plans);
    each batched row carries a ``batched_strategy_speedup`` column — its
    sequential twin's wall clock over its own. On ``grid_500_evict`` the
    batched planner must clear 2x: the sequential planner's per-store
    Python scans (holders walk + per-resident eviction checks) are the
    wall there, and the batched path amortizes them. ``scale_jobs`` caps
    *every* cell's job count (the CI smoke runs the whole sweep at
    2000). Writes machine-readable ``results/BENCH_scale.json``.

    Every cell runs with ``obs="report"`` (same overhead for every row,
    so the ratio columns stay fair) and carries the measured four-phase
    wall breakdown (``"phases"``: dispatch / strategy_plan / flush /
    other seconds partitioning ``wall_s``) plus the probe counters'
    plan-cache split — the engine-bound-vs-planner-bound evidence,
    measured rather than inferred."""
    from repro.core import SCENARIOS
    from repro.launch.experiments import run_scenario
    rows = []
    p = _probe()
    raw = [("bulk_diana", min(n, scale_jobs), seeds)
           for n, seeds in ((2000, (0, 1, 2)), (5000, (0, 1)),
                            (10000, (0, 1)))]
    raw.append(("grid_500", min(100_000, scale_jobs), (0,)))
    raw.append(("grid_5000", min(1_000_000, scale_jobs), (0,)))
    # a low cap collapses rungs onto the same (scenario, n_jobs) cell:
    # keep each once, with its widest seed set
    merged: dict = {}
    for scen, n, seeds in raw:
        key = (scen, n)
        if key not in merged or len(seeds) > len(merged[key]):
            merged[key] = seeds
    cells = [(scen, n, seeds) for (scen, n), seeds in merged.items()]
    specs = [(SCENARIOS[scen], n, seeds) for scen, n, seeds in cells]
    # the saturated cell runs twice — same world, numpy vs device engine
    sat = SCENARIOS["grid_500_saturated"]
    for net in ("numpy", "device"):
        specs.append((dataclasses.replace(sat, net=net),
                      min(sat.n_jobs, scale_jobs), (0,)))
    # the eviction-scan-bound planner regime (the batched replica
    # strategy's discriminating cell, sequential twin first)
    evict = SCENARIOS["grid_500_evict"]
    specs.append((evict, min(evict.n_jobs, scale_jobs), (0,)))
    # the 500-site rungs re-run with the batched strategy engine — one
    # strategy_plan pass per 50-job burst instead of 50 sequential
    # plan_fetch walks. grid_5000 stays sequential: the batched planner's
    # dense (S, S, depth) path tensor is a 500-site-class structure.
    for base, n in ((SCENARIOS["grid_500"], min(100_000, scale_jobs)),
                    (dataclasses.replace(sat, net="numpy"),
                     min(sat.n_jobs, scale_jobs)),
                    (dataclasses.replace(sat, net="device"),
                     min(sat.n_jobs, scale_jobs)),
                    (evict, min(evict.n_jobs, scale_jobs))):
        specs.append((dataclasses.replace(base, strategy_mode="batch"),
                      n, (0,)))
    with p.span("scale_sweep"):
        for spec, n, seeds in specs:
            cell = dataclasses.replace(spec, obs="report")
            for row in run_scenario(cell, n_jobs=n, seeds=seeds):
                out = {
                    "scenario": spec.name, "n_sites": spec.n_sites,
                    "net": spec.net, "strategy_mode": spec.strategy_mode,
                    "n_jobs": row["n_jobs"], "seed": row["seed"],
                    "wall_s": row["wall_s"],
                    "avg_job_time_s": row["avg_job_time_s"],
                    "avg_inter_comms": row["avg_inter_comms"],
                    "completed_jobs": row["completed_jobs"],
                    "makespan_s": row["makespan_s"],
                    "phases": row["phases"],
                }
                counters = row.get("counters", {})
                plan_cache = {k.split(".", 1)[1]: v
                              for k, v in counters.items()
                              if k.startswith("plan_cache.")}
                if plan_cache:
                    out["plan_cache"] = plan_cache
                rows.append(out)
    # derived column: wall-clock ratio vs the matching sequential cell
    seq_wall = {(r["scenario"], r["net"], r["n_jobs"], r["seed"]): r["wall_s"]
                for r in rows if r["strategy_mode"] == "sequential"}
    for r in rows:
        key = (r["scenario"], r["net"], r["n_jobs"], r["seed"])
        if r["strategy_mode"] == "batch" and key in seq_wall:
            r["batched_strategy_speedup"] = round(
                seq_wall[key] / max(r["wall_s"], 1e-9), 2)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "BENCH_scale.json"), "w") as f:
        json.dump({"strategy": "hrs", "scheduler": "dataaware",
                   "broker": "jax", "arrival_burst": 50, "rows": rows}, f,
                  indent=1)
    us = p.elapsed_us("scale_sweep") / len(rows)
    biggest = max(rows, key=lambda r: (r["n_sites"], r["n_jobs"]))
    sat_wall = {r["net"]: r["wall_s"] for r in rows
                if r["scenario"] == "grid_500_saturated"
                and r["strategy_mode"] == "sequential"}
    speedup = sat_wall["numpy"] / max(sat_wall["device"], 1e-9)
    batched = [r for r in rows if r["strategy_mode"] == "batch"
               and "batched_strategy_speedup" in r]
    b500 = next((r["batched_strategy_speedup"] for r in batched
                 if r["scenario"] == "grid_500"), float("nan"))
    bevict = next((r["batched_strategy_speedup"] for r in batched
                   if r["scenario"] == "grid_500_evict"), float("nan"))
    g500 = next((r for r in rows if r["scenario"] == "grid_500"
                 and r["strategy_mode"] == "sequential"), None)
    if g500 is not None:
        ph, wall = g500["phases"], max(g500["wall_s"], 1e-9)
        g500_phases = (f"grid_500_phases=dispatch:{ph['dispatch_s']/wall:.0%}"
                       f"/plan:{ph['strategy_plan_s']/wall:.0%}"
                       f"/flush:{ph['flush_s']/wall:.0%}"
                       f"/other:{ph['other_s']/wall:.0%}")
    else:
        g500_phases = "grid_500_phases=n/a"
    _row("scale_sweep", us,
         f"rows={len(rows)};biggest={biggest['scenario']};"
         f"biggest_wall={biggest['wall_s']:.1f}s;"
         f"biggest_jobs={biggest['n_jobs']};"
         f"biggest_completed={biggest['completed_jobs']};"
         f"saturated_device_speedup={speedup:.2f}x;"
         f"batched_strategy_speedup_500={b500:.2f}x;"
         f"batched_strategy_speedup_evict={bevict:.2f}x;"
         f"{g500_phases}")


def strategy_sweep(n_jobs: int = 10000) -> None:
    """Replication-strategy matrix: the reactive paper strategies
    {hrs, bhr, lru} vs the access-history-driven pair {economic,
    predictive} on the two discriminating regimes — ``cache_starved``
    (eviction pressure) and ``hotset_drift`` (the popular file set shifts
    mid-run). Multi-seed; writes ``results/BENCH_strategies.json``."""
    from repro.core import SCENARIOS
    from repro.launch.experiments import run_scenario
    strategies = ("hrs", "bhr", "lru", "economic", "predictive")
    seeds = (0, 1)
    rows = []
    p = _probe()
    with p.span("strategy_sweep"):
        for scen in ("cache_starved", "hotset_drift"):
            base = SCENARIOS[scen]
            for strat in strategies:
                spec = dataclasses.replace(base, strategy=strat)
                for row in run_scenario(spec, n_jobs=n_jobs, seeds=seeds):
                    rows.append({"scenario": scen, "strategy": strat, **row})
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "BENCH_strategies.json"), "w") as f:
        json.dump({"n_jobs": n_jobs, "seeds": list(seeds),
                   "strategies": list(strategies), "rows": rows}, f, indent=1)

    def mean_ajt(scen: str, strat: str) -> float:
        sel = [r["avg_job_time_s"] for r in rows
               if r["scenario"] == scen and r["strategy"] == strat]
        return sum(sel) / len(sel)

    us = p.elapsed_us("strategy_sweep") / len(rows)
    hrs_d, pred_d = mean_ajt("hotset_drift", "hrs"), mean_ajt("hotset_drift",
                                                              "predictive")
    hrs_s, econ_s = mean_ajt("cache_starved", "hrs"), mean_ajt("cache_starved",
                                                               "economic")
    _row("strategy_sweep", us,
         f"drift_hrs={hrs_d:.0f}s;drift_predictive={pred_d:.0f}s;"
         f"predictive_gain={100 * (hrs_d - pred_d) / hrs_d:+.1f}%;"
         f"starved_hrs={hrs_s:.0f}s;starved_economic={econ_s:.0f}s;"
         f"economic_gain={100 * (hrs_s - econ_s) / hrs_s:+.1f}%")


def net_sweep(n_jobs: int = 10000) -> None:
    """Network-engine fidelity sweep: deep-tree scenarios under the legacy
    topmost-uplink model vs the per-link path model (the incremental
    numpy engine). Writes ``results/BENCH_net.json``."""
    from repro.core import SCENARIOS
    from repro.launch.experiments import run_spec
    p = _probe()
    fidelity = []
    for scen in ("deep_5tier", "deep_contended"):
        base = SCENARIOS[scen]
        for net in ("topmost", "numpy"):
            spec = dataclasses.replace(base, net=net)
            cell = f"fidelity:{scen}:{net}"
            with p.span(cell):
                r = run_spec(spec, n_jobs=n_jobs)
            fidelity.append({
                "scenario": scen, "net": net, "n_jobs": n_jobs,
                "wall_s": round(p.elapsed_us(cell) / US_PER_S, 3),
                "avg_job_time_s": r.avg_job_time,
                "avg_inter_comms": r.avg_inter_comms,
                "total_wan_gb": r.total_wan_gb,
                "makespan_s": r.makespan,
                "completed_jobs": r.completed_jobs,
            })
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "BENCH_net.json"), "w") as f:
        json.dump({"n_jobs": n_jobs, "fidelity": fidelity}, f, indent=1)
    us = sum(p.phase_total_s.values()) * US_PER_S / len(fidelity)
    by = {(r["scenario"], r["net"]): r for r in fidelity}
    d5 = (by[("deep_5tier", "numpy")]["avg_job_time_s"]
          / by[("deep_5tier", "topmost")]["avg_job_time_s"] - 1.0)
    dc = (by[("deep_contended", "numpy")]["avg_job_time_s"]
          / by[("deep_contended", "topmost")]["avg_job_time_s"] - 1.0)
    _row("net_sweep", us,
         f"deep5_fidelity={100 * d5:+.1f}%;contended_fidelity={100 * dc:+.1f}%")


def kernel_flash_attention() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q = jnp.ones((2, 8, 512, 64), jnp.bfloat16)
    k = jnp.ones((2, 4, 512, 64), jnp.bfloat16)
    v = jnp.ones((2, 4, 512, 64), jnp.bfloat16)
    f = jax.jit(lambda a, b, c: flash_attention_ref(a, b, c, causal=True))
    f(q, k, v).block_until_ready()
    p = _probe()
    with p.span("flash_ref"):
        for _ in range(5):
            f(q, k, v).block_until_ready()
    us = p.elapsed_us("flash_ref") / 5
    flops = 2 * 2 * 8 * 512 * 512 * 64 * 2
    _row("kernel_flash_ref_cpu", us, f"gflops_s={flops/us*1e6/1e9:.1f}")


def kernel_selective_scan() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.selective_scan.ref import selective_scan_ref
    Bz, S, Di, N = 2, 512, 256, 16
    x = jnp.ones((Bz, S, Di), jnp.float32)
    dt = jnp.full((Bz, S, Di), 0.1, jnp.float32)
    B = jnp.ones((Bz, S, N), jnp.float32)
    C = jnp.ones((Bz, S, N), jnp.float32)
    A = -jnp.ones((Di, N), jnp.float32)
    D = jnp.ones((Di,), jnp.float32)
    h0 = jnp.zeros((Bz, Di, N), jnp.float32)
    f = jax.jit(lambda *a: selective_scan_ref(*a)[0])
    f(x, dt, B, C, A, D, h0).block_until_ready()
    p = _probe()
    with p.span("scan_ref"):
        for _ in range(5):
            f(x, dt, B, C, A, D, h0).block_until_ready()
    us = p.elapsed_us("scan_ref") / 5
    _row("kernel_scan_ref_cpu", us,
         f"tokens_per_s={Bz*S/us*1e6:.0f}")


#: name -> (fn, one-line description); listed by ``--help`` and runnable
#: as positional args. Order is the default full run.
BENCHES = {
    "fig4": (fig4_avg_job_time_vs_njobs,
             "avg job time vs n_jobs, HRS/BHR/LRU (paper fig4)"),
    "fig5": (fig5_avg_job_time_1000, "avg job time at 1000 jobs (paper fig5)"),
    "fig6": (fig6_inter_communications,
             "inter-region communications per job (paper fig6)"),
    "fig7": (fig7_wan_bandwidth_sweep,
             "avg job time vs WAN bandwidth (paper fig7)"),
    "sched_ablation": (scheduler_ablation,
                       "scheduler ablation at fixed HRS replication"),
    "eviction_ablation": (eviction_phase_ablation,
                          "HRS two-phase vs single-phase eviction"),
    "sched_throughput": (sched_throughput, "jitted dispatch decision latency"),
    "failover": (failover_recovery,
                 "fault-tolerance run: failures + speculative backups"),
    "scale_sweep": (scale_sweep,
                    "2k/5k/10k-job + 500-site/100k-job + saturated "
                    "numpy-vs-device engine pair + eviction-bound "
                    "planner point + 5000-site/1M-job scale sweep, "
                    "500-site rungs also in batched strategy mode "
                    "-> BENCH_scale.json"),
    "strategy_sweep": (strategy_sweep,
                       "reactive vs economic/predictive strategy matrix on "
                       "cache_starved + hotset_drift -> "
                       "BENCH_strategies.json"),
    "net_sweep": (net_sweep,
                  "network-engine sweep: topmost-vs-path fidelity "
                  "-> BENCH_net.json"),
    "kernel_flash": (kernel_flash_attention, "flash-attention µbench (CPU ref)"),
    "kernel_scan": (kernel_selective_scan, "selective-scan µbench (CPU ref)"),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=("Benchmark harness: prints name,us_per_call,derived "
                     "CSV rows and writes detail files under results/."),
        epilog="benches:\n" + "\n".join(
            f"  {name:>18}  {desc}" for name, (_, desc) in BENCHES.items()),
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("bench", nargs="*", choices=[[]] + list(BENCHES),
                    metavar="BENCH",
                    help="benches to run (default: all; see list below)")
    ap.add_argument("--net-jobs", type=int, default=10000,
                    help="job count for the net_sweep scale point "
                         "(default 10000)")
    ap.add_argument("--strategy-jobs", type=int, default=10000,
                    help="job count per strategy_sweep cell (default 10000)")
    ap.add_argument("--scale-jobs", type=int, default=1_000_000,
                    help="cap on every scale_sweep cell's job count "
                         "(default 1000000 = the full 2k/5k/10k + "
                         "500-site/100k + saturated pair + "
                         "5000-site/1M sweep)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name in args.bench or BENCHES:
        fn = BENCHES[name][0]
        if name == "net_sweep":
            fn(args.net_jobs)
        elif name == "strategy_sweep":
            fn(args.strategy_jobs)
        elif name == "scale_sweep":
            fn(args.scale_jobs)
        else:
            fn()


if __name__ == "__main__":
    main()
