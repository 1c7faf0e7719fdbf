"""Config-driven experiment runner: fan named scenarios through the engine.

Every experiment in this repo — the paper figures, the beyond-paper
regimes, ad-hoc CLI runs — is one :class:`repro.core.ScenarioSpec` lowered
to a ``run_experiment`` call. This module is the single place that does the
lowering (:func:`run_spec`), sweeps an axis of specs for the figure
benchmarks (:func:`sweep`), and runs the registry end to end:

    PYTHONPATH=src python -m repro.launch.experiments --list
    PYTHONPATH=src python -m repro.launch.experiments --scenario paper_baseline bulk_diana
    PYTHONPATH=src python -m repro.launch.experiments --scenario drift_strategies   # a named sweep
    PYTHONPATH=src python -m repro.launch.experiments --all

``--all`` (or an explicit ``--scenario`` list) writes machine-readable
``results/BENCH_scenarios.json``: per scenario the full spec plus one row
per seed with ``wall_s`` / ``avg_job_time_s`` / ``avg_inter_comms`` /
``completed_jobs`` / ``makespan_s``. ``--scenario`` also accepts named
:class:`repro.core.SweepSpec` grids (``--list`` shows both registries) —
a sweep's whole (axis value x seed) grid lands under the payload's
``"sweeps"`` key, one row per run with the axis value attached.
``--jobs N`` overrides every scenario's job count for quick smoke passes.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Iterable, Sequence

from repro.compile_cache import enable_compile_cache
from repro.core import (ExperimentResult, SCENARIOS, SWEEPS, ScenarioSpec,
                        SweepSpec, arrival_schedule, get_scenario, get_sweep,
                        injections, run_experiment, to_grid_config,
                        with_axis)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results")
ROW_KEYS = ("wall_s", "avg_job_time_s", "avg_inter_comms", "completed_jobs",
            "makespan_s")


def run_spec(spec: ScenarioSpec, *, seed: int | None = None,
             n_jobs: int | None = None) -> ExperimentResult:
    """Lower one spec (at one seed) to ``run_experiment`` and run it."""
    seed = spec.seeds[0] if seed is None else seed
    n = spec.n_jobs if n_jobs is None else n_jobs
    cfg = to_grid_config(spec, seed)
    failures, slowdowns = injections(spec, seed=seed)
    return run_experiment(
        cfg, scheduler=spec.scheduler, strategy=spec.strategy, n_jobs=n,
        failures=failures or None, slowdowns=slowdowns or None,
        broker=spec.broker, batch_window=spec.batch_window_s,
        strategy_mode=spec.strategy_mode,
        arrival_burst=spec.arrival_burst,
        arrival_times=arrival_schedule(spec, n, seed=seed),
        net=spec.net, econ=spec.econ, econ_interval=spec.econ_interval_s,
        # "off" lowers to None so the REPRO_OBS env override still applies
        # to registry scenarios that don't pin a telemetry mode
        obs=None if spec.obs == "off" else spec.obs,
        obs_interval=spec.obs_interval_s,
    )


def run_scenario(spec: ScenarioSpec, *, n_jobs: int | None = None,
                 seeds: Sequence[int] | None = None,
                 obs_dir: str | None = None) -> list[dict]:
    """Run a spec once per seed; one machine-readable row per run.

    When the run carries telemetry (``spec.obs`` or the ``REPRO_OBS``
    override), each row additionally gets the measured four-phase wall
    breakdown (``"phases"``: dispatch / strategy_plan / flush / other
    seconds, partitioning ``wall_s``) and the probe counters. With
    ``obs_dir`` set, the full telemetry JSON — and in trace mode the
    Perfetto trace + JSONL event log — is written there per run.
    """
    rows = []
    for seed in (spec.seeds if seeds is None else seeds):
        t0 = time.perf_counter()
        r = run_spec(spec, seed=seed, n_jobs=n_jobs)
        row = {
            "scenario": spec.name, "seed": seed, "n_jobs": r.n_jobs,
            "wall_s": round(time.perf_counter() - t0, 3),
            "avg_job_time_s": r.avg_job_time,
            "avg_inter_comms": r.avg_inter_comms,
            "completed_jobs": r.completed_jobs,
            "makespan_s": r.makespan,
            "total_wan_gb": r.total_wan_gb,
        }
        tel = r.telemetry
        if tel is not None:
            row["phases"] = tel.phase_breakdown(row["wall_s"])
            row["counters"] = dict(sorted(tel.counters.items()))
            if obs_dir is not None:
                os.makedirs(obs_dir, exist_ok=True)
                stem = os.path.join(obs_dir, f"{spec.name}_s{seed}")
                with open(stem + ".telemetry.json", "w") as f:
                    json.dump(tel.to_dict(), f, indent=1)
                if tel.trace is not None:
                    tel.save_trace(stem + ".trace.json")
                    tel.save_events_jsonl(stem + ".events.jsonl")
        rows.append(row)
    return rows


def run_sweep_spec(sweep: SweepSpec, *, n_jobs: int | None = None) -> dict:
    """Run a named sweep: every (axis value, seed) cell of the grid.

    Returns the sweep's ``BENCH_scenarios.json`` entry: the sweep spec,
    the base scenario, and one row per run with the axis value attached —
    a grid, not a point.
    """
    rows = []
    for value, cell in sweep.expand():
        for row in run_scenario(cell, n_jobs=n_jobs):
            rows.append({sweep.axis: value, **row})
    return {"sweep": sweep.to_dict(),
            "base_spec": get_scenario(sweep.base).to_dict(), "rows": rows}


def run_scenarios(names: Iterable[str], *, n_jobs: int | None = None,
                  out_path: str | None = None, quiet: bool = False,
                  obs: str | None = None,
                  obs_dir: str | None = None) -> dict:
    """Run each named scenario *or sweep* and write
    ``BENCH_scenarios.json`` (scenarios as points under ``"scenarios"``,
    sweeps as grids under ``"sweeps"``). ``obs`` overrides every
    scenario's telemetry mode; ``obs_dir`` receives the per-run
    telemetry/trace exports (see :func:`run_scenario`)."""
    payload: dict = {"n_jobs_override": n_jobs, "scenarios": {}, "sweeps": {}}
    for name in names:
        if name in SWEEPS:
            entry = run_sweep_spec(get_sweep(name), n_jobs=n_jobs)
            payload["sweeps"][name] = entry
            if not quiet:
                sw = entry["sweep"]
                print(f"{name:>16} sweep {sw['base']} x {sw['axis']}="
                      f"{sw['values']} rows={len(entry['rows'])}")
            continue
        spec = get_scenario(name)
        if obs is not None:
            spec = dataclasses.replace(spec, obs=obs)
        rows = run_scenario(spec, n_jobs=n_jobs, obs_dir=obs_dir)
        payload["scenarios"][name] = {"spec": spec.to_dict(), "rows": rows}
        if not quiet:
            r = rows[0]
            print(f"{name:>16} seeds={len(rows)} wall={r['wall_s']:7.2f}s "
                  f"avg_job_time={r['avg_job_time_s']:9.0f}s "
                  f"inter/job={r['avg_inter_comms']:6.2f} "
                  f"completed={r['completed_jobs']}/{r['n_jobs']} "
                  f"makespan={r['makespan_s']:9.0f}s")
    if out_path is None:
        out_path = os.path.join(RESULTS_DIR, "BENCH_scenarios.json")
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    if not quiet:
        print(f"wrote {os.path.relpath(out_path)}")
    return payload


# -- figure sweeps (used by benchmarks/run.py) ------------------------------
def sweep(base: ScenarioSpec, *, axis: str, values: Sequence,
          strategies: Sequence[str]) -> dict[tuple, ExperimentResult]:
    """Cross an axis with a set of replication strategies; returns
    ``{(value, strategy): result}``.

    This is the config-driven backbone of the per-figure benchmarks: each
    cell is ``run_spec`` of the base scenario with two fields replaced
    (:func:`repro.core.scenarios.with_axis` defines the axis vocabulary —
    every spec field plus ``wan_mbps``). Named grids live in
    :data:`repro.core.SWEEPS` (:class:`SweepSpec`) and run via
    ``--scenario NAME`` / :func:`run_sweep_spec`.
    """
    out = {}
    for v in values:
        spec = with_axis(base, axis, v)
        for s in strategies:
            out[(v, s)] = run_spec(dataclasses.replace(spec, strategy=s))
    return out


def main(argv: Sequence[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        description="Run named scenarios from the repro.core.scenarios "
                    "registry and write results/BENCH_scenarios.json")
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--scenario", nargs="+", metavar="NAME",
                   help="scenario or sweep names to run (see --list)")
    g.add_argument("--all", action="store_true",
                   help="run every registered scenario (sweeps only by name)")
    g.add_argument("--list", action="store_true",
                   help="list registered scenarios + sweeps and exit")
    ap.add_argument("--jobs", type=int, default=None,
                    help="override every scenario's job count")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default results/BENCH_scenarios.json)")
    ap.add_argument("--obs", default=None, metavar="MODE",
                    help="telemetry mode override for every scenario "
                         "(off|report|series|trace; see docs/OBSERVABILITY.md)")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="write per-run telemetry JSON (and, with "
                         "--obs trace, Perfetto trace + JSONL event log) "
                         "into DIR")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.list:
        for name, spec in sorted(SCENARIOS.items()):
            fan = "x".join(str(f) for f in spec.tier_fanouts)
            print(f"{name:>16}  [{fan} sites={spec.n_sites} "
                  f"arrival={spec.arrival} strategy={spec.strategy} "
                  f"broker={spec.broker}]  {spec.description}")
        for name, sw in sorted(SWEEPS.items()):
            print(f"{name:>16}  [sweep {sw.base} x {sw.axis}="
                  f"{list(sw.values)}]  {sw.description}")
        return
    names = sorted(SCENARIOS) if args.all else args.scenario
    for name in names:
        if name not in SWEEPS:
            get_scenario(name)  # fail fast on typos before running anything
    run_scenarios(names, n_jobs=args.jobs, out_path=args.out,
                  obs=args.obs, obs_dir=args.obs_dir)


if __name__ == "__main__":
    main()
