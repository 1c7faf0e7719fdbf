"""Simulation CLI: run a registered scenario, or an ad-hoc grid, across
replication strategies.

  PYTHONPATH=src python -m repro.launch.simulate --strategy hrs bhr lru \
      --jobs 500 --wan-mbps 10
  PYTHONPATH=src python -m repro.launch.simulate --scenario cache_starved

Both forms build a ``ScenarioSpec`` and run it through
``repro.launch.experiments.run_spec`` — the same config-driven path the
benchmarks and the scenario runner use. For machine-readable multi-scenario
output use ``python -m repro.launch.experiments`` instead.
"""

from __future__ import annotations

import argparse
import dataclasses

from repro.compile_cache import enable_compile_cache
from repro.core import (ChurnSpec, ECON_BACKENDS, OBS_MODES, SCENARIOS,
                        STRATEGIES, STRATEGY_MODES, SCHEDULERS, ScenarioSpec,
                        get_scenario)
from repro.core.simulator import NETS
from repro.launch.experiments import run_spec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default=None, choices=sorted(SCENARIOS),
                    help="run a registered scenario instead of the ad-hoc "
                         "grid flags below")
    ap.add_argument("--strategy", nargs="+", default=["hrs", "bhr", "lru"],
                    choices=list(STRATEGIES))
    ap.add_argument("--scheduler", default="dataaware",
                    choices=list(SCHEDULERS))
    ap.add_argument("--jobs", type=int, default=None,
                    help="job count (default: 500, or the scenario's)")
    ap.add_argument("--wan-mbps", type=float, default=10.0)
    ap.add_argument("--lan-mbps", type=float, default=1000.0)
    ap.add_argument("--regions", type=int, default=4)
    ap.add_argument("--sites", type=int, default=13)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--net", default=None, choices=list(NETS),
                    help="network-engine backend (default: the scenario's, "
                         "or 'numpy'; 'topmost' = legacy single-uplink model)")
    ap.add_argument("--econ", default=None, choices=list(ECON_BACKENDS),
                    help="replication-economy value-scoring backend "
                         "(default: the scenario's, or 'numpy')")
    ap.add_argument("--strategy-mode", default=None, choices=list(STRATEGY_MODES),
                    help="strategy planning engine (default: the scenario's, "
                         "or 'sequential'; 'batch' plans each arrival burst "
                         "in one strategy_plan kernel pass)")
    ap.add_argument("--econ-interval", type=float, default=None,
                    help="seconds between proactive-replication rounds "
                         "(default: auto — armed only for the economic/"
                         "predictive strategies; 0 disables)")
    ap.add_argument("--obs", default=None, choices=list(OBS_MODES),
                    help="telemetry mode (default: the scenario's, or off; "
                         "report/series/trace print the measured phase "
                         "breakdown per run — see docs/OBSERVABILITY.md)")
    ap.add_argument("--obs-interval", type=float, default=None,
                    help="sim-seconds between telemetry ring-buffer samples "
                         "(series/trace modes; default 300)")
    ap.add_argument("--failures", type=int, default=0,
                    help="number of random site failures to inject")
    args = ap.parse_args()
    enable_compile_cache()

    if args.scenario is not None:
        spec = get_scenario(args.scenario)
        if args.failures:
            spec = dataclasses.replace(spec, churn=ChurnSpec(
                n_failures=args.failures,
                window=(2000.0, 2000.0 * (args.failures + 1)),
                mean_downtime_s=4000.0))
    else:
        churn = ChurnSpec(n_failures=args.failures,
                          window=(2000.0, 2000.0 * (args.failures + 1)),
                          mean_downtime_s=4000.0) if args.failures else ChurnSpec()
        spec = ScenarioSpec(
            name="cli", description="ad-hoc CLI grid",
            tier_fanouts=(args.regions, args.sites),
            lan_mbps=args.lan_mbps, uplink_mbps=(args.wan_mbps,),
            scheduler=args.scheduler, churn=churn, seeds=(args.seed,))
    if args.net is not None:
        spec = dataclasses.replace(spec, net=args.net)
    if args.econ is not None:
        spec = dataclasses.replace(spec, econ=args.econ)
    if args.strategy_mode is not None:
        spec = dataclasses.replace(spec, strategy_mode=args.strategy_mode)
    if args.econ_interval is not None:
        spec = dataclasses.replace(spec, econ_interval_s=args.econ_interval)
    if args.obs is not None:
        spec = dataclasses.replace(spec, obs=args.obs)
    if args.obs_interval is not None:
        spec = dataclasses.replace(spec, obs_interval_s=args.obs_interval)
    print(f"{'strategy':>14} {'avg_job_time':>13} {'inter/job':>10} "
          f"{'WAN GB':>8} {'makespan':>10}")
    for strat in args.strategy:
        r = run_spec(dataclasses.replace(spec, strategy=strat),
                     seed=args.seed, n_jobs=args.jobs)
        print(f"{strat:>14} {r.avg_job_time:>12.0f}s {r.avg_inter_comms:>10.2f} "
              f"{r.total_wan_gb:>8.1f} {r.makespan:>9.0f}s")
        if r.telemetry is not None:
            ph = r.telemetry.phase_breakdown()
            print(f"{'':>14} phases[s]: "
                  f"dispatch={ph['dispatch_s']:.3f} "
                  f"strategy_plan={ph['strategy_plan_s']:.3f} "
                  f"flush={ph['flush_s']:.3f} other={ph['other_s']:.3f} "
                  f"(wall={r.telemetry.wall_s:.3f}, "
                  f"samples={r.telemetry.n_samples})")


if __name__ == "__main__":
    main()
