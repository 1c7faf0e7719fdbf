"""Aggregate metrics + the paper's experiment driver."""

from __future__ import annotations

import dataclasses
from typing import Sequence

from .quantities import GB
from .simulator import GridSimulator, SimResult
from .workload import GridConfig, build_catalog, build_topology, generate_jobs


@dataclasses.dataclass
class ExperimentResult:
    scheduler: str
    strategy: str
    n_jobs: int                  # submitted count (resubmissions not included)
    avg_job_time: float
    avg_inter_comms: float
    total_wan_gb: float
    total_lan_gb: float
    makespan: float
    completed_jobs: int = 0      # jobs that actually produced a record
    # engine-internal counters surfaced per run (PR 9): NetworkEngine
    # kernel stats and the speculative-prefetch ledger
    net_stats: dict = dataclasses.field(default_factory=dict)
    prefetches: int = 0
    prefetch_gb: float = 0.0
    #: :class:`repro.obs.TelemetryReport` when an ``obs=`` mode is on
    telemetry: object | None = None


def run_experiment(
    cfg: GridConfig,
    *,
    scheduler: str = "dataaware",
    strategy: str = "hrs",
    strategy_mode: str = "sequential",
    n_jobs: int | None = None,
    failures: list[tuple[int, float, float]] | None = None,
    slowdowns: list[tuple[int, float, float, float]] | None = None,
    speculative_backups: bool = False,
    broker: str = "event",
    batch_window: float = 0.0,
    arrival_burst: int = 1,
    arrival_times: Sequence[float] | None = None,
    net: str = "numpy",
    econ: str = "numpy",
    econ_interval: float | None = None,
    obs: str | None = None,
    obs_interval: float | None = None,
) -> ExperimentResult:
    """One full simulation run (the unit behind every paper figure).

    Builds the grid described by ``cfg``, bootstraps master replicas,
    submits the generated workload, and runs the discrete-event engine to
    completion. ``scheduler``/``strategy`` name entries in the
    :data:`repro.core.SCHEDULERS` / :data:`repro.core.STRATEGIES`
    registries.

    Arrivals: by default job ``j`` is submitted at ``j * cfg.interarrival``.
    ``arrival_burst`` > 1 submits jobs in bursts of that size (same mean
    arrival rate); combined with ``broker="jax"`` each burst is dispatched
    as one jitted batch decision. ``arrival_times`` (seconds, one per job)
    overrides both — this is how the scenario engine injects Poisson /
    flash-crowd / diurnal arrival processes.

    ``failures`` is a list of ``(site, at, duration)`` outages and
    ``slowdowns`` a list of ``(site, at, duration, factor)`` stragglers;
    see :mod:`repro.fault.failures` for spec-driven generation.

    ``net`` picks the network-engine backend (see
    :data:`repro.core.simulator.NETS`): ``"numpy"`` incremental re-rating,
    ``"device"`` / ``"device-interpret"`` the batched event-instant flush
    (:class:`repro.core.network.NetworkEngine`), ``"topmost"`` the legacy
    single-uplink accounting (fidelity baseline). ``"numpy"`` and
    ``"topmost"`` give identical results on two-level grids; the batched
    routes stay within the tolerance goldens of ``"numpy"``.

    ``strategy_mode`` picks the planning engine of the replication
    strategy: ``"sequential"`` (one ``plan_fetch`` per missing file — the
    default, the golden-pinned path) or ``"batch"`` (whole arrival bursts
    planned in one :mod:`repro.kernels.strategy_plan` pass; singleton
    plans are bit-identical to the sequential twin, multi-job bursts
    share one state snapshot — the jax-broker convention).

    ``econ`` picks the value-scoring backend of the replication economy
    (:data:`repro.core.economy.ECON_BACKENDS`, mirroring ``net``) and
    ``econ_interval`` its period in sim seconds — ``None`` arms the
    periodic optimizer only for the access-aware strategies
    (``economic`` / ``predictive``), an explicit value > 0 forces it on
    for any strategy, 0 disables it outright.

    ``obs`` picks the telemetry mode (:data:`repro.obs.OBS_MODES`:
    ``"off"``/``"report"``/``"series"``/``"trace"``; ``None`` defers to
    the ``REPRO_OBS`` env override, default off) and ``obs_interval``
    the sim-seconds between ring-buffer samples. Observation-only: every
    metric above is bit-identical under any mode; the report lands on
    ``ExperimentResult.telemetry``.
    """
    topology = build_topology(
        cfg, path_model="topmost" if net == "topmost" else "full")
    catalog = build_catalog(cfg, topology)
    sim = GridSimulator(topology, catalog, scheduler=scheduler, strategy=strategy,
                        strategy_mode=strategy_mode,
                        seed=cfg.seed, speculative_backups=speculative_backups,
                        broker=broker, batch_window=batch_window, net=net,
                        econ=econ, econ_interval=econ_interval,
                        obs=obs, obs_interval=obs_interval)
    for info in catalog.files.values():
        sim.storage.bootstrap(info.master_site, info.lfn)
    jobs = generate_jobs(cfg, n_jobs)
    if arrival_times is not None and len(arrival_times) < len(jobs):
        raise ValueError(f"arrival_times has {len(arrival_times)} entries "
                         f"for {len(jobs)} jobs")
    for j, job in enumerate(jobs):
        if arrival_times is not None:
            at = float(arrival_times[j])
        else:
            at = (j // arrival_burst) * cfg.interarrival * arrival_burst
        sim.submit_job(job, at=at)
    for site, at, dur in failures or []:
        sim.inject_failure(site, at, dur)
    for site, at, dur, factor in slowdowns or []:
        sim.inject_slowdown(site, at, dur, factor)
    res = sim.run()
    return ExperimentResult(
        scheduler=scheduler, strategy=strategy, n_jobs=len(jobs),
        avg_job_time=res.avg_job_time, avg_inter_comms=res.avg_inter_comms,
        total_wan_gb=res.total_wan_bytes / GB, total_lan_gb=res.total_lan_bytes / GB,
        makespan=res.makespan,
        completed_jobs=len(res.records),
        net_stats=res.net_stats,
        prefetches=res.prefetches,
        prefetch_gb=res.prefetch_bytes / GB,
        telemetry=res.telemetry,
    )
