"""The numbers that decide ``correct``: one simulated run against another.

Each number is worst over the runs compared, and each has its own limit
(``limits/<workload>.json``). A run is held to the reference job by job:

* ``jobs_lost``         jobs the reference finished and the run did not;
* ``site_moved_share``  share of jobs placed on another site (broker);
* ``finish_gap_p50``    median over jobs of the gap between the two finish
                        instants, over the reference's job time (network
                        engine and event loop);
* ``job_time_gap``      gap of the mean job time, relative;
* ``inter_comms_gap``   gap of the inter-region transfer count, relative
                        (replication strategy);
* ``makespan_gap``      gap of the makespan, relative.
"""

from __future__ import annotations

import statistics

NUMBERS = ("jobs_lost", "site_moved_share", "finish_gap_p50",
           "job_time_gap", "inter_comms_gap", "makespan_gap")


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else (0.0 if a == b else float("inf"))


def readings(got: dict, want: dict) -> dict[str, float]:
    """Numbers for one run. Both sides are ``{"jobs": {job_id: (site,
    submit, finish)}, "makespan": s, "inter_comms": n}``."""
    gj, wj = got["jobs"], want["jobs"]
    both = [j for j in wj if j in gj]
    n = max(1, len(wj))
    moved = sum(gj[j][0] != wj[j][0] for j in both)
    gaps = [abs(gj[j][2] - wj[j][2]) / (wj[j][2] - wj[j][1]) for j in both]
    mean = lambda jobs: (sum(f - s for _, s, f in jobs.values())
                         / max(1, len(jobs)))
    return {
        "jobs_lost": float(len(wj) - len(both)),
        "site_moved_share": moved / n,
        "finish_gap_p50": statistics.median(gaps) if gaps else float("inf"),
        "job_time_gap": _rel(mean(gj), mean(wj)) if gj else float("inf"),
        "inter_comms_gap": _rel(got["inter_comms"], want["inter_comms"]),
        "makespan_gap": _rel(got["makespan"], want["makespan"]),
    }


def worst(per_run: list[dict[str, float]]) -> dict[str, float]:
    return {k: max(r[k] for r in per_run) for k in NUMBERS}

