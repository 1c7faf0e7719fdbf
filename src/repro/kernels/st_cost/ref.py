"""Vectorized float64 oracle for the blocked shortest-transfer cost pass.

Costs every (job, site) pair of a dispatch batch under the
``shortesttransfer`` policy (Chang et al. [6]; see
:class:`repro.core.scheduler.ShortestTransferScheduler`):

1. ``best[f, s]`` — the best point bandwidth at which site ``s`` could
   fetch file ``f``: max over fetchable holders ``h`` of ``bw[h, s]``.
   Unlike ``value_score`` self-supply is *not* excluded — a held file is
   never missing at its holder, so the diagonal never reaches a cost.
2. ``t[j, s]`` — estimated staging time: the sum over the job's required
   files missing at ``s`` of ``size / best`` (``inf`` when a missing file
   has no usable bandwidth — the sequential policy's zero-bw guard).
3. ``cost[j, s] = max(t, relative_load[s])``, ``inf`` at offline sites.

Memory is the whole point: the pre-blocked formulation materialized a
``(sites, files, sites)`` broadcast (~200 MB at the 500-site scale
point); both passes here are blocked — the max accumulates one holder
group at a time into a ``(files, sites)`` buffer and the job sum
accumulates one file at a time into a ``(jobs, sites)`` buffer — so peak
memory is O(sites x files + jobs x sites).

Bit-identity contract (pinned by ``tests/test_kernels.py``):

* the max-reduction is order-independent and divide/max are exact IEEE
  ops, so this oracle equals the dense formulation
  (:func:`st_cost_dense_ref`, kept for exactly that test) bit for bit;
* the file sum is sequential over ascending file index — numpy reduces
  the *major* axis of a 2-D array sequentially, and skipping exact-zero
  terms leaves a nonnegative running sum unchanged (``x + 0.0 == x``),
  so the per-job gathered sum below, the dense ``sum(axis=1)`` and the
  Pallas kernel's fori-loop accumulation all agree bit for bit;
* the kernel under x64 interpret mode therefore reproduces this oracle
  exactly — the same contract ``value_score`` / ``strategy_plan`` pin.
"""

from __future__ import annotations

import numpy as np


def st_cost_ref(bw: np.ndarray, fetch_mask: np.ndarray,
                presence: np.ndarray, sizes: np.ndarray,
                required: np.ndarray, rel: np.ndarray,
                online: np.ndarray) -> np.ndarray:
    """Cost every (job, site) pair of one dispatch batch.

    Args:
      bw: ``(sites, sites)`` point-bandwidth matrix, ``bw[h, s]`` = bytes/s
        from holder ``h`` to site ``s``
        (:meth:`repro.core.network.NetworkEngine.point_bandwidth_matrix`).
      fetch_mask: ``(sites, files)`` bool — fetchable holders (online, or
        the durable master copy).
      presence: ``(sites, files)`` bool — all holders (a file present at
        ``s`` costs nothing there, fetchable or not).
      sizes: ``(files,)`` file sizes in bytes.
      required: ``(jobs, files)`` bool requirement masks (R_j rows).
      rel: ``(sites,)`` relative load (queued work / capacity).
      online: ``(sites,)`` bool.

    Returns ``(jobs, sites)`` float64 costs, ``inf`` at offline sites.
    """
    bw = np.asarray(bw, np.float64)
    fetch_mask = np.asarray(fetch_mask, bool)
    presence = np.asarray(presence, bool)
    sizes = np.asarray(sizes, np.float64)
    required = np.asarray(required, bool)
    rel = np.asarray(rel, np.float64)
    n_sites, n_files = presence.shape
    n_jobs = required.shape[0]
    # pass 1 — best fetchable bandwidth per (file, dst). Iterated per file
    # over its holder rows: strictly less work than the kernel's
    # fori-over-holders sweep (O(nnz x sites) vs O(sites^2 x files)) and
    # bit-identical to it, the max being order-independent.
    best = np.zeros((n_files, n_sites))
    for f in range(n_files):
        holders = np.flatnonzero(fetch_mask[:, f])
        if holders.size:
            best[f] = bw[holders].max(axis=0)
    # masked entries never read the quotient (same guard value_score uses)
    t_fs = np.where(best > 0.0, sizes[:, None] / np.where(best > 0.0, best,
                                                          1.0), np.inf)
    # pass 2 — per-job sum over its missing files, ascending file index.
    # Gathering only R_j's rows skips exact-zero terms of the full-axis
    # sequential sum, which is bit-exact (see module docstring).
    t = np.zeros((n_jobs, n_sites))
    presence_t = presence.T                       # (files, sites) view
    for j in range(n_jobs):
        idx = np.flatnonzero(required[j])
        if idx.size:
            t[j] = np.where(presence_t[idx], 0.0, t_fs[idx]).sum(axis=0)
    cost = np.maximum(t, rel[None, :])
    return np.where(np.asarray(online, bool)[None, :], cost, np.inf)


def st_cost_dense_ref(bw: np.ndarray, fetch_mask: np.ndarray,
                      presence: np.ndarray, sizes: np.ndarray,
                      required: np.ndarray, rel: np.ndarray,
                      online: np.ndarray) -> np.ndarray:
    """The pre-blocked dense formulation (materializes ``(sites, files,
    sites)`` / ``(jobs, files, sites)`` broadcasts). Exists only so the
    tests can pin the blocked pass bit-identical to what the engine used
    to compute — never call it at scale."""
    bw = np.asarray(bw, np.float64)
    fetch_mask = np.asarray(fetch_mask, bool)
    presence = np.asarray(presence, bool)
    sizes = np.asarray(sizes, np.float64)
    required = np.asarray(required, bool)
    rel = np.asarray(rel, np.float64)
    best = np.where(fetch_mask[:, :, None], bw[:, None, :], 0.0).max(axis=0)
    t_fs = np.where(best > 0.0, sizes[:, None] / np.where(best > 0.0, best,
                                                          1.0), np.inf)
    miss = required[:, :, None] & ~presence.T[None, :, :]
    t = np.where(miss, t_fs[None], 0.0).sum(axis=1)
    cost = np.maximum(t, rel[None, :])
    return np.where(np.asarray(online, bool)[None, :], cost, np.inf)
