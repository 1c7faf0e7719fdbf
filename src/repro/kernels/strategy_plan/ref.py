"""Vectorized float64 oracle for the batched replica-strategy plan pass.

Scores every (job, missing-file) pair of one arrival burst at once and
returns the per-pair decisions every replication strategy starts from
(see :mod:`repro.core.replica` for the sequential policies this
vectorizes):

1. ``src_global[p]`` — the best source over *all* fetchable holders of
   pair ``p``'s file: argmax over sites of the effective bandwidth
   ``bw[s, p] / (1.0 + serve[s])``. The history-blind strategies pass
   ``serve = 0`` and the division by exactly ``1.0`` is an IEEE no-op,
   so one formula serves both the raw-bandwidth key
   (:func:`repro.core.replica._best_bandwidth_source`) and the
   serve-load-discounted key (``_AccessAwareStrategy._select_source``).
   Ties break toward the lowest site id — ``np.argmax`` returns the
   first maximum, reproducing the sequential ``max(..., key=(bw, -s))``.
2. ``src_local[p]`` / ``has_local[p]`` — the same argmax restricted to
   holders in the destination's region (HRS's region-priority rule),
   plus whether any exist.
3. ``inter_global[p]`` — whether the global pick crosses a region
   boundary (the paper's inter-communication classification), read off
   the ``local`` mask at the chosen row.
4. ``store_ok[p]`` — the no-eviction store verdict ``free >= size``,
   the comparison every sequential strategy makes before falling into
   its eviction scan.

Eviction *contents* (two-phase LRU order, retention-vs-refetch trades)
stay host-side masked reductions over the
:class:`repro.core.replica.StorageTensorView` tensors — they touch only
the few pairs whose ``store_ok`` is false.

Bit-identity contract (pinned by ``tests/test_kernels.py``): where /
divide / compare are exact IEEE ops and the argmax is a first-occurrence
running maximum, so the Pallas kernel under x64 interpret mode
reproduces this oracle bit for bit — the same contract ``value_score`` /
``event_engine`` / ``st_cost`` pin.
"""

from __future__ import annotations

import numpy as np


def strategy_plan_ref(bw: np.ndarray, fetch: np.ndarray, local: np.ndarray,
                      serve: np.ndarray, free: np.ndarray,
                      size: np.ndarray) -> tuple[np.ndarray, ...]:
    """Plan one burst of (job, missing-file) pairs.

    Args:
      bw: ``(sites, pairs)`` point bandwidth from each site to pair
        ``p``'s destination (columns of
        :meth:`repro.core.network.NetworkEngine.point_bandwidth_matrix`).
      fetch: ``(sites, pairs)`` 0/1 — fetchable holders of pair ``p``'s
        file (online, or the durable master copy).
      local: ``(sites, pairs)`` 0/1 — site in the destination's region.
      serve: ``(sites,)`` decayed serving load per site (all zeros for
        the history-blind strategies).
      free: ``(pairs,)`` free SE bytes at each destination.
      size: ``(pairs,)`` file size of each pair.

    Returns ``(src_global, src_local, has_local, inter_global,
    store_ok)``, each ``(pairs,)`` float64 (site ids are exact small
    integers; the flags are 0.0/1.0).
    """
    bw = np.asarray(bw, np.float64)
    fetch = np.asarray(fetch, np.float64) > 0.0
    local = np.asarray(local, np.float64) > 0.0
    serve = np.asarray(serve, np.float64)
    free = np.asarray(free, np.float64)
    size = np.asarray(size, np.float64)
    n_pairs = bw.shape[1]
    eff = bw / (1.0 + serve)[:, None]
    key_g = np.where(fetch, eff, -1.0)
    key_l = np.where(fetch & local, eff, -1.0)
    src_g = np.argmax(key_g, axis=0)                 # first max = lowest id
    src_l = np.argmax(key_l, axis=0)
    has_l = (fetch & local).any(axis=0)
    inter_g = ~local[src_g, np.arange(n_pairs)]
    store_ok = free >= size
    return (src_g.astype(np.float64), src_l.astype(np.float64),
            has_l.astype(np.float64), inter_g.astype(np.float64),
            store_ok.astype(np.float64))
