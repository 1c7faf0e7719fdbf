"""Vectorized float64 oracle for the replica value-scoring pass.

Scores the full ``(sites, files)`` value matrix of the replication economy
(:mod:`repro.core.economy`) in one pass:

1. ``bestbw[s, f]`` — the best point bandwidth at which site ``s`` could
   fetch file ``f`` right now: max over holders ``h`` of ``bw[h, s]``,
   with **self-supply excluded** (``h == s`` never counts, so a file the
   site already holds scores its re-fetch-if-dropped cost — that is what
   makes the same matrix usable for both acquisition and retention value).
2. ``value[s, f]`` — under ``mode="cost"`` (the OptorSim-style economic
   valuation) ``demand * size / bestbw``: predicted future accesses times
   the transfer seconds each would cost without a local replica. Under
   ``mode="plain"`` (pure popularity prediction) just ``demand`` masked to
   pairs with a live source.

Pairs with no external holder score 0 in both modes (nothing to buy).

Max/divide are exact IEEE ops and the max-reduction is order-independent,
so the Pallas kernel (``kernel.py``) run under x64 interpret mode is
bit-identical to this oracle — the same contract ``st_cost`` and
``strategy_plan`` pin, here checked by ``tests/test_kernels.py`` and
reachable end-to-end via the ``econ="pallas-interpret"`` engine flag.
"""

from __future__ import annotations

import numpy as np

MODES = ("cost", "plain")


def value_score_ref(demand: np.ndarray, sizes: np.ndarray,
                    presence: np.ndarray, bw: np.ndarray, *,
                    mode: str = "cost") -> np.ndarray:
    """Score every (site, file) pair.

    Args:
      demand: ``(sites, files)`` predicted future accesses (decayed counts,
        already region-pooled by the caller).
      sizes: ``(files,)`` file sizes in bytes.
      presence: ``(sites, files)`` bool — which sites are fetchable holders.
      bw: ``(sites, sites)`` point-bandwidth matrix, ``bw[h, s]`` = bytes/s
        from holder ``h`` to site ``s``
        (:meth:`repro.core.network.NetworkEngine.point_bandwidth_matrix`).
      mode: ``"cost"`` (economic: demand x transfer cost, in predicted
        seconds saved) or ``"plain"`` (popularity: demand masked to pairs
        with a live source).

    Returns ``(sites, files)`` float64 values.
    """
    if mode not in MODES:
        raise ValueError(f"unknown value_score mode {mode!r} "
                         f"(want one of {MODES})")
    demand = np.asarray(demand, np.float64)
    sizes = np.asarray(sizes, np.float64)
    presence = np.asarray(presence, bool)
    bw = np.asarray(bw, np.float64)
    n_sites, n_files = demand.shape
    # best external source per (s, f): max over holders h != s of bw[h, s].
    # Accumulated one holder row at a time — O(sites) passes over an
    # (sites, files) buffer instead of materializing (sites, files, sites).
    best = np.zeros((n_sites, n_files))
    for h in range(n_sites):
        if not presence[h].any():
            continue
        contrib = np.where(presence[h][None, :], bw[h][:, None], 0.0)
        contrib[h, :] = 0.0                      # self-supply excluded
        np.maximum(best, contrib, out=best)
    if mode == "plain":
        return np.where(best > 0.0, demand, 0.0)
    # masked entries never read the quotient, so a safe denominator keeps
    # the kept entries bit-identical while avoiding 0 * inf warnings
    cost = sizes[None, :] / np.where(best > 0.0, best, 1.0)
    return np.where(best > 0.0, demand * cost, 0.0)
