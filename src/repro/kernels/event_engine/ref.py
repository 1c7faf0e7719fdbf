"""Vectorized float64 oracle for the batched event-engine flush.

One fused pass over *all* transfer slots, run once per drained event
instant by the ``net="device"`` engine backend instead of once per event:

1. reconstruct each live slot's remaining bytes from its cached
   ``(rate, eta)`` pair — ``rem = rate * (eta - now)`` — so the engine
   never integrates ``rem`` on the host between flushes;
2. re-rate every slot: min over its link path of
   ``bandwidth / max(1, active)`` (identical to the incremental numpy
   route);
3. recompute every slot's completion eta and reduce to the earliest one,
   which becomes the next NET wake-up.

Step 1 is the deliberate fidelity break: the numpy oracle engine advances
``rem -= rate * dt`` stepwise, while this pass reconstructs it as
``rate * (eta - now)``. Both describe the same fluid trajectory but round
differently, so the device engine is *not* bit-identical to the numpy
engine — it is pinned by the tolerance-golden contract
(``tests/golden_tolerance.json``) instead. Within the device route itself
the chip only picks each slot's least share by rank (``kernel.py``) and
:func:`settle` does the arithmetic on the host, so the route is
bit-identical to this oracle on any chip (``tests/test_kernels.py``
holds it there, on the interpret route).
"""

from __future__ import annotations

import numpy as np


def event_engine_ref(path: np.ndarray, rem: np.ndarray, rate: np.ndarray,
                     eta: np.ndarray, link_bw: np.ndarray,
                     link_act: np.ndarray, now: float
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray, float]:
    """Fused reconstruct + re-rate + next-completion pass.

    Args:
      path: ``(slots, max_links)`` int link-index matrix, ``-1``-padded;
        all ``-1`` rows are released/unused slots.
      rem: ``(slots,)`` remaining bytes *as of the previous flush* (used
        verbatim for slots whose cached rate is 0, i.e. freshly allocated
        or released slots).
      rate: ``(slots,)`` rates set by the previous flush.
      eta: ``(slots,)`` completion times set by the previous flush
        (``inf`` where rate is 0).
      link_bw: ``(links,)`` aggregate bandwidth per link.
      link_act: ``(links,)`` concurrent-transfer count per link (float).
      now: current simulation time (the flush instant).

    Returns ``(rem_now, rate_new, eta_new, eta_min)``: reconstructed
    remaining bytes, new per-slot rates (0.0 for all-padding rows), new
    per-slot completion times (``inf`` for dead slots) and their min
    (``inf`` when no slot is live).
    """
    path = np.asarray(path)
    rem = np.asarray(rem, dtype=np.float64)
    rate = np.asarray(rate, dtype=np.float64)
    eta = np.asarray(eta, dtype=np.float64)
    return event_engine_core(path, rem, rate, eta, link_bw, link_act, now)


def event_engine_core(path: np.ndarray, rem: np.ndarray, rate: np.ndarray,
                      eta: np.ndarray, link_bw: np.ndarray,
                      link_act: np.ndarray, now: float
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray, float]:
    """:func:`event_engine_ref` minus the input coercion — for callers
    that already hold float64 ndarrays (the engine's flush loop calls
    this hundreds of thousands of times per run).

    The share gather uses an ``inf`` sentinel appended to the share
    vector: the path matrix's ``-1`` padding legally indexes the last
    element, so no validity mask or ``(slots, links)`` where-temporary
    is ever built, and the per-slot min runs as one ``np.minimum`` pass
    per link column instead of a slow small-axis reduction. Same IEEE
    ops on the same values as the masked formulation — bit-identical
    outputs (the Pallas kernel equivalence test pins this)."""
    if path.shape[0] == 0:
        return np.zeros(0), np.zeros(0), np.zeros(0), float("inf")
    shares = np.empty(link_bw.shape[0] + 1)
    np.divide(link_bw, np.maximum(1.0, link_act), out=shares[:-1])
    shares[-1] = np.inf          # the -1 padding's landing cell
    rate_new = shares[path[:, 0]]
    for d in range(1, path.shape[1]):
        np.minimum(rate_new, shares[path[:, d]], out=rate_new)
    return settle(rem, rate, eta, rate_new, now)


def settle(rem: np.ndarray, rate: np.ndarray, eta: np.ndarray,
           rate_new: np.ndarray, now: float
           ) -> tuple[np.ndarray, np.ndarray, np.ndarray, float]:
    """The flush's float64 arithmetic once each slot's new rate is known
    (``inf`` for all-padding rows): reconstruct ``rem``, recompute every
    eta and reduce to the earliest. The device route calls it on the
    rates its least-rank program picked (:mod:`.kernel`). Takes
    ``rate_new`` over."""
    # all-padding rows reduced to the bare sentinel: dead, rate 0
    np.copyto(rate_new, 0.0, where=~np.isfinite(rate_new))
    # reconstruct remaining bytes from the cached (rate, eta) pair; slots
    # without a cached rate (fresh allocs, released rows) keep stored rem.
    # eta is masked before the multiply so inf etas on dead slots never
    # produce 0*inf NaNs in the untaken branch.
    carried = rate > 0.0
    eta_c = np.where(carried, eta, 0.0)
    rem_now = np.maximum(np.where(carried, rate * (eta_c - now), rem), 0.0)
    live = rate_new > 0.0
    eta_new = np.where(live, now + rem_now / np.where(live, rate_new, 1.0),
                       np.inf)
    return rem_now, rate_new, eta_new, float(eta_new.min(initial=np.inf))
