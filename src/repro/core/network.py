"""Path-contention fluid network engine for the grid DES.

Owns every piece of transfer-network state the simulator used to keep
inline: slot-indexed numpy arrays of remaining bytes and rates, plus a
padded ``(slots, max_links)`` link-path matrix over a **unified link
space** — NIC ``i`` is link ``i`` and ``topology.wan_links[j]`` is link
``n_sites + j`` (see ``GridTopology.link_ids_for``). A transfer's rate is
the min over *every* link in its row of ``bandwidth / max(1, active)``,
so mid-tier uplinks congest under through-traffic on deep trees; on
two-level grids the row is exactly the legacy {source NIC, region uplink}
pair and results are bit-identical to the pre-refactor engine.

Three routes (the ``net=`` engine flag):

``"numpy"`` (default)
    Incremental re-rating: only slots sharing a link whose membership
    changed are re-rated (rates are pure functions of link occupancy, so
    this equals a full recompute — bit-identically). Small groups take a
    scalar fast path; larger ones a vectorized gather-min. This is the
    host oracle the golden suite pins bit-exactly.

``"device"``
    The batched event engine (``repro.kernels.event_engine``): per-event
    ``rerate`` calls only mark the engine dirty, and the simulator runs
    one fused *flush* pass per drained event instant — remaining bytes
    are reconstructed on the fly from each slot's cached ``(rate, eta)``
    pair, every slot is re-rated, and a running-min over the new etas
    yields the next NET wake-up. Per-event work is O(1) regardless of
    how many transfers are in flight, at the price of ulp-level drift:
    the reconstruction ``rate * (eta - now)`` rounds differently from
    the stepwise ``rem -= rate * dt`` integration, so the device engine
    is pinned to the numpy oracle by *tolerance* goldens
    (``tests/golden_tolerance.json``), not the bit-exact suite. Rates
    themselves are the same pure function of link occupancy on every
    route. A flush whose dirty neighborhood holds no live slot re-rates
    nothing and returns the cached earliest eta, on every route. Off the
    TPU the flush otherwise runs the float64 oracle over the dirty
    neighborhood; on the TPU, when the neighborhood holds a live slot,
    the compiled kernel flushes the whole slot array: the chip picks each
    slot's least fair share by its float64 rank and the host does the
    arithmetic in float64, so each flush is bit-identical to the oracle
    over the same slots.

``"device-interpret"``
    The TPU's whole-array flush under the Pallas interpreter (slow): the
    chip's route, rehearsed on the host.
"""

from __future__ import annotations

import math
from typing import Iterable, Optional

import numpy as np

from .topology import GridTopology

# A transfer is complete when less than one byte remains. Sub-byte residue
# left by float rounding must count as done, otherwise the event loop can
# starve: eta increments below the clock's ulp make dt == 0 forever.
_DONE_EPS = 1.0

BACKENDS = ("numpy", "device", "device-interpret")


class NetworkEngine:
    """Slot-indexed fluid-model transfer network (see module docstring)."""

    def __init__(self, topology: GridTopology, backend: str = "numpy") -> None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown network backend {backend!r} "
                             f"(want one of {BACKENDS})")
        self.topology = topology
        self.backend = backend
        self._use_kernel = False
        self.batched = backend != "numpy"
        if self.batched:
            # the flush route, resolved once per engine: the compiled
            # event_engine kernel on TPU, its float64 numpy oracle inline
            # on CPU (no per-flush jax dispatch)
            from repro.kernels.event_engine import (event_engine,
                                                    event_engine_core)
            self._flush_op = event_engine
            self._flush_ref = event_engine_core
            if backend == "device":
                import jax
                self._use_kernel = jax.default_backend() == "tpu"
        n_sites = topology.n_sites
        self.n_links = n_sites + len(topology.wan_links)
        # the engine is the sole bookkeeper of link occupancy: alloc and
        # release update both the topology Link objects (read by
        # point_bandwidth during replica selection) and the float mirror
        # link_act (exact — the counts are small integers)
        self._link_objs = list(topology.nic_links) + list(topology.wan_links)
        self.link_bw = np.array([l.bandwidth for l in self._link_objs])
        self.link_act = np.array([float(l.active) for l in self._link_objs])
        # per-link member slots as insertion-ordered dicts (value unused):
        # O(1) add/remove like a set, but iteration order is allocation
        # order, not hash order — simlint SL001 bans iterating raw sets in
        # engine paths (rates are order-independent anyway; this keeps the
        # re-rate batch order reproducible by construction)
        self.members: list[dict[int, None]] = [
            {} for _ in range(self.n_links)]
        self.max_links = topology.depth        # NIC + up to depth-1 uplinks
        self.cap = 64
        self.rem = np.zeros(self.cap)
        self.rate = np.zeros(self.cap)
        # per-slot completion time cached by the last flush (inf where the
        # slot has no rate); the batched backend's only integration state —
        # rem is reconstructed from (rate, eta) instead of being advanced.
        # `due` is the precomputed completion deadline eta - eps/rate:
        # completions() is then a single compare against the clock instead
        # of an O(capacity) rem reconstruction per NET event
        self.eta = np.full(self.cap, np.inf)
        self.due = np.full(self.cap, np.inf)
        self.active = np.zeros(self.cap, bool)
        self.path = np.full((self.cap, self.max_links), -1, np.intp)
        self.obj: list[Optional[object]] = [None] * self.cap
        self._free = list(range(self.cap - 1, -1, -1))
        self.n_active = 0
        self.last = 0.0                        # last advance() timestamp
        self.dirty = False                     # batched: flush pending?
        # batched: links whose occupancy moved since the last flush
        # (insertion-ordered dict, same discipline as `members`)
        self._dirty_links: dict[int, None] = {}
        # per-event work counters (the saturated-backlog regression test
        # asserts on these, so they are part of the engine contract):
        # rerate_calls — rerate() invocations; rerate_slots — slots
        # re-rated *synchronously inside rerate()* (the incremental
        # route's per-event member-union + eta-scan work; identically 0
        # on the batched backend, whose rerate only marks dirty links);
        # flush_passes / flush_slots — fused passes and the slots they
        # re-rated (at most one pass per drained instant); flush_kernel /
        # flush_host — passes that re-rated slots on the compiled
        # event_engine kernel / on the host (float64 oracle or the Pallas
        # interpreter), so a run shows which route its flushes took;
        # flush_skipped — passes that re-rated nothing (no live slot on a
        # dirty link) and so made no crossing on any route.
        self.stats = {"rerate_calls": 0, "rerate_slots": 0,
                      "flush_passes": 0, "flush_slots": 0,
                      "flush_kernel": 0, "flush_host": 0,
                      "flush_skipped": 0}
        # the run's repro.obs probe (GridSimulator hands it over; None
        # when obs is off, and deep copies drop it): times the kernel
        # route's flush in parts
        self.probe = None
        self._pair_paths: Optional[np.ndarray] = None   # lazy (S, S, depth)
        # per-destination (link idx, validity) slices of the path tensor,
        # cached on first use: topology is static, only link shares move
        self._col_paths: dict[int, tuple[np.ndarray, np.ndarray]] = {}

    # -- slot lifecycle ----------------------------------------------------
    def alloc(self, tr, size: float, links: tuple[int, ...]) -> int:
        """Claim a slot for ``tr`` (sets ``tr.slot``), register it on every
        link of ``links`` (unified ids, source NIC first)."""
        if not self._free:
            old = self.cap
            self.cap = old * 2
            self.rem = np.concatenate([self.rem, np.zeros(old)])
            self.rate = np.concatenate([self.rate, np.zeros(old)])
            self.eta = np.concatenate([self.eta, np.full(old, np.inf)])
            self.due = np.concatenate([self.due, np.full(old, np.inf)])
            self.active = np.concatenate([self.active, np.zeros(old, bool)])
            self.path = np.concatenate(
                [self.path, np.full((old, self.max_links), -1, np.intp)])
            self.obj.extend([None] * old)
            self._free.extend(range(self.cap - 1, old - 1, -1))
        slot = self._free.pop()
        tr.slot = slot
        self.rem[slot] = size
        self.rate[slot] = 0.0
        self.eta[slot] = np.inf   # unrated: flush reads rem verbatim
        self.due[slot] = np.inf
        row = self.path[slot]
        row[:] = -1
        row[: len(links)] = links
        self.active[slot] = True
        self.obj[slot] = tr
        self.n_active += 1
        for li in links:
            self.members[li][slot] = None
            self.link_act[li] += 1.0
            self._link_objs[li].active += 1
        return slot

    def release(self, tr) -> tuple[int, ...]:
        """Free ``tr``'s slot and de-register its links; returns the link
        ids whose occupancy changed (feed them back into ``rerate``)."""
        slot = tr.slot
        links = tuple(int(li) for li in self.path[slot] if li >= 0)
        self.active[slot] = False
        self.rate[slot] = 0.0
        self.rem[slot] = 0.0
        self.eta[slot] = np.inf
        self.due[slot] = np.inf
        self.path[slot, :] = -1
        self.obj[slot] = None
        self.n_active -= 1
        for li in links:
            self.members[li].pop(slot, None)
            self.link_act[li] -= 1.0
            self._link_objs[li].active -= 1
        self._free.append(slot)
        tr.slot = -1
        return links

    # -- bandwidth queries -------------------------------------------------
    def point_bandwidth(self, src: int, dst: int) -> float:
        """Available bandwidth if one more transfer joined ``src -> dst``,
        computed from the engine's own link arrays. The counts mirror the
        topology ``Link`` objects exactly (both are updated in
        ``alloc``/``release``), so this equals
        :meth:`GridTopology.point_bandwidth` bit-for-bit; it exists so the
        replication economy prices transfers against the same state the
        fluid model drains."""
        ids = self.topology.link_ids_for(src, dst)
        bw = np.inf
        for li in ids:
            share = self.link_bw[li] / (self.link_act[li] + 1.0)
            if share < bw:
                bw = share
        return float(bw)

    def point_bandwidth_matrix(self) -> np.ndarray:
        """``B[h, s]`` = :meth:`point_bandwidth` for every (source, dst)
        pair, as one vectorized gather-min over the cached static
        ``(sites, sites, depth)`` link-id tensor
        (:meth:`GridTopology.pair_link_matrix`). This is the one shared
        point-bandwidth snapshot: the replication economy prices
        transfers off it and the jitted shortest-transfer broker costs
        dispatch batches off it, so neither builds a private path tensor.
        The diagonal is the source NIC share (no uplinks crossed);
        consumers mask self-supply themselves."""
        if self._pair_paths is None:
            self._pair_paths = self.topology.pair_link_matrix()
        share = self.link_bw / (self.link_act + 1.0)
        p = self._pair_paths
        valid = p >= 0
        return np.where(valid, share[np.maximum(p, 0)], np.inf).min(axis=-1)

    def point_bandwidth_columns(self, dsts) -> np.ndarray:
        """Destination columns of :meth:`point_bandwidth_matrix`:
        ``B[h, p]`` = :meth:`point_bandwidth` ``(h, dsts[p])``, without
        materializing the full ``(sites, sites)`` matrix. The batched
        replica planners (``strategy_mode="batch"``) read one column per
        (job, missing-file) pair each arrival burst, so this is their
        per-burst cost: ``O(sites x pairs x depth)`` on the shared cached
        path tensor."""
        if self._pair_paths is None:
            self._pair_paths = self.topology.pair_link_matrix()
        share = self.link_bw / (self.link_act + 1.0)
        d = np.asarray(dsts, np.intp)
        # bursts repeat destinations (all of a job's files land on its
        # site): gather the path tensor once per unique column, then
        # replicate — pure indexing, bit-identical to the direct gather
        u, inv = np.unique(d, return_inverse=True)
        p = self._pair_paths[:, u, :]
        cols = np.where(p >= 0, share[np.maximum(p, 0)], np.inf).min(axis=-1)
        return cols[:, inv]

    def point_bandwidth_column(self, dst: int) -> np.ndarray:
        """One destination column, ``(sites,)`` — the singleton-replan
        route of the batched planners. Same expression as
        :meth:`point_bandwidth_columns` but sliced (no fancy-index copy
        of the path tensor), so the values are bit-identical to
        ``point_bandwidth_columns([dst])[:, 0]``."""
        cached = self._col_paths.get(dst)
        if cached is None:
            if self._pair_paths is None:
                self._pair_paths = self.topology.pair_link_matrix()
            p = self._pair_paths[:, dst, :]
            cached = (np.ascontiguousarray(np.maximum(p, 0)), p >= 0)
            self._col_paths[dst] = cached
        idx, valid = cached
        share = self.link_bw / (self.link_act + 1.0)
        return np.where(valid, share[idx], np.inf).min(axis=-1)

    # -- fluid model -------------------------------------------------------
    def advance(self, now: float) -> None:
        """Integrate all active transfers to ``now``.

        The batched backend never integrates on the host: ``rem`` is
        reconstructed from the cached ``(rate, eta)`` pair whenever it is
        read (:meth:`rem_now`), so advancing is just moving the clock."""
        if self.batched:
            self.last = now
            return
        dt = now - self.last
        if dt > 0:
            np.maximum(self.rem - self.rate * dt, 0.0, out=self.rem)
        self.last = now

    def rem_now(self, now: Optional[float] = None) -> np.ndarray:
        """Remaining bytes per slot at ``now`` (default: the clock set by
        the last :meth:`advance`/:meth:`flush`). On the batched backend
        this reconstructs ``rate * (eta - now)`` for slots the last flush
        rated — the exact formulation the flush pass itself uses — and
        reads the stored array for fresh/released slots; on the
        incremental route ``rem`` is already integrated and is
        returned as-is."""
        if not self.batched:
            return self.rem
        if now is None:
            now = self.last
        carried = self.rate > 0.0
        eta_c = np.where(carried, self.eta, 0.0)
        return np.maximum(
            np.where(carried, self.rate * (eta_c - now), self.rem), 0.0)

    def completions(self) -> np.ndarray:
        """Slot indices of active transfers with < 1 byte remaining.

        Batched backends compare the precomputed per-slot deadline
        (``due = eta - eps/rate``, maintained by :meth:`flush`) against
        the clock — algebraically the same ``rem <= eps`` test
        (``rate * (eta - now) <= eps``), one compare per slot instead of
        a full rem reconstruction per NET event."""
        if self.batched:
            # released/fresh slots carry due = inf, so the deadline
            # compare alone is the active-and-due mask
            return np.nonzero(self.due <= self.last)[0]
        return np.nonzero(self.active & (self.rem_now() <= _DONE_EPS))[0]

    def _rate_slots(self, slots: list[int],
                    share: Optional[np.ndarray] = None) -> None:
        """Recompute rate = min over the slot's links of bw/active for
        ``slots``. Pure function of current link occupancy, so re-rating a
        slot twice (it sits in several changed link groups) is harmless.

        ``share`` is an optional precomputed per-link share vector
        (``link_bw / max(1, link_act)``) — ``rerate`` hoists it once per
        event when the batch is big enough to amortize it; element-wise
        it is the exact same IEEE division, so both forms produce
        identical rates."""
        n = len(slots)
        if n == 0:
            return
        if n <= 4:      # numpy call overhead dominates tiny groups
            for sl in slots:
                r = np.inf
                for li in self.path[sl]:
                    if li < 0:
                        break
                    s = (self.link_bw[li] / max(1.0, self.link_act[li])
                         if share is None else share[li])
                    if s < r:
                        r = s
                self.rate[sl] = r
            return
        idx = np.fromiter(slots, np.intp, n)
        p = self.path[idx]
        valid = p >= 0
        safe = np.where(valid, p, 0)
        sh = (self.link_bw[safe] / np.maximum(1.0, self.link_act[safe])
              if share is None else share[safe])
        self.rate[idx] = np.where(valid, sh, np.inf).min(axis=1)

    def rerate(self, changed: Iterable[int], now: float) -> Optional[float]:
        """Refresh rates after the occupancy of ``changed`` links moved;
        return the next completion time (None when nothing is draining).

        Every route computes the same pure function of link occupancy;
        they differ only in batching:

        * numpy — incremental: re-rate the union of the changed links'
          member slots in one vectorized gather-min (small unions take a
          scalar fast path), then scan for the next completion on the
          host.
        * device / device-interpret — deferred: record the changed link
          ids and mark the engine dirty, O(path length) per event no
          matter how many transfers are in flight; the simulator runs one
          fused :meth:`flush` per drained event instant, which re-rates
          the whole dirty neighborhood and reschedules the NET wake-up.
        """
        self.stats["rerate_calls"] += 1
        if self.batched:
            for li in changed:
                self._dirty_links[li] = None
            self.dirty = True
            return None
        # union the changed links' member slots first: a transfer whose
        # path crosses several changed links (source NIC + uplinks) is
        # re-rated once instead of once per link. Rates are pure functions
        # of current occupancy, so this is exactly the same computation.
        changed = list(changed)
        if len(changed) == 1:
            slots = list(self.members[changed[0]])
        else:
            # merge the changed links' member dicts: a transfer crossing
            # several changed links dedups, and the batch keeps a
            # deterministic (changed-order, then allocation-order) order
            merged: dict[int, None] = {}
            for li in changed:
                merged.update(self.members[li])
            slots = list(merged)
        self.stats["rerate_slots"] += len(slots)
        # share vector hoisted once per event (occupancy is fixed while
        # re-rating) when the batch is big enough to amortize it:
        # element-wise it is the exact same IEEE division as the per-slot
        # gather, so rates are bit-identical either way.
        share = (self.link_bw / np.maximum(1.0, self.link_act)
                 if len(slots) > 4 else None)
        self._rate_slots(slots, share)
        if self.n_active == 0:
            return None
        live = self.rate > 0.0   # released slots are zeroed, so live ⊆ active
        if not live.any():
            return None
        return float(np.min(now + self.rem[live] / self.rate[live]))

    def flush(self, now: float) -> Optional[float]:
        """Batched backends only: fold every occupancy change recorded
        since the last flush into one fused reconstruct + re-rate +
        next-completion pass (:mod:`repro.kernels.event_engine`) and
        clear the dirty state.

        The pass covers the *dirty neighborhood* — the union of the dirty
        links' member slots, merged once per instant instead of once per
        event (slots on untouched links keep their cached ``(rate, eta)``
        pair: rates are pure functions of link occupancy, so they are
        still exact). The next completion then comes from one vectorized
        running-min over the cached eta array (released slots are ``inf``)
        — O(capacity) *per instant*, where the incremental route pays an
        O(live) scan per *event*. When the neighborhood holds no live slot
        (a release freed the last slot on every link it touched, or a NET
        wake-up found no completion) the pass re-rates nothing on any
        route: it returns the cached earliest eta, makes no crossing and
        counts in ``stats["flush_skipped"]``. Otherwise, on TPU (and under
        ``device-interpret``) the kernel sees the full slot array in a
        single call — subset gathers save nothing when the whole array is
        one fused device pass — and its running-min output is used
        directly.

        Writes back the reconstructed ``rem``, the new ``rate`` and the
        new per-slot ``eta`` (so host readers — completions, the tie-race
        digest — see state as of ``now``) and returns the earliest
        completion time, or None when nothing is draining. The simulator
        calls this once per drained event instant
        (``GridSimulator._net_flush``)."""
        self.dirty = False
        self.last = now
        self.stats["flush_passes"] += 1
        dirty, self._dirty_links = self._dirty_links, {}
        if not any(self.members[li] for li in dirty):
            # no live slot uses a link whose occupancy moved: every cached
            # (rate, eta) pair is still exact, so the pass re-rates
            # nothing and makes no crossing on any route
            self.stats["flush_skipped"] += 1
            eta_min = float(self.eta.min())
            return eta_min if np.isfinite(eta_min) else None
        if self._use_kernel or self.backend == "device-interpret":
            self.stats["flush_slots"] += self.n_active
            self.stats["flush_kernel" if self._use_kernel
                       else "flush_host"] += 1
            args = (self.path, self.rem, self.rate, self.eta, self.link_bw,
                    self.link_act, now)
            backend = "pallas" if self._use_kernel else "interpret"
            probe = self.probe
            if probe is None:
                rem_now, rate_new, eta_new, eta_min = self._flush_op(
                    *args, backend=backend)
                self._write_back(rem_now, rate_new, eta_new)
            else:
                rem_now, rate_new, eta_new, eta_min = self._flush_op(
                    *args, backend=backend, probe=probe)
                with probe.part("net.flush.apply"):
                    self._write_back(rem_now, rate_new, eta_new)
            return eta_min if np.isfinite(eta_min) else None
        # CPU route: the same fused pass (float64 oracle) over the dirty
        # neighborhood, then the running-min over the eta array
        merged: dict[int, None] = {}
        for li in dirty:
            merged.update(self.members[li])
        self.stats["flush_slots"] += len(merged)
        self.stats["flush_host"] += 1
        if len(merged) <= 8:
            # scalar fast path: same IEEE-double math as the ref pass
            # (Python floats are f64), skipping the fancy-index
            # gather/scatter overhead that dominates tiny unions
            bw, act = self.link_bw, self.link_act
            for s in merged:
                r_new = math.inf
                for li in self.path[s]:
                    if li < 0:
                        break
                    a = act[li]
                    sh = bw[li] / (a if a > 1.0 else 1.0)
                    if sh < r_new:
                        r_new = sh
                if math.isinf(r_new):   # all-padding row
                    r_new = 0.0
                old_rate = self.rate[s]
                if old_rate > 0.0:
                    rn = old_rate * (self.eta[s] - now)
                else:
                    rn = self.rem[s]
                if rn < 0.0:
                    rn = 0.0
                self.rem[s] = rn
                self.rate[s] = r_new
                if r_new > 0.0:
                    e = now + rn / r_new
                    self.eta[s] = e
                    self.due[s] = e - _DONE_EPS / r_new
                else:
                    self.eta[s] = np.inf
                    self.due[s] = np.inf
        else:
            idx = np.fromiter(merged, np.intp, len(merged))
            rem_now, rate_new, eta_new, _ = self._flush_ref(
                self.path[idx], self.rem[idx], self.rate[idx],
                self.eta[idx], self.link_bw, self.link_act, now)
            self.rem[idx] = rem_now
            self.rate[idx] = rate_new
            self.eta[idx] = eta_new
            live = rate_new > 0.0
            self.due[idx] = np.where(
                live, eta_new - _DONE_EPS / np.where(live, rate_new, 1.0),
                np.inf)
        eta_min = float(self.eta.min())
        return eta_min if np.isfinite(eta_min) else None

    def _write_back(self, rem_now, rate_new, eta_new) -> None:
        """Kernel route: the flushed slot state over the whole array, and
        each live slot's completion deadline."""
        self.rem[:] = rem_now
        self.rate[:] = rate_new
        self.eta[:] = eta_new
        live = rate_new > 0.0
        self.due[:] = np.where(
            live, eta_new - _DONE_EPS / np.where(live, rate_new, 1.0),
            np.inf)
