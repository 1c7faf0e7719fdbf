"""Vectorized, jit-compiled scheduling decision (beyond-paper).

The paper's algorithm is an argmax over sites of S_s with a relative-load
tie-break. At cluster scale (thousands of hosts, thousands of artifacts) the
Python loop becomes the broker's bottleneck, so we express the decision as a
single fused XLA computation over:

  presence:  bool[n_sites, n_files]  — replica catalog as a bitmap
  sizes:     f32[n_files]            — file sizes
  required:  bool[n_files]           — the job's R_j as a mask
  load_rank: f32[n_sites]            — relative-load rank per site
  online:    bool[n_sites]

Tie-break is exact (no epsilon folding): stage 1 computes S_s and its max,
stage 2 arg-minimizes relative load over the tied sites only. Both stages
fuse into one XLA computation. The relative load itself is taken on the
host in float64, exactly as the sequential policies take it, and reaches
the device as its dense rank (:meth:`JaxScheduler.site_state_np`):
float32 queued work over float32 capacity would round both and divide
on a chip whose division may be a few ulps off, splitting sites whose
float64 loads tie. (:func:`select_sites_batch` still takes a capacity,
which its callers pass as ones.)

This module is also the bridge used by grid/placement.py to run dispatch
on-device for batches of jobs (vmap over the job axis).

Snapshot maintenance is incremental: the presence bitmap is kept current
by :class:`repro.core.catalog.ReplicaCatalog` change listeners (one cell
write per replica add/evict/loss) instead of a Python double loop over
the whole catalog per batch, and the file axis re-syncs lazily when files
are registered after broker construction (the same convention
:class:`repro.core.access.AccessHistory` uses).

Beyond the paper's policy, :class:`JaxShortestTransferBroker` vectorizes the
``shortesttransfer`` baseline the same way: each batch is costed against
the engine-shared point-bandwidth snapshot
(:meth:`repro.core.network.NetworkEngine.point_bandwidth_matrix`, the same
matrix the replication economy prices with) through the *blocked*
``repro.kernels.st_cost`` pass — running-max over holders, running-sum
over files — so peak broker memory is O(sites x files + sites x sites),
never the old ``(sites, files, sites)`` broadcast.

Degenerate-snapshot semantics match the sequential policies: dispatching
against a snapshot with **no online site** raises exactly what the
sequential policy would (``ValueError`` from the empty ``min``/``max`` for
the deterministic policies, ``IndexError`` from ``Random.choice(())`` for
``random`` — with no PRNG draw consumed), instead of argmin-over-inf
silently landing every job on site 0.

The batched *strategy* engine (``strategy_mode="batch"``,
:mod:`repro.core.replica`'s ``_BatchedStrategy`` family) follows the same
snapshot contract from the other side of the dispatch: once a burst's
placements are fixed, every missing (job, file) pair is planned against one
shared presence/bandwidth snapshot — the per-destination column view
(:meth:`repro.core.network.NetworkEngine.point_bandwidth_columns`) of the
same matrix the brokers cost with — and intra-burst conflicts are resolved
by revalidate-or-replan at execution time, exactly the tolerance convention
the jax brokers established for stale queue loads. Singleton bursts take the
sequential path bit-for-bit.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .catalog import ReplicaCatalog
from .topology import GridTopology

#: the batch broker's stages, timed as parts of the simulator's
#: ``broker.select_batch`` span when a probe is attached (``repro.obs``)
STAGE, LAUNCH, FETCH = ("broker.batch.stage", "broker.batch.launch",
                        "broker.batch.fetch")


@functools.partial(jax.jit, static_argnames=())
def select_site_vec(presence, sizes, required, load_rank, online):
    """Paper §3.2 as one fused computation. Returns the chosen site index."""
    # S_s for every site: presence masked by the job's requirement
    s = (presence & required[None, :]) @ sizes              # [n_sites]
    s = jnp.where(online, s, -1.0)
    tie = s >= jnp.max(s)                                    # max-S_s sites
    rel = jnp.where(tie, load_rank, jnp.inf)
    return jnp.argmin(rel)                                   # first min = min (rel, id)


@jax.jit
def select_sites_batch(presence, sizes, masks, load_rank, capacity, online):
    """Batched :func:`select_site_vec`, reformulated as one GEMM.

    A straight ``vmap`` of the single-job scorer materializes a
    ``(jobs, sites, files)`` bool intermediate (25M elements per 50-job
    burst at the 500-site scale point); algebraically the per-site byte
    sum is ``(masks * sizes) @ presence.T``, which XLA lowers to a real
    ``(jobs, files) x (files, sites)`` matmul instead. Same scores (file
    sizes are uniform per config, so the f32 sums are exact in any
    summation order), same tie-breaking as the vmapped form.
    ``capacity`` divides ``load_rank``; the brokers pass ones.
    """
    w = masks.astype(sizes.dtype) * sizes                   # [jobs, files]
    s = w @ presence.T.astype(sizes.dtype)                  # [jobs, sites]
    s = jnp.where(online[None, :], s, -1.0)
    tie = s >= jnp.max(s, axis=1, keepdims=True)
    rel = jnp.where(tie, (load_rank / capacity)[None, :], jnp.inf)
    return jnp.argmin(rel, axis=1)


class JaxScheduler:
    """Array-backed mirror of (catalog, topology) for on-device dispatch.

    Also the snapshot substrate for every jax broker: the host-side
    presence bitmap, per-site load-rank/online vectors and
    required-file masks built here are shared with
    :class:`JaxShortestTransferBroker`.

    The presence bitmap is maintained **incrementally**: the broker
    registers as a catalog change listener and flips single cells as
    replicas are added/evicted/lost. Files registered after construction
    are picked up by the lazy :meth:`sync` (cheap count check per batch),
    which rebuilds the file axis carrying maintained columns over.
    """

    def __init__(self, catalog: ReplicaCatalog, topology: GridTopology) -> None:
        self.catalog = catalog
        self.topology = topology
        self.lfns = sorted(catalog.files)
        self.lfn_index = {l: i for i, l in enumerate(self.lfns)}
        self._sizes_np = np.array([catalog.size(l) for l in self.lfns],
                                  np.float64)
        self.sizes = jnp.asarray(self._sizes_np, jnp.float32)
        self._n_catalog = len(catalog.files)
        self._presence: np.ndarray | None = None    # built on first use
        # repro.obs probe, set by GridSimulator (None: telemetry off)
        self.probe = None
        # select_sites_batch's capacity, moved to the device once
        self._ones = jnp.ones(topology.n_sites, jnp.float32)
        catalog.add_listener(self)

    # -- catalog change listeners (incremental presence maintenance) -------
    def on_register_file(self, lfn: str) -> None:
        """New file axis entry; the next :meth:`sync` rebuilds (lazily —
        registration bursts cost one rebuild, not one per file)."""

    def on_add_replica(self, lfn: str, site_id: int) -> None:
        if self._presence is not None:
            j = self.lfn_index.get(lfn)
            if j is not None:
                self._presence[site_id, j] = True

    def on_remove_replica(self, lfn: str, site_id: int) -> None:
        if self._presence is not None:
            j = self.lfn_index.get(lfn)
            if j is not None:
                self._presence[site_id, j] = False

    # -- catalog sync ------------------------------------------------------
    def sync(self) -> None:
        """Pick up files registered in the catalog *after* construction
        (dynamic workloads, late-registered artifacts): rebuild the file
        axis in sorted order, carrying the incrementally-maintained
        presence columns over by LFN and filling new columns from the
        catalog. No-op when the catalog is unchanged."""
        if len(self.catalog.files) == self._n_catalog:
            return
        old_index = self.lfn_index
        old_presence = self._presence
        self.lfns = sorted(self.catalog.files)
        self.lfn_index = {l: i for i, l in enumerate(self.lfns)}
        self._sizes_np = np.array([self.catalog.size(l) for l in self.lfns],
                                  np.float64)
        self.sizes = jnp.asarray(self._sizes_np, jnp.float32)
        if old_presence is not None:
            presence = np.zeros((self.topology.n_sites, len(self.lfns)), bool)
            for j, lfn in enumerate(self.lfns):
                i = old_index.get(lfn)
                if i is not None:
                    presence[:, j] = old_presence[:, i]
                else:
                    self._fill_column(presence, j, lfn)
            self._presence = presence
        self._n_catalog = len(self.catalog.files)
        self._resync()

    def _resync(self) -> None:
        """Hook for subclasses with extra per-file state (e.g. masters)."""

    def _fill_column(self, presence: np.ndarray, j: int, lfn: str) -> None:
        """One file's presence column from the catalog's holder set — the
        single definition of what a bitmap cell means."""
        for h in sorted(self.catalog.holders(lfn)):
            presence[h, j] = True

    # -- host-side snapshot pieces (shared by all brokers) -----------------
    def presence_np(self) -> np.ndarray:
        """bool[n_sites, n_files] replica bitmap (all holders).

        The *live* incrementally-maintained array — treat it as
        read-only; copy before masking (``presence & ...`` does)."""
        self.sync()     # no-op unless files were registered late
        if self._presence is None:
            presence = np.zeros((self.topology.n_sites, len(self.lfns)), bool)
            for j, lfn in enumerate(self.lfns):
                self._fill_column(presence, j, lfn)
            self._presence = presence
        return self._presence

    def site_state_np(self) -> tuple[np.ndarray, np.ndarray]:
        """(load_rank, online) per-site vectors.

        ``load_rank`` is the dense rank of each site's float64 relative
        load (``Site.relative_load``, the sequential policies' key):
        equal loads share a rank, and small whole numbers keep their
        order and their ties in float32 on any chip."""
        rel = np.array([s.relative_load() for s in self.topology.sites],
                       np.float64)
        rank = np.unique(rel, return_inverse=True)[1].astype(np.float32)
        online = np.array([s.online for s in self.topology.sites], bool)
        return rank, online

    def required_np(self, required_sets: list[list[str]]) -> np.ndarray:
        """bool[n_jobs, n_files] requirement masks (R_j rows)."""
        self.sync()     # no-op unless files were registered late
        m = np.zeros((len(required_sets), len(self.lfns)), dtype=bool)
        for i, req in enumerate(required_sets):
            for lfn in req:
                m[i, self.lfn_index[lfn]] = True
        return m

    @staticmethod
    def _check_online(online: np.ndarray) -> None:
        """All-offline guard, shared by every broker: raise exactly what
        the sequential policies' empty ``min``/``max`` raises instead of
        letting an argmin-over-inf dispatch to (offline) site 0."""
        if not online.any():
            raise ValueError("no online sites to dispatch to")

    def snapshot(self):
        load_rank, online = self.site_state_np()
        self._check_online(online)
        return (jnp.asarray(self.presence_np()), self.sizes,
                jnp.asarray(load_rank), jnp.asarray(online))

    def required_mask(self, required: list[str]) -> jnp.ndarray:
        return jnp.asarray(self.required_np([required])[0])

    def select(self, required: list[str]) -> int:
        self.sync()
        presence, sizes, load_rank, online = self.snapshot()
        return int(select_site_vec(presence, sizes, self.required_mask(required),
                                   load_rank, online))

    def select_batch(self, required_sets: list[list[str]]) -> list[int]:
        """Every job of a burst scored against one snapshot in one
        device program; with a probe, timed in three parts: ``STAGE``
        (the snapshot and the masks built and moved to the device),
        ``LAUNCH`` (the program's dispatch) and ``FETCH`` (the wait and
        the one copy of the picks back)."""
        part = (contextlib.nullcontext if self.probe is None
                else self.probe.part)
        with part(STAGE):
            self.sync()
            presence, sizes, load_rank, online = self.snapshot()
            masks = jnp.asarray(self.required_np(required_sets))
        with part(LAUNCH):
            picks = select_sites_batch(presence, sizes, masks, load_rank,
                                       self._ones, online)
        with part(FETCH):
            # one host transfer for the whole batch (per-element int()
            # would sync the device once per job)
            return np.asarray(picks).tolist()


@jax.jit
def leastloaded_select(load_rank, online):
    """LeastLoaded as one fused computation: argmin of the relative-load
    rank over online sites. ``jnp.argmin`` returns the first (lowest-id) minimum,
    matching the sequential policy's ``(relative_load, site_id)`` key.
    Callers must reject all-offline snapshots host-side — an argmin over
    all-``inf`` would silently return site 0."""
    return jnp.argmin(jnp.where(online, load_rank, jnp.inf))


class JaxLeastLoadedBroker(JaxScheduler):
    """Vectorized ``leastloaded`` dispatch.

    Snapshot semantics match the other jax brokers: every job in a batch
    sees the same load vector (queued work is not updated between batch
    members), so the whole batch lands on the argmin site — bulk placement
    trades spreading for one fused decision, exactly like the dataaware
    batch broker's shared-snapshot argmax.
    """

    def select_batch(self, required_sets: list[list[str]]) -> list[int]:
        load_rank, online = self.site_state_np()
        self._check_online(online)
        site = int(leastloaded_select(jnp.asarray(load_rank),
                                      jnp.asarray(online)))
        return [site] * len(required_sets)


class JaxRandomBroker(JaxScheduler):
    """Vectorized ``random`` dispatch: a host-PRNG index vector gathered
    over the online-site vector on device.

    Site-for-site identical to the sequential :class:`repro.core.scheduler.
    RandomScheduler`: ``rng.choice(seq)`` consumes exactly one
    ``_randbelow(len(seq))`` draw, and so does ``rng.randrange(n)`` here —
    share (or equally seed) the policy's ``Random`` and the decision
    streams coincide. With no online site the sequential policy's
    ``choice`` raises ``IndexError`` *without* touching the PRNG
    (``_randbelow(0)`` draws nothing), so the broker does the same — the
    shared stream stays aligned across a caught churn-to-zero window.
    """

    def __init__(self, catalog: ReplicaCatalog, topology: GridTopology,
                 rng) -> None:
        super().__init__(catalog, topology)
        self.rng = rng

    def select_batch(self, required_sets: list[list[str]]) -> list[int]:
        _, online = self.site_state_np()
        ids = np.flatnonzero(online)
        if ids.size == 0:
            raise IndexError("cannot choose from an empty online-site list")
        idx = np.array([self.rng.randrange(len(ids))
                        for _ in required_sets], np.intp)
        return np.asarray(
            jnp.take(jnp.asarray(ids), jnp.asarray(idx))).tolist()


class JaxShortestTransferBroker(JaxScheduler):
    """Vectorized ``shortesttransfer`` dispatch over a shared snapshot.

    Mirrors :meth:`repro.core.scheduler.ShortestTransferScheduler.
    select_site` — including the durable-masters rule, the zero-bandwidth
    guard and the all-``inf`` tie rule (first online site) — but costs
    every (job, site) pair at once through the blocked
    :func:`repro.kernels.st_cost.st_cost` pass against the
    **engine-shared** point-bandwidth snapshot
    (:meth:`repro.core.network.NetworkEngine.point_bandwidth_matrix`):
    one cached ``(sites, sites, depth)`` link tensor serves this broker
    and the replication economy alike, and no private path tensor is
    built. The file axis is restricted to the batch's required-file
    union before costing — bit-exact (absent files contribute exact
    zeros) and it keeps the per-batch oracle work O(union x sites).
    Like the dataaware batch broker, all jobs in a batch see the same
    snapshot (queued work is not updated between batch members).
    """

    def __init__(self, catalog: ReplicaCatalog, topology: GridTopology,
                 network) -> None:
        super().__init__(catalog, topology)
        self.network = network
        self._resync()
        # st_cost route, re-resolved per call ("auto" = compiled Pallas
        # kernel on TPU, float64 oracle on CPU); tests may override
        self._backend = "auto"

    def _resync(self) -> None:
        self.masters = np.array(
            [self.catalog.files[l].master_site for l in self.lfns], np.intp)

    def select_batch(self, required_sets: list[list[str]]) -> list[int]:
        from repro.kernels.st_cost import st_cost  # jax-free package import
        self.sync()
        presence = self.presence_np()
        online = np.array([s.online for s in self.topology.sites], bool)
        self._check_online(online)
        required = self.required_np(required_sets)
        # restrict every file-axis input to the batch's required-file
        # union up front (ascending ids, so sum order is preserved)
        union = np.flatnonzero(required.any(axis=0))
        presence_u = presence[:, union]
        # fetchable = online holder, or the durable master copy
        files = np.arange(union.size)
        masters_u = self.masters[union]
        fetch_mask = presence_u & online[:, None]
        fetch_mask[masters_u, files] |= presence_u[masters_u, files]
        # relative load in float64 straight from the sites — the exact
        # doubles the sequential policy reads
        rel = np.array([s.relative_load() for s in self.topology.sites],
                       np.float64)
        costs = st_cost(
            self.network.point_bandwidth_matrix(),
            fetch_mask, presence_u, self._sizes_np[union],
            required[:, union], rel, online, backend=self._backend)
        picks = np.argmin(costs, axis=1)
        # every online site at inf (nothing fetchable at finite cost):
        # the sequential (cost, site_id) min takes the first online site
        stuck = ~np.isfinite(costs[np.arange(len(picks)), picks])
        if stuck.any():
            picks[stuck] = np.flatnonzero(online)[0]
        return [int(i) for i in picks]
