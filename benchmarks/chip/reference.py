"""Plain reference of one simulated grid run, independent of the program.

It re-states, in straightforward Python, what one run of the data-grid
simulator means for the configurations this benchmark serves: a tier tree
of sites with fair-share links, a catalog of masters placed round-robin,
Zipf-drawn jobs arriving in bursts, the paper's data-aware placement
(most required bytes on site, then least relative load, then lowest id),
HRS replication (regional source first, two-phase LRU eviction for
inter-region stores, temporary buffers otherwise) and a fluid network in
which every transfer runs at the smallest fair share along its path and
remaining bytes are integrated at each transfer start and each network
wake-up. It imports nothing of the program and takes nothing it made.

``precision`` rounds the network arithmetic (each rate, each remaining
byte count and each time to completion, times kept relative to the
current instant) to a narrower float: ``float64`` is the plain model,
``float32`` and ``bfloat16`` are the controls that show what a
lower-precision flush does to the answers.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import random

import numpy as np

MB = 1e6
GB = 1e9
MBPS = 1e6 / 8.0            # bytes/s per Mbit/s
DONE_BYTES = 1.0            # a transfer with less than a byte left is done
SUBMIT, NET, CPU_DONE = range(3)


def _rounder(precision: str):
    """Elementwise rounding of a float64 array to ``precision``."""
    if precision == "float64":
        return lambda a: a
    if precision in ("float32", "bfloat16"):
        if precision == "float32":
            dtype = np.float32
        else:
            import ml_dtypes
            dtype = ml_dtypes.bfloat16
        return lambda a: np.asarray(a).astype(dtype).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


@dataclasses.dataclass
class JobOut:
    """What one job came to: where it ran and when each stage ended."""

    job_id: int
    site: int
    submit: float
    ready: float
    start: float
    finish: float
    inter_comms: int


@dataclasses.dataclass
class RunOut:
    jobs: list[JobOut]          # in completion order
    n_jobs: int
    makespan: float
    inter_comms: int            # inter-region transfers, all jobs


@dataclasses.dataclass(eq=False)
class _Job:
    job_id: int
    required: list[str]
    length: float
    submit: float
    site: int = -1
    missing: list[str] = dataclasses.field(default_factory=list)
    pending: int = 0
    temp: list[str] = dataclasses.field(default_factory=list)
    pinned: list[str] = dataclasses.field(default_factory=list)
    ready: float = -1.0
    start: float = -1.0
    done: bool = False
    rounds: int = 0
    pin_on_arrival: bool = False
    ops_left: float = 0.0
    inter: int = 0


@dataclasses.dataclass(eq=False)
class _Transfer:
    tid: int
    lfn: str
    src: int
    dst: int
    store: bool
    inter: bool
    links: tuple[int, ...]
    slot: int = -1
    waiters: list[_Job] = dataclasses.field(default_factory=list)


def zipf_jobs(cfg: dict, seed: int, n_jobs: int) -> list[tuple[int, list[str]]]:
    """(job type, required files) per job: each type ranks the catalog in
    its own seeded order and a job draws ``files_per_job`` distinct files,
    rank i weighted 1/(i+1)**alpha."""
    n_files = int(cfg["catalog_gb"] * GB / (cfg["file_size_mb"] * MB))
    names = [f"lfn{i:04d}" for i in range(n_files)]
    n_types, k, alpha = (cfg["n_job_types"], cfg["files_per_job"],
                         cfg["zipf_alpha"])
    shifts = cfg.get("hotset_shifts", 0)
    orders = []
    for phase in range(shifts + 1):
        rng = random.Random(seed + 1 + 7919 * phase)
        per_type = []
        for _ in range(n_types):
            perm = list(names)
            rng.shuffle(perm)
            per_type.append(perm)
        orders.append(per_type)
    cum, acc = [], 0.0
    for i in range(n_files):
        acc += 1.0 / (i + 1) ** alpha
        cum.append(acc)
    rng = random.Random(seed + 2)
    out = []
    for j in range(n_jobs):
        jt = rng.randrange(n_types)
        order = orders[j * (shifts + 1) // max(1, n_jobs)][jt]
        chosen: set[int] = set()
        while len(chosen) < k:
            chosen.add(bisect.bisect_right(cum, rng.random() * acc))
        out.append((jt, [order[i] for i in sorted(chosen)]))
    return out


class Grid:
    """One run of the simulated grid, from a configuration dict."""

    def __init__(self, cfg: dict, seed: int, *, precision: str = "float64"):
        unsupported = {k: cfg.get(k, d) for k, d in (
            ("scheduler", "dataaware"), ("strategy", "hrs"),
            ("strategy_mode", "sequential"), ("arrival", "uniform"),
            ("batch_window_s", 0.0)) if cfg.get(k, d) != d}
        unsupported.update({k: cfg[k] for k in (
            "uplink_scale", "storage_scale", "slowdowns") if cfg.get(k)})
        if cfg.get("churn", {}).get("n_failures", 0):
            unsupported["churn"] = cfg["churn"]
        if cfg.get("zipf_alpha") is None:
            unsupported["zipf_alpha"] = None
        if unsupported:
            raise ValueError(f"the reference does not model {unsupported}")
        self.cfg = cfg
        self.seed = seed
        self.q = _rounder(precision)
        fan = list(cfg["tier_fanouts"])
        self.group = fan[-1]                       # sites per leaf group
        n = 1
        for f in fan:
            n *= f
        self.n_sites = n
        self.cpu = [1e9 * (1 + ((s * 2654435761 + seed) % 4)) for s in range(n)]
        self.se_cap = cfg["storage_gb"] * GB
        self.used = [0.0] * n
        self.load = [0.0] * n
        # links: NIC s is link s; uplinks of each internal level follow,
        # top level first, one per tree node of that level
        bw = [cfg["lan_mbps"] * MBPS] * n
        self.level_divs = []           # sites under a node of each level
        self.up_of: list[list[int]] = [[] for _ in range(n)]
        width = 1
        for level, f in enumerate(fan[:-1]):
            width *= f
            first, div = len(bw), n // width
            bw += [cfg["uplink_mbps"][level] * MBPS] * width
            self.level_divs.append(div)
            for s in range(n):
                self.up_of[s].append(first + s // div)
        self.link_bw_list = bw
        self.link_bw = np.array(bw)
        self.link_users = [0] * len(bw)        # transfers per link
        self.link_act = np.zeros(len(bw))      # the same, as floats
        # catalog and SE contents
        self.size = cfg["file_size_mb"] * MB
        n_files = int(cfg["catalog_gb"] * GB / self.size)
        self.master = {f"lfn{i:04d}": (i * 7) % n for i in range(n_files)}
        self.holders = {lfn: set() for lfn in self.master}
        self.region_holders: dict[str, dict[int, int]] = {}
        self.se: list[dict[str, list]] = [{} for _ in range(n)]  # lfn -> [t, seq]
        self.pins: list[dict[str, int]] = [{} for _ in range(n)]
        self.add_seq = 0
        for lfn, m in self.master.items():
            self._se_insert(m, lfn, 0.0)
            self.used[m] += self.size
        # engine state
        self.now = 0.0
        self.heap: list = []
        self.seq = 0
        self.net_version = 0
        self.last_advance = 0.0
        self.rem = np.zeros(64)
        self.rate = np.zeros(64)
        self.live = np.zeros(64, bool)
        self.paths = np.full((64, len(fan)), -1)
        self.slot_tr: list[_Transfer | None] = [None] * 64
        self.free_slots = list(range(63, -1, -1))
        self.inflight: dict[tuple[int, str], _Transfer] = {}
        self.tid = 0
        self.queue = [[] for _ in range(n)]       # FIFO of jobs ready to run
        self.running: list[_Job | None] = [None] * n
        self.cpu_version = [0] * n
        self.cpu_last = [0.0] * n
        self.finished: list[JobOut] = []
        self.broker_jax = cfg.get("broker", "event") == "jax"

    # -- plumbing ----------------------------------------------------------
    def _push(self, t, kind, payload):
        self.seq += 1
        heapq.heappush(self.heap, (t, self.seq, kind, payload))

    def _region(self, s):
        return s // self.group

    def _path(self, src, dst):
        """NIC of the source, then every source-side uplink from the first
        tree level where source and destination part."""
        if self._region(src) == self._region(dst):
            return (src,)
        for lvl, div in enumerate(self.level_divs):
            if src // div != dst // div:
                return (src,) + tuple(self.up_of[src][lvl:])
        raise AssertionError("sites part nowhere")

    # -- storage ---------------------------------------------------------
    def _se_insert(self, site, lfn, t):
        self.add_seq += 1
        self.se[site][lfn] = [t, self.add_seq]
        self.holders[lfn].add(site)
        per_region = self.region_holders.setdefault(lfn, {})
        r = self._region(site)
        per_region[r] = per_region.get(r, 0) + 1

    def _touch(self, site, lfn, t):
        if lfn in self.se[site]:
            self.se[site][lfn][0] = t

    def _se_remove(self, site, lfn):
        assert self.pins[site].get(lfn, 0) == 0 and self.master[lfn] != site
        del self.se[site][lfn]
        self.used[site] -= self.size
        self.holders[lfn].discard(site)
        self.region_holders[lfn][self._region(site)] -= 1

    def _pin(self, site, lfn):
        self.pins[site][lfn] = self.pins[site].get(lfn, 0) + 1

    def _unpin(self, site, lfn):
        left = self.pins[site].get(lfn, 0) - 1
        if left <= 0:
            self.pins[site].pop(lfn, None)
        else:
            self.pins[site][lfn] = left

    def _free(self, site):
        return self.se_cap - self.used[site]

    # -- placement ---------------------------------------------------------
    def _pick_sites(self, jobs):
        """Most required bytes present, then least queued work over CPU
        speed, then lowest id; one snapshot for the whole burst."""
        rel = [self.load[s] / self.cpu[s] for s in range(self.n_sites)]
        out = []
        for job in jobs:
            score = [0.0] * self.n_sites
            for lfn in job.required:
                for h in self.holders[lfn]:
                    score[h] += self.size
            best = max(score)
            out.append(min((s for s in range(self.n_sites) if score[s] == best),
                           key=lambda s: (rel[s], s)))
        return out

    def _place(self, job, site):
        job.site = site
        job.ops_left = job.length
        self.load[site] += job.length
        job.missing = [l for l in job.required if l not in self.se[site]]
        for lfn in job.required:
            self._touch(site, lfn, self.now)
        self._fetch_next(job)

    # -- replication (HRS) -------------------------------------------------
    def _offer(self, src, dst):
        """Bandwidth one more transfer src -> dst would get."""
        return min(self.link_bw_list[l] / max(1, self.link_users[l] + 1)
                   for l in self._path(src, dst))

    def _plan(self, lfn, dst):
        holders = sorted(self.holders[lfn])
        local = [h for h in holders if self._region(h) == self._region(dst)]
        best = lambda cands: max(cands, key=lambda s: (self._offer(s, dst), -s))
        if local:
            return best(local), self._free(dst) >= self.size, [], False
        src = best(holders)
        if self._free(dst) >= self.size:
            return src, True, [], True
        evictable = [f for f, _ in sorted(self.se[dst].items(),
                                          key=lambda kv: tuple(kv[1]))
                     if self.master[f] != dst and not self.pins[dst].get(f)]
        region = self._region(dst)

        def dup(f):             # another site of dst's region holds f too
            return self.region_holders[f][region] > 1

        def two_phases():       # region-duplicated first, each in LRU order
            yield from (f for f in evictable if dup(f))
            yield from (f for f in evictable if not dup(f))

        freed, victims = self._free(dst), []
        for f in two_phases():
            if freed >= self.size:
                break
            victims.append(f)
            freed += self.size
        if freed >= self.size and victims:
            return src, True, victims, True
        return src, False, [], True

    # -- jobs ----------------------------------------------------------------
    def _fetch_next(self, job):
        if job.done:
            return
        while job.missing:
            lfn = job.missing.pop(0)
            if lfn in self.se[job.site]:
                self._touch(job.site, lfn, self.now)
                continue
            job.pending += 1
            self._start_transfer(lfn, job)
            return
        if job.pending == 0:
            if job.ready < 0:
                job.ready = self.now
            self.queue[job.site].append(job)
            self._try_start(job.site)

    def _try_start(self, site):
        if self.running[site] is not None:
            return
        q = self.queue[site]
        while q:
            job = q.pop(0)
            if job.done:
                continue
            gone = [f for f in job.required
                    if f not in job.temp and f not in self.se[site]]
            if gone:      # evicted while queued: stage again
                job.rounds += 1
                if job.rounds >= 3:
                    job.pin_on_arrival = True
                job.missing = gone
                self._fetch_next(job)
                continue
            for f in job.required:
                if f in self.se[site] and f not in job.pinned:
                    self._pin(site, f)
                    job.pinned.append(f)
                self._touch(site, f, self.now)
            job.start = self.now
            self.running[site] = job
            self.cpu_last[site] = self.now
            self.cpu_version[site] += 1
            self._push(self.now + job.ops_left / self.cpu[site], CPU_DONE,
                       (site, self.cpu_version[site]))
            return

    def _cpu_done(self, site, version):
        if version != self.cpu_version[site]:
            return
        job = self.running[site]
        if job is not None:
            job.ops_left = max(0.0, job.ops_left - (self.now - self.cpu_last[site])
                               * self.cpu[site])
        self.cpu_last[site] = self.now
        if job is None:
            return
        self.running[site] = None
        job.done = True
        self.load[site] -= job.length
        for f in job.pinned:
            self._unpin(site, f)
        job.temp.clear()
        self.finished.append(JobOut(job.job_id, site, job.submit, job.ready,
                                    job.start, self.now, job.inter))
        self._try_start(site)

    # -- network -------------------------------------------------------------
    # Transfers hold a slot in flat arrays (remaining bytes, rate, the link
    # path padded with -1); a freed slot is reused by the next transfer.
    def _slot(self, tr):
        if not self.free_slots:
            old = len(self.rem)
            self.rem = np.concatenate([self.rem, np.zeros(old)])
            self.rate = np.concatenate([self.rate, np.zeros(old)])
            self.live = np.concatenate([self.live, np.zeros(old, bool)])
            self.paths = np.concatenate(
                [self.paths, np.full((old, self.paths.shape[1]), -1)])
            self.slot_tr += [None] * old
            self.free_slots = list(range(2 * old - 1, old - 1, -1))
        k = self.free_slots.pop()
        self.rem[k], self.rate[k], self.live[k] = self.size, 0.0, True
        self.paths[k] = -1
        self.paths[k, :len(tr.links)] = tr.links
        self.slot_tr[k] = tr
        return k

    def _advance(self):
        dt = self.now - self.last_advance
        if dt > 0:
            self.rem = self.q(np.maximum(self.rem - self.rate * dt, 0.0))
        self.last_advance = self.now

    def _rerate(self):
        """Every transfer at its smallest fair share along its path; then
        wake the network at the earliest completion."""
        share = self.link_bw / np.maximum(1.0, self.link_act)
        p = self.paths[self.live]
        self.rate[self.live] = self.q(
            np.where(p >= 0, share[np.maximum(p, 0)], np.inf).min(axis=1))
        self.net_version += 1
        if self.live.any():
            left = self.q(self.rem[self.live] / self.rate[self.live])
            self._push(float(np.min(self.now + left)), NET, self.net_version)

    def _start_transfer(self, lfn, job):
        key = (job.site, lfn)
        if key in self.inflight and self.inflight[key].store:
            self.inflight[key].waiters.append(job)
            return
        self._advance()
        src, store, victims, inter = self._plan(lfn, job.site)
        if store:
            for v in victims:
                self._se_remove(job.site, v)
            self.used[job.site] += self.size       # reserve the space
        self._pin(src, lfn)
        self.tid += 1
        links = self._path(src, job.site)
        tr = _Transfer(self.tid, lfn, src, job.site, store, inter, links,
                       waiters=[job])
        tr.slot = self._slot(tr)
        for l in links:
            self.link_act[l] += 1.0
            self.link_users[l] += 1
        if store:
            self.inflight[key] = tr
        if inter:
            job.inter += 1
        self._rerate()

    def _finish_transfer(self, tr):
        k = tr.slot
        self.rem[k], self.rate[k], self.live[k] = 0.0, 0.0, False
        self.slot_tr[k] = None
        self.free_slots.append(k)
        self.inflight.pop((tr.dst, tr.lfn), None)
        for l in tr.links:
            self.link_act[l] -= 1.0
            self.link_users[l] -= 1
        self._unpin(tr.src, tr.lfn)
        self._touch(tr.src, tr.lfn, self.now)
        if tr.store:
            self.used[tr.dst] -= self.size
            if tr.lfn in self.se[tr.dst]:
                self._touch(tr.dst, tr.lfn, self.now)
            else:
                self._se_insert(tr.dst, tr.lfn, self.now)
                self.used[tr.dst] += self.size
        for job in tr.waiters:
            if job.done:
                continue
            if tr.store:
                if job.pin_on_arrival:
                    self._pin(tr.dst, tr.lfn)
                    job.pinned.append(tr.lfn)
            else:
                job.temp.append(tr.lfn)
            job.pending -= 1
            self._fetch_next(job)
        self._rerate()

    def _net_wake(self, version):
        if version != self.net_version:
            return
        self._advance()
        slots = np.nonzero(self.live & (self.rem <= DONE_BYTES))[0]
        if not slots.size:
            self._rerate()
        for tr in sorted((self.slot_tr[k] for k in slots),
                         key=lambda tr: tr.tid):
            self._finish_transfer(tr)

    # -- run -------------------------------------------------------------------
    def run(self, n_jobs: int) -> RunOut:
        cfg = self.cfg
        burst, gap = cfg["arrival_burst"], cfg["interarrival_s"]
        for j, (_, req) in enumerate(zipf_jobs(cfg, self.seed, n_jobs)):
            at = (j // burst) * gap * burst
            self._push(at, SUBMIT, _Job(j, req, cfg["job_length"], at))
        while self.heap:
            t, _, kind, payload = heapq.heappop(self.heap)
            self.now = t
            if kind == SUBMIT:
                batch = [payload]
                if self.broker_jax:
                    while self.heap and self.heap[0][0] <= t \
                            and self.heap[0][2] == SUBMIT:
                        batch.append(heapq.heappop(self.heap)[3])
                for job, site in zip(batch, self._pick_sites(batch)):
                    self._place(job, site)
            elif kind == NET:
                self._net_wake(payload)
            else:
                self._cpu_done(*payload)
        return RunOut(self.finished, n_jobs, self.now,
                      sum(j.inter_comms for j in self.finished))


def reference_run(cfg: dict, seed: int, n_jobs: int, *,
                  precision: str = "float64") -> RunOut:
    """One whole simulated run of ``cfg`` at ``seed``."""
    return Grid(cfg, seed, precision=precision).run(n_jobs)
