"""Chip smoke test: the simulator's device route on one TPU chip.

    python chip_smoke.py

One process, no arguments, exit code 0 only when every phase passed:

1. Device facts. Prints ``jax.devices()``, the platform, device kind and
   count, and the JAX / jaxlib / libtpu versions; exits non-zero unless
   JAX finds a TPU.
2. Main phase. Runs ``grid_500_saturated`` (500 sites in 5x10x10,
   50/100 Mbps uplinks, a 1 000-file catalog, bursts of 50 jobs placed by
   the jitted broker, the batched ``net="device"`` event engine) capped
   at 2 000 jobs through ``repro.launch.experiments.run_spec``, then the
   same spec and seed on the ``net="numpy"`` host engine as the oracle.
   Every flush pass that re-rated slots must have run on the compiled
   ``event_engine`` kernel, as the run's own counters report; both runs
   must complete every job; average job time, makespan and average
   inter-region transfers must agree with the oracle within 1%.
3. Kernel phase. Calls ``st_cost``, ``strategy_plan`` and ``value_score``
   through their ops wrappers with ``backend="pallas"`` on seeded inputs
   at the widths the ``grid_500`` scenarios reach, and compares each
   with its float64 numpy oracle fed the same float32-rounded inputs:
   floats within float32 rounding, site indices and flags equal. (The
   flush kernel is checked in the main phase, where the run's own
   flushes take it.)

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Wall times are host-clock times around work that ends on the host, so
they include dispatch and host<->device copies.
"""

from __future__ import annotations

import dataclasses
import importlib.metadata
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

SCENARIO = "grid_500_saturated"
N_JOBS = 2_000
SEED = 0
#: end-to-end metrics of the device run vs the host oracle
METRIC_RTOL = 0.01
#: kernel floats vs their oracle: float32 rounding of a short chain of
#: divides, products and (for st_cost) sums of up to 25 terms
F32_RTOL = 1e-5


@dataclasses.dataclass(frozen=True)
class Widths:
    """Kernel-phase shapes (defaults: the ``grid_500`` scenarios)."""

    sites: int = 500          # 5 x 10 x 10
    files: int = 1_000        # grid_500 catalog
    files_evict: int = 10_000 # grid_500_evict catalog
    jobs: int = 50            # burst size
    pairs: int = 1_250        # 50 jobs x 25 files (grid_500_evict)


class CompileCounter:
    """Counts XLA compiles and persistent-cache hits through
    ``jax.monitoring`` while in its ``with`` block."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.executables = 0      # compiled or loaded from the cache
        self.cache_hits = 0
        self.compile_s = 0.0      # tracing + lowering + backend compile

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.compile_s += secs
        if event == self._BACKEND:
            self.executables += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileCounter":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def snapshot(self) -> dict:
        return {"compiles": self.executables - self.cache_hits,
                "cache_hits": self.cache_hits,
                "compile_s": self.compile_s}


def _since(counter: CompileCounter, before: dict) -> dict:
    now = counter.snapshot()
    return {k: now[k] - before[k] for k in now}


def _emit(record: dict) -> None:
    print(json.dumps(record, default=float), flush=True)


def device_facts() -> dict:
    """Print what JAX found; return the final line's ``device`` object."""
    import jax
    import jaxlib

    devices = jax.devices()
    first = devices[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"devices={devices} platform={first.platform} "
          f"kind={first.device_kind} count={len(devices)} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}", flush=True)
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def main_phase(spec, n_jobs: int, counter: CompileCounter) -> list[str]:
    """The scenario on the device engine, then on the host oracle."""
    from repro.launch.experiments import run_spec

    spec = dataclasses.replace(spec, obs="report")
    runs = {}
    for net in ("device", "numpy"):
        before = counter.snapshot()
        t0 = time.perf_counter()
        r = run_spec(dataclasses.replace(spec, net=net), seed=SEED,
                     n_jobs=n_jobs)
        wall = time.perf_counter() - t0
        runs[net] = r
        _emit({"phase": "main", "scenario": spec.name, "net": net,
               "n_jobs": n_jobs, "wall_s": wall,
               "completed_jobs": r.completed_jobs,
               "avg_job_time_s": r.avg_job_time, "makespan_s": r.makespan,
               "avg_inter_comms": r.avg_inter_comms,
               "net_stats": r.net_stats,
               "phases": r.telemetry.phase_breakdown(),
               **_since(counter, before)})
    failures = []
    dev, ref = runs["device"], runs["numpy"]
    stats = dev.net_stats
    if stats["flush_kernel"] == 0 or stats["flush_host"] != 0:
        failures.append(f"main: flushes on the kernel {stats['flush_kernel']}"
                        f", on the host {stats['flush_host']}")
    for net, r in runs.items():
        if r.completed_jobs != n_jobs:
            failures.append(f"main: net={net} completed {r.completed_jobs}"
                            f"/{n_jobs} jobs")
    deviation = {}
    for name, attr in (("avg_job_time_s", "avg_job_time"),
                       ("makespan_s", "makespan"),
                       ("avg_inter_comms", "avg_inter_comms")):
        want = getattr(ref, attr)
        deviation[name] = abs(getattr(dev, attr) - want) / abs(want)
        if not deviation[name] <= METRIC_RTOL:
            failures.append(f"main: {name} device {getattr(dev, attr)!r} vs "
                            f"oracle {want!r}")
    _emit({"phase": "main", "relative_deviation": deviation,
           "limit": METRIC_RTOL})
    return failures


def _f32(a) -> np.ndarray:
    """The float32 rounding the chip sees, back in float64."""
    return np.asarray(a, np.float32).astype(np.float64)


def _close(got, want, rtol: float = F32_RTOL) -> tuple[bool, float]:
    """Equal infinities, finite entries within ``rtol``; also returns the
    largest relative error over the finite entries."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    fin = np.isfinite(want)
    if not np.array_equal(np.isfinite(got), fin) or not np.array_equal(
            got[~fin], want[~fin]):
        return False, float("inf")
    denom = np.maximum(np.abs(want[fin]), np.finfo(np.float64).tiny)
    err = float(np.max(np.abs(got[fin] - want[fin]) / denom, initial=0.0))
    return err <= rtol, err


def kernel_phase(w: Widths, backend: str,
                 counter: CompileCounter) -> list[str]:
    """Three kernels through their ops wrappers vs their numpy oracles."""
    from repro.kernels.st_cost import st_cost, st_cost_ref
    from repro.kernels.strategy_plan import strategy_plan
    from repro.kernels.value_score import value_score, value_score_ref

    rng = np.random.default_rng(SEED)
    failures = []

    def report(name, shape, ok, err, before):
        _emit({"phase": "kernel", "kernel": name, "backend": backend,
               "shape": shape, "ok": ok, "max_rel_err": err,
               **_since(counter, before)})
        if not ok:
            failures.append(f"kernel: {name} {shape} max rel err {err!r}")

    # st_cost: one 50-job burst over a 1 250-file batch union
    bw_ss = _f32(rng.random((w.sites, w.sites)) * 1.25e7 + 1e5)
    presence = rng.random((w.sites, w.pairs)) < 0.2
    presence[0] = True
    online = rng.random(w.sites) < 0.95
    online[0] = True
    fetch = presence & online[:, None]
    fetch[0] = presence[0]                  # site 0 plays durable master
    sizes = _f32(rng.random(w.pairs) * 1e9 + 1e6)
    required = np.zeros((w.jobs, w.pairs), bool)
    for j in range(w.jobs):
        required[j, rng.choice(w.pairs, 25, replace=False)] = True
    rel = _f32(rng.random(w.sites) * 50.0)
    args = (bw_ss, fetch, presence, sizes, required, rel, online)
    before = counter.snapshot()
    out = st_cost(*args, backend=backend)
    report("st_cost", [w.sites, w.pairs, w.jobs], *_close(out,
           st_cost_ref(*args)), before)

    # strategy_plan: bandwidths are multiples of 1 KiB below 2**24 and
    # 1 + serve a power of two, so every key is exact in float32 and the
    # site picks must equal the oracle's, ties included
    bw_sp = 1024.0 * rng.integers(1, 2 ** 14, (w.sites, w.pairs))
    fetch_sp = rng.random((w.sites, w.pairs)) < 0.15
    fetch_sp[rng.integers(0, w.sites, w.pairs), np.arange(w.pairs)] = True
    region = np.arange(w.sites) * 5 // w.sites
    local = region[:, None] == rng.integers(0, 5, w.pairs)[None, :]
    serve = rng.choice([0.0, 1.0, 3.0, 7.0], w.sites)
    free = 2.0 ** 20 * rng.integers(0, 2048, w.pairs)
    size = 2.0 ** 20 * rng.integers(1, 1024, w.pairs)
    args = (bw_sp, fetch_sp, local, serve, free, size)
    before = counter.snapshot()
    out = strategy_plan(*args, backend=backend)
    ref = strategy_plan(*args, backend="numpy")
    report("strategy_plan", [w.sites, w.pairs],
           all(np.array_equal(a, b) for a, b in zip(out, ref)), 0.0, before)

    # value_score on the grid_500 and grid_500_evict catalogs
    bw_vs = _f32(rng.random((w.sites, w.sites)) * 1.25e7 + 1e5)
    for files, mode in ((w.files, "cost"), (w.files, "plain"),
                        (w.files_evict, "cost")):
        demand = _f32(rng.random((w.sites, files)) * 20.0)
        sizes = _f32(rng.random(files) * 1e9 + 1e6)
        presence = rng.random((w.sites, files)) < 0.25
        presence[0] = True
        args = (demand, sizes, presence, bw_vs)
        before = counter.snapshot()
        out = value_score(*args, mode=mode, backend=backend)
        report(f"value_score[{mode}]", [w.sites, files],
               *_close(out, value_score_ref(*args, mode=mode)), before)
    return failures


def smoke(spec, n_jobs: int, widths: Widths, backend: str) -> list[str]:
    """Both phases; returns the failures (empty when all passed)."""
    with CompileCounter() as counter:
        failures = main_phase(spec, n_jobs, counter)
        failures += kernel_phase(widths, backend, counter)
    return failures


def main() -> int:
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    device = device_facts()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{device['platform']!r} ({device['kind']})", file=sys.stderr)
        return 1
    from repro.core import get_scenario

    t0 = time.perf_counter()
    failures = smoke(get_scenario(SCENARIO), N_JOBS, Widths(), "pallas")
    print(f"total wall {time.perf_counter() - t0:.3f} s", flush=True)
    for f in failures:
        print(f"FAILED {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
