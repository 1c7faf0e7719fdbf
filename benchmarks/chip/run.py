"""Chip benchmark of the data-grid simulator: one cell, one run.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration, ``configs/<name>.json`` (a full scenario spec of the
simulated grid), and a traffic mix, ``traffic/<name>.json`` (the arrival
fields laid over that spec, jobs per run, and what set-up warms). Its
limits are in ``limits/<workload>.json`` and each per-layer metric is a
reader in ``metrics/<name>.py``: all found by name, so a new cell or
metric is new files and entries only.

One run: set-up (imports, the accelerator, warming each program the
cell's traffic uses at its shapes), then a window of whole simulated
runs back to back through ``repro.launch.experiments.run_spec``, while
less than ``--seconds`` have passed; the run in flight at the close is
finished and counted. Every run simulates another world (a seed of
``run_spec``), drawn from ``--seed`` out of the traffic's ``worlds``:
the same seed runs the same worlds. Once the window has closed, every
run in it is replayed by the plain reference (``reference.py``) and
compared job by job (``compare.py``). With ``--trace 1`` the runs carry
the program's telemetry (``obs="report"``) and a profiler trace of the
window, and the per-layer metrics are printed instead of the end-to-end
ones.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (simulated jobs), ``metrics``, ``device``,
``breakdown`` with ``--trace 1``, and ``checks`` (each compared number
beside its limit), which are also the last lines of standard error.
Without an accelerator, or with fewer chips than the cell asks for, it
exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import compare  # noqa: E402
from reference import reference_run  # noqa: E402

#: spans of the program's telemetry that make up each host layer
PHASES = {"dispatch": ("broker.dispatch", "broker.select_batch"),
          "plan": ("strategy.plan",),
          "flush": ("net.rerate", "net.flush", "net.events")}
#: where profiler traces go, inside the checkout
TRACE_DIR = ROOT / ".bench_trace"


class Refused(Exception):
    """The run cannot be measured here (no accelerator, too few chips)."""


# -- cells ---------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<name>.json
    traffic: dict           # traffic/<name>.json
    limits: dict            # limits/<workload>.json
    end_to_end: list[dict]
    per_layer: list[dict]   # the per-layer metrics this cell reports

    def spec_dict(self) -> dict:
        return {**self.config["spec"], **self.traffic["spec"],
                "n_jobs": self.traffic["n_jobs"]}


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench: Path = ROOT / "BENCHMARK.json",
              base: Path = HERE) -> Cell:
    """The cell ``workload`` of ``bench``, its files found under ``base``
    by the names the cell gives."""
    spec = _read(bench)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r} "
                         f"(have {sorted(cells)})")
    w = cells[workload]

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, chips=w["chips"],
        config=_read(base / "configs" / f"{w['config']}.json"),
        traffic=_read(base / "traffic" / f"{w['traffic']}.json"),
        limits=_read(base / "limits" / f"{workload}.json"),
        end_to_end=[m for m in spec["end_to_end"] if reported(m)],
        per_layer=[m for m in spec["per_layer"] if reported(m)])


def load_reader(name: str, base: Path = HERE):
    """The ``read(window) -> float | None`` function of a per-layer
    metric, from ``metrics/<name>.py``."""
    path = base / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -- the device ------------------------------------------------------------
def device_facts(chips: int) -> dict:
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform == "cpu":
        raise Refused(f"JAX found no accelerator (platform {first.platform})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class CompileCounter:
    """XLA compiles (persistent-cache hits excluded) while in its block,
    through ``jax.monitoring``."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.executables = 0
        self.cache_hits = 0

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == self._BACKEND:
            self.executables += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self.executables - self.cache_hits

    def __enter__(self) -> "CompileCounter":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)


# -- set-up ----------------------------------------------------------------
def world_shape(spec: dict) -> dict:
    """Sites, links (site NICs plus one uplink per inner tree node),
    path depth and catalog size of a spec."""
    fan = list(spec["tier_fanouts"])
    sites, width, uplinks = 1, 1, 0
    for f in fan:
        sites *= f
    for f in fan[:-1]:
        width *= f
        uplinks += width
    files = int(spec["catalog_gb"] * 1e9 / (spec["file_size_mb"] * 1e6))
    return {"sites": sites, "links": sites + uplinks, "depth": len(fan),
            "files": files}


def warm_up(cell: Cell) -> None:
    """Run each program the cell's traffic drives once at its shapes: the
    flush of the device engine at every slot capacity the traffic
    reaches, and the batch broker at the burst size."""
    import numpy as np

    spec, shape = cell.spec_dict(), world_shape(cell.spec_dict())
    if spec["net"] == "device":
        from repro.kernels.event_engine import event_engine

        link_bw = np.full(shape["links"], 1.25e7)
        link_act = np.ones(shape["links"])
        for cap in cell.traffic["warm_slot_capacities"]:
            path = np.full((cap, shape["depth"]), -1, np.intp)
            path[:, 0] = np.arange(cap) % shape["sites"]
            state = np.zeros(cap)
            event_engine(path, state + 5e8, state, np.full(cap, np.inf),
                         link_bw, link_act, 0.0, backend="pallas")
    if spec["broker"] == "jax" and spec["arrival_burst"] > 1:
        import jax.numpy as jnp
        from repro.core.jaxsched import select_sites_batch

        s, f, b = shape["sites"], shape["files"], spec["arrival_burst"]
        sizes = jnp.asarray(np.full(f, spec["file_size_mb"] * 1e6),
                            jnp.float32)
        out = select_sites_batch(
            jnp.asarray(np.zeros((s, f), bool)), sizes,
            jnp.asarray(np.zeros((b, f), bool)),
            jnp.asarray(np.zeros(s, np.float32)),
            jnp.asarray(np.ones(s, np.float32)),
            jnp.asarray(np.ones(s, bool)))
        np.asarray(out)


@contextlib.contextmanager
def kept_results(into: list):
    """Keep, of what each ``GridSimulator.run`` returns, the per-job
    records behind the aggregates ``run_spec`` hands back."""
    from repro.core import GridSimulator

    run = GridSimulator.run

    def keeping(self, *args, **kwargs):
        out = run(self, *args, **kwargs)
        into.append(program_side(out))
        return out

    GridSimulator.run = keeping
    try:
        yield
    finally:
        GridSimulator.run = run


# -- the window --------------------------------------------------------------
@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    runs: list[dict] = dataclasses.field(default_factory=list)
    compiles: int = 0
    trace: object = None            # trace_reduce.TraceSummary

    @property
    def jobs_done(self) -> int:
        return sum(r["completed"] for r in self.runs)

    def totals(self) -> dict:
        """Host-layer seconds, program counters and engine stats summed
        over the runs, as the metric readers get them."""
        out = {"jobs": sum(r["n_jobs"] for r in self.runs),
               "phases": {k: 0.0 for k in (*PHASES, "other")},
               "counters": {}, "net": {}, "compiles": self.compiles,
               "trace": self.trace}
        for r in self.runs:
            for key in ("counters", "net"):
                for k, v in r[key].items():
                    out[key][k] = out[key].get(k, 0) + v
            for k, v in r["phases"].items():
                out["phases"][k] += v
        return out


def run_window(cell: Cell, seed: int, seconds: float, *, traced: bool,
               kept: list) -> Window:
    import jax
    from repro.launch.experiments import run_spec
    from repro.core import ScenarioSpec
    from trace_reduce import WINDOW_END, WINDOW_START

    spec = ScenarioSpec.from_dict(cell.spec_dict())
    if traced:
        spec = dataclasses.replace(spec, obs="report")
    pool = cell.traffic["worlds"]
    worlds = random.Random(seed).sample(pool, len(pool))
    win = Window()
    with CompileCounter() as counter, kept_results(kept):
        with jax.profiler.TraceAnnotation(WINDOW_START):
            t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            world = worlds[len(win.runs) % len(worlds)]
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"bench.run.{world}"):
                r = run_spec(spec, seed=world, n_jobs=spec.n_jobs)
            win.runs.append(_run_row(r, world, time.perf_counter() - t))
        with jax.profiler.TraceAnnotation(WINDOW_END):
            win.seconds = time.perf_counter() - t0
        win.compiles = counter.compiles
    return win


def _run_row(r, seed: int, wall: float) -> dict:
    row = {"seed": seed, "wall_s": wall, "n_jobs": r.n_jobs,
           "completed": r.completed_jobs, "net": dict(r.net_stats),
           "counters": {}, "phases": {}}
    tel = r.telemetry
    if tel is not None:
        row["counters"] = dict(tel.counters)
        named = {k: sum(tel.phase_self_s.get(n, 0.0) for n in names)
                 for k, names in PHASES.items()}
        row["phases"] = {**named, "other": wall - sum(named.values())}
    return row


# -- correctness -------------------------------------------------------------
def program_side(sim) -> dict:
    return {"jobs": {j.job_id: (j.site, j.submit_time, j.finish_time)
                     for j in sim.records},
            "makespan": sim.makespan, "inter_comms": sim.total_inter_comms}


def reference_side(out) -> dict:
    return {"jobs": {j.job_id: (j.site, j.submit, j.finish) for j in out.jobs},
            "makespan": out.makespan, "inter_comms": out.inter_comms}


def check(cell: Cell, win: Window, kept: list) -> dict:
    """Compare every run of the window with the reference; return each
    number beside its limit."""
    spec = cell.spec_dict()
    per_run = [compare.readings(side, reference_side(reference_run(
        spec, r["seed"], spec["n_jobs"]))) for side, r in zip(kept, win.runs)]
    values = compare.worst(per_run)
    if spec["net"] == "device":
        # every flush on the compiled kernel: a run that fell back to the
        # host measured the host
        values["flush_on_host"] = float(sum(r["net"]["flush_host"]
                                            for r in win.runs))
        values["flush_on_kernel_missing"] = float(
            sum(r["net"]["flush_kernel"] == 0 for r in win.runs))
    return beside_limits(cell, values)


def beside_limits(cell: Cell, values: dict) -> dict:
    """Each compared number beside its limit (the route checks' is 0)."""
    return {k: {"value": v,
                "limit": cell.limits[k] if k in compare.NUMBERS else 0.0}
            for k, v in values.items()}


def is_correct(checks: dict) -> bool:
    """True when every number is within its limit (NaN fails)."""
    return all(c["value"] <= c["limit"] for c in checks.values())


# -- output --------------------------------------------------------------------
def end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    values = {"sim_jobs_per_s": win.jobs_done / win.seconds,
              "setup_s": setup_s}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell: Cell, win: Window, peaks: dict, shape: dict) -> dict:
    data = {**win.totals(), "peaks": peaks, "world": shape}
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(data)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(summary) -> dict:
    ops = sorted(((name, ns * 1e-9) for name, (ns, _) in
                  summary.programs.items()), key=lambda x: -x[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[label, ns * 1e-9] for label, ns in summary.gaps]}


def peaks_for(kind: str) -> dict:
    table = _read(HERE / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][kind]


def set_up(cell: Cell) -> dict:
    """The compile cache inside the checkout, the device (refused off the
    chip), the cell's programs warmed; returns the device's facts."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = device_facts(cell.chips)
    warm_up(cell)
    return device


def measure(cell: Cell, seed: int, seconds: float, traced: bool) -> dict:
    """Set-up, window, comparison; the result line as a dict."""
    device = set_up(cell)
    peaks = peaks_for(device["kind"])
    setup_s = time.perf_counter() - T_START
    kept: list = []
    if traced:
        import jax
        from trace_reduce import find_xplane, reduce_trace

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        with jax.profiler.trace(str(TRACE_DIR), profiler_options=opts):
            win = run_window(cell, seed, seconds, traced=True, kept=kept)
    else:
        win = run_window(cell, seed, seconds, traced=False, kept=kept)
    for r in win.runs:
        print(json.dumps({"run": r}), flush=True)
    print(json.dumps({"window_s": win.seconds, "runs": len(win.runs),
                      "jobs_done": win.jobs_done,
                      "compiles_in_window": win.compiles}), flush=True)
    device["memory_peak_bytes"] = memory_peak_bytes()
    result = {}
    if traced:
        win.trace = reduce_trace(find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR)
        device["busy_s"] = win.trace.busy_ns * 1e-9
        device["window_s"] = win.trace.window_ns * 1e-9
        result["breakdown"] = breakdown(win.trace)
        metrics = per_layer(cell, win, peaks, world_shape(cell.spec_dict()))
    else:
        metrics = end_to_end(cell, win, setup_s)
    checks = check(cell, win, kept)
    attempted = sum(r["n_jobs"] for r in win.runs)
    return {"correct": is_correct(checks), "attempted": attempted,
            "failed": attempted - win.jobs_done, "metrics": metrics,
            "device": device, **result, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        line = measure(cell, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
