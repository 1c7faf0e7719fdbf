"""Host-phase timers + counters: the probe half of the telemetry layer.

A :class:`Probe` carries the three per-run telemetry surfaces:

* **spans** — ``with probe.span("broker.dispatch"): ...`` wall-clock
  phase timers with *exclusive* (self-time) accounting: a span's self
  time is its inclusive wall time minus the inclusive time of the spans
  nested inside it, so the per-phase self times are a partition of
  measured wall and always sum to <= the run's total wall clock (the
  invariant the telemetry property tests pin).
* **counters** — ``probe.count("plan_cache.keep")`` monotonic integer
  counters, plus ``probe.event(name, sim_t)`` which counts one DES
  event (``event.<KIND>``) and, in trace mode, records a sim-time
  instant in the Chrome trace.
* **parts** — ``with probe.part("net.flush.stage"): ...`` timed stages
  *within* the enclosing phase: each adds its elapsed nanoseconds to the
  counter ``<name>_ns`` and carves nothing out of the phase's self time,
  so the wall partition keeps its meaning.
* **GC pauses** — for the life of a run (:meth:`Probe.running`) a
  ``gc.callbacks`` hook counts Python's collections
  (``host.gc.collections``) and their pause (``host.gc.pause_ns``).
* **attachments** — an optional :class:`~repro.obs.series.GridSampler`
  (sim-time ring-buffer series) and
  :class:`~repro.obs.trace.TraceWriter` (Chrome trace export), owned
  here so the simulator holds exactly one telemetry handle.

The probe is *observation-only by construction*: it never holds a
reference to the simulator and none of its methods take mutable engine
state (``GridSampler.sample(sim)`` reads through the sim argument and is
machine-checked by simlint rule SL014). Wall-clock reads are sanctioned
here and only here among the sim-adjacent packages — simlint's SL005
scope explicitly exempts ``repro/obs/``.

Spans, parts and GC pauses also enter ``jax.profiler.TraceAnnotation``
when jax is imported, so inside a ``jax.profiler.trace`` session they sit
on the host thread beside JAX's dispatch spans, on the device's clock.
The probe never imports jax itself: a profiler session needs jax, and
the numpy routes never load it.

Zero-overhead-when-disabled contract: the simulator stores ``None``
instead of a probe when ``obs="off"``, so the engine hot paths pay one
``is None`` check and nothing else; this module is only imported, never
entered.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import time
from typing import TYPE_CHECKING, Iterator, Optional

if TYPE_CHECKING:                      # imports for annotations only
    from .report import TelemetryReport
    from .series import GridSampler
    from .trace import TraceWriter

#: ``obs=`` engine-flag vocabulary, weakest to strongest. Each mode is a
#: superset of the previous one:
#:
#: * ``"off"``     — no probe at all (the default; hot paths pay one
#:                   ``is None`` check).
#: * ``"report"``  — host-phase span timers + counters, aggregated into a
#:                   :class:`~repro.obs.report.TelemetryReport`.
#: * ``"series"``  — report + sim-time ring-buffer samplers driven by the
#:                   periodic OBS event (link/SE/queue utilization).
#: * ``"trace"``   — series + Chrome trace-event export (host-phase spans
#:                   on a wall-clock track, DES events on a sim-time
#:                   track) and a JSONL event log.
OBS_MODES = ("off", "report", "series", "trace")

#: Default sim-seconds between OBS sampling events (series/trace modes).
#: One sample per ~5 simulated minutes keeps a paper-baseline run (~30 k
#: sim-seconds) at ~100 rows and a grid_500 run (~1.5 M sim-seconds) well
#: inside the default ring capacity.
DEFAULT_OBS_INTERVAL_S = 300.0


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` when jax is already imported, else
    None (no profiler session can be live without jax)."""
    jax = sys.modules.get("jax")
    return None if jax is None else jax.profiler.TraceAnnotation


class _Span:
    """One active ``probe.span(name)`` context. Exclusive-time
    bookkeeping: ``child_s`` accumulates the *inclusive* seconds of
    directly nested spans, so on exit ``inclusive - child_s`` is this
    span's self time."""

    __slots__ = ("probe", "name", "t0", "child_s", "ann")

    def __init__(self, probe: "Probe", name: str) -> None:
        self.probe = probe
        self.name = name
        self.t0 = 0.0
        self.child_s = 0.0
        self.ann = None

    def __enter__(self) -> "_Span":
        ann = self.probe._annotation
        if ann is not None:
            self.ann = ann(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        self.probe._stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        p = self.probe
        incl = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        p._stack.pop()
        name = self.name
        p.phase_self_s[name] = (p.phase_self_s.get(name, 0.0)
                                + incl - self.child_s)
        p.phase_total_s[name] = p.phase_total_s.get(name, 0.0) + incl
        p.phase_calls[name] = p.phase_calls.get(name, 0) + 1
        if p._stack:
            p._stack[-1].child_s += incl
        if p.trace is not None:
            p.trace.add_span(name, self.t0 - p._t0, incl)


class _Part:
    """One active ``probe.part(name)`` context: a timed stage inside the
    enclosing span. Its nanoseconds go to the counter ``<name>_ns``; the
    span stack and the phase tables are left alone."""

    __slots__ = ("probe", "name", "t0", "ann")

    def __init__(self, probe: "Probe", name: str) -> None:
        self.probe = probe
        self.name = name
        self.t0 = 0
        self.ann = None

    def __enter__(self) -> "_Part":
        ann = self.probe._annotation
        if ann is not None:
            self.ann = ann(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        p = self.probe
        ns = time.perf_counter_ns() - self.t0
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        p.count(self.name + "_ns", ns)
        if p.trace is not None:
            p.trace.add_span(self.name, self.t0 * 1e-9 - p._t0, ns * 1e-9)


class Probe:
    """Per-run telemetry collector (see module doc).

    Spans may nest arbitrarily; re-entering the same name recursively is
    allowed (each activation is its own :class:`_Span`). The probe is
    single-threaded by design — the DES engine is.
    """

    def __init__(self, mode: str, *,
                 sampler: Optional["GridSampler"] = None,
                 trace: Optional["TraceWriter"] = None) -> None:
        if mode not in OBS_MODES or mode == "off":
            raise ValueError(f"Probe mode must be an enabled OBS mode, "
                             f"got {mode!r} (want one of {OBS_MODES[1:]})")
        self.mode = mode
        self.sampler = sampler
        self.trace = trace
        self.counters: dict[str, int] = {}
        self.phase_self_s: dict[str, float] = {}
        self.phase_total_s: dict[str, float] = {}
        self.phase_calls: dict[str, int] = {}
        self._stack: list[_Span] = []
        self._annotation = _profiler_annotation()
        self._gc_t0 = 0
        self._gc_ann = None
        self._t0 = time.perf_counter()
        self.wall_s = 0.0

    # -- recording ---------------------------------------------------------
    def span(self, name: str) -> _Span:
        """Context manager timing one phase activation."""
        return _Span(self, name)

    def part(self, name: str) -> _Part:
        """Context manager timing one stage within the enclosing phase
        into the counter ``<name>_ns`` (no self time carved out)."""
        return _Part(self, name)

    def count(self, name: str, n: int = 1) -> None:
        """Bump a monotonic counter."""
        self.counters[name] = self.counters.get(name, 0) + n

    def event(self, kind_name: str, sim_t: float) -> None:
        """Record one handled DES event: bumps ``event.<KIND>`` and, in
        trace mode, adds a sim-time instant to the Chrome trace."""
        key = "event." + kind_name
        self.counters[key] = self.counters.get(key, 0) + 1
        if self.trace is not None:
            self.trace.add_instant(kind_name, sim_t)

    def merge_counters(self, prefix: str, values: dict) -> None:
        """Fold an engine-owned counter dict (e.g. ``NetworkEngine.stats``)
        into the probe under ``prefix.<key>`` names."""
        for k in sorted(values):
            key = f"{prefix}.{k}"
            self.counters[key] = self.counters.get(key, 0) + int(values[k])

    # -- lifecycle ---------------------------------------------------------
    @contextlib.contextmanager
    def running(self) -> Iterator[None]:
        """The life of one simulator run: re-resolves the profiler
        annotation (jax may have been imported since the probe was made)
        and hooks ``gc.callbacks`` until the run ends, raising or not.
        The GC counters start at 0, so a run without a collection still
        reports them."""
        self._annotation = _profiler_annotation()
        self.count("host.gc.collections", 0)
        self.count("host.gc.pause_ns", 0)
        gc.callbacks.append(self._on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = self._annotation
            if ann is not None:
                self._gc_ann = ann("host.gc")
                self._gc_ann.__enter__()
            self._gc_t0 = time.perf_counter_ns()
            return
        ns = time.perf_counter_ns() - self._gc_t0
        if self._gc_ann is not None:
            self._gc_ann.__exit__(None, None, None)
            self._gc_ann = None
        self.count("host.gc.collections")
        self.count("host.gc.pause_ns", ns)

    def elapsed_us(self, name: str) -> float:
        """Total *inclusive* microseconds spent in phase ``name`` — the
        drop-in replacement for the bench harness's hand-rolled
        ``perf_counter`` deltas."""
        return self.phase_total_s.get(name, 0.0) * 1e6

    def finalize(self, *, net_stats: dict | None = None) -> "TelemetryReport":
        """Stamp the run's wall clock and build the
        :class:`~repro.obs.report.TelemetryReport`. Idempotent on the
        timing state (wall advances monotonically if called twice)."""
        from .report import TelemetryReport  # deferred: report imports probe
        self.wall_s = time.perf_counter() - self._t0
        series = None
        if self.sampler is not None:
            series = self.sampler.arrays()
        return TelemetryReport(
            mode=self.mode,
            wall_s=self.wall_s,
            phase_self_s=dict(self.phase_self_s),
            phase_total_s=dict(self.phase_total_s),
            phase_calls=dict(self.phase_calls),
            counters=dict(self.counters),
            net_stats=dict(net_stats or {}),
            series=series,
            n_samples=0 if self.sampler is None else self.sampler.n_total,
            trace=self.trace,
            dropped_trace_events=(0 if self.trace is None
                                  else self.trace.dropped),
        )

    def __deepcopy__(self, memo: dict) -> None:
        """Deep copies drop the probe (-> ``None``): the tie-race
        sanitizer's twin engines replay instants for *comparison* and
        must not double-count events into the primary's telemetry —
        the same convention as the catalog/storage ``__deepcopy__``
        contracts dropping listeners."""
        return None


def make_probe(mode: str, *,
               series_capacity: int = 8192,
               trace_max_events: int = 1_000_000) -> Optional[Probe]:
    """Build the probe for an ``obs=`` mode (``None`` for ``"off"``).

    ``"report"`` is timers + counters only; ``"series"`` attaches the
    ring-buffer :class:`~repro.obs.series.GridSampler`; ``"trace"``
    additionally attaches a :class:`~repro.obs.trace.TraceWriter`.
    """
    if mode not in OBS_MODES:
        raise ValueError(f"unknown obs mode {mode!r} "
                         f"(want one of {OBS_MODES})")
    if mode == "off":
        return None
    sampler = None
    trace = None
    if mode in ("series", "trace"):
        from .series import GridSampler
        sampler = GridSampler(capacity=series_capacity)
    if mode == "trace":
        from .trace import TraceWriter
        trace = TraceWriter(max_events=trace_max_events)
    return Probe(mode, sampler=sampler, trace=trace)
