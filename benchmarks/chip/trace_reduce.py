"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

The trace of one run holds a plane per TPU (``/device:TPU:<n>``) whose
``XLA Modules`` line has one event per execution of a compiled program,
named ``jit_<function>(<fingerprint>)``, and a host plane (``/host:CPU``)
with a line per thread; the line of the thread that ran the window
holds its ``jax.profiler.TraceAnnotation`` spans and JAX's own dispatch
spans. Timestamps of both are nanoseconds on one
clock. Line events are iterated (they have no ``len()``).

What comes out, all between the window's opening and closing marks
(two short annotations on that thread):

* per program: device nanoseconds and executions, fingerprint dropped;
* busy time: the union of program intervals, averaged over devices;
* the longest idle gaps between programs, each labelled by the innermost
  host span that covers its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

#: zero-length spans that open and close the measured window
WINDOW_START, WINDOW_END = "bench.window.start", "bench.window.end"
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float                          # averaged over devices
    devices: int
    programs: dict[str, list[float]]        # name -> [device ns, executions]
    gaps: list[tuple[str, float]]           # (label, ns), longest first

    def program_ns(self, prefix: str) -> tuple[float, int]:
        """Device time and executions of the programs named ``prefix*``."""
        ns, n = 0.0, 0
        for name, (t, k) in self.programs.items():
            if name.startswith(prefix):
                ns += t
                n += int(k)
        return ns, n


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_trace(path: str, *, n_gaps: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans: list[tuple[float, float, str]] = []
    device_events: list[list[tuple[float, float, str]]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                    _FINGERPRINT.sub("", ev.name)))
            device_events.append(evs)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in line.events]
                if any(n == WINDOW_START for _, _, n in spans):
                    host_spans += spans      # the thread that ran the window
    marks = {}
    for s, e, name in host_spans:
        if name in (WINDOW_START, WINDOW_END):
            if name in marks:
                raise ValueError(f"two {name!r} marks in {path}")
            marks[name] = (s, e)
    if len(marks) != 2:
        raise ValueError(f"want the marks {WINDOW_START!r} and "
                         f"{WINDOW_END!r} in {path}, found {sorted(marks)}")
    w0, w1 = marks[WINDOW_START][0], marks[WINDOW_END][1]
    programs: dict[str, list[float]] = {}
    busy = 0.0
    idle: list[tuple[float, float]] = []
    for evs in device_events:
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in evs
                  if e > w0 and s < w1]
        for s, e, n in inside:
            acc = programs.setdefault(n, [0.0, 0])
            acc[0] += e - s
            acc[1] += 1
        merged = _union([(s, e) for s, e, _ in inside])
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(1, len(device_events))
    idle.sort(key=lambda iv: iv[0] - iv[1])
    gaps = []
    for s, e in idle[:n_gaps]:
        mid = (s + e) / 2
        covering = [(he - hs, name) for hs, he, name in host_spans
                    if hs <= mid <= he]
        gaps.append((min(covering)[1] if covering else "outside any span",
                     e - s))
    return TraceSummary(window_ns=w1 - w0, busy_ns=busy / n_dev,
                        devices=len(device_events), programs=programs,
                        gaps=gaps)
