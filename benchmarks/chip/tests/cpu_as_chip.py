"""Drive a benchmark run on the CPU as if it held a chip.

The harness's look for an accelerator is skipped, and the network
engine's device route takes the flush oracle (float64 numpy over the
whole slot array, as the compiled kernel sees it) while counting its
passes as kernel passes. Everything else is the run as on the chip.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def small(cell, n_jobs: int = 100):
    """The cell with ``n_jobs`` jobs a run: a window shorter than a run
    then runs one world."""
    return dataclasses.replace(cell, traffic=dict(cell.traffic, n_jobs=n_jobs))


@contextlib.contextmanager
def cpu_as_chip(monkeypatch, *, kernel_route: bool = True):
    """Yield the harness module with the chip stood in by the CPU.
    ``kernel_route=False`` leaves the device route on its host fallback,
    as a chip whose kernel is not taken would."""
    import repro.kernels.event_engine as ee
    from repro.core import NetworkEngine
    import run

    flush = ee.event_engine

    def on_host(*args, backend="auto", **kwargs):
        return flush(*args, backend="numpy", **kwargs)

    init = NetworkEngine.__init__

    def init_as_chip(self, *args, **kwargs):
        init(self, *args, **kwargs)
        if self.backend == "device":
            self._use_kernel = kernel_route

    monkeypatch.setattr(ee, "event_engine", on_host)
    monkeypatch.setattr(NetworkEngine, "__init__", init_as_chip)
    monkeypatch.setattr(run, "device_facts", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(run, "memory_peak_bytes", lambda: 0)
    yield run
