"""The readers of the device flush's host stages and of Python's GC
pauses: what each makes of a window, and that a traced run on the CPU
stand-in reports them."""

from __future__ import annotations

import pytest

from cpu_as_chip import cpu_as_chip, small

PARTS = {"flush_stage_us": "net.flush.stage_ns",
         "flush_launch_us": "net.flush.launch_ns",
         "flush_fetch_us": "net.flush.fetch_ns",
         "flush_apply_us": "net.flush.apply_ns"}


@pytest.mark.parametrize("name", sorted(PARTS))
def test_flush_part_reader(name):
    import run

    read = run.load_reader(name)
    w = {"counters": {PARTS[name]: 3_000_000, "net.flush.other_ns": 7},
         "net": {"flush_kernel": 1_000, "flush_passes": 1_500}}
    assert read(w) == pytest.approx(3.0)       # us per kernel flush
    assert read(dict(w, counters={})) is None
    assert read(dict(w, net={})) is None
    assert read(dict(w, net={"flush_kernel": 0})) is None


def test_gc_pause_share_reader():
    import run
    from trace_reduce import TraceSummary

    read = run.load_reader("gc_pause_share")
    trace = TraceSummary(window_ns=2e9, busy_ns=0.0, devices=0, programs={},
                         gaps=[])
    w = {"counters": {"host.gc.pause_ns": 10_000_000}, "trace": trace}
    assert read(w) == pytest.approx(0.5)
    assert read(dict(w, counters={"host.gc.pause_ns": 0})) == 0.0
    assert read(dict(w, counters={})) is None
    assert read(dict(w, trace=None)) is None
    empty = TraceSummary(window_ns=0.0, busy_ns=0.0, devices=0, programs={},
                         gaps=[])
    assert read(dict(w, trace=empty)) is None


def test_traced_run_reports_the_host_stages(monkeypatch):
    with cpu_as_chip(monkeypatch) as run:
        line = run.measure(small(run.load_cell("paper_bulk50")), 2 ** 32 + 9,
                           0.1, True)
    metrics = line["metrics"]
    assert line["correct"] is True
    for name in PARTS:
        assert metrics[name]["unit"] == "us"
        assert metrics[name]["value"] > 0
    assert 0.0 <= metrics["gc_pause_share"]["value"] < 100.0
