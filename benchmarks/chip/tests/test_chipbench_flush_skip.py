"""The reader of the share of flush passes that made no crossing: what it
makes of the engine's counters, and that a traced run on the CPU
stand-in reports it."""

from __future__ import annotations

import pytest

from cpu_as_chip import cpu_as_chip, small


def test_flush_skip_share_reader():
    import run

    read = run.load_reader("flush_skip_share")
    w = {"net": {"flush_skipped": 70, "flush_passes": 1_000,
                 "flush_kernel": 930}}
    assert read(w) == pytest.approx(7.0)       # % of passes
    assert read(dict(w, net={"flush_skipped": 0, "flush_passes": 9})) == 0.0
    # a program without the counter (one that never skips) reads nothing
    assert read(dict(w, net={"flush_passes": 1_000})) is None
    assert read(dict(w, net={"flush_skipped": 0, "flush_passes": 0})) is None
    assert read(dict(w, net={})) is None


def test_traced_run_reports_the_flush_skip_share(monkeypatch):
    with cpu_as_chip(monkeypatch) as run:
        line = run.measure(small(run.load_cell("paper_bulk50")), 2 ** 32 + 9,
                           0.1, True)
    assert line["correct"] is True
    metric = line["metrics"]["flush_skip_share"]
    assert metric["unit"] == "%"
    # a burst into the empty grid frees links no other transfer uses
    assert 0.0 < metric["value"] < 100.0
