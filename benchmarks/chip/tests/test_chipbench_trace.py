"""The trace reduction on a small trace recorded on one TPU v5 lite: five
jobs of grid500_thin in one burst on the device engine (see the JSON
beside it for what was recorded and what the reduction read there)."""

from __future__ import annotations

import json

import pytest

from cpu_as_chip import HERE

DATA = HERE / "testdata"


@pytest.fixture(scope="module")
def recorded():
    from trace_reduce import reduce_trace

    expect = json.loads((DATA / "g500thin_5jobs.json").read_text())
    return reduce_trace(str(DATA / "g500thin_5jobs.xplane.pb")), expect


def test_idle_share_of_the_recorded_window(recorded):
    got, expect = recorded
    assert got.devices == 1
    assert got.window_ns == expect["window_ns"]
    assert got.busy_ns == expect["busy_ns"]
    assert 0 < got.busy_ns < got.window_ns
    # the device route leaves the chip idle nearly all the time
    assert 1.0 - got.busy_ns / got.window_ns > 0.95


def test_one_flush_program_per_kernel_flush(recorded):
    got, expect = recorded
    ns, n = got.program_ns("jit__flush_call")
    assert n == expect["net_stats"]["flush_kernel"] > 0
    assert ns == expect["programs"]["jit__flush_call"][0]
    # device time per flush: microseconds, not the milliseconds of host
    # time each flush costs
    assert 1e3 < ns / n < 1e5


def test_broker_program_and_gaps(recorded):
    got, _ = recorded
    assert got.program_ns("jit_select_sites_batch")[1] == 1
    assert len(got.gaps) == 10
    assert [g[1] for g in got.gaps] == sorted((g[1] for g in got.gaps),
                                              reverse=True)
    assert all(label for label, _ in got.gaps)


def test_device_readers_on_the_recorded_trace(recorded):
    import run

    got, expect = recorded
    net = expect["net_stats"]
    w = {"trace": got, "net": net, "world": {"depth": 3, "links": 555},
         "peaks": run.peaks_for("TPU v5 lite")}
    idle = run.load_reader("device_idle_share")(w)
    flush_us = run.load_reader("event_engine_device_us")(w)
    share = run.load_reader("event_engine_roofline")(w)
    assert idle == pytest.approx(100 * (1 - expect["busy_ns"]
                                        / expect["window_ns"]))
    ns, n = expect["programs"]["jit__flush_call"]
    assert flush_us == pytest.approx(ns / n / 1e3)
    assert 0 < share < 100
    no_trace = dict(w, trace=None)
    assert run.load_reader("device_idle_share")(no_trace) is None
    assert run.load_reader("event_engine_device_us")(no_trace) is None
