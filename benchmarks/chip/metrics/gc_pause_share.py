"""Share of the traced window spent in Python's garbage collections
during the simulated runs (``host.gc.pause_ns``)."""


def read(w):
    ns = w["counters"].get("host.gc.pause_ns")
    t = w["trace"]
    if ns is None or t is None or t.window_ns <= 0:
        return None
    return 100.0 * ns / t.window_ns
