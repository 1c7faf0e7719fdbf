"""Share of the traced window in which no program ran on the device."""


def read(w):
    t = w["trace"]
    if t is None or not t.devices or t.window_ns <= 0:
        return None
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)
