"""Host time of the event loop itself per handled event: run wall less
the broker, planner and network spans, over the ``event.*`` counters."""


def read(w):
    n = sum(v for k, v in w["counters"].items() if k.startswith("event."))
    return 1e6 * w["phases"]["other"] / n if n else None
