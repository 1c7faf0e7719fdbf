"""Host time of the batch broker per burst: the ``broker.select_batch``
span over ``broker.batch_calls``. Nothing nests in that span, so its self
time is the sum of its three parts (``broker.batch.{stage,launch,fetch}``),
which tile the call; the window's phases fold the span into ``dispatch``."""

PARTS = ("broker.batch.stage_ns", "broker.batch.launch_ns",
         "broker.batch.fetch_ns")


def read(w):
    c = w["counters"]
    n = c.get("broker.batch_calls", 0)
    if not n or any(p not in c for p in PARTS):
        return None
    return sum(c[p] for p in PARTS) / n / 1e3
