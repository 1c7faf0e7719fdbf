"""Host time of the network engine per flush pass on the device route:
the self time of its telemetry spans over ``flush_passes``."""


def read(w):
    n = w["net"].get("flush_passes", 0)
    return 1e3 * w["phases"]["flush"] / n if n else None
