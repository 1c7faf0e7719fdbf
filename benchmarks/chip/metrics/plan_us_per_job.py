"""Host time of the replication strategy's planning per simulated job."""


def read(w):
    n = w["jobs"]
    return 1e6 * w["phases"]["plan"] / n if n else None
