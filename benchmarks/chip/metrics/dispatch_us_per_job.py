"""Host time of the broker per simulated job: self time of its dispatch
and batch-selection spans over the jobs of the window."""


def read(w):
    n = w["jobs"]
    return 1e6 * w["phases"]["dispatch"] / n if n else None
