"""XLA compiles inside the measured window (cache hits excluded)."""


def read(w):
    return float(w["compiles"])
