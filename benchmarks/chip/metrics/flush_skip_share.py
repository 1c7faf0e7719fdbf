"""Share of the network engine's flush passes that re-rated nothing (no
live slot on a link whose occupancy moved) and so made no crossing: the
``flush_skipped`` engine counter over ``flush_passes``, in %."""


def read(w):
    skipped = w["net"].get("flush_skipped")
    n = w["net"].get("flush_passes", 0)
    return 100.0 * skipped / n if skipped is not None and n else None
