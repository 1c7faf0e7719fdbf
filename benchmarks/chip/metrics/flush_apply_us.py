"""Host time of the device flush's ``net.flush.apply`` part per kernel
flush: the float64 add-back of the flush instant and the engine's
write-back of the slot state."""


def read(w):
    ns = w["counters"].get("net.flush.apply_ns")
    n = w["net"].get("flush_kernel", 0)
    return ns / n / 1e3 if ns is not None and n else None
