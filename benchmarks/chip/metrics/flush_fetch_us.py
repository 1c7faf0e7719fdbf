"""Host time of the device flush's ``net.flush.fetch`` part per kernel
flush: the copy of the outputs back to the host, with the wait for the
device."""


def read(w):
    ns = w["counters"].get("net.flush.fetch_ns")
    n = w["net"].get("flush_kernel", 0)
    return ns / n / 1e3 if ns is not None and n else None
