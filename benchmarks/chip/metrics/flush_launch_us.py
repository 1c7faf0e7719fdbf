"""Host time of the device flush's ``net.flush.launch`` part per kernel
flush: the dispatch of the flush program and the output slices, the
outputs still on the device."""


def read(w):
    ns = w["counters"].get("net.flush.launch_ns")
    n = w["net"].get("flush_kernel", 0)
    return ns / n / 1e3 if ns is not None and n else None
