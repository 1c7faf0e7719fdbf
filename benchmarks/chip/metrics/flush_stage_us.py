"""Host time of the device flush's ``net.flush.stage`` part per kernel
flush: the engine's arrays made into the kernel's device inputs (dtype
casts, the ``eta - now`` shift, transfers, pads and the transpose)."""


def read(w):
    ns = w["counters"].get("net.flush.stage_ns")
    n = w["net"].get("flush_kernel", 0)
    return ns / n / 1e3 if ns is not None and n else None
