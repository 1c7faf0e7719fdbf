"""The flush program's share of its roofline: the bytes the flush needs
(``roofline.event_engine_bytes``) at the chip's HBM bandwidth, over the
program's device time in the trace. Bandwidth-bound: a flush does a few
operations per byte."""

from roofline import event_engine_bytes

PROGRAM = "jit__flush_call"


def read(w):
    if w["trace"] is None:
        return None
    ns, n = w["trace"].program_ns(PROGRAM)
    flushes = w["net"].get("flush_kernel", 0)
    if not n or not flushes:
        return None
    need = event_engine_bytes(w["net"]["flush_slots"], flushes,
                              depth=w["world"]["depth"],
                              links=w["world"]["links"])
    return 100.0 * need / w["peaks"]["hbm_bytes_per_s"] / (ns * 1e-9)
