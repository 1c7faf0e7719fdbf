"""Device time of the jitted batch broker (score GEMM, tie mask and
argmin over the sites, a whole burst at once) per execution, from the
trace."""

PROGRAM = "jit_select_sites_batch"


def read(w):
    if w["trace"] is None:
        return None
    ns, n = w["trace"].program_ns(PROGRAM)
    return ns / n / 1e3 if n else None
