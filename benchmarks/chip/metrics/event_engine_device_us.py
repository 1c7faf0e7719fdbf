"""Device time of the jitted flush program (share gather and the
``event_engine`` kernel) per execution, from the trace."""

PROGRAM = "jit__flush_call"


def read(w):
    if w["trace"] is None:
        return None
    ns, n = w["trace"].program_ns(PROGRAM)
    return ns / n / 1e3 if n else None
