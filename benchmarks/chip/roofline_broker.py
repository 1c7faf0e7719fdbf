"""Bytes and operations of the batch broker's program
(``jit_select_sites_batch``), from the world's shape, for its roofline
share (``metrics/broker_batch_roofline.py``)."""

F32 = 4      # bytes of a float32 or int32 element
BOOL = 1     # bytes of a bool element


def broker_batch_bytes(calls: int, jobs: int, *, sites: int,
                       files: int) -> int:
    """HBM bytes that ``calls`` batch-broker programs placing ``jobs``
    jobs in all must move.

    Per call: the presence bitmap (``sites x files`` bools), the file
    sizes (float32) and three site vectors (load rank and capacity
    float32, online bool). Per job: its requirement mask (``files``
    bools) in and its pick (int32) out.
    """
    per_call = sites * files * BOOL + files * F32 + sites * (2 * F32 + BOOL)
    per_job = files * BOOL + F32
    return calls * per_call + jobs * per_job


def broker_batch_flops(jobs: int, *, sites: int, files: int) -> int:
    """Operations of the score GEMM, ``(jobs, files) x (files, sites)``,
    over ``jobs`` jobs in all: a multiply and an add per term."""
    return 2 * jobs * sites * files
