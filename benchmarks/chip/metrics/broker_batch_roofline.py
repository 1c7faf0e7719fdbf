"""The batch broker program's share of its roofline: the least time the
chip could take for one burst, the larger of the score GEMM's operations
(``roofline_broker.broker_batch_flops``) at the bf16 peak (a float32
matmul's default precision on the chip) and the bytes it needs
(``roofline_broker.broker_batch_bytes``) at the HBM bandwidth, over the
program's device time per execution in the trace."""

from roofline_broker import broker_batch_bytes, broker_batch_flops

PROGRAM = "jit_select_sites_batch"


def read(w):
    if w["trace"] is None:
        return None
    ns, n = w["trace"].program_ns(PROGRAM)
    calls = w["counters"].get("broker.batch_calls", 0)
    jobs = w["counters"].get("broker.batch_jobs", 0)
    if not n or not calls or not ns:
        return None
    shape = {"sites": w["world"]["sites"], "files": w["world"]["files"]}
    peaks = w["peaks"]
    least_s = max(broker_batch_flops(jobs, **shape)
                  / peaks["bf16_flops_per_s"],
                  broker_batch_bytes(calls, jobs, **shape)
                  / peaks["hbm_bytes_per_s"]) / calls
    return 100.0 * least_s / (ns / n * 1e-9)
