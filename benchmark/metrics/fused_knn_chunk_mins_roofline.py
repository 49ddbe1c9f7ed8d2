"""The fused brute-force kernel's share of its roofline, in %: the least
time of one call, 2 * nq * n * d FLOP at the chip's bf16 peak (the
bound; one read of the rows is beside it), over the device time of the
``fused_knn_chunk_mins`` events in one run of the brute-force program,
the mean over the runs that lie wholly in the traced window."""

from benchmark import roofline

KERNEL = "fused_knn_chunk_mins"


PROGRAM = "jit__fused_l2_knn_impl"


def read(rec, tr, peak):
    work = rec.get("work", {}).get(KERNEL)
    if tr is None or work is None or not tr.module_runs.get(PROGRAM):
        return None
    per_run = tr.module_op_s.get(PROGRAM, {}).get(KERNEL)
    if not per_run:
        return None
    least, _bound = roofline.least_time(work[0], work[1], peak)
    return least / (per_run / tr.module_runs[PROGRAM]) * 100.0
